"""Device time by the program's stage names (`jax.named_scope`).

Where a scope lands in the .xplane.pb (first traced chip run of PR 25):
an `XLA Ops` event is named by its HLO line and carries no scope; the
scope is in the event's METADATA (`XEventMetadata.stats`), stat `tf_op`,
as the operation's JAX name stack — `jit(_flat_search_kernel)/flat.topk/
top_k`.  `jax.profiler.ProfileData` does not expose metadata stats, so the
file's protobuf wire format is read here directly, and only as far as the
device planes' metadata tables (field numbers of tsl's xplane.proto).
A fusion carries the name stack of one of the operations fused into it;
an operation a compiler pass made carries none (see `staged`).
"""

from __future__ import annotations

import bisect
import os

from benchmark.harness import tracered

HERE = os.path.dirname(os.path.abspath(__file__))
STAGES = ("flat.distance", "flat.topk", "dense.centroids", "dense.gather",
          "dense.probe", "dense.mask", "dense.topk")
SCOPE_STAT = "tf_op"


def trace_dir(workload: str) -> str:
    """Where benchmark.run leaves a traced run's profile."""
    return os.path.join(os.path.dirname(HERE), ".work", workload, "trace")


def _varint(buf, at: int) -> tuple:
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, at
        shift += 7


def fields(buf):
    """(field number, wire type, value) of one protobuf message; a
    length-delimited value is a memoryview, a fixed one its bytes."""
    at, end = 0, len(buf)
    while at < end:
        key, at = _varint(buf, at)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, at = _varint(buf, at)
        elif wire == 2:
            size, at = _varint(buf, at)
            value, at = buf[at:at + size], at + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, at = buf[at:at + size], at + size
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield number, wire, value


def _map_entry(buf) -> tuple:
    key = value = None
    for number, _, v in fields(buf):
        if number == 1:
            key = v
        elif number == 2:
            value = v
    return key, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def op_scopes(path: str) -> dict:
    """{operation (event) name: its JAX name stack} over the device
    planes of one .xplane.pb; operations with no such stat are left out."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for number, _, plane in fields(space):
        if number != 1:                                   # XSpace.planes
            continue
        name, events, stats = "", [], {}
        for n, _, v in fields(plane):
            if n == 2:                                    # XPlane.name
                name = _text(v)
            elif n == 4:                                  # .event_metadata
                events.append(_map_entry(v)[1])
            elif n == 5:                                  # .stat_metadata
                key, meta = _map_entry(v)
                stats[key] = next((_text(x) for m, _, x in fields(meta)
                                   if m == 2), "")
        if not name.startswith(tracered.DEVICE_PLANE_PREFIX):
            continue
        for meta in events:
            op, scope = None, None
            for n, _, v in fields(meta):
                if n == 2:                                # .name
                    op = _text(v)
                elif n == 5:                              # .stats (XStat)
                    stat = dict((m, x) for m, _, x in fields(v))
                    if stats.get(stat.get(1)) != SCOPE_STAT:
                        continue
                    if 5 in stat:                         # str_value
                        scope = _text(stat[5])
                    elif 7 in stat:                       # ref_value
                        scope = stats.get(stat[7])
            if op and scope:
                out[op] = scope
    return out


def stage_of(scope: str):
    """The stage a name stack lies under: its first component that is one
    of STAGES, or None."""
    return next((part for part in scope.split("/") if part in STAGES), None)


def staged(ops, modules, scopes: dict) -> list:
    """[(stage or None, seconds)] of `ops` in time order.  An operation
    with no name stack (one a compiler pass made: the two-stage TopK XLA
    builds at Q=1 carries none) takes the stage of the staged operations
    nearest before and after it in the same run of its program, where
    the two agree; otherwise it stays None."""
    ops = sorted(ops, key=lambda e: e[1])
    stages = [stage_of(scopes.get(name, "")) for name, _, _ in ops]
    starts = [op[1] for op in ops]
    for _, start, dur in modules:
        run = range(bisect.bisect_left(starts, start),
                    bisect.bisect_left(starts, start + dur))
        named = [stages[i] for i in run]
        before, forward = None, []
        for stage in named:
            before = stage or before
            forward.append(before)
        after = None
        for i, stage, before in zip(reversed(run), reversed(named),
                                    reversed(forward)):
            after = stage or after
            if stage is None and before == after:
                stages[i] = before
    return [(stage, op[2]) for stage, op in zip(stages, ops)]


def seconds_by_stage(raw: dict, scopes: dict) -> dict:
    """{stage: device seconds in the traced window} on the busiest device
    plane of `raw` (tracered.read_xplane's lists), plus "(no stage)" for
    the rest; {} where no operation of the window lies under a stage —
    a program without named stages."""
    lo, hi = tracered.window_of(raw["host"])
    planes = [(tracered.clip(dev["ops"], lo, hi),
               tracered.clip(dev["modules"], lo, hi))
              for dev in raw["devices"].values()]
    if not planes:
        return {}
    ops, modules = max(planes, key=lambda p: tracered.busy_seconds(p[0]))
    total = {}
    for stage, dur in staged(ops, modules, scopes):
        stage = stage or "(no stage)"
        total[stage] = total.get(stage, 0.0) + dur
    return total if set(total) - {"(no stage)"} else {}


def read_stages(workload: str) -> dict:
    """seconds_by_stage of the profile the cell's traced run left; {}
    where there is none."""
    try:
        path = tracered.find_xplane(trace_dir(workload))
    except FileNotFoundError:
        return {}
    return seconds_by_stage(tracered.read_xplane(path), op_scopes(path))
