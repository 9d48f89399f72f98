"""Device time of the operations under the mesh program's `mesh.merge`
scope (the all-gather of every shard's candidates over ICI and the
re-ranking top-k) on the busiest device plane of the traced slice, over
the `server.execute_batch` spans that lie in it.  On a chip that finished
its scan early the all-gather also holds the wait for the slowest one.
The scopes are read from the run's .xplane.pb by benchmark/harness/
scopes.py; None where no operation lies under the scope (a program
without it: before PR 27, or no mesh)."""

import bisect

from benchmark.harness import scopes, tracered

MERGE = "mesh.merge"


def merge_seconds(raw: dict, op_scopes: dict):
    """Seconds of the busiest device plane's operations, inside the traced
    window, whose JAX name stack has the component `mesh.merge`; an
    operation with no name stack (one a compiler pass made) counts where
    the named operations nearest before and after it in the same run of
    its program are both the merge's.  None where there is none."""
    lo, hi = tracered.window_of(raw["host"])
    planes = [(tracered.clip(dev["ops"], lo, hi),
               tracered.clip(dev["modules"], lo, hi))
              for dev in raw["devices"].values()]
    if not planes:
        return None
    ops, modules = max(planes, key=lambda p: tracered.busy_seconds(p[0]))
    ops = sorted(ops, key=lambda e: e[1])
    # True / False by the name stack; None where the operation has none
    merge = [MERGE in op_scopes[name].split("/") if name in op_scopes
             else None for name, _, _ in ops]
    starts = [op[1] for op in ops]
    total, seen = 0.0, False
    for _, start, dur in modules:
        run = range(bisect.bisect_left(starts, start),
                    bisect.bisect_left(starts, start + dur))
        before, forward = None, []
        for i in run:
            before = merge[i] if merge[i] is not None else before
            forward.append(before)
        after = None
        for i, before in zip(reversed(run), reversed(forward)):
            after = merge[i] if merge[i] is not None else after
            if merge[i] or (merge[i] is None and before and after):
                total, seen = total + ops[i][2], True
    return total if seen else None


def read(run):
    t = run["trace"]
    if not t:
        return None
    batches = t["host_span_counts"].get("server.execute_batch", 0)
    try:
        path = tracered.find_xplane(scopes.trace_dir(run["workload"]))
    except FileNotFoundError:
        return None
    seconds = merge_seconds(tracered.read_xplane(path),
                            scopes.op_scopes(path))
    if seconds is None or not batches:
        return None
    return 1e3 * seconds / batches
