"""One cell, once, on the chip, through the served path.

    python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell, its configuration, its traffic mix, its correctness rule and its
metrics are found by name: BENCHMARK.json -> benchmark/configs/<config>.json
-> benchmark/datasets/<dataset>.py, benchmark/checks/<rule>.py;
benchmark/traffic/<mix>.json -> benchmark/loops/<kind>.py;
benchmark/end_to_end/<metric>.py and benchmark/layer_metrics/<metric>.py.
README.md says how a later PR adds each as files.

One process owns the chip: it makes corpus and queries from --seed, builds
through the builder CLI's main() or loads a cached index, serves it from a
SearchServer on its own thread, warms every query-count bucket the cell
can form, and only then lets the load generator (a child process, held off
the chip) open the window.  The exact numpy reference runs after the
window.  Last line of stdout: one JSON object (keys `correct`, `attempted`,
`failed`, `metrics`, `device`; with --trace 1 also `breakdown`).  Exit 0
only with a result line; no TPU, or too few chips, is exit 2 and no line.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()          # set-up is counted from here

import argparse                        # noqa: E402
import contextlib                      # noqa: E402
import hashlib                         # noqa: E402
import json                            # noqa: E402
import os                              # noqa: E402
import shutil                          # noqa: E402
import subprocess                      # noqa: E402
import sys                             # noqa: E402
import threading                       # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import numpy as np                     # noqa: E402

from benchmark.harness import compare, serving, tracered  # noqa: E402
from benchmark.harness.serving import HarnessError, require  # noqa: E402
from benchmark.loadgen import load_by_name               # noqa: E402

CACHE = os.path.join(HERE, ".cache")
WORK = os.path.join(HERE, ".work")
INDEX_CACHE_KEEP = 8        # cached indexes kept per checkout (~70 MB each)
TRACE_START_S, TRACE_LENGTH_S = 2.0, 3.0


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def find_cell(workload: str) -> tuple:
    """-> (benchmark, cell, config, its file's path, traffic) by the names
    in BENCHMARK.json."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    require(workload in cells, f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config_path = os.path.join(ROOT, entry["file"])
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    return bench, cell, load_json(config_path), config_path, traffic


def metrics_of(bench: dict, group: str, workload: str) -> list:
    """The metrics of `group` that this cell reports."""
    return [m for m in bench[group]
            if "workloads" not in m or workload in m["workloads"]]


def source_hash(config_path: str) -> str:
    """Over the configuration's bytes and every source file of the
    program: a PR that changes the build never reads its parent's index."""
    h = hashlib.sha256()
    files = [config_path, os.path.join(ROOT, "native", "sptag_host.cpp")]
    for base, dirs, names in os.walk(os.path.join(ROOT, "sptag_tpu")):
        dirs[:] = sorted(d for d in dirs if d != "__pycache__")
        files += [os.path.join(base, n) for n in sorted(names)
                  if not n.endswith((".pyc", ".so"))]
    for path in files:
        h.update(os.path.relpath(path, ROOT).encode())
        with open(path, "rb") as f:
            h.update(f.read())
    return h.hexdigest()[:16]


def build_or_load(name, config, config_path, seed, data, workdir) -> tuple:
    """-> (index folder, seconds of index_builder.main that made it,
    whether this run built it).  A configuration with `index_cache` keeps
    the saved folder in benchmark/.cache/index/ by seed and source hash
    and loads it as a restarted server would."""
    if not config.get("index_cache"):
        folder = os.path.join(workdir, "index")
        return folder, serving.build_index(workdir, folder, data,
                                           config), True
    home = os.path.join(CACHE, "index")
    entry = os.path.join(
        home, f"{name}-s{seed}-{source_hash(config_path)}")
    note = os.path.join(entry, "build.json")
    folder = os.path.join(entry, "index")
    if os.path.exists(os.path.join(folder, "indexloader.ini")) \
            and os.path.exists(note):
        os.utime(entry)
        return folder, load_json(note)["build_seconds"], False
    shutil.rmtree(entry, ignore_errors=True)
    os.makedirs(entry)
    seconds = serving.build_index(workdir, folder, data, config)
    with open(note, "w") as f:
        json.dump({"build_seconds": seconds, "seed": seed}, f)
    kept = sorted((os.path.join(home, e) for e in os.listdir(home)),
                  key=os.path.getmtime)
    for old in kept[:-INDEX_CACHE_KEEP]:
        shutil.rmtree(old, ignore_errors=True)
    return folder, seconds, True


def warm_up(server, texts, buckets) -> int:
    """Every query-count bucket the cell can form, through the server's
    own executor, until a pass compiles nothing.  Returns the passes."""
    from sptag_tpu.utils import recompile_guard

    for passes in range(1, 6):
        with recompile_guard.track_compiles("benchmark.warm") as log:
            for q in buckets:
                server.executor.execute_batch(texts[:q])
        if log.count == 0:
            return passes
    raise HarnessError("warm-up still compiles after five passes")


class Generator:
    """The load generator child: started early so that its imports overlap
    the warm-up, released with `go`, always reaped."""

    def __init__(self, spec: dict, workdir: str):
        self.out = spec["out"]
        spec_path = os.path.join(workdir, "loadgen.json")
        with open(spec_path, "w") as f:
            json.dump(spec, f)
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "benchmark.loadgen", spec_path],
            cwd=ROOT, env=env, stdin=subprocess.PIPE,
            stdout=subprocess.PIPE, text=True)
        self._lines = []
        self._got = threading.Condition()
        self._reader = threading.Thread(target=self._read, daemon=True)
        self._reader.start()

    def _read(self):
        for line in self.proc.stdout:
            with self._got:
                self._lines.append(line.strip())
                self._got.notify_all()
        with self._got:
            self._lines.append(None)
            self._got.notify_all()

    def expect(self, word: str, timeout: float) -> None:
        with self._got:
            ok = self._got.wait_for(
                lambda: word in self._lines or None in self._lines, timeout)
        require(ok and word in self._lines,
                f"load generator did not say {word!r} "
                f"(exit code {self.proc.poll()})")

    def go(self) -> None:
        self.proc.stdin.write("go\n")
        self.proc.stdin.flush()

    def close(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait()
        self._reader.join(timeout=10)


def trace_slice(logdir: str, seconds: float):
    """Profile TRACE_LENGTH_S of the window, TRACE_START_S in (both
    shrunk for a window too short to hold them).  The python tracer is
    off: it would slow the server's event loop, which is what the slice
    is there to see."""
    import jax.profiler

    from sptag_tpu.utils import trace as program_trace

    time.sleep(min(TRACE_START_S, 0.2 * seconds))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=options)
    # what program_trace.start_trace sets (it takes no profiler options):
    # every span of the program becomes a TraceAnnotation
    program_trace._trace_active = True
    try:
        with jax.profiler.TraceAnnotation(tracered.WINDOW_SPAN):
            time.sleep(min(TRACE_LENGTH_S, 0.5 * seconds))
    finally:
        program_trace.stop_trace()


def span_deltas(before: dict, after: dict) -> dict:
    out = {}
    for name, rec in after.items():
        b = before.get(name, {"count": 0, "total_s": 0.0})
        if rec["count"] > b["count"]:
            out[name] = {"count": rec["count"] - b["count"],
                         "total_s": rec["total_s"] - b["total_s"]}
    return out


def run_cell(workload: str, seed: int, seconds: float, traced: bool,
             rehearse: dict | None = None, control: str | None = None
             ) -> dict:
    """One run of one cell -> the result line as a dict.

    `rehearse` (tests only): sizes that fit a CPU — skips the look for a
    chip, and the numbers it reads are never printed as metrics.
    `control` (the on-chip control only): the float precision the program
    is switched to before it serves (one to a process: a program traced
    at one precision is not traced again)."""
    bench, cell, config, config_path, traffic = find_cell(workload)
    if rehearse:
        config = {**config, **rehearse.get("config", {})}
        traffic = {**traffic, **rehearse.get("traffic", {})}
    require(os.path.isdir(os.path.join(ROOT, "sptag_tpu")),
            "the program (sptag_tpu/) is not in this checkout")
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(CACHE, "jax"))
    import jax

    if rehearse:
        devs = jax.devices()
        device = {"platform": devs[0].platform,
                  "kind": devs[0].device_kind, "count": len(devs)}
        peaks = None
    else:
        device = serving.check_device(cell["chips"])
        peaks = serving.peaks_for(device["kind"])

    from sptag_tpu.ops import distance as program_distance
    from sptag_tpu.utils import recompile_guard
    from sptag_tpu.utils import trace as program_trace

    workdir = os.path.join(WORK, workload)
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    name, k = cell["config"], config["k"]

    dataset = load_by_name("datasets", config["dataset"])
    data, queries = dataset.make(seed, config["rows"], config["dim"],
                                 traffic["distinct_queries"])
    folder, build_seconds, built = build_or_load(
        name, config, config_path, seed, data, workdir)
    if control:
        # the index is the sound one; what it is served with is not
        program_distance.set_float_precision(control)
    texts = [serving.query_text(name, k, q) for q in queries]
    texts_path = os.path.join(workdir, "texts.json")
    with open(texts_path, "w") as f:
        json.dump(texts, f)

    with contextlib.ExitStack() as stack:
        server, addr = stack.enter_context(
            serving.served(workdir, name, folder, config))
        generator = Generator(
            {"host": addr[0], "port": addr[1], "texts": texts_path,
             "traffic": traffic, "k": k, "seconds": seconds,
             "out": os.path.join(workdir, "requests.npz")}, workdir)
        stack.callback(generator.close)
        top = min((b for b in config["warm_buckets"]
                   if b >= traffic["callers"]),
                  default=max(config["warm_buckets"]))
        buckets = [b for b in config["warm_buckets"] if b <= top]
        warm_passes = warm_up(server, texts, buckets)
        generator.expect("ready", 120)
        errors_before = serving.serve_error_counts()
        spans_before = program_trace.report()
        with recompile_guard.track_compiles("benchmark.window") as compiles:
            setup_s = time.perf_counter() - T_START
            generator.go()
            if traced:
                trace_dir = os.path.join(workdir, "trace")
                trace_slice(trace_dir, seconds)
            generator.expect("done", seconds + 120)
        spans = span_deltas(spans_before, program_trace.report())
        errors = {n: v - errors_before[n]
                  for n, v in serving.serve_error_counts().items()}
    stats = jax.devices()[0].memory_stats() or {}
    device["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))

    with np.load(os.path.join(workdir, "requests.npz")) as z:
        record = {key: z[key] for key in z.files}
    run = {"workload": workload, "seed": seed, "seconds": seconds,
           "config": config, "traffic": traffic, "requests": record,
           "spans": spans, "serve_errors": errors, "peaks": peaks,
           "compiles_in_window": compiles.count, "setup_s": setup_s,
           "build_seconds": build_seconds, "built_this_run": built,
           "warm_passes": warm_passes, "trace": None, "check": None}
    if traced:
        raw = tracered.read_xplane(tracered.find_xplane(trace_dir))
        if raw["devices"] or not rehearse:
            run["trace"] = tracered.reduce_trace(raw, cell["chips"])
            run["trace"]["lines"] = raw["lines"]

    # the reference, after the window: not the system's set-up
    t_ref = time.perf_counter()
    status = record["status"]
    success = status == record["success_status"]
    attempted, failed = int(len(status)), int((~success).sum())
    numbers = [compare.number("answers_received", success.sum(), 1,
                              "higher"),
               compare.number("requests_failed", failed, 0, "lower"),
               compare.number("serve_errors", sum(errors.values()), 0,
                              "lower")]
    if success.any():
        answered = np.unique(record["query"][success])
        rng = np.random.default_rng(seed)
        want = min(config["check"]["queries"], len(answered))
        sample = np.sort(rng.choice(answered, want, replace=False))
        rule = load_by_name("checks", config["check"]["rule"])
        run["check"] = rule.check(data, queries, sample, record, config)
        numbers += run["check"]["numbers"]
    for n in numbers:
        print(f"check {n['name']}: value {n['value']!r} limit "
              f"{n['limit']!r} ({n['better']} is better) "
              f"{'ok' if n['ok'] else 'NOT OK'}", flush=True)
    reference_s = time.perf_counter() - t_ref

    group, readers = ("per_layer", "layer_metrics") if traced \
        else ("end_to_end", "end_to_end")
    values = {}
    for m in metrics_of(bench, group, workload):
        value = load_by_name(readers, m["name"]).read(run)
        if value is not None:
            values[m["name"]] = {"value": float(value), "unit": m["unit"]}
    done = (record["t_send"] + record["latency"])[success]
    quarters = [float(((done >= i * seconds / 4)
                       & (done < (i + 1) * seconds / 4)).sum())
                / (seconds / 4) for i in range(4)]
    result = {"correct": all(n["ok"] for n in numbers),
              "attempted": attempted, "failed": failed,
              "metrics": values, "device": device,
              "compared": numbers, "workload": workload, "seed": seed,
              "seen": {"setup_s": setup_s, "reference_s": reference_s,
                       "build_seconds": build_seconds,
                       "built_this_run": built, "warm_passes": warm_passes,
                       "compiles_in_window": compiles.count,
                       "qps_by_quarter": quarters,
                       "serve_errors": errors,
                       **(run["check"] or {}).get("seen", {})}}
    if control:
        result["control"] = control
    if run["trace"]:
        device["busy_s"] = run["trace"]["busy_s"]
        device["window_s"] = run["trace"]["window_s"]
        result["breakdown"] = {"device_ops": run["trace"]["device_ops"],
                               "idle_gaps": run["trace"]["idle_gaps"]}
        result["seen"]["programs"] = run["trace"]["programs"]
        result["seen"]["trace_lines"] = run["trace"]["lines"]
    if rehearse:
        # a rehearsal's numbers are the sandbox's, never a device metric
        result["rehearsal_values"] = result.pop("metrics")
        result["metrics"] = {}
        result["rehearsal"] = True
    return result


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except HarnessError as e:
        print(f"benchmark.run: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
