"""Device time of the operations under the program's `flat.topk` /
`dense.topk` scopes in the traced slice over the `server.execute_batch`
spans that lie in it.  The scopes are read from the run's .xplane.pb by
benchmark/harness/scopes.py (`run["trace"]` keeps operation names only);
None where the program names no stages (before PR 25)."""

from benchmark.harness import scopes

TOPK = ("flat.topk", "dense.topk")


def read(run):
    t = run["trace"]
    if not t:
        return None
    batches = t["host_span_counts"].get("server.execute_batch", 0)
    stages = scopes.read_stages(run["workload"])
    if not stages or not batches:
        return None
    return 1e3 * sum(stages.get(s, 0.0) for s in TOPK) / batches
