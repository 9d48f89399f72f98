"""Device time of the operations under the dense program's `dense.probe`
scope (the probed blocks' dot products: the Pallas kernel `probe_block_dots`
streaming nprobe blocks a query through VMEM, and the distance algebra) in the traced slice over the
`server.execute_batch` spans that lie in it; busiest plane.  The scopes
are read from the run's .xplane.pb by benchmark/harness/scopes.py; None
where the program names no stages or the slice holds no batch."""

from benchmark.harness import scopes

SCOPE = "dense.probe"


def read(run):
    t = run["trace"]
    if not t:
        return None
    batches = t["host_span_counts"].get("server.execute_batch", 0)
    stages = scopes.read_stages(run["workload"])
    if SCOPE not in stages or not batches:
        return None
    return 1e3 * stages[SCOPE] / batches
