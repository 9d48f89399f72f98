"""Device-busy seconds of the traced slice over the `server.execute_batch`
spans that lie in it."""


def read(run):
    t = run["trace"]
    if not t:
        return None
    batches = t["host_span_counts"].get("server.execute_batch", 0)
    return 1e3 * t["busy_s"] / batches if batches else None
