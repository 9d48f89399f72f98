"""Span `server.queue_wait` (enqueued -> its batch assembled), mean."""


def read(run):
    s = run["spans"].get("server.queue_wait")
    return 1e3 * s["total_s"] / s["count"] if s else None
