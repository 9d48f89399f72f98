"""Queries per executed batch: `server.queue_wait` records (one per query
that reached a batch) over `server.execute_batch` spans."""


def read(run):
    q = run["spans"].get("server.queue_wait")
    b = run["spans"].get("server.execute_batch")
    return q["count"] / b["count"] if q and b else None
