"""Span `server.execute_batch` (parse, grouping, dispatch, device program
and readback of one batch, timed from outside), mean."""


def read(run):
    s = run["spans"].get("server.execute_batch")
    return 1e3 * s["total_s"] / s["count"] if s else None
