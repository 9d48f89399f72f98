"""Span `server.decode` (wire body -> RemoteQuery), total / count over
the window."""


def read(run):
    s = run["spans"].get("server.decode")
    return 1e3 * s["total_s"] / s["count"] if s else None
