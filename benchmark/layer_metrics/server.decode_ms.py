"""Span `server.decode` (wire body -> RemoteQuery), total / count over
the window."""

from benchmark.harness.reduce import span_mean_ms


def read(run):
    return span_mean_ms(run, "server.decode")
