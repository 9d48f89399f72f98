"""Span `server.queue_wait` (enqueued -> its batch assembled), mean."""

from benchmark.harness.reduce import span_mean_ms


def read(run):
    return span_mean_ms(run, "server.queue_wait")
