"""Span `server.execute_batch` (parse, grouping, dispatch, device program
and readback of one batch, timed from outside), mean."""

from benchmark.harness.reduce import span_mean_ms


def read(run):
    return span_mean_ms(run, "server.execute_batch")
