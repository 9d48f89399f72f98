"""Record `server.batch_gather` (first request taken off the queue -> the
batch assembled: the window actually spent), mean."""

from benchmark.harness.reduce import span_mean_ms


def read(run):
    return span_mean_ms(run, "server.batch_gather")
