"""Record `server.batch_cycle` (one batch assembled -> the next
assembled), mean: 1000 / this = batches a second."""

from benchmark.harness.reduce import span_mean_ms


def read(run):
    return span_mean_ms(run, "server.batch_cycle")
