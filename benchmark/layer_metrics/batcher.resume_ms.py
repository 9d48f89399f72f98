"""Record `server.batch_resume` (the executor thread done with the batch
-> the batcher running again on the event loop), mean."""

from benchmark.harness.reduce import span_mean_ms


def read(run):
    return span_mean_ms(run, "server.batch_resume")
