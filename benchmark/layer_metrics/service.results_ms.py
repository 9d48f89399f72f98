"""Span `service.results` (building the batch's RemoteSearchResults and
the on_ready hand-offs), total over the window / executed batches."""

from benchmark.harness.stages import per_batch_ms


def read(run):
    return per_batch_ms(run, "service.results")
