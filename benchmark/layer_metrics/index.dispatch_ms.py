"""Span `index.search` less span `index.readback` inside it: query
preparation, padding to the bucket, host -> device and the dispatch of
the program, the delta merge; total over the window / executed batches."""

from benchmark.harness.stages import per_batch_ms


def read(run):
    return per_batch_ms(run, "index.search", minus="index.readback")
