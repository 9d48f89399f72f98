"""Span `index.add` (`VectorIndex.add`, the whole call: prepare, WAL
append and fsync, host write, the in-place device write), mean over the
window's adds, in ms.  None where the program has no such span (before
PR 40) or no add ran."""

from benchmark.harness.reduce import span_mean_ms


def read(run):
    return span_mean_ms(run, "index.add")
