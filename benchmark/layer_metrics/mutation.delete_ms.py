"""Span `index.delete` (`VectorIndex.delete_rows`, the whole call: the
search by content at k = CEF, the host's exact check of the matches, WAL
append and fsync, the mask write), mean over the window's deletes, in ms.
None where the program has no such span (before PR 40) or no delete
ran."""

from benchmark.harness.reduce import span_mean_ms


def read(run):
    return span_mean_ms(run, "index.delete")
