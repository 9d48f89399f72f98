"""Span `service.parse` (query texts -> one (Q, D) array: `parse_query`,
`extract_vector`, `np.stack`), total over the window / executed batches."""

from benchmark.harness.stages import per_batch_ms


def read(run):
    return per_batch_ms(run, "service.parse")
