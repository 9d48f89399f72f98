"""Span `index.readback` (the host blocked on the device program and the
copy back), total over the window / executed batches."""

from benchmark.harness.stages import per_batch_ms


def read(run):
    return per_batch_ms(run, "index.readback")
