"""Span `flat.block_update` (`FlatIndex._device_append` /
`_device_mask`: the host's part of one in-place write of the resident
block — pad to the write's rung, hand the rows or the slots to the
device, enqueue the donated update), mean over the window's writes, in
ms.  The device's part is in the trace.  None where the program has no
such span (before PR 40: a mutation there re-places the whole block at
the next search) or nothing was written."""

from benchmark.harness.reduce import span_mean_ms


def read(run):
    return span_mean_ms(run, "flat.block_update")
