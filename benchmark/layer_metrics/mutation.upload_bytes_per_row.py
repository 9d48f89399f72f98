"""Bytes the mutations of the window sent from the host to the device,
per row mutated: record `flat.block_upload_bytes`
(`trace.record_sum(name, bytes, rows)` in `FlatIndex._device_append` /
`_device_mask`: an add's rung of rows, a byte of mask a row and the start;
a delete's four bytes a slot of its rung), total / count over the
window.  Expected about (dim x itemsize + 5) / 2 where adds and deletes
balance; a whole re-placement of the block would read the corpus's bytes
per mutation.  None where the program has no such record (before PR 40)
or nothing was mutated."""


def read(run):
    r = run["spans"].get("flat.block_upload_bytes")
    return r["total_s"] / r["count"] if r and r["count"] else None
