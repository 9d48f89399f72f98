"""Seconds the program took to cut the tree into the dense partition and
lay the rows out block by block (host) and place them (device): its span
`build.dense_pack` (`algo/bkt.py::_build_dense_searcher`), total over the
process.  A served folder pays it at the first search after the load,
inside warm-up, so it is read from the program's own report and not from
the window's span deltas.  None where the program has no such span
(before PR 48)."""


def read(run):
    from sptag_tpu.utils import trace

    span = trace.report().get("build.dense_pack")
    return span["total_s"] if span and span["count"] else None
