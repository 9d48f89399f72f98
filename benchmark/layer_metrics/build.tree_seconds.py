"""Seconds of the balanced k-means tree's build for the index this run
serves: the program's span `build.bkt_tree` (`algo/bkt.py::_build`, around
`BKTree.build`), total over the process.  Read from the program's own
report, as `kernel.dense_scan_roofline` reads its gauges: the build lies
before the window, so the window's span deltas do not hold it.  None
where the process built no tree (a cached index was loaded)."""


def read(run):
    from sptag_tpu.utils import trace

    span = trace.report().get("build.bkt_tree")
    return span["total_s"] if span and span["count"] else None
