"""Least time the chip could take for the dense scans of the traced slice
over the traced device time of `jit__dense_search_kernel` (and of its
grouped twin, where that is what ran), in %.  Least time:
benchmark/harness/roofline_dense.py, from what the program says one query
of its most recent search scored (gauges `dense.rows_per_query`,
`dense.centroids_per_query`: the last search before the readers run is a
served batch of the window).  BKT dense configurations only; None where
the program publishes neither (before PR 25)."""

from benchmark.harness import roofline_dense

PROGRAMS = ("jit__dense_search_kernel", "jit__dense_search_grouped_kernel")
ITEMSIZE = {"Float": 4, "Int16": 2, "Int8": 1, "UInt8": 1}


def scored_per_query():
    """(corpus rows, centroids) one query scores, from the program's
    gauges; None where it has none."""
    from sptag_tpu.utils import metrics

    rows = metrics.gauge_value("dense.rows_per_query")
    return (rows, metrics.gauge_value("dense.centroids_per_query")) \
        if rows else None


def bound(run, scored):
    t, c = run["trace"], run["config"]
    q = run["spans"].get("server.queue_wait")
    b = run["spans"].get("server.execute_batch")
    if not t or not scored or not q or not b:
        return None
    ran = [t["programs"][p] for p in PROGRAMS if p in t["programs"]]
    if not ran:
        return None
    least = roofline_dense.dense_scan_least_seconds(
        sum(p["runs"] for p in ran), q["count"] / b["count"], scored[0],
        scored[1], c["dim"], ITEMSIZE[c["value_type"]], run["peaks"])
    return least, sum(p["seconds"] for p in ran)


def read(run):
    got = bound(run, scored_per_query())
    return 100.0 * got[0]["seconds"] / got[1] if got else None
