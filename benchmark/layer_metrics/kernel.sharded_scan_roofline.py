"""Least time one chip could take for its share of the scans of the traced
slice over the traced device time of `jit__sharded_search_kernel` on the
busiest device plane, in %.  Least time: benchmark/harness/
roofline_sharded.py, from the program's gauge `mesh.rows_per_shard` (row
slots a device holds, set where the corpus is placed).  Mesh FLAT
configurations only; None where the program publishes no such gauge
(before PR 27) or ran no such program."""

from benchmark.harness import roofline_sharded

PROGRAM = "jit__sharded_search_kernel"
ITEMSIZE = {"Float": 4, "Int16": 2, "Int8": 1, "UInt8": 1}


def rows_per_shard():
    """Row slots one device holds, from the program's gauge; None where
    it has none."""
    from sptag_tpu.utils import metrics

    return int(metrics.gauge_value("mesh.rows_per_shard")) or None


def bound(run, rows):
    t, c = run["trace"], run["config"]
    q = run["spans"].get("server.queue_wait")
    b = run["spans"].get("server.execute_batch")
    if not t or not rows or not q or not b or PROGRAM not in t["programs"]:
        return None
    prog = t["programs"][PROGRAM]
    least = roofline_sharded.sharded_scan_least_seconds(
        prog["runs"], q["count"] / b["count"], rows, c["dim"],
        ITEMSIZE[c["value_type"]], run["peaks"])
    return least, prog["seconds"]


def read(run):
    got = bound(run, rows_per_shard())
    return 100.0 * got[0]["seconds"] / got[1] if got else None
