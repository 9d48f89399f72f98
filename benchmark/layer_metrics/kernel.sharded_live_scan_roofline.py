"""Least time ONE chip could take for its share of the scans of the traced
slice over the traced device time of `jit__sharded_search_kernel` on the
busiest device plane, in %, for a mesh FLAT configuration whose corpus
changes while it is searched.  Least time: benchmark/harness/
roofline_live.py with a chip's share of the CONFIGURATION's rows (`rows`
/ `chips`: one read a program run of rows / chips x dim x 4 bytes, or its
share of the queries' dot products at the bf16 peak; the reserve a shard
keeps ahead of its rows, the tombstoned rows' share, the scores, the
candidates gathered over ICI and the re-rank are the implementation's and
are not counted).  The runs' mean query count is the searches' (queued
requests a served batch): a delete's search-by-content is one more run of
the program at the same rung.  Reads the configuration's file and the
trace alone, no gauge and no counter, so it reads the same work whatever
implements it; None on one chip, off a FLAT configuration, or where no
such program ran."""

from benchmark.harness import roofline_live

PROGRAM = "jit__sharded_search_kernel"


def bound(run):
    t, c = run["trace"], run["config"]
    if not t or c["algo"] != "FLAT" or c.get("chips", 1) < 2 \
            or PROGRAM not in t["programs"]:
        return None
    q = run["spans"].get("server.queue_wait")
    b = run["spans"].get("server.execute_batch")
    if not q or not b:
        return None
    prog = t["programs"][PROGRAM]
    least = roofline_live.live_scan_least_seconds(
        prog["runs"], q["count"] / b["count"], c["rows"] / c["chips"],
        c["dim"], 4, run["peaks"])
    return least, prog["seconds"]


def read(run):
    got = bound(run)
    return 100.0 * got[0]["seconds"] / got[1] if got else None
