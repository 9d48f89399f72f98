"""Least time the chip could take for the scans of the traced slice over
the traced device time of `jit__flat_search_kernel`, in %, for a FLAT
configuration whose corpus changes while it is searched.  Least time:
benchmark/harness/roofline_live.py (one read a program run of the
configuration's rows — the occupied slots, to the streamed rows' fraction
of a percent — or the queries' dot products; not the reserve, not the
score matrix).  The runs' mean query count is the searches' (queued
requests a served batch): a delete's search-by-content is one more run
of the program at the same rung.  Reads the configuration's file and the
trace alone, no counter, so it reads the same work whatever keeps the
block current (the parent's whole re-snapshot or the in-place write)."""

from benchmark.harness import roofline_live

PROGRAM = "jit__flat_search_kernel"


def bound(run):
    t, c = run["trace"], run["config"]
    if not t or c["algo"] != "FLAT" or PROGRAM not in t["programs"]:
        return None
    q = run["spans"].get("server.queue_wait")
    b = run["spans"].get("server.execute_batch")
    if not q or not b:
        return None
    prog = t["programs"][PROGRAM]
    least = roofline_live.live_scan_least_seconds(
        prog["runs"], q["count"] / b["count"], c["rows"], c["dim"], 4,
        run["peaks"])
    return least, prog["seconds"]


def read(run):
    got = bound(run)
    return 100.0 * got[0]["seconds"] / got[1] if got else None
