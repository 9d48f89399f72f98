"""Least time the chip could take for the scans of the traced slice over
the traced device time of `jit__flat_search_kernel`, in %, for a FLAT
configuration of one-byte rows.  Least time:
benchmark/harness/roofline_int8.py (one read of the int8 rows a program
run, or the queries' dot products at the int8 peak; not the score matrix).
Reads the configuration's file and the trace, no counter: it reads the
same work whatever implements the scan."""

from benchmark.harness import roofline_int8

PROGRAM = "jit__flat_search_kernel"


def bound(run):
    t, c = run["trace"], run["config"]
    if not t or c["algo"] != "FLAT" or c["value_type"] not in (
            "Int8", "UInt8") or PROGRAM not in t["programs"]:
        return None
    q = run["spans"].get("server.queue_wait")
    b = run["spans"].get("server.execute_batch")
    if not q or not b:
        return None
    prog = t["programs"][PROGRAM]
    least = roofline_int8.int8_scan_least_seconds(
        prog["runs"], q["count"] / b["count"], c["rows"], c["dim"],
        run["peaks"])
    return least, prog["seconds"]


def read(run):
    got = bound(run)
    return 100.0 * got[0]["seconds"] / got[1] if got else None
