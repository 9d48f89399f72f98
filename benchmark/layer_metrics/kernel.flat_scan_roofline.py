"""Least time the chip could take for the scans of the traced slice over
the traced device time of `jit__flat_search_kernel`, in %.  Least time:
benchmark/harness/roofline.py (one corpus read per batch, the queries' dot
products; not the score matrix).  FLAT configurations only."""

from benchmark.harness import roofline

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
    least = roofline.flat_scan_least_seconds(
        prog["runs"], q["count"] / b["count"], c["rows"], c["dim"], 4,
        run["peaks"])
    return least, prog["seconds"]


def read(run):
    got = bound(run)
    return 100.0 * got[0]["seconds"] / got[1] if got else None
