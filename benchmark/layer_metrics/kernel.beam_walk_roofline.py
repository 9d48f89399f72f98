"""Least time the chip could take for the beam walks of the traced slice
over the traced device time of the beam programs (`jit__beam_search_kernel`
and whichever twin ran), in %.  Least time: benchmark/harness/
roofline_beam.py, from what the program says a query scored: counter
`beam.rows_scored_total` over counter `beam.queries_total`, a ratio of
totals.  The harness hands readers the window's delta of spans, not of
counters, so both totals run FROM THE START OF THE PROCESS: the warm-up's
batches and the check's are in them beside the window's (all walk the
cell's one plan; a walk that ends early on some batches shifts the ratio
by their share of all queries, not of the slice's).  The pool, the pivot
table and the scoring itemsize are the plan's and the index's (gauges
`beam.pool`, `beam.pivots`, `beam.score_itemsize`, as the last batch set
them).  BKT beam configurations only; None where the program publishes
none of it (before PR 32) or ran no such program."""

from benchmark.harness import roofline_beam

# one run of an ENTRY program starts one batch's walk (the monolithic and
# chunked programs hold all of it; the segmented driver goes on through
# REST); the traced time is that of both lists
ENTRY = ("jit__beam_search_kernel", "jit__beam_search_chunked",
         "jit__beam_search_seeded_kernel",
         "jit__beam_search_seeded_chunked", "jit__beam_seed_kernel",
         "jit__beam_seed_seeded_kernel")
REST = ("jit__beam_segment_kernel", "jit__beam_finalize_kernel")


def walked_per_query():
    """(rows a query scored, pool rows, pivots, scoring itemsize) from
    the program's totals and gauges; None where it has none."""
    from sptag_tpu.utils import metrics

    rows = metrics.counter_value("beam.rows_scored_total")
    asked = metrics.counter_value("beam.queries_total")
    return (rows / asked, metrics.gauge_value("beam.pool"),
            metrics.gauge_value("beam.pivots"),
            int(metrics.gauge_value("beam.score_itemsize")) or 4) \
        if rows and asked else None


def bound(run, walked):
    t, c = run["trace"], run["config"]
    q = run["spans"].get("server.queue_wait")
    b = run["spans"].get("server.execute_batch")
    if not t or not walked or not q or not b:
        return None
    ran = {p: t["programs"][p] for p in ENTRY + REST if p in t["programs"]}
    batches = sum(ran[p]["runs"] for p in ENTRY if p in ran)
    if not batches:
        return None
    least = roofline_beam.beam_walk_least_seconds(
        batches, q["count"] / b["count"], walked[0], walked[1], walked[2],
        c["dim"], walked[3], run["peaks"])
    return least, sum(p["seconds"] for p in ran.values())


def read(run):
    got = bound(run, walked_per_query())
    return 100.0 * got[0]["seconds"] / got[1] if got else None
