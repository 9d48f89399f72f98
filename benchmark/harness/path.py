"""A request's way in and way back, from two records on one clock.

`time.perf_counter()` is CLOCK_MONOTONIC on Linux: one clock for every
process of the machine.  The load generator stamps each request with it
(`t_send` after the write, `t_send + latency` after the whole reply is
read, both counted from `window_t0`), and the program sums, per batch,
the instants its requests were enqueued (`server.arrival_clock`) and the
instants their replies were with the socket (`server.departure_clock`),
both counted from the gauge `server.clock_origin_s`.  Over a window in
which the three sets are the same requests,

    mean latency = way in + mean of `server.request` + way back

to the rounding of the sums.  Nothing to read (None) where the program
has no such records (the parent of the PR that added them), where a
request had no reply, or where the counts differ (a request shed, expired
or dropped is in the generator's record and not in the program's sums).
"""

import numpy as np

ARRIVAL, DEPARTURE = "server.arrival_clock", "server.departure_clock"
ORIGIN_GAUGE = "server.clock_origin_s"


def ways(run: dict):
    """-> (way in, way back) in seconds: the callers' mean send instant
    -> the requests' mean enqueue instant, and the replies' mean instant
    with the socket -> the callers' mean instant of having read them;
    None where the generator's record and the program's sums are not of
    the same requests."""
    from sptag_tpu.utils import metrics

    r = run.get("requests")
    arrived = run["spans"].get(ARRIVAL)
    departed = run["spans"].get(DEPARTURE)
    origin = metrics.gauge_value(ORIGIN_GAUGE)
    if r is None or not arrived or not departed or not origin:
        return None
    written = len(r["status"])
    if not written or np.isnan(r["latency"]).any() \
            or arrived["count"] != written or departed["count"] != written:
        return None
    sent = float(r["window_t0"]) + float(r["t_send"].mean())
    return (origin + arrived["total_s"] / written - sent,
            sent + float(r["latency"].mean())
            - (origin + departed["total_s"] / written))


def share_of_cycle(run: dict, name: str):
    """Total of record `name` over the total of `server.batch_cycle` in
    the window, in %; None where either is absent."""
    part, cycle = run["spans"].get(name), run["spans"].get(
        "server.batch_cycle")
    if not part or not cycle or not cycle["total_s"]:
        return None
    return 100.0 * part["total_s"] / cycle["total_s"]
