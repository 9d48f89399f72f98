"""`exact_ids_live`: an exact index that changes while it is searched
answers every search with the exact top-k of a state it may have seen.

The writer's operations are in the record in the order they were sent
(one in flight: a serial order), each with the instant it was written
(`w_t_send`, stamped BEFORE the write) and acknowledged (`w_t_send +
w_latency`, stamped after the reply was read).  A search is **admissible**
under state m (benchmark/harness/reference_live.py: base rows + the
first m operations) for a <= m <= b,

    a = operations acknowledged before the search was written,
    b = operations written before its reply was read.

Every stamp errs to the side that admits more: a search's `t_send` is
taken after its write, so its write lies in [t_send - turnaround, t_send]
(the caller read its previous reply at the lower end), and the lower end
is used.

Numbers compared (limits in the configuration's `check.limits`):
  stale_or_wrong_lists  answers of the sampled queries whose id list is
                        no admissible state's exact top-k, beyond the tie
                        rule (the true float64 scores of the returned
                        ids, sorted, equal that state's within `tie_ulps`
                        float32 ulps of |q|^2+|x|^2, and every id is live
                        in it).  EVERY answer is compared, not one a
                        query: the right answer changes.  Limit 0.
  invalid_lists         an id out of range or twice.  Limit 0.
  dist_err_ulps_rms     as `exact_ids`, over every compared answer.
  mutations_failed      operations with no `ok` reply, or whose replied
                        count is not the rows sent.  Limit 0.
  writer_steps_done_share  steps begun in the window / the window's
                        period boundaries: below the limit the cell did
                        not run its traffic.
"""

import numpy as np

from benchmark.harness import compare, reference, reference_live, runbook


def writer_ops(record: dict, queries: np.ndarray) -> list:
    """The writer's operations as the reference takes them: (kind, rows),
    the rows made again from the queries (harness/runbook.py)."""
    blocks = {}
    ops = []
    for kind, step, rows in zip(record["w_kind"], record["w_step"],
                                record["w_rows"]):
        key = (int(step), int(rows))
        if key not in blocks:
            blocks[key] = runbook.streamed_rows(
                queries, key[0], key[1], float(record["w_sigma"]))
        ops.append((int(kind), blocks[key]))
    return ops


def check(data, queries, sample, record, config) -> dict:
    limits, k = config["check"]["limits"], config["k"]
    ops = writer_ops(record, queries)
    ref = reference_live.LiveReference(data, queries[sample], k, ops)

    ok = record["status"] == record["success_status"]
    mine = np.flatnonzero(ok & np.isin(record["query"], sample))
    q_pos = np.searchsorted(sample, record["query"][mine])   # sorted sample
    ids = record["ids"][mine]
    dists = record["dists"][mine].astype(np.float32)

    # the window of states each answer may have seen
    w_sent = record["w_t_send"]
    w_acked = np.sort(w_sent + np.where(np.isnan(record["w_latency"]),
                                        np.inf, record["w_latency"]))
    t_send, turn = record["t_send"][mine], record["turnaround"][mine]
    written = np.where(turn > 0, t_send - turn, np.minimum(t_send, 0.0))
    read = t_send + record["latency"][mine]
    a = np.searchsorted(w_acked, written, side="left")
    b = np.searchsorted(w_sent, read, side="left")
    b = np.maximum(a, b)

    total = ref.base + len(ref.streamed)
    bad_id = ((ids < 0) | (ids >= total)).any(axis=1)
    srt = np.sort(ids, axis=1)
    invalid = bad_id | (srt[:, 1:] == srt[:, :-1]).any(axis=1)

    got = ref.exact_scores(q_pos, ids)
    got_sorted = np.sort(got, axis=1)
    scale = reference.ulp_scale(data, queries[sample])[q_pos][:, None]
    tol = limits["tie_ulps"] * reference.F32_EPS * scale
    admissible = np.zeros(len(mine), bool)
    under_older = np.zeros(len(mine), bool)
    for lag in range(int((b - a).max()) + 1 if len(mine) else 0):
        m = np.minimum(a + lag, b)
        fits = (ids == ref.ids[m, q_pos]).all(axis=1)
        # the tie rule, where the list differs: the same scores, live ids
        close = ~fits & (np.abs(got_sorted - ref.scores[m, q_pos])
                         <= tol).all(axis=1)
        for i in np.flatnonzero(close):
            fits[i] = ref.live(m[i], ids[i]).all()
        if lag == 0:
            under_older = fits
        admissible |= fits
    wrong = int((~admissible & ~invalid).sum())

    err = np.abs(dists.astype(np.float64) - got) / (reference.F32_EPS * scale)
    w_ok = record["w_ok"].astype(bool) & (record["w_count"]
                                          == record["w_rows"])
    expected = int(float(record["w_window_s"]) * 1e3
                   // float(record["w_period_ms"]))
    acks = {}
    for kind, name in ((reference_live.ADD, "add"),
                       (reference_live.DELETE, "delete")):
        lat = record["w_latency"][(record["w_kind"] == kind)
                                  & (w_sent >= 0)]
        lat = lat[~np.isnan(lat)]
        for q in (50, 95):
            acks[f"{name}_ack_p{q}_ms"] = (
                float(np.percentile(lat, q)) * 1e3 if len(lat) else None)
    streamed = (ids >= ref.base).any(axis=1)
    return {
        "numbers": [
            compare.number("stale_or_wrong_lists", wrong,
                           limits["stale_or_wrong_lists"], "lower"),
            compare.number("invalid_lists", int(invalid.sum()),
                           limits["invalid_lists"], "lower"),
            compare.number("dist_err_ulps_rms",
                           float(np.sqrt(np.mean(err ** 2))) if len(mine)
                           else 0.0,
                           limits["dist_err_ulps_rms"], "lower"),
            compare.number("mutations_failed", int((~w_ok).sum()),
                           limits["mutations_failed"], "lower"),
            compare.number("writer_steps_done_share",
                           float(record["w_steps_done"]) / max(expected, 1),
                           limits["writer_steps_done_share"], "higher"),
        ],
        "seen": {"answers_compared": int(len(mine)),
                 "queries_checked": int(len(np.unique(q_pos))),
                 "answers_newer_state_only": int((admissible
                                                  & ~under_older).sum()),
                 "answers_with_streamed_row": int(streamed.sum()),
                 "operations": int(len(ops)),
                 "operations_in_window": int((w_sent >= 0).sum()),
                 "writer_steps_done": int(record["w_steps_done"]),
                 "writer_steps_skipped": int(record["w_steps_skipped"]),
                 "rows_tombstoned_by_reference": int(ref.tombstoned.sum()),
                 "dist_err_ulps_max": float(err.max()) if len(mine) else 0.0,
                 **acks},
    }
