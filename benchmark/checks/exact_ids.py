"""`exact_ids`: an exact index answers with the exact scan's ids.

Numbers compared (limits in the configuration's `check.limits`):
  id_lists_wrong     distinct answers whose id list is invalid, or differs
                     from the reference's beyond the tie rule: the true
                     float64 scores of the returned ids, sorted, equal the
                     reference's within `tie_ulps` float32 ulps of
                     |q|^2+|x|^2.  Limit 0: the configuration's guarantee.
  dist_err_ulps_rms  root mean square over all returned distances of
                     |distance - exact float64 distance of its id| in the
                     same ulps.  Steady from seed to seed, and what a scan
                     in lower precision moves first (ids flip only where
                     neighbours lie closer than the rounding).
"""

import numpy as np

from benchmark.harness import compare, reference


def check(data, queries, sample, record, config) -> dict:
    limits, k = config["check"]["limits"], config["k"]
    q_idx, ids, dists, compared = compare.distinct_answers(record, sample)
    ref_ids, ref_scores = reference.exact_topk(data, queries[sample], k)
    where = np.searchsorted(sample, q_idx)          # sample is sorted
    ref_ids, ref_scores = ref_ids[where], ref_scores[where]

    invalid = compare.invalid_lists(ids, len(data))
    differs = (ids != ref_ids).any(axis=1)
    safe = np.clip(ids, 0, len(data) - 1)
    got = np.sort(reference.exact_scores(data, queries[q_idx], safe), axis=1)
    tol = (limits["tie_ulps"] * reference.F32_EPS
           * reference.ulp_scale(data, queries[q_idx])[:, None])
    beyond = differs & (np.abs(got - ref_scores) > tol).any(axis=1)
    wrong = int(beyond.sum()) + invalid
    err = compare.dist_err_ulps(data, queries, q_idx, ids, dists)
    return {
        "numbers": [
            compare.number("id_lists_wrong", wrong,
                           limits["id_lists_wrong"], "lower"),
            compare.number("dist_err_ulps_rms",
                           float(np.sqrt(np.mean(err ** 2))),
                           limits["dist_err_ulps_rms"], "lower"),
        ],
        "seen": {"answers_compared": compared,
                 "distinct_answers": int(len(q_idx)),
                 "queries_checked": int(len(np.unique(q_idx))),
                 "id_lists_tie_resolved": int((differs & ~beyond).sum()),
                 "dist_err_ulps_max": float(err.max())},
    }
