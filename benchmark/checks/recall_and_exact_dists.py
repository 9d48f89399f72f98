"""`recall_and_exact_dists`: an approximate index finds enough of the exact
neighbours, and the distances it returns are exact for the ids it returns.

Numbers compared (limits in the configuration's `check.limits`):
  recall_at_10       mean over the distinct answers of |got ∩ exact
                     top-k| / k.  Limit: the configuration's floor.
  invalid_lists      answers with an id out of range or twice.  Limit 0.
  dist_err_ulps_rms  as in `exact_ids`.  Recall alone would pass a scan in
                     lower precision; this does not.
Also hands `recall_at_10` to the end-to-end metric of that name.
"""

import numpy as np

from benchmark.harness import compare, reference


def check(data, queries, sample, record, config) -> dict:
    limits, k = config["check"]["limits"], config["k"]
    q_idx, ids, dists, compared = compare.distinct_answers(record, sample)
    ref_ids, _ = reference.exact_topk(data, queries[sample], k)
    ref_ids = ref_ids[np.searchsorted(sample, q_idx)]
    recall = reference.recall_at_k(ids, ref_ids, k)
    err = compare.dist_err_ulps(data, queries, q_idx, ids, dists)
    return {
        "numbers": [
            compare.number("recall_at_10", recall,
                           limits["recall_at_10_min"], "higher"),
            compare.number("invalid_lists",
                           compare.invalid_lists(ids, len(data)), 0,
                           "lower"),
            compare.number("dist_err_ulps_rms",
                           float(np.sqrt(np.mean(err ** 2))),
                           limits["dist_err_ulps_rms"], "lower"),
        ],
        "seen": {"answers_compared": compared,
                 "distinct_answers": int(len(q_idx)),
                 "queries_checked": int(len(np.unique(q_idx))),
                 "dist_err_ulps_max": float(err.max())},
        "end_to_end": {"recall_at_10": recall},
    }
