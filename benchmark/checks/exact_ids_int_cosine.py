"""`exact_ids_int_cosine`: an exact index over int8 rows answers with the
exact scan's ids and with exact integer distances, in SPTAG's integer
cosine convention (benchmark/harness/reference_int8_cosine.py).

Numbers compared (limits in the configuration's `check.limits`):
  id_lists_wrong  distinct answers whose id list is invalid, or differs
                  from the reference's other than by a tie: the exact
                  integer scores of the returned ids, sorted, EQUAL the
                  reference's rank for rank.  Integer scores tie often at
                  millions of rows; `seen.id_lists_tie_resolved` counts
                  the lists the tie rule let through.  Limit 0.
  invalid_lists   lists with an id out of range or an id twice.  Limit 0.
  dist_err_max    largest |returned distance - (127^2 - dot) of the id it
                  came with| over all returned distances.  Integers below
                  2^24 are exact in float32, so the limit is 0: a result
                  rounded to bfloat16 misses by up to 32.
"""

import numpy as np

from benchmark.harness import compare
from benchmark.harness import reference_int8_cosine as reference


def check(data, queries, sample, record, config) -> dict:
    limits, k = config["check"]["limits"], config["k"]
    q_idx, ids, dists, compared = compare.distinct_answers(record, sample)
    ref_ids, ref_scores = reference.exact_topk_int8_cosine(
        data, queries[sample], k)
    where = np.searchsorted(sample, q_idx)          # sample is sorted
    ref_ids, ref_scores = ref_ids[where], ref_scores[where]

    invalid = compare.invalid_lists(ids, len(data))
    safe = np.clip(ids, 0, len(data) - 1)
    exact = reference.exact_scores(data, queries[q_idx], safe)
    differs = (ids != ref_ids).any(axis=1)
    beyond = differs & (np.sort(exact, axis=1) != ref_scores).any(axis=1)
    err = np.abs(dists.astype(np.float64) - exact)
    return {
        "numbers": [
            compare.number("id_lists_wrong", int(beyond.sum()) + invalid,
                           limits["id_lists_wrong"], "lower"),
            compare.number("invalid_lists", invalid,
                           limits["invalid_lists"], "lower"),
            compare.number("dist_err_max", float(err.max()),
                           limits["dist_err_max"], "lower"),
        ],
        "seen": {"answers_compared": compared,
                 "distinct_answers": int(len(q_idx)),
                 "queries_checked": int(len(np.unique(q_idx))),
                 "id_lists_tie_resolved": int((differs & ~beyond).sum()),
                 "dists_off": int((err > 0).sum())},
    }
