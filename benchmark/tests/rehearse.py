"""Sizes at which a cell runs on this sandbox's CPU (tests and hand
rehearsals only).  Widths, metric, k and the served path are the cell's
own; rows, queries and callers are cut, and the BKT build knobs are the
ones the repo's CPU tests pin (default knobs take minutes at 10k rows)."""

FLAT = {"config": {"rows": 20_000,
                   "check": {"rule": "exact_ids", "queries": 32,
                             "limits": {"id_lists_wrong": 0, "tie_ulps": 8,
                                        "dist_err_ulps_rms": 4.0}}},
        "traffic": {"callers": 16, "connections": 2,
                    "distinct_queries": 64}}
BKT = {"config": {"rows": 4_000,
                  "index_params": {
                      "BKTNumber": "1", "BKTKmeansK": "32",
                      "TPTNumber": "4", "TPTLeafSize": "1000",
                      "NeighborhoodSize": "32", "CEF": "64",
                      "MaxCheckForRefineGraph": "128",
                      "RefineIterations": "2", "MaxCheck": "2048",
                      "RefineQueryGroup": "32",
                      "FinalRefineSearchMode": "same",
                      "SearchMode": "dense"},
                  "index_cache": False,
                  "check": {"rule": "recall_and_exact_dists", "queries": 32,
                            "limits": {"recall_at_10_min": 0.90,
                                       "dist_err_ulps_rms": 4.0}}},
       "traffic": {"callers": 16, "connections": 2,
                   "distinct_queries": 64}}
SINGLE = {"config": FLAT["config"],
          "traffic": {"distinct_queries": 64}}
BY_CELL = {"flat_1m.saturate": FLAT, "bkt_100k.saturate": BKT,
           "flat_1m.single": SINGLE}
