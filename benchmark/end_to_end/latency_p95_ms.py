"""`latency_p95_ms`: as latency_p50_ms, the 95th percentile."""

import numpy as np


def read(run):
    r = run["requests"]
    ok = r["status"] == r["success_status"]
    return float(np.percentile(r["latency"][ok], 95)) * 1e3 if ok.any() \
        else None
