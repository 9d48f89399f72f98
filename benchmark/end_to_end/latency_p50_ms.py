"""`latency_p50_ms`: request written -> whole reply read, median over
every request the window sent that was answered."""

import numpy as np


def read(run, q=50):
    r = run["requests"]
    ok = r["status"] == r["success_status"]
    return float(np.percentile(r["latency"][ok], q)) * 1e3 if ok.any() \
        else None
