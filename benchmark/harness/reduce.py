"""Small reductions the metric readers share."""

import numpy as np


def span_mean_ms(run: dict, name: str):
    """Mean of the program span `name` over the window, in ms; None where
    the span did not occur."""
    s = run["spans"].get(name)
    return 1e3 * s["total_s"] / s["count"] if s else None


def latency_percentile_ms(run: dict, q: float):
    """Percentile `q` of request written -> whole reply read over every
    answered request of the window, in ms."""
    r = run["requests"]
    ok = r["status"] == r["success_status"]
    return float(np.percentile(r["latency"][ok], q)) * 1e3 if ok.any() \
        else None
