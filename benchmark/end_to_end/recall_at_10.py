"""`recall_at_10`: what the cell's check measured against the exact scan
(checks/recall_and_exact_dists.py) — the recall the qps is stated at."""


def read(run):
    return ((run["check"] or {}).get("end_to_end") or {}).get("recall_at_10")
