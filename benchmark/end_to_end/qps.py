"""`qps`: well-formed Success answers received inside the window, over the
window's seconds — all the work and all the time of the window."""


def read(run):
    r = run["requests"]
    done = r["t_send"] + r["latency"]
    inside = (r["status"] == r["success_status"]) & (done <= run["seconds"])
    return float(inside.sum()) / run["seconds"]
