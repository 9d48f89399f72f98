"""A caller's reply read -> its next request written to the socket,
mean (generator's clock).  Large against latency_p50_ms = the generator,
not the server, sets the rate."""


def read(run):
    t = run["requests"]["turnaround"]
    t = t[t > 0]                     # a caller's first request has none
    return float(t.mean()) * 1e3 if len(t) else None
