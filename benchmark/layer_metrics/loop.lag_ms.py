"""Record `server.loop_lag` (the server's 10 ms heartbeat: due -> run),
mean: what a frame arriving at a random instant waits before the event
loop can read it — the loop's own callbacks ahead of it and its wait for
the interpreter lock."""

from benchmark.harness.reduce import span_mean_ms


def read(run):
    return span_mean_ms(run, "server.loop_lag")
