"""Record `server.request` (enqueued -> the reply with its socket: queue
wait, execute, hand-off and the request's share of the reply), mean over
the window's answered requests."""

from benchmark.harness.reduce import span_mean_ms


def read(run):
    return span_mean_ms(run, "server.request")
