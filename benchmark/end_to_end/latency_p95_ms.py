"""`latency_p95_ms`: as latency_p50_ms, the 95th percentile."""

from benchmark.harness.reduce import latency_percentile_ms


def read(run):
    return latency_percentile_ms(run, 95)
