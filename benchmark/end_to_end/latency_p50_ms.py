"""`latency_p50_ms`: request written -> whole reply read, median over
every request the window sent that was answered."""

from benchmark.harness.reduce import latency_percentile_ms


def read(run):
    return latency_percentile_ms(run, 50)
