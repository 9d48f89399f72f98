"""CPU seconds of the load generator process over its window, in % of
one core.  Python holds one core at a time: near 100 the generator is the
bottleneck and qps is its own, not the server's."""


def read(run):
    r = run["requests"]
    return 100.0 * float(r["generator_cpu_s"]) / float(r["generator_wall_s"])
