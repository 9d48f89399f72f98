"""The replies' mean instant with the server's socket
(`server.departure_clock`: the connection's drain returned) -> the
callers' mean instant of having read the whole reply, on one clock: the
kernel, and the generator's thread getting to the reply behind the
others of the same joined write.  benchmark/harness/path.py says when
there is nothing to read."""

from benchmark.harness import path


def read(run):
    ways = path.ways(run)
    return 1e3 * ways[1] if ways else None
