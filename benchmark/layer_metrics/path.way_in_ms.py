"""The callers' mean send instant -> the requests' mean enqueue instant
(`server.arrival_clock` over the window, on the generator's own clock):
the client's socket write, the kernel, the server's event loop getting to
the frame, decode and admission.  benchmark/harness/path.py says when
there is nothing to read."""

from benchmark.harness import path


def read(run):
    ways = path.ways(run)
    return 1e3 * ways[0] if ways else None
