"""CPU seconds of the server's event-loop thread (`server.loop_cpu`,
`time.thread_time()` at each batch's assembly) over the batch cycles
they span (`server.batch_cycle`), in %: with executor.cpu_share, how
much of a cycle a thread of the server can have held the interpreter."""

from benchmark.harness import path


def read(run):
    return path.share_of_cycle(run, "server.loop_cpu")
