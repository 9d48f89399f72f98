"""CPU seconds of the executor thread inside `execute_batch`
(`server.executor_cpu`, `time.thread_time()` on either side) over the
batch cycles of the window (`server.batch_cycle`), in %: parse, dispatch
and result building on that thread; its wait for the device is no CPU."""

from benchmark.harness import path


def read(run):
    return path.share_of_cycle(run, "server.executor_cpu")
