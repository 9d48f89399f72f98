"""Record `server.batch_reply` (a batch executed -> its last reply with
its socket: the response task's start, encode, one write and drain a
connection, the per-request duties after each), mean per batch."""

from benchmark.harness.reduce import span_mean_ms


def read(run):
    return span_mean_ms(run, "server.batch_reply")
