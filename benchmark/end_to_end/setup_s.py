"""`setup_s`: process start -> the window's first request may go: data,
build or load, server start, warm-up (host clock)."""


def read(run):
    return run["setup_s"]
