"""XLA compilations (utils/recompile_guard.py) between `go` and the
generator's end.  Expected 0: every shape was warmed in set-up."""


def read(run):
    return run["compiles_in_window"]
