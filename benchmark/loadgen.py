"""The load generator: a child process of benchmark.run, off the chip.

    python -m benchmark.loadgen <spec.json>

The parent starts it with JAX_PLATFORMS=cpu (importing the program's client
pulls jax into the process; the chip belongs to the parent).  The spec
names the server's address, the file of pre-encoded query texts, the
traffic mix (whose `kind` names a module of benchmark/loops/), the window's
seconds and the .npz file the per-request arrays go to.  Protocol with the
parent, over the child's stdout and stdin: the child prints `ready` once
it is connected, the parent answers `go`, the child prints `done` after
the file is written.
"""

import importlib.util
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load_by_name(folder: str, name: str):
    """The module benchmark/<folder>/<name>.py — found by the name a data
    file gives, so a later PR adds one by adding a file."""
    path = os.path.join(HERE, folder, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{folder}_{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def main(argv) -> int:
    with open(argv[0]) as f:
        spec = json.load(f)
    with open(spec["texts"]) as f:
        texts = json.load(f)
    loop = load_by_name("loops", spec["traffic"]["kind"])

    def ready():
        print("ready", flush=True)

    def go():
        if sys.stdin.readline().strip() != "go":
            raise SystemExit("parent went away before the window")

    record = loop.run(spec, texts, ready, go)
    np.savez(spec["out"], **record)
    print("done", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
