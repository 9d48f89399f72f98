"""Hand tool: the control of "How `correct` is decided", at a cell's own
size — the plain reference put in the program's place and computed in the
nearest precisions below float32, through the configuration's own check.

    chiprun -- python3 -m benchmark.tools.control_reference <config> \
        <seed>[,...] [<mode>[,...]]

Prints one line per seed and mode.  Modes `highest`, `high`, `default`:
the scan in plain jax.numpy on the default device (the chip, there) with
its matrix product at that precision — `highest` is the sound reading,
`high` (three bfloat16 passes) the nearest precision below it, `default`
one pass.  Modes `bits23`, `bits15`, `bits7`: numpy with every input
rounded to that many explicit mantissa bits (what the CPU tests use; 15
bits reads far milder than the chip's `high`).  Runs none of the
program.  Used where the program's own precision
switch (`ops.distance.set_float_precision`, benchmark/tools/chip_first.py)
does not reach the arithmetic the cell runs — the BKT dense path's final
distances and the FLAT scan at one query do not go through it (PR 24).
"""

import json
import os
import sys

import numpy as np

from benchmark.harness import compare, reference
from benchmark.loadgen import load_by_name

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv) -> int:
    with open(os.path.join(HERE, "configs", argv[0] + ".json")) as f:
        config = json.load(f)
    modes = (argv[2] if len(argv) > 2 else "highest,high,default").split(",")
    nq = config["check"]["queries"]
    make = load_by_name("datasets", config["dataset"]).make
    rule = load_by_name("checks", config["check"]["rule"])
    for seed in (int(s) for s in argv[1].split(",")):
        data, queries = make(seed, config["rows"], config["dim"], nq)
        for mode in modes:
            if mode.startswith("bits"):
                ids, dists = reference.lower_precision_answers(
                    data, queries, config["k"], int(mode[4:]))
            else:
                ids, dists = reference.device_answers(
                    data, queries, config["k"], mode)
            got = rule.check(data, queries, np.arange(nq),
                             compare.answers_as_window(ids, dists), config)
            print(json.dumps({
                "config": argv[0], "seed": seed, "mode": mode,
                "correct": all(x["ok"] for x in got["numbers"]),
                "compared": {x["name"]: x["value"]
                             for x in got["numbers"]},
                "dist_err_ulps_max": got["seen"]["dist_err_ulps_max"]}),
                  flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
