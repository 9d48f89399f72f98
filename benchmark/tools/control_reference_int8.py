"""Hand tool: the control of `exact_ids_int_cosine`, at a cell's own size
— the plain integer-cosine scan put in the program's place on the default
device and computed in the nearest precision below the configuration's
(int8 operands, int32 accumulation, exact integer distances), through the
configuration's own check.

    chiprun -- python3 -m benchmark.tools.control_reference_int8 <config> \
        <seed>[,...] [<mode>[,...]]

Prints one line per seed and mode.  Modes (harness/reference_int8_cosine
`device_answers`): `int32` the sound reading; `bf16` bfloat16 operands and
a result rounded to bfloat16 — not correct by `dist_err_max`; `bf16_jnp`
what jnp.dot of bfloat16 operands returns, widened at once (XLA may keep
the float32 accumulator); `f32_default` float32 operands at the default
matmul precision (one bfloat16 pass on the chip).  Runs none of the
program.
(benchmark/tools/control_reference.py is the float L2 rules' and prints
their `dist_err_ulps_max`.)
"""

import json
import os
import sys

import numpy as np

from benchmark.harness import compare, reference_int8_cosine
from benchmark.loadgen import load_by_name

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv) -> int:
    with open(os.path.join(HERE, "configs", argv[0] + ".json")) as f:
        config = json.load(f)
    modes = (argv[2] if len(argv) > 2 else "int32,bf16,bf16_jnp,f32_default"
             ).split(",")
    nq = config["check"]["queries"]
    make = load_by_name("datasets", config["dataset"]).make
    rule = load_by_name("checks", config["check"]["rule"])
    for seed in (int(s) for s in argv[1].split(",")):
        data, queries = make(seed, config["rows"], config["dim"], nq)
        for mode in modes:
            ids, dists = reference_int8_cosine.device_answers(
                data, queries, config["k"], mode)
            got = rule.check(data, queries, np.arange(nq),
                             compare.answers_as_window(ids, dists), config)
            print(json.dumps({
                "config": argv[0], "seed": seed, "mode": mode,
                "correct": all(x["ok"] for x in got["numbers"]),
                "compared": {x["name"]: x["value"]
                             for x in got["numbers"]},
                "seen": got["seen"]}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
