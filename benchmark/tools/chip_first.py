"""Hand tool for the chip (not run by the benchmark): several runs of one
cell in ONE process, sound and with the program switched to a lower float
precision (the control), printing one line per run.

    chiprun -- python3 -m benchmark.tools.chip_first <cell> <seconds> \
        <seed>[,<seed>...] [highest|high|default]

Precision `highest` is the program as committed.  The runs share the
process, so only the first pays the chip's start-up — and so ONE precision
to a process: a jitted program traced at one precision is not traced again
when the setting changes (first chip call of PR 24: `high` and `default`
after `highest` in one process read the same numbers to the last digit).
"""

import json
import sys

from benchmark import run


def main(argv) -> int:
    cell, seconds = argv[0], float(argv[1])
    seeds = [int(s) for s in argv[2].split(",")]
    mode = argv[3] if len(argv) > 3 else "highest"
    for seed in seeds:
        r = run.run_cell(cell, seed, seconds, False,
                         control=None if mode == "highest" else mode)
        print(json.dumps({
            "cell": cell, "seed": seed, "precision": mode,
            "correct": r["correct"],
            "compared": {n["name"]: n["value"] for n in r["compared"]},
            "seen": {k: v for k, v in r["seen"].items()
                     if k in ("dist_err_ulps_max", "build_seconds",
                              "id_lists_tie_resolved",
                              "compiles_in_window", "reference_s")},
            "metrics": {k: v["value"]
                        for k, v in r["metrics"].items()}}),
              flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
