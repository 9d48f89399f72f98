"""Hand tool: what a walk of the configuration's budget finds over the
configuration's own graph — the plain walk (benchmark/harness/
reference_walk.py) beside the program, and the program at a quarter and a
half of its budget (the control `recall_at_10_min` is set against).

    chiprun -- python3 -m benchmark.tools.control_walk <config> \
        <seed>[,<seed>...]

For each seed: data and the check's queries from the seed, the
configuration's index built through the builder CLI or loaded from
benchmark/.cache/index/ (the folder a run of the cell on that seed left
or will find), the exact scan, the plain walk over the folder's
`graph.bin` and `tree.bin`, and the loaded index searched in batches of
the cell's top rung at MaxCheck = the configuration's, a quarter and a
half of it, a sixteenth and a sixty-fourth (where the trips fall too:
`walk_plan` keeps 32 trips down to MaxCheck 512 and narrows the pops),
and at the configuration's MaxCheck with the walk cut to ONE trip and
to NONE (the same program: the trip limit is an argument; `no_trip`, the
nearest pivots re-ranked, is the control the floor `recall_at_10_min` is
set against and the fault benchmark/tests/test_rehearsal_beam.py
plants).  One line a seed:
recall@k against the exact scan and rows scored a query beside each (the
program's from its gauge `beam.rows_scored_per_query`, pops x neighbours
over the trips a query was alive in; the plain walk's are distance
evaluations, as upstream counts MaxCheck).
"""

import json
import os
import shutil
import sys
import time

import numpy as np

from benchmark import run
from benchmark.harness import reference, reference_walk
from benchmark.loadgen import load_by_name


def control(name: str, config: dict, config_path: str, seed: int) -> dict:
    import sptag_tpu as sp
    from sptag_tpu.algo.engine import GraphSearchEngine
    from sptag_tpu.utils import metrics

    k, nq = config["k"], config["check"]["queries"]
    budget = int(config["index_params"]["MaxCheck"])
    data, queries = load_by_name("datasets", config["dataset"]).make(
        seed, config["rows"], config["dim"], nq)
    workdir = os.path.join(run.WORK, "control_walk")
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    folder, build_seconds, built = run.build_or_load(
        name, config, config_path, seed, data, workdir)
    exact, _ = reference.exact_topk(data, queries, k)
    graph, tree = reference_walk.from_folder(folder)
    t0 = time.perf_counter()
    ids, _, scored = reference_walk.walk_all(data, graph, queries, k,
                                             budget, tree=tree)
    out = {"config": name, "seed": seed, "max_check": budget,
           "build_seconds": build_seconds, "built": built,
           "plain": {"recall": reference.recall_at_k(ids, exact, k),
                     "rows_scored": float(scored.mean()),
                     "seconds": time.perf_counter() - t0},
           "program": {}}
    index = sp.load_index(folder)
    rung = max(config["warm_buckets"])

    def walked(mc):
        got, rows, trips = [], [], []
        for lo in range(0, nq, rung):
            got.append(index.search_batch(queries[lo:lo + rung], k,
                                          max_check=mc)[1])
            rows.append(metrics.gauge_value("beam.rows_scored_per_query"))
            trips.append(metrics.gauge_value("beam.trips"))
        return {"recall": reference.recall_at_k(np.concatenate(got),
                                                exact, k),
                "rows_scored": float(np.mean(rows)),
                "trips": float(np.mean(trips))}

    for mc in (budget, budget // 4, budget // 2) + tuple(
            b for b in (budget // 16, budget // 64) if b >= 16):
        out["program"][str(mc)] = walked(mc)
    sound = GraphSearchEngine.walk_plan
    for name, trips in (("one_trip", 1), ("no_trip", 0)):
        GraphSearchEngine.walk_plan = lambda self, *a, **kw: (
            *sound(self, *a, **kw)[:3], trips, sound(self, *a, **kw)[4])
        try:
            out["program"][name] = walked(budget)
        finally:
            GraphSearchEngine.walk_plan = sound
    index.close()
    return out


def main(argv) -> int:
    import jax

    config_path = os.path.join(run.HERE, "configs", argv[0] + ".json")
    config = run.load_json(config_path)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(run.CACHE, "jax"))
    dev = jax.devices()[0]
    for seed in (int(s) for s in argv[1].split(",")):
        line = control(argv[0], config, config_path, seed)
        line["device"] = {"platform": dev.platform, "kind": dev.device_kind}
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
