"""Hand tool: `control_reference.py` for a configuration whose corpus
changes while it is searched (rule `exact_ids_live`) — the plain reference
put in the program's place and computed in the nearest precisions below
float32, through the configuration's own check.

    chiprun -- python3 -m benchmark.tools.control_reference_live <config> \
        <seed>[,...] [<mode>[,...]] [<rows>]

Modes as `control_reference.py`'s: `highest`, `high`, `default` (the plain
jax.numpy scan on the default device at that precision), `bits23`,
`bits15`, `bits7` (numpy, inputs rounded).  `rows` cuts the corpus for a
machine that cannot hold the configuration's (one chip and a mesh
configuration): a distance's error in ulps does not depend on how many
rows were scanned beside it.  Runs none of the program.

The writer's record is a SERIAL run of `traffic/stream128.json`'s steps
(an add of `rows_per_add` rows a step and, `delete_lag_steps` later, the
delete of that step's rows): every operation acknowledged before the next
is written, every query of the sample answered once in every state, so
each answer has ONE admissible state and a right list passes, a stale one
does not.  Used where the program's own precision switch does not reach
the arithmetic the cell runs: since PR 41 the 128-rung float scan takes
its minima from a kernel that contracts at `highest` whatever the switch
says and re-scores the chosen rows in float32 on the vector unit, so
`chip_first.py <cell> ... high` lowers only the small rungs' answers.
"""

import json
import os
import sys

import numpy as np

from benchmark.harness import reference, reference_live
from benchmark.harness.runbook import streamed_rows
from benchmark.loadgen import load_by_name

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXTRA_STEPS = 4          # steps after the first delete


def plain_answers(rows, queries, k: int, mode: str):
    if mode.startswith("bits"):
        return reference.lower_precision_answers(rows, queries, k,
                                                 int(mode[4:]))
    return reference.device_answers(rows, queries, k, mode)


def serial_record(data, queries, k: int, mode: str, traffic: dict) -> dict:
    """The record of a serial run answered by the plain scan at `mode`:
    operation i is written at i and acknowledged at i + 0.5; the answers
    of state m (m operations applied) are written at m - 0.4 and read at
    m - 0.3."""
    per, lag = traffic["rows_per_add"], traffic["delete_lag_steps"]
    sigma, steps = traffic["stream_sigma"], lag + EXTRA_STEPS
    ops = []                                   # (kind, step)
    for s in range(steps):
        ops.append((reference_live.ADD, s))
        if s >= lag:
            ops.append((reference_live.DELETE, s - lag))
    streamed = np.concatenate([streamed_rows(queries, s, per, sigma)
                               for s in range(steps)])
    base_ids, base_d = plain_answers(data, queries, k, mode)
    s_ids, s_d = plain_answers(streamed, queries, len(streamed), mode)
    late = np.empty(s_d.shape, np.float32)     # (Q, streamed) scores
    np.put_along_axis(late, s_ids, s_d, 1)
    nq, alive = len(queries), np.zeros(len(streamed), bool)
    ids, dists = [], []
    for m in range(len(ops) + 1):
        if m:
            kind, s = ops[m - 1]
            alive[s * per:(s + 1) * per] = kind == reference_live.ADD
        live = np.flatnonzero(alive)
        cand_ids = np.concatenate(
            [base_ids, np.broadcast_to(live + len(data), (nq, len(live)))],
            1)
        cand = np.concatenate([base_d, late[:, live]], 1)
        order = np.argsort(cand, axis=1, kind="stable")[:, :k]
        ids.append(np.take_along_axis(cand_ids, order, 1))
        dists.append(np.take_along_axis(cand, order, 1))
    states = np.repeat(np.arange(len(ops) + 1), nq)
    n = len(states)
    return {"query": np.tile(np.arange(nq), len(ops) + 1),
            "status": np.zeros(n, np.int64), "success_status": np.int64(0),
            "ids": np.concatenate(ids).astype(np.int64),
            "dists": np.concatenate(dists).astype(np.float32),
            "t_send": states - 0.4, "turnaround": np.full(n, 0.01),
            "latency": np.full(n, 0.1),
            "w_kind": np.asarray([o[0] for o in ops], np.int64),
            "w_step": np.asarray([o[1] for o in ops], np.int64),
            "w_rows": np.full(len(ops), per, np.int64),
            "w_t_send": np.arange(len(ops), dtype=np.float64),
            "w_latency": np.full(len(ops), 0.5),
            "w_ok": np.ones(len(ops), bool),
            "w_count": np.full(len(ops), per, np.int64),
            "w_steps_done": np.int64(steps), "w_steps_skipped": np.int64(0),
            "w_sigma": np.float64(sigma),
            "w_period_ms": np.float64(traffic["writer_period_ms"]),
            "w_window_s": np.float64(steps * traffic["writer_period_ms"]
                                     / 1e3)}


def main(argv) -> int:
    with open(os.path.join(HERE, "configs", argv[0] + ".json")) as f:
        config = json.load(f)
    with open(os.path.join(HERE, "traffic", "stream128.json")) as f:
        traffic = json.load(f)
    modes = (argv[2] if len(argv) > 2 else "highest,high").split(",")
    rows = int(argv[3]) if len(argv) > 3 else config["rows"]
    nq = config["check"]["queries"]
    make = load_by_name("datasets", config["dataset"]).make
    rule = load_by_name("checks", config["check"]["rule"])
    for seed in (int(s) for s in argv[1].split(",")):
        data, queries = make(seed, rows, config["dim"], nq)
        for mode in modes:
            record = serial_record(data, queries, config["k"], mode,
                                   traffic)
            got = rule.check(data, queries, np.arange(nq), record, config)
            print(json.dumps({
                "config": argv[0], "seed": seed, "rows": rows,
                "mode": mode,
                "correct": all(x["ok"] for x in got["numbers"]),
                "compared": {x["name"]: x["value"]
                             for x in got["numbers"]},
                "answers_compared": got["seen"]["answers_compared"],
                "answers_with_streamed_row":
                    got["seen"]["answers_with_streamed_row"],
                "dist_err_ulps_max": got["seen"]["dist_err_ulps_max"]}),
                  flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
