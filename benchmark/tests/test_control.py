"""The control at a size a test can hold: the plain reference put in the
program's place and computed in lower precision has to come out as not
correct under both rules, and the same reference at full precision as
correct.  On the chip the control is the program switched to `high`
float precision (benchmark/tools/chip_first.py) and the plain jax.numpy
scan at `high` (benchmark/tools/control_reference.py); a CPU computes
float32 whatever precision is asked, so here the lower precision is
bfloat16 inputs in numpy — one step further down the same ladder."""

import json
import os

import numpy as np
import pytest

from benchmark.harness import compare, reference
from benchmark.loadgen import load_by_name

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROWS, QUERIES, K = 20_000, 64, 10


@pytest.fixture(scope="module")
def corpus():
    return load_by_name("datasets", "clustered_f32").make(
        2**31 + 3, ROWS, 128, QUERIES)


@pytest.mark.parametrize("config_name, rule", [
    ("flat_1m_f32_l2", "exact_ids"),
    ("bkt_100k_f32_l2_dense", "recall_and_exact_dists")])
@pytest.mark.parametrize("mantissa_bits, sound", [(23, True), (7, False)])
def test_lower_precision_is_not_correct(corpus, config_name, rule,
                                        mantissa_bits, sound):
    """23 explicit mantissa bits = float32 (sound); 7 = bfloat16."""
    data, queries = corpus
    with open(os.path.join(HERE, "configs", config_name + ".json")) as f:
        config = json.load(f)
    ids, dists = reference.lower_precision_answers(data, queries, K,
                                                   mantissa_bits)
    got = load_by_name("checks", rule).check(
        data, queries, np.arange(QUERIES), compare.answers_as_window(ids, dists),
        config)
    assert all(n["ok"] for n in got["numbers"]) is sound, got["numbers"]
