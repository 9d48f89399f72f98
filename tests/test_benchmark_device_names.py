"""The benchmark's device-side names against the program itself.

tests/test_benchmark_readers.py holds the host-side names a reader takes
(spans, counters, gauges).  The `kernel.*` metrics read a chip's profile
by two more kinds of name, which nothing off the chip held: the PROGRAM a
device event belongs to (`jit_` + the `__name__` of the jitted callable:
`ENTRY` / `REST` / `PROGRAM` / `PROGRAMS` of benchmark/layer_metrics/) and
the `jax.named_scope` an operation was traced under
(benchmark/harness/scopes.py `STAGES`, `kernel.mesh_merge_ms_per_batch`'s
`MERGE`, benchmark/tools/stage_dump_beam.py `BEAM_STAGES`).  A program PR
that renames one turns a metric to null on the chip only.

The names are the benchmark's own constants, read and never edited; the
tables below say where the program keeps each, and a name the benchmark
adds without a row here fails every case of its kind.
"""

import ast
import functools
import glob
import importlib
import os

import pytest

from benchmark.harness import scopes
from benchmark.loadgen import load_by_name
from benchmark.tools import stage_dump_beam

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: program name -> the module whose jitted callable it is
PROGRAMS = {
    "jit__flat_search_kernel": "sptag_tpu.algo.flat",
    "jit__sharded_search_kernel": "sptag_tpu.parallel.sharded",
    "jit__dense_search_kernel": "sptag_tpu.algo.dense",
    "jit__dense_search_grouped_kernel": "sptag_tpu.algo.dense",
    "jit__beam_search_kernel": "sptag_tpu.algo.engine",
    "jit__beam_search_chunked": "sptag_tpu.algo.engine",
    "jit__beam_search_seeded_kernel": "sptag_tpu.algo.engine",
    "jit__beam_search_seeded_chunked": "sptag_tpu.algo.engine",
    "jit__beam_seed_kernel": "sptag_tpu.algo.engine",
    "jit__beam_seed_seeded_kernel": "sptag_tpu.algo.engine",
    "jit__beam_segment_kernel": "sptag_tpu.algo.engine",
    "jit__beam_finalize_kernel": "sptag_tpu.algo.engine",
}

#: scope prefix -> the file that traces the programs it is read in
SCOPE_FILES = {
    "flat": "sptag_tpu/algo/flat.py",
    "dense": "sptag_tpu/algo/dense.py",
    "beam": "sptag_tpu/algo/engine.py",
    "mesh": "sptag_tpu/parallel/sharded.py",
}
#: the scopes by the constant that lists them (15 in all)
SCOPES = ([("STAGES", s) for s in (
              "flat.distance", "flat.topk", "dense.centroids",
              "dense.gather", "dense.probe", "dense.mask", "dense.topk")]
          + [("MERGE", "mesh.merge")]
          # kernel.dense_mask_ms_per_batch / kernel.dense_probe_ms_per_batch
          + [("SCOPE", s) for s in ("dense.mask", "dense.probe")]
          + [("BEAM_STAGES", s) for s in (
              "beam.seed", "beam.gather", "beam.score", "beam.merge",
              "beam.finalize")])


@functools.lru_cache(maxsize=None)
def _benchmark_programs() -> frozenset:
    """Every program name a `kernel.*` reader looks a device event up by."""
    names = set()
    for path in glob.glob(os.path.join(
            REPO, "benchmark", "layer_metrics", "kernel.*.py")):
        module = load_by_name("layer_metrics",
                              os.path.basename(path)[:-len(".py")])
        for constant in ("ENTRY", "REST", "PROGRAM", "PROGRAMS"):
            value = getattr(module, constant, ())
            names.update((value,) if isinstance(value, str) else value)
    return frozenset(names)


@functools.lru_cache(maxsize=None)
def _benchmark_scopes() -> dict:
    merge = load_by_name("layer_metrics", "kernel.mesh_merge_ms_per_batch")
    one_scope = tuple(
        load_by_name("layer_metrics", name).SCOPE
        for name in ("kernel.dense_mask_ms_per_batch",
                     "kernel.dense_probe_ms_per_batch"))
    # a one-scope reader takes its seconds from scopes.read_stages
    assert set(one_scope) <= set(scopes.STAGES)
    return {"STAGES": tuple(scopes.STAGES), "MERGE": (merge.MERGE,),
            "SCOPE": one_scope,
            "BEAM_STAGES": tuple(stage_dump_beam.BEAM_STAGES)}


@functools.lru_cache(maxsize=None)
def _named_scopes(path: str) -> frozenset:
    """The string literals `path` hands to `jax.named_scope`."""
    with open(os.path.join(REPO, path), encoding="utf-8") as f:
        tree = ast.parse(f.read())
    return frozenset(
        node.args[0].value for node in ast.walk(tree)
        if isinstance(node, ast.Call)
        and isinstance(node.func, ast.Attribute)
        and node.func.attr == "named_scope"
        and isinstance(node.func.value, ast.Name)
        and node.func.value.id == "jax"
        and node.args and isinstance(node.args[0], ast.Constant))


@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_a_program_the_benchmark_reads_is_a_jitted_callable(program):
    assert _benchmark_programs() == set(PROGRAMS)
    assert program.startswith("jit_")
    module = importlib.import_module(PROGRAMS[program])
    fn = getattr(module, program[len("jit_"):], None)
    assert fn is not None, f"{PROGRAMS[program]} has no {program[4:]}"
    assert fn.__name__ == program[len("jit_"):]
    assert callable(fn) and hasattr(fn, "lower")        # a jax.jit wrapper


def test_jax_names_a_program_jit_and_the_callables_name():
    """The rule the table above rests on, held to the installed jax: a
    jitted callable's program is `jit_` + its `__name__`."""
    import jax

    @jax.jit
    def _some_kernel(x):
        return x + 1

    text = _some_kernel.lower(1.0).as_text()
    assert "module @jit__some_kernel" in text


@pytest.mark.parametrize("constant,scope", SCOPES)
def test_a_scope_the_benchmark_reads_is_a_named_scope_of_its_module(
        constant, scope):
    listed = _benchmark_scopes()
    assert {c: set(v) for c, v in listed.items()} == {
        c: {s for k, s in SCOPES if k == c} for c in listed}
    assert scope in listed[constant]
    path = SCOPE_FILES[scope.split(".")[0]]
    assert scope in _named_scopes(path), f"{path} traces no {scope!r}"
