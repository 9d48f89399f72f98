"""A trip of the walk fetches its candidates' vectors as blocks (PR 45).

Under the packed-neighbour layout (`BeamPackedNeighbors`) `engine.
_walk_machine`'s exact body fetches by POPPED NODE, Q x B blocks of
(m, D), scores them in the graph's order and carries the scores through
the one sort by id as its payload, so that everything after the fetch is
the sorted-id ensemble the row layout runs (`_sorted_fresh`).  The engine
takes the layout where the table fits the device (`packed_layout_fits`)
and builds the table at its first walk.  Held here: what the traced body
fetches and how; the rule over the sizes the issue names; `auto` off a
TPU is the row layout; an index that never walks builds no table; the
counters; the three drivers bit for bit with the table on.
"""

import numpy as np
import pytest

import sptag_tpu as sp
from benchmark.loadgen import load_by_name
from sptag_tpu.algo import engine
from sptag_tpu.core.types import DistCalcMethod
from sptag_tpu.utils import devmem, metrics
from tests.test_beam_sorted_order import body_equations

K = 10
V5E_BYTES = 15 * 2**30 + 3 * 2**28     # 15.75 GiB of a v5e's 16 GB of HBM


# ---- (1) what the traced body fetches -------------------------------------

@pytest.mark.parametrize("packed", [True, False])
def test_the_vectors_come_as_blocks_by_popped_node(packed):
    """The vector fetch of the exact body: under the packed layout ONE
    gather from the (N, m, D) table with Q x B indices and slice
    (1, m, D), and none from the (N, D) rows; under the row layout one
    from the rows with Q x B x m indices and slice (1, D)."""
    (Q, B, m, D, N), eqns = body_equations(packed)
    blocks, rows = [], []
    for eqn, scope in eqns:
        if eqn.primitive.name != "gather" or "beam.gather" not in scope:
            continue
        operand, idx = eqn.invars[0].aval, eqn.invars[1].aval
        if operand.shape == (N, m, D):
            blocks.append((idx.shape[:-1], tuple(eqn.params["slice_sizes"])))
        elif operand.shape == (N, D):
            rows.append((idx.shape[:-1], tuple(eqn.params["slice_sizes"])))
    if packed:
        assert blocks == [((Q, B), (1, m, D))] and rows == []
    else:
        assert rows == [((Q, B * m), (1, D))] and blocks == []


def test_the_packed_body_holds_one_wide_element_gather():
    """Of X = B x m wide ELEMENT gathers (one word or one float a
    candidate) the packed exact body holds one, of `visited` words: no
    score, no mask and no id is carried back to the graph's order.  No
    argsort's positions and no X-wide scatter (the inverse permutation)
    either; the one sort and its operands are held in
    `tests/test_beam_sorted_order.py`."""
    (Q, B, m, D, N), eqns = body_equations(True)
    X, W = B * m, engine._num_words(N)
    wide = [eqn.invars[0].aval.shape for eqn, _ in eqns
            if eqn.primitive.name == "gather"
            and eqn.outvars[0].aval.shape == (Q, X)]
    assert wide == [(Q, W)]
    for eqn, _ in eqns:                 # no positions made, none undone
        if eqn.primitive.name == "scatter":
            assert eqn.invars[0].aval.shape != (Q, X)
        assert eqn.primitive.name != "iota" \
            or eqn.outvars[0].aval.shape != (Q, X)


def test_the_binned_body_keeps_its_positional_ensemble():
    """`merge_bins` > 0 is not touched: no X-wide sort, the `visited`
    test by position, the blocks fetched the same way."""
    (Q, B, m, D, N), eqns = body_equations(True, merge_bins=128)
    X = B * m
    assert not [e for e, _ in eqns if e.primitive.name == "sort"
                and e.outvars[0].aval.shape == (Q, X)]
    assert [tuple(e.params["slice_sizes"]) for e, _ in eqns
            if e.primitive.name == "gather"
            and e.invars[0].aval.shape == (N, m, D)] == [(1, m, D)]


# ---- (2) the rule ----------------------------------------------------------

@pytest.mark.parametrize("n,m,dim,itemsize,device_bytes,in_use,fits", [
    (100_000, 32, 128, 2, V5E_BYTES, 0, True),      # the beam cell: 0.82 GB
    (200_000, 32, 128, 2, V5E_BYTES, 0, True),      # chip_smoke's: 1.64 GB
    (1_000_000, 32, 128, 2, V5E_BYTES, 0, False),   # the source's: 8.2 GB
    (200_000, 32, 128, 4, V5E_BYTES, 0, False),     # float32 scoring: 3.3 GB
    (100_000, 32, 128, 2, None, 0, False),          # no limit stated: rows
    (100_000, 32, 128, 2, 0, 0, False),
    (100_000, 32, 128, 2, 8 * 819_200_000, 0, True),    # the eighth, exactly
    (100_000, 32, 128, 2, 8 * 819_200_000 - 8, 0, False),
    # half of what is free: the 0.82 GB table beside 15 GiB of other
    # indexes' blocks (0.75 GiB free) is rows, beside 14 GiB it fits
    (100_000, 32, 128, 2, V5E_BYTES, 15 * 2**30, False),
    (100_000, 32, 128, 2, V5E_BYTES, 14 * 2**30, True),
    (100_000, 32, 128, 2, V5E_BYTES, V5E_BYTES - 2 * 819_200_000, True),
    (100_000, 32, 128, 2, V5E_BYTES, V5E_BYTES - 2 * 819_200_000 + 2, False),
])
def test_packed_layout_fits(n, m, dim, itemsize, device_bytes, in_use, fits):
    assert engine.packed_layout_fits(n, m, dim, itemsize, device_bytes,
                                     in_use) is fits


@pytest.mark.parametrize("value,asks", [
    ("auto", "auto"), (" AUTO ", "auto"), ("1", True), (1, True),
    # the default until PR 45, in every folder saved before: the rule's
    ("0", "auto"), (0, "auto")])
def test_the_parameters_values(value, asks):
    assert engine.packed_param(value) == asks


@pytest.mark.parametrize("value", ["2", "on", "", "yes", "True"])
def test_the_parameter_refuses_other_values(value):
    with pytest.raises(ValueError, match="BeamPackedNeighbors"):
        engine.packed_param(value)
    with pytest.raises(ValueError, match="packed_neighbors"):
        _tiny_engine(packed_neighbors=value)


def _tiny_engine(**kw):
    rng = np.random.default_rng(45)
    data = rng.standard_normal((300, 16)).astype(np.float32)
    graph = rng.integers(0, 300, (300, 8)).astype(np.int32)
    return engine.GraphSearchEngine(
        data, graph, np.arange(0, 300, 10, dtype=np.int32), None,
        DistCalcMethod.L2, 1, **kw), data


def _settled(eng):
    """The layout `eng`'s first walk settles on."""
    eng.walk_table()
    return eng.packed


def test_auto_off_a_tpu_is_the_row_layout(monkeypatch):
    assert engine._device_memory() == (None, 0)         # the suite's CPU
    eng, data = _tiny_engine()                          # the default: auto
    assert eng.packed is None                           # nobody has walked
    eng.search(data[:4], 5, max_check=64)
    assert eng.packed is False and eng.nbr_vecs is None
    # the same engine where the device states room: the table's layout,
    # asked when the first walk is (what is resident THEN counts)
    monkeypatch.setattr(engine, "_device_memory", lambda: (V5E_BYTES, 0))
    eng, _ = _tiny_engine()
    monkeypatch.setattr(engine, "_device_memory", lambda: (V5E_BYTES, 2**30))
    assert _settled(eng) and eng.nbr_vecs is not None
    assert _settled(_tiny_engine(packed_neighbors=False)[0]) is False
    table = 300 * 8 * 16 * 4
    monkeypatch.setattr(engine, "_device_memory",
                        lambda: (V5E_BYTES, V5E_BYTES - table))
    assert not _settled(_tiny_engine()[0])              # no room left
    assert _settled(_tiny_engine(packed_neighbors=True)[0])     # obeyed
    assert eng.walk_table() is not None                 # settled once


def test_the_cascade_tier_supersedes_the_table(monkeypatch):
    monkeypatch.setattr(engine, "_device_memory", lambda: (V5E_BYTES, 0))
    for asked in ("auto", True):
        eng, data = _tiny_engine(packed_neighbors=asked,
                                 cascade_search=True)
        assert eng.cascade and eng.packed is False
        eng.search(data[:4], 5, max_check=64)
        assert eng.nbr_vecs is None


def test_the_table_is_built_by_the_first_walk_and_holds_the_scored_rows():
    eng, data = _tiny_engine(packed_neighbors=True, score_dtype="bf16")
    assert eng.packed and eng.nbr_vecs is None
    eng.exact_scan(data[:4], 5)                         # no walk: no table
    assert eng.nbr_vecs is None
    eng.search(data[:4], 5, max_check=64)
    table = eng.nbr_vecs
    assert table.shape == (300, 8, 16) and str(table.dtype) == "bfloat16"
    assert np.array_equal(
        np.asarray(table.astype(np.float32)),
        np.asarray(eng.data_score.astype(np.float32))[np.asarray(eng.graph)])
    eng.search(data[:4], 5, max_check=64)
    assert eng.nbr_vecs is table                        # built once


# ---- (3) an index: the parameter, the dense mode, the counters -------------

@pytest.fixture(scope="module")
def folder(tmp_path_factory):
    data, queries = load_by_name("datasets", "clustered_f32").make(
        2**31 + 45, 2000, 32, 32)
    index = sp.create_instance("BKT", "Float")
    for name, value in [("DistCalcMethod", "L2"), ("BKTNumber", "1"),
                        ("BKTKmeansK", "32"), ("TPTNumber", "4"),
                        ("TPTLeafSize", "500"), ("NeighborhoodSize", "32"),
                        ("CEF", "64"), ("MaxCheckForRefineGraph", "128"),
                        ("RefineIterations", "1"),
                        ("FinalRefineSearchMode", "same")]:
        assert index.set_parameter(name, value)
    assert index.get_parameter("BeamPackedNeighbors") == "auto"
    index.build(data)
    path = str(tmp_path_factory.mktemp("block_fetch") / "index")
    index.save_index(path)
    index.close()
    return path, queries


def _packed_bytes():
    return devmem.component_bytes().get("packed_neighbors", 0)


def test_a_saved_folder_carries_the_parameters_line(folder, monkeypatch):
    """`save_config` writes every parameter: a folder saved by this code
    says `auto`; one saved before PR 45 says 0, the default then, and is
    read as `auto`: the rule decides for it too.  1 insists."""
    path, queries = folder
    with open(path + "/indexloader.ini") as fh:
        assert "BeamPackedNeighbors=auto\n" in fh.read()
    index = sp.load_index(path)
    assert index.get_parameter("BeamPackedNeighbors") == "auto"
    assert index.set_parameter("SearchMode", "beam")
    assert index.set_parameter("BeamPackedNeighbors", "0")
    index.search_batch(queries, K)
    assert index._get_engine().packed is False          # the CPU: rows
    assert index.set_parameter("BeamPackedNeighbors", "1")
    index.search_batch(queries, K)
    assert index._get_engine().packed is True
    monkeypatch.setattr(engine, "_device_memory", lambda: (V5E_BYTES, 0))
    assert index.set_parameter("BeamPackedNeighbors", "0")
    index.search_batch(queries, K)
    assert index._get_engine().packed is True           # room: the table
    index.close()


def test_an_engine_made_to_link_or_refine_walks_rows(folder):
    """`_make_engine(serving=False)`: the engines an index makes to link
    a delta or refine a graph and then drops never gather a table."""
    path, queries = folder
    index = sp.load_index(path)
    assert index.set_parameter("BeamPackedNeighbors", "1")
    before = _packed_bytes()
    eng = index._make_engine(index._graph.graph, serving=False)
    eng.search(queries, K, max_check=64)
    assert eng.packed is False and _packed_bytes() == before
    assert index._make_engine(index._graph.graph).packed is True
    index.close()


def test_a_dense_mode_index_builds_no_table(folder):
    path, queries = folder
    index = sp.load_index(path)
    assert index.get_parameter("SearchMode") == "dense"
    assert index.set_parameter("BeamPackedNeighbors", "1")
    before = _packed_bytes()
    index.search_batch(queries, K)
    eng = index._get_engine()           # the dense refresh makes an engine
    assert eng.packed and eng.nbr_vecs is None
    assert _packed_bytes() == before
    # the same index asked to walk: the table, once
    assert index.set_parameter("SearchMode", "beam")
    index.search_batch(queries, K)
    assert index._get_engine() is eng and eng.nbr_vecs is not None
    assert _packed_bytes() - before == 2000 * 32 * 32 * 4
    index.close()


@pytest.fixture(scope="module")
def packed_engine(folder):
    path, queries = folder
    index = sp.load_index(path)
    assert index.set_parameter("BeamPackedNeighbors", "1")
    assert index.set_parameter("SearchMode", "beam")
    rows = sp.load_index(path)
    yield index._get_engine(), rows._get_engine(), queries
    index.close()
    rows.close()


@pytest.mark.parametrize("max_check", [256, 2048])
def test_the_three_drivers_agree_with_the_table_on(packed_engine,
                                                   monkeypatch, max_check):
    """Monolithic, segmented and chunked trace ONE body: bit for bit the
    same answers with the table on, and the row layout's; every batch is
    counted once by the way it fetched."""
    eng, rows_eng, queries = packed_engine
    metrics.reset()
    d_m, ids_m = eng.search(queries, K, max_check=max_check)
    d_s, ids_s = eng.search(queries, K, max_check=max_check,
                            segment_iters=3)
    # 16 queries a program: two chunks under one lax.map
    monkeypatch.setattr(engine, "_VISITED_BUDGET", 16 * (eng.n // 8))
    assert eng.chunk_size() == 16
    d_c, ids_c = eng.search(queries, K, max_check=max_check)
    B, m = eng.walk_plan(K, max_check)[2], 32
    assert metrics.gauge_value("beam.fetches_per_trip") == 16 * B
    d_r, ids_r = rows_eng.search(queries, K, max_check=max_check)
    assert metrics.gauge_value("beam.fetches_per_trip") == 16 * B * m
    for d, ids in ((d_s, ids_s), (d_c, ids_c), (d_r, ids_r)):
        assert np.array_equal(ids, ids_m) and np.array_equal(d, d_m)
    drivers = [metrics.counter_value("beam." + name)
               for name in ("monolithic", "segmented", "chunked")]
    assert drivers == [1, 1, 2]
    assert metrics.counter_value("beam.fetch_blocks") == 3
    assert metrics.counter_value("beam.fetch_rows") == 1
    assert metrics.counter_value("beam.dedup_sorted") == sum(drivers)
    assert metrics.counter_value("beam.dedup_positional") == 0


def test_the_slot_scheduler_walks_the_table_too(folder):
    """Continuous batching runs `run_segment`: the same body, the same
    table, the same answers."""
    path, queries = folder
    index = sp.load_index(path)
    for name, value in [("BeamPackedNeighbors", "1"),
                        ("SearchMode", "beam"), ("MaxCheck", "256")]:
        assert index.set_parameter(name, value)
    d_m, ids_m = index.search_batch(queries, K)
    assert index.set_parameter("ContinuousBatching", "1")
    d_s, ids_s = index.search_batch(queries, K)
    assert index._get_engine().nbr_vecs is not None
    assert np.array_equal(ids_s, ids_m) and np.array_equal(d_s, d_m)
    index.close()
