"""The mesh FLAT index through the normal path (ISSUE 27): builder CLI with
`Index.MeshShardAxis=N` -> mesh folder -> `load_index` -> `ServingAdapter`
-> `SearchServer`, against an exact scan that imports nothing of
`parallel/`.

96-d seeded rows, a row count that no shard count divides evenly, deleted
rows in the shard folders, one neighbour set that lies wholly in one shard
and one that spans all four.  Small meshes over the suite's forced CPU
devices (`host_mesh`); the four-shard folder is built once a module.
"""

import hashlib
import json
import os
import socket

import numpy as np
import pytest

import jax

import sptag_tpu as sp
from conftest import ServerThread
from sptag_tpu.core.types import DistCalcMethod
from sptag_tpu.serve import wire
from sptag_tpu.serve.server import SearchServer
from sptag_tpu.serve.service import ServiceContext
from sptag_tpu.tools import index_builder
from sptag_tpu.utils import metrics, recompile_guard, trace

N, D, K = 1003, 96, 10
ONE_SHARD_QUERY, ALL_SHARDS_QUERY = 0, 1
DELETED = (5, 300, 301, 777, 1002)       # rows of shards 0, 1, 1, 3, 3 of 4
TINY_BKT = ["Index.BKTNumber=1", "Index.BKTKmeansK=4", "Index.TPTNumber=2",
            "Index.TPTLeafSize=32", "Index.NeighborhoodSize=8",
            "Index.CEF=16", "Index.MaxCheckForRefineGraph=64",
            "Index.RefineIterations=1", "Index.MaxCheck=128",
            "Index.SearchMode=beam"]


def rows_per_shard(n, shards):
    return -(-max(n, shards) // (shards * 8)) * 8


def corpus():
    """-> (rows, queries).  Query 0's ten nearest rows all lie in shard 2
    of 4, query 1's in all four (three a shard, the nearest ten of them
    taken), both planted at distances 0.05, 0.10, ... so that no two tie;
    one planted row of each set is among DELETED's neighbours below."""
    rng = np.random.default_rng(27)
    data = rng.standard_normal((N, D)).astype(np.float32)
    queries = rng.standard_normal((40, D)).astype(np.float32)
    n_local = rows_per_shard(N, 4)
    unit = rng.standard_normal((24, D)).astype(np.float32)
    unit /= np.linalg.norm(unit, axis=1, keepdims=True)
    for j in range(12):
        data[2 * n_local + 7 * j + 3] = \
            queries[ONE_SHARD_QUERY] + 0.05 * (j + 1) * unit[j]
        data[(j % 4) * n_local + 11 * (j // 4) + 40] = \
            queries[ALL_SHARDS_QUERY] + 0.05 * (j + 1) * unit[12 + j]
    return data, queries


def exact_scan(data, deleted, queries, k):
    """The plain reference: float64 squared L2 of every row, deleted rows
    left out, nearest first.  Nothing of the program."""
    d = ((queries.astype(np.float64)[:, None, :]
          - data.astype(np.float64)[None, :, :]) ** 2).sum(-1)
    d[:, list(deleted)] = np.inf
    ids = np.argsort(d, axis=1, kind="stable")[:, :k]
    return ids, np.take_along_axis(d, ids, axis=1)


def write_bin(path, array):
    with open(path, "wb") as f:
        f.write(np.asarray(array.shape, "<i4").tobytes())
        array.tofile(f)


def build_cli(tmp, data, algo, *params):
    """BIN file -> `index_builder.main` -> the saved folder."""
    bin_path = os.path.join(tmp, "vectors.bin")
    write_bin(bin_path, data)
    folder = os.path.join(tmp, "index")
    rc = index_builder.main(
        ["-d", str(data.shape[1]), "-v", "Float", "-i", f"BIN:{bin_path}",
         "-o", folder, "-a", algo, "Index.DistCalcMethod=L2", *params])
    assert rc == 0
    return folder


def mesh_folder(tmp, data, shards):
    """A mesh FLAT folder from the CLI, then DELETED tombstoned in the
    shard folders that hold them, as a Server deletes from its own
    partition."""
    folder = build_cli(str(tmp), data, "FLAT", f"Index.MeshShardAxis={shards}")
    n_local = rows_per_shard(len(data), shards)
    for s in sorted({row // n_local for row in DELETED}):
        path = os.path.join(folder, f"shard_{s:03d}")
        sub = sp.load_index(path)
        mine = [row for row in DELETED if row // n_local == s]
        assert sub.delete(data[mine]) == sp.ErrorCode.Success
        assert sub.num_deleted == len(mine)
        assert sub.save_index(path) == sp.ErrorCode.Success
    return folder


@pytest.fixture(scope="module")
def four(tmp_path_factory):
    """-> (rows, queries, the four-shard folder, its loaded adapter, the
    reference's (ids, float64 distances))."""
    data, queries = corpus()
    folder = mesh_folder(tmp_path_factory.mktemp("mesh4"), data, 4)
    adapter = sp.load_index(folder)
    return data, queries, folder, adapter, \
        exact_scan(data, DELETED, queries, K)


def same_as_reference(got, want):
    dists, ids = got
    assert np.array_equal(ids, want[0])
    np.testing.assert_allclose(dists, want[1], rtol=1e-4, atol=1e-3)


# ---------------------------------------------------------------- (a) ----

def test_cli_writes_a_mesh_folder_with_its_family(four):
    _, _, folder, adapter, _ = four
    from sptag_tpu.parallel.sharded import ServingAdapter, ShardedFlatIndex

    with open(os.path.join(folder, "sharded.json")) as f:
        manifest = json.load(f)
    assert manifest == {"n_shards": 4, "n": N, "dim": D,
                        "metric": int(DistCalcMethod.L2),
                        "value_type": int(sp.VectorValueType.Float),
                        "algo": "FLAT"}
    assert sorted(os.listdir(folder)) == [
        "shard_000", "shard_001", "shard_002", "shard_003", "sharded.json"]
    # the last partition is the short one; padding exists on the device only
    assert [sp.load_index(os.path.join(folder, f"shard_{s:03d}")).num_samples
            for s in range(4)] == [256, 256, 256, N - 768]
    assert isinstance(adapter, ServingAdapter)
    assert isinstance(adapter._impl, ShardedFlatIndex)
    assert adapter.num_samples == N and adapter.feature_dim == D
    assert adapter._impl.mesh.devices.size == 4
    assert adapter._impl.data.shape == (1024, D)


def test_four_shards_answer_as_the_exact_scan(four):
    _, queries, _, adapter, want = four
    got = adapter.search_batch(queries, K)
    same_as_reference(got, want)
    n_local = rows_per_shard(N, 4)
    assert set(got[1][ONE_SHARD_QUERY] // n_local) == {2}
    assert set(got[1][ALL_SHARDS_QUERY] // n_local) == {0, 1, 2, 3}
    assert not set(DELETED) & set(got[1].ravel().tolist())


def test_two_shards_answer_as_the_exact_scan(tmp_path):
    data, queries = corpus()
    adapter = sp.load_index(mesh_folder(tmp_path, data, 2))
    assert adapter._impl.mesh.devices.size == 2
    same_as_reference(adapter.search_batch(queries, K),
                      exact_scan(data, DELETED, queries, K))


def test_a_deleted_row_queried_as_itself_is_not_returned(four):
    data, _, _, adapter, _ = four
    rows = np.asarray(DELETED)
    _, ids = adapter.search_batch(data[rows], K)
    assert not set(DELETED) & set(ids.ravel().tolist())
    want, _ = exact_scan(data, DELETED, data[rows], K)
    assert np.array_equal(ids, want)


def test_k_beyond_the_corpus_pads_with_sentinels(tmp_path):
    rng = np.random.default_rng(3)
    data = rng.standard_normal((37, D)).astype(np.float32)
    adapter = sp.load_index(
        build_cli(str(tmp_path), data, "FLAT", "Index.MeshShardAxis=2"))
    dists, ids = adapter.search_batch(data[:2], 64)
    assert dists.shape == ids.shape == (2, 64)
    assert sorted(ids[0][ids[0] >= 0].tolist()) == list(range(37))
    assert (ids[:, 37:] == -1).all()


def test_fewer_devices_than_shards_is_a_value_error(four, tmp_path):
    _, _, folder, _, _ = four
    with open(os.path.join(folder, "sharded.json")) as f:
        manifest = json.load(f)
    manifest["n_shards"] = len(jax.devices()) + 1
    os.makedirs(tmp_path / "m")
    with open(tmp_path / "m" / "sharded.json", "w") as f:
        json.dump(manifest, f)
    with pytest.raises(ValueError, match="devices"):
        sp.load_index(str(tmp_path / "m"))


def test_an_empty_partition_is_refused_at_build(tmp_path):
    data = np.ones((9, D), np.float32)
    bin_path = str(tmp_path / "v.bin")
    write_bin(bin_path, data)
    with pytest.raises(ValueError, match="empty"):
        index_builder.main(
            ["-d", str(D), "-v", "Float", "-i", f"BIN:{bin_path}", "-o",
             str(tmp_path / "index"), "-a", "FLAT", "Index.MeshShardAxis=4"])


# ---------------------------------------------------------------- (b), (e)

def burst(sock, texts) -> list:
    """len(texts) requests in one send -> as many results, by resource id."""
    out = b""
    for rid, text in enumerate(texts):
        body = wire.RemoteQuery(text).pack()
        out += wire.PacketHeader(wire.PacketType.SearchRequest,
                                 wire.PacketProcessStatus.Ok, len(body), 0,
                                 rid).pack() + body
    sock.sendall(out)
    got, buf = {}, b""
    while len(got) < len(texts):
        chunk = sock.recv(1 << 20)
        assert chunk, "server closed the connection"
        buf += chunk
        while len(buf) >= wire.HEADER_SIZE:
            head = wire.PacketHeader.unpack(buf[:wire.HEADER_SIZE])
            end = wire.HEADER_SIZE + head.body_length
            if len(buf) < end:
                break
            got[head.resource_id] = wire.RemoteSearchResult.unpack(
                buf[wire.HEADER_SIZE:end])
            buf = buf[end:]
    return [got[rid] for rid in range(len(texts))]


@pytest.fixture(scope="module")
def served(four, tmp_path_factory):
    """The four-shard folder behind a SearchServer configured from an ini
    (`ServiceContext.from_ini` -> `load_index`), 40 queries in flight on
    one connection -> the answers, the span report and the gauges."""
    _, queries, folder, _, _ = four
    trace.reset()
    metrics.reset()
    ini = str(tmp_path_factory.mktemp("serve") / "mesh.ini")
    with open(ini, "w") as f:
        f.write("[Service]\nListenAddr=127.0.0.1\nListenPort=0\n"
                f"[QueryConfig]\nDefaultMaxResultNumber={K}\n"
                "[Index]\nList=mesh\n"
                f"[Index_mesh]\nIndexFolder={folder}\n")
    ctx = ServiceContext.from_ini(ini)
    assert "mesh" in ctx.indexes
    # a batch is full at 40, so the burst is one batch whatever the window
    thread = ServerThread(SearchServer(ctx, batch_window_ms=300.0,
                                       max_batch=40))
    thread.start()
    host, port = thread.wait_ready()
    texts = ["$indexname:mesh " + "|".join(repr(float(x)) for x in q)
             for q in queries]
    try:
        with socket.create_connection((host, port), timeout=60) as sock:
            sock.settimeout(60)
            answers = burst(sock, texts)
    finally:
        thread.stop()
    snap = metrics.snapshot()
    return {"answers": answers, "spans": trace.report(),
            "gauges": dict(snap["gauges"]),
            "counters": dict(snap["counters"])}


def test_forty_queries_in_flight_get_the_direct_answers(four, served):
    _, queries, _, adapter, want = four
    dists, ids = adapter.search_batch(queries, K)
    assert len(served["answers"]) == 40
    for row, answer in enumerate(served["answers"]):
        assert answer.status == wire.ResultStatus.Success
        result = answer.results[0]
        assert list(result.ids) == ids[row].tolist() == want[0][row].tolist()
        np.testing.assert_array_equal(
            np.asarray(result.dists, np.float32), dists[row])
    # every future was resolved when submit_batch returned: PR 26's path
    assert served["counters"]["service.batched_results"] == 40
    assert "service.streamed_results" not in served["counters"]


def test_a_served_batch_opens_search_and_readback_and_sets_the_gauges(served):
    spans, gauges = served["spans"], served["gauges"]
    assert spans["server.execute_batch"]["count"] == 1
    assert spans["index.search"]["count"] == 1
    assert spans["index.readback"]["count"] == 1
    assert spans["index.readback"]["total_s"] \
        <= spans["index.search"]["total_s"] \
        <= spans["server.execute_batch"]["total_s"]
    assert gauges["mesh.shards"] == 4
    assert gauges["mesh.rows_per_shard"] == rows_per_shard(N, 4) == 256


# ---------------------------------------------------------------- (c) ----

def test_every_batch_size_runs_its_rung_of_the_one_ladder(four):
    from sptag_tpu.algo.flat import _QUERY_BUCKETS
    from sptag_tpu.parallel.sharded import _sharded_search_kernel

    data, _, _, adapter, _ = four
    sizes = (1, 3, 9, 33, 100)             # rungs 1, 8, 32, 128, 128
    assert _QUERY_BUCKETS[:4] == (1, 8, 32, 128)
    jax.clear_caches()
    programs = _sharded_search_kernel._cache_size()
    compiled = []
    for q in sizes:
        with recompile_guard.track_compiles(f"mesh.q{q}") as log:
            _, ids = adapter.search_batch(data[100:100 + q], 3)
        assert ids.shape == (q, 3)
        compiled.append(log.count)
    assert all(c <= 1 for c in compiled[:4]) and compiled[4] == 0, compiled
    assert _sharded_search_kernel._cache_size() - programs == 4
    with recompile_guard.no_recompiles("mesh.second-pass") as again:
        for q in sizes:
            adapter.search_batch(data[200:200 + q], 3)
    assert again.count == 0
    assert _sharded_search_kernel._cache_size() - programs == 4


# ---------------------------------------------------------------- (d) ----

def test_the_shards_own_answers_merged_on_the_host_are_the_whole(four):
    """The share ties to the whole: every shard folder loaded alone (a
    one-chip FLAT index, nothing of the mesh), its local top-k lifted to
    global row ids and merged on the host, is the mesh program's answer
    and the reference's."""
    _, queries, folder, adapter, want = four
    n_local = rows_per_shard(N, 4)
    cand_d, cand_i = [], []
    for s in range(4):
        sub = sp.load_index(os.path.join(folder, f"shard_{s:03d}"))
        d, i = sub.search_batch(queries, K)
        cand_d.append(d)
        cand_i.append(np.where(i >= 0, i + s * n_local, -1))
    cand_d, cand_i = np.concatenate(cand_d, 1), np.concatenate(cand_i, 1)
    order = np.argsort(cand_d, axis=1, kind="stable")[:, :K]
    merged = (np.take_along_axis(cand_d, order, 1),
              np.take_along_axis(cand_i, order, 1))
    same_as_reference(merged, want)
    dists, ids = adapter.search_batch(queries, K)
    assert np.array_equal(ids, merged[1])
    np.testing.assert_allclose(dists, merged[0], rtol=1e-6, atol=1e-5)


def test_mesh_program_is_the_one_chip_scan_plus_a_named_merge(host_mesh):
    """One scan body: the shard-local stages carry the one-chip program's
    scope names, the collective its own; the outputs are named."""
    import jax.numpy as jnp

    from sptag_tpu.algo import flat
    from sptag_tpu.parallel import sharded

    assert sharded.scan_topk is flat.scan_topk
    S = jax.ShapeDtypeStruct
    lowered = sharded._sharded_search_kernel.lower(
        S((64, D), jnp.float32), S((64,), jnp.float32), S((64,), jnp.bool_),
        S((8, D), jnp.float32), k_local=5, k_final=5,
        metric=int(DistCalcMethod.L2), base=1, mesh=host_mesh(2))
    text = lowered.as_text(debug_info=True)
    for scope in ("flat.distance/dot_general", "flat.topk/top_k",
                  "mesh.merge/all_gather", "mesh.merge/top_k"):
        assert scope in text, scope
    assert "merged_dists" in text and "global_ids" in text


def test_a_wide_shard_selects_in_two_stages_and_is_exact(host_mesh,
                                                         bench_reference):
    """ISSUE 28: a shard wide enough for the two-stage select (its device
    block padded past the rows it stands for, as the one-chip snapshot
    is), deleted rows among the true neighbours, global ids by the rows a
    shard stands for — against the benchmark's plain numpy scan."""
    from sptag_tpu.algo import flat
    from sptag_tpu.parallel.sharded import ShardedFlatIndex

    rng = np.random.default_rng(28)
    n, dim = 2 * 128_100 + 11, 8
    data = rng.standard_normal((n, dim)).astype(np.float32)
    queries = data[rng.integers(0, n, 6)] + np.float32(0.02)
    nearest, _ = bench_reference.exact_topk(data, queries, K)
    deleted = np.zeros(n, bool)
    deleted[nearest[:, 0]] = True
    deleted[n - 1] = True
    kept = np.flatnonzero(~deleted)
    want_ids, want_d = bench_reference.exact_topk(data[kept], queries, K)

    index = ShardedFlatIndex(data, DistCalcMethod.L2, base=1,
                             mesh=host_mesh(2), deleted=deleted)
    stride = rows_per_shard(n, 2)
    assert index.row_stride == stride and stride % 128
    n_slot = index.data.shape[0] // 2
    assert n_slot == -(-stride // 128) * 128
    assert flat.select_stages(8, n_slot, K) == 2
    assert metrics.gauge_value("mesh.rows_per_shard") == stride
    before = metrics.counter_value("flat.select_two_stage")
    dists, ids = index.search(queries, K)
    assert metrics.counter_value("flat.select_two_stage") == before + 1
    assert np.array_equal(ids, kept[want_ids])
    assert set(ids.ravel() // stride) == {0, 1}
    np.testing.assert_allclose(dists, want_d, rtol=1e-4, atol=1e-3)


def test_an_int8_cosine_shard_takes_the_fused_scan_and_is_exact(host_mesh):
    """PR 37: `scan_topk` is the mesh program's scan body too, so a shard
    of one-byte cosine rows takes its group minima from the Pallas scan
    (interpret mode here) and scores the chosen groups again - the answers
    of the materialised route bit for bit, deleted rows and the padded
    block's tail among them, and the counter says which route ran."""
    from sptag_tpu.algo import flat
    from sptag_tpu.ops import pallas_kernels
    from sptag_tpu.parallel.sharded import ShardedFlatIndex

    rng = np.random.default_rng(37)
    n, dim, k = 2 * 12_900 + 11, 128, 1
    data = rng.integers(-127, 128, (n, dim)).astype(np.int8)
    queries = data[rng.integers(0, n, 128)]
    deleted = np.zeros(n, bool)
    deleted[rng.integers(0, n, 300)] = True
    index = ShardedFlatIndex(data, DistCalcMethod.Cosine, base=127,
                             mesh=host_mesh(2), deleted=deleted,
                             normalized=True)
    n_slot = index.data.shape[0] // 2
    assert flat.fused_minima(data.dtype, 128, n_slot, dim, k,
                             int(DistCalcMethod.Cosine), "interpret")
    want = index.search(queries, k, normalized=True)
    assert metrics.counter_value("flat.scan_materialized") == 1
    pallas_kernels.set_interpret(True)
    try:
        got = index.search(queries, k, normalized=True)
    finally:
        pallas_kernels.set_interpret(False)
    assert metrics.counter_value("flat.scan_fused_minima") == 1
    assert np.array_equal(got[0], want[0])
    assert np.array_equal(got[1], want[1])
    assert set(got[1].ravel() // index.row_stride) == {0, 1}
    assert not deleted[got[1].ravel()].any()


@pytest.mark.parametrize("dim", [96, 128])
def test_a_float_l2_shard_takes_the_proved_scan(host_mesh, dim):
    """PR 41: a shard of float32 rows under L2 takes its group minima
    from the Pallas scan too (interpret mode here; 96 columns: the
    deep-10M cell's, read column-major), proves its selection per shard
    and answers the ids the materialised mesh program answers, deleted
    rows and the padded block's tail among them; the flag comes back
    replicated and nothing is counted unproved.  Rows hold small whole
    numbers, so both routes' distances are one number a row."""
    from sptag_tpu.algo import flat
    from sptag_tpu.ops import pallas_kernels
    from sptag_tpu.parallel.sharded import ShardedFlatIndex

    rng = np.random.default_rng(41 + dim)
    n, k = 2 * 12_900 + 11, 1
    data = rng.integers(-60, 61, (n, dim)).astype(np.float32)
    queries = (data[rng.integers(0, n, 128)]
               + rng.integers(-2, 3, (128, dim))).astype(np.float32)
    deleted = np.zeros(n, bool)
    deleted[rng.integers(0, n, 300)] = True
    index = ShardedFlatIndex(data, DistCalcMethod.L2, base=1,
                             mesh=host_mesh(2), deleted=deleted)
    n_slot = index.data.shape[0] // 2
    assert flat.fused_minima(data.dtype, 128, n_slot, dim, k,
                             int(DistCalcMethod.L2), "interpret")
    want = index.search(queries, k)
    assert metrics.counter_value("flat.scan_materialized") == 1
    pallas_kernels.set_interpret(True)
    try:
        got = index.search(queries, k)
    finally:
        pallas_kernels.set_interpret(False)
    assert metrics.counter_value("flat.scan_fused_minima") == 1
    assert metrics.counter_value("flat.scan_margin_unproved") == 0
    assert np.array_equal(got[0], want[0])
    assert np.array_equal(got[1], want[1])
    assert set(got[1].ravel() // index.row_stride) == {0, 1}
    assert not deleted[got[1].ravel()].any()


def test_a_shard_that_cannot_prove_answers_from_its_scores(host_mesh):
    """One shard holds a query's nearest row in nine groups (more copies
    than the proved select has spare groups): that shard answers from
    its materialised scores, the other from its proved selection, the
    flag comes back set on every shard and is counted once; the merged
    answer is the materialised mesh program's."""
    from sptag_tpu.ops import pallas_kernels
    from sptag_tpu.parallel.sharded import ShardedFlatIndex

    rng = np.random.default_rng(4141)
    n, dim = 2 * 12_900 + 11, 96
    data = rng.integers(-60, 61, (n, dim)).astype(np.float32)
    queries = (data[rng.integers(0, n, 128)]
               + rng.integers(-2, 3, (128, dim))).astype(np.float32)
    for g in range(9):
        data[(3 + 5 * g) * 128 + g] = queries[0]        # all in shard 0
    index = ShardedFlatIndex(data, DistCalcMethod.L2, base=1,
                             mesh=host_mesh(2))
    want = index.search(queries, 1)
    pallas_kernels.set_interpret(True)
    try:
        got = index.search(queries, 1)
    finally:
        pallas_kernels.set_interpret(False)
    assert metrics.counter_value("flat.scan_fused_minima") == 1
    assert metrics.counter_value("flat.scan_margin_unproved") == 1
    assert np.array_equal(got[0], want[0])
    assert np.array_equal(got[1], want[1])
    assert got[1][0, 0] == 3 * 128 and got[0][0, 0] == 0.0


# ---------------------------------------------------------------- (f) ----

def golden_rows(n=300, d=24):
    """Rows that need no generator: the same bytes on any numpy."""
    x = (np.arange(n * d, dtype=np.int64) * 2654435761 % 2003) / 2003.0
    return x.astype(np.float32).reshape(n, d)


# sha256 of every file `index_builder -a FLAT` wrote for golden_rows() at
# b2c8c6d, the parent of the PR that gave MeshShardAxis its meaning there
GOLDEN_FLAT = {
    "deletes.bin":
        "02e63a37515fde99af67997f5dcbc33645b6634fd2e4330941744099b9355565",
    "indexloader.ini":
        "f151587d04ef9ebeef8716cfde1a5fca55746b9be8ce7157df0a657b607b90a3",
    "manifest.json":
        "54d06936a2763198ba8594bfe3102e21058af85c6bdca2c9a73649b4c5555682",
    "vectors.bin":
        "ff1e7b3d89a2bdbd74f81e7b0697c42e11ea84888066d77718400b1f95a59501",
}


def folder_hashes(folder):
    """b2c8c6d wrote `RooflineProbe=0` before `DeviceBytesLedger=`; the
    parameter left the registry since (PR 46) and `save_config` writes
    the line no more: the ini is held to the golden one with that line
    put back, every other file as it is."""
    hashes = {}
    for name in sorted(os.listdir(folder)):
        with open(os.path.join(folder, name), "rb") as f:
            blob = f.read()
        if name == "indexloader.ini":
            assert b"RooflineProbe" not in blob
            blob = blob.replace(b"DeviceBytesLedger=",
                                b"RooflineProbe=0\nDeviceBytesLedger=", 1)
        hashes[name] = hashlib.sha256(blob).hexdigest()
    return hashes


@pytest.mark.parametrize("params", [(), ("Index.MeshShardAxis=0",)])
def test_without_a_shard_axis_the_folder_is_the_parents(tmp_path, params):
    folder = build_cli(str(tmp_path), golden_rows(), "FLAT", *params)
    assert not os.path.exists(os.path.join(folder, "sharded.json"))
    assert folder_hashes(folder) == GOLDEN_FLAT


# ---------------------------------------------------------------- (g) ----

def test_a_manifest_without_algo_still_loads_as_bkt(tmp_path):
    """Mesh folders saved before the manifest named a family hold BKT
    shards; the CLI's BKT mesh build goes through the same switch."""
    from sptag_tpu.parallel.sharded import ShardedBKTIndex

    rng = np.random.default_rng(4)
    data = rng.standard_normal((256, 16)).astype(np.float32)
    folder = build_cli(str(tmp_path), data, "BKT", "Index.MeshShardAxis=2",
                       *TINY_BKT)
    path = os.path.join(folder, "sharded.json")
    with open(path) as f:
        manifest = json.load(f)
    assert manifest["algo"] == "BKT" and manifest["n_shards"] == 2
    del manifest["algo"]
    with open(path, "w") as f:
        json.dump(manifest, f)
    adapter = sp.load_index(folder)
    assert isinstance(adapter._impl, ShardedBKTIndex)
    _, ids = adapter.search_batch(data[:4], 1)
    assert ids[:, 0].tolist() == [0, 1, 2, 3]
