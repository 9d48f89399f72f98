"""In-mesh sharded serving (ISSUE 11) — tier-1 mesh tests.

Small corpora on 2-4 virtual CPU devices (the conftest `host_mesh`
helper; the suite boots with 8 forced host devices) so the mesh serve
spine is exercised in tier-1 instead of living behind `slow` markers:

* the mesh programs go through the installed `jax.shard_map`;
* the merge contract: the in-mesh path returns the SAME ids as the
  socket fan-out aggregator + host merge over identical shard contents,
  across k / MaxCheck / deleted-mask cases;
* the mesh-wide slot scheduler (parallel/mesh_engine.py under
  algo/scheduler.py) returning search()'s ids in retire order;
* MeshServe end-to-end over sockets (streaming responses, mesh
  admission signals, epoch swap, /healthz mutation state);
* MeshServe OFF: serve bytes byte-identical (the ci_check.sh
  off-parity pass).
"""

import base64
import socket

import numpy as np
import pytest

import jax

from sptag_tpu.core.types import DistCalcMethod
from sptag_tpu.parallel.sharded import (
    ServingAdapter,
    ShardedBKTIndex,
    ShardedFlatIndex,
    make_mesh,
)
from sptag_tpu.serve import wire
from sptag_tpu.serve.client import AnnClient
from sptag_tpu.serve.server import SearchServer
from sptag_tpu.serve.service import (
    SearchExecutor,
    ServiceContext,
    ServiceSettings,
)
from sptag_tpu.utils import metrics

TINY_PARAMS = {"BKTNumber": 1, "BKTKmeansK": 4, "TPTNumber": 2,
               "TPTLeafSize": 32, "NeighborhoodSize": 8, "CEF": 16,
               "MaxCheckForRefineGraph": 64, "RefineIterations": 1,
               # beam: the fan-out shard servers must run the SAME
               # engine family the mesh walk runs — the single-chip
               # default (dense) would make the parity test compare
               # different algorithms (coincidentally equal only at
               # toy scale where dense covers everything)
               "MaxCheck": 128, "SearchMode": "beam"}
N, D = 256, 16          # divisible by every submesh we use: equal shards


def _corpus(n=N, d=D, seed=0):
    rng = np.random.default_rng(seed)
    return rng.standard_normal((n, d)).astype(np.float32)


from conftest import ServerThread as _ServerThread  # noqa: E402


@pytest.fixture(scope="module")
def mesh_built(tmp_path_factory):
    """One tiny 2-shard BKT mesh index, persisted (shard folders reused
    by the fan-out parity test and the load_index test)."""
    data = _corpus()
    folder = str(tmp_path_factory.mktemp("mesh_idx"))
    mesh = make_mesh(jax.devices()[:2])
    index = ShardedBKTIndex.build(data, DistCalcMethod.L2, mesh=mesh,
                                  params=TINY_PARAMS, save_to=folder)
    return data, index, folder


# ------------------------------------------------------------- shard_map

def test_mesh_programs_use_the_installed_shard_map(host_mesh):
    """One installation: the mesh modules call `jax.shard_map` itself (no
    version shim in between), and a sharded search runs through it."""
    import jax

    from sptag_tpu.parallel import mesh_engine, sharded

    assert sharded.shard_map is jax.shard_map
    assert mesh_engine.shard_map is jax.shard_map
    data = _corpus(n=96, d=8, seed=1)
    idx = ShardedFlatIndex(data, DistCalcMethod.L2, base=1,
                           mesh=host_mesh(2))
    _, ids = idx.search(data[:3], k=1)
    np.testing.assert_array_equal(ids[:, 0], np.arange(3))


# ------------------------------------------- mesh-wide slot scheduler spine

def test_mesh_scheduler_matches_monolithic_ids(mesh_built):
    """The mesh-wide continuous-batching path (MeshGraphEngine under
    BeamSlotScheduler) returns the SAME ids as the monolithic mesh
    search at the same knobs (distances last-ulp-tolerant — the PR-4
    scheduler caveat), and the pools drain clean."""
    data, index, _ = mesh_built
    q = _corpus(n=12, seed=2)[:, :D]
    d_mono, i_mono = index.search(q, 5)
    sched = index.enable_continuous_batching(slots=64)
    futs = index.submit_batch(q, 5)
    from sptag_tpu.algo.scheduler import gather_futures

    d_cb, i_cb = gather_futures(futs, 5)
    np.testing.assert_array_equal(i_mono, i_cb)
    np.testing.assert_allclose(d_mono, d_cb, rtol=1e-5, atol=1e-6)
    st = sched.stats()
    assert st["live"] == 0 and st["pending"] == 0
    # shard-axis accounting: the scheduler published the mesh scope
    assert metrics.gauge_value("scheduler.mesh_shards") == 2.0
    assert metrics.counter_value("scheduler.shard_retired") >= 2 * len(q)


# ----------------------------------------------------- merge contract tests

def _fanout_merge(result, shard_of, n_local, k):
    """Host-side global merge of the aggregator's flat-concatenated
    per-shard lists — exactly what the reference leaves to clients, and
    the baseline the in-mesh collective merge must reproduce."""
    cand = []
    for r in result.results:
        s = shard_of[r.index_name]
        for vid, dist in zip(r.ids, r.dists):
            if vid >= 0:
                cand.append((float(dist), s * n_local + int(vid)))
    cand.sort(key=lambda t: t[0])          # stable: shard-major on ties
    out_i = np.full(k, -1, np.int64)
    out_d = np.full(k, np.inf, np.float64)
    for j, (dist, gid) in enumerate(cand[:k]):
        out_d[j], out_i[j] = dist, gid
    return out_d, out_i


def test_merge_contract_vs_socket_fanout(mesh_built):
    """Parity across k / MaxCheck: the one-dispatch in-mesh path returns
    bit-identical ids (distances within last-ulp tolerance) to the
    socket fan-out aggregator over the SAME shard contents — each shard
    server loads the exact sub-index folder the mesh was packed from."""
    from sptag_tpu.core.index import load_index
    from sptag_tpu.serve.aggregator import (
        AggregatorContext, AggregatorService, RemoteServer)

    data, index, folder = mesh_built
    n_local = index.n_local
    shard_of = {}
    shard_threads = []
    backends = []
    try:
        for s in range(2):
            ctx = ServiceContext(ServiceSettings(default_max_result=10))
            ctx.add_index(f"s{s}", load_index(f"{folder}/shard_{s:03d}"))
            shard_of[f"s{s}"] = s
            t = _ServerThread(SearchServer(ctx, batch_window_ms=1.0))
            t.start()
            shard_threads.append(t)
            backends.append(t.wait_ready())
        agg_ctx = AggregatorContext(search_timeout_s=20.0)
        agg_ctx.servers = [RemoteServer(h, p) for h, p in backends]
        tg = _ServerThread(AggregatorService(agg_ctx))
        tg.start()
        ha, pa = tg.wait_ready()
        try:
            client = AnnClient(ha, pa, timeout_s=20.0)
            client.connect()
            queries = _corpus(n=6, seed=3)
            for k, mc in ((3, 64), (10, 128)):
                for row in range(len(queries)):
                    # per-row dispatch on BOTH paths: the per-shard
                    # programs then run at identical (1, D) shapes, so
                    # the id contract is exact (a batched mesh dispatch
                    # against single-query servers could differ in the
                    # last ulp from XLA's batch-shape reduction tiling)
                    d_mesh, i_mesh = index.search(
                        queries[row:row + 1], k, max_check=mc)
                    qb = base64.b64encode(queries[row].tobytes()).decode()
                    res = client.search(
                        f"$resultnum:{k} $maxcheck:{mc} #{qb}")
                    assert res.status == wire.ResultStatus.Success
                    fd, fi = _fanout_merge(res, shard_of, n_local, k)
                    np.testing.assert_array_equal(
                        i_mesh[0], fi,
                        err_msg=f"k={k} mc={mc} row={row}")
                    real = i_mesh[0] >= 0
                    np.testing.assert_allclose(
                        d_mesh[0][real], fd[real], rtol=1e-5)
            client.close()
        finally:
            tg.stop()
    finally:
        for t in shard_threads:
            t.stop()


def test_merge_contract_deleted_mask(host_mesh):
    """Deleted-mask case over FLAT shards: the in-mesh tombstone filter
    agrees with per-shard deletes on the fan-out side — no deleted row
    surfaces, and the surviving ids match exactly."""
    import sptag_tpu as sp

    data = _corpus(n=128, d=8, seed=4)
    deleted = np.zeros(128, bool)
    deleted[[5, 70, 71, 100]] = True
    mesh = host_mesh(2)
    idx = ShardedFlatIndex(data, DistCalcMethod.L2, base=1, mesh=mesh,
                           deleted=deleted)
    n_local = idx.row_stride        # the rows a shard stands for
    # fan-out baseline WITHOUT sockets: per-shard single-chip FLAT
    # indexes with the same rows deleted, host-merged like the
    # aggregator's client-side merge (the socket path itself is covered
    # above; this case isolates the tombstone semantics)
    per_shard = []
    for s in range(2):
        sub = sp.create_instance("FLAT", "Float")
        sub.set_parameter("DistCalcMethod", "L2")
        block = data[s * 64:(s + 1) * 64]
        sub.build(block)
        sub.delete(block[deleted[s * 64:(s + 1) * 64]])
        per_shard.append(sub)
    queries = data[[5, 20, 70, 100]]        # include deleted rows' vectors
    k = 6
    d_mesh, i_mesh = idx.search(queries, k)
    assert not set(np.flatnonzero(deleted)) & set(i_mesh.ravel())
    for row, q in enumerate(queries):
        cand = []
        for s, sub in enumerate(per_shard):
            dd, ii = sub.search_batch(q[None], k)
            for dist, vid in zip(dd[0], ii[0]):
                if vid >= 0:
                    cand.append((float(dist), s * n_local + int(vid)))
        cand.sort(key=lambda t: t[0])
        want = [gid for _, gid in cand[:k]]
        got = [gid for gid in i_mesh[row] if gid >= 0]
        assert got == want[:len(got)], (row, got, want)


# --------------------------------------------------- MeshServe serve tier

def test_mesh_serve_streaming_end_to_end(mesh_built):
    """[Service] MeshServe=1 over a mesh adapter: responses stream from
    the mesh-wide scheduler in retire order, the admission signals carry
    the mesh scope, and /healthz-visible mutation state reports the
    placement epoch."""
    data, index, _ = mesh_built
    ad = ServingAdapter(index, feature_dim=D)
    ctx = ServiceContext(ServiceSettings(default_max_result=5,
                                         mesh_serve=True))
    ctx.add_index("mesh", ad)
    server = SearchServer(ctx, batch_window_ms=1.0)
    t = _ServerThread(server)
    t.start()
    host, port = t.wait_ready()
    try:
        assert ad._mesh_serve                 # armed at server start
        client = AnnClient(host, port, timeout_s=20.0)
        client.connect()
        for j in (7, 100, 200):
            qb = base64.b64encode(data[j].tobytes()).decode()
            res = client.search(f"$resultnum:3 #{qb}")
            assert res.status == wire.ResultStatus.Success
            assert res.results[0].ids[0] == j
        client.close()
        assert metrics.counter_value("scheduler.retired") >= 3
        assert metrics.counter_value("server.streamed_responses") >= 1
        sig = server._admission_signals()
        assert sig["mesh_shards"] == 2.0
        health = server._healthz()
        assert health["indexes"]["mesh"]["mutation"]["mesh"]["shards"] == 2
    finally:
        t.stop()


def test_mesh_swap_epoch(mesh_built):
    """swap_impl publishes a whole mesh placement atomically: new
    queries see the new shards, the epoch advances, and the old
    placement's scheduler is retired (drains, never drops)."""
    data, index, _ = mesh_built
    ad = ServingAdapter(index, feature_dim=D)
    assert ad.enable_mesh_serve(slots=32)
    _, ids0 = ad.search_batch(data[:2], 1)
    np.testing.assert_array_equal(ids0[:, 0], [0, 1])
    data2 = _corpus(seed=9)
    index2 = ShardedBKTIndex.build(data2, DistCalcMethod.L2,
                                   mesh=index.mesh, params=TINY_PARAMS)
    old_sched = index._scheduler
    assert ad.swap_impl(index2) == 1
    assert index._scheduler is None and old_sched is not None
    assert index2._scheduler is not None      # MeshServe re-armed
    _, ids1 = ad.search_batch(data2[:2], 1)
    np.testing.assert_array_equal(ids1[:, 0], [0, 1])
    st = ad.mutation_state()
    assert st["epoch"] == 1 and st["swap_count"] == 1
    assert metrics.counter_value("mesh.swaps") == 1


def test_load_index_mesh_folder(mesh_built):
    """A folder with sharded.json loads as a ServingAdapter through the
    plain load_index path — the [Index_<name>] IndexFolder deployment
    story for in-mesh serving."""
    from sptag_tpu.core.index import load_index

    data, index, folder = mesh_built
    loaded = load_index(folder)
    assert isinstance(loaded, ServingAdapter)
    assert loaded.num_samples == N
    d_l, i_l = loaded.search_batch(data[:3], 2)
    d_m, i_m = index.search(data[:3], 2)
    np.testing.assert_array_equal(i_l, i_m)


def test_mesh_knobs(host_mesh):
    """MeshShardAxis sizes the shard axis at build; MeshKLocal caps the
    per-shard merge contribution (monolithic AND scheduler paths agree
    at the capped width); index-level MeshServe=1 arms the scheduler at
    placement time (the offline mirror of the [Service] setting)."""
    data = _corpus(n=128, d=8, seed=5)
    idx = ShardedBKTIndex.build(
        data, DistCalcMethod.L2,
        params=dict(TINY_PARAMS, MeshShardAxis=2, MeshKLocal=2,
                    MeshServe=1))
    assert idx.mesh.devices.size == 2
    assert int(idx.params.mesh_k_local) == 2
    assert idx._scheduler is not None      # armed by the index param
    q = data[:4]
    d5, i5 = idx.search(q, 5)
    # each shard contributes at most MeshKLocal=2 candidates: at most 4
    # real results per row, padded with -1 past that
    assert (i5[:, 4] == -1).all()
    assert ((i5[:, :4] >= 0).sum(axis=1) <= 4).all()
    from sptag_tpu.parallel.mesh_engine import MeshGraphEngine

    eng = MeshGraphEngine(idx)
    k_eff, L, B, T, limit = eng.walk_plan(5, 128)
    assert k_eff == 4                      # min(k, n, k_local * shards)
    # the scheduler path pads k_eff back out to the caller's k — the
    # streaming serve surface must honor the same (k,) row contract as
    # every synchronous path (MAX_DIST / -1 sentinels past k_eff)
    idx.enable_continuous_batching(slots=16)
    fd, fi = idx.submit_batch(q[:2], 5)[0].result()
    assert fd.shape == (5,) and fi.shape == (5,)
    assert fi[4] == -1


# -------------------------------------------------- off-parity golden bytes

def test_mesh_serve_off_parity_golden_bytes(mesh_built):
    """With MeshServe at its default (off), a server over a mesh adapter
    produces byte-identical wire responses to the reference layout and
    never builds a scheduler (the ci_check.sh standalone parity pass)."""
    data, index, _ = mesh_built
    # a FRESH adapter proves off means off (the module fixture's index
    # may carry a scheduler armed by the scheduler-parity test — the
    # ADAPTER path must not route to it with MeshServe off)
    ad = ServingAdapter(index, feature_dim=D)
    ctx = ServiceContext(ServiceSettings(default_max_result=5))
    ctx.add_index("mesh", ad)
    server = SearchServer(ctx, batch_window_ms=1.0)
    t = _ServerThread(server)
    t.start()
    host, port = t.wait_ready()
    try:
        assert not ad._mesh_serve
        qtext = "|".join(str(x) for x in data[7])
        expected_result = SearchExecutor(ctx).execute(qtext)
        expected_result.request_id = ""
        expected_body = expected_result.pack()
        expected = wire.PacketHeader(
            wire.PacketType.SearchResponse, wire.PacketProcessStatus.Ok,
            len(expected_body), 1, 77).pack() + expected_body

        body = wire.RemoteQuery(qtext).pack()
        s = socket.create_connection((host, port), timeout=10)
        s.sendall(wire.PacketHeader(
            wire.PacketType.SearchRequest, wire.PacketProcessStatus.Ok,
            len(body), 0, 77).pack() + body)
        s.settimeout(10)
        got = b""
        while len(got) < len(expected):
            chunk = s.recv(65536)
            if not chunk:
                break
            got += chunk
        s.close()
        assert got == expected
    finally:
        t.stop()
