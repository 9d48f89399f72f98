"""Live mutation under load (ISSUE 9): WAL durability, crash-recovery
matrix, manifest checksums, delta-shard ingest, background refine +
atomic snapshot swap, serve-tier exposure, and the knobs-at-defaults
byte-parity contract (the ci_check.sh standalone passes).

The crash matrix is DETERMINISTIC: every "process death" is an
InjectedCrash raised by a seeded storage-fault rule
(utils/faultinject.py `torn_write`/`short_read`/`crash`), after which
the in-memory index is abandoned and the folder reloaded — exactly the
state a real kill at that byte offset would leave.
"""

import json
import os
import signal
import socket
import struct
import subprocess
import sys
import time
import urllib.request

import numpy as np
import pytest

import sptag_tpu as sp
from sptag_tpu.core.delta import DeltaShard, merge_topk
from sptag_tpu.io import atomic, wal
from sptag_tpu.serve import wire
from sptag_tpu.serve.server import SearchServer
from sptag_tpu.serve.service import (SearchExecutor, ServiceContext,
                                     ServiceSettings)
from sptag_tpu.utils import faultinject, metrics

from test_serve import _ServerThread

RNG = np.random.default_rng(0xA5)
D = 8
DATA = RNG.standard_normal((48, D)).astype(np.float32)


@pytest.fixture(autouse=True)
def _clean_faults():
    faultinject.reset()
    yield
    faultinject.reset()


def _flat(wal_on=True, **params):
    idx = sp.create_instance("FLAT", "Float")
    idx.set_parameter("DistCalcMethod", "L2")
    if wal_on:
        idx.set_parameter("WalEnabled", "1")
    for n, v in params.items():
        idx.set_parameter(n, str(v))
    assert idx.build(DATA) == sp.ErrorCode.Success
    return idx


def _saved_flat(folder, **params):
    idx = _flat(**params)
    assert idx.save_index(str(folder)) == sp.ErrorCode.Success
    return idx


# ---------------------------------------------------------------- WAL unit

def test_wal_pack_replay_roundtrip(tmp_path):
    path = str(tmp_path / "wal.bin")
    w = wal.WalWriter(path)
    rows = RNG.standard_normal((3, 4)).astype(np.float32)
    w.append(wal.pack_add(10, rows, [b"a", b"", b"c"]))
    w.append(wal.pack_delete([7, 11]))
    w.append(wal.pack_add(13, rows[:1].astype(np.int8), None))
    w.close()
    records, torn = wal.replay(path)
    assert not torn
    add1, del1, add2 = records
    assert add1.begin == 10 and add1.metas == [b"a", b"", b"c"]
    np.testing.assert_array_equal(add1.rows, rows)
    assert del1.vids == [7, 11]
    assert add2.rows.dtype == np.int8 and add2.metas is None


def test_wal_torn_tail_truncates_exactly_once(tmp_path):
    path = str(tmp_path / "wal.bin")
    w = wal.WalWriter(path)
    rows = RNG.standard_normal((2, 4)).astype(np.float32)
    w.append(wal.pack_add(0, rows, None))
    w.close()
    good_size = os.path.getsize(path)
    # torn tail: half a record beyond the good prefix
    with open(path, "ab") as f:
        f.write(struct.pack("<II", 64, 0) + b"\x01" * 10)
    records, torn = wal.replay(path)
    assert torn and len(records) == 1
    assert os.path.getsize(path) == good_size       # truncated in place
    records2, torn2 = wal.replay(path)
    assert not torn2 and len(records2) == 1
    # a writer reopening after truncation appends cleanly
    w2 = wal.WalWriter(path)
    w2.append(wal.pack_delete([1]))
    w2.close()
    records3, _ = wal.replay(path)
    assert len(records3) == 2


def test_wal_crc_corruption_truncates(tmp_path):
    path = str(tmp_path / "wal.bin")
    w = wal.WalWriter(path)
    w.append(wal.pack_delete([1]))
    w.append(wal.pack_delete([2]))
    w.close()
    # flip one payload byte of the SECOND record
    with open(path, "r+b") as f:
        f.seek(-2, os.SEEK_END)
        b = f.read(1)
        f.seek(-2, os.SEEK_END)
        f.write(bytes([b[0] ^ 0xFF]))
    records, torn = wal.replay(path)
    assert torn and len(records) == 1
    assert records[0].vids == [1]


# ------------------------------------------------------- acked-write cycle

def test_acked_add_and_delete_survive_reload(tmp_path):
    folder = tmp_path / "idx"
    idx = _saved_flat(folder)
    fresh = RNG.standard_normal((3, D)).astype(np.float32)
    assert idx.add(fresh) == sp.ErrorCode.Success
    assert idx.delete(DATA[5:6]) == sp.ErrorCode.Success
    st = idx.mutation_state()
    assert st["wal"] and st["acked_writes"] == 2
    # crash: abandon the object, reload the folder
    loaded = sp.load_index(str(folder))
    assert loaded.num_samples == 51
    assert loaded.num_deleted == 1
    d, ids = loaded.search_batch(fresh, 1)
    assert (ids[:, 0] >= 48).all()
    assert (d[:, 0] <= 1e-4).all()
    _, ids5 = loaded.search_batch(DATA[5:6], 1)
    assert ids5[0, 0] != 5


def test_save_resets_wal_and_no_double_apply(tmp_path):
    folder = tmp_path / "idx"
    idx = _saved_flat(folder)
    fresh = RNG.standard_normal((2, D)).astype(np.float32)
    idx.add(fresh)
    assert idx.save_index(str(folder)) == sp.ErrorCode.Success
    # published snapshot folded the records; the log is empty again
    records, torn = wal.replay(str(folder / wal.WAL_NAME))
    assert records == [] and not torn
    loaded = sp.load_index(str(folder))
    assert loaded.num_samples == 50     # not 52: no double-apply


def test_wal_metadata_add_replays(tmp_path):
    from sptag_tpu.core.vectorset import MetadataSet

    folder = tmp_path / "idx"
    idx = _saved_flat(folder)
    fresh = RNG.standard_normal((2, D)).astype(np.float32)
    assert idx.add(fresh, MetadataSet([b"x1", b"x2"])) == \
        sp.ErrorCode.Success
    loaded = sp.load_index(str(folder))
    assert loaded.metadata is not None
    assert loaded.metadata.get_metadata(49) == b"x2"


# ------------------------------------------------------ crash matrix

def _expect_crash(fn):
    with pytest.raises(faultinject.InjectedCrash):
        fn()
    faultinject.configure("")


def test_crash_matrix_mid_wal_append(tmp_path):
    folder = tmp_path / "idx"
    idx = _saved_flat(folder)
    r1 = RNG.standard_normal((1, D)).astype(np.float32)
    r2 = RNG.standard_normal((1, D)).astype(np.float32)
    assert idx.add(r1) == sp.ErrorCode.Success          # acked
    faultinject.configure("torn_write@wal.append")
    _expect_crash(lambda: idx.add(r2))                  # NOT acked
    loaded = sp.load_index(str(folder))
    # every acked write present, the torn one absent
    assert loaded.num_samples == 49
    _, ids = loaded.search_batch(r1, 1)
    assert ids[0, 0] == 48
    assert atomic.verify_manifest(str(folder)) > 0


def test_crash_matrix_mid_snapshot_blob(tmp_path):
    folder = tmp_path / "idx"
    idx = _saved_flat(folder)
    r1 = RNG.standard_normal((1, D)).astype(np.float32)
    idx.add(r1)
    # tear the SECOND staged file of the next save
    faultinject.configure("torn_write@snapshot.write:after=1")
    _expect_crash(lambda: idx.save_index(str(folder)))
    # old snapshot + old WAL intact: acked state reconstructs
    loaded = sp.load_index(str(folder))
    assert loaded.num_samples == 49


def test_crash_matrix_pre_rename(tmp_path):
    folder = tmp_path / "idx"
    idx = _saved_flat(folder)
    idx.add(RNG.standard_normal((1, D)).astype(np.float32))
    faultinject.configure("crash@save.pre_rename")
    _expect_crash(lambda: idx.save_index(str(folder)))
    loaded = sp.load_index(str(folder))
    assert loaded.num_samples == 49


def test_crash_matrix_post_rename(tmp_path):
    folder = tmp_path / "idx"
    idx = _saved_flat(folder)
    idx.add(RNG.standard_normal((1, D)).astype(np.float32))
    faultinject.configure("crash@save.post_rename")
    _expect_crash(lambda: idx.save_index(str(folder)))
    # the swap landed: new snapshot with the add folded in, fresh log —
    # replay must not double-apply (the begin-skip contract)
    loaded = sp.load_index(str(folder))
    assert loaded.num_samples == 49
    assert loaded.mutation_state()["acked_writes"] == 0
    records, _ = wal.replay(str(folder / wal.WAL_NAME))
    assert records == []


def test_crash_matrix_fresh_save_interrupted(tmp_path):
    """A FIRST save dying pre-rename leaves no folder; the staging dir
    is recoverable via _recover_interrupted_save (load prefers the
    complete .saving sibling)."""
    folder = tmp_path / "fresh"
    idx = _flat()
    faultinject.configure("crash@save.pre_rename")
    _expect_crash(lambda: idx.save_index(str(folder)))
    assert not os.path.exists(str(folder / "indexloader.ini"))
    loaded = sp.load_index(str(folder))     # heals from .saving-*
    assert loaded.num_samples == 48


def test_manifest_detects_blob_corruption(tmp_path):
    folder = tmp_path / "idx"
    _saved_flat(folder)
    with open(str(folder / "vectors.bin"), "r+b") as f:
        f.seek(32)
        f.write(b"\xde\xad\xbe\xef")
    with pytest.raises(atomic.ManifestError):
        sp.load_index(str(folder))


def test_short_read_wal_fails_safe(tmp_path):
    folder = tmp_path / "idx"
    idx = _saved_flat(folder)
    idx.add(RNG.standard_normal((1, D)).astype(np.float32))
    idx.add(RNG.standard_normal((1, D)).astype(np.float32))
    faultinject.configure("short_read@wal.read")
    loaded = sp.load_index(str(folder))
    faultinject.configure("")
    # a prefix of the acked writes (possibly none) — never garbage,
    # never a crash
    assert loaded.num_samples in (48, 49, 50)


def test_wal_fsync_off_still_crash_consistent(tmp_path):
    folder = tmp_path / "idx"
    idx = _saved_flat(folder, WalFsync=0)
    idx.add(RNG.standard_normal((1, D)).astype(np.float32))
    loaded = sp.load_index(str(folder))
    assert loaded.num_samples == 49


# ------------------------------------------------------- delta shard
#
# FLAT takes adds in its resident block in place (PR 40: algo/flat.py
# `_device_append`), so `DeltaShardCapacity` routes nothing there any
# more: the five FLAT cases below keep their scenarios (immediate
# visibility, tombstones over old and new rows, adds past the old
# capacity, a bulk add, WAL replay) and now hold the resident block to
# them, with `delta_rows` 0 throughout; the shard itself is tested on
# its own (`test_delta_shard_unit_masking`, `test_merge_topk_*`) and
# under BKT (`test_bkt_delta_*`).  tests/test_flat_live.py has the
# block's own contract (no compiles, bytes sent, growth, the served path).

def test_delta_shard_immediate_visibility_flat():
    idx = _flat(wal_on=False, DeltaShardCapacity=16)
    idx.search_batch(DATA[:4], 3)               # place the block
    fresh = RNG.standard_normal((5, D)).astype(np.float32)
    assert idx.add(fresh) == sp.ErrorCode.Success
    st = idx.mutation_state()
    assert st["delta_rows"] == 0 and st["delta_capacity"] == 16
    assert metrics.gauge_value("flat.rows_resident") == 53
    d, ids = idx.search_batch(fresh, 1)
    assert (ids[:, 0] == np.arange(48, 53)).all()
    assert (d[:, 0] <= 1e-4).all()
    # oracle sees the delta too
    _, ei = idx.exact_search_batch(fresh, 1)
    assert (ei[:, 0] >= 48).all()


def test_delta_tombstones_mask_both_tiers():
    idx = _flat(wal_on=False, DeltaShardCapacity=16)
    idx.search_batch(DATA[:4], 3)
    fresh = RNG.standard_normal((4, D)).astype(np.float32)
    idx.add(fresh)
    # delete one MAIN row and one DELTA row by content
    assert idx.delete(DATA[3:4]) == sp.ErrorCode.Success
    assert idx.delete(fresh[1:2]) == sp.ErrorCode.Success
    _, ids = idx.search_batch(DATA[3:4], 2)
    assert 3 not in ids[0]
    _, ids = idx.search_batch(fresh[1:2], 2)
    assert 49 not in ids[0]
    # the tombstoned delta row stays gone after absorb
    idx.refine_index()
    assert idx.mutation_state()["delta_rows"] == 0
    d, ids = idx.search_batch(fresh[1:2], 1)
    assert d[0, 0] > 1e-3


def test_delta_overflow_absorbs_then_reuses():
    idx = _flat(wal_on=False, DeltaShardCapacity=8)
    idx.search_batch(DATA[:4], 3)
    a = RNG.standard_normal((6, D)).astype(np.float32)
    b = RNG.standard_normal((6, D)).astype(np.float32)
    idx.add(a)
    grows = metrics.counter_value("flat.block_grows")
    idx.add(b)      # 6+6 > 8 (the old shard's bound): the block's reserve
    assert idx.mutation_state()["delta_rows"] == 0
    assert metrics.counter_value("flat.block_grows") == grows
    _, ids = idx.search_batch(np.concatenate([a, b]), 1)
    assert (ids[:, 0] == np.arange(48, 60)).all()


def test_delta_bulk_add_falls_back_to_linked_path():
    idx = _flat(wal_on=False, DeltaShardCapacity=4)
    idx.search_batch(DATA[:4], 3)
    bulk = RNG.standard_normal((9, D)).astype(np.float32)
    idx.add(bulk)           # > capacity: linked path, no delta
    assert idx.mutation_state()["delta_rows"] == 0
    _, ids = idx.search_batch(bulk, 1)
    assert (ids[:, 0] == np.arange(48, 57)).all()


def test_delta_wal_compose_replay_lands_in_delta(tmp_path):
    folder = tmp_path / "idx"
    idx = _saved_flat(folder, DeltaShardCapacity=16)
    fresh = RNG.standard_normal((3, D)).astype(np.float32)
    idx.add(fresh)
    loaded = sp.load_index(str(folder))
    # replayed adds take the live path's route: the resident block
    assert loaded.num_samples == 51
    assert loaded.mutation_state()["delta_rows"] == 0
    _, ids = loaded.search_batch(fresh, 1)
    assert (ids[:, 0] >= 48).all()


def test_merge_topk_dedupes_and_pads():
    d1 = np.array([[0.1, 0.5, 3.4e38]], np.float32)
    i1 = np.array([[4, 7, -1]], np.int32)
    d2 = np.array([[0.2, 0.5]], np.float32)
    i2 = np.array([[9, 7]], np.int32)
    d, i = merge_topk(d1, i1, d2, i2, 4)
    assert i.tolist() == [[4, 9, 7, -1]]
    assert d[0, 0] == np.float32(0.1)
    assert i.dtype == np.int32 and d.dtype == np.float32


def test_delta_shard_unit_masking():
    ds = DeltaShard(100, D, np.float32, 8, 0, 1)   # L2
    rows = RNG.standard_normal((3, D)).astype(np.float32)
    ds.append(rows, 100)
    deleted = np.zeros(103, bool)
    deleted[101] = True
    d, ids = ds.search(rows, 2, deleted)
    assert ids[0, 0] == 100 and ids[2, 0] == 102
    assert 101 not in ids[1]


# ---------------------------------------------- BKT delta + swap (slower)

@pytest.fixture(scope="module")
def bkt_base():
    rng = np.random.default_rng(7)
    data = rng.standard_normal((192, 12)).astype(np.float32)
    return data, rng


def _bkt(data, **params):
    idx = sp.create_instance("BKT", "Float")
    base = {"DistCalcMethod": "L2", "BKTKmeansK": 8, "TPTNumber": 2,
            "TPTLeafSize": 64, "NeighborhoodSize": 12, "CEF": 48,
            "AddCEF": 24, "MaxCheckForRefineGraph": 96, "MaxCheck": 256,
            "RefineIterations": 1, "Samples": 100,
            "DenseClusterSize": 64, "SearchMode": "beam",
            "AddCountForRebuild": 100000}
    base.update(params)
    for n, v in base.items():
        idx.set_parameter(n, str(v))
    assert idx.build(data) == sp.ErrorCode.Success
    return idx


def test_bkt_delta_add_and_background_swap(bkt_base):
    data, rng = bkt_base
    idx = _bkt(data, DeltaShardCapacity=64, AutoRefineThreshold=16)
    try:
        idx.search_batch(data[:4], 5)
        fresh = rng.standard_normal((8, 12)).astype(np.float32)
        t0 = time.perf_counter()
        assert idx.add(fresh) == sp.ErrorCode.Success
        add_s = time.perf_counter() - t0
        # searchable immediately, delta-resident, no engine rebuild
        st = idx.mutation_state()
        assert st["delta_rows"] == 8
        _, ids = idx.search_batch(fresh, 3)
        assert (ids[:, 0] == np.arange(192, 200)).all()
        # the add never paid a link/search pass (sanity: well under the
        # inline-link cost; generous bound for contended CI)
        assert add_s < 5.0, add_s
        # cross the threshold -> background refine + swap
        fresh2 = rng.standard_normal((12, 12)).astype(np.float32)
        assert idx.add(fresh2) == sp.ErrorCode.Success
        deadline = time.time() + 120
        while time.time() < deadline:
            st = idx.mutation_state()
            if st["swap_count"] >= 1 and not st["refine_in_flight"]:
                break
            time.sleep(0.05)
        assert st["swap_count"] >= 1, st
        assert st["delta_rows"] == 0
        assert st["swap_windows_ms"], st
        # absorbed rows now served by the ENGINE, still all findable
        _, ids = idx.search_batch(np.concatenate([fresh, fresh2]), 3)
        assert (ids[:, 0] == np.arange(192, 212)).all()
        # epoch advanced: readers observed a publish, not a mutation
        assert st["epoch"] >= 1
    finally:
        idx.wait_for_rebuild(timeout=120)
        idx.close()


def test_bkt_continuous_batching_streams_delta_rows(bkt_base):
    data, rng = bkt_base
    idx = _bkt(data, DeltaShardCapacity=64, ContinuousBatching=1)
    try:
        idx.search_batch(data[:4], 5)
        fresh = rng.standard_normal((4, 12)).astype(np.float32)
        assert idx.add(fresh) == sp.ErrorCode.Success
        futs = idx.submit_batch(fresh, 3)
        for i, f in enumerate(futs):
            d, ids = f.result(timeout=120)
            assert ids[0] == 192 + i, (i, ids)
            assert len(ids) == 3
    finally:
        idx.wait_for_rebuild(timeout=120)
        idx.close()


# ----------------------------------------------------- serve exposure

def _make_context(**settings):
    idx = _flat(wal_on=False)
    ctx = ServiceContext(ServiceSettings(default_max_result=5,
                                         **settings))
    ctx.add_index("main", idx)
    return ctx


def test_healthz_and_debug_mutation_expose_swap_state():
    ctx = _make_context(metrics_port=-1)
    ctx.indexes["main"].set_parameter("DeltaShardCapacity", "16")
    ctx.indexes["main"].add(
        RNG.standard_normal((2, D)).astype(np.float32))
    server = SearchServer(ctx, batch_window_ms=1.0)
    t = _ServerThread(server)
    t.start()
    try:
        t.wait_ready()
        mport = server._metrics_http.port
        with urllib.request.urlopen(
                f"http://127.0.0.1:{mport}/healthz", timeout=10) as r:
            health = json.loads(r.read())
        mut = health["indexes"]["main"]["mutation"]
        # FLAT holds no delta: its adds are in the resident block
        assert mut["delta_rows"] == 0 and mut["delta_capacity"] == 16
        assert mut["swap_count"] == 0
        with urllib.request.urlopen(
                f"http://127.0.0.1:{mport}/debug/mutation",
                timeout=10) as r:
            dbg = json.loads(r.read())
        assert dbg["tier"] == "server"
        assert dbg["indexes"]["main"]["delta_rows"] == 0
        assert "wal_appends" in dbg
    finally:
        t.stop()


# ------------------------------------------------- off-default parity

def test_mutation_off_parity_serve_bytes():
    """With every ISSUE-9 knob at its default (WalEnabled 0,
    DeltaShardCapacity 0, AutoRefineThreshold 0) the serve path
    produces byte-identical wire responses and the mutation subsystem
    does zero work — the ci_check.sh standalone parity pass."""
    ctx = _make_context()
    index = ctx.indexes["main"]
    st = index.mutation_state()
    assert not st["wal"] and st["delta_rows"] == 0 \
        and st["delta_capacity"] == 0
    server = SearchServer(ctx, batch_window_ms=1.0)
    t = _ServerThread(server)
    t.start()
    host, port = t.wait_ready()
    try:
        qtext = "|".join(str(x) for x in DATA[7])
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
        for name in ("mutation.wal_appends", "mutation.swaps",
                     "mutation.wal_replayed", "mutation.refine_errors",
                     "mutation.swap_stale_discards",
                     "faultinject.torn_writes", "faultinject.short_reads",
                     "faultinject.crashes"):
            assert metrics.counter_value(name) == 0, name
    finally:
        t.stop()


# ------------------------------------------------- e2e kill/restart

def _free_port():
    """A free port BELOW the kernel's ephemeral range (32768 up): the
    child binds it seconds after this probe, and a port the kernel hands
    out for `bind(0)` can go to another xdist worker's test server in
    between (seen once: the client then talked to that server)."""
    import random

    rng = random.Random(os.getpid() ^ time.time_ns())
    for _ in range(64):
        port = rng.randrange(20000, 30000)
        with socket.socket() as s:
            try:
                s.bind(("127.0.0.1", port))
            except OSError:
                continue
        return port
    raise RuntimeError("no free port under the ephemeral range")


def _spawn_server(cfg):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.Popen(
        [sys.executable, "-m", "sptag_tpu.serve.server", "-m", "socket",
         "-c", str(cfg)],
        env=env, cwd=os.path.dirname(os.path.dirname(__file__)),
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)


def _wait_port(port, proc, timeout=120.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if proc.poll() is not None:
            raise RuntimeError(f"server died rc={proc.returncode}")
        try:
            socket.create_connection(("127.0.0.1", port),
                                     timeout=1.0).close()
            return
        except OSError:
            time.sleep(0.25)
    raise TimeoutError("server never came up")


def test_e2e_add_kill_restart_search(tmp_path):
    """THE durability acceptance: add over the wire, `kill -9` the
    server process, restart it on the same folder, and the vector is
    found — the acked write survived real process death via the WAL."""
    import base64

    from sptag_tpu.serve.client import AnnClient

    folder = tmp_path / "idx"
    _saved_flat(folder)
    port = _free_port()
    cfg = tmp_path / "server.ini"
    cfg.write_text(
        "[Service]\n"
        "ListenAddr=127.0.0.1\n"
        f"ListenPort={port}\n"
        "EnableRemoteAdmin=1\n"
        "[Index]\n"
        "List=main\n"
        "[Index_main]\n"
        f"IndexFolder={folder}\n")
    marker = RNG.standard_normal((1, D)).astype(np.float32)
    b64 = base64.b64encode(marker.tobytes()).decode()
    proc = _spawn_server(cfg)
    try:
        _wait_port(port, proc)
        client = AnnClient("127.0.0.1", port, timeout_s=60.0)
        client.connect()
        res = client.search(f"$admin:add $indexname:main #{b64}")
        assert res.status == wire.ResultStatus.Success, res.results
        assert res.results[0].index_name == "admin:ok:added"
        client.close()
    finally:
        # SIGKILL: no atexit, no flush — only fsync'd bytes survive
        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=30)
    proc2 = _spawn_server(cfg)
    try:
        _wait_port(port, proc2)
        client = AnnClient("127.0.0.1", port, timeout_s=60.0)
        client.connect()
        line = "|".join(str(float(v)) for v in marker[0])
        r = client.search(f"$indexname:main $resultnum:1 {line}")
        assert r.status == wire.ResultStatus.Success
        assert r.results[0].ids[0] == 48, r.results[0].ids
        assert r.results[0].dists[0] <= 1e-4
        client.close()
    finally:
        proc2.send_signal(signal.SIGKILL)
        proc2.wait(timeout=30)
