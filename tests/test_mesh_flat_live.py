"""A living corpus on the mesh (PR 43): a mesh FLAT index takes adds and
deletes in place, on the shard that owns them.

Through the normal path — builder CLI with `Index.MeshShardAxis=4
Index.WalEnabled=1` -> mesh folder -> `load_index` -> `ServingAdapter` over
`ShardedFlatIndex` (a `FlatIndex` whose placement is a mesh) -> `$admin:add`
/ `$admin:delete` over a socket — held to the semantics of
benchmark/harness/reference_live.py, RESTATED here in numpy (tier-1 tests
import nothing of the benchmark): a corpus is its base rows, the rows added
since (ids in arrival order, never reused) and the ids deleted.  Beside it
the placement's own contract (parallel/sharded.py): a write rung goes whole
to one shard and successive rungs to successive shards, a write touches one
device's buffers, all blocks grow together and nothing compiles after a
growth, a living folder saves, loads and replays its log.

And the guard of what this PR must not move: the one-chip programs
(`algo/flat.py`) and the static mesh program lower to the text the PARENT
lowered them to (hashes recorded from commit 7166fef at these shapes).

Small on purpose (4,003 x 100 float32 over four forced CPU devices); the
chip-sized form is the cell `sharded_live20m.stream`.
"""

import base64
import hashlib
import os
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import sptag_tpu as sp
from conftest import ServerThread
from sptag_tpu.algo import flat
from sptag_tpu.core.types import DistCalcMethod
from sptag_tpu.parallel import sharded
from sptag_tpu.serve import wire
from sptag_tpu.serve.client import AnnClient
from sptag_tpu.serve.server import SearchServer
from sptag_tpu.serve.service import ServiceContext, ServiceSettings
from sptag_tpu.tools import index_builder
from sptag_tpu.utils import metrics, recompile_guard, trace

ROWS, DIM, BLOCK, K, SHARDS = 4_003, 100, 128, 10, 4
STRIDE = 1_008                      # rows_per_shard(4003, 4)
RNG = np.random.default_rng(43)
BASE = RNG.standard_normal((ROWS, DIM)).astype(np.float32)
QUERIES = RNG.standard_normal((32, DIM)).astype(np.float32)


def _near(queries, seed, sigma=0.25):
    """Rows drawn near `queries`: each enters its query's top-k."""
    noise = np.random.default_rng(seed).standard_normal(queries.shape)
    return (queries + sigma * noise).astype(np.float32)


def _block(seed, rows=BLOCK):
    return _near(np.resize(QUERIES, (rows, DIM)), seed)


def _sq_dists(a, b):
    a, b = a.astype(np.float64), b.astype(np.float64)
    return np.maximum((a * a).sum(1)[:, None] + (b * b).sum(1)[None, :]
                      - 2.0 * a @ b.T, 0.0)


class Reference:
    """The live corpus in numpy: rows in id order and the ids deleted.
    Knows nothing of partitions: that is the point."""

    def __init__(self, base):
        self.rows = base.copy()
        self.dead = np.zeros(len(base), bool)

    def add(self, block):
        self.rows = np.concatenate([self.rows, block])
        self.dead = np.concatenate([self.dead, np.zeros(len(block), bool)])

    def delete(self, block) -> int:
        near = np.flatnonzero((_sq_dists(block, self.rows) <= 1e-3).any(0)
                              & ~self.dead)
        exact = ((self.rows[near].astype(np.float64)[:, None, :]
                  - block.astype(np.float64)[None, :, :]) ** 2).sum(-1)
        hit = near[(exact <= 1e-6).any(1)] if len(near) else near
        self.dead[hit] = True
        return len(hit)

    def topk(self, queries, k=K):
        d = _sq_dists(queries, self.rows)
        d[:, self.dead] = np.inf
        ids = np.argsort(d, axis=1, kind="stable")[:, :k]
        return ids, np.take_along_axis(d, ids, axis=1)

    def copy(self):
        out = Reference(self.rows)
        out.dead = self.dead.copy()
        return out


def _folder(tmp, *params) -> str:
    """BIN file -> `index_builder.main` -> a four-shard mesh folder."""
    os.makedirs(str(tmp), exist_ok=True)
    bin_path = os.path.join(str(tmp), "vectors.bin")
    with open(bin_path, "wb") as f:
        f.write(np.asarray(BASE.shape, "<i4").tobytes())
        BASE.tofile(f)
    folder = os.path.join(str(tmp), "index")
    rc = index_builder.main(
        ["-d", str(DIM), "-v", "Float", "-i", f"BIN:{bin_path}", "-o",
         folder, "-a", "FLAT", "Index.DistCalcMethod=L2",
         f"Index.MeshShardAxis={SHARDS}", *params])
    assert rc == 0
    return folder


@pytest.fixture()
def living(tmp_path):
    """-> (the folder, its loaded adapter): WAL on, as the cell has it."""
    folder = _folder(tmp_path, "Index.WalEnabled=1", "Index.WalFsync=1")
    return folder, sp.load_index(folder)


def _same(got, want):
    dists, ids = got
    np.testing.assert_array_equal(ids, want[0])
    np.testing.assert_allclose(dists, want[1], rtol=1e-4, atol=1e-3)


# ------------------------------------------------------------ the runbook

def test_a_runbook_of_adds_deletes_and_searches_equals_the_reference(living):
    """Interleaved adds (1 row, a ragged rung, a whole rung, more than a
    top rung), deletes by content (streamed AND base rows) and searches:
    after every operation the lists are the reference's, bit for bit."""
    _, adapter = living
    index = adapter._impl
    assert isinstance(index, flat.FlatIndex)
    ref = Reference(BASE)
    q = QUERIES[:8]
    _same(adapter.search_batch(q, K), ref.topk(q))
    # a delete before any add: a mask write on the blocks as placed
    assert adapter.delete_rows(BASE[[9]])[1] == ref.delete(BASE[[9]]) == 1
    _same(adapter.search_batch(BASE[[9]], K), ref.topk(BASE[[9]]))
    assert index._slot_ids is None
    blocks = []
    for step, rows in enumerate([1, 7, BLOCK, 300, 1500, BLOCK, 40, 9]):
        block = _block(100 + step, rows)
        assert adapter.add(block) == sp.ErrorCode.Success
        ref.add(block)
        blocks.append(block)
        _same(adapter.search_batch(q, K), ref.topk(q))
        if step % 2:
            victim = blocks.pop(0)
            code, count = adapter.delete_rows(victim)
            assert code == sp.ErrorCode.Success
            assert count == ref.delete(victim) == len(victim)
            _same(adapter.search_batch(q, K), ref.topk(q))
        if step == 3:
            # base rows of three partitions, found by content
            some = BASE[[5, STRIDE + 3, 3 * STRIDE + 11]]
            assert adapter.delete_rows(some)[1] == ref.delete(some) == 3
            _same(adapter.search_batch(q[:1], K), ref.topk(q[:1]))
            _same(adapter.search_batch(BASE[[5, STRIDE + 3]], K),
                  ref.topk(BASE[[5, STRIDE + 3]]))
    got = adapter.search_batch(q, K)[1]
    assert (got >= ROWS).any()          # streamed rows are in the answers
    assert adapter.num_samples == len(ref.rows)
    assert index.num_deleted == int(ref.dead.sum())


def test_ids_base_rows_keep_theirs_and_streamed_rows_arrive_in_order(living):
    """A base row answers as its row in the input file whichever
    partition holds it, before and after the first write; a streamed
    row's id is base rows + arrival order; a rung lies whole in one
    shard, successive rungs in successive shards, so no shard is ahead of
    another by more than a rung."""
    _, adapter = living
    index = adapter._impl
    probes = [0, STRIDE - 1, STRIDE, 2 * STRIDE + 17, ROWS - 1]
    assert list(adapter.search_batch(BASE[probes], 1)[1][:, 0]) == probes
    for step in range(9):
        block = _block(200 + step)
        first = adapter.num_samples
        assert first == ROWS + step * BLOCK
        assert adapter.add(block) == sp.ErrorCode.Success
        ids = adapter.search_batch(block[:5], 1)[1][:, 0]
        assert list(ids) == list(range(first, first + 5))
        place = index._place[first - ROWS:first - ROWS + BLOCK]
        assert set(place[:, 0]) == {step % SHARDS}       # one shard, whole
        assert list(np.diff(place[:, 1])) == [1] * (BLOCK - 1)
        live = [f - d for f, d in zip(index._fill, index._dead)]
        assert max(live) - min(live) <= BLOCK + (STRIDE * SHARDS - ROWS)
    assert list(adapter.search_batch(BASE[probes], 1)[1][:, 0]) == probes
    # 1,500 rows: two pieces, a top rung and the rest, two shards
    big = _block(300, 1500)
    first = adapter.num_samples
    devices = trace.report().get("mesh.write_devices", {"total_s": 0.0})
    assert adapter.add(big) == sp.ErrorCode.Success
    place = index._place[first - ROWS:first - ROWS + 1500]
    assert len(set(place[:1024, 0])) == 1 and len(set(place[1024:, 0])) == 1
    assert place[0, 0] != place[1024, 0]
    after = trace.report()["mesh.write_devices"]
    assert after["total_s"] - devices["total_s"] == 2
    assert metrics.gauge_value("mesh.live_rows_max") == max(
        f - d for f, d in zip(index._fill, index._dead))
    assert metrics.gauge_value("mesh.rows_per_shard") == max(index._fill)


def test_a_write_touches_one_devices_buffers(living):
    """The owning shard's rows, norms and mask are written where they lie
    on THAT device: the other devices' buffers are the same buffers
    before and after, an add's rung is one device written, and a delete's
    mask write touches the shards that own its rows."""
    _, adapter = living
    index = adapter._impl
    assert adapter.add(_block(1)) == sp.ErrorCode.Success    # the growth
    for turn in range(SHARDS):
        before = [[a.unsafe_buffer_pointer() for a in part]
                  for part in index._parts]
        owner = index._next_shard
        count = trace.report()["mesh.write_devices"]
        assert adapter.add(_block(10 + turn)) == sp.ErrorCode.Success
        after = [[a.unsafe_buffer_pointer() for a in part]
                 for part in index._parts]
        for s in range(SHARDS):
            if s != owner:
                assert after[s] == before[s]
        now = trace.report()["mesh.write_devices"]
        assert now["count"] - count["count"] == 1
        assert now["total_s"] - count["total_s"] == 1
        # the program's arrays are those buffers, not copies
        assert sorted(sh.data.unsafe_buffer_pointer()
                      for sh in index.data.addressable_shards) \
            == sorted(p[0] for p in after)
    count = trace.report()["mesh.write_devices"]
    assert adapter.delete_rows(BASE[[1, 2 * STRIDE + 1]])[1] == 2
    now = trace.report()["mesh.write_devices"]
    assert now["total_s"] - count["total_s"] == 2


def test_all_shards_grow_together_and_nothing_compiles_after(living):
    """The first add reserves a sixteenth (at least 8,192 slots) a shard
    on every device; inside the reserve 40 mutations and their searches
    compile nothing; the add that outgrows it grows every block again,
    runs every recorded program at the new shape, and the next searches
    compile nothing either."""
    _, adapter = living
    index = adapter._impl
    q = QUERIES[:8]
    adapter.search_batch(q, K)
    adapter.search_batch(q[:1], K)
    assert index.data.shape[0] == SHARDS * flat.pad_rows(STRIDE)
    grows = metrics.counter_value("flat.block_grows")
    first = _block(1)
    assert adapter.add(first) == sp.ErrorCode.Success
    slots = flat.reserved_slots(STRIDE + BLOCK)
    assert index.data.shape[0] == SHARDS * slots
    assert {p[0].shape[0] for p in index._parts} == {slots}
    assert metrics.gauge_value("flat.slots_reserved") == slots
    assert metrics.counter_value("flat.block_grows") - grows == 1
    assert adapter.delete_rows(first)[1] == BLOCK        # k = 32, warmed
    ref = Reference(BASE)
    ref.add(first)
    ref.delete(first)
    with recompile_guard.track_compiles("mesh_live") as log:
        for step in range(20):
            block = _block(10 + step)
            assert adapter.add(block) == sp.ErrorCode.Success
            ref.add(block)
            _same(adapter.search_batch(q, K), ref.topk(q))
            assert adapter.delete_rows(block)[1] == ref.delete(block)
            _same(adapter.search_batch(q[:1], K), ref.topk(q[:1]))
    assert log.count == 0, log.count
    assert metrics.counter_value("flat.block_grows") - grows == 1
    # outgrow the reserve: 8,192 slots a shard ahead
    bulk = _near(np.resize(QUERIES, (SHARDS * 8_300, DIM)), 77, sigma=2.0)
    assert adapter.add(bulk) == sp.ErrorCode.Success
    ref.add(bulk)
    assert metrics.counter_value("flat.block_grows") - grows == 2
    assert index.data.shape[0] > SHARDS * slots
    assert len({p[0].shape[0] for p in index._parts}) == 1
    with recompile_guard.track_compiles("mesh_live_grown") as log:
        _same(adapter.search_batch(q, K), ref.topk(q))
        _same(adapter.search_batch(q[:1], K), ref.topk(q[:1]))
        block = _block(99)
        assert adapter.add(block) == sp.ErrorCode.Success
        ref.add(block)
        assert adapter.delete_rows(block)[1] == ref.delete(block) == BLOCK
        _same(adapter.search_batch(q, K), ref.topk(q))
    assert log.count == 0, log.count


# ------------------------------------------------------- the served system

def _b64(rows) -> str:
    return base64.b64encode(np.ascontiguousarray(rows).tobytes()).decode()


def _text(vec) -> str:
    return (f"$resultnum:{K} $indexname:live "
            + "|".join(repr(float(v)) for v in vec))


class Served:
    """A mesh folder's adapter behind a real socket server with the admin
    surface, as `ServiceContext.from_ini` registers it."""

    def __init__(self, adapter):
        ctx = ServiceContext(ServiceSettings(default_max_result=K,
                                             enable_remote_admin=True))
        ctx.add_index("live", adapter)
        self.thread = ServerThread(SearchServer(ctx, batch_window_ms=1.0))
        self.thread.start()
        self.addr = self.thread.wait_ready()

    def client(self) -> AnnClient:
        c = AnnClient(*self.addr, timeout_s=120.0)
        c.connect()
        return c

    def stop(self):
        self.thread.stop()


def _admin(client, op, rows) -> int:
    res = client.search(f"$admin:{op} $indexname:live #{_b64(rows)}")
    assert res.status == wire.ResultStatus.Success, res.results
    assert res.results[0].index_name.startswith("admin:ok:"), res.results
    return int(res.results[0].ids[0])


def _search(client, queries):
    return np.asarray([client.search(_text(q)).results[0].ids
                       for q in queries])


def test_admin_add_and_delete_reach_the_mesh_index_over_the_wire(living):
    _, adapter = living
    served = Served(adapter)
    try:
        client = served.client()
        ref = Reference(BASE)
        q = QUERIES[:6]
        np.testing.assert_array_equal(_search(client, q), ref.topk(q)[0])
        appends = metrics.counter_value("mutation.wal_appends")
        for seed in (1, 2, 3):
            assert _admin(client, "add", _block(seed)) == BLOCK
            ref.add(_block(seed))
            np.testing.assert_array_equal(_search(client, q),
                                          ref.topk(q)[0])
        assert _admin(client, "delete", _block(2)) == ref.delete(_block(2))
        np.testing.assert_array_equal(_search(client, q), ref.topk(q)[0])
        assert metrics.counter_value("mutation.wal_appends") - appends == 4
        client.close()
    finally:
        served.stop()


def test_searches_racing_a_writer_are_admissible(living):
    """Two searchers beside one writer for some hundreds to a few thousand
    searches (as many as the machine answers in the writer's time): every
    answer is the exact top-k of a state between the last operation
    acknowledged before it was sent and the last one sent before it was
    read (`exact_ids_live`'s own rule)."""
    _, adapter = living
    served = Served(adapter)
    steps, lag = 40, 3
    blocks = [_block(100 + s) for s in range(steps)]
    q = QUERIES[:6]
    states, ops = [Reference(BASE)], []
    for s in range(steps):
        ops.append(("add", blocks[s]))
        if s >= lag:
            ops.append(("delete", blocks[s - lag]))
    for op, block in ops:
        nxt = states[-1].copy()
        nxt.add(block) if op == "add" else nxt.delete(block)
        states.append(nxt)
    truth = [st.topk(q)[0] for st in states]
    try:
        warm = served.client()
        # the growth, the k = 32 scan and every shard's write programs
        for s in range(SHARDS):
            _admin(warm, "add", _block(900 + s))
        for s in range(SHARDS):
            _admin(warm, "delete", _block(900 + s))
        warm.close()
        shift = SHARDS * BLOCK          # the warm blocks took those ids
        truth = [np.where(t >= ROWS, t + shift, t) for t in truth]
        sent, acked, answers, errors = [], [], [], []
        stop = threading.Event()

        def writer():
            try:
                client = served.client()
                for op, block in ops:
                    sent.append(time.perf_counter())
                    assert _admin(client, op, block) == BLOCK
                    acked.append(time.perf_counter())
                    time.sleep(0.02)
                client.close()
            except Exception as e:                      # noqa: BLE001
                errors.append(e)
            finally:
                stop.set()

        def searcher(offset):
            try:
                client = served.client()
                i = offset
                while not stop.is_set():
                    t0 = time.perf_counter()
                    ids = client.search(_text(q[i % len(q)])).results[0].ids
                    answers.append((i % len(q), t0, time.perf_counter(),
                                    list(ids)))
                    i += 1
                client.close()
            except Exception as e:                      # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=writer)] + [
            threading.Thread(target=searcher, args=(o,)) for o in (0, 3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        served.stop()
    assert not errors, errors
    # a loaded machine answers fewer: the race needs searches between the
    # operations, not a rate
    assert len(answers) > len(ops)
    for qi, t0, t1, ids in answers:
        a = sum(1 for t in acked if t < t0)
        b = max(a, sum(1 for t in sent if t < t1))
        assert any(list(truth[m][qi]) == ids for m in range(a, b + 1)), \
            (qi, a, b, ids, truth[a][qi], truth[b][qi])


# -------------------------------------------------------------- persistence

def test_a_living_mesh_folder_saves_and_loads(living, tmp_path):
    """Streamed rows and tombstones go into a mesh folder of the one
    format (`save_shards`), partitioned anew in id order: every id and
    answer is kept, the log beside it is empty, and the loaded index
    takes writes."""
    _, adapter = living
    ref = Reference(BASE)
    for seed in (1, 2, 3):
        adapter.add(_block(seed, 200))
        ref.add(_block(seed, 200))
    assert adapter.delete_rows(_block(2, 200))[1] == ref.delete(
        _block(2, 200))
    some = BASE[[7, STRIDE + 7]]
    assert adapter.delete_rows(some)[1] == ref.delete(some) == 2
    q = QUERIES[:8]
    want = adapter.search_batch(q, K)
    saved = str(tmp_path / "saved")
    assert adapter.save_index(saved) == sp.ErrorCode.Success
    manifest = sharded.read_manifest(saved)
    assert manifest["n"] == ROWS + 600 and manifest["algo"] == "FLAT"
    assert manifest["index_params"] == {"WalEnabled": "1"}
    empty = os.path.getsize(os.path.join(saved, "wal.bin"))
    assert empty <= 16                          # a header, no record
    again = sp.load_index(saved)
    assert isinstance(again, sharded.ServingAdapter)
    assert again.num_samples == ROWS + 600
    assert again._impl.num_deleted == int(ref.dead.sum())
    got = again.search_batch(q, K)
    np.testing.assert_array_equal(got[1], want[1])
    _same(got, ref.topk(q))
    # and lives on: the log is armed at the new folder
    assert again.add(_block(4)) == sp.ErrorCode.Success
    ref.add(_block(4))
    _same(again.search_batch(q, K), ref.topk(q))
    assert os.path.getsize(os.path.join(saved, "wal.bin")) > empty


def test_acknowledged_mutations_survive_a_kill(living):
    """WalEnabled=1 on a mesh folder: add and delete over the wire, drop
    the process's state without a save, `load_index`: the same rows, the
    same tombstones, the same answers, one log append an operation."""
    folder, adapter = living
    served = Served(adapter)
    try:
        client = served.client()
        appends = metrics.counter_value("mutation.wal_appends")
        ref = Reference(BASE)
        for op, seed in (("add", 21), ("add", 22), ("delete", 21),
                         ("add", 23)):
            assert _admin(client, op, _block(seed)) == BLOCK
            ref.add(_block(seed)) if op == "add" \
                else ref.delete(_block(seed))
        assert metrics.counter_value("mutation.wal_appends") - appends == 4
        want = _search(client, QUERIES[:8])
        np.testing.assert_array_equal(want, ref.topk(QUERIES[:8])[0])
        client.close()
    finally:
        served.stop()
    del adapter, served                         # the kill: nothing saved
    replayed = metrics.counter_value("mutation.wal_replayed")
    again = sp.load_index(folder)
    assert metrics.counter_value("mutation.wal_replayed") - replayed == 4
    assert again.num_samples == ROWS + 3 * BLOCK
    assert again._impl.num_deleted == BLOCK
    np.testing.assert_array_equal(
        again.search_batch(QUERIES[:8], K)[1], want)
    # the replayed rungs went round the shards as the live ones did
    assert sorted(again._impl._fill) == sorted(
        f + BLOCK * (s < 3) for s, f in enumerate(
            [STRIDE, STRIDE, STRIDE, ROWS - 3 * STRIDE]))


def test_a_mesh_that_takes_no_write_is_todays_static_index(tmp_path):
    """No `WalEnabled`, no add: the folder's manifest, the placed shapes
    and the program's static arguments are what they were (the
    `sharded_deep10m` cells'), and nothing per-shard exists."""
    folder = _folder(tmp_path)
    assert "index_params" not in sharded.read_manifest(folder)
    assert not os.path.exists(os.path.join(folder, "wal.bin"))
    adapter = sp.load_index(folder)
    index = adapter._impl
    adapter.search_batch(QUERIES[:8], K)
    assert index.data.shape == (SHARDS * flat.pad_rows(STRIDE), DIM)
    assert index._parts is None and index._slot_ids is None
    assert index._wal is None
    statics = index._scan_statics(8, K, flat.pad_rows(STRIDE))
    assert statics["row_stride"] == STRIDE
    assert index._programs == {(8, K)}
    _same(adapter.search_batch(QUERIES[:8], K),
          Reference(BASE).topk(QUERIES[:8]))


# ------------------------------------------- the guard of the accepted paths

def _sha(lowered) -> str:
    return hashlib.sha256(lowered.as_text().encode()).hexdigest()[:16]


_S = jax.ShapeDtypeStruct
_BLOCK = (_S((2048, 100), jnp.float32), _S((2048,), jnp.float32),
          _S((2048,), jnp.bool_))
# sha256[:16] of `.lower(...).as_text()` on the PARENT (commit 7166fef),
# this container's jax, at the shapes below
PARENT_SCAN = {(1, 10): "68eb06541a67c2bd", (1, 32): "66a985b0ed24012c",
               (8, 10): "6606935ca04fba8b", (8, 32): "79f29b726de8fb0c",
               (32, 10): "9bd4c24d0e77874d", (32, 32): "8e6ddd76b5e9f8b1",
               (128, 10): "3be7e8886c3aacf8", (128, 32): "88e32cb0b29c1fe9"}
PARENT_WRITE = {8: "13de0e73a02eed0d", 128: "ed0ad709acfcb6f0",
                1024: "8b80414e76b656b9"}
PARENT_MASK = {8: "121bed60024a8ef7", 128: "24e592633af86fbe",
               1024: "95f819133f223770"}
PARENT_GROWN = "17630fdace0bebdc"
PARENT_MESH = {(1, 10): "5c6bb6605d3308e1", (128, 10): "0ea5ddfd1842697f",
               (128, 32): "a1cdb8066837c7cb"}


@pytest.mark.parametrize("q,k", sorted(PARENT_SCAN))
def test_the_one_chip_scan_lowers_to_the_parents_text(q, k):
    lowered = flat._flat_search_kernel.lower(
        *_BLOCK, _S((q, 100), jnp.float32), k,
        metric=int(DistCalcMethod.L2), base=1, approx=False,
        recall_target=0.99, binned_bins=0, fused=False, interpret=False)
    assert _sha(lowered) == PARENT_SCAN[(q, k)]


@pytest.mark.parametrize("rung", sorted(PARENT_WRITE))
def test_the_one_chip_writes_lower_to_the_parents_text(rung):
    assert _sha(flat._block_write_rows.lower(
        *_BLOCK, _S((rung, 100), jnp.float32), _S((rung,), jnp.bool_),
        _S((), jnp.int32))) == PARENT_WRITE[rung]
    assert _sha(flat._block_mask_rows.lower(
        _BLOCK[2], _S((rung,), jnp.int32))) == PARENT_MASK[rung]


def test_the_one_chip_growth_lowers_to_the_parents_text():
    assert _sha(flat._block_grown.lower(
        *_BLOCK, slots=flat.reserved_slots(2048 + 128))) == PARENT_GROWN


@pytest.mark.parametrize("q,k", sorted(PARENT_MESH))
def test_the_static_mesh_program_lowers_to_the_parents_text(host_mesh, q, k):
    n = 4 * 1024
    lowered = sharded._sharded_search_kernel.lower(
        _S((n, 96), jnp.float32), _S((n,), jnp.float32),
        _S((n,), jnp.bool_), _S((q, 96), jnp.float32), k_local=k,
        k_final=k, metric=int(DistCalcMethod.L2), base=1,
        mesh=host_mesh(4), row_stride=1000, fused=False, interpret=False)
    assert _sha(lowered) == PARENT_MESH[(q, k)]


def test_the_one_chip_index_makes_the_calls_it_made(monkeypatch):
    """`FlatIndex` without a mesh: an add is one `_block_write_rows` a
    rung and a delete one `_block_mask_rows`, on the one block, under the
    index's lock, and neither touches anything of `parallel/`."""
    calls = []

    def held() -> bool:
        """Whether another thread finds the index's lock taken."""
        got = []

        def probe():
            got.append(index._lock.acquire(blocking=False))
            if got[0]:
                index._lock.release()

        t = threading.Thread(target=probe)
        t.start()
        t.join()
        return not got[0]

    for name in ("_block_write_rows", "_block_mask_rows", "_block_grown"):
        sound = getattr(flat, name)

        def spy(*a, _sound=sound, _name=name, **kw):
            calls.append((_name, held()))
            return _sound(*a, **kw)

        monkeypatch.setattr(flat, name, spy)
    index = sp.create_instance("FLAT", "Float")
    index.set_parameter("DistCalcMethod", "L2")
    assert index.build(BASE) == sp.ErrorCode.Success
    index.search_batch(QUERIES[:8], K)
    assert type(index) is flat.FlatIndex
    assert index.add(_block(1)) == sp.ErrorCode.Success
    assert index.add(_block(2, 1500)) == sp.ErrorCode.Success
    assert index.delete_rows(_block(1))[1] == BLOCK
    assert calls == [("_block_grown", True), ("_block_write_rows", True),
                     ("_block_write_rows", True),
                     ("_block_write_rows", True),
                     ("_block_mask_rows", True)]
