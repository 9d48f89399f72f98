"""A FLAT index whose device state follows its mutations (PR 40).

The served system — `SearchServer`, `$admin:add` / `$admin:delete` over a
socket, the write-ahead log — held to the semantics of
benchmark/harness/reference_live.py, RESTATED here in numpy (tier-1 tests
import nothing of the benchmark): a corpus is its base rows, the rows
added since (ids in arrival order, never reused) and the ids deleted;
state m is the base plus the first m operations; a search sent after an
operation's acknowledgement sees it, one that overlaps sees the state
before or after.  Beside it the resident block's own contract
(algo/flat.py): between two growths no program compiles, a mutation
sends the device what it changed, a growth keeps every id and answer.

Small on purpose (20k x 100 float32, blocks of 128): the chip-sized form
is the cell `flat_live5m.stream`.
"""

import base64
import threading
import time

import numpy as np
import pytest

import sptag_tpu as sp
from sptag_tpu.algo import flat
from sptag_tpu.serve import wire
from sptag_tpu.serve.client import AnnClient
from sptag_tpu.serve.server import SearchServer
from sptag_tpu.serve.service import ServiceContext, ServiceSettings
from sptag_tpu.utils import metrics, recompile_guard, trace

from conftest import ServerThread

ROWS, DIM, BLOCK, K = 20_000, 100, 128, 10
RNG = np.random.default_rng(40)
BASE = RNG.standard_normal((ROWS, DIM)).astype(np.float32)
QUERIES = RNG.standard_normal((32, DIM)).astype(np.float32)


def _near(queries, seed, sigma=0.25):
    """Rows drawn near `queries`: each enters its query's top-k."""
    noise = np.random.default_rng(seed).standard_normal(queries.shape)
    return (queries + sigma * noise).astype(np.float32)


def _sq_dists(a, b):
    """float64 squared L2 of every row of `a` to every row of `b`."""
    a, b = a.astype(np.float64), b.astype(np.float64)
    return np.maximum((a * a).sum(1)[:, None] + (b * b).sum(1)[None, :]
                      - 2.0 * a @ b.T, 0.0)


class Reference:
    """The live corpus in numpy: rows in id order and the ids deleted."""

    def __init__(self, base):
        self.rows = base.copy()
        self.dead = np.zeros(len(base), bool)

    def add(self, block):
        self.rows = np.concatenate([self.rows, block])
        self.dead = np.concatenate([self.dead, np.zeros(len(block), bool)])

    def delete(self, block) -> int:
        """By content: every live row within 1e-6 (float64 squared L2)
        of a row of `block` -> how many were tombstoned.  The expanded
        form screens (its rounding is ~1e-11 here), the difference
        decides."""
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
        return np.argsort(d, axis=1, kind="stable")[:, :k]

    def copy(self):
        out = Reference(self.rows)
        out.dead = self.dead.copy()
        return out


def _index(**params):
    idx = sp.create_instance("FLAT", "Float")
    idx.set_parameter("DistCalcMethod", "L2")
    for name, value in params.items():
        idx.set_parameter(name, str(value))
    assert idx.build(BASE) == sp.ErrorCode.Success
    return idx


def _b64(rows) -> str:
    return base64.b64encode(np.ascontiguousarray(rows).tobytes()).decode()


def _text(vec) -> str:
    return (f"$resultnum:{K} $indexname:live "
            + "|".join(repr(float(v)) for v in vec))


class Served:
    """A FLAT index behind a real socket server with the admin surface."""

    def __init__(self, index):
        ctx = ServiceContext(ServiceSettings(default_max_result=K,
                                             enable_remote_admin=True))
        ctx.add_index("live", index)
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
    """One `$admin:<op>` -> the count it replied."""
    res = client.search(f"$admin:{op} $indexname:live #{_b64(rows)}")
    assert res.status == wire.ResultStatus.Success, res.results
    assert res.results[0].index_name.startswith("admin:ok:"), res.results
    return int(res.results[0].ids[0])


def _search(client, queries):
    return np.asarray([client.search(_text(q)).results[0].ids
                       for q in queries])


@pytest.fixture()
def served():
    s = Served(_index())
    yield s
    s.stop()


# ------------------------------------------------------- the served system

@pytest.mark.parametrize("rows", [1, 7, BLOCK, 300])
def test_served_serial_mutations_equal_the_reference(served, rows):
    """Each search after its acknowledgement: the lists are the
    reference's, whatever the block's size (one row, a ragged rung, a
    whole rung, more than one)."""
    client = served.client()
    ref = Reference(BASE)
    q = QUERIES[:8]
    np.testing.assert_array_equal(_search(client, q), ref.topk(q))
    blocks = [_near(np.resize(QUERIES, (rows, DIM)), seed)
              for seed in (1, 2, 3)]
    for block in blocks:
        assert _admin(client, "add", block) == rows
        ref.add(block)
        np.testing.assert_array_equal(_search(client, q), ref.topk(q))
    got = _search(client, q)
    assert (got >= ROWS).any()          # streamed rows are in the answers
    for block in blocks[:2]:
        assert _admin(client, "delete", block) == ref.delete(block) == rows
        np.testing.assert_array_equal(_search(client, q), ref.topk(q))
    client.close()


def test_admin_delete_replies_the_rows_tombstoned(served):
    """Not `len(rows)`: rows never added tombstone nothing, a block
    deleted twice tombstones nothing the second time."""
    client = served.client()
    block = _near(QUERIES[:16], 7)
    assert _admin(client, "add", block) == 16
    strangers = RNG.standard_normal((8, DIM)).astype(np.float32)
    mixed = np.concatenate([block[:5], strangers])
    assert _admin(client, "delete", mixed) == 5
    res = client.search(f"$admin:delete $indexname:live #{_b64(block[:5])}")
    assert res.results[0].ids[0] == 0       # found, all already deleted
    assert _admin(client, "delete", block) == 11
    # a base row is deletable by content like any other
    assert _admin(client, "delete", BASE[3:4]) == 1
    client.close()


def test_searches_overlapping_a_writer_are_admissible(served):
    """Two searchers beside one writer: every answer is the exact top-k
    of a state between the last operation acknowledged before it was
    sent and the last one sent before it was read."""
    steps, lag = 12, 3
    blocks = [_near(np.resize(QUERIES, (BLOCK, DIM)), 100 + s)
              for s in range(steps)]
    q = QUERIES[:6]
    states = [Reference(BASE)]
    ops = []
    for s in range(steps):
        ops.append(("add", blocks[s]))
        if s >= lag:
            ops.append(("delete", blocks[s - lag]))
    for op, block in ops:
        nxt = states[-1].copy()
        nxt.add(block) if op == "add" else nxt.delete(block)
        states.append(nxt)
    truth = [st.topk(q) for st in states]

    warm = served.client()
    _admin(warm, "add", blocks[0][:BLOCK])      # compile the programs
    _admin(warm, "delete", blocks[0][:BLOCK])
    warm.close()
    # the warm block took ids ROWS..ROWS+127: shift the truth's streamed ids
    truth = [np.where(t >= ROWS, t + BLOCK, t) for t in truth]

    sent, acked = [], []
    answers = []                        # (query, t_before, t_after, ids)
    stop = threading.Event()
    errors = []

    def writer():
        try:
            client = served.client()
            for op, block in ops:
                sent.append(time.perf_counter())
                assert _admin(client, op, block) == BLOCK
                acked.append(time.perf_counter())
                time.sleep(0.01)
            client.close()
        except Exception as e:                          # noqa: BLE001
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
        except Exception as e:                          # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=writer)] + [
        threading.Thread(target=searcher, args=(o,)) for o in (0, 3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    assert len(answers) > len(ops)
    for qi, t0, t1, ids in answers:
        a = sum(1 for t in acked if t < t0)
        b = max(a, sum(1 for t in sent if t < t1))
        fits = [m for m in range(a, b + 1)
                if list(truth[m][qi]) == ids]
        assert fits, (qi, a, b, ids, truth[a][qi], truth[b][qi])


# ------------------------------------------------------ the resident block

def test_no_compiles_over_100_mutations_inside_the_reserve():
    idx = _index()
    q = QUERIES[:8]
    idx.search_batch(q, K)
    first = _near(np.resize(QUERIES, (BLOCK, DIM)), 1)
    idx.add(first)                      # grows the block, compiles writes
    idx.search_batch(q, K)
    assert idx.delete_rows(first) == (sp.ErrorCode.Success, BLOCK)
    slots = metrics.gauge_value("flat.slots_reserved")
    assert slots == flat.reserved_slots(ROWS + BLOCK)
    grows = metrics.counter_value("flat.block_grows")
    ref = Reference(BASE)
    ref.add(first)
    ref.delete(first)
    with recompile_guard.track_compiles("flat_live") as log:
        for step in range(50):
            block = _near(np.resize(QUERIES, (BLOCK, DIM)), 10 + step)
            assert idx.add(block) == sp.ErrorCode.Success
            ref.add(block)
            _, ids = idx.search_batch(q, K)
            np.testing.assert_array_equal(ids, ref.topk(q))
            assert idx.delete_rows(block)[1] == ref.delete(block) == BLOCK
            _, ids = idx.search_batch(q, K)
            np.testing.assert_array_equal(ids, ref.topk(q))
    assert log.count == 0, log.count
    assert metrics.counter_value("flat.block_grows") == grows
    assert metrics.gauge_value("flat.slots_reserved") == slots
    assert metrics.gauge_value("flat.rows_resident") == ROWS + 51 * BLOCK


@pytest.mark.parametrize("rows", [1, 100, BLOCK, 1500])
def test_a_mutation_sends_the_device_what_it_changed(rows):
    """Bytes host -> device follow the rows mutated, not the corpus: an
    add at most its rungs of rows + a byte of mask a row + the start, a
    delete four bytes a slot of its rungs."""
    idx = _index()
    idx.search_batch(QUERIES[:1], K)
    idx.add(_near(QUERIES[:1], 0))      # the growth is not a mutation's
    before = trace.report().get("flat.block_upload_bytes",
                                {"count": 0, "total_s": 0.0})
    block = _near(np.resize(QUERIES, (rows, DIM)), rows)
    idx.add(block)
    mid = trace.report()["flat.block_upload_bytes"]
    assert mid["count"] - before["count"] == rows
    pieces = flat._write_pieces(rows)
    padded = sum(rung for _, _, rung in pieces)
    assert padded == {1: 8, 100: 128, BLOCK: 128, 1500: 2048}[rows]
    assert [count for _, count, _ in pieces] \
        == [1024] * (rows // 1024) + [rows % 1024] * bool(rows % 1024)
    assert mid["total_s"] - before["total_s"] \
        == padded * (DIM * 4 + 1) + 4 * len(pieces)
    # the ISSUE's bound, for blocks that fill their rung
    if rows in (BLOCK,):
        assert mid["total_s"] - before["total_s"] \
            <= rows * (DIM * 4 + 8) + 64
    assert idx.delete_rows(block)[1] == rows
    after = trace.report()["flat.block_upload_bytes"]
    assert after["count"] - mid["count"] == rows
    assert after["total_s"] - mid["total_s"] == 4 * padded
    assert after["total_s"] - mid["total_s"] < ROWS * DIM      # not the corpus


def test_a_growth_keeps_every_id_and_answer():
    idx = _index()
    q = QUERIES[:8]
    ref = Reference(BASE)
    idx.search_batch(q, K)
    grows = metrics.counter_value("flat.block_grows")
    seen_slots = set()
    # past the first reserve (a sixteenth of the rows, at least 8192)
    # and into the second: 4 x 4000 rows in, some deleted on the way
    for step in range(4):
        block = _near(np.resize(QUERIES, (4000, DIM)), 50 + step)
        assert idx.add(block) == sp.ErrorCode.Success
        ref.add(block)
        if step == 1:
            assert idx.delete_rows(block[::3])[1] == ref.delete(block[::3])
        d, ids = idx.search_batch(q, K)
        np.testing.assert_array_equal(ids, ref.topk(q))
        seen_slots.add(metrics.gauge_value("flat.slots_reserved"))
    assert metrics.counter_value("flat.block_grows") - grows == 2
    assert len(seen_slots) == 2
    # every id still names its row: each streamed row finds itself
    for first in (ROWS, ROWS + 4000, ROWS + 12000):
        live = [i for i in range(first, first + 50) if not ref.dead[i]]
        d, ids = idx.search_batch(ref.rows[live], 1)
        assert list(ids[:, 0]) == live and (d[:, 0] <= 1e-3).all()
    # and the block, placed anew from the host rows, answers the same
    _, before = idx.search_batch(QUERIES, K)
    idx._dirty = True
    _, after = idx.search_batch(QUERIES, K)
    np.testing.assert_array_equal(before, after)


def test_a_growth_compiles_for_the_writer_not_for_the_next_search():
    idx = _index()
    for q in (1, 8):
        idx.search_batch(QUERIES[:q], K)
    idx.add(_near(QUERIES[:4], 3))              # grows: the scans re-run
    with recompile_guard.track_compiles("after_growth") as log:
        for q in (1, 8):
            idx.search_batch(QUERIES[:q], K)
    assert log.count == 0, log.count


def test_the_fused_scan_sees_a_write_in_place_and_a_growth():
    """PR 41: at the 128 rung the float scan takes its group minima from
    the Pallas kernel (interpret mode here) over the donated block as it
    lies: a search after a growth, after a write in place and after a
    delete takes the route again, answers from the new state, and the
    growth compiled the route's program for the writer."""
    from sptag_tpu.ops import pallas_kernels

    q = _near(np.resize(QUERIES, (128, DIM)), 41, sigma=0.05)
    names = ("flat.scan_fused_minima", "flat.scan_materialized",
             "flat.scan_margin_unproved", "flat.block_grows",
             "flat.block_updates")
    pallas_kernels.set_interpret(True)
    try:
        idx = _index()
        ref = Reference(BASE)
        assert flat.fused_minima(np.float32, 128, flat.pad_rows(ROWS), DIM, 1,
                                 0, "interpret")
        before = {n: metrics.counter_value(n) for n in names}
        np.testing.assert_array_equal(idx.search_batch(q, 1)[1],
                                      ref.topk(q, 1))
        first = _near(q[:64], 42, sigma=0.01)       # grows the block
        assert idx.add(first) == sp.ErrorCode.Success
        ref.add(first)
        with recompile_guard.track_compiles("fused_after_growth") as log:
            d, ids = idx.search_batch(q, 1)
        assert log.count == 0, log.count
        np.testing.assert_array_equal(ids, ref.topk(q, 1))
        assert (ids[:64, 0] >= ROWS).all()          # the rows just added
        second = _near(q[64:], 43, sigma=0.01)      # inside the reserve
        assert idx.add(second) == sp.ErrorCode.Success
        ref.add(second)
        d, ids = idx.search_batch(q, 1)
        np.testing.assert_array_equal(ids, ref.topk(q, 1))
        assert (ids[:, 0] >= ROWS).all()
        assert idx.delete_rows(first[:32])[1] == ref.delete(first[:32]) == 32
        d, ids = idx.search_batch(q, 1)
        np.testing.assert_array_equal(ids, ref.topk(q, 1))
        assert not np.isin(ids, np.arange(ROWS, ROWS + 32)).any()
        moved = {n: metrics.counter_value(n) - before[n] for n in names}
    finally:
        pallas_kernels.set_interpret(False)
    # four searches and the delete's own search by content (32 rows pad
    # to the 32 rung: materialised), one growth, three writes in place
    assert moved == {"flat.scan_fused_minima": 4,
                     "flat.scan_materialized": 1,
                     "flat.scan_margin_unproved": 0,
                     "flat.block_grows": 1, "flat.block_updates": 3}


def test_derived_caches_fall_back_to_a_fresh_block():
    """A sketch holds the block's arrays outside the lock: with one
    cached a mutation does not write in place (`_live`), and answers
    stay right."""
    idx = _index(SketchPrefilter="true", SketchRerank="2048")
    q = QUERIES[:4]
    idx.search_batch(q, K)
    assert idx._sketch is not None
    updates = metrics.counter_value("flat.block_updates")
    block = _near(q, 9)
    idx.add(block)
    assert metrics.counter_value("flat.block_updates") == updates
    _, ids = idx.search_batch(block, 1)
    assert list(ids[:, 0]) == list(range(ROWS, ROWS + 4))


# ------------------------------------------------------------- durability

def test_acknowledged_add_and_delete_survive_a_kill(tmp_path):
    """WalEnabled=1: serve, add and delete over the wire, drop the
    process's state without a save, `load_index`: the same answers, one
    log append an operation."""
    folder = str(tmp_path / "idx")
    assert _index(WalEnabled=1, WalFsync=1).save_index(folder) \
        == sp.ErrorCode.Success
    live = sp.load_index(folder)                # arms the log
    served = Served(live)
    try:
        client = served.client()
        appends = metrics.counter_value("mutation.wal_appends")
        a = _near(np.resize(QUERIES, (BLOCK, DIM)), 21)
        b = _near(np.resize(QUERIES, (BLOCK, DIM)), 22)
        ref = Reference(BASE)
        for op, block in (("add", a), ("add", b), ("delete", a)):
            assert _admin(client, op, block) == BLOCK
            ref.add(block) if op == "add" else ref.delete(block)
        assert metrics.counter_value("mutation.wal_appends") - appends == 3
        want = _search(client, QUERIES[:8])
        np.testing.assert_array_equal(want, ref.topk(QUERIES[:8]))
        client.close()
    finally:
        served.stop()
    del live, served                            # the kill: nothing saved
    again = sp.load_index(folder)
    assert again.num_samples == ROWS + 2 * BLOCK
    assert again.num_deleted == BLOCK
    _, ids = again.search_batch(QUERIES[:8], K)
    np.testing.assert_array_equal(ids, want)
