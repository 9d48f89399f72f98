"""Slot scheduler — continuous batching for the beam walk.

The monolithic walk (algo/engine.py) runs a whole (Q, ...) batch under one
`lax.while_loop` whose cond is `any(row_alive)`: every query pays for the
slowest query's iterations, so a MaxCheck=8192 straggler convoys 1023 fast
queries and device time tracks the MAX per-query iteration count.  This
module applies the inference-serving answer — continuous batching — to the
walk: queries occupy SLOTS in a fixed-shape state array, one compiled
segment program advances every resident row by at most `segment_iters`
walk iterations, and between segments the scheduler

* RETIRES rows whose `alive` flag dropped (their pool is final — the
  engine's absorbing-state contract, engine._walk_machine), resolving the
  per-query futures so callers stream results as queries finish;
* REFILLS freed slots from the pending queue (seeding refill buckets with
  the standalone seed kernel); and
* COMPACTS surviving rows into a smaller capacity bucket when occupancy
  drops and nothing is pending, so drain tails don't pay full-batch
  iteration cost.

Device time then tracks the MEAN per-query iteration count instead of the
max.  All shapes are quantized — slot capacity and refill sizes ride the
utils.QUERY_BUCKETS ladder, budgets ride per-row `t_limit` vectors — so a
warmed scheduler mints ZERO new XLA compiles (the recompile guard stays
quiet; tests/test_beam_segmented.py pins it).

Correctness: rows are per-query independent in the walk body, non-live
rows are bit-frozen, and seeding/segments/finalize share the monolithic
kernels' code verbatim — a scheduled query takes the SAME walk trajectory
as `engine.search` at the same (k, MaxCheck, beam_width, nbp) regardless
of what shares its slots, returning the same ids (the parity contract,
DESIGN.md §10).  One numerical caveat: refill buckets seed/score at
quantized batch shapes, and XLA tiles reductions per shape, so distances
can differ from the monolithic batch's in the last ulp; at equal shapes
(engine.search(segment_iters=...)) results are bit-identical, which
tests/test_beam_segmented.py pins.

Pools: one slot pool per (k_eff, L, B, nbp_limit, inject, seed-width)
static configuration; queries whose budgets agree on those share a pool
(and its compiled programs) with per-row iteration limits, which is how a
mixed-MaxCheck workload runs as ONE continuously batched stream.
"""

from __future__ import annotations

import collections
import logging
import threading
import time
from concurrent.futures import Future
from typing import Dict, List, Optional, Tuple

import numpy as np

from sptag_tpu.utils import (devmem, flightrec, hostprof, locksan, metrics,
                             query_bucket, recompile_guard)

log = logging.getLogger(__name__)

#: sentinel distance, shared with engine.py (module import must not pull
#: jax in — the scheduler is importable backend-free)
MAX_DIST = np.float32(3.4e38)

# ---------------------------------------------------------------------------
# mesh shard-skew telemetry (ISSUE 15): per-shard work from the mesh
# scheduler's (cap, n_shards) iteration counters, published as labeled
# series through the shared provider surface so /metrics exposes
# ``scheduler_shard_iters{shard=}`` and the timeline records its history
# ---------------------------------------------------------------------------

_skew_lock = locksan.make_lock("scheduler._skew_lock")
#: shard index -> mean resident iterations per live row (last cycle);
#: last-writer-wins across pools — one mesh scheduler per host in
#: practice, and the straggler picture is per-host anyway
_shard_iters: Dict[int, float] = {}


def _publish_shard_skew(pool: "_SlotPool", shards: int) -> None:
    """Per-shard work + skew gauges from one mesh pool's live rows.
    Called once per scheduler cycle (never per row) — host-side numpy
    over at most (cap, n_shards) ints."""
    live = [i for i, e in enumerate(pool.entries) if e is not None]
    if not live:
        return
    it = np.asarray(pool.state["it"])[live].reshape(len(live), shards)
    per_shard = it.sum(axis=0).astype(np.float64)
    mean = float(per_shard.mean())
    with _skew_lock:
        _shard_iters.clear()
        for s in range(shards):
            _shard_iters[s] = round(float(per_shard[s]) / len(live), 3)
    if mean > 0:
        # skew: straggler's excess over the mesh mean (0 = balanced).
        # The straggler is the shard with the MOST iterations — its
        # sub-walks converge last, so it holds every slot row hostage
        metrics.set_gauge("scheduler.shard_skew",
                          float(per_shard.max()) / mean - 1.0)
        metrics.set_gauge("scheduler.straggler_shard",
                          int(per_shard.argmax()))


def _shard_iter_families() -> List[metrics.Family]:
    with _skew_lock:
        if not _shard_iters:
            return []
        fam = metrics.Family(
            "scheduler.shard_iters",
            help="mean resident walk iterations per live slot row, "
                 "per mesh shard (straggler telemetry)")
        for s, v in sorted(_shard_iters.items()):
            fam.add(v, {"shard": str(s)})
    return [fam]


def reset_shard_skew() -> None:
    """Drop the published per-shard series (test isolation)."""
    with _skew_lock:
        _shard_iters.clear()


metrics.register_family_provider("mesh_skew", _shard_iter_families)


class SchedulerStopped(RuntimeError):
    """submit() after stop(), or the worker thread died."""


def pad_result_row(d: np.ndarray, ids: np.ndarray, k: int
                   ) -> Tuple[np.ndarray, np.ndarray]:
    """Pad one query's (k_eff,) results out to (k,) with the MAX_DIST /
    -1 sentinels — THE one row-pad implementation for the per-query
    future paths (gather_futures below and the streaming submit_batch
    wrappers)."""
    dd = np.full((k,), MAX_DIST, np.float32)
    ii = np.full((k,), -1, np.int32)
    kc = min(k, d.shape[0])
    dd[:kc] = d[:kc]
    ii[:kc] = ids[:kc]
    return dd, ii


def gather_futures(futs, k: int) -> Tuple[np.ndarray, np.ndarray]:
    """Resolve per-query (dists, ids) futures into search_batch's output
    contract: (Q, k) float32/int32, MAX_DIST / -1 padded.  THE one
    gather implementation, shared by BeamSlotScheduler.search_batch and
    the index-level ContinuousBatching branches."""
    out_d = np.zeros((len(futs), k), np.float32)
    out_i = np.zeros((len(futs), k), np.int32)
    for i, f in enumerate(futs):
        d, ids = f.result()
        out_d[i], out_i[i] = pad_result_row(d, ids, k)
    return out_d, out_i


class _Item:
    __slots__ = ("query", "seeds", "t_limit", "future", "t_enq", "rid",
                 "slot_wait", "segments", "refills")

    def __init__(self, query, seeds, t_limit, future, t_enq, rid=""):
        self.query = query
        self.seeds = seeds
        self.t_limit = t_limit
        self.future = future
        self.t_enq = t_enq
        # flight-recorder attribution (ISSUE 5): the request id this
        # query rides under, plus the per-query lifecycle numbers the
        # slow-query log and flight dump both report — time queued before
        # a slot opened, device segments resident, refill batches that
        # joined the pool while resident
        self.rid = rid
        self.slot_wait = 0.0
        self.segments = 0
        self.refills = 0


class _SlotPool:
    """Host-side slot state for one static walk configuration.

    State arrays live as numpy between segments (insert / retire /
    compact are plain fancy indexing); each segment call round-trips
    them through the device.  Capacity rides the QUERY_BUCKETS ladder so
    every distinct shape the device sees is a quantized bucket."""

    def __init__(self, key, engine, seg_iters: int, slots: int):
        self.key = key
        (self.k_eff, self.L, self.B, self.nbp_limit, self.inject,
         self.seed_width) = key
        self.engine = engine
        self.seg_iters = seg_iters
        self.max_slots = slots
        self.capacity = 0
        self.entries: List[Optional[_Item]] = []
        self.state: Dict[str, np.ndarray] = {}
        self.t_limit = np.zeros((0,), np.int32)

    # ---- state plumbing ---------------------------------------------------

    def live_count(self) -> int:
        return sum(e is not None for e in self.entries)

    def _blank_rows(self, idx) -> None:
        """Reset slots `idx` to the canonical empty-row encoding: t_limit=0
        (never alive — the segment kernel's no-op row), -1/MAX_DIST pools.
        The `...` in the expanded dump-slot write covers both state
        layouts: (cap, L+1) single-chip and (cap, n_shards, L+1) mesh
        (parallel/mesh_engine.py — one slot row spans every shard)."""
        s = self.state
        s["cand_ids"][idx] = -1
        s["cand_d"][idx] = MAX_DIST
        s["expanded"][idx] = True
        s["expanded"][idx, ..., self.L] = False
        s["visited"][idx] = 0
        s["no_better"][idx] = 0
        s["ptr"][idx] = 0
        s["it"][idx] = 0
        self.t_limit[idx] = 0
        s["queries"][idx] = 0
        if s.get("spare_ids") is not None:
            s["spare_ids"][idx] = -1
            s["spare_d"][idx] = MAX_DIST

    def _alloc(self, capacity: int, like: Dict[str, np.ndarray]) -> None:
        """(Re)allocate the slot arrays at `capacity`, moving live rows to
        the FRONT (the compaction step).  `like` supplies dtypes/widths —
        either a previous state or a freshly seeded bucket."""
        old_state, old_entries = self.state, self.entries
        old_tl = self.t_limit
        self.state = {
            name: np.zeros((capacity,) + arr.shape[1:], arr.dtype)
            for name, arr in like.items() if arr is not None}
        if like.get("spare_ids") is None:
            self.state["spare_ids"] = None
            self.state["spare_d"] = None
        self.t_limit = np.zeros((capacity,), np.int32)
        self.entries = [None] * capacity
        self.capacity = capacity
        # device-memory ledger: the pool's slot-state footprint (these
        # arrays round-trip through the device every segment); re-tracked
        # at every grow/compact so the gauge follows occupancy
        devmem.track("slot_pool", self,
                     sum(a.nbytes for a in self.state.values()
                         if a is not None) + self.t_limit.nbytes,
                     host=True)
        self._blank_rows(slice(None))
        if old_entries:
            src = [i for i, e in enumerate(old_entries) if e is not None]
            dst = list(range(len(src)))
            for name, arr in old_state.items():
                if arr is not None:
                    self.state[name][dst] = arr[src]
            self.t_limit[dst] = old_tl[src]
            for d, s_i in zip(dst, src):
                self.entries[d] = old_entries[s_i]

    def target_capacity(self, incoming: int) -> int:
        need = max(self.live_count() + incoming, 1)
        return query_bucket(min(need, self.max_slots), self.max_slots)


@locksan.race_track
class BeamSlotScheduler:
    """Continuous-batching front end over one GraphSearchEngine snapshot.

    `submit()` returns a `concurrent.futures.Future` resolving to
    `(dists (k_eff,), ids (k_eff,))` for that query; `search_batch()` is
    the submit-all-and-wait convenience with engine.search's output
    contract.  One daemon worker thread owns all device work; submitters
    only touch the pending queue.  Thread-safe; locks are lock-sanitizer
    wrapped (utils/locksan.py)."""

    def __init__(self, engine, slots: int = 1024, segment_iters: int = 0,
                 name: str = "beam-sched"):
        self._engine = engine
        self._slots = max(1, min(slots, engine.chunk_size()))
        self._segment_iters = segment_iters
        self._lock = locksan.make_lock("BeamSlotScheduler._lock")
        self._cv = threading.Condition(self._lock)
        self._pending: Dict[tuple, collections.deque] = {}
        self._pools: Dict[tuple, _SlotPool] = {}
        self._stopped = False
        self._draining = False
        self._worker_error: Optional[BaseException] = None
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name=name)
        self._thread.start()

    # ---- submission surface ----------------------------------------------

    def submit(self, query: np.ndarray, k: int, max_check: int,
               beam_width: int = 16, pool_size: Optional[int] = None,
               nbp_limit: int = 3, dynamic_pivots: int = 4,
               seeds: Optional[np.ndarray] = None,
               rid: str = "") -> Future:
        """Queue one query; the future resolves to (dists, ids) — the
        same values `engine.search` would return for it, bit for bit.
        `rid` tags the query's flight-recorder events and per-rid stats
        (slot-wait / segments / refills) for the slow-query log."""
        k_eff, L, B, T, limit = self._engine.walk_plan(
            k, max_check, beam_width, pool_size, nbp_limit)
        seeds_row = None
        seed_width = -1
        if seeds is not None:
            seeds_row = np.asarray(seeds, np.int32).reshape(-1)
            seed_width = seeds_row.shape[0]
            inject = 0
        else:
            inject = dynamic_pivots
        key = (k_eff, L, B, limit, inject, seed_width)
        fut: Future = Future()
        item = _Item(np.asarray(query).reshape(-1), seeds_row,
                     T, fut, time.perf_counter(), rid=rid)
        if flightrec.enabled():
            flightrec.record("scheduler", "pending", rid,
                             payload={"max_check": max_check})
        with self._cv:
            if (self._stopped or self._draining
                    or self._worker_error is not None):
                raise SchedulerStopped(
                    f"scheduler is stopped ({self._worker_error!r})")
            self._pending.setdefault(key, collections.deque()).append(item)
            metrics.set_gauge("scheduler.pending", self._pending_count())
            self._cv.notify()
        metrics.inc("scheduler.submitted")
        return fut

    def search_batch(self, queries: np.ndarray, k: int, max_check: int,
                     beam_width: int = 16, pool_size: Optional[int] = None,
                     nbp_limit: int = 3, dynamic_pivots: int = 4,
                     seeds: Optional[np.ndarray] = None,
                     rids: Optional[List[str]] = None
                     ) -> Tuple[np.ndarray, np.ndarray]:
        """Submit a whole (Q, D) batch and wait; engine.search's output
        contract ((Q, k) dists/ids, MAX_DIST / -1 padded)."""
        queries = np.asarray(queries)
        if queries.ndim == 1:
            queries = queries[None, :]
        futs = [self.submit(queries[i], k, max_check,
                            beam_width=beam_width, pool_size=pool_size,
                            nbp_limit=nbp_limit,
                            dynamic_pivots=dynamic_pivots,
                            seeds=None if seeds is None else seeds[i],
                            rid=rids[i] if rids else "")
                for i in range(queries.shape[0])]
        return gather_futures(futs, k)

    def stats(self) -> Dict[str, int]:
        """Live/pending/capacity snapshot — the no-slot-leak probe the
        hammer test asserts on after a drain."""
        with self._lock:
            return {
                "live": sum(p.live_count() for p in self._pools.values()),
                "pending": self._pending_count(),
                "capacity": sum(p.capacity for p in self._pools.values()),
                "pools": len(self._pools),
            }

    def retire(self) -> None:
        """Stop accepting NEW queries but let everything already pending
        or resident finish; the worker exits on its own once drained (no
        join).  This is the snapshot-swap path: a superseded scheduler
        keeps walking its in-flight queries on the old engine snapshot —
        exactly like monolithic searches that were already executing —
        while the replacement serves new traffic."""
        with self._cv:
            already = self._draining
            self._draining = True
            self._cv.notify()
            resident = (sum(p.live_count() for p in self._pools.values())
                        + self._pending_count())
        if not already:
            # swap-drain observability (ISSUE 9): how many schedulers a
            # mutation stream retired and how much work each drained —
            # the serve-tier witness that a snapshot swap dropped nothing
            metrics.inc("scheduler.retired_schedulers")
            if flightrec.enabled():
                flightrec.record("scheduler", "retire_drain",
                                 payload={"resident": resident})

    def stop(self) -> None:
        """Stop the worker and fail outstanding queries with
        SchedulerStopped (idempotent).  The engine snapshot is untouched."""
        with self._cv:
            self._stopped = True
            self._cv.notify()
        self._thread.join(timeout=30.0)
        if self._thread.is_alive():       # pragma: no cover - wedged device
            metrics.inc("scheduler.leaked_workers")
            log.warning("scheduler worker still running after stop join")
        # worker is gone: fail whatever it left behind
        leftovers: List[_Item] = []
        with self._lock:
            for dq in self._pending.values():
                leftovers.extend(dq)
                dq.clear()
            for pool in self._pools.values():
                leftovers.extend(e for e in pool.entries if e is not None)
                pool.entries = [None] * pool.capacity
                devmem.untrack(pool)
        for item in leftovers:
            if not item.future.done():
                item.future.set_exception(
                    SchedulerStopped("scheduler stopped"))

    # ---- internals --------------------------------------------------------

    def _pending_count(self) -> int:
        return sum(len(dq) for dq in self._pending.values())

    def _has_work_locked(self) -> bool:
        return (self._pending_count() > 0
                or any(p.live_count() for p in self._pools.values()))

    def _run(self) -> None:
        try:
            while True:
                with self._cv:
                    while not self._stopped and not self._has_work_locked():
                        if self._draining:
                            # retired + drained: release the pools' ledger
                            # entries eagerly — the scheduler object may
                            # be referenced long after its last query
                            for pool in self._pools.values():
                                devmem.untrack(pool)
                            return        # exit clean
                        self._cv.wait(timeout=1.0)
                    if self._stopped:
                        return
                    # move pending items into their pools' intake under
                    # the lock; device work happens outside it
                    intake: Dict[tuple, List[_Item]] = {}
                    for key, dq in self._pending.items():
                        pool = self._pools.get(key)
                        if pool is None:
                            pool = self._make_pool(key, dq[0].t_limit)
                            self._pools[key] = pool
                        free = pool.max_slots - pool.live_count()
                        take = min(free, len(dq))
                        if take:
                            intake[key] = [dq.popleft()
                                           for _ in range(take)]
                    metrics.set_gauge("scheduler.pending",
                                      self._pending_count())
                    active_pools = [p for p in self._pools.values()
                                    if p.live_count()
                                    or intake.get(p.key)]
                for pool in active_pools:
                    self._cycle(pool, intake.get(pool.key, []))
        except BaseException as e:      # noqa: BLE001 - worker must report
            log.exception("scheduler worker died")
            with self._cv:
                self._worker_error = e
                self._stopped = True
            metrics.inc("scheduler.worker_errors")
            # fail everything in flight so no caller blocks forever
            with self._lock:
                items = [i for dq in self._pending.values() for i in dq]
                for dq in self._pending.values():
                    dq.clear()
                for pool in self._pools.values():
                    items.extend(e for e in pool.entries if e is not None)
                    pool.entries = [None] * pool.capacity
            for item in items:
                if not item.future.done():
                    item.future.set_exception(e)

    def _make_pool(self, key, first_t: int) -> _SlotPool:
        seg = self._segment_iters
        if seg <= 0:
            # auto: quarter of the first submitter's budget — segments
            # short enough that retire/refill bites, long enough that the
            # per-segment fixed cost (state round trip, finalize) amortizes
            seg = max(1, -(-first_t // 4))
        return _SlotPool(key, self._engine, seg, self._slots)

    def _cycle(self, pool: _SlotPool, incoming: List[_Item]) -> None:
        import jax.numpy as jnp

        engine = self._engine
        now = time.perf_counter()
        rec = flightrec.enabled()
        if hostprof.armed():
            # host-profiler stage pin (ISSUE 10): everything this worker
            # thread does — seeding, segment dispatch, finalize, retire
            # bookkeeping — is execute-stage serve work.  Re-pinned per
            # cycle (one dict store) so a profiler armed mid-flight
            # attributes the very next cycle; never cleared — the worker
            # does nothing else.
            hostprof.set_stage("execute")
        # ---- resize (grow for intake / compact a drained pool) ----------
        target = pool.target_capacity(len(incoming))
        residents = pool.live_count()
        if incoming and residents:
            # refill: a pool that already had live rows takes on a fresh
            # intake batch — count it against every RESIDENT query
            # (newcomers join after) for per-rid attribution
            for e in pool.entries:
                if e is not None:
                    e.refills += 1
            if rec:
                flightrec.record("scheduler", "refill",
                                 payload={"count": len(incoming),
                                          "live": residents})
        if incoming and pool.capacity == 0:
            # first allocation needs dtype/width templates: seed one
            # bucket first, then allocate from it
            seeded = self._seed_bucket(pool, incoming)
            pool._alloc(target, seeded)
            self._insert(pool, incoming, seeded)
        else:
            if target != pool.capacity:
                if rec and target < pool.capacity and residents:
                    flightrec.record("scheduler", "compact",
                                     payload={"from": pool.capacity,
                                              "to": target})
                pool._alloc(target, pool.state)
            if incoming:
                seeded = self._seed_bucket(pool, incoming)
                self._insert(pool, incoming, seeded)
        for item in incoming:
            item.slot_wait = now - item.t_enq
            metrics.observe("scheduler.slot_wait", item.slot_wait)
            if rec:
                flightrec.record("scheduler", "slot_assign", item.rid,
                                 dur_ns=int(item.slot_wait * 1e9))
        metrics.set_gauge("scheduler.occupancy",
                          pool.live_count() / max(pool.capacity, 1))
        if not pool.live_count():
            return
        # ---- one segment on device --------------------------------------
        # hot_section: the trace sentinel's guarded region — implicit
        # device->host readbacks in here are violations, and every XLA
        # compile is charged to the "scheduler.cycle" budget (zero after
        # warmup: pools key on (k_eff, L, B, limit), t_limit is traced)
        t_seg0 = time.monotonic_ns() if rec else 0
        seg_guard = recompile_guard.hot_section("scheduler.cycle")
        with seg_guard:
            state = {name: (jnp.asarray(arr) if arr is not None else None)
                     for name, arr in pool.state.items()}
            new_state, alive = engine.run_segment(
                state, jnp.asarray(pool.t_limit), pool.k_eff, pool.L,
                pool.B, pool.nbp_limit, pool.seg_iters,
                inject=pool.inject)
            alive_host = recompile_guard.device_get(alive)
            host_state = {
                name: np.array(recompile_guard.device_get(new_state[name]))
                for name in ("cand_ids", "cand_d", "expanded", "visited",
                             "no_better", "ptr", "it")}
        metrics.inc("scheduler.segments")
        # shard-axis accounting (mesh engines, parallel/mesh_engine.py):
        # one mesh segment advances the walk on EVERY shard at once, so
        # the device-work counter scales by the shard count and the
        # admission controller's occupancy/slot-wait signals — read from
        # the same scheduler gauges — are mesh-wide by construction
        shards = int(getattr(engine, "n_shards", 1))
        if shards > 1:
            metrics.inc("scheduler.shard_segments", shards)
            metrics.set_gauge("scheduler.mesh_shards", shards)
        live_now = 0
        for e in pool.entries:
            if e is not None:
                e.segments += 1
                live_now += 1
        if rec:
            flightrec.record("scheduler", "segment",
                             dur_ns=time.monotonic_ns() - t_seg0,
                             payload={"live": live_now,
                                      "capacity": pool.capacity})
        alive_np = alive_host
        done = [i for i, e in enumerate(pool.entries)
                if e is not None and not alive_np[i]]
        for name in ("cand_ids", "cand_d", "expanded", "visited",
                     "no_better", "ptr", "it"):
            # np.array (in host_state above), not a bare device_get:
            # device arrays export as READ-ONLY host views, and
            # blank/insert mutate these in place
            pool.state[name] = host_state[name]
        if shards > 1:
            # mesh skew telemetry (ISSUE 15): per-shard work + straggler
            # gauges from the fresh (cap, n_shards) iteration counters
            _publish_shard_skew(pool, shards)
        # ---- retire ------------------------------------------------------
        if done:
            # finalize ONLY the retiring rows, gathered to a bucketed
            # sub-batch: running the rerank/top-k epilogue over the whole
            # capacity every cycle was the dominant per-cycle overhead
            Rb = query_bucket(len(done), pool.capacity)
            rows = np.asarray(done + [done[0]] * (Rb - len(done)))
            with recompile_guard.hot_section("scheduler.finalize"):
                sub = {name: jnp.asarray(pool.state[name][rows])
                       for name in ("queries", "cand_ids", "cand_d")}
                d, ids = engine.finalize(sub, pool.k_eff)
            t_done = time.perf_counter()
            items = [pool.entries[i] for i in done]
            # np.max covers the mesh layout ((cap, n_shards) counters —
            # device residency tracks the slowest shard's walk)
            iters_done = [int(np.max(pool.state["it"][i])) for i in done]
            for i in done:
                pool.entries[i] = None
            # publish EVERY observation for the retiring queries BEFORE
            # resolving any future (ISSUE 5 satellite): a caller sampling
            # metrics or flight stats at result time must find this
            # query's numbers already recorded — previously the retired
            # counter landed after the futures, so completion-triggered
            # dumps undercounted the very query that triggered them
            metrics.inc("scheduler.retired", len(done))
            if shards > 1:
                # retire frees one slot row PER SHARD: the per-axis twin
                # of scheduler.retired for mesh capacity accounting
                metrics.inc("scheduler.shard_retired", len(done) * shards)
            for j, item in enumerate(items):
                metrics.observe("scheduler.query_s", t_done - item.t_enq)
                if rec:
                    flightrec.record(
                        "scheduler", "retire", item.rid,
                        dur_ns=int((t_done - item.t_enq) * 1e9),
                        payload={"segments": item.segments,
                                 "refills": item.refills})
                if item.rid:
                    # iters vs t_budget is the quality monitor's triage
                    # input (utils/qualmon.py classify_low_recall):
                    # iters == budget means the walk was CUT OFF by
                    # MaxCheck ("beam terminated early"), so both ride
                    # the stats.
                    # _replace=True: retire OWNS the query lifecycle —
                    # a client-reused rid must not inherit the previous
                    # query's verdict keys (flightrec merge semantics;
                    # later annotators like qualmon merge)
                    stats = dict(
                        _replace=True,
                        slot_wait_ms=round(item.slot_wait * 1000.0, 3),
                        segments=item.segments, refills=item.refills,
                        iters=iters_done[j], t_budget=int(item.t_limit))
                    if shards > 1:
                        # per-query shard skew (ISSUE 15): the row's own
                        # per-shard iteration counters — qualmon's
                        # classify_low_recall turns a straggler-dominated
                        # budget exhaustion into a "shard_skew" verdict
                        # naming the shard
                        row_it = np.asarray(
                            pool.state["it"][done[j]]).reshape(-1)
                        row_mean = float(row_it.mean())
                        if row_mean > 0:
                            stats["shard_imbalance"] = round(
                                float(row_it.max()) / row_mean, 3)
                            stats["slow_shard"] = int(row_it.argmax())
                    flightrec.note_query_stats(item.rid, **stats)
            for j, item in enumerate(items):
                if not item.future.done():
                    item.future.set_result((d[j].copy(), ids[j].copy()))
            self._blank(pool, done)
        metrics.set_gauge("scheduler.occupancy",
                          pool.live_count() / max(pool.capacity, 1))

    @staticmethod
    def _blank(pool: _SlotPool, idx: List[int]) -> None:
        pool._blank_rows(np.asarray(idx, np.int64))

    def _seed_bucket(self, pool: _SlotPool,
                     incoming: List[_Item]) -> Dict[str, np.ndarray]:
        """Seed `incoming` queries at a QUERY_BUCKETS-quantized batch shape
        and return the host copies of the seeded state rows."""
        import jax.numpy as jnp

        engine = self._engine
        R = len(incoming)
        Rb = query_bucket(R, pool.max_slots)
        D = incoming[0].query.shape[0]
        q = np.zeros((Rb, D), incoming[0].query.dtype)
        for i, item in enumerate(incoming):
            q[i] = item.query
        seeds = None
        if pool.seed_width >= 0:
            seeds = np.full((Rb, pool.seed_width), -1, np.int32)
            for i, item in enumerate(incoming):
                seeds[i] = item.seeds
        with recompile_guard.hot_section("scheduler.seed"):
            if seeds is not None:
                seeds = jnp.asarray(seeds)
            seeded = engine.seed_state(jnp.asarray(q), pool.L, seeds=seeds)
            # np.array: seeded rows are mutated in place by _insert
            return {name: (np.array(recompile_guard.device_get(arr))
                           if arr is not None else None)
                    for name, arr in seeded.items()}

    @staticmethod
    def _insert(pool: _SlotPool, incoming: List[_Item],
                seeded: Dict[str, np.ndarray]) -> None:
        free = [i for i, e in enumerate(pool.entries) if e is None]
        assert len(free) >= len(incoming), "intake exceeded free slots"
        for row, item in enumerate(incoming):
            slot = free[row]
            for name, arr in pool.state.items():
                if arr is not None:
                    arr[slot] = seeded[name][row]
            pool.t_limit[slot] = item.t_limit
            pool.entries[slot] = item
