"""Delta shard — fresh vectors searchable in O(ms) without re-linking.

SPTAG's AddIndex pays an AddCEF-budget graph search + RNG prune per
appended row (BKTIndex.cpp:462-529) INLINE in the mutation path, and the
TPU port additionally invalidates the immutable engine snapshot, so the
next search pays a full device re-materialization.  TPU-KNN (arXiv
2206.14286, PAPERS.md) shows small dense scans run at near-peak MXU
throughput — which is exactly why a FLAT-scanned side index for the
freshest rows is cheap enough to merge into EVERY query:

* appended rows land in a bounded host buffer (``DeltaShardCapacity``)
  whose device snapshot is a fixed-shape padded block — ONE compiled
  scan shape for the shard's whole lifetime;
* every search runs the main engine over its frozen coverage
  ``[0, base_id)`` plus the exact delta scan over ``[base_id, n)`` and
  merges the two top-k lists (the KBest coarse-scan + exact-shortlist
  union shape, arXiv 2508.03016) — ids are disjoint by construction;
* tombstones mask BOTH tiers: the engine keeps its own mask, the delta
  reads the owner's global mask at query time (a (capacity,) bool
  upload — no dirty tracking, no snapshot rebuild per delete);
* a background refine (algo/bkt.py) links the delta rows into the graph
  off-thread and atomically swaps a new engine in, advancing
  ``base_id`` — the shard never grows past its bound.

The scan rides :func:`sptag_tpu.algo.flat.exact_device_scan`: the
``flat.scan`` kernel family, no jit site of the shard's own.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np

from sptag_tpu.utils import devmem, locksan, round_up

#: sentinel distance (core/index.py MAX_DIST; kept a local scalar so the
#: module imports backend-free)
_MAX_DIST = np.float32(3.4e38)

_ROW_PAD = 128      # TPU lane width, same ladder as algo/flat.py


@locksan.race_track
class DeltaShard:
    """Bounded side index for rows appended after the engine snapshot.

    Thread contract: ``append`` runs under the owner VectorIndex's
    writer lock; ``search`` runs lock-free from any reader.  The host
    buffer is preallocated at capacity (appends never realloc), `count`
    is read once per search, and the device snapshot is republished as
    one atomic attribute — readers see either the old or the new
    (count, arrays) tuple, never a torn pair."""

    def __init__(self, base_id: int, dim: int, dtype, capacity: int,
                 metric: int, base: int):
        self.base_id = int(base_id)
        self.capacity = int(capacity)
        self.metric = int(metric)
        self.base = int(base)
        self._pad = max(_ROW_PAD, round_up(self.capacity, _ROW_PAD))
        self._rows = np.zeros((self._pad, dim), np.dtype(dtype))
        self.count = 0
        # (count, data_d, sqnorm_d) republished atomically
        self._device: Optional[tuple] = None
        # serializes the lazy snapshot rebuild below: searchers race to
        # fill the cache (the owner lock is deliberately NOT held on
        # the search path), and without this two threads upload the
        # same buffer twice and publish with no common lock (GL801/
        # racesan).  Leaf lock — never nested.
        self._cache_lock = locksan.make_lock("DeltaShard._cache_lock")

    def append(self, data: np.ndarray, begin: int) -> None:
        """Append prepared rows whose global ids start at `begin`
        (owner-lock held).  The shard is the TAIL of the id space:
        `begin` must continue it exactly."""
        assert begin == self.base_id + self.count, \
            (begin, self.base_id, self.count)
        n = data.shape[0]
        assert self.count + n <= self.capacity, "delta shard overflow"
        self._rows[self.count:self.count + n] = data
        self.count += n

    def _snapshot(self) -> tuple:
        """(count, data_d, sqnorm_d) — rebuilt when appends outran the
        cached copy.  The (pad, D) shape is FIXED, so the scan kernel
        compiles once; a full-buffer re-upload per append batch is a
        few MB at most (bounded by capacity)."""
        snap = self._device
        if snap is not None and snap[0] == self.count:
            return snap
        with self._cache_lock:
            snap = self._device            # double-checked: a racing
            count = self.count             # filler may have finished
            if snap is not None and snap[0] == count:
                return snap
            import jax.numpy as jnp

            from sptag_tpu.ops import distance as dist_ops

            data_d = jnp.asarray(self._rows)
            sqnorm_d = dist_ops.row_sqnorms(data_d)
            snap = (count, data_d, sqnorm_d)
            devmem.track("delta_shard", self,
                         data_d.nbytes + sqnorm_d.nbytes)
            self._device = snap
            return snap

    def search(self, queries: np.ndarray, k: int,
               deleted: Optional[np.ndarray]
               ) -> Tuple[np.ndarray, np.ndarray]:
        """Exact masked scan over the shard; ((Q, k) dists, (Q, k)
        GLOBAL int32 ids), ascending, MAX_DIST / -1 padded.  `deleted`
        is the owner's full tombstone mask (global ids); rows beyond
        `count` and tombstoned rows are masked."""
        from sptag_tpu.algo.flat import exact_device_scan
        import jax.numpy as jnp

        count, data_d, sqnorm_d = self._snapshot()
        invalid = np.ones(self._pad, bool)
        if deleted is not None and len(deleted) >= self.base_id + count:
            invalid[:count] = deleted[self.base_id:self.base_id + count]
        else:
            invalid[:count] = False
        k_eff = max(1, min(k, count))
        d, ids = exact_device_scan(data_d, sqnorm_d, jnp.asarray(invalid),
                                   queries, k_eff, self.metric, self.base)
        ids = np.where(ids >= 0, ids + np.int32(self.base_id),
                       np.int32(-1))
        return d, ids

    def rebased(self, new_base: int, tail_rows: Optional[np.ndarray]
                ) -> Optional["DeltaShard"]:
        """A fresh shard holding only the rows at/after `new_base` —
        the swap path's handoff (rows absorbed into the new engine
        leave the shard; rows appended during the background build stay
        delta).  None when nothing remains."""
        if tail_rows is None or tail_rows.shape[0] == 0:
            devmem.untrack(self)
            return None
        out = DeltaShard(new_base, self._rows.shape[1], self._rows.dtype,
                         self.capacity, self.metric, self.base)
        out.append(np.asarray(tail_rows), new_base)
        devmem.untrack(self)
        return out


def merge_topk(d_main: np.ndarray, i_main: np.ndarray,
               d_delta: np.ndarray, i_delta: np.ndarray, k: int
               ) -> Tuple[np.ndarray, np.ndarray]:
    """Union-merge two ascending top-k lists into one (Q, k) result —
    the delta/main result union (and the shape KBest validates for
    coarse+exact merges).  Duplicate ids keep their best distance: the
    tiers' id ranges are disjoint in steady state, but a swap landing
    between the two scans may briefly cover a row twice."""
    d = np.concatenate([np.asarray(d_main, np.float32),
                        np.asarray(d_delta, np.float32)], axis=1)
    i = np.concatenate([np.asarray(i_main, np.int32),
                        np.asarray(i_delta, np.int32)], axis=1)
    order = np.argsort(d, axis=1, kind="stable")
    d = np.take_along_axis(d, order, axis=1)
    i = np.take_along_axis(i, order, axis=1)
    # duplicate suppression: rows are distance-sorted, so a stable
    # id-sort keeps the BEST occurrence first within each id run
    ido = np.argsort(i, axis=1, kind="stable")
    si = np.take_along_axis(i, ido, axis=1)
    dup_sorted = np.zeros_like(si, bool)
    dup_sorted[:, 1:] = (si[:, 1:] == si[:, :-1]) & (si[:, 1:] >= 0)
    dup = np.zeros_like(dup_sorted)
    np.put_along_axis(dup, ido, dup_sorted, axis=1)
    d = np.where(dup, _MAX_DIST, d)
    i = np.where(dup, np.int32(-1), i)
    order = np.argsort(d, axis=1, kind="stable")
    d = np.take_along_axis(d, order, axis=1)[:, :k]
    i = np.take_along_axis(i, order, axis=1)[:, :k]
    if d.shape[1] < k:
        q = d.shape[0]
        d = np.concatenate(
            [d, np.full((q, k - d.shape[1]), _MAX_DIST, np.float32)],
            axis=1)
        i = np.concatenate(
            [i, np.full((q, k - i.shape[1]), -1, np.int32)], axis=1)
    return d, i.astype(np.int32)
