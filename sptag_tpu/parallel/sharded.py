"""Sharded (multi-chip) search over a jax.sharding.Mesh.

This is the TPU-native replacement for the reference's distributed serving
topology (SURVEY.md §2b P6 / §2c): where SPTAG runs one index per server
process and an Aggregator that scatters each query over TCP and flat-merges
the per-server result lists (/root/reference/AnnService/src/Aggregator/
AggregatorService.cpp:206-366), here each device in the mesh holds one shard
of the corpus as a `jax.Array` and the scatter + per-shard search + top-k
merge is ONE compiled program: `shard_map` over the 'shard' axis, per-shard
local top-k, `all_gather` of the (k, id) candidates over ICI, and a final
`lax.top_k` re-rank.  (The merge is actually stronger than the reference's:
the Aggregator concatenates per-index lists without a global re-rank —
clients re-rank; here the global top-k comes back already merged.)

Across hosts the same program runs under multi-host jax.distributed over DCN;
nothing in this module changes.
"""

from __future__ import annotations

import functools
import json
import os
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from sptag_tpu.algo.flat import (FlatIndex, _block_grown, _block_mask_rows,
                                 _block_write_rows, _write_pieces,
                                 count_route, count_select, count_unproved,
                                 pad_rows, pad_to_bucket, proved_form,
                                 reserved_slots, scan_route, scan_topk)
from sptag_tpu.core.index import MAX_DIST
from sptag_tpu.core.types import DistCalcMethod, value_type_of
from sptag_tpu.ops import distance as dist_ops
from sptag_tpu.ops import topk_bins
from sptag_tpu.utils import (devmem, locksan, metrics, round_up,
                             trace)

SHARD_AXIS = "shard"


def make_mesh(devices=None, axis_name: str = SHARD_AXIS) -> Mesh:
    if devices is None:
        devices = jax.devices()
    return Mesh(np.asarray(devices), (axis_name,))


def _pad_to_k(d: np.ndarray, ids: np.ndarray, k: int, k_final: int):
    """Host-side sentinel padding of merged results out to k columns."""
    if k_final < k:
        q = d.shape[0]
        d = np.concatenate(
            [d, np.full((q, k - k_final), MAX_DIST, np.float32)], 1)
        ids = np.concatenate(
            [ids, np.full((q, k - k_final), -1, np.int32)], 1)
    return d, ids


def _gather_merge(d, gids, k_final: int):
    """In-kernel global merge: ICI all-gather of every shard's (dist,
    global-id) top-k, then one re-ranking top_k; sentinel rows -> -1."""
    all_d = jax.lax.all_gather(d, SHARD_AXIS, axis=1, tiled=True)
    all_i = jax.lax.all_gather(gids, SHARD_AXIS, axis=1, tiled=True)
    gneg, gpos = jax.lax.top_k(-all_d, k_final)
    gd = -gneg
    gi = jnp.take_along_axis(all_i, gpos, axis=1)
    return gd, jnp.where(gd >= jnp.float32(MAX_DIST), -1, gi)


class MeshTopK(NamedTuple):
    """What the mesh FLAT program returns: (Q, k) distances and GLOBAL row
    ids, replicated.  Field names of its own, as `DeviceTopK`'s are the
    one-chip programs': they reach the StableHLO and so the persistent
    compile cache's key, which the scope names alone do not
    (core/types.py `DeviceTopK`)."""

    merged_dists: jax.Array
    global_ids: jax.Array


class ProvedMeshTopK(NamedTuple):
    """`MeshTopK` and the run's flag: the mesh program's answer on the
    proved route (float32 L2 rows, `flat._proved_scan`)."""

    merged_dists: jax.Array
    global_ids: jax.Array
    unproved: jax.Array


@functools.partial(jax.jit,
                   static_argnames=("k_local", "k_final", "metric", "base",
                                    "mesh", "row_stride", "fused",
                                    "interpret"))
def _sharded_search_kernel(data, sqnorm, invalid, queries, k_local: int,
                           k_final: int, metric: int, base: int, mesh: Mesh,
                           row_stride: Optional[int] = None,
                           fused: bool = False, interpret: bool = False):
    """One program: per shard the one-chip scan body (`algo/flat.py
    scan_topk`: distances, mask, local top-k_local), then under scope
    `mesh.merge` the ICI all-gather of the (dist, global-id) candidates
    and the global top-k_final re-rank.  `row_stride`: the corpus rows a
    shard stands for, where its device block is padded beyond them
    (absent: the block's own row count).  `fused` / `interpret`: the
    exact select's route in every shard (`flat.fused_minima` of one
    shard's block, decided by the caller)."""

    def local_search(data_s, sqnorm_s, invalid_s, q_s):
        d, ids, *flag = scan_topk(data_s, sqnorm_s, invalid_s, q_s, k_local,
                                  metric, base, fused=fused,
                                  interpret=interpret)
        # the merge is what a trace calls the mesh's own stage (benchmark
        # kernel.mesh_merge_ms_per_batch reads `mesh.merge`)
        with jax.named_scope("mesh.merge"):
            shard = jax.lax.axis_index(SHARD_AXIS)
            gids = jnp.where(
                ids >= 0, ids + shard * (row_stride or data_s.shape[0]), -1)
            # a shard's `unproved` (the proved route: each shard answers
            # from its own materialised scores where its own proof
            # failed): any shard's, on every shard
            return _gather_merge(d, gids, k_final) + tuple(
                jax.lax.pmax(f.astype(jnp.int32), SHARD_AXIS) > 0
                for f in flag)

    proved = proved_form(fused, data.dtype)
    out = shard_map(
        local_search,
        mesh=mesh,
        in_specs=(P(SHARD_AXIS, None), P(SHARD_AXIS), P(SHARD_AXIS),
                  P(None, None)),
        out_specs=(P(None, None), P(None, None)) + (P(),) * proved,
        # outputs are replicated by construction (all_gather + identical
        # top_k on every shard); the static VMA check can't see that
        check_vma=False,
    )(data, sqnorm, invalid, queries)
    return ProvedMeshTopK(*out) if proved else MeshTopK(*out)


MANIFEST = "sharded.json"


def read_manifest(folder: str) -> dict:
    with open(os.path.join(folder, MANIFEST)) as f:
        return json.load(f)


def write_manifest(folder: str, manifest: dict, metadata=None) -> None:
    """Commit a mesh folder whose shard sub-folders are already saved:
    the frontend metadata (global-id keyed, reference metadata.bin /
    metadataIndex.bin format, top level), then the manifest.

    The manifest is written atomically and LAST: the per-shard saves are
    crash-safe (staged swap in save_index) and the manifest is the commit
    point, so everything it vouches for must already be durable.  A
    rebuild without metadata removes stale files so a load can't serve
    the previous corpus's payloads."""
    manifest_path = os.path.join(folder, MANIFEST)
    staged = f".tmp.{os.getpid()}"
    with open(manifest_path + staged, "w") as f:
        json.dump(manifest, f)
    mpath = os.path.join(folder, "metadata.bin")
    ipath = os.path.join(folder, "metadataIndex.bin")
    if metadata is not None:
        metadata.save(mpath + staged, ipath + staged)
        os.replace(mpath + staged, mpath)
        os.replace(ipath + staged, ipath)
    else:
        for p in (mpath, ipath):
            try:
                os.remove(p)
            except OSError:
                pass
    os.replace(manifest_path + staged, manifest_path)


def _folder_metadata(folder: str):
    """The frontend metadata a mesh folder carries at its top level, lazy
    and file-backed (a LAION-class blob is not pulled resident), or
    None."""
    mpath = os.path.join(folder, "metadata.bin")
    ipath = os.path.join(folder, "metadataIndex.bin")
    if os.path.exists(mpath) and os.path.exists(ipath):
        from sptag_tpu.core.vectorset import FileMetadataSet
        return FileMetadataSet(mpath, ipath)
    return None


def _mesh_for(n_shards: int, mesh: Optional[Mesh]) -> Mesh:
    """The mesh a saved folder of `n_shards` loads onto.  The default is
    sized from the manifest: a 2-shard save loads onto the first 2 local
    devices of an 8-device host.  An EXPLICIT mesh must match exactly —
    placement is the caller's statement of intent."""
    if mesh is None:
        devs = jax.devices()
        if len(devs) < n_shards:
            raise ValueError(
                f"saved index has {n_shards} shards but the "
                f"host exposes only {len(devs)} devices")
        mesh = make_mesh(devs[:n_shards])
    if mesh.devices.size != n_shards:
        raise ValueError(
            f"mesh has {mesh.devices.size} devices but the saved index "
            f"has {n_shards} shards")
    return mesh


def load_mesh_index(folder: str, mesh: Optional[Mesh] = None):
    """A persisted mesh folder -> the sharded index of the family its
    manifest names (`algo`; absent = BKT, what every folder saved before
    the key existed holds)."""
    algo = str(read_manifest(folder).get("algo", "BKT")).upper()
    cls = ShardedFlatIndex if algo == "FLAT" else ShardedBKTIndex
    return cls.load(folder, mesh=mesh)


def _publish_placement(n_shards: int, rows_per_shard: int) -> None:
    """Gauges of the most recent mesh placement: how many devices hold a
    share of the corpus, and how many row slots (padding included) each
    holds — what the benchmark's sharded-scan roofline counts bytes
    from."""
    metrics.set_gauge("mesh.shards", n_shards)
    metrics.set_gauge("mesh.rows_per_shard", rows_per_shard)


class ShardedFlatIndex(FlatIndex):
    """Exact search over a corpus sharded across every device of a mesh.

    The data-parallel analog of running one reference Server per machine
    behind an Aggregator — minus the sockets.

    It IS a `FlatIndex` whose placement is a mesh (docs/DESIGN.md "A
    living corpus on the mesh"): rows, tombstones, stable ids, the log,
    `add` and delete by content are `core/index.py`'s and `algo/flat.py`'s
    code, as on one chip; what this class gives is the hooks that write a
    placement (`_device_append` / `_device_mask` / `_grow`) and the
    program that searches it (`_search_batch`: `_sharded_search_kernel`).

    **Ids.**  A base row's id is its row in the partitioned corpus; a
    streamed row's is `rows so far`, in arrival order, never reused: the
    row's index in the host buffer either way.  Where a row LIES is the
    placement's: base row i in shard i // row_stride at slot i %
    row_stride; a write rung whole in ONE shard, successive rungs in
    successive shards, at the shard's next free slots.  Until the first
    write the program turns a shard's slot into the id by arithmetic
    (`row_stride`, today's static program); from it on the program's
    `shard * slots + slot` indexes a host table of ids (`_slot_ids`: a
    rung's slots are filled in when it is written, so a search
    dispatched before the write cannot return them).
    """

    def __init__(self, data: np.ndarray, metric: DistCalcMethod, base: int,
                 mesh: Optional[Mesh] = None,
                 deleted: Optional[np.ndarray] = None,
                 normalized: bool = False):
        super().__init__(value_type_of(np.dtype(data.dtype)))
        if base != self.base:
            raise ValueError(f"base {base} is not {data.dtype}'s "
                             f"({self.base})")
        self.mesh = mesh if mesh is not None else make_mesh()
        self._row_sharding = NamedSharding(self.mesh, P(SHARD_AXIS, None))
        self._vec_sharding = NamedSharding(self.mesh, P(SHARD_AXIS))
        self.metric = DistCalcMethod(metric)
        self.params.dist_calc_method = self.metric
        n_dev = self.mesh.devices.size

        if self.metric == DistCalcMethod.Cosine and not normalized:
            data = dist_ops.normalize(data, base)
        # the host-side truth, FlatIndex's own
        self._build(data)
        if deleted is not None:
            self._deleted[:self._n] = deleted[:self._n]
            self._num_deleted = int(self._deleted.sum())

        # a shard stands for `row_stride` consecutive corpus rows (the
        # last one for what is left) and holds them in a device block
        # padded as the one-chip snapshot is, under the `invalid` mask
        self.row_stride = stride = self.rows_per_shard(self._n, n_dev)
        n_slot = pad_rows(stride)
        # slots a shard has filled, and which of them are tombstoned
        self._fill = [max(0, min(stride, self._n - s * stride))
                      for s in range(n_dev)]
        self._dead = [int(self._deleted[s * stride:(s + 1) * stride].sum())
                      for s in range(n_dev)]
        # what the first in-place write makes (`_grow`): the blocks a
        # device, the ids a slot, the (shard, slot) a streamed row
        self._parts: Optional[list] = None
        self._slot_ids: Optional[np.ndarray] = None
        self._place = np.empty((0, 2), np.int32)
        self._streamed_from = self._n
        self._next_shard = 0
        self._write_rungs: set = set()

        def blocks_of(source, fill):
            def block(index):
                s = (index[0].start or 0) // n_slot
                rows = source[s * stride:(s + 1) * stride]
                out = np.empty((n_slot,) + source.shape[1:], source.dtype)
                out[:len(rows)] = rows
                out[len(rows):] = fill
                return out
            return block

        n_pad = n_slot * n_dev
        data_d = jax.make_array_from_callback(
            (n_pad, data.shape[1]), self._row_sharding,
            blocks_of(self._host, 0))
        invalid_d = jax.make_array_from_callback(
            (n_pad,), self._vec_sharding,
            blocks_of(self._deleted[:self._n], True))
        if self.metric == DistCalcMethod.L2:
            sqnorm_d = jax.jit(
                dist_ops.row_sqnorms,
                out_shardings=self._vec_sharding)(data_d)
        else:
            # cosine kernel never reads sqnorm; keep a zero placeholder so
            # the kernel signature stays uniform without HBM cost
            sqnorm_d = jax.device_put(
                np.zeros(n_pad, np.float32), self._vec_sharding)
        self._publish((data_d, sqnorm_d, invalid_d))
        self._dirty = False
        _publish_placement(n_dev, stride)

    # the resident block, as the static index named its arrays
    data = property(lambda self: self._device[0])
    sqnorm = property(lambda self: self._device[1])
    invalid = property(lambda self: self._device[2])
    n = property(lambda self: self._n)

    @staticmethod
    def rows_per_shard(n: int, n_shards: int) -> int:
        """Row slots each of `n_shards` contiguous equal blocks holds
        for an `n`-row corpus (the last block's tail is padding)."""
        return round_up(max(n, n_shards), n_shards * 8) // n_shards

    @classmethod
    def save_shards(cls, data: np.ndarray, folder: str, n_shards: int,
                    value_type, params=(), metadata=None,
                    deleted: Optional[np.ndarray] = None) -> None:
        """Persist `data` as a mesh FLAT folder without touching a device:
        `n_shards` contiguous blocks of `rows_per_shard` rows (the last
        one shorter — its padding exists only on the device), each a
        reference-format FLAT folder `shard_NNN` exactly as a reference
        Server persists its partition, plus the manifest.  `params` are
        (name, value) pairs set on every shard index (DistCalcMethod
        among them), so they persist in each shard's indexloader.ini;
        `deleted` the rows' tombstones (a living index's save)."""
        from sptag_tpu.core.index import create_instance
        from sptag_tpu.core.types import ErrorCode

        n, n_local = data.shape[0], cls.rows_per_shard(data.shape[0],
                                                       n_shards)
        if n <= (n_shards - 1) * n_local:
            raise ValueError(
                f"corpus ({n} rows) leaves one of {n_shards} shards empty")
        os.makedirs(folder, exist_ok=True)
        params = [(str(name), str(value)) for name, value in params]
        for s in range(n_shards):
            sub = create_instance("FLAT", value_type)
            for name, value in params:
                # the log is the whole index's, at the folder's top
                if name.lower() != "walenabled":
                    sub.set_parameter(name, value)
            code = sub.build(data[s * n_local:(s + 1) * n_local])
            if code == ErrorCode.Success and deleted is not None:
                # a shard's own save compacts nothing: an id is a row of
                # the whole corpus
                sub.set_parameter("DeletePercentageForRefine", "2")
                sub._delete_ids(np.flatnonzero(
                    deleted[s * n_local:(s + 1) * n_local]))
            if code == ErrorCode.Success:
                code = sub.save_index(
                    os.path.join(folder, f"shard_{s:03d}"))
            if code != ErrorCode.Success:
                raise RuntimeError(f"shard {s}: {code}")
        manifest = {
            "n_shards": n_shards, "n": n, "dim": int(data.shape[1]),
            "metric": int(sub.dist_calc_method),
            "value_type": int(sub.value_type), "algo": "FLAT"}
        whole = {name: value for name, value in params
                 if name.lower() == "walenabled"}
        if whole:
            # what the shards' own files were not given
            manifest["index_params"] = whole
        write_manifest(folder, manifest, metadata)

    @classmethod
    def load(cls, folder: str,
             mesh: Optional[Mesh] = None) -> "ShardedFlatIndex":
        """Load a folder `save_shards` wrote: the shard folders are read
        in order, their rows and tombstones laid end to end (cosine rows
        were normalized at ingest) and placed over the mesh; then, with
        `WalEnabled`, the folder's log is replayed over the placement
        and armed (`core/index.py load_index`'s order)."""
        from sptag_tpu.core.index import load_index

        meta = read_manifest(folder)
        mesh = _mesh_for(meta["n_shards"], mesh)
        n_local = cls.rows_per_shard(meta["n"], meta["n_shards"])
        subs = [load_index(os.path.join(folder, f"shard_{s:03d}"))
                for s in range(meta["n_shards"])]
        rows = [sub.num_samples for sub in subs]
        want = [min(n_local, meta["n"] - s * n_local)
                for s in range(meta["n_shards"])]
        if rows != want:
            raise ValueError(
                f"shards of {folder} hold {rows} rows, the manifest's "
                f"partition of {meta['n']} rows gives them {want}")
        self = cls(np.concatenate([sub._host[:r]
                                   for sub, r in zip(subs, rows)]),
                   DistCalcMethod(meta["metric"]), subs[0].base, mesh=mesh,
                   deleted=np.concatenate([sub._deleted[:r]
                                           for sub, r in zip(subs, rows)]),
                   normalized=True)
        # the parameters the folder was saved with: the shards' own, and
        # what only the whole index was given
        self.params = subs[0].params
        self.params.load_config(meta.get("index_params", {}))
        del subs
        self.metadata = _folder_metadata(folder)
        if int(getattr(self.params, "wal_enabled", 0) or 0):
            self._replay_wal(folder)
            self._arm_wal(folder)
        return self

    def save_index(self, folder: str):
        """The living index as a mesh folder (`save_shards`' format, no
        other): every row so far, base and streamed, partitioned anew in
        id order with its tombstones, and an empty log beside it.  An id
        stays the row's index in that order, so `load` places a streamed
        row by the base rule."""
        from sptag_tpu.core.types import ErrorCode
        from sptag_tpu.io import wal

        with self._lock:
            n = self._n
            self.save_shards(
                self._host[:n], folder, int(self.mesh.devices.size),
                self.value_type, params=list(self.params.non_default_items()),
                metadata=self.metadata, deleted=self._deleted[:n])
            if int(getattr(self.params, "wal_enabled", 0) or 0):
                wal.create_empty(os.path.join(folder, wal.WAL_NAME))
                self._arm_wal(folder)
        return ErrorCode.Success

    # ---- the placement follows its mutations ------------------------------

    def _live(self) -> bool:
        # a sketch or a cascade state stays out of the mesh: every
        # mutation writes the placement in place
        return self._device is not None

    def _snapshot(self):
        return self._device

    def _cascade_active(self) -> bool:
        return False

    def _slots(self) -> int:
        """Row slots a shard's block has (lock held)."""
        return self._device[0].shape[0] // self.mesh.devices.size

    def _shard_blocks(self) -> list:
        """`_parts`: the resident block as one [rows, norms, mask] a
        device, in the mesh's order, made at the first write (lock held):
        each a single-device array ON the buffer the whole array holds
        there, so a donated write of one device's touches no other's."""
        if self._parts is None:
            per_array = [{shard.device: shard.data
                          for shard in array.addressable_shards}
                         for array in self._device]
            self._parts = [[by_device[d] for by_device in per_array]
                           for d in self.mesh.devices.flat]
        return self._parts

    def _assembled(self):
        """The devices' blocks as the program's three arrays: no copy."""
        n_dev = len(self._parts)
        rows, dim = self._parts[0][0].shape
        return tuple(
            jax.make_array_from_single_device_arrays(shape, sharding,
                                                     [p[i] for p in
                                                      self._parts])
            for i, (shape, sharding) in enumerate((
                ((n_dev * rows, dim), self._row_sharding),
                ((n_dev * rows,), self._vec_sharding),
                ((n_dev * rows,), self._vec_sharding))))

    def _publish(self, block) -> None:
        """`block` as the resident one (lock held)."""
        self._device = block
        n_dev = self.mesh.devices.size
        metrics.set_gauge("flat.rows_resident", self._n)
        metrics.set_gauge("flat.slots_reserved", block[0].shape[0] // n_dev)
        live = [f - d for f, d in zip(self._fill, self._dead)]
        metrics.set_gauge("mesh.live_rows_max", max(live))
        metrics.set_gauge("mesh.live_rows_min", min(live))
        if self._slot_ids is not None:
            metrics.set_gauge("mesh.rows_per_shard", max(self._fill))
        devmem.track("shard_blocks", self,
                     block[0].nbytes + block[1].nbytes + block[2].nbytes)

    def _reserve(self, extra: int) -> None:
        super()._reserve(extra)
        room = self._host.shape[0] - self._streamed_from
        if room > self._place.shape[0]:
            grown = np.empty((room, 2), np.int32)
            grown[:len(self._place)] = self._place
            self._place = grown

    def _device_append(self, begin: int, rows: np.ndarray) -> None:
        """Rows [begin, begin + len(rows)) of the host buffer written
        into the placement (lock held): each write rung whole into ONE
        shard's block at its next free slots, by the one-chip write
        program on that device, successive rungs to successive shards;
        the blocks of every device grow first (`_grow`) where a rung
        would pass the fullest shard's last slot, and at the first write
        of all."""
        n_dev = self.mesh.devices.size
        pieces = _write_pieces(len(rows))
        # room for each rung past the FULLEST shard as its turn finds
        # them (`_warm_rung` writes a rung's first use on every shard)
        fill, need = list(self._fill), 0
        for turn, (_, count, rung) in enumerate(pieces):
            need = max(need, max(fill) + rung)
            fill[(self._next_shard + turn) % n_dev] += count
        if self._slot_ids is None or need > self._slots():
            self._grow(need)
        slots, sent, written = self._slots(), 0, set()
        with trace.span("flat.block_update"):
            for lo, count, rung in pieces:
                self._warm_rung("rows", rung)
                s, self._next_shard = (self._next_shard,
                                       (self._next_shard + 1) % n_dev)
                start = self._fill[s]
                padded = np.zeros((rung, rows.shape[1]), rows.dtype)
                padded[:count] = rows[lo:lo + count]
                dead = np.ones(rung, bool)
                dead[:count] = self._deleted[begin + lo:begin + lo + count]
                self._parts[s] = list(_block_write_rows(
                    *self._parts[s], padded, dead, np.int32(start)))
                ids = np.arange(begin + lo, begin + lo + count)
                self._slot_ids[s * slots + start:
                               s * slots + start + count] = ids
                at = ids - self._streamed_from
                self._place[at, 0], self._place[at, 1] = s, start + (
                    ids - ids[0])
                self._fill[s] += count
                self._dead[s] += int(dead[:count].sum())
                sent += padded.nbytes + dead.nbytes + 4
                written.add(s)
            self._publish(self._assembled())
        metrics.inc("flat.block_updates")
        trace.record_sum("flat.block_upload_bytes", sent, len(rows))
        trace.record_sum("mesh.write_devices", len(written), 1)

    def _device_mask(self, vids) -> None:
        """Mask bits of rows `vids` set where they lie (lock held): one
        mask write a rung on each shard that owns some of them."""
        parts = self._shard_blocks()
        vids = np.asarray(vids, np.int64)
        late = vids >= self._streamed_from
        shard = np.where(late, 0, vids // self.row_stride)
        slot = np.where(late, 0, vids % self.row_stride)
        at = vids[late] - self._streamed_from
        shard[late], slot[late] = self._place[at, 0], self._place[at, 1]
        slots, sent, written = self._slots(), 0, set()
        with trace.span("flat.block_update"):
            for s in np.unique(shard):
                mine = slot[shard == s].astype(np.int32)
                for lo, count, rung in _write_pieces(len(mine)):
                    self._warm_rung("mask", rung)
                    where = np.full(rung, slots, np.int32)
                    where[:count] = mine[lo:lo + count]
                    parts[s][2] = _block_mask_rows(parts[s][2], where)
                    sent += where.nbytes
                self._dead[s] += len(mine)
                written.add(int(s))
            self._publish(self._assembled())
        metrics.inc("flat.block_updates")
        trace.record_sum("flat.block_upload_bytes", sent, len(vids))
        trace.record_sum("mesh.write_devices", len(written), 1)

    def _warm_rung(self, kind: str, rung: int) -> None:
        """A write rung's first use on these blocks runs its program once
        on EVERY device (lock held), so the compiles of a rung are the
        writer's that first sends one and no later rung's, whichever
        shard its turn gives it.  What is run changes nothing: a mask of
        slots past the end (dropped); zero rows, masked, at a shard's
        free slots (`_device_append` left room on the fullest)."""
        if (kind, rung) in self._write_rungs:
            return
        self._write_rungs.add((kind, rung))
        dim, dtype = self._parts[0][0].shape[1], self._parts[0][0].dtype
        for s, part in enumerate(self._parts):
            if kind == "mask":
                part[2] = _block_mask_rows(
                    part[2], np.full(rung, part[2].shape[0], np.int32))
            else:
                self._parts[s] = list(_block_write_rows(
                    *part, np.zeros((rung, dim), dtype), np.ones(rung, bool),
                    np.int32(self._fill[s])))

    def _grow(self, need: int) -> None:
        """Every device's block copied, on its device, into one of
        `reserved_slots(need)` slots (lock held; the shapes stay equal
        under `shard_map`), the table of ids laid out anew, and every
        scan program and write rung that ran on the old shape run once
        on the new one: the writer that outgrew the reserve pays the
        compiles, not the searches after it."""
        with trace.span("flat.block_grow"):
            slots, old = reserved_slots(need), self._slots()
            n_dev = self.mesh.devices.size
            self._parts = [list(_block_grown(*part, slots=slots))
                           for part in self._shard_blocks()]
            table = np.full(n_dev * slots, -1, np.int32)
            for s in range(n_dev):
                if self._slot_ids is None:
                    table[s * slots:s * slots + self._fill[s]] = (
                        s * self.row_stride + np.arange(self._fill[s]))
                else:
                    table[s * slots:s * slots + old] = \
                        self._slot_ids[s * old:(s + 1) * old]
            self._slot_ids = table
            block = self._assembled()
            zeros = {}
            for q, k in self._programs:
                if q not in zeros:
                    zeros[q] = jnp.zeros((q, block[0].shape[1]),
                                         block[0].dtype)
                _sharded_search_kernel(*block, zeros[q],
                                       **self._scan_statics(q, k, slots))
            rungs, self._write_rungs = self._write_rungs, set()
            for kind, rung in sorted(rungs):
                self._warm_rung(kind, rung)
            self._publish(self._assembled())
        metrics.inc("flat.block_grows")

    # ---- search -----------------------------------------------------------

    def _scan_statics(self, q: int, k: int, slots: int) -> dict:
        """The mesh program's static arguments for `q` queries at `k`
        over blocks of `slots` slots a shard: the exact select's route
        is a rule of that shape, the id rule of whether a write has
        happened (lock held)."""
        n_dev = self.mesh.devices.size
        k_local = min(k, slots)
        return {"k_local": k_local, "k_final": min(k, k_local * n_dev),
                "metric": int(self.metric), "base": self.base,
                "mesh": self.mesh,
                "row_stride": (self.row_stride if self._slot_ids is None
                               else None),
                **scan_route(self._device[0].dtype, q, slots,
                             self._device[0].shape[1], k_local,
                             int(self.metric))}

    def _search_batch(self, queries: np.ndarray, k: int,
                      max_check: Optional[int] = None,
                      search_mode: Optional[str] = None
                      ) -> Tuple[np.ndarray, np.ndarray]:
        del max_check, search_mode      # exact scan: no budget, no modes
        # the one-chip scan's ladder: a served window forms batches of
        # every size, and each size would be a program of its own
        q = queries.shape[0]
        queries = pad_to_bucket(queries)
        queries_d = jnp.asarray(queries)
        # read the block and enqueue the program on it under the writer's
        # lock, as on one chip: the next in-place write donates a
        # device's arrays, and each device runs the program before it
        with self._lock:
            block, table = self._device, self._slot_ids
            statics = self._scan_statics(queries.shape[0], k, self._slots())
            count_select(queries.shape[0], self._slots(),
                         statics["k_local"])
            count_route(statics["fused"])
            self._programs.add((queries.shape[0], k))
            dists, ids, *flag = _sharded_search_kernel(*block, queries_d,
                                                       **statics)
        with trace.span("index.readback"):
            # the host blocks here until the program has run
            dists = np.asarray(dists)[:q]
            ids = np.asarray(ids)[:q]
            if flag:
                count_unproved(flag[0])
        if table is not None:
            ids = np.where(ids >= 0, table[np.maximum(ids, 0)], -1)
        return _pad_to_k(dists, ids, k, statics["k_final"])

    _exact_scan = _search_batch

    def search(self, queries: np.ndarray,
               k: int = 10, normalized: bool = False,
               max_check: Optional[int] = None
               ) -> Tuple[np.ndarray, np.ndarray]:
        """A (Q, D) block -> ((Q, k) distances, (Q, k) ids): the mesh
        index's own batch surface, which `ServingAdapter` serves.
        `max_check` is accepted (and ignored — the scan is exact): the
        wire surface forwards the $maxcheck option to every index type."""
        queries = np.asarray(queries)
        if self.metric == DistCalcMethod.Cosine and not normalized:
            queries = dist_ops.normalize(queries, self.base)
        return self._search_batch(queries, k, max_check)


# --------------------------------------------------------------------------
# Sharded GRAPH search — the flagship BKT/KDT engine over a mesh
# --------------------------------------------------------------------------

@functools.partial(
    jax.jit,
    static_argnames=("k_local", "k_final", "L", "B", "T", "metric", "base",
                     "nbp_limit", "mesh", "merge_bins", "finalize_bins",
                     "seed_keep", "score_scale"))
def _sharded_beam_kernel(data, sqnorm, graph, deleted, pivot_ids, pivot_vecs,
                         pivot_mask, queries, k_local: int, k_final: int,
                         L: int, B: int, T: int,
                         metric: int, base: int, nbp_limit: int, mesh: Mesh,
                         merge_bins: int = 0, finalize_bins: int = 0,
                         seed_keep: int = 0, score_scale: float = 0.0,
                         data_score=None):
    """One program: per-shard pivot-seeded beam walk over the shard's OWN
    RNG graph (local ids), then ICI all-gather of each shard's (dist,
    global-id) top-k and a global top-k re-rank.  This subsumes the
    reference's Server-per-shard + Aggregator flat-merge topology
    (AggregatorService.cpp:206-366) — and re-ranks globally, which the
    reference leaves to the client."""
    from sptag_tpu.algo.engine import _beam_search_kernel

    def local_search(data_s, sqnorm_s, graph_s, deleted_s, pids_s, pvecs_s,
                     pmask_s, q_s, *score_s):
        n_local = data_s.shape[0]
        shard = jax.lax.axis_index(SHARD_AXIS)
        t_limit = jnp.full((q_s.shape[0],), T, jnp.int32)
        # the walk's live counts (engine.BeamWalk) are one chip's: unread
        d, ids, _ = _beam_search_kernel(
            data_s, sqnorm_s, graph_s, deleted_s, pids_s[0], pvecs_s[0],
            pmask_s[0], q_s, t_limit, k_local, L, B, metric, base,
            nbp_limit, merge_bins=merge_bins, finalize_bins=finalize_bins,
            seed_keep=seed_keep, score_scale=score_scale,
            data_score=score_s[0] if score_s else None)
        gids = jnp.where(ids >= 0, ids + shard * n_local, -1)
        return _gather_merge(d, gids, k_final)

    # the optional int8 scoring shadow (CascadeSearch, ops/cascade.py)
    # rides as an extra row-sharded operand; its STATIC score_scale is
    # resolved by the same shared rule the mesh scheduler engine uses,
    # which is what keeps scheduler-vs-monolithic id-parity intact
    args = (data, sqnorm, graph, deleted, pivot_ids, pivot_vecs,
            pivot_mask, queries)
    in_specs = (P(SHARD_AXIS, None), P(SHARD_AXIS), P(SHARD_AXIS, None),
                P(SHARD_AXIS), P(SHARD_AXIS, None),
                P(SHARD_AXIS, None, None), P(SHARD_AXIS, None),
                P(None, None))
    if data_score is not None:
        args = args + (data_score,)
        in_specs = in_specs + (P(SHARD_AXIS, None),)
    return shard_map(
        local_search,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=(P(None, None), P(None, None)),
        check_vma=False,
    )(*args)


@functools.partial(
    jax.jit,
    static_argnames=("k_local", "k_final", "nprobe", "metric", "base",
                     "dedup", "mesh", "binned_bins"))
def _sharded_dense_kernel(data_perm, member_ids, member_sq, centroids,
                          cent_sq, cent_valid, deleted, queries,
                          k_local: int, k_final: int, nprobe: int,
                          metric: int, base: int, dedup: bool, mesh: Mesh,
                          binned_bins: int = 0):
    """One program: per-shard dense block scan (each shard probes the top
    `nprobe` of its OWN kd/k-means partition blocks — padded blocks are
    masked out of the centroid ranking), then ICI all-gather + global
    top-k, exactly like `_sharded_beam_kernel`.  The multi-chip face of
    the throughput-serving dense mode."""
    from sptag_tpu.algo.dense import MAX_DIST as _MD, _finalize_topk

    def local(dp_s, mi_s, ms_s, ce_s, cs_s, cv_s, del_s, q_s):
        shard = jax.lax.axis_index(SHARD_AXIS)
        n_local = del_s.shape[0]
        qf = q_s.astype(jnp.float32)
        d0 = dist_ops.pairwise_distance(qf, ce_s[0],
                                        DistCalcMethod(metric),
                                        x_sqnorm=cs_s[0])
        d0 = jnp.where(cv_s[0][None, :], d0, _MD)
        _, topc = jax.lax.top_k(-d0, nprobe)                # (Q, nprobe)
        Q = q_s.shape[0]
        Pb = dp_s.shape[2]                                  # block size
        ids = mi_s[0][topc].reshape(Q, nprobe * Pb)         # local ids
        sq = ms_s[0][topc].reshape(Q, nprobe * Pb)
        vecs = dp_s[0][topc].reshape(Q, nprobe * Pb, dp_s.shape[3])
        nd = dist_ops.batched_gathered_distance(
            q_s, vecs, DistCalcMethod(metric), base, sq)
        # the shard's own row mask by candidate id (the single-chip
        # searcher keeps a per-slot table instead: DenseTreeSearcher.set_deleted)
        with jax.named_scope("dense.mask"):
            dead = del_s[jnp.maximum(ids, 0)] | (ids < 0)
        d, out_ids = _finalize_topk(nd, ids, dead, dedup, k_local,
                                    binned_bins=binned_bins)
        gids = jnp.where(out_ids >= 0, out_ids + shard * n_local, -1)
        return _gather_merge(d, gids, k_final)

    return shard_map(
        local,
        mesh=mesh,
        in_specs=(P(SHARD_AXIS, None, None, None),
                  P(SHARD_AXIS, None, None), P(SHARD_AXIS, None, None),
                  P(SHARD_AXIS, None, None), P(SHARD_AXIS, None),
                  P(SHARD_AXIS, None), P(SHARD_AXIS), P(None, None)),
        out_specs=(P(None, None), P(None, None)),
        check_vma=False,
    )(data_perm, member_ids, member_sq, centroids, cent_sq, cent_valid,
      deleted, queries)


@locksan.race_track
class ServingAdapter:
    """Presents a sharded mesh index through the VectorIndex serving
    surface (value_type / feature_dim / search / search_batch) so it can be
    registered in a SearchServer's index map — external clients speak the
    reference wire protocol while the search itself is the one-program
    mesh scatter-gather.  This is the full reference deployment picture
    (client -> server -> shards) with the Aggregator tier replaced by ICI
    collectives.  Metadata is not sharded (serve corpus metadata from the
    frontend's own store if needed)."""

    def __init__(self, sharded, feature_dim: int, value_type=None,
                 mode: str = "beam", metadata=None):
        from sptag_tpu.core.types import VectorValueType, value_type_of

        self._impl = sharded
        self.feature_dim = feature_dim
        self.value_type = (VectorValueType(value_type)
                           if value_type is not None
                           else value_type_of(np.dtype(
                               sharded.data.dtype)))
        # frontend metadata store, keyed by GLOBAL row id (the mesh search
        # returns original corpus ids): explicit argument wins, else the
        # store the mesh index was built/loaded with.  The reference
        # topology attaches metadata per Server shard
        # (/root/reference/AnnService/src/Socket/RemoteSearchQuery.cpp:
        # 94-210, src/Server/SearchService.cpp:205-262); here one frontend
        # store is equivalent because shard-local ids are already remapped
        # to global ids inside the merge kernel.
        self.metadata = (metadata if metadata is not None
                         else getattr(sharded, "metadata", None))
        # "dense" serves the multi-chip block scan (requires the index
        # built with dense=True); "beam" the per-shard walk
        if mode not in ("beam", "dense"):
            raise ValueError(f"unknown serving mode: {mode!r}")
        # $searchmode:auto crossover (same default as the single-chip
        # AutoModeThreshold param)
        self.auto_mode_threshold = 1024
        if mode == "dense":
            if not hasattr(sharded, "search_dense"):
                raise ValueError("index type has no dense mode")
            if not hasattr(sharded, "dense_perm"):
                # same exception type + message as search_dense itself
                raise RuntimeError(
                    "dense layout not packed — build with dense=True")
        self.mode = mode
        # mesh-serve spine (ISSUE 11): epoch-published placement + the
        # continuous-batching flag.  Readers pin `impl = self._impl`
        # once per call (the PR-9 epoch-handoff pattern) so a concurrent
        # swap_impl can never hand them a half-published placement.
        self._swap_lock = locksan.make_lock("ServingAdapter._swap_lock")
        self._epoch = 0
        self._swap_count = 0
        self._mesh_serve = False

    @property
    def num_samples(self) -> int:
        return self._impl.n

    # ---- MeshServe spine (ISSUE 11) ---------------------------------------

    def enable_mesh_serve(self, slots: int = 1024,
                          segment_iters: int = 0) -> bool:
        """Arm the mesh-wide continuous-batching spine ([Service]
        MeshServe=1): the backing index builds a `MeshGraphEngine` +
        `BeamSlotScheduler` whose slot pools span the shard axis, and
        `submit_batch` starts resolving per-query futures in retire
        order — the serve tier then streams responses while stragglers
        are still walking.  Returns False (and stays sync) for indexes
        without the scheduler surface (ShardedFlatIndex, dense-only)."""
        impl = self._impl
        enable = getattr(impl, "enable_continuous_batching", None)
        if enable is None or self.mode == "dense":
            return False
        enable(slots=slots, segment_iters=segment_iters)
        self._mesh_serve = True
        self._mesh_slots = slots
        self._mesh_segment_iters = segment_iters
        return True

    def swap_impl(self, new_impl) -> int:
        """Atomically publish a NEW sharded index as this adapter's mesh
        placement (the live-mutation epoch swap of PR 9, mesh-wide): the
        whole placement — every shard's corpus/graph/pivot arrays —
        switches in one reference store; in-flight queries finish on the
        OLD placement (its retired scheduler keeps walking residents,
        exactly like a superseded single-chip snapshot), new queries see
        the new one.  Returns the new epoch."""
        with self._swap_lock:
            old = self._impl
            self._impl = new_impl
            self._epoch += 1
            self._swap_count += 1
            epoch = self._epoch
            # retire + re-arm INSIDE the lock: two concurrent swaps must
            # serialize end to end, or swap B could retire a scheduler
            # swap A has not armed yet and A's late re-arm would leave a
            # live scheduler (worker thread + pools) on a superseded
            # placement forever.  Both calls are cheap (retire only
            # flags the drain; enable starts one thread).
            retire = getattr(old, "retire_scheduler", None)
            if retire is not None:
                retire()
            if self._mesh_serve:
                # the new placement serves the same MeshServe contract
                # the old one did — re-arm before traffic lands
                enable = getattr(new_impl, "enable_continuous_batching",
                                 None)
                if enable is not None:
                    enable(slots=getattr(self, "_mesh_slots", 1024),
                           segment_iters=getattr(
                               self, "_mesh_segment_iters", 0))
        metrics.inc("mesh.swaps")
        return epoch

    def mutation_state(self) -> dict:
        """Swap/placement state for /healthz + GET /debug/mutation —
        the mesh analog of VectorIndex.mutation_state."""
        impl = self._impl
        return {
            "epoch": self._epoch,
            "swap_count": self._swap_count,
            "mesh": {
                "shards": int(impl.mesh.devices.size),
                "rows": int(impl.n),
                "mesh_serve": self._mesh_serve,
                "scheduler": getattr(impl, "_scheduler", None) is not None,
            },
        }

    # ---- mutation surface -------------------------------------------------
    # The backing index's own (`$admin:add` / `$admin:delete` / `$admin:
    # save` reach it through these): a mesh FLAT index is a `FlatIndex`
    # and takes them in place; a mesh BKT index has no such method and
    # the call raises, which the admin surface answers as before.

    def add(self, vectors, metadata=None, with_meta_index: bool = False):
        return self._impl.add(vectors, metadata, with_meta_index)

    def delete(self, vectors):
        return self._impl.delete(vectors)

    def delete_rows(self, vectors):
        return self._impl.delete_rows(vectors)

    def save_index(self, folder: str):
        return self._impl.save_index(folder)

    def submit_batch(self, queries: np.ndarray, k: int = 10,
                     max_check: Optional[int] = None,
                     search_mode: Optional[str] = None,
                     rids=None):
        """Per-query futures over a (Q, D) block — the streaming serve
        surface (VectorIndex.submit_batch contract).  With MeshServe
        armed and the mode resolving to beam, futures resolve AS QUERIES
        RETIRE from the mesh-wide slot scheduler; otherwise the batch
        executes synchronously and the futures come back resolved (the
        base-class semantics — identical results, batch granularity)."""
        from sptag_tpu.core.index import resolved_futures

        queries = np.asarray(queries)
        if queries.ndim == 1:
            queries = queries[None, :]
        impl = self._impl                      # epoch pin
        mode = self._resolve_mode(search_mode, max_check, impl=impl)
        sub = getattr(impl, "submit_batch", None)
        if self._mesh_serve and mode == "beam" and sub is not None:
            # the hand-over to the mesh scheduler; the walk is waited for
            # by the caller
            with trace.span("index.search"):
                return sub(queries, k, max_check=max_check, rids=rids)
        return resolved_futures(
            lambda: self.search_batch(queries, k, max_check=max_check,
                                      search_mode=search_mode),
            queries.shape[0])

    def search_batch(self, queries: np.ndarray, k: int = 10,
                     max_check: Optional[int] = None,
                     search_mode: Optional[str] = None
                     ) -> Tuple[np.ndarray, np.ndarray]:
        """`max_check` / `search_mode` override the adapter's build-time
        budget and configured mode per request (reachable over the wire
        via the framework's `$maxcheck` / `$searchmode` query options —
        extensions; the reference has no per-request knobs,
        serve/protocol.py docstring).  A `$searchmode:dense` request on an
        adapter whose index was not packed dense raises, surfaced as
        FailedExecute by the service layer.  `auto` resolves by budget
        like the single-chip index (beam below 1024, dense at or above),
        falling back to the configured mode when the dense pack is
        absent — a wire value the protocol accepts must never hard-fail
        a query that the configured mode could serve."""
        impl = self._impl                      # epoch pin (swap_impl)
        mode = self._resolve_mode(search_mode, max_check, impl=impl)
        # the adapter is no VectorIndex: its own seam, the same name
        with trace.span("index.search"):
            if mode == "dense":
                return impl.search_dense(np.asarray(queries), k=k,
                                         max_check=max_check)
            return impl.search(np.asarray(queries), k=k,
                               max_check=max_check)

    def _resolve_mode(self, search_mode: Optional[str],
                      max_check: Optional[int], impl=None) -> str:
        """Per-request serving-mode resolution shared by search_batch
        and submit_batch (see search_batch's docstring for the `auto`
        crossover + degrade semantics)."""
        impl = impl if impl is not None else self._impl
        mode = search_mode or self.mode
        if mode == "auto":
            mc = (max_check if max_check is not None
                  else getattr(impl, "max_check", 2048))
            want = ("dense" if mc >= self.auto_mode_threshold else "beam")
            # only resolve to an engine this index can actually serve;
            # otherwise degrade to the configured mode
            if want == "dense" and not hasattr(impl, "dense_perm"):
                want = self.mode
            params = getattr(impl, "params", None)
            has_graph = (int(getattr(params, "build_graph", 1))
                         if params is not None else 1)
            if want == "beam" and not has_graph:
                want = self.mode
            mode = want
        if mode not in ("beam", "dense"):     # same contract as the ctor
            raise ValueError(f"unknown serving mode: {mode!r}")
        return mode

    def search(self, query, k: int = 10, with_metadata: bool = False,
               max_check: Optional[int] = None,
               search_mode: Optional[str] = None):
        from sptag_tpu.core.index import SearchResult

        q = np.asarray(query)
        if q.ndim == 1:
            q = q[None, :]
        d, ids = self.search_batch(q, k=k, max_check=max_check,
                                   search_mode=search_mode)
        from sptag_tpu.core.vectorset import metas_for
        metas = metas_for(self.metadata, ids[0]) if with_metadata else None
        return SearchResult(ids=ids[0], dists=d[0], metas=metas)


def pack_shard_block(sub, n_local: int, dim: int, m_width: int, max_p: int,
                     words: int) -> dict:
    """Pad one built BKT sub-index into the fixed per-shard geometry.

    Shared by the single-process build (ShardedBKTIndex.build) and the
    multi-controller build (parallel/multihost.py) so the packing/padding
    semantics cannot diverge: rows beyond the shard's count are zero
    vectors marked deleted; graph rows are -1-padded to `m_width`; pivot
    ids are -1-padded to `max_p`; the pivot bitset covers `words` int32s.
    """
    nb = sub._n
    # rows are normalized at ingest for cosine — take the INDEX's copy,
    # not the raw input block
    block = np.zeros((n_local, dim), sub._host.dtype)
    block[:nb] = sub._host[:nb]
    g = np.full((n_local, m_width), -1, np.int32)
    gw = min(m_width, sub._graph.graph.shape[1])
    g[:nb, :gw] = sub._graph.graph[:, :gw]
    dele = np.ones(n_local, bool)              # padding rows = deleted
    dele[:nb] = sub._deleted[:nb]
    pids = np.full(max_p, -1, np.int32)
    got = np.asarray(sub._pivot_ids(), np.int32)[:max_p]
    pids[:len(got)] = got
    pvec = block[np.maximum(pids, 0)]
    mask = np.zeros(words, np.uint32)
    np.bitwise_or.at(mask, got >> 5,
                     np.uint32(1) << (got.astype(np.uint32) & 31))
    return dict(data=block, graph=g, deleted=dele, pivot_ids=pids,
                pivot_vecs=pvec, pivot_mask=mask.view(np.int32))


class ShardedBKTIndex:
    """The flagship graph index, corpus-sharded over a device mesh.

    Each device holds an INDEPENDENT shard index — its block of the corpus
    plus a BKT forest + RNG graph built over that block with shard-local
    ids — exactly as each reference Server owns an independent index over
    its partition.  Search runs the batched beam walk on every shard
    simultaneously inside one `shard_map` program and merges with an
    all-gather + `lax.top_k` over ICI (SURVEY.md §7.9, milestone C).

    Across hosts the same program runs under multi-host jax.distributed
    over DCN.
    """

    def __init__(self, mesh: Optional[Mesh] = None):
        self.mesh = mesh if mesh is not None else make_mesh()
        self.metric = DistCalcMethod.L2
        self.base = 1
        self.n = 0
        self.n_local = 0
        self.max_check = 2048
        self.nbp_limit = 3
        self.beam_width = 16
        self.metadata = None
        # per-shard budget policy (VERDICT r3 item 8): "full" runs every
        # shard at the whole MaxCheck — total work scales n_dev x the
        # single-chip budget (the reference Aggregator's fan-out semantics,
        # AggregatorService.cpp:206-279, where each Server owns an
        # INDEPENDENT index and must be searched at full budget);
        # "proportional" gives each shard ceil(MaxCheck / n_dev) (floored)
        # so the mesh does single-chip total work; "guarded" calibrates
        # the smallest proportional multiplier whose results overlap the
        # full-budget results >= the guard threshold, per (MaxCheck, k)
        self.budget_policy = "full"
        self.budget_guard_overlap = 0.99
        self._guarded_cache: dict = {}
        # tiered cascade (CascadeSearch): filled by _place when armed
        self.data_score = None
        self.score_scale = 0.0
        # mesh-wide continuous batching (ISSUE 11): built on demand by
        # enable_continuous_batching(); retired as a unit on swap
        self._scheduler = None
        self._mesh_engine = None

    # ---- mesh-wide continuous batching (ISSUE 11) -------------------------

    def enable_continuous_batching(self, slots: int = 1024,
                                   segment_iters: int = 0):
        """Build the mesh serving spine: a `MeshGraphEngine` over this
        index's placed shard arrays plus ONE `BeamSlotScheduler` whose
        slot pools span the shard axis — every resident query occupies a
        slot row on every shard, one bucketed refill queue feeds the
        mesh-wide segment step, and converged queries retire (and
        resolve their futures) while stragglers keep walking.  Idempotent;
        returns the scheduler."""
        if self._scheduler is not None:
            return self._scheduler
        from sptag_tpu.algo.scheduler import BeamSlotScheduler
        from sptag_tpu.parallel.mesh_engine import MeshGraphEngine

        # no devmem entry here: the engine wraps the PLACEMENT's arrays
        # (tracked as shard_blocks by _place) — re-tracking them under
        # the engine would double-count the same residency
        engine = MeshGraphEngine(self)
        self._mesh_engine = engine
        self._scheduler = BeamSlotScheduler(
            engine, slots=slots, segment_iters=segment_iters,
            name="mesh-sched")
        return self._scheduler

    def retire_scheduler(self) -> None:
        """Drop this placement's scheduler WITHOUT dropping in-flight
        work: residents finish on the old snapshot (scheduler.retire's
        drain semantics), new submits go to whoever replaced us.  The
        swap path (ServingAdapter.swap_impl) calls this on the outgoing
        placement."""
        sched, self._scheduler = self._scheduler, None
        self._mesh_engine = None
        if sched is not None:
            sched.retire()

    def submit_batch(self, queries: np.ndarray, k: int = 10,
                     max_check: Optional[int] = None,
                     search_mode: Optional[str] = None,
                     rids=None):
        """Per-query futures (VectorIndex.submit_batch contract): with
        the mesh scheduler armed and a beam-capable request, each future
        resolves in retire order from the mesh-wide slot pools —
        identical ids to `search()` at the same budget (distances may
        differ in the last ulp across refill-bucket shapes, the PR-4
        scheduler caveat).  Dense requests, non-"full" budget policies
        and scheduler-less indexes fall back to one synchronous
        search_batch with pre-resolved futures."""
        from concurrent.futures import Future

        queries = np.asarray(queries)
        if queries.ndim == 1:
            queries = queries[None, :]
        sched = self._scheduler
        mode = search_mode or "beam"
        if (sched is not None and mode == "beam"
                and self.budget_policy == "full"
                and int(getattr(self.params, "build_graph", 1))):
            from sptag_tpu.algo.scheduler import (SchedulerStopped,
                                                  pad_result_row)

            if self.metric == DistCalcMethod.Cosine:
                queries = dist_ops.normalize(queries, self.base)
            mc = max_check if max_check is not None else self.max_check
            out = []
            try:
                for i in range(queries.shape[0]):
                    inner = sched.submit(queries[i], k, mc,
                                         beam_width=self.beam_width,
                                         nbp_limit=self.nbp_limit,
                                         rid=rids[i] if rids else "")
                    # pad k_eff (the global merge width, possibly < k
                    # under MeshKLocal / small meshes) out to the
                    # caller's k — the same wire contract every
                    # synchronous path honors
                    outer: Future = Future()

                    def _pad(f, outer=outer):
                        e = f.exception()
                        if e is not None:
                            outer.set_exception(e)
                            return
                        d, ids = f.result()
                        outer.set_result(pad_result_row(d, ids, k))
                    inner.add_done_callback(_pad)
                    out.append(outer)
            except SchedulerStopped:
                # a placement swap retired this scheduler mid-batch:
                # rows already submitted still resolve (retire drains
                # pending + residents); the remainder serves
                # synchronously on whatever placement is live now.
                # normalized=True — this branch already normalized.
                from sptag_tpu.core.index import resolved_futures

                done = len(out)
                rest = queries[done:]
                out.extend(resolved_futures(
                    lambda: self.search(rest, k, max_check=max_check,
                                        normalized=True),
                    rest.shape[0]))
            return out
        from sptag_tpu.core.index import resolved_futures

        return resolved_futures(
            lambda: (self.search_dense(queries, k, max_check=max_check)
                     if mode == "dense"
                     else self.search(queries, k, max_check=max_check)),
            queries.shape[0])

    def set_deleted(self, deleted: np.ndarray) -> None:
        """Publish a new GLOBAL tombstone mask (row-aligned with the
        build corpus; rows beyond `n` — ceil-division padding — stay
        deleted).  Mutation-path analog of GraphSearchEngine.set_deleted:
        the next dispatch of every search path (monolithic AND the mesh
        scheduler's finalize) reads the new mask."""
        n_dev = self.mesh.devices.size
        mask = np.ones(n_dev * self.n_local, bool)
        mask[:self.n] = np.asarray(deleted, bool)[:self.n]
        vec = NamedSharding(self.mesh, P(SHARD_AXIS))
        self.deleted = jax.device_put(mask, vec)
        if self._mesh_engine is not None:
            self._mesh_engine.deleted = self.deleted

    @classmethod
    def load(cls, folder: str,
             mesh: Optional[Mesh] = None,
             dense: bool = False) -> "ShardedBKTIndex":
        """Load a mesh index persisted by `build(..., save_to=folder)`:
        one reference-format sub-index folder per shard (`shard_000`,
        `shard_001`, ...), exactly how each reference Server persists its
        own partition.  The mesh size must match the shard count."""
        from sptag_tpu.core.index import load_index

        meta = read_manifest(folder)
        mesh = _mesh_for(meta["n_shards"], mesh)
        subs = [load_index(os.path.join(folder, f"shard_{s:03d}"))
                for s in range(meta["n_shards"])]
        self = cls._assemble(subs, meta["n"], meta["dim"],
                             DistCalcMethod(meta["metric"]), mesh,
                             meta.get("empty_shards", []), dense)
        # frontend metadata (global-id keyed), persisted at the mesh-folder
        # top level by build(..., metadata=...)
        self.metadata = _folder_metadata(folder)
        return self

    def save(self, folder: str) -> None:
        raise NotImplementedError(
            "save happens at build time: ShardedBKTIndex.build(..., "
            "save_to=folder) — the packed device arrays do not retain the "
            "per-shard tree structures a reference-format save needs")

    @classmethod
    def build(cls, data: np.ndarray,
              metric: DistCalcMethod = DistCalcMethod.L2,
              mesh: Optional[Mesh] = None,
              value_type=None,
              params: Optional[dict] = None,
              dense: bool = False,
              save_to: Optional[str] = None,
              algo: str = "BKT",
              metadata=None) -> "ShardedBKTIndex":
        """Partition `data` into contiguous equal blocks, build one
        sub-index per shard (host-side, device-batched k-means/graph
        build), and lay the per-shard arrays out over the mesh.

        `algo` picks the shard index family: "BKT" (default) or "KDT"
        (kd-tree forest shards — the walk seeds from each shard's fallback
        pivot set, and `dense=True` cuts kd cells).

        `dense=True` additionally packs each shard's dense tree-partition
        layout so `search_dense` (the multi-chip throughput mode) is
        available — at the cost of a second device-resident copy of the
        corpus in cluster-contiguous order.

        `save_to` persists every sub-index as a reference-format folder
        under `save_to/shard_NNN` plus a `sharded.json` manifest, loadable
        with `ShardedBKTIndex.load` — the persistence story of the
        reference's one-Server-per-shard topology.

        `metadata` (a MetadataSet over the FULL corpus, row-aligned with
        `data`) is held at the frontend keyed by global id — the mesh
        search returns original corpus ids, so one store serves all
        shards; persisted in reference metadata.bin/metadataIndex.bin
        format at the mesh-folder top level when `save_to` is given.

        With SPTAG_TPU_BUILD_CKPT set, each shard's build is resumable
        (utils/build_ckpt.py): shard blocks differ, so their fingerprints
        key distinct checkpoint subfolders — a death in shard s re-runs
        shards [0, s) from their finished checkpoints' stages and resumes
        s where it stopped."""
        from sptag_tpu.core.index import create_instance
        from sptag_tpu.core.types import value_type_of

        if str(algo).upper() not in ("BKT", "KDT"):
            # fail before the expensive shard builds: the packer needs the
            # graph-index composition (_graph/_pivot_ids/_dense_clusters)
            raise ValueError(
                f"sharded mesh indexes support BKT or KDT shards, not "
                f"{algo!r}")

        if mesh is None:
            # MeshShardAxis (core/params.py): size the shard axis to the
            # first N local devices instead of all of them (0 = all) —
            # an operator carving one host's chips between tenants
            n_axis = int((params or {}).get("MeshShardAxis", 0) or 0)
            mesh = make_mesh(jax.devices()[:n_axis] if n_axis > 0
                             else None)
        n_dev = mesh.devices.size
        n = data.shape[0]
        if n < n_dev:
            raise ValueError(f"corpus ({n}) smaller than mesh ({n_dev})")
        n_local = -(-n // n_dev)
        metric = DistCalcMethod(metric)

        if value_type is None:
            value_type = value_type_of(np.asarray(data).dtype)

        shard_indexes = []
        empty_shards = []
        for s in range(n_dev):
            block = np.asarray(data[s * n_local:(s + 1) * n_local])
            if block.shape[0] == 0:
                # ceil-division tail shard with no rows (e.g. n=49 over 8
                # devices): one tombstoned placeholder row keeps the shard
                # in the program without ever appearing in results
                empty_shards.append(s)
                block = np.zeros((1, data.shape[1]), data.dtype)
            sub = create_instance(algo, value_type)
            sub.set_parameter("DistCalcMethod",
                              "Cosine" if metric ==
                              DistCalcMethod.Cosine else "L2")
            for name, value in (params or {}).items():
                sub.set_parameter(name, str(value))
            # keep_checkpoint: a finished shard's stages must survive
            # until EVERY shard is done — clearing per shard would force
            # a death in shard s to rebuild shards [0, s) from scratch
            # on resume (the whole point of a resumable MULTI-shard
            # build is that only the interrupted shard re-runs)
            sub.build(block, keep_checkpoint=True)
            shard_indexes.append(sub)
        # all shards succeeded: retire every shard's checkpoint now
        for sub in shard_indexes:
            ck = getattr(sub, "last_checkpoint", None)
            if ck is not None:
                ck.clear()
                sub.last_checkpoint = None
        if save_to is not None:
            os.makedirs(save_to, exist_ok=True)
            for s, sub in enumerate(shard_indexes):
                sub.save_index(os.path.join(save_to, f"shard_{s:03d}"))
            write_manifest(save_to, {
                "n_shards": n_dev, "n": n, "dim": int(data.shape[1]),
                "metric": int(metric), "empty_shards": empty_shards,
                "algo": str(algo).upper()}, metadata)
        self = cls._assemble(shard_indexes, n, int(data.shape[1]), metric,
                             mesh, empty_shards, dense)
        self.metadata = metadata
        # truthy when ANY shard resumed from build checkpoints — the
        # accurate signal for resume drives (a non-empty checkpoint dir
        # alone can be stale state from a different config)
        self.build_resumed = any(getattr(sub, "build_resumed", False)
                                 for sub in shard_indexes)
        return self

    @classmethod
    def _assemble(cls, shard_indexes, n: int, dim: int,
                  metric: DistCalcMethod, mesh: Mesh, empty_shards,
                  dense: bool) -> "ShardedBKTIndex":
        """Pack built sub-indexes into the mesh arrays (shared by build
        and load)."""
        self = cls(mesh)
        self.metric = DistCalcMethod(metric)
        n_dev = self.mesh.devices.size
        n_local = -(-n // n_dev)
        self.n = n
        self.n_local = n_local
        self.base = shard_indexes[0].base
        self.params = shard_indexes[0].params
        m_width = max(sub._graph.graph.shape[1] for sub in shard_indexes)

        from sptag_tpu.algo.engine import _num_words
        words = _num_words(n_local)
        max_p = max(len(sub._pivot_ids()) for sub in shard_indexes)
        blocks_data, blocks_graph, blocks_del = [], [], []
        blocks_pid, blocks_pvec, blocks_pmask = [], [], []
        for s, sub in enumerate(shard_indexes):
            packed = pack_shard_block(sub, n_local, dim, m_width,
                                      max_p, words)
            if s in empty_shards:
                packed["deleted"][:] = True
            blocks_data.append(packed["data"])
            blocks_graph.append(packed["graph"])
            blocks_del.append(packed["deleted"])
            blocks_pid.append(packed["pivot_ids"])
            blocks_pvec.append(packed["pivot_vecs"])
            blocks_pmask.append(packed["pivot_mask"])
        self.max_check = int(getattr(self.params, "max_check", 2048))
        self.nbp_limit = int(getattr(
            self.params, "no_better_propagation_limit", 3))
        self.beam_width = int(getattr(self.params, "beam_width", 16))
        self._place(np.concatenate(blocks_data),
                    np.concatenate(blocks_graph),
                    np.concatenate(blocks_del),
                    np.stack(blocks_pid), np.stack(blocks_pvec),
                    np.stack(blocks_pmask))
        if dense:
            self._place_dense(shard_indexes)
        if int(getattr(self.params, "mesh_serve", 0)):
            # index-level MeshServe=1 (core/params.py): the OFFLINE
            # mirror of the [Service] setting — bench / CLI runs arm the
            # mesh scheduler at placement time, no serve tier required
            self.enable_continuous_batching()
        return self

    def _place_dense(self, shard_indexes) -> None:
        """Pad every shard's dense layout to one (C, P) geometry and lay
        the stacked arrays out over the mesh (leading shard axis).

        Layouts are computed entirely HOST-side (DenseTreeSearcher.
        build_layout) — device-building each shard's searcher would
        concentrate a full second corpus copy on the default device and
        round-trip it back to host, an OOM at exactly the multi-chip
        scale this mode targets."""
        from sptag_tpu.algo.dense import DenseTreeSearcher

        host = []
        for sub in shard_indexes:
            _, clusters = sub._dense_clusters()
            host.append(DenseTreeSearcher.build_layout(
                sub._host[:sub._n], clusters, self.metric, replicas=1))
        n_dev = self.mesh.devices.size
        C = max(h["perm"].shape[0] for h in host)
        Pb = max(h["perm"].shape[1] for h in host)
        D = host[0]["perm"].shape[2]
        # preallocate the stacked buffers and fill per-shard VIEWS so the
        # padded layouts never exist twice in host memory (dense_perm is a
        # full second corpus copy)
        dp = np.zeros((n_dev, C, Pb, D), host[0]["perm"].dtype)
        mi = np.empty((n_dev, C, Pb), np.int32)
        ms = np.zeros((n_dev, C, Pb), np.float32)
        ce = np.zeros((n_dev, C, D), np.float32)
        cs = np.zeros((n_dev, C), np.float32)
        cv = np.zeros((n_dev, C), bool)
        for s, h in enumerate(host):
            DenseTreeSearcher.pad_layout(
                h, C, Pb, D,
                out=dict(dense_perm=dp[s], dense_ids=mi[s], dense_sq=ms[s],
                         dense_cent=ce[s], dense_cent_sq=cs[s],
                         dense_cent_valid=cv[s]))
        mesh = self.mesh
        r2 = NamedSharding(mesh, P(SHARD_AXIS, None))
        r3 = NamedSharding(mesh, P(SHARD_AXIS, None, None))
        r4 = NamedSharding(mesh, P(SHARD_AXIS, None, None, None))
        self.dense_perm = jax.device_put(dp, r4)
        self.dense_ids = jax.device_put(mi, r3)
        self.dense_sq = jax.device_put(ms, r3)
        self.dense_cent = jax.device_put(ce, r3)
        self.dense_cent_sq = jax.device_put(cs, r2)
        self.dense_cent_valid = jax.device_put(cv, r2)
        self.dense_cluster_size = Pb
        self.dense_num_clusters = C
        # the dense pack is a second mesh-resident corpus copy — its own
        # ledger component so /debug/memory attributes it separately
        devmem.track("dense_blocks", self,
                     self.dense_perm.nbytes + self.dense_ids.nbytes
                     + self.dense_sq.nbytes + self.dense_cent.nbytes
                     + self.dense_cent_sq.nbytes
                     + self.dense_cent_valid.nbytes)

    def search_dense(self, queries: np.ndarray, k: int = 10,
                     max_check: Optional[int] = None,
                     normalized: bool = False,
                     budget_policy: Optional[str] = None
                     ) -> Tuple[np.ndarray, np.ndarray]:
        """Multi-chip dense mode: every shard probes the top blocks of its
        own partition in one shard_map program with an all-gather top-k
        merge.  Requires `build(..., dense=True)`.  `budget_policy`
        splits MaxCheck across shards like `search` does (the budget
        drives each shard's nprobe)."""
        if not hasattr(self, "dense_perm"):
            raise RuntimeError(
                "dense layout not packed — build with dense=True")
        queries = np.asarray(queries)
        if queries.ndim == 1:
            queries = queries[None, :]
        if self.metric == DistCalcMethod.Cosine and not normalized:
            queries = dist_ops.normalize(queries, self.base)
        max_check = max_check if max_check is not None else self.max_check
        policy = budget_policy or self.budget_policy
        if policy not in ("full", "proportional", "guarded"):
            raise ValueError(f"unknown budget policy {policy!r}")
        k_local_cap = min(k, self.n_local)
        mc_shard = self._resolve_budget(
            queries, k, max_check, k_local_cap, policy,
            lambda qs, mc: self._search_dense_raw(qs, k, mc),
            mode="dense")
        if policy != "full":
            # dense budget maps to nprobe: never drop below 2 probes per
            # shard — a single probe has no second-best block to rescue
            # boundary rows, which craters recall on coarse partitions
            mc_shard = min(max_check,
                           max(mc_shard, 2 * self.dense_cluster_size))
        return self._search_dense_raw(queries, k, mc_shard)

    def _search_dense_raw(self, queries: np.ndarray, k: int,
                          max_check: int
                          ) -> Tuple[np.ndarray, np.ndarray]:
        nprobe = int(np.clip(-(-max_check // self.dense_cluster_size), 1,
                             self.dense_num_clusters))
        n_dev = self.mesh.devices.size
        k_local = min(self._merge_k_local(k),
                      nprobe * self.dense_cluster_size)
        k_final = min(k, self.n, k_local * n_dev)
        # dedup=False: shards are packed replica-free (_place_dense forces
        # replicas=1), so no id can appear in two probed blocks
        d, ids = _sharded_dense_kernel(
            self.dense_perm, self.dense_ids, self.dense_sq,
            self.dense_cent, self.dense_cent_sq, self.dense_cent_valid,
            self.deleted, jnp.asarray(queries), k_local, k_final, nprobe,
            int(self.metric), self.base, False, self.mesh,
            binned_bins=topk_bins.resolve_bins(
                self._binned_mode(), k_local,
                nprobe * self.dense_cluster_size, self._recall_target()))
        with trace.span("index.readback"):
            d, ids = np.asarray(d), np.asarray(ids)
        return _pad_to_k(d, ids, k, k_final)

    def _place(self, data, graph, deleted, pivot_ids, pivot_vecs,
               pivot_mask) -> None:
        """device_put the stacked per-shard arrays with row sharding."""
        mesh = self.mesh
        rows = NamedSharding(mesh, P(SHARD_AXIS, None))
        vec = NamedSharding(mesh, P(SHARD_AXIS))
        rows3 = NamedSharding(mesh, P(SHARD_AXIS, None, None))
        self.data = jax.device_put(data, rows)
        self.sqnorm = jax.jit(dist_ops.row_sqnorms,
                              out_shardings=vec)(self.data)
        self.graph = jax.device_put(graph, rows)
        self.deleted = jax.device_put(deleted, vec)
        self.pivot_ids = jax.device_put(pivot_ids, rows)
        self.pivot_vecs = jax.device_put(pivot_vecs, rows3)
        self.pivot_mask = jax.device_put(pivot_mask, rows)
        # tiered cascade (CascadeSearch, ops/cascade.py ISSUE 14): place
        # the int8 quantization as the walk's scoring shadow (quarter
        # the gather bytes per shard); the per-shard finalize re-ranks
        # against the resident fp blocks.  Mesh serving keeps the fp
        # corpus device-resident — CorpusTier=host is a single-chip
        # residency feature and is rejected rather than silently
        # downgraded.
        self.data_score = None
        self.score_scale = 0.0
        if int(getattr(self.params, "cascade_search", 0) or 0) \
                and np.issubdtype(np.asarray(data).dtype, np.floating):
            from sptag_tpu.ops import cascade as cascade_ops

            tier = cascade_ops.normalize_tier(
                getattr(self.params, "corpus_tier", "device"))
            if tier != "device":
                raise ValueError(
                    "CorpusTier=host is a single-chip engine feature; "
                    "mesh shards keep the fp corpus resident (run the "
                    "mesh cascade with CorpusTier=device)")
            int8_np, scale = cascade_ops.quantize_int8(
                np.asarray(data, np.float32))
            self.data_score = jax.device_put(int8_np, rows)
            self.score_scale = cascade_ops.walk_score_scale(
                True, np.int8, scale)
        # device-memory ledger (ISSUE 11 satellite): the mesh-resident
        # shard blocks, one aggregate entry per placement — a swap's old
        # placement drops off the gauge when it is collected
        devmem.track("shard_blocks", self,
                     self.data.nbytes + self.sqnorm.nbytes
                     + self.graph.nbytes + self.deleted.nbytes
                     + self.pivot_ids.nbytes + self.pivot_vecs.nbytes
                     + self.pivot_mask.nbytes)
        if self.data_score is not None:
            devmem.track("int8_blocks", self, self.data_score.nbytes)
        _publish_placement(mesh.devices.size, self.n_local)

    # ---- per-shard budget policy (VERDICT r3 item 8) ---------------------

    def set_budget_policy(self, policy: str,
                          guard_overlap: Optional[float] = None) -> None:
        """"full" | "proportional" | "guarded" — how the query MaxCheck
        splits across shards.  Changing the policy clears the guarded
        calibration cache."""
        if policy not in ("full", "proportional", "guarded"):
            raise ValueError(f"unknown budget policy {policy!r}")
        self.budget_policy = policy
        if guard_overlap is not None:
            self.budget_guard_overlap = float(guard_overlap)
        self._guarded_cache.clear()

    def _proportional_budget(self, max_check: int, k_local: int,
                             mult: int = 1) -> int:
        """ceil(MaxCheck / n_dev) * mult, floored so tiny budgets still
        walk (4*k_local candidates or 64, whichever is larger) and capped
        at the full budget."""
        n_dev = self.mesh.devices.size
        mc = -(-max_check // n_dev) * mult
        return int(min(max_check, max(mc, 4 * k_local, 64)))

    def _resolve_budget(self, queries: np.ndarray, k: int, max_check: int,
                        k_local: int, policy: str, search_at,
                        mode: str = "beam") -> int:
        """Per-shard budget under the active policy.  "guarded"
        calibrates ONCE per (mode, max_check, k): the smallest
        proportional multiplier whose top-k overlaps the full-budget
        top-k by >= budget_guard_overlap on a sample of the live batch —
        the multiplier is cached, so steady-state searches pay nothing."""
        if policy == "full" or self.mesh.devices.size == 1:
            return max_check
        if policy == "proportional":
            return self._proportional_budget(max_check, k_local)
        key = (mode, int(max_check), int(k))
        hit = self._guarded_cache.get(key)
        if hit is not None:
            return hit
        sample = queries[:min(32, len(queries))]
        _, ids_full = search_at(sample, max_check)
        mult = 1
        while True:
            mc = self._proportional_budget(max_check, k_local, mult)
            if mc >= max_check:
                self._guarded_cache[key] = max_check
                return max_check
            _, ids_m = search_at(sample, mc)
            # -1 sentinels (padding / tombstoned slots) must not count as
            # agreement — overlap is over the REAL full-budget ids only
            overlaps = []
            for i in range(len(sample)):
                full = set(int(v) for v in ids_full[i] if v >= 0)
                got = set(int(v) for v in ids_m[i] if v >= 0)
                overlaps.append(len(got & full) / max(1, len(full)))
            if float(np.mean(overlaps)) >= self.budget_guard_overlap:
                self._guarded_cache[key] = mc
                return mc
            mult *= 2

    def search(self, queries: np.ndarray, k: int = 10,
               max_check: Optional[int] = None,
               beam_width: Optional[int] = None,
               pool_size: Optional[int] = None,
               normalized: bool = False,
               budget_policy: Optional[str] = None
               ) -> Tuple[np.ndarray, np.ndarray]:
        """Batched mesh search; same knob semantics as
        GraphSearchEngine.search, applied per shard.  `max_check` and
        `beam_width` default to the build params (MaxCheck / BeamWidth).
        `budget_policy` overrides the index policy for this call (see
        set_budget_policy — "full" reproduces the reference Aggregator's
        n_dev x total work; "proportional"/"guarded" hold total work near
        the single-chip budget)."""
        queries = np.asarray(queries)
        if queries.ndim == 1:
            queries = queries[None, :]
        if not int(getattr(self.params, "build_graph", 1)):
            raise RuntimeError(
                "mesh beam search needs the RNG graph, but the shards were "
                "built with BuildGraph=0 (dense-only); use search_dense or "
                "rebuild with BuildGraph=1")
        if self.metric == DistCalcMethod.Cosine and not normalized:
            queries = dist_ops.normalize(queries, self.base)
        max_check = max_check if max_check is not None else self.max_check
        beam_width = (beam_width if beam_width is not None
                      else self.beam_width)
        k_local = min(k, self.n_local)     # per-shard beam cap
        policy = budget_policy or self.budget_policy
        if policy not in ("full", "proportional", "guarded"):
            raise ValueError(f"unknown budget policy {policy!r}")
        mc_shard = self._resolve_budget(
            queries, k, max_check, k_local, policy,
            lambda qs, mc: self._search_raw(qs, k, mc, beam_width,
                                            pool_size))
        return self._search_raw(queries, k, mc_shard, beam_width,
                                pool_size)

    def _binned_mode(self) -> str:
        """BinnedTopK of the shard params (the mesh face of the
        engine-baked knob); normalized once per call — the kernels key
        their compiles on the resolved bin count, not the string."""
        return topk_bins.normalize_mode(
            getattr(self.params, "binned_topk", "off"))

    def _recall_target(self) -> float:
        return topk_bins.validate_recall_target(
            getattr(self.params, "approx_recall_target", 0.99))

    def _merge_k_local(self, k: int) -> int:
        """Per-shard contribution to the global merge: min(k, n_local)
        by default; `MeshKLocal` (core/params.py) caps it lower to trade
        all-gather traffic for merge completeness on wide meshes (a
        shard holding more than k_local of the true global top-k drops
        the excess).  0 = off (exact merge)."""
        cap = int(getattr(self.params, "mesh_k_local", 0) or 0)
        k_local = min(k, self.n_local)
        return min(k_local, cap) if cap > 0 else k_local

    def _search_raw(self, queries: np.ndarray, k: int, max_check: int,
                    beam_width: int, pool_size: Optional[int]
                    ) -> Tuple[np.ndarray, np.ndarray]:
        n_dev = self.mesh.devices.size
        k_local = self._merge_k_local(k)   # per-shard beam cap
        k_final = min(k, self.n, k_local * n_dev)   # global merge cap
        from sptag_tpu.algo.engine import beam_pool_size, beam_width_for
        L = beam_pool_size(k_local, max_check, self.n_local, pool_size)
        B = beam_width_for(beam_width, max_check, L)
        T = max(1, -(-max_check // B))
        limit = max(self.nbp_limit, (max_check // 64) // B, 1)
        # BinnedTopK (ISSUE 13): the SAME shared bin rules the
        # single-chip engine and the mesh scheduler resolve, so the
        # monolithic and scheduler mesh paths stay id-identical
        mb = topk_bins.walk_merge_bins(
            self._binned_mode(), L, L + B * int(self.graph.shape[1]))
        fb = topk_bins.resolve_bins(self._binned_mode(), k_local, L,
                                    self._recall_target())
        sk = topk_bins.seed_spare_keep(
            self._binned_mode(), L,
            max(int(self.pivot_ids.shape[1]), L))
        d, ids = _sharded_beam_kernel(
            self.data, self.sqnorm, self.graph, self.deleted,
            self.pivot_ids, self.pivot_vecs, self.pivot_mask,
            jnp.asarray(queries), k_local, k_final, L, B, T,
            int(self.metric), self.base, limit, self.mesh,
            merge_bins=mb, finalize_bins=fb, seed_keep=sk,
            score_scale=getattr(self, "score_scale", 0.0),
            data_score=getattr(self, "data_score", None))
        with trace.span("index.readback"):
            d, ids = np.asarray(d), np.asarray(ids)
        return _pad_to_k(d, ids, k, k_final)
