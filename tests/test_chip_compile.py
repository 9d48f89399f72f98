"""The served path's device programs, compiled for a DESCRIBED TPU v5e.

Interpret-mode and CPU tests cannot see what the chip's compiler refuses
(an unaligned int8 row load got through every one of them), so the
programs the benchmark's cells and `chip_smoke.py` run are compiled here
at their real widths
against a `v5e:2x2` topology that is described, not attached.  Nothing
runs: a pass says "the compiler accepts it", never "it is right" or
"it is fast".

The topology is described only inside the module-scoped fixture below
(never at import: under pytest-xdist every worker imports this file, and
only the one that runs it may load libtpu), and every compile happens in
this process with the persistent compile cache off.
"""

import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from sptag_tpu.core.types import DistCalcMethod

L2 = int(DistCalcMethod.L2)
COS = int(DistCalcMethod.Cosine)

# chip_smoke.py's shapes: 200k x 128 f32 L2 and 200k x 384 int8 cosine BKT
# (DenseClusterSize 256 -> ~1024 blocks of 256 rows, MaxCheck 2048 ->
# nprobe 8), FLAT at 1M x 128, k = 10
N_BKT, C_BLK, P_BLK, NPROBE, K = 200_000, 1024, 256, 8, 10
DTYPES = {"f32": jnp.float32, "int8": jnp.int8}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                              # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-device executable is written to the persistent cache
    # but can never be read back without the chip
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    from sptag_tpu.parallel.sharded import SHARD_AXIS

    assert len(topo.devices) == 4
    return Mesh(np.asarray(topo.devices), (SHARD_AXIS,))


def _on(sharding, tree):
    """Shapes of `tree` (ShapeDtypeStructs / eval_shape output) placed on
    `sharding`; None leaves (optional kernel operands) stay None."""
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _s(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _has_mosaic_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def _row_wide_selections(compiled, n):
    """Operations of the compiled program that sort or top-k an operand
    `n` columns (or rows) wide: the N-wide selection the FLAT scan's
    two-stage select (ISSUE 28) exists to avoid."""
    wide = re.compile(rf"[\[,]{n}[\],]")
    return [line.strip()[:160] for line in compiled.as_text().splitlines()
            if ('custom_call_target="TopK"' in line or " sort(" in line
                or "/top_k" in line) and wide.search(line)]


# ---------------------------------------------------------------------------
# Pallas kernels
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype,D,Q,nprobe", [
    ("f32", 128, 256, 8), ("f32", 128, 1024, 64), ("f32", 768, 256, 8),
    ("int8", 128, 256, 8), ("int8", 384, 1024, 8), ("int8", 128, 1024, 64),
])
def test_probe_block_dots_compiles(one_chip, dtype, D, Q, nprobe):
    from sptag_tpu.ops import pallas_kernels

    dt = DTYPES[dtype]
    compiled = pallas_kernels.probe_block_dots.lower(
        _s(one_chip, (C_BLK, P_BLK, D), dt), _s(one_chip, (Q, D), dt),
        _s(one_chip, (Q, nprobe), jnp.int32)).compile()
    assert _has_mosaic_kernel(compiled)


@pytest.mark.parametrize("dtype,Q", [
    (jnp.int8, 128), (jnp.uint8, 128), (jnp.int8, 512),
])
def test_scan_group_minima_compiles_at_msmarco(one_chip, dtype, Q):
    """The fused FLAT scan's kernel alone (PR 37) at
    `flat_msmarco_i8.saturate`'s shape: 69,077 groups in tiles of 64 (the
    last one ragged), every rung `flat.fused_minima` sends to it; its
    only output is the (groups, Q) minima."""
    from sptag_tpu.algo.flat import pad_rows
    from sptag_tpu.ops import pallas_kernels

    n, D = pad_rows(8_841_823), 384
    lowered = pallas_kernels.scan_group_minima.lower(
        _s(one_chip, (n, D), dtype), _s(one_chip, (n,), jnp.bool_),
        _s(one_chip, (Q, D), dtype), base=127)
    assert lowered.out_info.shape == (n // 128, Q)
    compiled = lowered.compile()
    assert _has_mosaic_kernel(compiled)
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize("dtype,D,G", [
    ("f32", 128, 8), ("f32", 128, 32), ("int8", 128, 32), ("int8", 384, 32),
])
def test_group_block_dots_compiles(one_chip, dtype, D, G):
    from sptag_tpu.ops import pallas_kernels

    dt = DTYPES[dtype]
    Q, U = 256, 2 * NPROBE
    compiled = pallas_kernels.group_block_dots.lower(
        _s(one_chip, (C_BLK, P_BLK, D), dt), _s(one_chip, (Q, D), dt),
        _s(one_chip, (Q // G, U), jnp.int32)).compile()
    assert _has_mosaic_kernel(compiled)


# ---------------------------------------------------------------------------
# dense tree-partition search (SearchMode=dense), Pallas route
# ---------------------------------------------------------------------------

def _dense_args(sh, dtype, D, Q):
    dt = DTYPES[dtype]
    return (_s(sh, (C_BLK, P_BLK, D), dt),             # data_perm
            _s(sh, (C_BLK, P_BLK), jnp.int32),         # member_ids
            _s(sh, (C_BLK, P_BLK), jnp.float32),       # member_sq
            _s(sh, (C_BLK, D), jnp.float32),           # centroids
            _s(sh, (C_BLK,), jnp.float32),             # cent_sq
            _s(sh, (C_BLK, P_BLK), jnp.bool_),         # dead_slot
            _s(sh, (Q, D), dt))                        # queries


@pytest.mark.parametrize("dtype,D,metric,base", [
    ("f32", 128, L2, 1), ("int8", 384, COS, 127),
])
def test_dense_search_kernel_compiles_with_pallas(one_chip, dtype, D,
                                                  metric, base):
    from sptag_tpu.algo.dense import _dense_search_kernel

    compiled = _dense_search_kernel.lower(
        *_dense_args(one_chip, dtype, D, 256), k=K, nprobe=NPROBE,
        metric=metric, base=base, use_pallas=True,
        interpret=False).compile()
    assert _has_mosaic_kernel(compiled)


@pytest.mark.parametrize("dtype,D,metric,base,G", [
    ("f32", 128, L2, 1, 8), ("int8", 384, COS, 127, 32),
])
def test_dense_grouped_kernel_compiles_with_pallas(one_chip, dtype, D,
                                                   metric, base, G):
    from sptag_tpu.algo.dense import _dense_search_grouped_kernel

    compiled = _dense_search_grouped_kernel.lower(
        *_dense_args(one_chip, dtype, D, 256),
        _s(one_chip, (), jnp.int32), k=K, nprobe=NPROBE, U=2 * NPROBE, G=G,
        metric=metric, base=base, use_pallas=True,
        interpret=False).compile()
    assert _has_mosaic_kernel(compiled)


# the dense-only cell (bkt_deep10m.saturate, PR 48): 10M x 96 f32 in ~45k
# blocks of 256 rows, MaxCheck 65,536 -> 256 blocks a query.  96-d rows are
# off the Pallas route (`pallas_kernels.supported`: the kernel's (P, D)
# block wants whole 128-lane rows; compiled for 96 it re-lays the WHOLE
# resident layout out, 5.9 GB of temporaries a call), so the XLA gather
# scores the probed blocks
DEEP_N, DEEP_C, DEEP_D, DEEP_NPROBE = 10_000_000, 45_056, 96, 256


@pytest.mark.parametrize("Q", [1, 8, 32, 128])
def test_dense_search_kernel_compiles_deep10m_dense_only(one_chip, Q):
    from sptag_tpu.algo.dense import _dense_search_kernel
    from sptag_tpu.ops import pallas_kernels

    perm = _s(one_chip, (DEEP_C, P_BLK, DEEP_D), jnp.float32)
    assert not pallas_kernels.supported(perm)
    compiled = _dense_search_kernel.lower(
        perm, _s(one_chip, (DEEP_C, P_BLK), jnp.int32),
        _s(one_chip, (DEEP_C, P_BLK), jnp.float32),
        _s(one_chip, (DEEP_C, DEEP_D), jnp.float32),
        _s(one_chip, (DEEP_C,), jnp.float32),
        _s(one_chip, (DEEP_C, P_BLK), jnp.bool_),
        _s(one_chip, (Q, DEEP_D), jnp.float32), k=K, nprobe=DEEP_NPROBE,
        metric=L2, base=1, use_pallas=False, interpret=False).compile()
    assert not _has_mosaic_kernel(compiled)
    mem = compiled.memory_analysis()
    # the resident layout is held compact (no 96 -> 128 lane padding: that
    # would be 5.9 GB): blocks + ids + norms + one dead byte a slot + means
    resident = DEEP_C * P_BLK * (DEEP_D * 4 + 9) + DEEP_C * (DEEP_D + 1) * 4
    assert mem.argument_size_in_bytes <= resident + (1 << 20)
    # the gathered candidates (Q, nprobe x 256, 96) f32 and their scores,
    # once: 3.26 GB at the 128 rung, inside `gather_budget()` of a v5e
    assert mem.temp_size_in_bytes \
        <= 1.1 * Q * DEEP_NPROBE * P_BLK * (DEEP_D + 4) * 4 + (1 << 22)


@pytest.mark.parametrize("n,D,Q", [
    (1_000_064, 128, 128), (1_000_064, 128, 512),       # flat_1m
    (5_312_640, 100, 128), (5_312_640, 100, 512),       # flat_live5m
    (2_500_096, 96, 128),                               # a deep-10M shard
])
def test_scan_group_minima_compiles_on_float_rows(one_chip, n, D, Q):
    """The float32 L2 form of the scan kernel (PR 41) at the three float
    cells' blocks.  Rows narrower than a lane tile are resident
    column-major and read through their transpose: a kernel that asked
    for `(rows, D)` blocks of them would show a copy of the whole block
    among the temporaries (2.72 GB at 5,312,640 x 100)."""
    from sptag_tpu.ops import pallas_kernels

    lowered = pallas_kernels.scan_group_minima.lower(
        _s(one_chip, (n, D), jnp.float32), _s(one_chip, (n,), jnp.bool_),
        _s(one_chip, (Q, D), jnp.float32), base=1,
        sqnorm=_s(one_chip, (n,), jnp.float32))
    assert lowered.out_info.shape == (n // 128, Q)
    compiled = lowered.compile()
    assert _has_mosaic_kernel(compiled)
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2 ** 20
    param = re.search(r"entry_computation_layout=\{\(f32\[\d+,\d+\]\{(\d),",
                      compiled.as_text())
    assert (param.group(1) == "0") == pallas_kernels.column_major(
        jnp.float32, D)


@pytest.mark.parametrize("D", [100, 128])
def test_float_filter_stays_inside_eps_on_the_chip(D):
    """CHIP-ONLY (skipped wherever the first device is no TPU; run it
    with `chiprun -- python -m pytest tests/test_chip_compile.py -k
    on_the_chip --noconftest`: tests/conftest.py pins the CPU): over a
    real block of clustered rows the kernel's group minima lie within a
    small share of `l2_minima_eps` of the materialised scores' (the
    bound is a worst case: 0.003-0.004 of it was read, PERF.md section
    6, PR 41), a masked group reads MAX_DIST on both sides, and the
    program's selection is proved with the materialised program's
    lists, place for place inside the configurations' tie rule."""
    if jax.devices()[0].platform != "tpu":
        pytest.skip("needs the chip: the MXU's float32 contraction")
    from sptag_tpu.algo import flat
    from sptag_tpu.core.index import MAX_DIST
    from sptag_tpu.ops import distance as dist_ops
    from sptag_tpu.ops import pallas_kernels

    n, Q = 1_000_064, 128
    keys = jax.random.split(jax.random.PRNGKey(D), 5)
    centers = 4.0 * jax.random.normal(keys[0], (256, D), jnp.float32)
    data = jax.jit(lambda: centers[jax.random.randint(keys[1], (n,), 0, 256)]
                   + jax.random.normal(keys[2], (n, D), jnp.float32))()
    queries = (centers[jax.random.randint(keys[3], (Q,), 0, 256)]
               + jax.random.normal(keys[4], (Q, D), jnp.float32))
    queries = queries.at[Q // 2:].set(0.0)
    invalid = jnp.zeros((n,), bool).at[-4_000:].set(True)
    sqnorm = dist_ops.row_sqnorms(data)
    got = pallas_kernels.scan_group_minima(data, invalid, queries, base=1,
                                           sqnorm=sqnorm)
    d = jnp.where(invalid[None, :], jnp.float32(MAX_DIST),
                  dist_ops.pairwise_l2(queries, data, sqnorm))
    want = d.T.reshape(n // 128, 128, Q).min(axis=1)
    eps = pallas_kernels.l2_minima_eps(D, dist_ops.row_sqnorms(queries),
                                       sqnorm)
    live = want < MAX_DIST
    assert bool(jnp.all((got < MAX_DIST) == live))
    off = jnp.where(live, jnp.abs(got - want), 0.0) / eps[None, :]
    assert float(off.max()) < 0.05
    dists, ids, unproved = flat._flat_search_kernel(
        data, sqnorm, invalid, queries, K, metric=L2, base=1, fused=True)
    want_dists, want_ids = flat._flat_search_kernel(
        data, sqnorm, invalid, queries, K, metric=L2, base=1)
    assert not bool(unproved)
    # the two programs score a row with two schedules of one float32
    # contraction: place for place the lists agree inside the
    # configurations' tie rule (8 float32 ulps of |q|^2 + |x|^2, the
    # magnitude both round at), and an id differs only where two
    # neighbours lie that close (one swap in 1,280 places was seen)
    scale = np.asarray(dist_ops.row_sqnorms(queries) + jnp.max(sqnorm))
    assert (np.abs(np.asarray(dists) - np.asarray(want_dists))
            <= 8 * np.finfo(np.float32).eps * scale[:, None]).all()
    assert float(jnp.mean(ids == want_ids)) > 0.99


def _assert_proved_route(compiled, n, Q, k):
    """A float program on the fused route (PR 41): the kernel, the flag,
    nothing sorted N wide, no copy of the block, and the (Q, N) scores
    alone among the temporaries (the branch an unproved run takes)."""
    text = compiled.as_text()
    assert _has_mosaic_kernel(compiled)
    assert not _row_wide_selections(compiled, n)
    assert not _row_wide_selections(compiled, n // 128)     # a partial TopK
    assert f"f32[{n // 128},{Q}]" in text                   # the minima
    # (at 512 queries that branch's slabs of the chosen groups, Q*Q*k*512
    # bytes, do not stay out of device memory: `exact_topk`)
    mem = compiled.memory_analysis()
    slabs = Q * Q * k * 128 * 4 if Q > 128 else 0
    assert mem.temp_size_in_bytes < Q * n * 4 + slabs \
        + Q * (k + 6) * (1 << 16) + (64 << 20)


# ---------------------------------------------------------------------------
# FLAT exact scan at SIFT1M's shape
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("Q", [1, 128, 512])
def test_flat_search_kernel_compiles_1m(one_chip, Q):
    from sptag_tpu.algo.flat import _flat_search_kernel, fused_minima

    n, D = 1_000_064, 128           # 1M rows padded to the 128-row bucket
    # the route is the rule's for a TPU, passed as the index passes it
    # (this process sees a CPU): the 128 and 512 rungs since PR 41
    fused = fused_minima(np.dtype(np.float32), Q, n, D, K, L2, "tpu")
    assert fused == (Q >= 128)
    lowered = _flat_search_kernel.lower(
        _s(one_chip, (n, D), jnp.float32), _s(one_chip, (n,), jnp.float32),
        _s(one_chip, (n,), jnp.bool_), _s(one_chip, (Q, D), jnp.float32),
        k=K, metric=L2, base=1, fused=fused)
    assert len(lowered.out_info) == 2 + fused
    compiled = lowered.compile()
    if fused:
        _assert_proved_route(compiled, n, Q, K)
    mem = compiled.memory_analysis()
    # corpus + the (Q, N) distance matrix + top-k scratch must fit 16 GB
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes) < 15 * 2 ** 30
    text = compiled.as_text()
    for scope in ("flat.distance", "flat.topk"):
        assert scope in text, scope
    if Q > 1:
        # two stages: nothing sorts the 1M-wide rows, and the scores are
        # written once, in the layout both stages read (the parent's
        # temporaries were the (Q, N) scores alone: 512.0 MB at Q=128);
        # at 512 queries the slabs of the chosen groups, Q*Q*k*512 bytes,
        # no longer stay out of device memory
        assert not _row_wide_selections(compiled, n)
        slabs = Q * Q * K * 128 * 4 if Q > 128 else 0
        assert mem.temp_size_in_bytes < 1.01 * (Q * n * 4 + slabs)


@pytest.mark.parametrize("Q", [1, 8, 32, 128, 512])
def test_flat_search_kernel_compiles_msmarco_int8(one_chip, Q):
    """`flat_msmarco_i8.saturate`'s own programs (PR 34): 8,841,823 x 384
    int8 rows in SPTAG's integer cosine, one per warm bucket (and the 512
    rung the rule sends the same way).  The contraction takes the
    one-byte rows as they are (s8 x s8 -> s32: no int32 copy of the 3.4
    GB block, which would be 13.6 GB).  At 128 queries and more (PR 37)
    the group minima come out of the Pallas scan and the (Q, N) scores -
    4.53 GB at 128 queries - are never written: the route is the rule's
    for a TPU, passed as the index passes it (this process sees a CPU)."""
    from sptag_tpu.algo.flat import (_flat_search_kernel, fused_minima,
                                     pad_rows)

    n, D = pad_rows(8_841_823), 384
    assert n == 8_841_856
    fused = fused_minima(np.dtype(np.int8), Q, n, D, K, COS, "tpu")
    assert fused == (Q >= 128)
    compiled = _flat_search_kernel.lower(
        _s(one_chip, (n, D), jnp.int8), _s(one_chip, (n,), jnp.float32),
        _s(one_chip, (n,), jnp.bool_), _s(one_chip, (Q, D), jnp.int8),
        k=K, metric=COS, base=127, fused=fused).compile()
    mem = compiled.memory_analysis()
    rows = n * D
    text = compiled.as_text()
    for scope in ("flat.distance", "flat.topk"):
        assert scope in text, scope
    assert _has_mosaic_kernel(compiled) == fused
    if fused:
        # the cosine program never reads the norms: they are no argument
        assert rows <= mem.argument_size_in_bytes < rows + n + (1 << 20)
        # the minima, the chosen slabs (Q*k*128*384 bytes) and their
        # scores: nothing N x Q wide, no N-wide selection
        assert mem.temp_size_in_bytes < 0.3 * 2 ** 30
        assert f"[{n},{Q}]" not in text and f"[{Q},{n}]" not in text
        assert not _row_wide_selections(compiled, n)
        assert f"f32[{n // 128},{Q}]" in text               # the minima
        return
    assert rows <= mem.argument_size_in_bytes < rows + 5 * n + (1 << 20)
    # nothing beside the scores: no widened copy of the rows
    assert mem.temp_size_in_bytes < 1.01 * Q * n * 4 + (1 << 20)
    contractions = [line for line in text.splitlines()
                    if " convolution(" in line or " dot(" in line]
    # (one query: a multiply-and-add fusion over the rows, no contraction)
    assert len(contractions) == (Q > 1)
    assert all(" s32[" in line for line in contractions)
    if Q == 8:
        assert not _row_wide_selections(compiled, n)        # two stages


# ---------------------------------------------------------------------------
# FLAT with a corpus that changes (PR 40): `flat_live5m.stream`'s block
# once the first add has reserved a sixteenth ahead, 5,312,640 x 100 f32
# ---------------------------------------------------------------------------

LIVE_ROWS, LIVE_D = 5_000_000, 100


def _live_block(sh):
    """-> (slots, the block's shapes on `sh`; None: the slots alone)."""
    from sptag_tpu.algo.flat import reserved_slots

    n = reserved_slots(LIVE_ROWS + 128)
    assert n == 5_312_640
    if sh is None:
        return n, None
    return n, (_s(sh, (n, LIVE_D), jnp.float32), _s(sh, (n,), jnp.float32),
               _s(sh, (n,), jnp.bool_))


@pytest.mark.parametrize("Q,k", [(1, 10), (8, 10), (32, 10), (128, 10),
                                 (128, 32)])
def test_flat_search_kernel_compiles_live5m(one_chip, Q, k):
    """The cell's four search rungs and a delete's search by content
    (128 rows at k = CEF's default 32): the resident block, the (Q,
    slots) scores and the select's workspace fit the chip with the
    block's next copy (a growth holds two) to spare."""
    from sptag_tpu.algo.flat import _flat_search_kernel, fused_minima

    n, block = _live_block(one_chip)
    fused = fused_minima(np.dtype(np.float32), Q, n, LIVE_D, k, L2, "tpu")
    assert fused == (Q >= 128)
    compiled = _flat_search_kernel.lower(
        *block, _s(one_chip, (Q, LIVE_D), jnp.float32), k=k, metric=L2,
        base=1, fused=fused).compile()
    if fused:
        _assert_proved_route(compiled, n, Q, k)
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes) < 8 * 2 ** 30
    if Q in (8, 128):
        assert not _row_wide_selections(compiled, n)        # two stages


@pytest.mark.parametrize("rung", [8, 128, 1024])
def test_flat_block_writes_compile_in_place_live5m(one_chip, rung):
    """An add's write and a delete's mask write update the donated block
    where it lies: no second copy of the 2.1 GB of rows among the
    temporaries or the outputs."""
    from sptag_tpu.algo.flat import _block_mask_rows, _block_write_rows

    n, block = _live_block(one_chip)
    compiled = _block_write_rows.lower(
        *block, _s(one_chip, (rung, LIVE_D), jnp.float32),
        _s(one_chip, (rung,), jnp.bool_),
        _s(one_chip, (), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= n * LIVE_D * 4        # donated
    assert mem.temp_size_in_bytes < 2 ** 20
    compiled = _block_mask_rows.lower(
        block[2], _s(one_chip, (rung,), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= n and mem.temp_size_in_bytes < 2 ** 20


def test_flat_block_growth_compiles_live5m(one_chip):
    """The growth the cell's first add makes: the block as built
    (5,000,064 slots) copied on the device into the reserved one."""
    from sptag_tpu.algo.flat import _block_grown, pad_rows

    n0 = pad_rows(LIVE_ROWS)
    n, _ = _live_block(one_chip)
    compiled = _block_grown.lower(
        _s(one_chip, (n0, LIVE_D), jnp.float32),
        _s(one_chip, (n0,), jnp.float32), _s(one_chip, (n0,), jnp.bool_),
        slots=n).compile()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes >= n * LIVE_D * 4
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes) < 6 * 2 ** 30


# ---------------------------------------------------------------------------
# beam walk (SearchMode=beam) with the bf16 scoring corpus the engine
# picks only when it sees a TPU — a branch no CPU test takes
# ---------------------------------------------------------------------------

def _beam_plan(max_check=2048):
    from sptag_tpu.algo import engine

    L = engine.beam_pool_size(K, max_check, N_BKT)
    B = engine.beam_width_for(16, max_check, L)
    limit = max(3, (max_check // 64) // B, 1)
    return L, B, limit


def _engine_arrays(sh, n, D, m=32, pivots=8192):
    from sptag_tpu.algo.engine import _num_words

    return dict(
        data=_s(sh, (n, D), jnp.float32),
        data_score=_s(sh, (n, D), jnp.bfloat16),
        sqnorm=_s(sh, (n,), jnp.float32),
        graph=_s(sh, (n, m), jnp.int32),
        deleted=_s(sh, (n,), jnp.bool_),
        pivot_ids=_s(sh, (pivots,), jnp.int32),
        pivot_vecs=_s(sh, (pivots, D), jnp.float32),
        pivot_mask=_s(sh, (_num_words(n),), jnp.int32))


def test_beam_trio_compiles_bf16_scoring(one_chip):
    from sptag_tpu.algo import engine

    Q, D = 256, 128
    L, B, limit = _beam_plan()
    a = _engine_arrays(one_chip, N_BKT, D)
    q = _s(one_chip, (Q, D), jnp.float32)

    seed = engine._beam_seed_kernel.lower(
        a["pivot_ids"], a["pivot_vecs"], a["pivot_mask"], q, L=L,
        metric=L2, seed_keep=0)
    seed.compile()
    cand_ids, cand_d, visited, spare_ids, spare_d = _on(
        one_chip, seed.out_info)
    state = _on(one_chip, jax.eval_shape(engine._init_walk_state, cand_ids,
                                         cand_d, visited))

    seg = engine._beam_segment_kernel.lower(
        a["data"], a["sqnorm"], a["graph"], q,
        _s(one_chip, (Q,), jnp.int32), *state, k=K, L=L, B=B, S=4,
        metric=L2, base=1, nbp_limit=limit, inject=4, spare_ids=spare_ids,
        spare_d=spare_d, data_score=a["data_score"]).compile()
    assert "bf16" in seg.as_text()

    engine._beam_finalize_kernel.lower(
        a["data"], a["sqnorm"], a["deleted"], q, state[0], state[1],
        k_eff=K, metric=L2, base=1, rerank=True).compile()


def test_beam_monolithic_walk_compiles_bf16_scoring(one_chip):
    """The program `GraphSearchEngine.search` dispatches by default
    (seed + walk + finalize fused)."""
    from sptag_tpu.algo import engine

    Q, D = 256, 128
    L, B, limit = _beam_plan()
    a = _engine_arrays(one_chip, N_BKT, D)
    engine._beam_search_kernel.lower(
        a["data"], a["sqnorm"], a["graph"], a["deleted"], a["pivot_ids"],
        a["pivot_vecs"], a["pivot_mask"], _s(one_chip, (Q, D), jnp.float32),
        _s(one_chip, (Q,), jnp.int32), k=K, L=L, B=B, metric=L2, base=1,
        nbp_limit=limit, inject=4, data_score=a["data_score"]).compile()


@pytest.mark.parametrize("Q", [1, 8, 32, 128])
def test_beam_cell_rungs_compile_at_100k(one_chip, Q):
    """`bkt_100k_beam.saturate`'s own programs (PR 32): 100k x 128, the
    n / 24 = 4,166 pivots, MaxCheck 2048's plan (L 320, B 64), one per
    warm bucket; each returns the answers and the walk's live counts."""
    from sptag_tpu.algo import engine

    n, D = 100_000, 128
    L = engine.beam_pool_size(K, 2048, n)
    B = engine.beam_width_for(16, 2048, L)
    assert (L, B) == (320, 64)
    a = _engine_arrays(one_chip, n, D, pivots=n // 24)
    lowered = engine._beam_search_kernel.lower(
        a["data"], a["sqnorm"], a["graph"], a["deleted"], a["pivot_ids"],
        a["pivot_vecs"], a["pivot_mask"], _s(one_chip, (Q, D), jnp.float32),
        _s(one_chip, (Q,), jnp.int32), k=K, L=L, B=B, metric=L2, base=1,
        nbp_limit=3, inject=4, data_score=a["data_score"])
    out = lowered.out_info
    assert [o.shape for o in out] == [(Q, K), (Q, K), (Q,)]
    compiled = lowered.compile()
    text = compiled.as_text()
    assert "bf16" in text
    if Q == 128:
        # PR 33: of the trip's B x 32 = 2,048-wide ELEMENT gathers the
        # chip's compiler keeps one, of `visited` words (the parent's
        # program: three from s32 operands and the duplicate mask's way
        # back from a pred one)
        assert re.findall(rf"= (s32|pred)\[{Q},2048\]\S* gather\(",
                          text) == ["s32"]
        # PR 44: no float a candidate is fetched by id beside its row (the
        # parent gathered f32[128,2048] from the (N,) norms), and the
        # merge's pool-wide gathers by position are ONE, of the id's word
        # with the `expanded` flag in it (the parent: an s32 and a pred
        # one; the pred one left is the re-rank's tombstones)
        assert not re.findall(rf"= f32\[{Q},2048\]\S* gather\(", text)
        assert sorted(re.findall(rf"= (s32|pred)\[{Q},{L}\]\S* gather\(",
                                 text)) == ["pred", "s32"]
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 2 << 30


@pytest.mark.parametrize("Q", [1, 8, 32, 128])
def test_beam_cell_rungs_compile_at_100k_packed(one_chip, Q):
    """`bkt_100k_beam.saturate`'s programs since PR 45: the same plan with
    the packed-neighbour table (`bf16[100000,32,128]`, 0.82 GB: what
    `BeamPackedNeighbors=auto` takes on the chip at this size).  A trip
    fetches Q x B blocks of (32, 128) and no row by candidate id."""
    from sptag_tpu.algo import engine

    n, D, m = 100_000, 128, 32
    L = engine.beam_pool_size(K, 2048, n)
    B = engine.beam_width_for(16, 2048, L)
    assert engine.packed_layout_fits(n, m, D, 2, 16 << 30)
    a = _engine_arrays(one_chip, n, D, pivots=n // 24)
    compiled = engine._beam_search_kernel.lower(
        a["data"], a["sqnorm"], a["graph"], a["deleted"], a["pivot_ids"],
        a["pivot_vecs"], a["pivot_mask"], _s(one_chip, (Q, D), jnp.float32),
        _s(one_chip, (Q,), jnp.int32), k=K, L=L, B=B, metric=L2, base=1,
        nbp_limit=3, inject=4, data_score=a["data_score"],
        nbr_vecs=_s(one_chip, (n, m, D), jnp.bfloat16)).compile()
    text = compiled.as_text()
    # the vectors: one gather of Q x B blocks from the table, none of
    # Q x B x m rows from the shadow
    lead = "" if Q == 1 else f"{Q},"        # a lone query's axis is dropped
    assert len(re.findall(rf"= bf16\[{lead}{B},{m},{D}\]\S* gather\(",
                          text)) == 1
    assert not re.findall(rf"= bf16\[{lead}{B * m},{D}\]\S* gather\(", text)
    # the scores ride the ONE X-wide sort beside the ids, nothing else does
    assert re.findall(rf"= \((s32\[{Q},2048\]\S*, f32\[{Q},2048\]\S*)\) "
                      r"sort\(", text)
    if Q == 128:
        # the ensemble stays PR 33's: one X-wide element gather, of
        # `visited` words; the merge's one word gather by position
        assert re.findall(rf"= (s32|pred|f32)\[{Q},2048\]\S* gather\(",
                          text) == ["s32"]
        assert sorted(re.findall(rf"= (s32|pred)\[{Q},{L}\]\S* gather\(",
                                 text)) == ["pred", "s32"]
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 2 << 30


def test_the_beam_cells_table_is_written_with_no_copy_beside_it(one_chip):
    """`engine._pack_neighbors` at the beam cell's size: the program's
    output is the 0.82 GB table and it holds next to nothing else while
    it runs (dispatched op by op the same gather peaked at twice the
    table: PERF.md, PR 45), so `packed_layout_fits` budgets the table
    alone."""
    from sptag_tpu.algo import engine

    n, D, m = 100_000, 128, 32
    compiled = engine._pack_neighbors.lower(
        _s(one_chip, (n, D), jnp.bfloat16),
        _s(one_chip, (n, m), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes == n * m * D * 2
    assert mem.temp_size_in_bytes < 64 << 20


# ---------------------------------------------------------------------------
# four chips: one program across a (4,) mesh of the described devices
# ---------------------------------------------------------------------------

def _assert_per_device(compiled, at_most_bytes):
    mem = compiled.memory_analysis()
    per_dev = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
               + mem.output_size_in_bytes)
    assert per_dev < at_most_bytes, per_dev


def test_sharded_flat_kernel_compiles_4m_on_four(mesh4):
    """The sharded FLAT program at 4M x 128 f32, a quarter (512 MB) per
    chip, per-shard top-k merged by an all-gather (the deep-10M cell's
    own shape is compiled by the `deep10m` case)."""
    from sptag_tpu.parallel.sharded import (SHARD_AXIS,
                                            _sharded_search_kernel)

    n, D, Q = 4_000_000, 128, 64
    rows = NamedSharding(mesh4, P(SHARD_AXIS, None))
    vec = NamedSharding(mesh4, P(SHARD_AXIS))
    rep = NamedSharding(mesh4, P(None, None))
    compiled = _sharded_search_kernel.lower(
        _s(rows, (n, D), jnp.float32), _s(vec, (n,), jnp.float32),
        _s(vec, (n,), jnp.bool_), _s(rep, (Q, D), jnp.float32),
        k_local=K, k_final=K, metric=L2, base=1, mesh=mesh4).compile()
    assert "all-gather" in compiled.as_text()
    # per-device bytes: a quarter of the 2 GB corpus plus the (Q, N/4)
    # scores — a program that gathered the corpus onto one chip would
    # show the whole 2 GB here
    _assert_per_device(compiled, 2 ** 30)


@pytest.mark.parametrize("Q", [1, 8, 32, 128])
def test_sharded_flat_kernel_compiles_deep10m_on_four(mesh4, Q):
    """The benchmark's `sharded_deep10m.saturate`: 10M x 96 f32, 2.5M rows
    (960 MB) a chip, at every rung of the query ladder the cell warms."""
    from sptag_tpu.parallel.sharded import (SHARD_AXIS,
                                            _sharded_search_kernel)

    from sptag_tpu.algo.flat import pad_rows
    from sptag_tpu.parallel.sharded import ShardedFlatIndex

    # as `ShardedFlatIndex` places it: a shard stands for 2,500,000 rows
    # in a device block padded to 2,500,096
    D, stride = 96, ShardedFlatIndex.rows_per_shard(10_000_000, 4)
    n_slot = pad_rows(stride)
    n = 4 * n_slot
    rows = NamedSharding(mesh4, P(SHARD_AXIS, None))
    vec = NamedSharding(mesh4, P(SHARD_AXIS))
    rep = NamedSharding(mesh4, P(None, None))
    from sptag_tpu.algo.flat import fused_minima

    fused = fused_minima(np.dtype(np.float32), Q, n_slot, D, K, L2, "tpu")
    assert fused == (Q >= 128)
    lowered = _sharded_search_kernel.lower(
        _s(rows, (n, D), jnp.float32), _s(vec, (n,), jnp.float32),
        _s(vec, (n,), jnp.bool_), _s(rep, (Q, D), jnp.float32),
        k_local=K, k_final=K, metric=L2, base=1, mesh=mesh4,
        row_stride=stride, fused=fused)
    assert len(lowered.out_info) == 2 + fused       # the flag, replicated
    compiled = lowered.compile()
    text = compiled.as_text()
    assert _has_mosaic_kernel(compiled) == fused    # under shard_map too
    # at one query the compiler gathers by all-reduce of a padded slice
    assert "all-gather" in text or "all-reduce" in text
    # the stage names reach the compiled program's metadata
    for scope in ("flat.distance", "flat.topk", "mesh.merge"):
        assert scope in text, scope
    if Q == 128:
        # two stages a shard: nothing sorts its 2.5M-wide rows, and the
        # temporaries are the (Q, 2.5M) scores as before (1,280.1 MB)
        assert not _row_wide_selections(compiled, n_slot)
        assert compiled.memory_analysis().temp_size_in_bytes \
            < 1.02 * Q * n_slot * 4
    # a chip's share of the corpus, its (Q, 2.5M) scores and the top-k's
    # workspace: well inside 16 GB
    _assert_per_device(compiled, 6 * 2 ** 30)


# `sharded_live20m.stream` (PR 43): 20M x 100 f32 over four chips, a shard
# what `flat_live5m.stream`'s one chip holds (5,312,640 x 100 once the
# first add has reserved a sixteenth ahead)

def _mesh_live_block(mesh4, n_slot):
    from sptag_tpu.parallel.sharded import SHARD_AXIS

    rows = NamedSharding(mesh4, P(SHARD_AXIS, None))
    vec = NamedSharding(mesh4, P(SHARD_AXIS))
    return (_s(rows, (4 * n_slot, LIVE_D), jnp.float32),
            _s(vec, (4 * n_slot,), jnp.float32),
            _s(vec, (4 * n_slot,), jnp.bool_))


@pytest.mark.parametrize("Q,k", [(1, 10), (8, 10), (32, 10), (128, 10),
                                 (128, 32)])
def test_sharded_flat_kernel_compiles_live20m_on_four(mesh4, Q, k):
    """The cell's four search rungs and a delete's search by content
    (k = 32) as the living mesh index dispatches them after its first
    write: `row_stride` absent (the program's id is shard * slots + slot,
    the host's table turns it into the row's), the fused proved route a
    shard at 128 queries, no (Q, slots) scores outside its unproved
    branch, a chip's share well inside 16 GB with the block's next copy
    (a growth holds two) to spare."""
    from sptag_tpu.algo.flat import fused_minima
    from sptag_tpu.parallel.sharded import (ShardedFlatIndex,
                                            _sharded_search_kernel)

    assert ShardedFlatIndex.rows_per_shard(20_000_000, 4) == LIVE_ROWS
    n_slot, _ = _live_block(None)
    fused = fused_minima(np.dtype(np.float32), Q, n_slot, LIVE_D, k, L2,
                         "tpu")
    assert fused == (Q >= 128)
    lowered = _sharded_search_kernel.lower(
        *_mesh_live_block(mesh4, n_slot),
        _s(NamedSharding(mesh4, P(None, None)), (Q, LIVE_D), jnp.float32),
        k_local=k, k_final=k, metric=L2, base=1, mesh=mesh4,
        row_stride=None, fused=fused)
    assert len(lowered.out_info) == 2 + fused
    compiled = lowered.compile()
    text = compiled.as_text()
    assert _has_mosaic_kernel(compiled) == fused
    assert "all-gather" in text or "all-reduce" in text
    for scope in ("flat.distance", "flat.topk", "mesh.merge"):
        assert scope in text, scope
    if Q in (8, 128):
        assert not _row_wide_selections(compiled, n_slot)    # two stages
    _assert_per_device(compiled, 8 * 2 ** 30)


@pytest.mark.parametrize("rung", [8, 128, 1024])
def test_mesh_block_writes_are_one_devices_and_in_place_live20m(topo, rung):
    """A write rung and a mask rung of the living mesh index are the
    one-chip programs on the OWNING device's arrays (`ShardedFlatIndex.
    _device_append` / `_device_mask`): compiled for a device of the
    described 2x2 that is not the first, each is one device's program
    (no collective, no other device in it), donates the shard's block
    and keeps no temporary near its size."""
    from sptag_tpu.algo.flat import _block_mask_rows, _block_write_rows

    third = SingleDeviceSharding(topo.devices[2])
    n, block = _live_block(third)
    for compiled, donated in (
            (_block_write_rows.lower(
                *block, _s(third, (rung, LIVE_D), jnp.float32),
                _s(third, (rung,), jnp.bool_),
                _s(third, (), jnp.int32)).compile(), n * LIVE_D * 4),
            (_block_mask_rows.lower(
                block[2], _s(third, (rung,), jnp.int32)).compile(), n)):
        text = compiled.as_text()
        for collective in ("all-gather", "all-reduce", "all-to-all",
                           "collective-permute"):
            assert collective not in text, collective
        assert "num_partitions=1" in text or "num_partitions" not in text
        mem = compiled.memory_analysis()
        assert mem.alias_size_in_bytes >= donated
        assert mem.temp_size_in_bytes < 2 ** 20


def test_mesh_block_growth_compiles_a_device_live20m(topo):
    """The growth the cell's first add makes on every device: a shard's
    block as placed (5,000,064 slots) copied on ITS device into the
    reserved one; two copies of a shard's rows fit a chip."""
    from sptag_tpu.algo.flat import _block_grown, pad_rows

    last = SingleDeviceSharding(topo.devices[3])
    n0 = pad_rows(LIVE_ROWS)
    n, _ = _live_block(last)
    compiled = _block_grown.lower(
        _s(last, (n0, LIVE_D), jnp.float32), _s(last, (n0,), jnp.float32),
        _s(last, (n0,), jnp.bool_), slots=n).compile()
    assert "all-gather" not in compiled.as_text()
    _assert_per_device(compiled, 6 * 2 ** 30)


def test_a_write_to_one_shard_leaves_the_others_buffers_on_the_chip():
    """CHIP-ONLY, four chips (skipped wherever the first device is no
    TPU; `chiprun --chips 4 -- python -m pytest tests/test_chip_compile.py
    -k on_the_chip --noconftest`): an add's rung is written on the owning
    device alone — the other three devices' rows, norms and mask are the
    same buffers before and after — and the next search finds its rows."""
    if jax.devices()[0].platform != "tpu" or len(jax.devices()) < 4:
        pytest.skip("needs four chips")
    from sptag_tpu.parallel.sharded import ShardedFlatIndex, make_mesh

    rng = np.random.default_rng(43)
    data = rng.standard_normal((400_000, LIVE_D)).astype(np.float32)
    index = ShardedFlatIndex(data, DistCalcMethod.L2, 1,
                             mesh=make_mesh(jax.devices()[:4]))
    first = data[:128] + np.float32(0.01)
    index.add(first)                                    # the growth
    for turn in range(4):
        before = [[a.unsafe_buffer_pointer() for a in part]
                  for part in index._parts]
        owner = index._next_shard
        block = data[1000 * turn:1000 * turn + 128] + np.float32(0.02)
        index.add(block)
        after = [[a.unsafe_buffer_pointer() for a in part]
                 for part in index._parts]
        assert all(after[s] == before[s] for s in range(4) if s != owner)
        ids = index.search(block[:8], 1)[1][:, 0]
        assert list(ids) == list(range(index.n - 128, index.n - 120))


def test_sharded_flat_kernel_compiles_int8_cosine_fused_on_four(mesh4):
    """An int8 cosine mesh folder (no cell runs one yet): every shard
    takes the fused scan (PR 37), 2.2M x 384 one-byte rows a chip, and
    holds no (Q, rows) scores."""
    from sptag_tpu.algo.flat import fused_minima, pad_rows
    from sptag_tpu.parallel.sharded import (SHARD_AXIS, ShardedFlatIndex,
                                            _sharded_search_kernel)

    D, Q, stride = 384, 128, ShardedFlatIndex.rows_per_shard(8_841_823, 4)
    n_slot = pad_rows(stride)
    assert fused_minima(np.dtype(np.int8), Q, n_slot, D, K, COS, "tpu")
    rows = NamedSharding(mesh4, P(SHARD_AXIS, None))
    vec = NamedSharding(mesh4, P(SHARD_AXIS))
    rep = NamedSharding(mesh4, P(None, None))
    compiled = _sharded_search_kernel.lower(
        _s(rows, (4 * n_slot, D), jnp.int8),
        _s(vec, (4 * n_slot,), jnp.float32),
        _s(vec, (4 * n_slot,), jnp.bool_), _s(rep, (Q, D), jnp.int8),
        k_local=K, k_final=K, metric=COS, base=127, mesh=mesh4,
        row_stride=stride, fused=True).compile()
    text = compiled.as_text()
    assert _has_mosaic_kernel(compiled) and "all-gather" in text
    for scope in ("flat.distance", "flat.topk", "mesh.merge"):
        assert scope in text, scope
    assert compiled.memory_analysis().temp_size_in_bytes < 0.3 * 2 ** 30
    _assert_per_device(compiled, n_slot * D + 2 ** 30)


def test_sharded_beam_kernel_compiles_on_four(mesh4):
    from sptag_tpu.algo.engine import _num_words
    from sptag_tpu.parallel.sharded import SHARD_AXIS, _sharded_beam_kernel

    n_dev, n_local, D, Q, m, pv = 4, N_BKT // 4, 128, 256, 32, 2048
    L, B, limit = _beam_plan()
    rows = NamedSharding(mesh4, P(SHARD_AXIS, None))
    vec = NamedSharding(mesh4, P(SHARD_AXIS))
    rows3 = NamedSharding(mesh4, P(SHARD_AXIS, None, None))
    rep = NamedSharding(mesh4, P(None, None))
    compiled = _sharded_beam_kernel.lower(
        _s(rows, (n_dev * n_local, D), jnp.float32),
        _s(vec, (n_dev * n_local,), jnp.float32),
        _s(rows, (n_dev * n_local, m), jnp.int32),
        _s(vec, (n_dev * n_local,), jnp.bool_),
        _s(rows, (n_dev, pv), jnp.int32),
        _s(rows3, (n_dev, pv, D), jnp.float32),
        _s(rows, (n_dev, _num_words(n_local)), jnp.int32),
        _s(rep, (Q, D), jnp.float32),
        k_local=K, k_final=K, L=L, B=B, T=-(-2048 // B), metric=L2, base=1,
        nbp_limit=limit, mesh=mesh4).compile()
    assert "all-gather" in compiled.as_text()
    _assert_per_device(compiled, 2 ** 30)


def test_sharded_dense_kernel_compiles_on_four(mesh4):
    from sptag_tpu.parallel.sharded import SHARD_AXIS, _sharded_dense_kernel

    n_dev, n_local, D, Q, C = 4, N_BKT // 4, 128, 256, C_BLK // 4
    s4 = NamedSharding(mesh4, P(SHARD_AXIS, None, None, None))
    s3 = NamedSharding(mesh4, P(SHARD_AXIS, None, None))
    s2 = NamedSharding(mesh4, P(SHARD_AXIS, None))
    vec = NamedSharding(mesh4, P(SHARD_AXIS))
    rep = NamedSharding(mesh4, P(None, None))
    compiled = _sharded_dense_kernel.lower(
        _s(s4, (n_dev, C, P_BLK, D), jnp.float32),
        _s(s3, (n_dev, C, P_BLK), jnp.int32),
        _s(s3, (n_dev, C, P_BLK), jnp.float32),
        _s(s3, (n_dev, C, D), jnp.float32),
        _s(s2, (n_dev, C), jnp.float32),
        _s(s2, (n_dev, C), jnp.bool_),
        _s(vec, (n_dev * n_local,), jnp.bool_),
        _s(rep, (Q, D), jnp.float32),
        k_local=K, k_final=K, nprobe=NPROBE, metric=L2, base=1,
        dedup=False, mesh=mesh4).compile()
    assert "all-gather" in compiled.as_text()
    _assert_per_device(compiled, 2 ** 30)
