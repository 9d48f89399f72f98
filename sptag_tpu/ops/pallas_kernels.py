"""Pallas TPU kernels for the gather-heavy hot ops.

The dense tree-partition search (algo/dense.py) scores, per query, the
`nprobe` corpus blocks nearest to the query.  In pure XLA that is
``data_perm[topc]`` — a (Q, nprobe, P, D) generic gather that materializes
~1 GB per kilo-query batch in HBM before a batched-matvec contraction reads
it back (measured ~20x off the HBM roofline on v5e).  The reference's
equivalent inner loop is the one-row-at-a-time SIMD distance call
(/root/reference/AnnService/src/Core/BKT/BKTIndex.cpp:145-152).

The Pallas version never materializes the gathered blocks: the grid walks
(query, probe) pairs, the scalar-prefetched `topc` drives the BlockSpec
index_map so each step's (P, D) block is DMA'd HBM->VMEM directly (Pallas
double-buffers consecutive steps automatically), and one (1, D) x (D, P)
MXU contraction per step writes the (1, P) dot-product row straight to the
output.  Total HBM traffic = the blocks actually probed, once.

Only the dot products are computed in-kernel; the metric composition
(``qn + sq - 2 dot`` / ``base^2 - dot``) stays in XLA where it fuses with
the downstream top-k.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from sptag_tpu.core.index import MAX_DIST

_INTERPRET = False        # tests may flip this to run on CPU


def set_interpret(value: bool) -> None:
    """Run kernels in interpreter mode (CPU tests)."""
    global _INTERPRET
    _INTERPRET = value


def interpret() -> bool:
    return _INTERPRET


def platform() -> str:
    """What a kernel's gate is asked about: the first device's platform,
    or "interpret" while the kernels run interpreted (CPU tests)."""
    return "interpret" if _INTERPRET else jax.devices()[0].platform


def supported(data_perm) -> bool:
    """Pallas path gate, decided BEFORE the call from what can be
    observed: TPU (or interpret mode) + f32/int8 data + MXU-friendly
    block shape.  There is no switch that turns the route off after a
    failure: a kernel that passes this gate and then fails to compile or
    run raises to the caller (tests/test_chip_compile.py holds the
    compiles the gate promises)."""
    if data_perm.dtype not in (jnp.float32, jnp.dtype(jnp.int8)):
        return False
    C, P, D = data_perm.shape
    # int8 VMEM tiles are (32, 128); f32 tiles are (8, 128)
    min_sub = 32 if data_perm.dtype == jnp.dtype(jnp.int8) else 8
    if P % min_sub != 0 or D % 128 != 0:
        return False
    return platform() in ("tpu", "interpret")


@functools.partial(jax.jit, static_argnames=("interpret",))
def probe_block_dots(data_perm: jax.Array, queries: jax.Array,
                     topc: jax.Array, interpret: bool = False) -> jax.Array:
    """(C, P, D) blocks, (Q, D) queries, (Q, nprobe) int32 block ids ->
    (Q, nprobe, P) dot products of each query with every row of its probed
    blocks.  Returns float32 for float blocks; int32 (exact) for int8
    blocks — int8 expects int8 queries and contracts on the native
    s8xs8->s32 MXU path, matching ops/distance's integer convention (the
    reference's int cosine is an exact integer dot, DistanceUtils.h:452)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    C, P, D = data_perm.shape
    Q, nprobe = topc.shape
    int_path = data_perm.dtype == jnp.dtype(jnp.int8)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Q, nprobe),
        in_specs=[
            # whole query matrix resident in VMEM, sliced by program_id
            # in-kernel: a (1, D) block would violate the min-tile rule
            # ((8,128) f32 / (32,128) int8)
            pl.BlockSpec((Q, D), lambda q, j, t: (0, 0)),
            pl.BlockSpec((1, P, D), lambda q, j, t: (t[q, j], 0, 0)),
        ],
        # one (1, nprobe, P) output block per query, revisited across the
        # j steps (consecutive in grid order -> stays in VMEM); each step
        # writes its own j row
        out_specs=pl.BlockSpec((1, nprobe, P), lambda q, j, t: (q, 0, 0)),
    )

    def kernel(t_ref, q_ref, blk_ref, out_ref):
        q = pl.program_id(0)
        j = pl.program_id(1)
        qv = q_ref[pl.ds(q, 1), :]                    # (1, D)
        if int_path:
            # native s8 x s8 -> s32 MXU contraction.  The query row was
            # widened to int32 for the dynamic row load (see below);
            # narrowing it back is exact, and the (P, D) block — the
            # operand that carries the bytes — stays int8 end to end
            dot = jax.lax.dot_general(
                qv.astype(jnp.int8), blk_ref[0],
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.int32)
        else:
            # HIGHEST = the f32-accurate multi-pass algorithm, matching
            # ops/distance's default contraction precision (a plain bf16
            # pass showed ~1.5% dot error on d=128)
            dot = jax.lax.dot_general(
                qv, blk_ref[0],
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST)
        out_ref[0, pl.ds(j, 1), :] = dot

    out_dt = jnp.int32 if int_path else jnp.float32
    if int_path:
        # Mosaic loads a dynamic single row only from a 32-bit ref: int8
        # rows pack four to a sublane, and the v5e compiler refuses
        # `q_ref[pl.ds(q, 1)]` on them ("cannot statically prove that
        # index in dimension 0 is a multiple of 8").  So the resident
        # query matrix is int32 — the same VMEM footprint as the f32 path
        queries = queries.astype(jnp.int32)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((Q, nprobe, P), out_dt),
        grid_spec=grid_spec,
        interpret=interpret,
    )(topc, queries, data_perm)


@functools.partial(jax.jit, static_argnames=("interpret",))
def group_block_dots(data_perm: jax.Array, queries: jax.Array,
                     union_c: jax.Array, interpret: bool = False
                     ) -> jax.Array:
    """(C, P, D) blocks, (Q, D) queries sorted into Q/G groups of G, and
    (Q/G, U) int32 per-GROUP block ids -> (Q/G, U, G, P) dot products of
    every query in a group with every row of the group's union blocks.

    The probe-major `probe_block_dots` issues one grid step per
    (query, probe) — Q*nprobe steps whose (1, D) x (D, P) matvecs leave the
    MXU rows idle and whose per-step fixed cost dominates at small P.  Here
    queries are pre-sorted by nearest centroid (algo/dense.py) so a GROUP of
    G neighbors shares most of its probed blocks; one step scores the whole
    group against one union block as a real (G, D) x (D, P) contraction:
    (Q/G)*U steps, G-fold fewer DMAs for the shared blocks, and G MXU rows
    busy instead of one.  `union_c` entries must be valid block ids
    (callers clamp padding to 0 and mask downstream)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    C, P, D = data_perm.shape
    Q, _ = queries.shape
    NG, U = union_c.shape
    G = Q // NG
    int_path = data_perm.dtype == jnp.dtype(jnp.int8)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(NG, U),
        in_specs=[
            # one (G, D) query block per group, constant across the U steps
            pl.BlockSpec((G, D), lambda g, j, t: (g, 0)),
            pl.BlockSpec((1, P, D), lambda g, j, t: (t[g, j], 0, 0)),
        ],
        # 3D output with a flattened (group, union-slot) leading axis —
        # the same block shape family as the proven probe_block_dots
        # kernel ((1, minor, minor)); each grid step owns one block
        out_specs=pl.BlockSpec((1, G, P), lambda g, j, t: (g * U + j, 0, 0)),
    )

    def kernel(t_ref, q_ref, blk_ref, out_ref):
        if int_path:
            dot = jax.lax.dot_general(
                q_ref[...], blk_ref[0],
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.int32)
        else:
            dot = jax.lax.dot_general(
                q_ref[...], blk_ref[0],
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST)
        out_ref[0] = dot

    out_dt = jnp.int32 if int_path else jnp.float32
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((NG * U, G, P), out_dt),
        grid_spec=grid_spec,
        interpret=interpret,
    )(union_c, queries, data_perm)
    return out.reshape(NG, U, G, P)


SCAN_GROUP = 128      # rows a group minimum covers: a lane tile
# rows one grid step brings into VMEM, in bytes (twice: Pallas
# double-buffers the block), and rows x queries one MXU contraction of a
# step scores before their maxima are taken, in elements
_SCAN_TILE_BYTES = 4 << 20
_SCAN_CHUNK_SCORES = 1 << 17
_SCAN_VMEM_BYTES = 48 << 20


def _scan_tiling(d: int, q: int):
    """(groups a grid step, groups a contraction) of `scan_group_minima`
    at rows `d` bytes wide and `q` queries: powers of two, a step's rows
    within `_SCAN_TILE_BYTES`, a contraction's scores within
    `_SCAN_CHUNK_SCORES` (8,192 rows a step in chunks of 1,024 at 384
    bytes and 128 queries)."""
    chunk = max(1, _SCAN_CHUNK_SCORES // (SCAN_GROUP * q))
    tile = max(8, _SCAN_TILE_BYTES // (SCAN_GROUP * d))
    tile = 1 << (tile.bit_length() - 1)
    chunk = min(1 << (chunk.bit_length() - 1), tile)
    return tile, chunk


def column_major(dtype, d: int) -> bool:
    """Whether a resident (N, d) block of `dtype` lies column-major on a
    TPU: float32 rows narrower than a lane tile do (the compiler's
    compact layout, `f32[N,100]{0,1:T(8,128)}`: 400 bytes a row in HBM
    and no lane padding), so what reads them row by row - a `(rows, d)`
    kernel block, a gather of `(128, d)` slabs - would first copy the
    whole block into the other layout, every call.  Seen in the programs
    compiled for a described v5e (tests/test_chip_compile.py holds the
    temporaries that a copy would show in)."""
    return jnp.dtype(dtype) == jnp.float32 and d < SCAN_GROUP


# slabs one grid step of `group_rows` copies: as many block DMAs in flight
_SLABS_A_STEP = 8


def group_rows(data: jax.Array, chosen: jax.Array,
               interpret: bool = False) -> jax.Array:
    """(N, D) rows and (Q, c) int32 group ids -> (Q, c, SCAN_GROUP, D):
    the rows of every chosen group, each group one contiguous slab of the
    resident block as it lies.  Row-major blocks: XLA's gather.  A
    `column_major` block is read through its transpose (a bitcast) as
    `(D, SCAN_GROUP)` slabs at whole lane tiles by a Pallas copy whose
    BlockSpecs follow the prefetched ids, `_SLABS_A_STEP` slabs a step
    (XLA's gather of such slabs is a loop of one copy at a time: 3.4 us
    a slab on the chip, 7 ms for a 128-query batch at k = 10)."""
    n, d = data.shape
    if not column_major(data.dtype, d):
        return jnp.take(data.reshape(n // SCAN_GROUP, SCAN_GROUP, d), chosen,
                        axis=0)
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    per = _SLABS_A_STEP
    ids = chosen.reshape(-1)
    steps = -(-ids.shape[0] // per)
    ids = jnp.pad(ids, (0, steps * per - ids.shape[0]))

    def kernel(ids_ref, *refs):
        for j in range(per):
            refs[per][j] = refs[j][...]

    slabs = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((steps * per, d, SCAN_GROUP),
                                       data.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(steps,),
            in_specs=[pl.BlockSpec((d, SCAN_GROUP),
                                   functools.partial(
                                       lambda i, g, j: (0, g[i * per + j]),
                                       j=j))
                      for j in range(per)],
            out_specs=pl.BlockSpec((per, d, SCAN_GROUP),
                                   lambda i, g: (i, 0, 0))),
        interpret=interpret,
    )(ids, *[data.T] * per)
    return slabs[:chosen.size].reshape(*chosen.shape, d,
                                       SCAN_GROUP).swapaxes(2, 3)


@functools.partial(jax.jit, static_argnames=("base", "interpret"))
def scan_group_minima(data: jax.Array, invalid: jax.Array,
                      queries: jax.Array, base: int,
                      interpret: bool = False, sqnorm=None) -> jax.Array:
    """(N, D) rows, (N,) bool `invalid`, (Q, D) queries of the rows' type
    -> (N/128, Q) float32: for every group of 128 consecutive rows and
    every query the smallest distance over the group's valid rows,
    `MAX_DIST` where none is valid, without the (Q, N) scores ever being
    written: the FLAT scan's HBM traffic is the rows, once.

    The grid walks the rows in tiles (Pallas double-buffers the DMA); the
    (D, Q) queries stay in VMEM.  A step contracts 1,024-row chunks on
    the MXU with the rows on the sublanes and the queries on the lanes,
    so a group's minimum is an elementwise one over 16 vregs and one
    sublane reduce.  The third operand is (groups, 128), a group's rows
    on the lanes.  The last tile may pass the rows' end: what it reads
    there lands in groups past the output's end, which are not written.

    One-byte rows (`sqnorm` absent), the integer cosine `base^2 - dot`:
    what `where(invalid, MAX_DIST, pairwise_cosine(queries, data, base))`
    holds as group minima, BIT FOR BIT.  The contraction is s8 x s8 ->
    s32 and the minimum is taken as the MAXIMUM of the int32 dots:
    `base^2 - float32(dot)` falls as the dot rises (the conversion and
    the subtraction round monotonically where they round at all), so the
    largest dot's distance is the smallest distance, and one conversion a
    group replaces one a row.  Invalid rows drop out under a cap a row
    (int32's minimum, which no dot of one-byte operands reaches; int32's
    maximum on a valid row): one `minimum` a score.

    Float32 rows (`sqnorm` (N,), the rows' cached squared norms), squared
    L2: the group minima of `|q|^2 + |x|^2 - 2 q.x`, clamped at 0, within
    `l2_minima_eps` of what `pairwise_l2` and the gathered re-score hold
    for the same row, NOT bit for bit (another schedule of the same
    float32 contraction): a FILTER, whose caller proves its selection
    (`algo/flat.py::_select_from_groups`).  The contraction runs at
    `Precision.HIGHEST` against the queries doubled (exact), the third
    operand is `where(invalid, MAX_DIST, sqnorm)` - a live row's norm,
    `MAX_DIST` for a masked one, which no finite dot moves - so a score
    is one subtraction, and `|q|^2` is added once a group (monotone).
    The rows are taken as wide as they are resident (100 and 96 columns
    in two of the cells: a block whose last dimension is the array's)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, d = data.shape
    q = queries.shape[0]
    groups = n // SCAN_GROUP
    tile, chunk = _scan_tiling(d * data.dtype.itemsize, q)
    int_min = jnp.iinfo(jnp.int32).min
    base2 = float(base) * float(base)
    float_path = sqnorm is not None
    by_column = float_path and column_major(data.dtype, d)

    def chunks():
        for c in range(tile // chunk):
            yield (slice(c * chunk, (c + 1) * chunk),
                   slice(c * chunk * SCAN_GROUP, (c + 1) * chunk * SCAN_GROUP))

    def kernel(qt_ref, rows_ref, cap_ref, out_ref):
        for at, rows in chunks():
            dot = jnp.dot(rows_ref[rows], qt_ref[...],
                          preferred_element_type=jnp.int32)
            best = jnp.minimum(dot.reshape(chunk, SCAN_GROUP, q),
                               cap_ref[at][:, :, None]).max(axis=1)
            out_ref[at] = jnp.where(
                best == int_min, jnp.float32(MAX_DIST),
                jnp.float32(base2) - best.astype(jnp.float32))

    def float_kernel(qt_ref, rows_ref, norm_ref, out_ref):
        for at, rows in chunks():
            # (rows, d) x (d, Q); a column-major block is (d, rows) and
            # contracts over its sublanes
            dot2 = jax.lax.dot_general(
                rows_ref[:, rows] if by_column else rows_ref[rows],
                qt_ref[...],
                (((0 if by_column else 1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST)
            out_ref[at] = (norm_ref[at][:, :, None]
                           - dot2.reshape(chunk, SCAN_GROUP, q)).min(axis=1)

    rows_spec = pl.BlockSpec((tile * SCAN_GROUP, d), lambda i: (i, 0))
    if float_path:
        qf = queries.astype(jnp.float32)
        operands = ((2.0 * qf).T, data,
                    jnp.where(invalid, jnp.float32(MAX_DIST), sqnorm).reshape(
                        groups, SCAN_GROUP))
        if by_column:
            # the transpose is a bitcast where a `(rows, d)` block would
            # be a copy of the whole corpus a call
            operands = (operands[0], data.T, operands[2])
            rows_spec = pl.BlockSpec((d, tile * SCAN_GROUP),
                                     lambda i: (0, i))
    else:
        operands = (queries.T, data,
                    jnp.where(invalid, int_min,
                              jnp.iinfo(jnp.int32).max).reshape(
                                  groups, SCAN_GROUP))
    minima = pl.pallas_call(
        float_kernel if float_path else kernel,
        out_shape=jax.ShapeDtypeStruct((groups, q), jnp.float32),
        grid=(pl.cdiv(groups, tile),),
        in_specs=[pl.BlockSpec((d, q), lambda i: (0, 0)), rows_spec,
                  pl.BlockSpec((tile, SCAN_GROUP), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((tile, q), lambda i: (i, 0)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_SCAN_VMEM_BYTES),
        interpret=interpret,
    )(*operands)
    if float_path:
        # MAX_DIST + |q|^2 is MAX_DIST: a masked group stays masked
        return jnp.maximum(minima + jnp.sum(qf * qf, axis=-1)[None, :], 0.0)
    return minima


def l2_minima_eps(d: int, qnorm: jax.Array, sqnorm: jax.Array) -> jax.Array:
    """(Q,) float32: a bound a query on |s~ - s| over every valid row of
    the block, s~ the score `scan_group_minima` takes its float32 minima
    of and s the one XLA computes for the same row at `highest`
    (`distance.pairwise_l2`, `batched_gathered_distance`), both from the
    cached norms.  With u = 2^-24, S = |q|^2 + max |x|^2 over the block
    and P = sum |q_i x_i| <= |q| |x| <= S / 2:

    - either contraction of `d` products accumulated in float32, in any
      order, with the six-pass bf16 split's dropped terms (2u a product),
      is off the true dot by at most (d + 8) u P: the two doubled dots
      differ by at most 2 (d + 8) u S;
    - the two sides add their three terms in different orders, every
      partial sum at most 2 S in magnitude: 3 u S and 4 u S of rounding;
    - each side reduces |q|^2 itself: at most 2 d u |q|^2 <= 2 d u S
      apart;
    - the clamp at 0 is applied on both sides and moves nothing apart.

    (4 d + 23) u S in all; (4 d + 32) u S is what is used, which leaves
    the comparison `m - eps > v` its own rounding."""
    bound = jnp.float32((4 * d + 32) * 2.0 ** -24)
    return bound * (qnorm + jnp.max(sqnorm))
