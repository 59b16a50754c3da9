"""Pallas TPU kernels: blocked pairwise squared-L2 **threshold join**.

This is the paper's hot spot (§V pairwise inner joins + Algorithm 4's distance
predicate). One fused pass computes, for tiles A:(bm,d), B:(bn,d) resident in
VMEM:

    sq[i,j]  = ||a_i||^2 + ||b_j||^2 - 2 a_i.b_j        (MXU matmul)
    count    = #{(i,j) : sq[i,j] <= r^2}                (the inner-join edge
                                                         weight M[vi,vj])

Two entry points share the kernel body:

  * :func:`pairwise_l2_join` — one (M, d) x (N, d) join. The threshold ``r``
    is a *runtime* scalar delivered through a scalar-prefetch SMEM ref, so
    per-query ``r_k`` thresholds never force a recompilation (they used to be
    baked into the kernel as a static float).
  * :func:`pairwise_l2_join_batched` — the serving hot path: a whole batch of
    padded subsets (S, P, d) self-joined in **one** dispatch, with per-subset
    lengths and per-subset radii prefetched into SMEM. This is what
    ``core.backend.PallasBackend`` calls once per scale for all covering-bucket
    subsets of a query batch.

A third entry point, :func:`pairwise_l2_join_batched_masked`, emits the join
*result* as a packed per-subset adjacency bitmask instead of (or in addition
to) the dense fp32 block: word ``mask[s, i, w]`` holds bits for columns
``32*w .. 32*w+31`` of row ``i`` (LSB-first), bit set iff
``sq[s, i, j] <= r[s]^2`` and both endpoints are valid. The mask is the
enumeration stage's entire join contract, so the D2H readback shrinks 32x
(uint32 words vs fp32 cells) and the dense ``sq`` block becomes optional.
In-kernel packing rides the MXU: a static (2W, bn) weight matrix of powers of
two times the 0/1 bit tile accumulates each 16-bit half-word exactly in fp32
(max 0xFFFF < 2^24), and the halves are fused into 32-bit words with one
shift-or.

Grid is (ceil(M/bm), ceil(N/bn)) (with a leading subset axis for the batched
variant); the full d extent is kept per block (for the embedding widths we
index, bm*d*4B + bn*d*4B + bm*bn*4B stays well inside the ~16 MiB v5e VMEM
budget: 128x8192 fp32 tiles are 4 MiB each). Tail tiles are masked with an
in-kernel iota validity test — no host-side padding games.

Mosaic layout rules (checked by AOT compiles for a described v5e in
``tests/test_tpu_compile.py``): the last two dims of every block are
(8, 128)-divisible or span the whole array dim, and nothing stores a scalar
to VMEM. So each tile writes its join counts as a lane-dense (1, bn) row of
per-column counts (the wrapper sums the rows back into the per-tile
(gm, gn) grid), the mask words leave the kernel word-major as (W, bm) tiles
of an (S, gn, W, P) array (the wrapper transposes them into the
(S, P, ceil(P/32)) contract), and eligibility rides as (1, P) rows that
enter the counts through a (1, bm) x (bm, bn) matvec. Words are built in
int32 (Mosaic has no uint32<->f32 casts) and bitcast to uint32 outside the
kernel. The fp32 Gram matmul asks for ``Precision.HIGHEST``: the slack
contract of ``core.backend`` assumes fp32 rounding, not a single bf16 pass.

MXU notes: bm=bn=128 aligns the matmul to the 128x128 systolic array;
``preferred_element_type=float32`` keeps the accumulator fp32 even for bf16
inputs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_FMAX = float(jnp.finfo(jnp.float32).max)


def _join_block(a, b):
    """sq-L2 block from fp32 tiles: ||a||^2 + ||b||^2 - 2ab on the MXU."""
    a2 = jnp.sum(a * a, axis=1, keepdims=True)    # (bm, 1)
    b2 = jnp.sum(b * b, axis=1, keepdims=True)    # (bn, 1)
    ab = jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                             precision=jax.lax.Precision.HIGHEST,
                             preferred_element_type=jnp.float32)  # (bm, bn)
    return jnp.maximum(a2 + b2.T - 2.0 * ab, 0.0)


def _col_counts(joined):
    """(bm, bn) bool -> (1, bn) int32 per-column join counts (exact: the
    fp32 column sums stay <= bm < 2^24)."""
    return jnp.sum(joined.astype(jnp.float32), axis=0,
                   keepdims=True).astype(jnp.int32)


def _tile_counts(rows, gm: int, gn: int, bn: int):
    """(..., gm, 1, gn*bn) per-column count rows -> (..., gm, gn) per-tile
    join sizes."""
    lead = rows.shape[:-3]
    return rows.reshape(*lead, gm, gn, bn).sum(axis=-1, dtype=jnp.int32)


def _kernel(r2_ref, a_ref, b_ref, sq_ref, cnt_ref, *, m_actual: int,
            n_actual: int, bm: int, bn: int):
    i = pl.program_id(0)
    j = pl.program_id(1)
    sq = _join_block(a_ref[...].astype(jnp.float32),
                     b_ref[...].astype(jnp.float32))
    rows = (i * bm + jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 0)) < m_actual
    cols = (j * bn + jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 1)) < n_actual
    valid = rows & cols
    sq = jnp.where(valid, sq, jnp.float32(_FMAX))
    sq_ref[...] = sq
    cnt_ref[0] = _col_counts((sq <= r2_ref[0]) & valid)


def pairwise_l2_join(a: jax.Array, b: jax.Array,
                     r: float | jax.Array = jnp.inf, *, bm: int = 128,
                     bn: int = 128, interpret: bool = False
                     ) -> tuple[jax.Array, jax.Array]:
    """Returns (sq, counts): sq (M,N) squared distances (invalid tail = fmax),
    counts (gm, gn) int32 per-tile join sizes. ``sum(counts)`` is the paper's
    inner-join edge weight for the group pair. ``r`` may be a traced scalar —
    it rides in SMEM, so sweeping r_k costs zero recompiles."""
    m, d = a.shape
    n, _ = b.shape
    gm = pl.cdiv(m, bm)
    gn = pl.cdiv(n, bn)
    a_p = jnp.pad(a, ((0, gm * bm - m), (0, 0)))
    b_p = jnp.pad(b, ((0, gn * bn - n), (0, 0)))
    r2 = jnp.square(jnp.asarray(r, jnp.float32)).reshape((1,))

    kern = functools.partial(_kernel, m_actual=m, n_actual=n, bm=bm, bn=bn)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(gm, gn),
        in_specs=[
            pl.BlockSpec((bm, d), lambda i, j, r2_ref: (i, 0)),
            pl.BlockSpec((bn, d), lambda i, j, r2_ref: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, r2_ref: (i, j)),
            pl.BlockSpec((1, 1, bn), lambda i, j, r2_ref: (i, 0, j)),
        ],
    )
    sq, cnt = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((gm * bm, gn * bn), jnp.float32),
            jax.ShapeDtypeStruct((gm, 1, gn * bn), jnp.int32),
        ],
        interpret=interpret,
    )(r2, a_p, b_p)
    return sq[:m, :n], _tile_counts(cnt, gm, gn, bn)


def _batched_kernel(len_ref, r2_ref, a_ref, b_ref, sq_ref, cnt_ref, *,
                    bm: int, bn: int):
    s = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)
    sq = _join_block(a_ref[0].astype(jnp.float32),
                     b_ref[0].astype(jnp.float32))
    n_valid = len_ref[s]
    rows = (i * bm + jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 0)) < n_valid
    cols = (j * bn + jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 1)) < n_valid
    valid = rows & cols
    sq = jnp.where(valid, sq, jnp.float32(_FMAX))
    sq_ref[0] = sq
    cnt_ref[0, 0] = _col_counts((sq <= r2_ref[s]) & valid)


def _count_spec(bn: int):
    """(1, bn) count row of tile (s, i, j) in an (S, gm, 1, gn*bn) array."""
    return pl.BlockSpec((1, 1, 1, bn), lambda s, i, j, *_: (s, i, 0, j))


def pairwise_l2_join_batched(x: jax.Array, lengths: jax.Array,
                             r: jax.Array | float = jnp.inf, *, bm: int = 128,
                             bn: int = 128, interpret: bool = False
                             ) -> tuple[jax.Array, jax.Array]:
    """Self-join every padded subset of a batch in one fused dispatch.

    x        : (S, P, d) — S subsets, each padded to P points.
    lengths  : (S,) int32 — valid point count per subset; rows/cols past the
               length are masked (sq = fmax, excluded from counts).
    r        : per-subset join radii, (S,) or scalar, runtime-traced (SMEM).

    Returns (sq, counts): sq (S, P, P) squared distances, counts (S, gm, gn)
    per-tile join sizes (``counts.sum(axis=(1, 2))`` is the per-subset inner
    join cardinality).
    """
    n_subsets, p, d = x.shape
    gm = pl.cdiv(p, bm)
    gn = pl.cdiv(p, bn)
    p_pad = max(gm * bm, gn * bn)
    x_p = jnp.pad(x, ((0, 0), (0, p_pad - p), (0, 0)))
    lengths = jnp.asarray(lengths, jnp.int32).reshape((n_subsets,))
    r2 = jnp.square(jnp.broadcast_to(jnp.asarray(r, jnp.float32), (n_subsets,)))

    kern = functools.partial(_batched_kernel, bm=bm, bn=bn)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_subsets, gm, gn),
        in_specs=[
            pl.BlockSpec((1, bm, d), lambda s, i, j, *_: (s, i, 0)),
            pl.BlockSpec((1, bn, d), lambda s, i, j, *_: (s, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bm, bn), lambda s, i, j, *_: (s, i, j)),
            _count_spec(bn),
        ],
    )
    sq, cnt = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((n_subsets, gm * bm, gn * bn), jnp.float32),
            jax.ShapeDtypeStruct((n_subsets, gm, 1, gn * bn), jnp.int32),
        ],
        interpret=interpret,
    )(lengths, r2, x_p, x_p)
    return sq[:, :p, :p], _tile_counts(cnt, gm, gn, bn)


def _prune_block(a, b):
    """sq-L2 block from bf16 tiles: norms in fp32, Gram on the bf16 MXU.

    The matmul runs at bf16 input precision (the point of the prune tier —
    half the MXU input bandwidth), accumulated in fp32; the self-norm terms
    are computed from the *same* bf16 values upcast to fp32, so the only
    error sources are the bf16 rounding of the coordinates (bounded by the
    caller's slack radius) and the fp32 accumulation (covered by the fp32
    slack term)."""
    af = a.astype(jnp.float32)
    bf = b.astype(jnp.float32)
    a2 = jnp.sum(af * af, axis=1, keepdims=True)   # (bm, 1)
    b2 = jnp.sum(bf * bf, axis=1, keepdims=True)   # (bn, 1)
    ab = jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (bm, bn)
    return jnp.maximum(a2 + b2.T - 2.0 * ab, 0.0)


def _batched_prune_kernel(len_ref, r2_ref, a_ref, b_ref, ea_ref, eb_ref,
                          cnt_ref, *, bm: int, bn: int):
    s = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)
    sq = _prune_block(a_ref[0], b_ref[0])
    n_valid = len_ref[s]
    rows = (i * bm + jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 0)) < n_valid
    cols = (j * bn + jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 1)) < n_valid
    joined = ((sq <= r2_ref[s]) & rows & cols).astype(jnp.float32)
    # Row eligibility enters as a (1, bm) x (bm, bn) matvec (0/1 operands,
    # exact), column eligibility as a lane-wise product: both stay rows, so
    # no (bm, 1) column has to be laid out.
    per_col = jax.lax.dot_general(ea_ref[0], joined, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    cnt_ref[0, 0] = (per_col * eb_ref[0]).astype(jnp.int32)


def pairwise_l2_join_batched_prune(x: jax.Array, lengths: jax.Array,
                                   r: jax.Array | float, elig: jax.Array, *,
                                   bm: int = 128, bn: int = 128,
                                   interpret: bool = False) -> jax.Array:
    """Coarse bf16 threshold-join: per-subset join *counts* only, no mask.

    The cascade's pruning tier. ``x`` is (S, P, d) **bfloat16** (cast outside
    the call so the H2D transfer itself is halved); ``r`` carries the
    error-widened coarse radii (``PallasBackend`` computes
    ``(r + slack32 + slack16) * (1 + eps)``), so the coarse count is a
    guaranteed upper bound of the fp32 join count. A subset whose coarse
    count stays at or below its live diagonal cannot produce an off-diagonal
    fp32 pair — the fp32 tier (and its 32x-heavier mask readback) is skipped
    for it entirely.

    ``elig`` is a dense (S, P) fp32 0/1 eligibility row (all-ones when no
    filter is active): ineligible points drop out of the counts so the
    diagonal bound matches the fp32 tier's eligible-pair counts.

    Returns counts (S, gm, gn) int32 (``sum(axis=(1, 2))`` per subset).
    """
    n_subsets, p, d = x.shape
    gm = pl.cdiv(p, bm)
    gn = pl.cdiv(p, bn)
    p_pad = max(gm * bm, gn * bn)
    x_p = jnp.pad(x.astype(jnp.bfloat16), ((0, 0), (0, p_pad - p), (0, 0)))
    e_p = jnp.pad(jnp.asarray(elig, jnp.float32),
                  ((0, 0), (0, p_pad - p)))[:, None, :]       # (S, 1, P)
    lengths = jnp.asarray(lengths, jnp.int32).reshape((n_subsets,))
    r2 = jnp.square(jnp.broadcast_to(jnp.asarray(r, jnp.float32), (n_subsets,)))

    kern = functools.partial(_batched_prune_kernel, bm=bm, bn=bn)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_subsets, gm, gn),
        in_specs=[
            pl.BlockSpec((1, bm, d), lambda s, i, j, *_: (s, i, 0)),
            pl.BlockSpec((1, bn, d), lambda s, i, j, *_: (s, j, 0)),
            pl.BlockSpec((1, 1, bm), lambda s, i, j, *_: (s, 0, i)),
            pl.BlockSpec((1, 1, bn), lambda s, i, j, *_: (s, 0, j)),
        ],
        out_specs=[_count_spec(bn)],
    )
    (cnt,) = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((n_subsets, gm, 1, gn * bn),
                                        jnp.int32)],
        interpret=interpret,
    )(lengths, r2, x_p, x_p, e_p, e_p)
    return _tile_counts(cnt, gm, gn, bn)


def _pack_bits_mxu(bits: jax.Array, bn: int) -> jax.Array:
    """(bm, bn) 0/1 fp32 -> (bn//32, bm) int32 words, word-major: entry
    ``[w, i]`` holds columns 32w..32w+31 of row i, LSB-first.

    One MXU matmul of a static (2W, bn) powers-of-two weight against the bit
    tile accumulates the low/high 16-bit halves of every word exactly in fp32
    (<= 0xFFFF; the operands are exact in bf16, so no precision setting can
    round them), then a shift-or fuses them. The word-major result is a
    lane-dense (W, bm) tile, and no >=3D reshape enters the kernel.
    """
    w = bn // 32
    hh = jax.lax.broadcasted_iota(jnp.int32, (2 * w, bn), 0)     # half slot
    cc = jax.lax.broadcasted_iota(jnp.int32, (2 * w, bn), 1)     # column id
    target = cc // 32 + w * ((cc // 16) % 2)   # lo halves 0..W-1, hi W..2W-1
    # powers of two via integer shift: jnp.exp2 is a polynomial approximation
    # in fp32 (2^13 -> 8192.0039) and would corrupt the packed words
    pow2 = (jnp.int32(1) << (cc % 16)).astype(jnp.float32)
    weight = jnp.where(hh == target, pow2, 0.0)
    halves = jax.lax.dot_general(weight, bits, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    return halves[:w].astype(jnp.int32) | (halves[w:].astype(jnp.int32) << 16)


def _batched_masked_kernel(len_ref, r2_ref, a_ref, b_ref, *out_refs,
                           bm: int, bn: int, with_sq: bool):
    s = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)
    sq = _join_block(a_ref[0].astype(jnp.float32),
                     b_ref[0].astype(jnp.float32))
    n_valid = len_ref[s]
    rows = (i * bm + jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 0)) < n_valid
    cols = (j * bn + jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 1)) < n_valid
    valid = rows & cols
    sq = jnp.where(valid, sq, jnp.float32(_FMAX))
    joined = (sq <= r2_ref[s]) & valid
    if with_sq:
        sq_ref, mask_ref, cnt_ref = out_refs
        sq_ref[0] = sq
    else:
        mask_ref, cnt_ref = out_refs
    mask_ref[0, 0] = _pack_bits_mxu(joined.astype(jnp.float32), bn)
    cnt_ref[0, 0] = _col_counts(joined)


def pairwise_l2_join_batched_masked(x: jax.Array, lengths: jax.Array,
                                    r: jax.Array | float = jnp.inf, *,
                                    bm: int = 128, bn: int = 128,
                                    with_sq: bool = False,
                                    interpret: bool = False):
    """Batched self-join emitting the packed adjacency bitmask.

    Same contract as :func:`pairwise_l2_join_batched` plus a packed join mask:

    Returns ``(mask, counts[, sq])``:
      mask   : (S, P, ceil(P/32)) uint32 — bit ``j % 32`` of ``mask[s, i, j//32]``
               is 1 iff ``sq[s, i, j] <= r[s]^2`` and i, j < lengths[s].
      counts : (S, gm, gn) int32 per-tile join sizes (``sum(axis=(1, 2))`` is
               the per-subset inner-join cardinality at r).
      sq     : dense (S, P, P) fp32 block, only when ``with_sq`` — the mask
               replaces it as the enumeration contract, making the 32x-larger
               dense readback optional.
    """
    if bn % 32:
        raise ValueError(f"bn must be a multiple of 32 for mask packing: {bn}")
    n_subsets, p, d = x.shape
    gm = pl.cdiv(p, bm)
    gn = pl.cdiv(p, bn)
    p_pad = max(gm * bm, gn * bn)
    x_p = jnp.pad(x, ((0, 0), (0, p_pad - p), (0, 0)))
    lengths = jnp.asarray(lengths, jnp.int32).reshape((n_subsets,))
    r2 = jnp.square(jnp.broadcast_to(jnp.asarray(r, jnp.float32), (n_subsets,)))
    wn = bn // 32

    kern = functools.partial(_batched_masked_kernel, bm=bm, bn=bn,
                             with_sq=with_sq)
    out_specs = [
        pl.BlockSpec((1, 1, wn, bm), lambda s, i, j, *_: (s, j, 0, i)),
        _count_spec(bn),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((n_subsets, gn, wn, p_pad), jnp.int32),
        jax.ShapeDtypeStruct((n_subsets, gm, 1, gn * bn), jnp.int32),
    ]
    if with_sq:
        out_specs.insert(0, pl.BlockSpec((1, bm, bn),
                                         lambda s, i, j, *_: (s, i, j)))
        out_shape.insert(0, jax.ShapeDtypeStruct(
            (n_subsets, gm * bm, gn * bn), jnp.float32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_subsets, gm, gn),
        in_specs=[
            pl.BlockSpec((1, bm, d), lambda s, i, j, *_: (s, i, 0)),
            pl.BlockSpec((1, bn, d), lambda s, i, j, *_: (s, j, 0)),
        ],
        out_specs=out_specs,
    )
    out = pl.pallas_call(kern, grid_spec=grid_spec, out_shape=out_shape,
                         interpret=interpret)(lengths, r2, x_p, x_p)
    if with_sq:
        sq, words, cnt = out
    else:
        words, cnt = out
    # (S, gn, W, P) word-major tiles -> (S, P, gn*W) row-major words
    mask = jax.lax.bitcast_convert_type(words, jnp.uint32) \
        .transpose(0, 3, 1, 2).reshape(n_subsets, p_pad, gn * wn)
    n_words = (p + 31) // 32
    mask = mask[:, :p, :n_words]
    cnt = _tile_counts(cnt, gm, gn, bn)
    if with_sq:
        return mask, cnt, sq[:, :p, :p]
    return mask, cnt
