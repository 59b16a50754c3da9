"""Jit'd public wrappers around the Pallas kernels.

On a TPU backend the wrappers emit Mosaic kernels (``interpret=False``);
``tests/test_tpu_compile.py`` compiles them for a described v5e and
``chip_smoke.py`` runs them on a chip. Off-TPU, ``interpret=None`` resolves
to ``interpret=True``: the kernel body runs in Python per grid step, which
checks the program logic but says nothing about what Mosaic accepts.

The serving hot path (:func:`pairwise_l2_join_batched_masked`) additionally
routes by *implementation*: the Pallas program is a Mosaic artifact, and
interpreting it per grid step is a debugging tool, not a lowering — a
(S, gm, gn) grid costs milliseconds of Python per step. Off-TPU the same
math (the ``kernels.ref`` formulation, bit-exact in fp32 modulo reduction
order) compiles through XLA instead, so ``impl=None`` picks Mosaic on TPU
and the XLA lowering everywhere else. Kernel-validation tests pin
``impl="pallas", interpret=True`` to keep exercising the TPU program logic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import diameter as _diameter
from repro.kernels import pairwise_l2 as _pairwise
from repro.kernels import project_bin as _project


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def pairwise_l2_join(a: jax.Array, b: jax.Array,
                     r: float | jax.Array = float("inf"), *,
                     bm: int = 128, bn: int = 128,
                     interpret: bool | None = None):
    """Blocked pairwise sq-L2 + threshold-join counts. Returns (sq, counts)
    where counts is the per-tile join-size grid (sum() = edge weight). ``r``
    is a traced operand (SMEM scalar): per-query r_k sweeps share one
    compiled program."""
    interpret = _default_interpret() if interpret is None else interpret
    return _pairwise.pairwise_l2_join(a, b, r, bm=bm, bn=bn, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def pairwise_l2_join_batched(x: jax.Array, lengths: jax.Array,
                             r: jax.Array | float = float("inf"), *,
                             bm: int = 128, bn: int = 128,
                             interpret: bool | None = None):
    """One fused self-join over a batch of padded subsets (S, P, d) with
    per-subset valid lengths (S,) and per-subset radii (S,). Returns
    (sq (S, P, P), counts (S, gm, gn))."""
    interpret = _default_interpret() if interpret is None else interpret
    return _pairwise.pairwise_l2_join_batched(x, lengths, r, bm=bm, bn=bn,
                                              interpret=interpret)


def _xla_join_batched_masked(x, lengths, r, with_sq):
    """Optimized XLA lowering of the masked batched self-join.

    Same contract as the Pallas kernel, tuned for memory traffic: one batched
    gemm for the Gram term, one fused elementwise pass for the join bits, and
    a (…, 16)-wide fp32 matvec that packs 16-bit half-words exactly (max
    0xFFFF < 2^24) — no 32x uint32 broadcast like the naive pack. Counts come
    from popcounting the packed words (cells/32 traffic instead of cells).
    """
    n_subsets, p, _ = x.shape
    xf = x.astype(jnp.float32)
    lengths = jnp.asarray(lengths, jnp.int32).reshape((n_subsets,))
    r2 = jnp.square(jnp.broadcast_to(jnp.asarray(r, jnp.float32), (n_subsets,)))
    n2 = jnp.sum(xf * xf, axis=-1)                              # (S, P)
    gram = jax.lax.dot_general(xf, xf, (((2,), (2,)), ((0,), (0,))),
                               preferred_element_type=jnp.float32)
    sq = jnp.maximum(n2[:, :, None] + n2[:, None, :] - 2.0 * gram, 0.0)
    valid_row = jnp.arange(p)[None, :] < lengths[:, None]       # (S, P)
    joined = ((sq <= r2[:, None, None])
              & valid_row[:, :, None] & valid_row[:, None, :])
    w = (p + 31) // 32
    bits = jnp.pad(joined.astype(jnp.float32),
                   ((0, 0), (0, 0), (0, w * 32 - p)))
    halves = bits.reshape(n_subsets, p, w, 2, 16) @ (
        jnp.uint32(1) << jnp.arange(16, dtype=jnp.uint32)).astype(jnp.float32)
    mask = (halves[..., 0].astype(jnp.uint32)
            | (halves[..., 1].astype(jnp.uint32) << 16))        # (S, P, W)
    cnt = jnp.sum(jax.lax.population_count(mask), axis=(1, 2)) \
        .astype(jnp.int32)
    if with_sq:
        fmax = jnp.float32(jnp.finfo(jnp.float32).max)
        sq = jnp.where(valid_row[:, :, None] & valid_row[:, None, :], sq, fmax)
        return mask, cnt, sq
    return mask, cnt


def _elig_dense(elig, p):
    """Packed (S, ceil(P/32)) uint32 eligibility words -> dense (S, P) bool."""
    col = jnp.arange(p)
    return ((elig[:, col // 32] >> (col % 32).astype(jnp.uint32))
            & jnp.uint32(1)) > 0


def _xla_join_batched_counts(x, lengths, r, elig_row, dtype):
    """XLA lowering of the coarse prune tier: per-subset join counts only.

    ``dtype`` picks the coarse arithmetic:

      * ``"bf16"`` — coordinates round to bfloat16, Gram matmul at bf16
        input precision with fp32 accumulation, self-norms computed from the
        same bf16 values in fp32. Identical math to the Pallas prune kernel
        (modulo reduction order, which the caller's slack radius covers).
      * ``"int8"`` — symmetric per-subset quantization
        ``q = round(x * 127 / maxabs)``; Gram and norms are *exact* int32,
        and the threshold is widened on the integer side by the worst-case
        quantization slack ``sqrt(d) * maxabs / 127`` (0.5 rounding error
        per coordinate, two endpoints), so the integer count is again a
        guaranteed upper bound of the fp32 join count.

    ``elig_row`` is a dense (S, P) bool eligibility mask (or None). Returns
    counts (S,) int32.
    """
    n_subsets, p, d = x.shape
    lengths = jnp.asarray(lengths, jnp.int32).reshape((n_subsets,))
    rr = jnp.broadcast_to(jnp.asarray(r, jnp.float32), (n_subsets,))
    valid_row = jnp.arange(p)[None, :] < lengths[:, None]        # (S, P)
    if elig_row is not None:
        valid_row = valid_row & elig_row
    if dtype == "int8":
        xf = x.astype(jnp.float32)
        maxabs = jnp.maximum(jnp.max(jnp.abs(xf), axis=(1, 2)),
                             jnp.float32(1e-30))                 # (S,)
        scale = jnp.float32(127.0) / maxabs
        q = jnp.round(xf * scale[:, None, None]).astype(jnp.int8)
        qi = q.astype(jnp.int32)
        n2 = jnp.sum(qi * qi, axis=-1)                           # (S, P) exact
        gram = jax.lax.dot_general(q, q, (((2,), (2,)), ((0,), (0,))),
                                   preferred_element_type=jnp.int32)
        sq = n2[:, :, None] + n2[:, None, :] - 2 * gram          # exact int32
        # ||x_i - x_j|| >= (||q_i - q_j|| - sqrt(d)) / scale: include iff
        # ||q||^2 <= (r*scale + sqrt(d))^2, +1 absorbs the fp32 threshold
        # rounding (the quadratic fits int32: d * 254^2).
        rq = rr * scale + jnp.float32(d) ** 0.5
        thr = (jnp.ceil(rq * rq) + 1.0).astype(jnp.int32)
        joined = sq <= thr[:, None, None]
    elif dtype == "bf16":
        xb = x.astype(jnp.bfloat16)
        xf = xb.astype(jnp.float32)
        r2 = jnp.square(rr)
        n2 = jnp.sum(xf * xf, axis=-1)                           # (S, P)
        gram = jax.lax.dot_general(xb, xb, (((2,), (2,)), ((0,), (0,))),
                                   preferred_element_type=jnp.float32)
        sq = jnp.maximum(n2[:, :, None] + n2[:, None, :] - 2.0 * gram, 0.0)
        joined = sq <= r2[:, None, None]
    else:
        raise ValueError(f"unknown prune dtype: {dtype!r}")
    joined = joined & valid_row[:, :, None] & valid_row[:, None, :]
    return jnp.sum(joined, axis=(1, 2), dtype=jnp.int32)


def join_batched_counts_local(x, lengths, r, elig=None, *, dtype: str = "bf16",
                              bm: int = 128, bn: int = 128,
                              impl: str | None = None,
                              interpret: bool | None = None):
    """Un-jit'd coarse prune-tier counts, safe to call under an outer trace
    (``core.device_plane`` shard_maps it). ``elig`` uses the packed uint32
    word layout shared with the masked join; the Pallas lowering consumes it
    as a dense fp32 row (unpacked at trace time). ``impl="pallas"`` requires
    ``dtype="bf16"`` — the int8 path is XLA-only (int8 Gram through Mosaic is
    a ROADMAP item). Returns counts (S,) int32."""
    if impl is None:
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl not in ("pallas", "xla"):
        raise ValueError(f"unknown impl: {impl!r}")
    if impl == "pallas" and dtype != "bf16":
        impl = "xla"
    interpret = _default_interpret() if interpret is None else interpret
    p = x.shape[1]
    elig_row = None if elig is None \
        else _elig_dense(jnp.asarray(elig, jnp.uint32), p)
    if impl == "xla":
        return _xla_join_batched_counts(x, lengths, r, elig_row, dtype)
    ones = jnp.ones(x.shape[:2], jnp.float32) if elig_row is None \
        else elig_row.astype(jnp.float32)
    cnt = _pairwise.pairwise_l2_join_batched_prune(
        x, lengths, r, ones, bm=bm, bn=bn, interpret=interpret)
    return cnt.sum(axis=(1, 2))


@functools.partial(jax.jit, static_argnames=("dtype", "bm", "bn", "impl",
                                             "interpret"))
def _join_batched_counts(x, lengths, r, elig, *, dtype, bm, bn, impl,
                         interpret):
    return join_batched_counts_local(x, lengths, r, elig, dtype=dtype, bm=bm,
                                     bn=bn, impl=impl, interpret=interpret)


def pairwise_l2_join_batched_counts(x: jax.Array, lengths: jax.Array,
                                    r: jax.Array | float,
                                    elig: jax.Array | None = None, *,
                                    dtype: str = "bf16", bm: int = 128,
                                    bn: int = 128, impl: str | None = None,
                                    interpret: bool | None = None):
    """Coarse mixed-precision threshold-join counts (the cascade's tier 0).

    Same batching contract as :func:`pairwise_l2_join_batched_masked` but
    counts-only: no mask is materialised, no dense block, the readback is S
    int32 words. Call with the error-widened coarse radii; a subset whose
    count is at or below its live diagonal provably has no off-diagonal fp32
    pair, so the fp32 masked join can skip it. ``dtype`` is "bf16" or
    "int8" (int8 is XLA-only)."""
    if impl is None:
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl not in ("pallas", "xla"):
        raise ValueError(f"unknown impl: {impl!r}")
    interpret = _default_interpret() if interpret is None else interpret
    return _join_batched_counts(x, lengths, r, elig, dtype=dtype, bm=bm,
                                bn=bn, impl=impl, interpret=interpret)


def _fold_eligibility(mask, cnt, elig):
    """AND a packed per-subset eligibility vector into the packed join mask.

    ``elig`` is (S, ceil(P/32)) uint32 — bit ``j % 32`` of word ``j // 32``
    set iff point j of the subset satisfies the query's predicate (same
    LSB-first layout as the mask words). Folding is two elementwise passes on
    the packed words (columns: one AND against the broadcast eligibility
    row; rows: zero every ineligible row, the row bit gathered back out of
    the packed words), so the output *is* the existing (S, P, ceil(P/32))
    layout — eligibility adds H2D words but no new device->host transfer,
    and join counts become eligible-pair counts (popcount of the folded
    mask), which is what drives the empty-join host-enumeration skip at low
    selectivity."""
    s, p, _ = mask.shape
    col = jnp.arange(p)
    row_bit = (elig[:, col // 32] >> (col % 32).astype(jnp.uint32)) & jnp.uint32(1)
    folded = jnp.where((row_bit > 0)[:, :, None],
                       mask & elig[:, None, :], jnp.uint32(0))
    cnt = jnp.sum(jax.lax.population_count(folded), axis=(1, 2)) \
        .astype(jnp.int32)
    return folded, cnt


def join_batched_masked_local(x, lengths, r, elig=None, *, bm: int = 128,
                              bn: int = 128, with_sq: bool = False,
                              impl: str | None = None,
                              interpret: bool | None = None):
    """Un-jit'd masked batched self-join, safe to call under an outer trace.

    Same contract as :func:`pairwise_l2_join_batched_masked` but composable:
    ``core.device_plane`` calls this inside a ``shard_map`` body so each mesh
    shard runs the join on its local (S/n, P, d) slab. ``impl`` routing is
    resolved at trace time (Mosaic on TPU, the XLA lowering elsewhere).
    ``elig`` (packed (S, ceil(P/32)) uint32 eligibility words) ANDs a
    filtered query's point-eligibility into the mask and counts — a fused
    epilogue on the packed words, identical math on either lowering."""
    if impl is None:
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl not in ("pallas", "xla"):
        raise ValueError(f"unknown impl: {impl!r}")
    interpret = _default_interpret() if interpret is None else interpret
    if impl == "xla":
        out = _xla_join_batched_masked(x, lengths, r, with_sq)
        if with_sq:
            mask, cnt, sq = out
        else:
            mask, cnt = out
    else:
        out = _pairwise.pairwise_l2_join_batched_masked(
            x, lengths, r, bm=bm, bn=bn, with_sq=with_sq, interpret=interpret)
        if with_sq:
            mask, cnt, sq = out
        else:
            mask, cnt = out
        cnt = cnt.sum(axis=(1, 2))
    if elig is not None:
        mask, cnt = _fold_eligibility(mask, cnt, jnp.asarray(elig, jnp.uint32))
    if with_sq:
        return mask, cnt, sq
    return mask, cnt


@functools.partial(jax.jit, static_argnames=("bm", "bn", "with_sq", "impl",
                                             "interpret"))
def _join_batched_masked(x, lengths, r, elig, *, bm, bn, with_sq, impl,
                         interpret):
    return join_batched_masked_local(x, lengths, r, elig, bm=bm, bn=bn,
                                     with_sq=with_sq, impl=impl,
                                     interpret=interpret)


def pairwise_l2_join_batched_masked(x: jax.Array, lengths: jax.Array,
                                    r: jax.Array | float = float("inf"),
                                    elig: jax.Array | None = None, *,
                                    bm: int = 128, bn: int = 128,
                                    with_sq: bool = False,
                                    impl: str | None = None,
                                    interpret: bool | None = None):
    """Fused batched self-join emitting the packed adjacency bitmask.

    Returns ``(mask, counts[, sq])`` — mask (S, P, ceil(P/32)) uint32 (bit
    ``j % 32`` of word ``j // 32`` of row i set iff points i, j of the subset
    join at its radius), counts (S,) int32 per-subset join cardinalities
    (diagonal included), and the dense fp32 block only when ``with_sq``.

    ``elig`` ((S, ceil(P/32)) uint32, same LSB-first packing as the mask)
    scopes the join to a filtered query's eligible points: ineligible rows
    and columns are zeroed in the output mask and counts become
    eligible-pair counts — fused into the same program, so the D2H readback
    is byte-identical to the unfiltered dispatch.

    ``impl`` selects the lowering: "pallas" (the Mosaic kernel; interpreted
    off-TPU), "xla" (the reference formulation compiled by XLA), or None to
    pick "pallas" on TPU and "xla" elsewhere. Both lowerings share the mask
    contract bit-for-bit on identical fp32 inputs.
    """
    if impl is None:
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl not in ("pallas", "xla"):
        raise ValueError(f"unknown impl: {impl!r}")
    interpret = _default_interpret() if interpret is None else interpret
    return _join_batched_masked(x, lengths, r, elig, bm=bm, bn=bn,
                                with_sq=with_sq, impl=impl,
                                interpret=interpret)


@functools.partial(jax.jit, static_argnames=("w", "c", "bn", "interpret"))
def project_and_bin(x: jax.Array, z: jax.Array, w: float, c: int, *,
                    bn: int = 256, interpret: bool | None = None):
    """Fused projection + dual-bin keys (eqs. 1-2). Returns (h1, h2, proj)."""
    interpret = _default_interpret() if interpret is None else interpret
    return _project.project_and_bin(x, z, w, c, bn=bn, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("bt", "interpret"))
def tuple_diameters(pts: jax.Array, *, bt: int = 128,
                    interpret: bool | None = None):
    """Batched candidate diameters r(A) for padded tuples (T, q, d)."""
    interpret = _default_interpret() if interpret is None else interpret
    return _diameter.tuple_diameters(pts, bt=bt, interpret=interpret)


def pairwise_distances(a, b, *, interpret: bool | None = None) -> jnp.ndarray:
    """Convenience: dense (M, N) Euclidean distances via the join kernel."""
    sq, _ = pairwise_l2_join(a, b, interpret=interpret)
    return jnp.sqrt(sq)
