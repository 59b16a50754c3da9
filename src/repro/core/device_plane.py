"""The device plane: one mesh/placement layer for every serving tier.

Before this layer existed the repo had two parallel universes: the batched
bitmask-join pipeline (``core.backend``) dispatched every packed (S, P, d)
bin on a single device, while the shard_map anchor-star tier lived alone in
``core.distributed`` behind a separate engine code path. :class:`DevicePlane`
makes multi-device execution a property of the backend instead:

  * **mesh acquisition** — a plane wraps a jax mesh (``launch.mesh``
    constructors, ``REPRO_MESH_OVERRIDE`` honored) and exposes the serving
    axis contract: the ``data`` axis shards subsets/groups; ``model`` is
    unused by serving.
  * **sharded batched join** — :meth:`join_batched_masked` runs the packed
    masked self-join as a ``shard_map`` over ``data``: each shard computes
    its (S/n, P, d) slab locally through the same lowering as the
    single-device path (``kernels.ops.join_batched_masked_local`` — Mosaic
    on TPU, XLA elsewhere), packed bitmasks + join counts gather back on
    readback. The join is embarrassingly parallel over S, so the per-shard
    math is *identical* to the single-device dispatch and the bitmasks are
    bit-exact (the parity suite asserts this).
  * **group/tile packing** — :func:`pack_groups` (moved here from
    ``core.distributed``) pads keyword groups to an MXU/shard-aligned (q, R,
    d) block and now reports truncation instead of silently dropping points.
  * **replicated top-k merge** — :func:`replicated_topk_merge` is the
    phase-C collective every sharded tier ends on; ``nks_topk`` rebuilds the
    anchor-star tier (``distributed_nks_topk``) on it.

``PallasBackend(plane=...)`` routes size-binned dispatches here when a bin
packs at least one subset per shard; remainder bins (S < mesh size) fall
back to its single-device dispatch. ``serve.engine.NKSEngine(mesh=...)``
builds the plane once and threads it through all three tiers.

Corpus generations (streaming ingest): the plane's jit program caches
(``_join_fns``/``_nks_fns``) are keyed on *shapes and tile params only* —
they hold compiled programs, never corpus data, so they survive delta
absorbs and compactions untouched. Corpus-dependent state (packed subset
rows, device-committed tiles) lives in the backend's LRU, which the engine
scopes to its ``corpus_generation`` token: absorbs retain entries, a
compaction (id remap) purges them. Nothing on the plane needs invalidation
when the corpus changes.
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P


@dataclasses.dataclass
class PackedGroups:
    """Padded (q, R, d) group tensor + mask + ids for one query.

    Iterates as the classic ``(groups, mask, ids)`` triple so existing
    callers keep unpacking it; ``truncated`` counts relevant points silently
    dropped because a keyword group exceeded ``r_max`` (0 when every group
    fit), and ``group_sizes`` records the pre-truncation group sizes.
    """

    groups: np.ndarray          # (q, R, d) float32
    mask: np.ndarray            # (q, R) bool
    ids: np.ndarray             # (q, R) int32
    truncated: int
    group_sizes: list[int]

    def __iter__(self):
        return iter((self.groups, self.mask, self.ids))


def pack_groups(dataset, query, r_max: int | None = None, *,
                strict: bool = False, align: int = 128,
                eligible: np.ndarray | None = None) -> PackedGroups:
    """Host packing of per-keyword relevant groups for the device tiers.

    R defaults to the largest group size rounded up to ``align`` (128 = MXU
    lane alignment; planes round it up further to a shard multiple). A group
    larger than an explicit ``r_max`` is truncated to the first ``r_max``
    points — counted in ``PackedGroups.truncated`` and fatal under
    ``strict=True`` (candidates containing a dropped point are unreachable,
    so a strict caller wants the signal, not a quietly degraded answer).
    ``eligible`` (a filtered query's (N,) point mask) restricts each group
    before packing, so the anchor-star tier never ships an ineligible point.
    """
    groups = [dataset.points_with(v) for v in query]
    if eligible is not None:
        groups = [g[eligible[g]] for g in groups]
    sizes = [len(g) for g in groups]
    if r_max is None:
        r_max = max(align, int(np.ceil(max(max(sizes), 1) / align)) * align)
    truncated = sum(max(s - r_max, 0) for s in sizes)
    if strict and truncated:
        raise ValueError(
            f"pack_groups: {truncated} relevant points truncated beyond "
            f"r_max={r_max} (group sizes {sizes}); raise r_max or drop strict")
    q = len(query)
    out = np.zeros((q, r_max, dataset.dim), np.float32)
    mask = np.zeros((q, r_max), bool)
    ids = np.zeros((q, r_max), np.int32)
    for j, g in enumerate(groups):
        g = g[:r_max]
        out[j, :len(g)] = dataset.points[g]
        mask[j, :len(g)] = True
        ids[j, :len(g)] = g
    return PackedGroups(out, mask, ids, truncated, sizes)


def replicated_topk_merge(axis: str, diams, cand_ids, k: int):
    """Phase-C collective: merge per-shard top-k into a replicated global one.

    ``diams`` (k,) ascending per shard, ``cand_ids`` (k, q). all_gathers both
    over ``axis`` and re-selects the k smallest — every shard returns the
    identical merged (diams (k,), ids (k, q))."""
    d_all = jax.lax.all_gather(diams, axis, tiled=True)            # (n*k,)
    c_all = jax.lax.all_gather(cand_ids, axis, axis=0, tiled=True)  # (n*k, q)
    neg, sel = jax.lax.top_k(-d_all, k)
    return -neg, c_all[sel]


def balance_order(lengths: np.ndarray, n_shards: int) -> np.ndarray:
    """Work-levelling shard placement: a permutation of ``range(len(lengths))``
    that deals subsets round-robin in descending size order.

    The plane assigns shard i the contiguous slab [i*S/n, (i+1)*S/n), so a
    length-sorted batch (the size-binned packer emits near-sorted bins) piles
    the big subsets onto the first shards. Dealing the descending sort
    across the n slabs in boustrophedon (snake) order — forward on even
    passes, backward on odd — pairs each shard's large draws with small
    ones, keeping slab work sums within one subset of each other (plain
    round-robin systematically favours low shard ids). The sort key is the *packed* work
    proxy (valid length; eligible counts when a filter packs eligible-dense),
    not the pruning radius: placement must stay radius-independent because
    committed tiles are reused across radii, so the ISSUE's "radius-sorted"
    placement is realised as size-sorted — the quantity that actually sets
    per-shard join cost. ``len(lengths)`` must be a shard multiple (callers
    pad first); returns ``perm`` such that ``x[perm]`` is the levelled order
    and ``out[np.argsort(perm)]`` restores dispatch order on readback.
    """
    s = len(lengths)
    assert s % n_shards == 0, (s, n_shards)
    order = np.argsort(-np.asarray(lengths, np.int64), kind="stable")
    ranks = order.reshape(-1, n_shards).copy()   # row = one dealing pass
    ranks[1::2] = ranks[1::2, ::-1]              # snake: reverse odd passes
    # shard i's contiguous slab = column i across passes
    return np.ascontiguousarray(ranks.T).reshape(-1)


class DevicePlane:
    """One mesh + the serving-axis contract, shared by every sharded tier."""

    def __init__(self, mesh: Mesh | None = None, *, axis: str = "data"):
        if mesh is None:
            from repro.launch.mesh import make_serving_mesh
            mesh = make_serving_mesh()
        if axis not in mesh.axis_names:
            raise ValueError(f"mesh has no {axis!r} axis: {mesh.axis_names}")
        self.mesh = mesh
        self.axis = axis
        self._join_fns: dict[tuple, object] = {}
        self._nks_fns: dict[tuple, object] = {}

    @property
    def n_shards(self) -> int:
        return int(self.mesh.shape[self.axis])

    def shard_pad(self, n: int) -> int:
        """Round ``n`` up to a multiple of the shard count (shard_map needs
        the sharded axis evenly divisible)."""
        s = self.n_shards
        return ((n + s - 1) // s) * s

    def sharding(self, spec: P) -> NamedSharding:
        return NamedSharding(self.mesh, spec)

    # ------------------------------------------------------------ sharded join
    def _join_fn(self, bm: int, bn: int, impl: str | None,
                 interpret: bool | None, with_elig: bool):
        key = (bm, bn, impl, interpret, with_elig)
        fn = self._join_fns.get(key)
        if fn is None:
            from repro.kernels import ops
            ax = self.axis

            if with_elig:
                def body(x_loc, len_loc, r_loc, e_loc):
                    return ops.join_batched_masked_local(
                        x_loc, len_loc, r_loc, e_loc, bm=bm, bn=bn,
                        impl=impl, interpret=interpret)
            else:
                def body(x_loc, len_loc, r_loc):
                    return ops.join_batched_masked_local(
                        x_loc, len_loc, r_loc, bm=bm, bn=bn,
                        impl=impl, interpret=interpret)

            n_in = 4 if with_elig else 3
            sharded = jax.shard_map(body, mesh=self.mesh,
                                    in_specs=(P(ax),) * n_in,
                                    out_specs=(P(ax), P(ax)),
                                    check_vma=False)
            fn = jax.jit(sharded,
                         in_shardings=(self.sharding(P(ax)),) * n_in)
            self._join_fns[key] = fn
        return fn

    def join_batched_masked(self, x, lengths, r, elig=None, *, bm: int = 128,
                            bn: int = 128, impl: str | None = None,
                            interpret: bool | None = None):
        """Sharded masked batched self-join: (S, P, d) sharded on S over the
        ``data`` axis, one local join per shard, no cross-shard collectives.

        Returns (mask (S, P, ceil(P/32)) uint32, counts (S,) int32) with the
        same contract as ``ops.pairwise_l2_join_batched_masked`` — including
        the optional packed per-subset eligibility words ``elig``
        ((S, ceil(P/32)) uint32), sharded on S like everything else: each
        shard folds eligibility into its local slab's mask, so filtered
        dispatches stay bit-exact with the single-device route. S must be a
        multiple of :attr:`n_shards` (callers pad with zero-length subsets,
        which produce all-zero mask rows and zero counts)."""
        s = x.shape[0]
        if s % self.n_shards:
            raise ValueError(
                f"sharded join needs S % n_shards == 0, got S={s} over "
                f"{self.n_shards} shards (pad with zero-length subsets)")
        fn = self._join_fn(bm, bn, impl, interpret, elig is not None)
        if elig is None:
            return fn(x, lengths, r)
        return fn(x, lengths, r, elig)

    def _counts_fn(self, dtype: str, bm: int, bn: int, impl: str | None,
                   interpret: bool | None, with_elig: bool):
        key = ("counts", dtype, bm, bn, impl, interpret, with_elig)
        fn = self._join_fns.get(key)
        if fn is None:
            from repro.kernels import ops
            ax = self.axis

            if with_elig:
                def body(x_loc, len_loc, r_loc, e_loc):
                    return ops.join_batched_counts_local(
                        x_loc, len_loc, r_loc, e_loc, dtype=dtype, bm=bm,
                        bn=bn, impl=impl, interpret=interpret)
            else:
                def body(x_loc, len_loc, r_loc):
                    return ops.join_batched_counts_local(
                        x_loc, len_loc, r_loc, dtype=dtype, bm=bm, bn=bn,
                        impl=impl, interpret=interpret)

            n_in = 4 if with_elig else 3
            sharded = jax.shard_map(body, mesh=self.mesh,
                                    in_specs=(P(ax),) * n_in,
                                    out_specs=P(ax),
                                    check_vma=False)
            fn = jax.jit(sharded,
                         in_shardings=(self.sharding(P(ax)),) * n_in)
            self._join_fns[key] = fn
        return fn

    def join_batched_counts(self, x, lengths, r, elig=None, *,
                            dtype: str = "bf16", bm: int = 128, bn: int = 128,
                            impl: str | None = None,
                            interpret: bool | None = None):
        """Sharded coarse prune-tier counts: the cascade's tier 0 on the
        plane. Same sharding contract as :meth:`join_batched_masked` — S
        sharded over ``data``, one local counts pass per shard, no
        collectives — but the readback is S int32 words instead of the packed
        mask, so the prune decision costs almost no D2H. ``elig`` uses the
        packed uint32 word layout."""
        s = x.shape[0]
        if s % self.n_shards:
            raise ValueError(
                f"sharded counts need S % n_shards == 0, got S={s} over "
                f"{self.n_shards} shards (pad with zero-length subsets)")
        fn = self._counts_fn(dtype, bm, bn, impl, interpret, elig is not None)
        if elig is None:
            return fn(x, lengths, r)
        return fn(x, lengths, r, elig)

    def put_sharded(self, *arrays):
        """Commit host arrays to the mesh, sharded on dim 0 over ``data``."""
        sh = self.sharding(P(self.axis))
        return tuple(jax.device_put(a, sh) for a in arrays)

    def shard_cells(self, lengths: np.ndarray, p_pad: int
                    ) -> tuple[list[int], list[int]]:
        """Per-shard (valid, total) join-block cell counts for one dispatch.

        ``lengths`` is the padded (S,) valid-point vector the dispatch
        shipped; shard i owns the contiguous slab [i*S/n, (i+1)*S/n). Valid
        cells are sum(len^2) over the slab, total is slab * P^2 — the
        utilisation ratio the stats report per shard."""
        n = self.n_shards
        per = len(lengths) // n
        lens = np.asarray(lengths, np.int64)
        valid = [int((lens[i * per:(i + 1) * per] ** 2).sum())
                 for i in range(n)]
        total = [per * p_pad * p_pad] * n
        return valid, total

    # --------------------------------------------------------- anchor-star tier
    def _nks_fn(self, k: int):
        fn = self._nks_fns.get(k)
        if fn is None:
            from repro.core.distributed import nks_anchor_topk
            ax = self.axis

            def body(g_loc, m_loc, i_loc):
                # phase A: gather the full relevant set (small by eq. 4
                # selectivity); phase B: anchors stay partitioned — each
                # shard scores its local slice of group 0.
                g_all = jax.lax.all_gather(g_loc, ax, axis=1, tiled=True)
                m_all = jax.lax.all_gather(m_loc, ax, axis=1, tiled=True)
                i_all = jax.lax.all_gather(i_loc, ax, axis=1, tiled=True)
                diams, cids = nks_anchor_topk(
                    g_all, m_all, i_all, k,
                    anchors=g_loc[0], anchor_mask=m_loc[0],
                    anchor_ids=i_loc[0])
                # phase C: replicated global top-k
                return replicated_topk_merge(ax, diams, cids, k)

            spec_in = P(None, self.axis, None)
            fn = jax.jit(jax.shard_map(body, mesh=self.mesh,
                                       in_specs=(spec_in, P(None, self.axis),
                                                 P(None, self.axis)),
                                       out_specs=(P(), P()),
                                       check_vma=False))
            self._nks_fns[k] = fn
        return fn

    def nks_topk(self, groups, mask, ids, k: int):
        """Anchor-star NKS top-k over the plane: ``groups`` (q, R, d) sharded
        on R over ``data``; returns (diams (k,), ids (k, q)) replicated."""
        if groups.shape[1] % self.n_shards:
            raise ValueError(
                f"nks_topk needs R % n_shards == 0, got R={groups.shape[1]} "
                f"over {self.n_shards} shards (pack with a shard-aligned r_max)")
        return self._nks_fn(k)(groups, mask, ids)

    def pack_groups(self, dataset, query, r_max: int | None = None, *,
                    strict: bool = False,
                    eligible: np.ndarray | None = None) -> PackedGroups:
        """:func:`pack_groups` with R rounded up to a shard multiple so the
        result feeds :meth:`nks_topk` directly."""
        pg = pack_groups(dataset, query, r_max, strict=strict,
                         eligible=eligible)
        r_pad = self.shard_pad(pg.groups.shape[1])
        if r_pad != pg.groups.shape[1]:
            extra = r_pad - pg.groups.shape[1]
            pg = PackedGroups(
                np.pad(pg.groups, ((0, 0), (0, extra), (0, 0))),
                np.pad(pg.mask, ((0, 0), (0, extra))),
                np.pad(pg.ids, ((0, 0), (0, extra))),
                pg.truncated, pg.group_sizes)
        return pg


def get_plane(mesh=None, *, axis: str = "data") -> DevicePlane:
    """Resolve a plane spec: an existing plane, a jax Mesh, or None/"auto"
    (acquire the serving mesh from the environment)."""
    if isinstance(mesh, DevicePlane):
        return mesh
    if mesh is None or mesh == "auto":
        return DevicePlane(axis=axis)
    return DevicePlane(mesh, axis=axis)
