"""Distance backends for the subset-search pipeline.

The §V inner joins and Algorithm 4 predicates consume one *join structure*
per covering-bucket subset. This module routes that production:

  * :class:`NumpyBackend` — float64 on the control plane; distances are exact,
    so enumeration needs no slack and no rescoring. Emits dense distance
    blocks; the enumeration stage packs its own bitmask at the live r_k. One
    "dispatch" per subset (the per-query loop the paper measures).
  * :class:`PallasBackend` — packs every subset of a batch into one dense
    (S, P, d) tile block and issues **one** fused
    ``kernels.ops.pairwise_l2_join_batched_masked`` dispatch, with per-subset
    pruning radii riding in SMEM. The result shipped back to the host is the
    **packed adjacency bitmask** (S, P, ceil(P/32)) — a 32x smaller D2H
    readback than the dense fp32 block, which is no longer materialised on
    the host at all. fp32 on device is a *pruning filter*: the per-subset
    radius is widened by an absolute slack bounding fp32 cancellation error,
    and the enumeration stage re-scores surviving tuples through the float64
    path before they enter the queue (``subset_search.enumerate_with_block``).

The block contract (:class:`DistanceBlock`) carries either ``dist`` (dense
float64, numpy) or ``mask`` (packed uint32 at the dispatch-time pruning
radius, device), plus ``join_count`` — the kernel's inner-join cardinality,
which the enumeration stage uses to skip subsets whose join is empty before
any host work (the adaptive-radii feedback loop).

``PallasBackend`` keeps a byte-bounded LRU cache keyed on the Algorithm-2
subset hash (the sorted-id bytes): per-subset packed fp32 rows + slack, and
whole packed dispatch tiles already committed to the device — steady-state
repeated subsets skip gather, packing, and H2D entirely.

Backends are deliberately jax-free at import time: the device stack loads
only when a PallasBackend actually dispatches, keeping the numpy control
plane importable everywhere.
"""
from __future__ import annotations

import abc
import dataclasses
import time
from collections import OrderedDict
from typing import Sequence

import numpy as np

from repro.core.subset_search import (_sq_dists_f64, pack_join_mask,
                                      pairwise_l2_numpy)
from repro.utils.timing import span

_EPS32 = float(np.finfo(np.float32).eps)
_F32_MAX = float(np.finfo(np.float32).max)


@dataclasses.dataclass
class BackendStats:
    """Dispatch accounting for the pipeline stats (§VII-style instrumentation)."""

    dispatches: int = 0        # device/loop calls issued
    subsets: int = 0           # join blocks produced
    points_packed: int = 0     # total valid points shipped
    points_padded: int = 0     # pad waste (packed tile points - valid points)
    join_pairs: int = 0        # threshold-join survivors across all subsets
    t_pack_s: float = 0.0      # host time: gather + tile packing
    t_dispatch_s: float = 0.0  # device time: dispatch + D2H readback
    cache_hits: int = 0        # packed-subset/tile LRU hits
    cache_misses: int = 0
    cache_evictions: int = 0
    generation_purges: int = 0  # cache invalidations on corpus-generation bump
    # Transfer accounting (device backends): host->device bytes shipped
    # (tiles + lengths + radii + packed eligibility words) and device->host
    # bytes read back (packed masks + join counts). The filtered-NKS
    # contract — eligibility folds into the existing packed mask, adding no
    # new D2H — is asserted on these counters.
    h2d_bytes: int = 0
    d2h_bytes: int = 0
    # Sharded-dispatch accounting (populated when a DevicePlane routes the
    # dispatch over the mesh; lists are indexed by shard/device position on
    # the plane's data axis and sized lazily on first device dispatch).
    sharded_dispatches: int = 0            # dispatches routed via shard_map
    t_collective_s: float = 0.0            # wall inside sharded dispatches
    shard_dispatches: list = dataclasses.field(default_factory=list)
    shard_valid_cells: list = dataclasses.field(default_factory=list)
    shard_total_cells: list = dataclasses.field(default_factory=list)
    # Cascade / routing accounting (PallasBackend): the coarse mixed-precision
    # prune tier and the cost-model host route. ``t_prune_s`` and ``t_host_s``
    # are *components* of ``t_dispatch_s`` (the engine subtracts them out to
    # report the fp32 join share). ``bin_points`` maps each size-class edge to
    # cumulative (valid, padded) point totals packed under it.
    prune_tier_dispatches: int = 0         # coarse counts passes issued
    join_dispatches: int = 0               # fp32 masked-join passes issued
    cells_pruned: int = 0                  # fp32 tile cells skipped via prune
    t_prune_s: float = 0.0                 # wall inside coarse counts passes
    host_routed_dispatches: int = 0        # bins routed to the host backend
    host_routed_subsets: int = 0           # subsets served by host routing
    t_host_s: float = 0.0                  # wall inside host-routed bins
    bin_points: dict = dataclasses.field(default_factory=dict)
    # Out-of-core accounting: bytes gathered out of a memory-mapped corpus
    # (the cold tier under the packed-row/tile LRU). Each counted gather is
    # an upper bound on the pages faulted in — rows already resident in the
    # page cache cost nothing at runtime but are still counted, so the
    # number reads as "bytes served from below the hot tier".
    cold_bytes_read: int = 0

    def ensure_shards(self, n: int) -> None:
        for lst in (self.shard_dispatches, self.shard_valid_cells,
                    self.shard_total_cells):
            lst.extend([0] * (n - len(lst)))


@dataclasses.dataclass(frozen=True)
class DistanceBlock:
    """One subset's join structure plus the contract needed to consume it.

    n          : number of valid points in the subset.
    dist       : (n, n) float64 pairwise L2 distances, or None for mask-only
                 device blocks.
    mask       : (n, ceil(n/32)) uint32 packed adjacency at the dispatch-time
                 pruning radius (bit j%32 of word j//32 set iff points i, j
                 join). None for dense blocks — and for device blocks whose
                 radius was infinite (every pair joins by construction; the
                 backend skips the dispatch and enumeration treats the
                 adjacency as all-ones).
    slack      : absolute distance error bound; dense approximate blocks are
                 pruned at r + slack (mask blocks bake it into the radius).
    rescore    : True when the block is approximate and accepted tuples must
                 be re-scored in float64 before entering the top-k queue.
    join_count : #{pairs joining at the pruning radius}, diagonal included —
                 ``join_count <= n`` proves the inner join empty, letting the
                 enumeration stage skip the subset (adaptive radii).
    n_eligible : number of subset points satisfying the query's predicate
                 mask, or None on an unfiltered call. When set, ``mask`` and
                 ``join_count`` cover eligible pairs only (the eligibility
                 fold), so the empty-join test becomes
                 ``join_count <= n_eligible``.
    rows       : eligible-dense packing (low-selectivity filtered dispatch):
                 sorted subset-local row positions actually packed into the
                 device tile. ``mask`` then covers only those rows — the
                 enumeration stage remaps its keyword groups into the packed
                 row space (``subset_search.enumerate_with_block``). None on
                 the standard full-subset pack.
    """

    n: int
    slack: float
    rescore: bool
    join_count: int
    dist: np.ndarray | None = None
    mask: np.ndarray | None = None
    n_eligible: int | None = None
    rows: np.ndarray | None = None


class DistanceBackend(abc.ABC):
    """Produces per-subset self-join blocks for the enumeration stage."""

    name: str = "abstract"

    def __init__(self) -> None:
        self.stats = BackendStats()

    def _note_cold_read(self, points: np.ndarray, n_rows: int) -> None:
        """Count a row gather against the cold tier when ``points`` is a
        memory-mapped store leaf (resident corpora cost nothing)."""
        if isinstance(points, np.memmap):
            self.stats.cold_bytes_read += \
                int(n_rows) * int(points.shape[1]) * points.itemsize

    @abc.abstractmethod
    def pairwise(self, a: np.ndarray, b: np.ndarray) -> np.ndarray:
        """Dense (n, m) distance matrix for one pair of point sets."""

    @abc.abstractmethod
    def self_join_blocks(self, points: np.ndarray,
                         id_lists: Sequence[np.ndarray],
                         radii: Sequence[float],
                         keys: Sequence[bytes] | None = None,
                         generation: int | None = None,
                         eligible: np.ndarray | None = None
                         ) -> list[DistanceBlock]:
        """Self-join blocks for a batch of subsets at per-subset radii.

        ``points`` is the full corpus; each ``id_lists[i]`` selects one
        subset's rows (sorted unique ids). ``keys`` are the Algorithm-2
        subset hashes (sorted-id bytes) used as cache keys; pass None to
        bypass caching. ``generation`` is the caller's corpus-generation
        token: calls under the same token may share cache entries even if
        the ``points`` array object changed (streaming absorbs are
        append-only, so existing rows are immutable within a generation);
        a token change invalidates everything (compaction remapped ids).

        ``eligible`` is a filtered query's (N,) bool point mask: the emitted
        blocks scope their mask/counts to eligible pairs (``n_eligible``
        set), while subsets, cache keys, and packed tiles stay
        filter-independent — the same dispatch under a different filter
        reuses every cache entry and ships only fresh eligibility words."""


@dataclasses.dataclass(frozen=True)
class DispatchCostModel:
    """Measured crossover model for dispatch routing (calibrated at warmup).

    Costs are a two-point linear fit per route: a fixed per-dispatch term
    plus a per-join-cell term, probed at the corpus dimensionality the
    backend actually serves (so no cross-d extrapolation). ``prune_cell_s``
    is the coarse counts-pass cost per cell; the prune tier only pays off
    where the coarse gemm is genuinely cheaper than the fp32 one (the TPU
    MXU's double-rate bf16 path — on CPU/XLA there is no such discount, so
    ``prune_profitable`` is False off-TPU regardless of timings).
    """

    platform: str
    d: int
    dev_fixed_s: float     # per-dispatch overhead (trace/launch/readback)
    dev_cell_s: float      # fp32 masked join, per padded tile cell
    prune_cell_s: float    # coarse counts pass, per padded tile cell
    host_fixed_s: float    # numpy route, per subset
    host_cell_s: float     # numpy float64 join, per valid cell
    settle_cell_s: float = 0.0   # expected host f64 settlement of a device
    settle_fixed_s: float = 0.0  # block (unpack + table + expansion), per
    #                              valid cell / per subset

    def device_cost(self, padded_cells: int, valid_cells: int = 0,
                    n_subsets: int = 0) -> float:
        # A device block is not free after readback: subsets whose join is
        # non-empty settle on the host in float64 — work a host-routed block
        # (which ships exact distances) never repeats. The settle terms make
        # the two routes comparable as *end-to-end* costs; on accelerators
        # the dev term shrinks by orders of magnitude (and the prune tier
        # kills most settlements), which is exactly the measured crossover.
        return self.dev_fixed_s + self.dev_cell_s * padded_cells \
            + self.settle_cell_s * valid_cells \
            + self.settle_fixed_s * n_subsets

    def host_cost(self, n_subsets: int, valid_cells: int) -> float:
        return self.host_fixed_s * n_subsets + self.host_cell_s * valid_cells

    @property
    def prune_profitable(self) -> bool:
        return (self.platform == "tpu"
                and self.prune_cell_s < 0.7 * self.dev_cell_s)


_COST_MODELS: dict[tuple, DispatchCostModel] = {}


def calibrate_cost_model(d: int, *, bm: int = 128, bn: int = 128,
                         interpret: bool | None = None) -> DispatchCostModel:
    """Measure the device/host crossover at dimensionality ``d`` (memoized
    per process). Probes the warm path: each probe is compiled + warmed once,
    then timed best-of-3, so jit tracing never lands in the model."""
    import jax
    from repro.kernels import ops

    platform = jax.default_backend()
    key = (platform, d, bm, bn, interpret)
    model = _COST_MODELS.get(key)
    if model is not None:
        return model

    def best(f, reps=3):
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            f()
            ts.append(time.perf_counter() - t0)
        return min(ts)

    x_s = np.zeros((8, 32, d), np.float32)
    x_b = np.zeros((8, 256, d), np.float32)
    l_s = np.full(8, 32, np.int32)
    l_b = np.full(8, 256, np.int32)
    r = np.ones(8, np.float32)

    def dev(x, lens):
        mask, cnt = ops.pairwise_l2_join_batched_masked(
            x, lens, r, bm=bm, bn=bn, interpret=interpret)
        np.asarray(cnt)

    def prune(x, lens):
        np.asarray(ops.pairwise_l2_join_batched_counts(
            x, lens, r, bm=bm, bn=bn, interpret=interpret))

    dev(x_s, l_s)
    dev(x_b, l_b)
    prune(x_b, l_b)
    t_ds, t_db = best(lambda: dev(x_s, l_s)), best(lambda: dev(x_b, l_b))
    cells_s, cells_b = 8 * 32 * 32, 8 * 256 * 256
    dev_cell = max((t_db - t_ds) / (cells_b - cells_s), 1e-13)
    dev_fixed = max(t_ds - dev_cell * cells_s, 0.0)
    prune_cell = max((best(lambda: prune(x_b, l_b)) - dev_fixed) / cells_b,
                     1e-13)

    p_s = np.zeros((32, d))
    p_b = np.zeros((256, d))

    def host(pts):
        dist = pairwise_l2_numpy(pts, pts)
        (dist <= 1.0).sum()

    host(p_s)
    t_hs, t_hb = best(lambda: host(p_s)), best(lambda: host(p_b))
    host_cell = max((t_hb - t_hs) / (cells_b // 8 - cells_s // 8), 1e-13)
    host_fixed = max(t_hs - host_cell * (cells_s // 8), 0.0)

    # Settlement share of a device block's end-to-end cost, as a fraction of
    # the equivalent host join. Without an accelerator the fp32 dispatch buys
    # no arithmetic advantage, every settled subset re-pays host-f64 work on
    # top of the dispatch, and measured end-to-end rates show the host route
    # winning (the exact-tier inversion this model exists to fix) — so the
    # full host cost is charged. On TPU the prune tier removes most
    # settlements and the dispatch term collapses, so half is charged.
    settle_frac = 0.5 if platform == "tpu" else 1.0
    model = DispatchCostModel(
        platform=platform, d=d, dev_fixed_s=dev_fixed, dev_cell_s=dev_cell,
        prune_cell_s=prune_cell, host_fixed_s=host_fixed,
        host_cell_s=host_cell,
        settle_cell_s=settle_frac * host_cell,
        settle_fixed_s=settle_frac * host_fixed)
    _COST_MODELS[key] = model
    return model


def _dp_segment(values: np.ndarray, counts: np.ndarray,
                cap: int) -> np.ndarray:
    """Waste-minimizing size-class edges over a length histogram.

    ``values`` are distinct (rounded) subset lengths, ``counts`` their
    multiplicities. A segmentation assigns every value to the segment's top
    value (the bin edge each member pads to); its cost is total padded tile
    cells ``sum(edge^2 * members)`` plus ``lam`` per segment. The O(u^2) DP
    is exact for a given ``lam``; ``lam`` escalates x4 from one cell until
    the optimum uses at most ``cap`` segments, so edges are deterministic —
    no timing enters the choice."""
    u = len(values)
    if u <= cap:
        return values.copy()
    v2 = values.astype(np.float64) ** 2
    csum = np.concatenate([[0.0], np.cumsum(counts.astype(np.float64))])
    lam = 1.0
    while True:
        dp = np.zeros(u + 1)
        prev = np.zeros(u + 1, np.int64)
        nseg = np.zeros(u + 1, np.int64)
        for j in range(1, u + 1):
            cost = dp[:j] + v2[j - 1] * (csum[j] - csum[:j]) + lam
            bi = int(np.argmin(cost))
            dp[j], prev[j], nseg[j] = cost[bi], bi, nseg[bi] + 1
        if nseg[u] <= cap:
            edges = []
            j = u
            while j > 0:
                edges.append(int(values[j - 1]))
                j = prev[j]
            return np.asarray(sorted(edges), dtype=values.dtype)
        lam *= 4.0


class NumpyBackend(DistanceBackend):
    """float64 control-plane backend: exact, loops subset by subset."""

    name = "numpy"

    def pairwise(self, a: np.ndarray, b: np.ndarray) -> np.ndarray:
        self.stats.dispatches += 1
        return pairwise_l2_numpy(a, b)

    def self_join_blocks(self, points: np.ndarray,
                         id_lists: Sequence[np.ndarray],
                         radii: Sequence[float],
                         keys: Sequence[bytes] | None = None,
                         generation: int | None = None,
                         eligible: np.ndarray | None = None
                         ) -> list[DistanceBlock]:
        out = []
        with span("nks.backend.dispatch", self.stats, "t_dispatch_s",
                  subsets=len(id_lists)):
            for ids, r in zip(id_lists, radii):
                pts = points[ids]
                self._note_cold_read(points, len(ids))
                dist = self.pairwise(pts, pts)
                n_elig = None
                if eligible is None:
                    count = int((dist <= r).sum()) if np.isfinite(r) \
                        else dist.size
                else:
                    # Mirror the device fold: counts cover eligible pairs
                    # only, so the empty-join signal fires at the filtered
                    # selectivity.
                    el = eligible[ids]
                    n_elig = int(el.sum())
                    pair_ok = el[:, None] & el[None, :]
                    count = int(((dist <= r) & pair_ok).sum()) \
                        if np.isfinite(r) else int(pair_ok.sum())
                self.stats.subsets += 1
                self.stats.points_packed += len(ids)
                self.stats.join_pairs += count
                out.append(DistanceBlock(n=len(ids), dist=dist, slack=0.0,
                                         rescore=False, join_count=count,
                                         n_eligible=n_elig))
        return out


class PallasBackend(DistanceBackend):
    """Fused device backend: one batched threshold-join dispatch per call.

    Subset counts and pad widths are rounded up (``quantum``) so repeated
    scales reuse compiled programs instead of retracing per shape. A call
    whose packed (S, P, P) on-device join block would exceed
    ``max_block_bytes`` (the fallback stage can pack near-corpus-sized
    subsets for many queries at once) is split into size-bounded chunks —
    still one dispatch per chunk, and a single dispatch in the common
    per-scale case.

    Off-TPU the fused dispatch lowers through XLA (``kernels.ops`` routes by
    backend; the Pallas program is the Mosaic artifact, its interpreter a
    debugging tool). ``cache_bytes`` bounds the packed-subset/tile LRU.

    ``plane`` (a :class:`~repro.core.device_plane.DevicePlane`) makes
    multi-device execution a property of this backend: a size-binned dispatch
    that packs at least one subset per mesh shard is routed through the
    plane's ``shard_map`` join — subsets sharded on S over the ``data`` axis,
    packed bitmasks + join counts gathered back on readback, per-shard
    utilisation recorded in the stats. Remainder bins (fewer subsets than
    shards) keep the single-device dispatch; the per-shard math is identical
    either way, so blocks are bit-exact across routes.
    """

    name = "pallas"

    def __init__(self, *, bm: int = 128, bn: int = 128,
                 interpret: bool | None = None, quantum: int = 8,
                 max_block_bytes: int = 256 << 20,
                 cache_bytes: int = 128 << 20,
                 plane=None,
                 bin_strategy: str = "quantile",
                 n_classes: int = 6,
                 route: str = "auto",
                 prune_tier: str = "auto",
                 prune_dtype: str = "bf16",
                 prune_eps: float = 0.05,
                 elig_pack_threshold: float = 0.25,
                 placement: str = "sorted",
                 cost_model: DispatchCostModel | None = None) -> None:
        super().__init__()
        self.bm = bm
        self.bn = bn
        self.interpret = interpret
        self.quantum = quantum
        self.max_block_bytes = max_block_bytes
        self.cache_bytes = cache_bytes
        self.plane = plane
        # --- raw-speed campaign knobs (see README "Performance tuning") ---
        # bin_strategy: "quantile" fits size-class edges to the planned
        #   subset-length distribution per call (deterministic DP, at most
        #   n_classes edges, never more padded cells than "pow2").
        # route: "auto" sends bins below the measured Pallas break-even to
        #   the exact host path (one dispatch per bin either way); "device"
        #   pins every finite-radius bin on the device.
        # prune_tier: "on"/"off"/"auto" — the coarse bf16/int8 counts pass
        #   ahead of the fp32 masked join; "auto" enables it only where the
        #   calibrated model shows a coarse-gemm discount (TPU).
        # elig_pack_threshold: below this filter selectivity, tiles pack
        #   eligible rows densely instead of folding an eligibility mask.
        # placement: "sorted" deals sharded bins to shards in snake order of
        #   packed size so slab work stays level; "none" keeps plan order.
        if bin_strategy not in ("quantile", "pow2"):
            raise ValueError(f"unknown bin_strategy: {bin_strategy!r}")
        if route not in ("auto", "device"):
            raise ValueError(f"unknown route: {route!r}")
        if prune_tier not in ("auto", "on", "off"):
            raise ValueError(f"unknown prune_tier: {prune_tier!r}")
        if prune_dtype not in ("bf16", "int8"):
            raise ValueError(f"unknown prune_dtype: {prune_dtype!r}")
        if placement not in ("sorted", "none"):
            raise ValueError(f"unknown placement: {placement!r}")
        self.bin_strategy = bin_strategy
        self.n_classes = n_classes
        self.route = route
        self.prune_tier = prune_tier
        self.prune_dtype = prune_dtype
        self.prune_eps = prune_eps
        self.elig_pack_threshold = elig_pack_threshold
        self.placement = placement
        self._model = cost_model
        self._edge_cache: dict[bytes, np.ndarray] = {}
        # LRU over both per-subset packed rows and whole device-committed
        # dispatch tiles; values are (nbytes, payload). Entries are only
        # valid for one corpus *generation*: subset keys are id bytes, so a
        # backend re-used against a remapped id space must drop the cache
        # (see ``self_join_blocks``). Within a generation the id space is
        # append-only (streaming absorbs/tombstones), so entries survive
        # corpus growth — a tombstoned id never recurs in a subset key, and
        # existing rows are immutable.
        self._cache: OrderedDict[tuple, tuple[int, tuple]] = OrderedDict()
        self._cache_nbytes = 0
        self._corpus: np.ndarray | None = None
        self._generation: int | None = None
        self._min_class: int | None = None

    # ------------------------------------------------------------------ cache
    def _cache_get(self, key: tuple):
        entry = self._cache.get(key)
        if entry is None:
            return None
        self._cache.move_to_end(key)
        return entry[1]

    def _cache_put(self, key: tuple, payload: tuple, nbytes: int) -> None:
        if nbytes > self.cache_bytes:
            return
        old = self._cache.pop(key, None)
        if old is not None:
            self._cache_nbytes -= old[0]
        self._cache[key] = (nbytes, payload)
        self._cache_nbytes += nbytes
        while self._cache_nbytes > self.cache_bytes:
            _, (dropped, _) = self._cache.popitem(last=False)
            self._cache_nbytes -= dropped
            self.stats.cache_evictions += 1

    @staticmethod
    def _slack(pts: np.ndarray) -> float:
        """Absolute L2 error bound for the fp32 ||a||^2+||b||^2-2ab identity.

        The squared-distance error is dominated by cancellation at the
        squared-norm scale S: |err_sq| <= c*eps32*S with c a small constant
        times the reduction depth (the kernel tests bound the diagonal at
        32*eps*S). sqrt is monotone, so |err_dist| <= sqrt(err_sq); we take
        c = 64 + 4d for headroom across accumulation orders.
        """
        if pts.size == 0:
            return 0.0
        d = pts.shape[1]
        s_norm = float((pts.astype(np.float64) ** 2).sum(axis=1).max())
        return float(np.sqrt((64.0 + 4.0 * d) * _EPS32 * s_norm))

    def pairwise(self, a: np.ndarray, b: np.ndarray) -> np.ndarray:
        from repro.kernels import ops
        self.stats.dispatches += 1
        sq, _ = ops.pairwise_l2_join(np.asarray(a, np.float32),
                                     np.asarray(b, np.float32),
                                     bm=self.bm, bn=self.bn,
                                     interpret=self.interpret)
        return np.sqrt(np.asarray(sq, np.float64))

    def _round(self, n: int) -> int:
        q = self.quantum
        return max(q, ((n + q - 1) // q) * q)

    def _subset_rows(self, points: np.ndarray, ids: np.ndarray,
                     key: bytes | None) -> tuple[np.ndarray, float]:
        """fp32 rows + fp32 slack for one subset, through the LRU."""
        if key is not None:
            hit = self._cache_get(("subset", key))
            if hit is not None:
                self.stats.cache_hits += 1
                return hit
        rows = np.ascontiguousarray(points[ids], dtype=np.float32)
        self._note_cold_read(points, len(ids))
        payload = (rows, self._slack(rows))
        if key is not None:
            self.stats.cache_misses += 1
            self._cache_put(("subset", key), payload, rows.nbytes)
        return payload

    def _class_pad(self, n: int) -> int:
        """Size class for one subset: next power of two >= max(n, floor).
        Pow2 classes bound both pad waste (< 2x the valid points) and the
        number of compiled program shapes. On TPU the floor is the kernel
        tile ``bm`` (Mosaic pads every block to it anyway, so sub-tile
        classes would only add dispatches); the XLA lowering uses exact
        shapes, so small classes genuinely save compute there."""
        if self._min_class is None:
            import jax
            self._min_class = self.bm if jax.default_backend() == "tpu" \
                else max(self.quantum, 1)
        p = self._min_class
        while p < n:
            p <<= 1
        return p

    def _cost_model(self, d: int) -> DispatchCostModel:
        if self._model is None:
            self._model = calibrate_cost_model(
                d, bm=self.bm, bn=self.bn, interpret=self.interpret)
        return self._model

    def _prune_active(self, d: int) -> bool:
        if self.prune_tier == "on":
            return True
        if self.prune_tier == "off":
            return False
        # "auto": only where the coarse gemm is actually discounted. Off-TPU
        # the answer is a platform property, so skip the calibration probes.
        import jax
        if jax.default_backend() != "tpu":
            return False
        return self._cost_model(d).prune_profitable

    def _quantile_edges(self, sizes: np.ndarray) -> np.ndarray:
        """Data-driven size-class edges for one call's subset lengths.

        Lengths are rounded up to the quantum (shape reuse) and floored at
        the platform min class, then segmented by the waste-minimizing DP
        (:func:`_dp_segment`) capped at ``n_classes`` edges — or the pow2
        class count if that is larger, which makes the pow2 segmentation a
        *feasible* DP choice and hence quantile padded cells <= pow2 padded
        cells on every call (the guard below enforces it exactly). Edges are
        cached per sorted-length signature; the cache lives inside one
        corpus generation (purged with the LRU)."""
        self._class_pad(1)                      # resolve _min_class
        q = self.quantum
        vals = np.maximum(((np.maximum(sizes, 1) + q - 1) // q) * q,
                          self._min_class).astype(np.int64)
        svals = np.sort(vals)
        sig = svals.tobytes()
        hit = self._edge_cache.get(sig)
        if hit is not None:
            return hit
        distinct, counts = np.unique(svals, return_counts=True)
        pow2_edges = np.unique([self._class_pad(int(v)) for v in distinct])
        cap = max(self.n_classes, len(pow2_edges))
        edges = _dp_segment(distinct, counts, cap)

        def total_cells(e):
            cls = e[np.searchsorted(e, distinct)]
            return int((counts * cls.astype(np.int64) ** 2).sum())

        if total_cells(edges) > total_cells(pow2_edges):
            edges = pow2_edges
        if len(self._edge_cache) > 128:
            self._edge_cache.clear()
        self._edge_cache[sig] = edges
        return edges

    def _purge_cache(self, generation_bump: bool) -> None:
        if self._cache:
            self.stats.generation_purges += int(generation_bump)
        self._cache.clear()
        self._cache_nbytes = 0
        self._edge_cache.clear()

    def self_join_blocks(self, points: np.ndarray,
                         id_lists: Sequence[np.ndarray],
                         radii: Sequence[float],
                         keys: Sequence[bytes] | None = None,
                         generation: int | None = None,
                         eligible: np.ndarray | None = None
                         ) -> list[DistanceBlock]:
        if not len(id_lists):
            return []
        if keys is None:
            keys = [None] * len(id_lists)
        # Cache entries are keyed on subset-id bytes, which only identify
        # points *within one corpus generation*. A generation-aware caller
        # (the streaming engine) keeps entries live across absorbs — the
        # merged points array is re-realized per batch, but ids are
        # append-only and rows immutable until a compaction bumps the token.
        # Legacy callers (no token) fall back to array-identity invalidation.
        if generation is not None:
            if generation != self._generation:
                self._purge_cache(generation_bump=self._generation is not None)
                self._generation = generation
            self._corpus = points
        elif self._corpus is not points:
            self._purge_cache(generation_bump=False)
            self._generation = None
            self._corpus = points
        # Size-binned dispatch: padding every subset of a scale to the batch
        # max wastes quadratically (a single near-corpus subset makes every
        # tiny one pay its P^2). Size-class edges come from the bin strategy:
        # "quantile" fits them to this call's length distribution (DP over
        # the histogram, <= n_classes edges, never more padded cells than
        # pow2), "pow2" keeps the classic powers of two. Within a class,
        # chunk so one dispatch's (S, P, P) on-device join block stays under
        # the memory budget, then route each chunk: bins whose estimated
        # device cost exceeds the measured host cost go to the exact numpy
        # path (route="auto"), the rest dispatch on device. Result order
        # matches the task order.
        blocks: list[DistanceBlock | None] = [None] * len(id_lists)
        finite: list[int] = []
        for i, ids in enumerate(id_lists):
            if not np.isfinite(radii[i]):
                # An infinite pruning radius joins every pair by construction
                # (fresh queues at scale 0): the mask is all-ones, so skip the
                # device round-trip and synthesize the trivial block. The
                # enumeration stage prunes with its live r_k instead. Under a
                # filter the all-ones adjacency covers eligible pairs only —
                # same contract as the device fold.
                n = len(ids)
                n_elig = None if eligible is None else int(eligible[ids].sum())
                pairs = n * n if n_elig is None else n_elig * n_elig
                self.stats.subsets += 1
                self.stats.points_packed += n
                self.stats.join_pairs += pairs
                blocks[i] = DistanceBlock(n=n, slack=0.0, rescore=True,
                                          join_count=pairs, n_eligible=n_elig)
                continue
            finite.append(i)
        if not finite:
            return blocks
        lens = np.fromiter((len(id_lists[i]) for i in finite), np.int64,
                           count=len(finite))
        # Eligible-dense packing: when a filter keeps only a thin slice of
        # each subset, folding an eligibility mask into a full-width tile
        # wastes ~1/selectivity^2 of the join cells. Below the threshold the
        # tiles pack eligible rows densely instead — sized by eligible
        # counts, uncached (the pack is filter-dependent), blocks carrying
        # the packed row map for the enumeration stage.
        elig_dense = False
        if eligible is not None and len(lens):
            el_counts = np.fromiter(
                (int(eligible[id_lists[i]].sum()) for i in finite), np.int64,
                count=len(finite))
            tot = int(lens.sum())
            elig_dense = tot > 0 and \
                int(el_counts.sum()) < self.elig_pack_threshold * tot
        sizes = el_counts if elig_dense else lens
        if self.bin_strategy == "quantile":
            edges = self._quantile_edges(sizes)
            cls = edges[np.searchsorted(edges, np.maximum(sizes, 1))]
        else:
            cls = np.array([self._class_pad(int(max(s, 1))) for s in sizes])
        classes: dict[int, list[int]] = {}
        for pos, i in enumerate(finite):
            classes.setdefault(int(cls[pos]), []).append(pos)
        model = None
        if self.route == "auto":
            model = self._cost_model(points.shape[1])
        budget = max(1, self.max_block_bytes // 4)
        for p_pad, poss in sorted(classes.items()):
            # Budget the *padded* subset count: _dispatch rounds it up to
            # quantum for shape reuse, so floor max_s to a quantum multiple
            # (falling back to unrounded single-subset dispatches when even
            # one quantum of this class would blow the budget).
            max_s = budget // (p_pad * p_pad)
            if max_s >= self.quantum:
                max_s = (max_s // self.quantum) * self.quantum
            max_s = max(1, max_s)
            for c0 in range(0, len(poss), max_s):
                chunk = poss[c0:c0 + max_s]
                idxs = [finite[p] for p in chunk]
                if model is not None:
                    padded_cells = self._round(len(chunk)) * p_pad * p_pad
                    valid_cells = int((sizes[chunk] ** 2).sum())
                    if model.host_cost(len(chunk), valid_cells) \
                            < model.device_cost(padded_cells, valid_cells,
                                                len(chunk)):
                        out = self._host_dispatch(
                            points, [id_lists[i] for i in idxs],
                            [radii[i] for i in idxs], eligible,
                            keys=[keys[i] for i in idxs])
                        for i, b in zip(idxs, out):
                            blocks[i] = b
                        continue
                out = self._dispatch(points, [id_lists[i] for i in idxs],
                                     [radii[i] for i in idxs],
                                     [keys[i] for i in idxs], p_pad,
                                     eligible, elig_dense=elig_dense)
                for i, b in zip(idxs, out):
                    blocks[i] = b
        return blocks

    def _host_dispatch(self, points: np.ndarray,
                       id_lists: Sequence[np.ndarray],
                       radii: Sequence[float],
                       eligible: np.ndarray | None,
                       keys: Sequence[bytes | None] | None = None
                       ) -> list[DistanceBlock]:
        """Cost-model host route: one bin served by the exact float64 path.

        Blocks carry dense float64 distances (no slack, no rescore) computed
        with the *same* difference-based arithmetic the enumeration stage's
        float64 settlement uses (``sqrt`` of ``_sq_dists_f64``) — not the
        norms identity of :class:`NumpyBackend`, which rounds differently at
        the last ulp. That keeps the routing decision invisible in the
        output: a bin served here yields bitwise the same diameters the
        device route's rescore would have produced, so the cost model can
        flip a bin between routes without changing a single result. The
        whole bin counts as one dispatch — the same accounting unit as the
        device route it replaces.

        Distance tables are LRU-cached per subset key (generation-scoped,
        like the device tiles): distances are radius- and filter-independent,
        so a steady-state host-routed bin recomputes nothing — only the
        threshold count per call. This is the host route's analogue of the
        device tile cache, and what makes auto routing faster than a pure
        :class:`NumpyBackend` pass at the same results."""
        if keys is None:
            keys = [None] * len(id_lists)
        out = []
        with span("nks.backend.host", self.stats, ("t_host_s", "t_dispatch_s"),
                  subsets=len(id_lists)):
            for ids, r, key in zip(id_lists, radii, keys):
                ck = None if key is None else ("hostdist", key)
                dist = self._cache_get(ck) if ck is not None else None
                if dist is None:
                    pts = points[ids]
                    self._note_cold_read(points, len(ids))
                    dist = np.sqrt(_sq_dists_f64(np.asarray(pts, np.float64)))
                    if ck is not None:
                        self.stats.cache_misses += 1
                        self._cache_put(ck, dist, dist.nbytes)
                else:
                    self.stats.cache_hits += 1
                n_elig = None
                if eligible is None:
                    count = int((dist <= r).sum())
                else:
                    el = eligible[ids]
                    n_elig = int(el.sum())
                    count = int(((dist <= r) & el[:, None]
                                 & el[None, :]).sum())
                self.stats.subsets += 1
                self.stats.points_packed += len(ids)
                self.stats.join_pairs += count
                out.append(DistanceBlock(n=len(ids), dist=dist, slack=0.0,
                                         rescore=False, join_count=count,
                                         n_eligible=n_elig))
        self.stats.dispatches += 1
        self.stats.host_routed_dispatches += 1
        self.stats.host_routed_subsets += len(id_lists)
        return out

    def _dispatch(self, points: np.ndarray, id_lists: Sequence[np.ndarray],
                  radii: Sequence[float], keys: Sequence[bytes | None],
                  p_pad: int,
                  eligible: np.ndarray | None = None, *,
                  elig_dense: bool = False) -> list[DistanceBlock]:
        from repro.kernels import ops
        import jax.numpy as jnp

        with span("nks.backend.pack", self.stats, "t_pack_s",
                  subsets=len(id_lists), p=p_pad):
            n_subsets = len(id_lists)
            # Eligible-dense packing: tiles hold only the eligible rows; the
            # block carries the packed row map. The pack is filter-dependent,
            # so both the subset-row cache and the tile cache are bypassed.
            if elig_dense:
                row_lists = [np.flatnonzero(eligible[ids]) for ids in id_lists]
                lengths = np.fromiter((len(rw) for rw in row_lists), np.int32,
                                      count=n_subsets)
            else:
                row_lists = None
                lengths = np.fromiter((len(ids) for ids in id_lists), np.int32,
                                      count=n_subsets)
            # Route over the device plane when the bin packs at least one
            # subset per shard; thinner bins (the remainder after chunking)
            # stay on a single device — sharding them would only ship empty
            # slabs.
            plane = self.plane
            sharded = plane is not None and n_subsets >= plane.n_shards
            s_pad = self._round(n_subsets)
            if sharded:
                s_pad = plane.shard_pad(s_pad)
            budget_cells = max(1, self.max_block_bytes // 4)
            if s_pad * p_pad * p_pad > budget_cells:
                # Shape-reuse rounding must not blow the budget. Sharding needs
                # a shard multiple; if even the minimal one is over budget, the
                # bin drops to the single-device route at its exact size.
                s_pad = plane.shard_pad(n_subsets) if sharded else n_subsets
                if sharded and s_pad * p_pad * p_pad > budget_cells:
                    sharded = False
                    s_pad = n_subsets

            lens_pad = np.zeros(s_pad, np.int32)
            lens_pad[:n_subsets] = lengths
            # Shard placement: deal subsets to tile slots in snake order of
            # packed size so each shard's contiguous slab carries level work
            # (``device_plane.balance_order``). The permutation is a pure
            # function of the packed lengths — radius-independent, so cached
            # tiles (which are reused across radii) stay valid — and
            # slot->shard is what ``shard_cells`` reports, so
            # ``shard_utilisation`` reads the levelled layout directly.
            # ``inv[i]`` is subset i's tile slot.
            inv = None
            if sharded and self.placement == "sorted":
                from repro.core.device_plane import balance_order
                perm = balance_order(lens_pad, plane.n_shards)
                inv = np.empty(s_pad, np.int64)
                inv[perm] = np.arange(s_pad)

            def slot(i: int) -> int:
                return i if inv is None else int(inv[i])

            def to_slots(arr):
                if inv is None:
                    return arr
                out = np.zeros_like(arr)
                out[inv] = arr
                return out

            lens_ship = to_slots(lens_pad)
            tile_key = None
            if not elig_dense and not any(k is None for k in keys):
                tile_key = ("tile", tuple(keys), s_pad, p_pad, sharded,
                            self.placement if sharded else "none")
            cached_tile = self._cache_get(tile_key) if tile_key else None
            if cached_tile is not None:
                # Packed tiles already live on the device: skip gather,
                # packing, and H2D entirely; only the radii change between
                # calls. Slacks ride in the payload, so the hit path touches no
                # per-subset state at all. Hit/miss counters are per *subset*
                # (a tile hit serves every subset it packs), so cache_hit_rate
                # reads as the fraction of subset packs avoided.
                self.stats.cache_hits += n_subsets
                x_dev, lens_dev, slacks = cached_tile
                # Keep the per-subset row entries warm too: a long streak of
                # tile hits must not LRU-starve them, or a later re-binning
                # (chunk boundaries shift when radii tighten) re-packs rows the
                # cache nominally still held. Recency touch only — the hit
                # counter above already accounts for these subsets.
                for key in keys:
                    if ("subset", key) in self._cache:
                        self._cache.move_to_end(("subset", key))
            else:
                slacks = np.zeros(n_subsets, np.float64)
                d = points.shape[1]
                x = np.zeros((s_pad, p_pad, d), np.float32)
                for i, (ids, key) in enumerate(zip(id_lists, keys)):
                    if elig_dense:
                        rows = np.ascontiguousarray(
                            points[ids[row_lists[i]]], dtype=np.float32)
                        self._note_cold_read(points, len(row_lists[i]))
                        slacks[i] = self._slack(rows)
                    else:
                        rows, slacks[i] = self._subset_rows(points, ids, key)
                    x[slot(i), : lengths[i]] = rows
                if sharded:
                    # Commit the tile scattered over the mesh's data axis so
                    # the sharded dispatch starts from the right placement (a
                    # cached sharded tile stays resident exactly where it will
                    # be used).
                    x_dev, lens_dev = plane.put_sharded(x, lens_ship)
                else:
                    x_dev = jnp.asarray(x)
                    lens_dev = jnp.asarray(lens_ship)
                if tile_key is not None:
                    self._cache_put(tile_key, (x_dev, lens_dev, slacks),
                                    x.nbytes + slacks.nbytes)

            # Pruning radius r + slack, rounded *up* to fp32 so the device
            # comparison can never be tighter than the published slack
            # contract. ``r_orig`` is indexed by subset, the shipped vectors by
            # tile slot.
            r_orig = np.zeros(s_pad, np.float32)
            r_mask = np.asarray(radii, np.float64) + slacks
            # nextafter(f32max) saturates to inf
            with np.errstate(over="ignore"):
                r_orig[:n_subsets] = np.nextafter(r_mask.astype(np.float32),
                                                  np.float32(np.inf))
            r_orig[:n_subsets][~np.isfinite(r_mask)] = np.float32(np.inf)
            r = to_slots(r_orig)
            # Filtered dispatch (fold mode): pack each subset's eligibility
            # bits into the mask word layout. These words are the *only* extra
            # traffic a filter adds — the tile (cached or not) is
            # filter-independent, and the readback stays the same packed mask.
            # Eligible-dense tiles skip the fold (every packed row is eligible
            # by construction).
            elig_words = el_counts = None
            if eligible is not None and not elig_dense:
                el = np.zeros((s_pad, p_pad), dtype=bool)
                el_counts = np.zeros(n_subsets, np.int64)
                for i, ids in enumerate(id_lists):
                    eli = eligible[ids]
                    el[slot(i), : len(ids)] = eli
                    el_counts[i] = int(eli.sum())
                elig_words = pack_join_mask(el)    # (s_pad, ceil(p_pad/32))
        self.stats.h2d_bytes += r.nbytes + \
            (elig_words.nbytes if elig_words is not None else 0) + \
            (0 if cached_tile is not None
             else x.nbytes + lens_ship.nbytes)

        # n_live: the diagonal bound the enumeration stage's empty-join test
        # uses — eligible counts under a fold, packed lengths otherwise.
        n_live = lengths.astype(np.int64) if el_counts is None else el_counts
        # ---- tier 0: coarse mixed-precision prune (counts only) ----
        pruned = None
        cc = None
        if self._prune_active(points.shape[1]):
            # Coarse radius: the fp32 pruning radius widened by the coarse
            # tier's own error budget — a second fp32-identity slack (the
            # coarse pass accumulates in fp32 too) plus the bf16 coordinate
            # rounding (2 * eps16 * max-norm, eps16 = 2^-8; the max norm is
            # recovered from the cached slack, sqrt(S_norm) = slack /
            # sqrt((64+4d)*eps32)), all scaled by (1 + prune_eps) headroom.
            # Any pair the fp32 tier could join is therefore inside the
            # coarse radius: coarse count <= diagonal bound proves the fp32
            # join empty, and the singleton path the enumeration stage takes
            # is decided by that bound alone — results stay bit-identical
            # whether or not the fp32 tier ran. int8 adds its quantization
            # slack inside the op itself.
            d = points.shape[1]
            eps16 = 2.0 ** -8
            rtnorm = slacks / np.sqrt((64.0 + 4.0 * d) * _EPS32)
            r_c = (r_mask + slacks + 2.0 * eps16 * rtnorm) \
                * (1.0 + self.prune_eps)
            rc_orig = np.zeros(s_pad, np.float32)
            with np.errstate(over="ignore"):
                rc_orig[:n_subsets] = np.nextafter(
                    r_c.astype(np.float32), np.float32(np.inf))
            rc = to_slots(rc_orig)
            with span("nks.backend.prune", self.stats,
                      ("t_prune_s", "t_dispatch_s"), s=s_pad, p=p_pad):
                if sharded:
                    cnt_c = plane.join_batched_counts(
                        x_dev, lens_dev, rc, elig_words,
                        dtype=self.prune_dtype, bm=self.bm, bn=self.bn,
                        interpret=self.interpret)
                else:
                    cnt_c = ops.pairwise_l2_join_batched_counts(
                        x_dev, lens_dev, rc, elig_words,
                        dtype=self.prune_dtype, bm=self.bm, bn=self.bn,
                        interpret=self.interpret)
                counts_c = np.asarray(cnt_c)
            self.stats.prune_tier_dispatches += 1
            self.stats.h2d_bytes += rc.nbytes
            self.stats.d2h_bytes += counts_c.nbytes
            cc = counts_c[:n_subsets] if inv is None \
                else counts_c[inv[:n_subsets]]
            pruned = cc <= n_live
            self.stats.cells_pruned += int(pruned.sum()) * p_pad * p_pad

        # ---- tier 1: fp32 masked join on surviving subsets ----
        mask = counts = None
        sub_slots = None
        if pruned is None or not pruned.all():
            fields = ("t_dispatch_s", "t_collective_s") if sharded \
                else "t_dispatch_s"
            with span("nks.backend.dispatch", self.stats, fields,
                      s=s_pad, p=p_pad):
                if pruned is not None and pruned.any():
                    # Survivor sub-dispatch: gather surviving slots out of the
                    # committed tile on device (no re-pack, no H2D of rows).
                    surv = np.flatnonzero(~pruned)
                    slots_surv = surv if inv is None else inv[surv]
                    n_surv = len(surv)
                    s_sub = self._round(n_surv)
                    sub_sharded = sharded and n_surv >= plane.n_shards
                    if sub_sharded:
                        s_sub = plane.shard_pad(s_sub)
                    idx_pad = np.zeros(s_sub, np.int64)
                    idx_pad[:n_surv] = slots_surv
                    lens_sub = np.zeros(s_sub, np.int32)
                    lens_sub[:n_surv] = lengths[surv]
                    r_sub = np.zeros(s_sub, np.float32)
                    r_sub[:n_surv] = r_orig[surv]
                    elig_sub = None
                    if elig_words is not None:
                        elig_sub = np.zeros((s_sub, elig_words.shape[1]),
                                            np.uint32)
                        elig_sub[:n_surv] = elig_words[slots_surv]
                    x_sub = jnp.take(x_dev, jnp.asarray(idx_pad), axis=0)
                    if sub_sharded:
                        m, c = plane.join_batched_masked(
                            x_sub, lens_sub, r_sub, elig_sub, bm=self.bm,
                            bn=self.bn, interpret=self.interpret)
                    else:
                        m, c = ops.pairwise_l2_join_batched_masked(
                            x_sub, lens_sub, r_sub, elig_sub, bm=self.bm,
                            bn=self.bn, interpret=self.interpret)
                    sub_slots = {int(i): j for j, i in enumerate(surv)}
                else:
                    if sharded:
                        m, c = plane.join_batched_masked(
                            x_dev, lens_dev, r, elig_words, bm=self.bm,
                            bn=self.bn, interpret=self.interpret)
                    else:
                        m, c = ops.pairwise_l2_join_batched_masked(
                            x_dev, lens_dev, r, elig_words, bm=self.bm,
                            bn=self.bn, interpret=self.interpret)
                mask = np.asarray(m)
                counts = np.asarray(c)
            self.stats.join_dispatches += 1
            self.stats.d2h_bytes += mask.nbytes + counts.nbytes

        self.stats.dispatches += 1
        self.stats.subsets += n_subsets
        self.stats.points_packed += int(lengths.sum())
        self.stats.points_padded += s_pad * p_pad - int(lengths.sum())
        bp = self.stats.bin_points.get(p_pad, (0, 0))
        self.stats.bin_points[p_pad] = (
            bp[0] + int(lengths.sum()),
            bp[1] + s_pad * p_pad - int(lengths.sum()))
        if sharded:
            # Per-shard accounting: every device participated; utilisation is
            # valid vs total join-block cells on each shard's slab (computed
            # on the shipped, i.e. placement-permuted, lengths).
            self.stats.sharded_dispatches += 1
            n_sh = plane.n_shards
            self.stats.ensure_shards(n_sh)
            valid, total = plane.shard_cells(lens_ship, p_pad)
            for i in range(n_sh):
                self.stats.shard_dispatches[i] += 1
                self.stats.shard_valid_cells[i] += valid[i]
                self.stats.shard_total_cells[i] += total[i]
        else:
            # Single-device dispatch lands on the default device (shard 0 of
            # the plane when one is attached).
            self.stats.ensure_shards(max(1, plane.n_shards if plane else 1))
            self.stats.shard_dispatches[0] += 1
            self.stats.shard_valid_cells[0] += int(
                (lengths.astype(np.int64) ** 2).sum())
            self.stats.shard_total_cells[0] += s_pad * p_pad * p_pad

        out = []
        for i, ids in enumerate(id_lists):
            n = len(ids)
            n_elig = None
            if elig_dense:
                n_elig = int(lengths[i])
            elif el_counts is not None:
                n_elig = int(el_counts[i])
            rows_i = None
            if elig_dense:
                rows_i = row_lists[i]
            if pruned is not None and pruned[i]:
                # Coarse count at or below the diagonal bound: the fp32 join
                # is provably empty off-diagonal, emit the mask-free block
                # (the enumeration stage's singleton path never unpacks it).
                self.stats.join_pairs += int(cc[i])
                out.append(DistanceBlock(
                    n=n, slack=float(slacks[i]), rescore=True,
                    join_count=int(cc[i]), mask=None, n_eligible=n_elig,
                    rows=rows_i))
                continue
            row = i if sub_slots is None else sub_slots[i]
            row = slot(row) if sub_slots is None else row
            npk = int(lengths[i])
            words = (npk + 31) // 32
            self.stats.join_pairs += int(counts[row])
            out.append(DistanceBlock(
                n=n, mask=mask[row, :npk, :words], slack=float(slacks[i]),
                rescore=True, join_count=int(counts[row]),
                n_eligible=n_elig, rows=rows_i))
        return out


def get_backend(spec: str | DistanceBackend, **kw) -> DistanceBackend:
    """Resolve a backend name ("numpy" | "pallas") or pass an instance through."""
    if isinstance(spec, DistanceBackend):
        return spec
    if spec == "numpy":
        return NumpyBackend()
    if spec == "pallas":
        return PallasBackend(**kw)
    raise ValueError(f"unknown distance backend: {spec!r}")
