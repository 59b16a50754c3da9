"""Batched NKS serving engine.

Production shape: a frontend batches keyword-set queries; the engine answers
from a ProMiSH index over an embedding corpus. Three quality/latency tiers:

  * ``exact``   — ProMiSH-E (100% accuracy, Lemma-2 guarantee);
  * ``approx``  — ProMiSH-A (the paper's fast tier);
  * ``device``  — the anchor-star device kernel (repro.core.distributed),
                  batched and shardable over the mesh; used when the corpus
                  is sharded across chips.

All three tiers flow through one device plane (``core.device_plane``) when
the engine is built with ``mesh=...``: the exact/approx pipeline routes its
size-binned join dispatches through the plane's shard_map (subsets sharded
on S over the ``data`` axis), and the device tier dispatches the anchor-star
shard_map program on the same mesh. Without a mesh everything runs
single-device — multi-device execution is a property of the backend, not a
separate code path.

``query_batch`` runs the exact/approx tiers as a **staged batched pipeline**
on the plan/backend layers: per scale, bucket selection for the whole batch
is amortised through ``core.plan.plan_scale`` (shared per-query Algorithm-2
dedup), surviving subsets are packed into a handful of size-binned fused
Pallas threshold-join dispatches (``backend="pallas"``, each emitting the
packed join bitmask; subsets whose pruning radius is still infinite skip the
device entirely) or looped through float64 numpy (``backend="numpy"``), and
the host enumeration stage consumes the join blocks through the vectorized
frontier of ``subset_search.enumerate_with_block``. Per-scale device traffic,
phase timings, and packed-subset cache hits are recorded in
:class:`PipelineStats` (``engine.last_batch_stats``).

The corpus can be ingested directly (points + keywords) or produced by any
assigned architecture through ``ingest_embeddings`` (models.api.embed ->
ProMiSH points — the paper's Flickr use case with learned features).

**Streaming ingest** (``insert`` / ``delete`` / ``compact``): the engine
serves while the corpus changes. Inserts land in an append-only delta
(:class:`~repro.core.types.StreamingCorpus` +
:class:`~repro.core.index.IndexDelta` per index flavour) binned with the
bulk index's hash geometry; deletes are tombstones; a size/ratio-triggered
compaction (``compact_ratio``/``compact_min``) folds everything into a fresh
immutable index, swapped atomically, bumping ``corpus_generation`` — the
token the backend LRU caches are scoped to (absorbs keep caches warm, only
compaction invalidates). Consistency model: a query issued after an ingest
call returns sees all of that call's batch and every earlier one — never a
partial batch; results carry *external* ids that stay stable across
compactions. ``PipelineStats`` records generation/delta/tombstone state per
batch, ``engine.ingest`` the lifetime counters.
"""
from __future__ import annotations

import contextlib
import dataclasses
import itertools
import os
import time
from typing import Sequence

import numpy as np

from repro.core import brute_force, plan, promish_a, promish_e
from repro.core import store as storemod
from repro.core.backend import DistanceBackend, get_backend
from repro.core.filters import Filter
from repro.core.index import IndexDelta, PromishIndex, absorb_into, build_index
from repro.core.semantics import QuerySemantics
from repro.core.subset_search import enumerate_with_block, local_groups
from repro.core.types import (Candidate, KeywordDataset, StreamingCorpus,
                              TopK, make_dataset)
from repro.serve import wal as walmod
from repro.serve.faults import NO_FAULTS, FaultPlan
from repro.utils.timing import span

# Process-global corpus-generation tokens: every (engine, compaction) pair
# gets a unique token, so a DistanceBackend shared across engines can never
# serve one engine's packed rows to another (generation numbers restart at 0
# per engine; tokens do not).
_CORPUS_TOKENS = itertools.count(1)

# repro.core.distributed / device_plane import the jax device stack; they are
# loaded lazily so the numpy control plane stays importable everywhere and
# XLA_FLAGS can still be set after importing this module.


@dataclasses.dataclass
class QueryResult:
    query: list[int]
    candidates: list[Candidate]
    latency_s: float
    tier: str


@dataclasses.dataclass
class ScaleStats:
    """One pipeline stage = one scale of the multi-scale index."""

    scale: int
    active_queries: int = 0
    buckets_selected: int = 0
    duplicate_subsets: int = 0
    filtered_subsets: int = 0    # predicate-pruned before pack/dispatch
    tasks_planned: int = 0
    tasks_searched: int = 0      # tasks with all keyword groups non-empty
    dispatches: int = 0          # device/loop distance dispatches this scale
    join_pairs: int = 0
    queries_finished: int = 0
    # Out-of-core pruning (zone maps / bounding radii; zero without synopses):
    buckets_pruned_zonemap: int = 0
    buckets_pruned_radius: int = 0


@dataclasses.dataclass
class PipelineStats:
    """End-to-end accounting for one ``query_batch`` call.

    The phase timers split the batch wall time by pipeline stage:
    ``plan`` (bucket selection + keyword grouping),
    ``pack`` (host gather/tile packing, backend-side), ``dispatch`` (device
    dispatch + D2H readback), ``enumerate`` (host Alg. 4 over the join
    masks). On the device tier ``dispatch`` is the transfer in and the
    program call only: the copy back of the k sets and their diameters,
    one blocking transfer of both outputs, is ``readback``
    (``t_readback_s``), and their float64 rescoring, ranking
    and id mapping is ``rescore`` (``t_rescore_s``). Each timer is a
    ``nks.*`` span (``repro.utils.timing.span``), so the same stages show
    in a profiler trace. Cache counters mirror the backend's packed-subset
    LRU.
    """

    batch_size: int
    tier: str
    backend: str
    scales: list[ScaleStats] = dataclasses.field(default_factory=list)
    fallback_queries: int = 0
    fallback_dispatches: int = 0
    candidates_explored: int = 0
    t_plan_s: float = 0.0
    t_pack_s: float = 0.0
    t_dispatch_s: float = 0.0
    t_readback_s: float = 0.0
    t_enumerate_s: float = 0.0
    cache_hits: int = 0
    cache_misses: int = 0
    # Device-plane accounting (empty / zero when no mesh is attached):
    # ``shard_dispatches[i]`` counts dispatches device i participated in
    # (single-device dispatches land on shard 0), the cell counters measure
    # per-shard join-block utilisation (valid vs padded cells on each
    # shard's slab), and ``t_collective_s`` is the wall time spent inside
    # shard_map dispatches (device compute + cross-device gather-back).
    sharded_dispatches: int = 0
    t_collective_s: float = 0.0
    shard_dispatches: list[int] = dataclasses.field(default_factory=list)
    shard_valid_cells: list[int] = dataclasses.field(default_factory=list)
    shard_total_cells: list[int] = dataclasses.field(default_factory=list)
    # Streaming-ingest accounting: the corpus generation the batch ran
    # against (bumped by compaction only), the delta/tombstone sizes at
    # dispatch time, and the engine's lifetime compaction count.
    corpus_generation: int = 0
    delta_points: int = 0
    tombstones: int = 0
    compactions: int = 0
    # Filtered-NKS accounting: eligible_points/selectivity describe the
    # batch's predicate mask (None on an unfiltered batch); filtered_subsets
    # counts planned subsets pruned because no member satisfied the
    # predicate; h2d/d2h_bytes are the backend's transfer deltas for this
    # batch — the "no new D2H" contract of the eligibility fold is asserted
    # on d2h_bytes.
    eligible_points: int | None = None
    filter_selectivity: float | None = None
    filtered_subsets: int = 0
    h2d_bytes: int = 0
    d2h_bytes: int = 0
    # Cascade accounting (ISSUE 6): the three-tier distance cascade splits
    # device time into the coarse bf16 count pass (``t_prune_s``), the fp32
    # masked join (the remainder of ``t_dispatch_s``), and the host float64
    # settlement of surviving tuples (``t_rescore_s``, measured inside the
    # enumeration stage; on the device tier, the span ``nks.engine.rescore``
    # of the k selected sets). ``cells_pruned`` counts fp32 join cells the
    # coarse tier proved empty and never dispatched. Cost-model routing lands
    # in ``host_routed_dispatches`` (bins the crossover model sent to the f64
    # host loop instead of the device). ``bin_occupancy`` maps each size class
    # (padded width) to [valid, padded] packed point counts, and
    # ``bin_strategy`` names the binning that produced it.
    prune_tier_dispatches: int = 0
    cells_pruned: int = 0
    t_prune_s: float = 0.0
    t_rescore_s: float = 0.0
    t_host_s: float = 0.0
    host_routed_dispatches: int = 0
    host_routed_subsets: int = 0
    bin_occupancy: dict = dataclasses.field(default_factory=dict)
    bin_strategy: str = ""
    # Out-of-core tiering (ISSUE 8): buckets the planner skipped because the
    # filter was provably disjoint from their zone maps, subsets dispatched
    # through the all-ones fast path because their bucket's diameter bound
    # already beat the live r_k, and bytes gathered from a memory-mapped
    # (cold-tier) corpus. All zero on a resident engine without synopses.
    buckets_pruned_zonemap: int = 0
    buckets_pruned_radius: int = 0
    cold_bytes_read: int = 0
    # Flexible semantics (ISSUE 9): planned subqueries after m-of-k
    # expansion (== batch_size on a classic batch — one subquery per query).
    subqueries: int = 0

    @property
    def dispatches_per_scale(self) -> list[int]:
        return [s.dispatches for s in self.scales]

    @property
    def total_dispatches(self) -> int:
        return sum(s.dispatches for s in self.scales) + self.fallback_dispatches

    @property
    def shard_utilisation(self) -> list[float]:
        """Valid-cell fraction of each shard's packed join blocks (the
        complement is pad waste shipped to that device)."""
        return [round(v / t, 4) if t else 0.0
                for v, t in zip(self.shard_valid_cells, self.shard_total_cells)]

    @property
    def phases(self) -> dict:
        """JSON-ready phase breakdown for the benchmark trajectory."""
        probed = self.cache_hits + self.cache_misses
        return {
            "plan_s": round(self.t_plan_s, 6),
            "pack_s": round(self.t_pack_s, 6),
            "dispatch_s": round(self.t_dispatch_s, 6),
            "readback_s": round(self.t_readback_s, 6),
            "enumerate_s": round(self.t_enumerate_s, 6),
            "rescore_s": round(self.t_rescore_s, 6),
            "collective_s": round(self.t_collective_s, 6),
            "cache_hit_rate": round(self.cache_hits / probed, 4) if probed else None,
        }

    @property
    def padded_cell_ratio(self) -> float | None:
        """Fraction of dispatched join-block cells that were padding (the
        quantity size-binning exists to minimise); None with no dispatches."""
        total = sum(self.shard_total_cells)
        if not total:
            return None
        return round(1.0 - sum(self.shard_valid_cells) / total, 6)

    @property
    def cascade(self) -> dict:
        """JSON-ready per-tier cascade summary for the benchmark trajectory."""
        return {
            "prune_tier_dispatches": self.prune_tier_dispatches,
            "cells_pruned": self.cells_pruned,
            "prune_s": round(self.t_prune_s, 6),
            "join_s": round(max(self.t_dispatch_s - self.t_prune_s
                                - self.t_host_s, 0.0), 6),
            "rescore_s": round(self.t_rescore_s, 6),
            "host_routed_dispatches": self.host_routed_dispatches,
            "host_routed_subsets": self.host_routed_subsets,
            "host_s": round(self.t_host_s, 6),
        }

    @property
    def binning(self) -> dict:
        """JSON-ready size-class occupancy for the benchmark trajectory."""
        return {
            "strategy": self.bin_strategy,
            "padded_cell_ratio": self.padded_cell_ratio,
            "bins": {str(k): {"points": v[0], "padded": v[1]}
                     for k, v in sorted(self.bin_occupancy.items())},
        }

    @property
    def sharding(self) -> dict:
        """JSON-ready device-plane summary for the benchmark trajectory."""
        return {
            "sharded_dispatches": self.sharded_dispatches,
            "shard_dispatches": list(self.shard_dispatches),
            "shard_utilisation": self.shard_utilisation,
            "collective_s": round(self.t_collective_s, 6),
        }

    @property
    def ingest(self) -> dict:
        """JSON-ready streaming-ingest summary for the benchmark trajectory."""
        return {
            "generation": self.corpus_generation,
            "delta_points": self.delta_points,
            "tombstones": self.tombstones,
            "compactions": self.compactions,
        }

    @property
    def filtering(self) -> dict:
        """JSON-ready filtered-NKS summary for the benchmark trajectory."""
        return {
            "eligible_points": self.eligible_points,
            "selectivity": self.filter_selectivity,
            "filtered_subsets": self.filtered_subsets,
            "h2d_bytes": self.h2d_bytes,
            "d2h_bytes": self.d2h_bytes,
        }

    @property
    def tiering(self) -> dict:
        """JSON-ready out-of-core tiering summary for the benchmark
        trajectory."""
        return {
            "buckets_pruned_zonemap": self.buckets_pruned_zonemap,
            "buckets_pruned_radius": self.buckets_pruned_radius,
            "cold_bytes_read": self.cold_bytes_read,
        }


@dataclasses.dataclass
class IngestStats:
    """Lifetime streaming counters for one engine (``engine.ingest``)."""

    inserts: int = 0            # insert calls absorbed
    points_inserted: int = 0
    deletes: int = 0            # delete calls absorbed
    points_deleted: int = 0
    compactions: int = 0
    generation: int = 0         # == engine.corpus_generation
    wal_appends: int = 0        # ops made durable before their ack
    replayed_ops: int = 0       # ops re-applied by the last recover()
    snapshots: int = 0          # log-rolling snapshots taken

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


class StaleCompactionError(RuntimeError):
    """A prepared compaction no longer matches the live streaming state —
    an ingest op slipped in between prepare and commit. The runtime prevents
    this by deferring ingest while a rebuild is in flight; hitting it means
    the caller broke that protocol, so the commit refuses rather than swap
    in a bulk that silently drops the interleaved ops."""


@dataclasses.dataclass
class PreparedCompaction:
    """The O(N) half of a compaction, computed off-thread: the folded bulk
    dataset, freshly built indices, and the external-id remap. ``version``
    pins the streaming state it was prepared against; commit re-checks it."""

    version: tuple[int, int]            # (corpus rows, tombstones) at prepare
    bulk: KeywordDataset
    index_e: PromishIndex | None
    index_a: PromishIndex | None
    live: np.ndarray
    ext: np.ndarray


class NKSEngine:
    def __init__(self, dataset: KeywordDataset, *, m: int = 2, n_scales: int = 5,
                 seed: int = 0, build_exact: bool = True, build_approx: bool = True,
                 mesh=None, w0: float | None = None, n_buckets: int | None = None,
                 compact_ratio: float = 0.25, compact_min: int = 4096,
                 auto_compact: bool = True, faults: FaultPlan | None = None,
                 synopsis: bool = False,
                 resident_budget_bytes: int | None = None,
                 _indices: tuple | None = None):
        """``mesh`` attaches a device plane: a jax Mesh (with a ``data``
        axis), an existing :class:`~repro.core.device_plane.DevicePlane`, or
        ``"auto"`` to acquire the serving mesh from the environment
        (``REPRO_MESH_OVERRIDE`` / all local devices). With a plane attached,
        ``backend="pallas"`` dispatches shard over the mesh and the device
        tier runs the sharded anchor-star program; ``mesh=None`` (default)
        keeps every tier single-device.

        Streaming knobs: ``w0``/``n_buckets`` pin the hash geometry across
        compactions (None derives both from the corpus, per the paper);
        ``compact_ratio``/``compact_min`` set the rebuild cadence — after an
        insert or delete, the delta is folded into a fresh bulk index once
        ``delta_points + tombstones >= max(compact_min, compact_ratio * N)``
        (``auto_compact=False`` leaves compaction to explicit
        :meth:`compact` calls)."""
        self._bulk = dataset
        self.index_e: PromishIndex | None = None
        self.index_a: PromishIndex | None = None
        self.last_batch_stats: PipelineStats | None = None
        self.plane = None
        if mesh is not None:
            from repro.core.device_plane import get_plane
            self.plane = get_plane(mesh)
        self._build_params = dict(m=m, n_scales=n_scales, seed=seed,
                                  w0=w0, n_buckets=n_buckets,
                                  synopsis=synopsis)
        # Hot-tier budget for out-of-core serving: caps the pallas backend's
        # packed-tile LRU so a memory-mapped corpus stays within its
        # configured resident footprint (None = backend default).
        self.resident_budget_bytes = resident_budget_bytes
        if _indices is not None:
            # Recovery path: the snapshot already holds the built structures.
            self.index_e, self.index_a = _indices
        else:
            if build_exact:
                self.index_e = build_index(dataset, exact=True,
                                           **self._build_params)
            if build_approx:
                self.index_a = build_index(dataset, exact=False,
                                           **self._build_params)
        # Streaming-ingest state: lazy — a never-mutated engine keeps the
        # frozen KeywordDataset and the classic single-corpus code paths.
        self._view: StreamingCorpus | None = None
        self._deltas: dict[str, IndexDelta] = {}
        # internal -> external id map, stored in a capacity-doubled buffer so
        # absorbing a batch appends in O(batch), not O(corpus).
        self._ext_buf = np.arange(dataset.n, dtype=np.int64)
        self._ext_len = dataset.n
        self._next_ext = dataset.n
        self._identity_ids = True
        self.corpus_generation = 0
        self._corpus_token = next(_CORPUS_TOKENS)
        self.compact_ratio = float(compact_ratio)
        self.compact_min = int(compact_min)
        self.auto_compact = bool(auto_compact)
        self.ingest = IngestStats()
        # Durability (attach_wal / recover): every mutating op is appended —
        # and fsync'd — before its ack. None = volatile engine (the default).
        self._faults = faults or NO_FAULTS
        self._wal: walmod.WriteAheadLog | None = None
        self._wal_root: str | None = None
        self._wal_epoch = 0
        self._wal_group = 0         # ingest_group() nesting depth
        self._replaying = False

    # ------------------------------------------------------------- streaming
    @property
    def dataset(self):
        """The corpus the engine currently serves: the merged streaming view
        while a delta/tombstone set is live, the frozen bulk otherwise."""
        return self._view if self._view is not None else self._bulk

    @property
    def delta_points(self) -> int:
        return self._view.n_delta if self._view is not None else 0

    @property
    def tombstone_count(self) -> int:
        return self._view.n_tombstones if self._view is not None else 0

    def _streaming_dirty(self) -> bool:
        return self._view is not None and self._view.dirty

    @property
    def next_external_id(self) -> int:
        """The id the next inserted point will receive. External ids are
        assigned strictly sequentially, so this horizon lets an ingest
        pipeline decide after a crash whether an intended batch landed
        (``data/ingest.py`` reconciliation)."""
        return int(self._next_ext)

    @property
    def _ext_of(self) -> np.ndarray:
        return self._ext_buf[: self._ext_len]

    def _ext_append(self, ext: np.ndarray) -> None:
        need = self._ext_len + len(ext)
        if len(self._ext_buf) < need:
            grown = np.empty(max(2 * len(self._ext_buf), need), dtype=np.int64)
            grown[: self._ext_len] = self._ext_buf[: self._ext_len]
            self._ext_buf = grown
        self._ext_buf[self._ext_len:need] = ext
        self._ext_len = need

    def _streaming_state(self) -> tuple[StreamingCorpus, dict[str, IndexDelta]]:
        """The live streaming state, or a freshly built (uncommitted) one —
        callers assign it back via ``_commit_streaming`` only after the
        mutation succeeded, so a rejected op leaves the engine on the frozen
        bulk path."""
        if self._view is not None:
            return self._view, self._deltas
        view = StreamingCorpus(self._bulk)
        deltas = {}
        if self.index_e is not None:
            deltas["e"] = IndexDelta(self.index_e, view)
        if self.index_a is not None:
            deltas["a"] = IndexDelta(self.index_a, view)
        return view, deltas

    def _commit_streaming(self, view: StreamingCorpus,
                          deltas: dict[str, IndexDelta]) -> None:
        self._view = view
        self._deltas = deltas

    def insert(self, points: np.ndarray,
               keywords: Sequence[Sequence[int]],
               attrs: dict | None = None,
               tenant=None) -> np.ndarray:
        """Absorb a batch of tagged points; returns their external ids.

        The batch is visible to every query issued after this call returns
        (absorbed atomically: queries see all of it or none of it — there is
        no partial-batch state, and a rejected batch changes nothing). Cost
        is O(batch * scales), never O(corpus); the bulk index is untouched
        until compaction folds the delta in.

        ``attrs``/``tenant`` carry the batch's per-point attribute columns
        and tenant assignment; a corpus built with attributes (or tenants)
        requires them on every insert, and a corpus without rejects them —
        the streaming schema is fixed at build time, so filtered queries
        never see a half-attributed corpus. ``keywords`` are *global*
        dictionary ids at this layer; a frontend speaking tenant-local ids
        resolves them through ``dataset.tenants`` first (``launch/serve.py``
        does this for its JSONL insert op).
        """
        view, deltas = self._streaming_state()
        # validates schema + keywords before any mutation
        ids = view.absorb(points, keywords, attrs=attrs, tenant=tenant)
        absorb_into(deltas.values(), view.points[ids])
        self._commit_streaming(view, deltas)
        ext = np.arange(self._next_ext, self._next_ext + len(ids),
                        dtype=np.int64)
        self._next_ext += len(ids)
        self._ext_append(ext)
        self.ingest.inserts += 1
        self.ingest.points_inserted += len(ids)
        # Durability point: the op is in memory; make it survive process
        # death *before* anything downstream (auto-compaction, the ack) runs.
        self._wal_append({
            "op": "insert",
            "points": walmod.encode_array(
                np.ascontiguousarray(points, np.float32)),
            "keywords": [[int(v) for v in ks] for ks in keywords],
            "attrs": ({name: walmod.encode_array(np.asarray(col))
                       for name, col in attrs.items()}
                      if attrs is not None else None),
            "tenant": (walmod.encode_array(tenant)
                       if isinstance(tenant, np.ndarray) else tenant),
            "first_ext": int(ext[0]) if len(ext) else int(self._next_ext),
            "count": len(ext),
        })
        self._maybe_compact()
        return ext

    def delete(self, external_ids: Sequence[int]) -> int:
        """Tombstone points by external id; returns the number deleted.
        Unknown, duplicate, or already-deleted ids raise without applying
        anything (the caller's view of the corpus is stale — a serving
        frontend should surface that, not mask it)."""
        ext = np.asarray(list(external_ids), dtype=np.int64)
        if not len(ext):
            return 0
        if len(np.unique(ext)) != len(ext):
            raise KeyError(f"duplicate ids in delete batch: {ext.tolist()}")
        internal = np.searchsorted(self._ext_of, ext)
        bad = (internal >= len(self._ext_of)) | (self._ext_of[np.minimum(
            internal, len(self._ext_of) - 1)] != ext)
        if bad.any():
            raise KeyError(f"unknown external ids: {ext[bad].tolist()}")
        view, deltas = self._streaming_state()
        dead = view.tombstoned(internal)
        if dead.any():
            raise KeyError(f"already deleted: {ext[dead].tolist()}")
        for d in deltas.values():
            d.retire(internal)
        view.delete(internal)
        self._commit_streaming(view, deltas)
        self.ingest.deletes += 1
        self.ingest.points_deleted += len(ext)
        self._wal_append({"op": "delete", "ids": [int(i) for i in ext]})
        self._maybe_compact()
        return len(ext)

    def compact_prepare(self) -> PreparedCompaction | None:
        """The O(N) half of :meth:`compact`, safe to run off-thread.

        Reads (never mutates) the live streaming view: folds bulk ∪ delta
        into a fresh frozen dataset and builds the new indices. Serving
        continues against the old generation the whole time — the swap is
        :meth:`compact_commit`, a cheap pointer exchange. The caller must
        hold ingest still between prepare and commit (the runtime defers
        ingest ops while a rebuild is in flight); commit verifies that via
        ``version``. Returns None when nothing is dirty."""
        if not self._streaming_dirty():
            return None
        view = self._view
        live = view.live_internal_ids()
        if not len(live):
            # An all-deleted corpus has no projection span to rebuild from;
            # keep serving from tombstones until something is inserted.
            raise ValueError("compact: corpus would be empty — insert points "
                             "before compacting away the last live one")
        version = (view.n, view.n_tombstones)
        bulk = view.compacted_dataset()
        # Mid-rebuild fault point: the compacted dataset exists, the new
        # indices do not — a crash here must leave the old generation fully
        # intact (nothing has been swapped yet).
        self._faults.check("compact")
        index_e = build_index(bulk, exact=True, **self._build_params) \
            if self.index_e is not None else None
        index_a = build_index(bulk, exact=False, **self._build_params) \
            if self.index_a is not None else None
        return PreparedCompaction(version=version, bulk=bulk,
                                  index_e=index_e, index_a=index_a, live=live,
                                  ext=np.ascontiguousarray(self._ext_of[live]))

    def compact_commit(self, prep: PreparedCompaction | None) -> bool:
        """Atomically swap a prepared compaction in (the double-buffer flip).

        Cheap — pointer swaps plus the generation bump that scopes the
        backend LRU caches. Raises :class:`StaleCompactionError` when the
        streaming state moved since prepare (an interleaved ingest op)."""
        if prep is None:
            return False
        if self._view is None or \
                (self._view.n, self._view.n_tombstones) != prep.version:
            raise StaleCompactionError(
                f"streaming state moved since prepare "
                f"(prepared @ rows,tombstones={prep.version}, live="
                f"{(self._view.n, self._view.n_tombstones) if self._view is not None else None})")
        self._bulk = prep.bulk
        if self.index_e is not None:
            self.index_e = prep.index_e
        if self.index_a is not None:
            self.index_a = prep.index_a
        self._ext_buf = prep.ext
        self._ext_len = len(prep.live)
        # The map is identity iff no id was ever retired: ext values are
        # strictly increasing in [0, _next_ext), so full size == identity.
        # (_next_ext must participate: a compaction that trimmed only
        # *trailing* ids leaves ext_buf == arange, yet the next insert gets
        # external id _next_ext != its internal row.)
        self._identity_ids = self._ext_len == self._next_ext
        self._view = None
        self._deltas = {}
        self.corpus_generation += 1
        self._corpus_token = next(_CORPUS_TOKENS)
        self.ingest.compactions += 1
        self.ingest.generation = self.corpus_generation
        self._wal_append({"op": "compact",
                          "generation": self.corpus_generation})
        return True

    def compact(self) -> bool:
        """Fold the delta into a fresh immutable bulk index (atomic swap).

        Rebuilds with the constructor's build params over the live points in
        external-id order, remaps internal ids, bumps ``corpus_generation``
        (invalidating backend packed-subset/tile caches), and resets the
        delta. No-op (returns False) when nothing is dirty. Synchronous
        convenience over the prepare/commit split the runtime uses for
        off-thread rebuilds."""
        return self.compact_commit(self.compact_prepare())

    def _maybe_compact(self) -> None:
        if not self.auto_compact or self._view is None or self._replaying:
            # During WAL replay the logged compact records drive compaction —
            # the cadence already fired once, at its logged position.
            return
        if self._view.n_tombstones >= self._view.n:
            # Everything is dead: nothing to rebuild from. The delete that
            # got us here already succeeded — stay on tombstones until an
            # insert brings the corpus back (explicit compact() still raises).
            return
        churn = self._view.n_delta + self._view.n_tombstones
        if churn >= max(self.compact_min, self.compact_ratio * self._bulk.n):
            self.compact()

    def _externalize(self, cands: list[Candidate]) -> list[Candidate]:
        """Map internal candidate ids to stable external ids (identity until
        a compaction leaves holes in the id space)."""
        if self._identity_ids:
            return cands
        return [dataclasses.replace(
                    c, ids=tuple(int(self._ext_of[i]) for i in c.ids))
                for c in cands]

    def _record_ingest(self, stats: PipelineStats) -> None:
        stats.corpus_generation = self.corpus_generation
        stats.delta_points = self.delta_points
        stats.tombstones = self.tombstone_count
        stats.compactions = self.ingest.compactions

    # ------------------------------------------------------------ durability
    def _wal_append(self, record: dict) -> None:
        if self._wal is None or self._replaying:
            return
        # Inside an ingest_group() the fsync is deferred to the group barrier
        # (one fsync per batch window); the ack ordering contract moves with
        # it — callers must not ack grouped ops until the group exits.
        self._wal.append(record, sync=self._wal_group == 0)
        self.ingest.wal_appends += 1

    @contextlib.contextmanager
    def ingest_group(self):
        """Group-commit scope: WAL appends inside the block defer their fsync
        to one barrier at exit (``WriteAheadLog.sync``), so a run of ingest
        ops acknowledged together pays a single durability barrier.

        The fsync-before-ack contract is preserved at the group granularity:
        every record in the group is durable before the ``with`` block
        returns, so a caller that acks only after the block (the runtime's
        ingest-run path) never acks a volatile write. Nests harmlessly — only
        the outermost exit issues the barrier. A volatile engine (no WAL)
        degrades to a no-op scope."""
        self._wal_group += 1
        try:
            yield self
        finally:
            self._wal_group -= 1
            if self._wal_group == 0 and self._wal is not None \
                    and not self._replaying:
                # InjectedCrash from the wal_ack fault point propagates from
                # here — after the fsync, before any caller could ack.
                self._wal.sync()

    def _engine_meta(self) -> dict:
        return {
            "next_ext": int(self._next_ext),
            "identity_ids": bool(self._identity_ids),
            "corpus_generation": int(self.corpus_generation),
            "compact_ratio": self.compact_ratio,
            "compact_min": self.compact_min,
            "auto_compact": self.auto_compact,
            "build_exact": self.index_e is not None,
            "build_approx": self.index_a is not None,
            "ingest": self.ingest.as_dict(),
        }

    def attach_wal(self, root: str, faults: FaultPlan | None = None) -> None:
        """Make the engine durable under ``root`` (see ``serve.wal``).

        Writes the genesis snapshot (epoch 0: the current frozen state, so
        recovery always has a base corpus) and opens the WAL segment; from
        here every insert/delete/compact is fsync'd before its ack. A dirty
        engine compacts first — a snapshot is a clean generation boundary."""
        if self._wal is not None:
            raise RuntimeError(f"WAL already attached at {self._wal_root}")
        if faults is not None:
            self._faults = faults
        if self._streaming_dirty():
            self.compact()
        os.makedirs(root, exist_ok=True)
        self._wal_root = root
        self._wal_epoch = 0
        self._write_snapshot(0)
        walmod.write_manifest(root, 0)
        self._wal = walmod.WriteAheadLog(walmod.wal_path(root, 0),
                                         faults=self._faults)

    def _write_snapshot(self, epoch: int) -> None:
        walmod.save_snapshot(
            walmod.snap_dir(self._wal_root, epoch),
            dataset=self._bulk, index_e=self.index_e, index_a=self.index_a,
            build_params=self._build_params,
            engine_meta={**self._engine_meta(),
                         "ext": walmod.encode_array(
                             np.ascontiguousarray(self._ext_of))})

    def snapshot(self) -> str:
        """Roll the log: fold the delta (if dirty), persist the full engine
        state as the next epoch's snapshot, and start an empty WAL segment.
        After this, recovery replays nothing — the ack horizon moves from
        "snapshot + log suffix" to "snapshot". Returns the snapshot dir."""
        if self._wal is None:
            raise RuntimeError("snapshot() requires an attached WAL "
                               "(attach_wal first)")
        if self._streaming_dirty():
            self.compact()
        epoch = self._wal_epoch + 1
        self._write_snapshot(epoch)
        self._wal.close()
        # Ordering: the new (empty) segment must exist before the manifest
        # names its epoch — recovery reads the manifest first.
        new_wal = walmod.WriteAheadLog(walmod.wal_path(self._wal_root, epoch),
                                       faults=self._faults)
        walmod.write_manifest(self._wal_root, epoch)
        self._wal = new_wal
        self._wal_epoch = epoch
        self.ingest.snapshots += 1
        walmod.gc_epochs(self._wal_root, epoch)
        return walmod.snap_dir(self._wal_root, epoch)

    def _replay_record(self, rec: dict) -> None:
        op = rec["op"]
        if op == "insert":
            attrs = rec["attrs"]
            if attrs is not None:
                attrs = {name: walmod.decode_array(col)
                         for name, col in attrs.items()}
            tenant = rec["tenant"]
            if isinstance(tenant, dict) and "__nd__" in tenant:
                tenant = walmod.decode_array(tenant)
            ext = self.insert(walmod.decode_array(rec["points"]),
                              rec["keywords"], attrs=attrs, tenant=tenant)
            if len(ext) != rec["count"] or \
                    (len(ext) and int(ext[0]) != rec["first_ext"]):
                raise IOError(
                    f"WAL replay diverged: insert assigned ids "
                    f"{int(ext[0]) if len(ext) else None}+{len(ext)}, log "
                    f"recorded {rec['first_ext']}+{rec['count']}")
        elif op == "delete":
            self.delete(rec["ids"])
        elif op == "compact":
            self.compact()
            if self.corpus_generation != rec["generation"]:
                raise IOError(
                    f"WAL replay diverged: compact reached generation "
                    f"{self.corpus_generation}, log recorded "
                    f"{rec['generation']}")
        else:
            raise IOError(f"unknown WAL record op {op!r}")

    @classmethod
    def recover(cls, root: str, *, mesh=None, verify: bool = True,
                faults: FaultPlan | None = None) -> "NKSEngine":
        """Rebuild an engine from its WAL root: latest snapshot + log replay.

        The recovered engine answers **bit-identically** to an uninterrupted
        engine that executed the same acknowledged op sequence (the snapshot
        stores the built index structures verbatim, and replay re-runs the
        deterministic ingest path, including logged compactions at their
        logged positions). The WAL stays attached — the engine keeps
        appending to the recovered segment."""
        man = walmod.read_manifest(root)
        epoch = int(man["epoch"])
        snap = walmod.load_snapshot(walmod.snap_dir(root, epoch),
                                    verify=verify)
        bp, em = snap["build_params"], snap["engine"]
        engine = cls(snap["dataset"],
                     m=bp["m"], n_scales=bp["n_scales"], seed=bp["seed"],
                     w0=bp["w0"], n_buckets=bp["n_buckets"],
                     synopsis=bp.get("synopsis", False),
                     build_exact=em["build_exact"],
                     build_approx=em["build_approx"], mesh=mesh,
                     compact_ratio=em["compact_ratio"],
                     compact_min=em["compact_min"],
                     auto_compact=em["auto_compact"], faults=faults,
                     _indices=(snap["index_e"], snap["index_a"]))
        engine._ext_buf = walmod.decode_array(em["ext"])
        engine._ext_len = len(engine._ext_buf)
        engine._next_ext = em["next_ext"]
        engine._identity_ids = em["identity_ids"]
        engine.corpus_generation = em["corpus_generation"]
        for field, value in em["ingest"].items():
            setattr(engine.ingest, field, value)
        engine.ingest.replayed_ops = 0
        engine._wal_root = root
        engine._wal_epoch = epoch
        wal_file = walmod.wal_path(root, epoch)
        rstats = walmod.WalStats()
        engine._replaying = True
        try:
            for rec in walmod.WriteAheadLog.replay(wal_file, rstats):
                engine._replay_record(rec)
                engine.ingest.replayed_ops += 1
        finally:
            engine._replaying = False
        if rstats.torn_tail:
            # A torn tail is an unacknowledged op and replay skipped it, but
            # its bytes are still on disk: appending after them would plant a
            # CRC mismatch mid-file, and the *next* recovery would raise
            # TornRecordError — losing every write acknowledged after this
            # recovery. Truncate to the last whole record before reopening.
            with open(wal_file, "rb+") as f:
                f.truncate(rstats.valid_bytes)
                f.flush()
                os.fsync(f.fileno())
        engine._wal = walmod.WriteAheadLog(wal_file, faults=engine._faults)
        engine._wal.stats.replayed = rstats.replayed
        engine._wal.stats.torn_tail = rstats.torn_tail
        return engine

    @classmethod
    def from_store(cls, directory: str, *, mesh=None, mmap: bool = True,
                   verify: bool = False,
                   resident_budget_bytes: int | None = None,
                   **kw) -> "NKSEngine":
        """Open an engine over an out-of-core bulk store (``core.store``).

        With ``mmap=True`` (the default, and the point) the corpus points,
        keyword CSRs, and index bucket tables stay on disk as memory-mapped
        leaves — only touched pages become resident, the per-bucket synopses
        load eagerly (they are tiny and consulted per plan), and
        ``resident_budget_bytes`` caps the backend's hot-tier tile cache.
        Answers are bit-identical to an in-RAM engine built with the store's
        recorded ``build_params``: the store pins the hash geometry, so
        streaming absorbs and compactions continue the exact same sequence.
        """
        st = storemod.load_store(directory, mmap=mmap, verify=verify)
        bp = st["build_params"] or {}
        return cls(st["dataset"],
                   m=bp.get("m", 2), n_scales=bp.get("n_scales", 5),
                   seed=bp.get("seed", 0), w0=bp.get("w0"),
                   n_buckets=bp.get("n_buckets"),
                   synopsis=bp.get("synopsis", False),
                   build_exact=st["index_e"] is not None,
                   build_approx=st["index_a"] is not None,
                   mesh=mesh, resident_budget_bytes=resident_budget_bytes,
                   _indices=(st["index_e"], st["index_a"]), **kw)

    @property
    def wal_stats(self) -> "walmod.WalStats | None":
        return self._wal.stats if self._wal is not None else None

    def close(self) -> None:
        if self._wal is not None:
            self._wal.close()

    @classmethod
    def ingest_embeddings(cls, api, params, batches: Sequence[dict],
                          keywords: Sequence[Sequence[int]], **kw) -> "NKSEngine":
        """Build the corpus from model embeddings (any assigned arch)."""
        import jax.numpy as jnp
        embs = [np.asarray(api.embed(params, b), np.float32) for b in batches]
        points = np.concatenate(embs, axis=0)
        return cls(make_dataset(points, keywords), **kw)

    def _device_topk(self, keywords: Sequence[int], k: int,
                     stats: PipelineStats | None = None,
                     eligible: np.ndarray | None = None) -> list[Candidate]:
        """One anchor-star dispatch through the plane (sharded) or the
        single-device kernel — the device tier's unit of work. ``eligible``
        (a filtered query's point mask) restricts the packed groups; a group
        the filter empties means no feasible candidate, so the dispatch is
        skipped outright. The device's fp32 diameters only select the k
        sets; each is rescored in float64 on the host and the k are ranked
        by that, so every mesh that selects the same sets returns the same
        answer. Returns external ids.

        Four spans, none inside another: ``nks.device.pack``,
        ``nks.device.dispatch`` (transfers in and the program call, tagged
        with the shape q, R, k that picks the compiled program),
        ``nks.device.readback`` (one ``jax.device_get`` of both outputs,
        which starts both copies before it waits on either; everything after
        it is numpy) and ``nks.engine.rescore``. On a plane
        ``t_collective_s`` covers the dispatch and the readback."""
        import jax
        import jax.numpy as jnp
        from repro.core.device_plane import pack_groups
        from repro.core.distributed import nks_anchor_topk
        if eligible is not None:
            if any(not eligible[self.dataset.points_with(v)].any()
                   for v in keywords):
                return []
        on_plane = self.plane is not None
        collective = ("t_collective_s",) if on_plane else ()
        with span("nks.device.pack", stats, "t_pack_s"):
            pack = self.plane.pack_groups if on_plane else pack_groups
            groups, mask, ids = pack(self.dataset, list(keywords),
                                     eligible=eligible)
        q, r = groups.shape[:2]
        with span("nks.device.dispatch", stats, ("t_dispatch_s", *collective),
                  q=q, r=r, k=k):
            topk = self.plane.nks_topk if on_plane else nks_anchor_topk
            diams, cids = topk(jnp.asarray(groups), jnp.asarray(mask),
                               jnp.asarray(ids), k)
        with span("nks.device.readback", stats, ("t_readback_s", *collective)):
            diams, cids = jax.device_get((diams, cids))
            sets = [tuple(sorted(set(row.tolist())))
                    for d, row in zip(diams, cids) if np.isfinite(d)]
        if stats is not None:
            if on_plane:
                stats.sharded_dispatches += 1
                for i in range(self.plane.n_shards):
                    stats.shard_dispatches[i] += 1
            else:
                stats.shard_dispatches[0] += 1
        with span("nks.engine.rescore", stats, "t_rescore_s"):
            cands = [Candidate(ids=s, diameter=brute_force.set_diameter(
                         s, self.dataset)) for s in sets]
            return self._externalize(
                sorted(cands, key=lambda c: (c.diameter, c.ids)))

    def _resolve_filter(self, filter) -> "Filter | None":
        return Filter.coerce(filter)

    def _resolve_namespace(self, queries: Sequence[Sequence[int]],
                           flt: "Filter | None") -> list[list[int]]:
        """Per-tenant dictionary resolution, run before planning: a
        tenant-scoped query on a namespaced corpus speaks *tenant-local*
        keyword ids, mapped into the tenant's global dictionary slots here
        (out-of-range local ids raise — the tenant cannot name, let alone
        reach, another tenant's keywords)."""
        if flt is None or flt.tenant is None or self.dataset.tenants is None:
            return [list(q) for q in queries]
        ns = self.dataset.tenants
        return [ns.resolve(flt.tenant, q) for q in queries]

    def query(self, keywords: Sequence[int], k: int = 1,
              tier: str = "approx", filter=None,
              semantics=None) -> QueryResult:
        t0 = time.perf_counter()
        # Same API-boundary validation as query_batch: every entry path
        # (clean per-query searches included) rejects out-of-dictionary
        # keywords with the same ValueError instead of a numpy IndexError
        # from inside the search.
        self._validate_queries([keywords])
        flt = self._resolve_filter(filter)
        sem = QuerySemantics.coerce(semantics)
        flex = sem is not None and not sem.trivial_for(
            sorted(set(int(v) for v in keywords)))
        if tier == "device" and flex:
            raise ValueError(
                "device tier does not support flexible semantics; "
                "use tier='exact' or 'approx'")
        if tier in ("exact", "approx") and (self._streaming_dirty()
                                            or flt is not None or flex):
            # The per-query searches walk a frozen index; with a live delta
            # the batched pipeline (a batch of one reproduces them exactly,
            # per the PR-1 parity suite) is the delta-aware path — and the
            # filtered path, which evaluates the predicate once and threads
            # the eligibility mask through every stage. Flexible semantics
            # ride the same batched path (m-of-k expansion, weights, scored
            # queues live in ``_batch_search``).
            res = self.query_batch([keywords], k=k, tier=tier,
                                   backend="numpy", filter=flt,
                                   semantics=sem)[0]
            return dataclasses.replace(res, latency_s=time.perf_counter() - t0)
        if tier == "exact":
            pq = promish_e.search(self.dataset, self.index_e, keywords, k=k)
        elif tier == "approx":
            pq = promish_a.search(self.dataset, self.index_a, keywords, k=k)
        elif tier == "device":
            eligible = None
            resolved = list(keywords)
            if flt is not None:
                resolved = self._resolve_namespace([keywords], flt)[0]
                eligible = flt.evaluate(self.dataset)
                if self._view is not None:
                    self._view.mask_tombstones(eligible)
            cands = self._device_topk(resolved, k, eligible=eligible)
            return QueryResult(list(keywords), cands,
                               time.perf_counter() - t0, tier)
        else:
            raise ValueError(tier)
        return QueryResult(list(keywords), self._externalize(pq.items),
                           time.perf_counter() - t0, tier)

    # ------------------------------------------------------------- batched path
    def _validate_queries(self, queries: Sequence[Sequence[int]]
                          ) -> list[list[int]]:
        out = []
        for q in queries:
            q = sorted(set(int(v) for v in q))
            if any(v < 0 or v >= self.dataset.n_keywords for v in q):
                raise ValueError("query keyword outside dictionary")
            out.append(q)
        return out

    def _run_tasks(self, tasks: list[plan.SubsetTask], queries: list[list[int]],
                   pqs: list[TopK], backend: DistanceBackend,
                   stats: PipelineStats,
                   eligible: np.ndarray | None = None,
                   ctx: "plan.BatchPlanContext | None" = None,
                   timers: dict | None = None,
                   weights: "list[np.ndarray | None] | None" = None
                   ) -> tuple[int, int, int]:
        """Distance stage + enumeration stage for one batch of subset tasks.

        ``eligible`` is the batch's predicate mask: keyword groups restrict
        to eligible rows (a task whose filtered groups lose a keyword is
        dropped before any pack), and the backend folds the mask into the
        device-side join bitmask. ``ctx`` carries the batch's keyword-mask
        memoization; ``timers`` accumulates the enumeration stage's float64
        rescore wall time. ``weights`` maps each task's ``qidx`` to the
        query's (N,) keyword-weight vector (or None — unweighted): the
        dispatch/pack stages are weight-blind (the geometric join is a
        superset of the weighted one), only host settlement consumes it.
        Returns (tasks_searched, dispatches_issued, join_pairs)."""
        prepared = []
        with span("nks.engine.plan", stats, "t_plan_s"):
            for t in tasks:
                gl = local_groups(t.f_ids, queries[t.qidx], self.dataset,
                                  eligible=eligible, ctx=ctx)
                if gl is not None:
                    prepared.append((t, gl))
        if not prepared:
            return 0, 0, 0
        d0 = backend.stats.dispatches
        # Radius substitution: when the source bucket's diameter bound
        # already beats the query's live r_k, every pair in the subset joins
        # — the backend's infinite-radius path synthesizes the identical
        # all-ones join without touching the (possibly cold) point rows.
        # Result- and join_count-preserving for both backends.
        radii = []
        for t, _ in prepared:
            r = pqs[t.qidx].kth_diameter()
            if np.isfinite(r) and t.diam_ub <= r:
                r = float("inf")
                stats.buckets_pruned_radius += 1
            radii.append(r)
        blocks = backend.self_join_blocks(
            self.dataset.points,
            [t.f_ids for t, _ in prepared],
            radii,
            keys=[t.f_ids.tobytes() for t, _ in prepared],
            generation=self._corpus_token,
            eligible=eligible)
        join_pairs = 0
        with span("nks.engine.enumerate", stats, "t_enumerate_s"):
            for (t, gl), db in zip(prepared, blocks):
                join_pairs += db.join_count
                stats.candidates_explored += enumerate_with_block(
                    t.f_ids, gl, queries[t.qidx], self.dataset, pqs[t.qidx],
                    db, timers=timers,
                    weights=None if weights is None else weights[t.qidx])
        return len(prepared), backend.stats.dispatches - d0, join_pairs

    def _batch_search(self, queries: list[list[int]], k: int, tier: str,
                      backend: DistanceBackend,
                      flt: "Filter | None" = None,
                      sem: "QuerySemantics | None" = None
                      ) -> tuple[list[TopK], PipelineStats]:
        exact = tier == "exact"
        index = self.index_e if exact else self.index_a
        if index is None:
            raise ValueError(f"engine built without the {tier!r} index")
        stats = PipelineStats(batch_size=len(queries), tier=tier,
                              backend=backend.name)
        b0 = dataclasses.replace(backend.stats)
        # dataclasses.replace shares the list fields — snapshot them by value
        # so the end-of-batch delta below is meaningful.
        b0_shards = (list(backend.stats.shard_dispatches),
                     list(backend.stats.shard_valid_cells),
                     list(backend.stats.shard_total_cells))
        b0_bins = dict(getattr(backend.stats, "bin_points", None) or {})
        # Flexible semantics: each query's m-of-k subqueries run the
        # plan/dispatch/enumerate loop as independent *execution* entries
        # that share the original query's queue (and weight vector) — the
        # queue's id-set dedup resolves cross-subquery duplicates, since a
        # candidate's cost and coverage depend only on (ids, Q). A classic
        # batch (``sem`` None) expands to itself: one execution entry per
        # query, plain TopK queues, no weights — every index below then
        # degenerates to the old per-query one, keeping results
        # bit-identical.
        if sem is None:
            pqs = [TopK(k, init_full=exact) for _ in queries]
            exec_queries: list[list[int]] = list(queries)
            exec_orig = list(range(len(queries)))
            exec_pqs, exec_weights = pqs, None
        else:
            pqs = [sem.make_pq(self.dataset, q, k, init_full=exact)
                   for q in queries]
            wvecs = [sem.weight_vector(self.dataset, q) for q in queries]
            exec_queries, exec_orig = [], []
            for o, q in enumerate(queries):
                for sub in sem.expand_subqueries(q):
                    exec_queries.append(sub)
                    exec_orig.append(o)
            exec_pqs = [pqs[o] for o in exec_orig]
            exec_weights = [wvecs[o] for o in exec_orig]
        stats.subqueries = len(exec_queries)
        # Streaming: plan over bulk ∪ delta, tombstones cleared from every
        # bitset (the subsets the backend packs and the enumeration walks
        # then contain live points only).
        delta = None
        if self._streaming_dirty():
            delta = self._deltas["e" if exact else "a"]
        with span("nks.engine.plan", stats, "t_plan_s"):
            # Filtered batch: evaluate the predicate/tenant mask ONCE here;
            # every downstream stage (plan pruning, group restriction, device
            # fold) consumes this same array. Tombstoned points are cleared
            # from the mask too, so eligibility always implies liveness.
            eligible = None
            if flt is not None:
                eligible = flt.evaluate(self.dataset)
                if self._view is not None:
                    self._view.mask_tombstones(eligible)
                stats.eligible_points = int(eligible.sum())
                live = self.dataset.n - self.tombstone_count
                stats.filter_selectivity = round(
                    stats.eligible_points / live, 6) if live else 0.0
            # Zone-map pruning: with per-bucket synopses built
            # (synopsis=True / a disk store) and a filter in play, the planner
            # can skip buckets whose zone maps are provably disjoint from the
            # predicate before their member lists are gathered. Pure
            # accounting win — results are bit-identical with the pruner on
            # or off.
            zone = None
            if flt is not None and eligible is not None \
                    and index.structures[0].synopsis is not None:
                zp = storemod.ZoneMapPruner(flt, self.dataset)
                zone = zp if zp.active else None
            # One BatchPlanContext per batch: keyword masks and
            # covering-bucket selections are memoized for the batch's
            # lifetime (the corpus is frozen while the batch runs).
            pctx = plan.BatchPlanContext(self.dataset)
            bitsets = [pctx.query_bitset(q) for q in exec_queries]
            if delta is not None:
                for bs in bitsets:
                    self._view.mask_tombstones(bs)
        explored = {i: set() for i in range(len(exec_queries))} if exact \
            else None
        active = list(range(len(exec_queries)))
        timers = {"rescore_s": 0.0}

        for s in range(index.n_scales):
            if not active:
                break
            sstats = ScaleStats(scale=s, active_queries=len(active))
            pstats = plan.PlanStats()
            with span("nks.engine.plan", stats, "t_plan_s", scale=s):
                tasks = plan.plan_scale(index, s, exec_queries, bitsets,
                                        active, explored, pstats, delta=delta,
                                        eligible=eligible, ctx=pctx, zone=zone)
            sstats.buckets_selected = pstats.buckets_selected
            sstats.duplicate_subsets = pstats.duplicate_subsets
            sstats.filtered_subsets = pstats.filtered_subsets
            stats.filtered_subsets += pstats.filtered_subsets
            sstats.buckets_pruned_zonemap = pstats.buckets_pruned_zonemap
            stats.buckets_pruned_zonemap += pstats.buckets_pruned_zonemap
            sstats.tasks_planned = len(tasks)
            pr0 = stats.buckets_pruned_radius
            searched, dispatches, pairs = self._run_tasks(
                tasks, exec_queries, exec_pqs, backend, stats,
                eligible=eligible, ctx=pctx, timers=timers,
                weights=exec_weights)
            sstats.tasks_searched = searched
            sstats.dispatches = dispatches
            sstats.join_pairs = pairs
            sstats.buckets_pruned_radius = stats.buckets_pruned_radius - pr0
            # Per-query termination, exactly as the per-query searches do it:
            # E: Lemma-2 radius test after the scale; A: first full PQ.
            # Termination is a property of the ORIGINAL query's shared queue,
            # so one decision per original deactivates all its subqueries.
            still = []
            done_orig: dict[int, bool] = {}
            for qidx in active:
                o = exec_orig[qidx]
                if o not in done_orig:
                    if exact:
                        done_orig[o] = pqs[o].kth_diameter() \
                            <= index.w0 * (2.0 ** (s - 1))
                    else:
                        done_orig[o] = pqs[o].full()
                    if done_orig[o]:
                        sstats.queries_finished += 1
                if not done_orig[o]:
                    still.append(qidx)
            active = still
            stats.scales.append(sstats)

        if active:
            stats.fallback_queries = len(active)
            tasks = plan.fallback_tasks(bitsets, active, eligible=eligible)
            _, stats.fallback_dispatches, _ = self._run_tasks(
                tasks, exec_queries, exec_pqs, backend, stats,
                eligible=eligible, ctx=pctx, timers=timers,
                weights=exec_weights)
        stats.t_rescore_s = timers["rescore_s"]
        stats.t_pack_s = backend.stats.t_pack_s - b0.t_pack_s
        stats.t_dispatch_s = backend.stats.t_dispatch_s - b0.t_dispatch_s
        stats.cache_hits = backend.stats.cache_hits - b0.cache_hits
        stats.cache_misses = backend.stats.cache_misses - b0.cache_misses
        stats.h2d_bytes = backend.stats.h2d_bytes - b0.h2d_bytes
        stats.d2h_bytes = backend.stats.d2h_bytes - b0.d2h_bytes
        stats.cold_bytes_read = (backend.stats.cold_bytes_read
                                 - b0.cold_bytes_read)
        stats.sharded_dispatches = (backend.stats.sharded_dispatches
                                    - b0.sharded_dispatches)
        stats.t_collective_s = backend.stats.t_collective_s - b0.t_collective_s
        for dst, now, before in zip(
                (stats.shard_dispatches, stats.shard_valid_cells,
                 stats.shard_total_cells),
                (backend.stats.shard_dispatches, backend.stats.shard_valid_cells,
                 backend.stats.shard_total_cells), b0_shards):
            dst.extend(v - (before[i] if i < len(before) else 0)
                       for i, v in enumerate(now))
        # Cascade / routing counters (zero on backends without the fields).
        for f in ("prune_tier_dispatches", "cells_pruned",
                  "host_routed_dispatches", "host_routed_subsets"):
            setattr(stats, f, getattr(backend.stats, f, 0) - getattr(b0, f, 0))
        for f in ("t_prune_s", "t_host_s"):
            setattr(stats, f,
                    getattr(backend.stats, f, 0.0) - getattr(b0, f, 0.0))
        stats.bin_strategy = getattr(backend, "bin_strategy", "")
        for edge, (pts, padded) in (getattr(backend.stats, "bin_points", None)
                                    or {}).items():
            before = b0_bins.get(edge, (0, 0))
            dp, dpad = pts - before[0], padded - before[1]
            if dp or dpad:
                stats.bin_occupancy[edge] = (dp, dpad)
        return pqs, stats

    def query_batch(self, queries: Sequence[Sequence[int]], k: int = 1,
                    tier: str = "approx",
                    backend: str | DistanceBackend = "numpy",
                    filter=None, semantics=None) -> list[QueryResult]:
        """Answer a batch of queries through the staged pipeline.

        Bucket selection, Algorithm-2 dedup, and device dispatch are amortised
        across the batch: with ``backend="pallas"`` each scale issues a few
        size-binned fused threshold-join dispatches covering all live subsets
        (subsets at an infinite pruning radius skip the device — their join
        mask is all-ones by construction); on a mesh-attached engine those
        dispatches shard over the device plane. The ``device`` tier issues
        one anchor-star dispatch per query — through the plane's shard_map
        program when a mesh is attached, the single-device kernel otherwise —
        and records the same PipelineStats. Per-result latency is the batch
        wall time divided by the batch size (attribution inside a fused
        dispatch is meaningless). Pipeline accounting lands in
        ``self.last_batch_stats``.

        ``filter`` (a :class:`~repro.core.filters.Filter` or its JSON dict
        form) applies attribute predicates and tenant scoping to the whole
        batch: the mask is evaluated once, planning prunes fully-ineligible
        subsets, the device folds eligibility into the packed join bitmask
        (no new D2H), and every candidate is drawn from eligible points only.
        On a namespaced multi-tenant corpus a tenant-scoped batch speaks
        tenant-local keyword ids, resolved through the tenant's dictionary
        before planning.

        ``semantics`` (a :class:`~repro.core.semantics.QuerySemantics` or
        its JSON dict form ``{"m": ..., "weights": {...}, "score": ...,
        "alpha": ...}``) applies m-of-k partial coverage, per-keyword
        weights, and scored ranking to the whole batch. Degenerate semantics
        (full coverage, unit weights, no scoring) are dropped before
        planning, so results stay bit-identical to a plain call; the device
        tier rejects non-trivial semantics.

        The call is the span ``nks.engine.query_batch`` (metadata: tier,
        number of queries); its stages are spans inside it.
        """
        with span("nks.engine.query_batch", tier=tier, queries=len(queries)):
            return self._query_batch(queries, k, tier, backend, filter,
                                     semantics)

    def _query_batch(self, queries: Sequence[Sequence[int]], k: int,
                     tier: str, backend: str | DistanceBackend, filter,
                     semantics) -> list[QueryResult]:
        flt = self._resolve_filter(filter)
        sem = QuerySemantics.coerce(semantics)
        if sem is not None and tier == "device":
            if any(not sem.trivial_for(sorted(set(int(v) for v in q)))
                   for q in queries):
                raise ValueError(
                    "device tier does not support flexible semantics; "
                    "use tier='exact' or 'approx'")
            sem = None
        if tier == "device":
            t0 = time.perf_counter()
            stats = PipelineStats(
                batch_size=len(queries), tier=tier,
                backend="device-plane" if self.plane is not None else "anchor")
            stats.shard_dispatches = [0] * (
                self.plane.n_shards if self.plane is not None else 1)
            eligible = None
            resolved = [list(q) for q in queries]
            if flt is not None:
                resolved = self._resolve_namespace(queries, flt)
                eligible = flt.evaluate(self.dataset)
                if self._view is not None:
                    self._view.mask_tombstones(eligible)
                stats.eligible_points = int(eligible.sum())
            out = []
            for q, rq in zip(queries, resolved):
                cands = self._device_topk(rq, k, stats, eligible=eligible)
                # echo the caller's keywords (tenant-local on a namespaced
                # corpus), never the resolved global slots
                out.append(QueryResult(list(q), cands, 0.0, tier))
            per_q = (time.perf_counter() - t0) / max(len(queries), 1)
            out = [dataclasses.replace(r, latency_s=per_q) for r in out]
            self._record_ingest(stats)
            self.last_batch_stats = stats
            return out
        if tier not in ("exact", "approx"):
            raise ValueError(tier)
        t0 = time.perf_counter()
        qlists = self._validate_queries(self._resolve_namespace(queries, flt))
        if sem is not None:
            if flt is not None and flt.tenant is not None \
                    and self.dataset.tenants is not None:
                # Weight keys speak the same tenant-local ids as the query
                # keywords — resolve them through the same namespace.
                ns, tenant = self.dataset.tenants, flt.tenant
                sem = sem.resolve_keywords(
                    lambda kw: ns.resolve(tenant, [kw])[0])
            # Degenerate semantics normalise away entirely: the classic
            # pipeline below is then byte-for-byte the pre-semantics one.
            if all(sem.trivial_for(q) for q in qlists):
                sem = None
        pqs, stats = self._batch_search(qlists, k, tier,
                                        self._resolve_backend(backend),
                                        flt=flt, sem=sem)
        self._record_ingest(stats)
        self.last_batch_stats = stats
        per_q = (time.perf_counter() - t0) / max(len(qlists), 1)
        # results echo the caller's keyword lists verbatim — resolved global
        # slots (tenant namespaces) and normalization stay internal
        return [QueryResult(list(q), self._externalize(pq.items), per_q, tier)
                for q, pq in zip(queries, pqs)]

    def _resolve_backend(self, backend: str | DistanceBackend) -> DistanceBackend:
        """Backend resolution is where the plane plugs in: a string
        ``"pallas"`` on a mesh-attached engine gets the sharded dispatch
        route, and an out-of-core engine's ``resident_budget_bytes`` caps
        the hot-tier tile LRU; instances pass through untouched (caller's
        placement — and cache sizing — wins)."""
        if backend == "pallas":
            kw = {}
            if self.plane is not None:
                kw["plane"] = self.plane
            if self.resident_budget_bytes is not None:
                kw["cache_bytes"] = self.resident_budget_bytes
            return get_backend(backend, **kw)
        return get_backend(backend)
