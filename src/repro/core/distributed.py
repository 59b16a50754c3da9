"""Distributed NKS search on the production mesh (DESIGN.md §5).

Two layers:

1. ``nks_anchor_topk`` — the TPU-native device kernel (single shard):
   anchor-star candidate generation. For each anchor point of the rarest
   query keyword, pick the nearest point per remaining keyword (one masked
   pairwise-distance matmul per keyword — the Pallas ``pairwise_l2`` hot
   spot) and score the resulting candidate by its exact diameter
   (``tuple_diameters`` kernel). By the triangle inequality the best
   anchor-star diameter is within 2x of the true optimum (each member is
   within nn-dist of the anchor, so pairwise <= 2 max nn-dist); empirically
   (tests) the ratio is ~1.0-1.3, i.e. ProMiSH-A-grade quality at full MXU
   utilisation. The exact ProMiSH-E path (host-orchestrated, repro.core)
   re-scores the returned candidates when exactness is required.

2. ``distributed_nks_topk`` — the same tier on the device plane
   (``core.device_plane``): each shard holds a slice of every keyword group,
   phase A all_gathers the (q, R, d) groups, phase B keeps anchors
   partitioned (each device scores its local anchor slice), phase C merges
   per-shard top-k through ``device_plane.replicated_topk_merge``. The mesh/
   placement logic lives in :class:`~repro.core.device_plane.DevicePlane`,
   shared with the sharded batched-join dispatch — this module keeps only
   the single-shard kernel and thin compatibility wrappers.

``pack_groups`` moved to ``core.device_plane`` (it is placement logic: the
plane rounds R up to shard multiples); re-exported here unchanged.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.core.device_plane import (DevicePlane, PackedGroups,  # noqa: F401
                                     pack_groups)

BIG = np.float32(3.4e38)     # host constant: importing starts no backend


def _masked_sq_dists(a, b, b_mask):
    """(A,d) x (B,d) -> (A,B) squared L2 with invalid b masked to +BIG."""
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    ab = jnp.matmul(a, b.T, precision=jax.lax.Precision.HIGHEST)
    sq = jnp.sum(a * a, 1)[:, None] + jnp.sum(b * b, 1)[None, :] - 2.0 * ab
    sq = jnp.maximum(sq, 0.0)
    return jnp.where(b_mask[None, :], sq, BIG)


@functools.partial(jax.jit, static_argnames=("k",))
def nks_anchor_topk(groups, mask, ids, k: int, *, anchors=None,
                    anchor_mask=None, anchor_ids=None):
    """Anchor-star NKS top-k on one shard.

    groups (q, R, d) fp32; mask (q, R) bool; ids (q, R) int32 global ids.
    anchors (A, d) default groups[0]. Returns (diams (k,), cand_ids (k, q)).

    Points are centred before the distance math: the fp32
    ||a||^2+||b||^2-2ab identity cancels catastrophically for large
    coordinates (same contract as the Pallas join kernel — this tier is a
    fast filter; the engine rescores the k sets it returns in float64).
    Compiled as one program (op-by-op dispatch would compile every
    primitive per shape); the plane calls it inside its shard_map body.
    """
    q = groups.shape[0]
    center = jnp.sum(jnp.where(mask[..., None], groups, 0.0), axis=(0, 1)) \
        / jnp.maximum(jnp.sum(mask), 1)
    groups = groups - center
    if anchors is None:
        anchors, anchor_mask, anchor_ids = groups[0], mask[0], ids[0]
    else:
        anchors = anchors - center
    a = anchors.shape[0]

    members = [anchors[:, None, :]]                      # (A, 1, d)
    member_ids = [anchor_ids[:, None]]                   # (A, 1)
    worst_nn = jnp.zeros((a,), jnp.float32)
    for j in range(1, q):
        sq = _masked_sq_dists(anchors, groups[j], mask[j])   # (A, R)
        nn = jnp.argmin(sq, axis=1)                          # (A,)
        nn_d = jnp.take_along_axis(sq, nn[:, None], axis=1)[:, 0]
        worst_nn = jnp.maximum(worst_nn, nn_d)
        members.append(groups[j][nn][:, None, :])
        member_ids.append(ids[j][nn][:, None])

    tuples = jnp.concatenate(members, axis=1)            # (A, q, d)
    cand_ids = jnp.concatenate(member_ids, axis=1)       # (A, q)

    # exact diameter of each candidate (the paper's r(A) ranking)
    pts = tuples.astype(jnp.float32)
    sq = jnp.sum(pts * pts, -1)
    gram = jnp.einsum("aqd,ard->aqr", pts, pts,
                      precision=jax.lax.Precision.HIGHEST)
    d2 = jnp.maximum(sq[:, :, None] + sq[:, None, :] - 2.0 * gram, 0.0)
    diam = jnp.sqrt(jnp.max(d2, axis=(1, 2)))

    valid = anchor_mask & (worst_nn < BIG)
    diam = jnp.where(valid, diam, jnp.inf)
    neg, idx = jax.lax.top_k(-diam, k)
    return -neg, cand_ids[idx]


_PLANES: dict[tuple, DevicePlane] = {}


def distributed_nks_topk(mesh: Mesh, groups, mask, ids, k: int,
                         axis: str = "data"):
    """Sharded NKS top-k on the device plane. ``groups`` (q, R_total, d) is
    sharded on R over ``axis``; returns (diams (k,), ids (k, q)) fully
    replicated. Compatibility wrapper over ``DevicePlane.nks_topk``; planes
    are memoised per (mesh, axis) so repeat calls reuse the compiled
    shard_map program instead of retracing."""
    plane = _PLANES.get((mesh, axis))
    if plane is None:
        plane = _PLANES[(mesh, axis)] = DevicePlane(mesh, axis=axis)
    return plane.nks_topk(groups, mask, ids, k)


def search_step_specs(q: int, r_total: int, d: int, k: int):
    """ShapeDtypeStructs + PartitionSpecs for dry-running the serve step."""
    structs = (jax.ShapeDtypeStruct((q, r_total, d), jnp.float32),
               jax.ShapeDtypeStruct((q, r_total), jnp.bool_),
               jax.ShapeDtypeStruct((q, r_total), jnp.int32))
    specs = (P(None, "data", None), P(None, "data"), P(None, "data"))
    return structs, specs
