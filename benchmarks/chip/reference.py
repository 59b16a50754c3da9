"""Plain reference for nearest keyword set (NKS) queries.

Written from the problem's definition and independent of the system under
test: it imports nothing of ``src/repro`` and reads only the corpus arrays
that the benchmark's own generators make. A candidate for a query Q is a
set of points that together carry every keyword of Q; its diameter is the
largest pairwise L2 distance among its points. The exact answer at k=1 is
the candidate of smallest diameter.

* :func:`exact_top1` — branch and bound over the points of the query's
  smallest keyword group. Every candidate holds a point of that group, so
  anchoring on each of them in turn and keeping only members within the
  best diameter found so far is exhaustive. Screening distances come from
  the norms identity; every distance that decides the answer is computed
  from coordinate differences in the working precision.
* :func:`anchor_star_top1` — the device tier's semantics: each point of the
  query's first keyword anchors the set of its nearest point of every other
  keyword; the anchor set of smallest diameter wins.

Both take ``dtype``: ``float64`` is the reference; ``float32`` (exact) and
``bfloat16`` (anchor-star selection) are the lower-precision controls that
the correctness check must refuse.
"""
from __future__ import annotations

import numpy as np


class InvertedIndex:
    """Keyword -> sorted point ids, built from a point -> keywords CSR."""

    def __init__(self, kw_offsets: np.ndarray, kw_values: np.ndarray,
                 n_keywords: int):
        n = len(kw_offsets) - 1
        owner = np.repeat(np.arange(n, dtype=np.int64), np.diff(kw_offsets))
        order = np.argsort(kw_values, kind="stable")
        self.points = owner[order]
        self.starts = np.searchsorted(kw_values[order],
                                      np.arange(n_keywords + 1))

    def group(self, keyword: int) -> np.ndarray:
        return self.points[self.starts[keyword]:self.starts[keyword + 1]]

    def populated(self) -> np.ndarray:
        """Keywords carried by at least one point."""
        return np.flatnonzero(np.diff(self.starts) > 0)


def _diffs(a: np.ndarray, b: np.ndarray, dtype) -> np.ndarray:
    """Exact pairwise distances (len(a), len(b)) from coordinate differences."""
    d = a.astype(dtype)[:, None, :] - b.astype(dtype)[None, :, :]
    return np.sqrt(np.einsum("ijk,ijk->ij", d, d))


def _screen(a: np.ndarray, b: np.ndarray) -> tuple[np.ndarray, float]:
    """Float64 norms-identity distances and an absolute bound on their
    error (rounding of |a|^2 + |b|^2 - 2ab, taken generously)."""
    a = a.astype(np.float64)
    b = b.astype(np.float64)
    na = (a * a).sum(1)
    nb = (b * b).sum(1)
    sq = np.maximum(na[:, None] + nb[None, :] - 2.0 * (a @ b.T), 0.0)
    scale = float(na.max(initial=0.0) + nb.max(initial=0.0))
    return np.sqrt(sq), float(np.sqrt(64.0 * a.shape[1] * 2.0 ** -52 * scale))


def set_diameter(points: np.ndarray, ids, dtype=np.float64) -> float:
    """Largest pairwise distance among ``ids`` (0 for one point)."""
    ids = np.unique(np.asarray(ids, dtype=np.int64))
    if len(ids) < 2:
        return 0.0
    p = points[ids]
    return float(_diffs(p, p, dtype).max())


def exact_top1(points: np.ndarray, groups: list[np.ndarray],
               dtype=np.float64) -> tuple[float, tuple[int, ...]] | None:
    """Smallest-diameter candidate: (diameter, sorted ids), or None when a
    keyword has no point. Distances that decide the answer are computed in
    ``dtype``; screening uses float64 with a margin that keeps it exhaustive
    for either working precision."""
    if any(len(g) == 0 for g in groups):
        return None
    groups = sorted(groups, key=len)
    anchors, others = groups[0], groups[1:]
    if not others:
        return 0.0, (int(anchors[0]),)
    # f32 rounding of one distance is far below this, so the screen stays
    # exhaustive when the working precision is float32 too.
    tol_rel = 1e-5 if np.dtype(dtype) != np.float64 else 1e-9
    pa = points[anchors]
    screens = [_screen(pa, points[g]) for g in others]
    lb = np.max([s.min(axis=1) - e for s, e in screens], axis=0)
    # Upper bound: the anchor-star set of the anchor with the lowest bound.
    a0 = int(np.argmin(lb))
    start = [int(anchors[a0])] + [int(g[np.argmin(s[a0])])
                                   for g, (s, _) in zip(others, screens)]
    best_ids = tuple(sorted(set(start)))
    best = set_diameter(points, best_ids, dtype)
    for ai in np.argsort(lb, kind="stable"):
        if lb[ai] > best * (1 + tol_rel):
            break
        a = int(anchors[ai])
        cands = []
        for g, (s, e) in zip(others, screens):
            near = g[s[ai] <= best * (1 + tol_rel) + e]
            if len(near) == 0:
                break
            cands.append(near)
        if len(cands) != len(others):
            continue
        found = _best_with_anchor(points, a, cands, best, dtype)
        if found is not None and (found[0] < best or (
                found[0] == best and found[1] < best_ids)):
            best, best_ids = found
    return float(best), best_ids


def _best_with_anchor(points, a, cands, bound, dtype):
    """Smallest diameter over one member from each of ``cands`` together
    with point ``a``, if it is at most ``bound``: (diameter, ids) or None."""
    members = [np.asarray([a], np.int64)] + [np.asarray(c, np.int64)
                                             for c in cands]
    uniq = np.unique(np.concatenate(members))
    pos = {int(p): i for i, p in enumerate(uniq)}
    dist = _diffs(points[uniq], points[uniq], dtype)
    idx = [np.asarray([pos[int(p)] for p in m]) for m in members]
    best = None

    def walk(level, chosen, cur):
        nonlocal best, bound
        if level == len(idx) - 1:
            last = idx[level]
            worst = np.full(len(last), cur, dtype=dist.dtype)
            for c in chosen:
                worst = np.maximum(worst, dist[c, last])
            j = int(np.argmin(worst))
            diam = float(worst[j])
            if diam <= bound:
                ids = tuple(sorted({int(uniq[c]) for c in chosen}
                                   | {int(uniq[last[j]])}))
                if best is None or diam < best[0] or (
                        diam == best[0] and ids < best[1]):
                    best = (diam, ids)
                    bound = diam
            return
        for c in idx[level]:
            step = cur
            for p in chosen:
                step = max(step, float(dist[p, c]))
            if step <= bound:
                walk(level + 1, chosen + [int(c)], step)

    walk(1, [int(idx[0][0])], 0.0)
    if best is None:
        return None
    return float(set_diameter(points, best[1], dtype)), best[1]


def anchor_star_top1(points: np.ndarray, groups: list[np.ndarray],
                     select_dtype=np.float64
                     ) -> tuple[float, tuple[int, ...]] | None:
    """The anchor-star answer at k=1: (float64 diameter, sorted ids).

    ``groups[0]`` are the anchors. Nearest members are chosen with squared
    distances in ``select_dtype`` (coordinates rounded to it first, sums in
    at least float32); the chosen sets are ranked by their float64
    diameters, as the served tier ranks them."""
    if any(len(g) == 0 for g in groups):
        return None
    work = np.float64 if np.dtype(select_dtype) == np.float64 else np.float32

    def rounded(ids):
        return points[ids].astype(select_dtype).astype(work)

    anchors = groups[0]
    pa = rounded(anchors)
    members = [anchors]
    for g in groups[1:]:
        pb = rounded(g)
        sq = ((pa * pa).sum(1)[:, None] + (pb * pb).sum(1)[None, :]
              - 2.0 * (pa @ pb.T))
        members.append(g[np.argmin(sq, axis=1)])
    sets = np.stack(members, axis=1)                       # (A, q)
    pts = points[sets].astype(np.float64)                  # (A, q, d)
    diff = pts[:, :, None, :] - pts[:, None, :, :]
    diam = np.sqrt(np.einsum("aijd,aijd->aij", diff, diff).max(axis=(1, 2)))
    t = int(np.argmin(diam))
    return float(diam[t]), tuple(sorted(set(int(x) for x in sets[t])))
