"""The plain reference against exhaustive enumeration on small corpora."""
import itertools

import numpy as np
import pytest

import harness
import reference

CFG = dict(n=300, d=6, u=25, coord_range=100.0, n_clusters=4, zipf_a=1.3,
           affinity=0.7)


def _corpus(seed):
    gen = "flickr" if seed % 2 else "uniform"
    cfg = dict(CFG, t=3 if seed % 2 else 1)
    pts, off, val = harness.generator(gen)(cfg, np.random.default_rng(seed))
    idx = reference.InvertedIndex(off, val, cfg["u"])
    rng = np.random.default_rng(seed + 100)
    q = sorted(rng.choice(idx.populated(), 3, replace=False).tolist())
    return pts, [idx.group(v) for v in q]


def _all_tuples(pts, groups):
    for combo in itertools.product(*groups):
        ids = tuple(sorted(set(int(c) for c in combo)))
        yield reference.set_diameter(pts, ids), ids


@pytest.mark.parametrize("seed", range(16))
def test_exact_top1_is_the_smallest_diameter(seed):
    pts, groups = _corpus(seed)
    want = min(d for d, _ in _all_tuples(pts, groups))
    got = reference.exact_top1(pts, groups)
    assert got[0] == want
    assert reference.set_diameter(pts, got[1]) == got[0]


@pytest.mark.parametrize("seed", range(8))
def test_anchor_star_by_definition(seed):
    pts, groups = _corpus(seed)
    p = pts.astype(np.float64)
    best = None
    for a in groups[0]:
        ids = [int(a)] + [int(g[np.argmin(((p[g] - p[a]) ** 2).sum(1))])
                          for g in groups[1:]]
        ids = tuple(sorted(set(ids)))
        d = reference.set_diameter(pts, ids)
        best = (d, ids) if best is None or d < best[0] else best
    got = reference.anchor_star_top1(pts, groups)
    assert got[0] == pytest.approx(best[0], rel=1e-12)


def test_empty_group_has_no_answer():
    pts = np.zeros((3, 2), np.float32)
    assert reference.exact_top1(pts, [np.array([0]), np.array([], int)]) \
        is None
