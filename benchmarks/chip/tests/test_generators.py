"""The benchmark's generators against the program's ones, at small N."""
import numpy as np
import pytest

import harness
from repro.data.flickr_like import flickr_like_dataset
from repro.data.synthetic import synthetic_dataset

FLICKR = dict(n=6000, d=8, u=3000, t=11, n_clusters=16, zipf_a=1.3,
              affinity=0.7)


def _assign(seed, cfg):
    """The cluster of every point: both generators draw centres, scales
    and assignments first, in that order."""
    rng = np.random.default_rng(seed)
    rng.uniform(0.0, 255.0, size=(cfg["n_clusters"], cfg["d"]))
    rng.uniform(4.0, 24.0, size=(cfg["n_clusters"], 1))
    return rng.integers(0, cfg["n_clusters"], size=cfg["n"])


def _stats(offsets, values, assign, cfg):
    tags = np.diff(offsets)
    freq = np.bincount(values, minlength=cfg["u"]).astype(float)
    ranked = np.sort(freq)[::-1]
    # Zipf slope over ranks 2..100 of the rank-frequency curve.
    r = np.arange(2, 101)
    slope = np.polyfit(np.log(r), np.log(ranked[r - 1]), 1)[0]
    # Affinity: share of a cluster's tag slots taken by its 4t most
    # frequent tags, averaged over clusters.
    owner = np.repeat(assign, tags)
    share = []
    for c in range(cfg["n_clusters"]):
        f = np.bincount(values[owner == c], minlength=cfg["u"])
        share.append(np.sort(f)[::-1][:4 * cfg["t"]].sum() / f.sum())
    return tags.mean(), ranked[0] / cfg["n"], slope, float(np.mean(share))


def test_flickr_matches_the_program_generator():
    seed = 3
    gen = harness.generator("flickr")
    pts, off, val = gen(FLICKR, np.random.default_rng(seed))
    assert pts.shape == (FLICKR["n"], FLICKR["d"]) and pts.dtype == np.float32
    rows = [val[off[i]:off[i + 1]] for i in range(FLICKR["n"])]
    assert all((np.diff(r) > 0).all() for r in rows)     # sorted, distinct
    ours = _stats(off, val, _assign(seed, FLICKR), FLICKR)

    ds = flickr_like_dataset(**FLICKR, seed=seed)
    theirs = _stats(ds.kw.offsets, ds.kw.values, _assign(seed, FLICKR),
                    FLICKR)
    tags, top, slope, share = ours
    assert tags == pytest.approx(theirs[0], rel=0.01)
    assert top == pytest.approx(theirs[1], rel=0.15)
    assert slope == pytest.approx(theirs[2], abs=0.15)
    assert share == pytest.approx(theirs[3], abs=0.03)
    # the same points, drawn from the same laws
    assert pts.mean() == pytest.approx(ds.points.mean(), rel=0.05)
    assert pts.std() == pytest.approx(ds.points.std(), rel=0.05)


def test_uniform_is_the_paper_generator():
    cfg = dict(n=500, d=4, u=20, t=1, coord_range=10000.0)
    pts, off, val = harness.generator("uniform")(cfg,
                                                 np.random.default_rng(9))
    ds = synthetic_dataset(n=500, d=4, u=20, t=1, seed=9)
    assert np.array_equal(pts, ds.points)
    assert np.array_equal(off, ds.kw.offsets)
    assert np.array_equal(val, ds.kw.values)
