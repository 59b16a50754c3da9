"""Flickr-like clustered corpus with Zipf tags, vectorised.

The distributions are those of ``repro.data.flickr_like.flickr_like_dataset``:

* ``n_clusters`` Gaussian clusters, centres uniform in [0, 255]^d, one
  isotropic scale per cluster uniform in [4, 24];
* tag popularity Zipf(``zipf_a``) over the dictionary of ``u``; each cluster
  draws a pool of max(4t, 16) distinct tags by popularity;
* each point takes round(t * ``affinity``) distinct tags uniformly from its
  cluster's pool and the rest of its ``t`` from the global Zipf law (with
  replacement), and keeps the distinct ones.

That generator loops over points in Python; this one draws the same laws in
blocks of rows, so a million points take seconds. The random streams differ,
so one seed gives another corpus with the same statistics.
"""
from __future__ import annotations

import numpy as np

_BLOCK = 1 << 17


def generate(cfg: dict, rng: np.random.Generator):
    """Returns (points (n, d) float32, kw_offsets (n+1,), kw_values)."""
    n, d, u, t = cfg["n"], cfg["d"], cfg["u"], cfg["t"]
    n_clusters = cfg["n_clusters"]
    centers = rng.uniform(0.0, 255.0, size=(n_clusters, d)).astype(np.float32)
    scales = rng.uniform(4.0, 24.0, size=(n_clusters, 1)).astype(np.float32)
    assign = rng.integers(0, n_clusters, size=n)
    points = centers[assign] + rng.standard_normal(
        (n, d), dtype=np.float32) * scales[assign]

    pop = np.arange(1, u + 1, dtype=np.float64) ** (-cfg["zipf_a"])
    pop /= pop.sum()
    cdf = np.cumsum(pop)
    cdf[-1] = 1.0
    pool_size = max(t * 4, 16)
    pools = np.stack([rng.choice(u, size=pool_size, replace=False, p=pop)
                      for _ in range(n_clusters)])
    n_aff = min(int(round(t * cfg["affinity"])), pool_size)
    n_glob = t - n_aff

    rows = np.empty((n, t), np.int64)
    for lo in range(0, n, _BLOCK):
        hi = min(n, lo + _BLOCK)
        pick = np.argsort(rng.random((hi - lo, pool_size)), axis=1)[:, :n_aff]
        rows[lo:hi, :n_aff] = pools[assign[lo:hi, None], pick]
        rows[lo:hi, n_aff:] = np.searchsorted(
            cdf, rng.random((hi - lo, n_glob)), side="right")
    rows.sort(axis=1)
    keep = np.ones_like(rows, dtype=bool)
    keep[:, 1:] = rows[:, 1:] != rows[:, :-1]
    offsets = np.zeros(n + 1, np.int64)
    np.cumsum(keep.sum(axis=1), out=offsets[1:])
    return points.astype(np.float32), offsets, rows[keep].astype(np.int32)
