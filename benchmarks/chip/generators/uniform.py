"""The paper's §VIII synthetic corpus.

Each coordinate is uniform in [0, ``coord_range``]; each point carries ``t``
distinct keywords drawn uniformly from a dictionary of ``u``. The random
stream for t=1 is the one ``repro.data.synthetic.synthetic_dataset`` draws
(points, then keywords), so the same seed gives the same corpus.
"""
from __future__ import annotations

import numpy as np


def generate(cfg: dict, rng: np.random.Generator):
    """Returns (points (n, d) float32, kw_offsets (n+1,), kw_values)."""
    n, d, u, t = cfg["n"], cfg["d"], cfg["u"], cfg["t"]
    points = rng.uniform(0.0, cfg["coord_range"], size=(n, d)) \
        .astype(np.float32)
    if t == 1:
        kws = rng.integers(0, u, size=(n, 1))
    else:
        # t distinct keywords a point, uniform without replacement
        kws = np.sort(np.argsort(rng.random((n, u)), axis=1)[:, :t], axis=1)
    offsets = np.arange(0, n * t + 1, t, dtype=np.int64)
    return points, offsets, kws.reshape(-1).astype(np.int32)
