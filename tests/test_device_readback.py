"""Device tier readback: the anchor-star program's two outputs reach the
host in one ``jax.device_get``, and the answers through ``query`` and
``query_batch`` are the float64 anchor-star answer, on one device and on a
one-shard plane."""
import importlib.util
import math
import pathlib

import jax
import numpy as np
import pytest

from repro.core import brute_force
from repro.core.types import make_dataset
from repro.serve.engine import NKSEngine

ROOT = pathlib.Path(__file__).resolve().parents[1]
RARE = 5          # keyword carried by two points only


@pytest.fixture(scope="module")
def anchor_star():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.anchor_star


@pytest.fixture(scope="module")
def ds():
    rng = np.random.default_rng(15)
    points = rng.uniform(0.0, 100.0, (240, 8))
    keywords = [[i % 5] for i in range(240)]
    keywords[17].append(RARE)
    keywords[101].append(RARE)
    return make_dataset(points, keywords)


@pytest.fixture(scope="module", params=["single", "plane"])
def engine(request, ds):
    mesh = None
    if request.param == "plane":
        from repro.launch.mesh import make_local_mesh
        mesh = make_local_mesh(data=1, model=1)
    return NKSEngine(ds, m=2, n_scales=3, seed=0, mesh=mesh,
                     build_exact=False, build_approx=False)


def _serve(engine, entry, query, k):
    if entry == "query":
        return engine.query(query, k=k, tier="device").candidates
    return engine.query_batch([query], k=k, tier="device")[0].candidates


@pytest.mark.parametrize("entry", ["query", "query_batch"])
@pytest.mark.parametrize("k", [1, 2, 4])
@pytest.mark.parametrize("query", [[0, 1, 2], [3, 4], [RARE, 0, 3]])
def test_device_tier_matches_float64_anchor_star(engine, anchor_star, entry,
                                                 query, k):
    """Rank by rank the float64 reference's set (or one tied with it to fp32
    rounding) at its exact float64 diameter; a query whose first keyword has
    fewer points than k drops the program's infinite rows."""
    got = _serve(engine, entry, query, k)
    want = anchor_star(engine.dataset, query, k)
    assert len(got) == len(want) == min(k, len(engine.dataset.points_with(
        query[0])))
    for c, (ids, diam) in zip(got, want):
        assert all(isinstance(i, int) for i in c.ids)
        assert c.diameter == brute_force.set_diameter(c.ids, engine.dataset)
        assert c.ids == ids or math.isclose(c.diameter, diam, rel_tol=1e-5)


class _Watched:
    """A device output that counts every element-wise or implicit read."""

    def __init__(self, array, reads):
        self.array, self._reads = array, reads

    def _read(self, name):
        self._reads.append(name)

    def __getitem__(self, i):
        self._read("__getitem__")
        return self.array[i]

    def __iter__(self):
        self._read("__iter__")
        return iter(self.array)

    def __int__(self):
        self._read("__int__")
        return int(self.array)

    def __float__(self):
        self._read("__float__")
        return float(self.array)

    def __array__(self, *args, **kw):
        self._read("__array__")
        return np.asarray(self.array, *args, **kw)


@pytest.mark.parametrize("entry", ["query", "query_batch"])
@pytest.mark.parametrize("k", [1, 4])
def test_readback_is_one_transfer_of_both_outputs(engine, monkeypatch,
                                                  entry, k):
    """One ``jax.device_get`` of both outputs a query, and no device array
    read element by element; the answer is the one served unwatched."""
    import repro.core.distributed as distributed
    query = [RARE, 0, 3]
    unwatched = _serve(engine, entry, query, k)
    reads, transfers = [], []

    def watch(topk):
        def wrapped(*args):
            diams, cids = topk(*args)
            return _Watched(diams, reads), _Watched(cids, reads)
        return wrapped

    real_device_get = jax.device_get

    def device_get(x):
        transfers.append(x)
        return real_device_get(jax.tree.map(
            lambda y: y.array if isinstance(y, _Watched) else y, x,
            is_leaf=lambda y: isinstance(y, _Watched)))

    if engine.plane is not None:
        monkeypatch.setattr(engine.plane, "nks_topk",
                            watch(engine.plane.nks_topk))
    else:
        monkeypatch.setattr(distributed, "nks_anchor_topk",
                            watch(distributed.nks_anchor_topk))
    monkeypatch.setattr(jax, "device_get", device_get)
    got = _serve(engine, entry, query, k)
    assert reads == []
    assert len(transfers) == 1
    leaves = jax.tree.leaves(transfers[0],
                             is_leaf=lambda y: isinstance(y, _Watched))
    assert len(leaves) == 2 and all(isinstance(y, _Watched) for y in leaves)
    assert got == unwatched
