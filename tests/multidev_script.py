"""Multi-device correctness script, run in a subprocess with 8 forced host
devices (tests/test_multidevice.py drives it). Asserts:

  1. distributed_nks_topk (shard_map over data axis) == single-device
     anchor-star result;
  2. compressed_psum over the pod axis == exact mean within int8 quant error;
  3. pipeline_forward (ppermute GPipe) == sequential layer application;
  4. the dryrun entry-point machinery works on a small mesh (sanity).
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"


import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.distributed import (distributed_nks_topk, nks_anchor_topk,
                                    pack_groups)
from repro.data.synthetic import random_queries, synthetic_dataset
from repro.launch.mesh import make_local_mesh
from repro.train.grad_compress import compressed_psum
from repro.train.pipeline_parallel import pipeline_forward


def test_distributed_nks():
    """Parity on a forced 8-device CPU mesh: distributed_nks_topk (now
    rebuilt on core.device_plane) == the single-device anchor-star kernel,
    and == DevicePlane.nks_topk (the wrapper and the plane share one
    program)."""
    from repro.core.device_plane import DevicePlane
    mesh = make_local_mesh(data=8, model=1)
    plane = DevicePlane(mesh)
    ds = synthetic_dataset(n=2000, d=12, u=20, t=2, seed=1)
    for query in random_queries(ds, 3, 3, seed=5):
        groups, mask, ids = pack_groups(ds, query, r_max=256)
        # single device
        d1, c1 = nks_anchor_topk(jnp.asarray(groups), jnp.asarray(mask),
                                 jnp.asarray(ids), k=3)
        # sharded, via the compatibility wrapper and via the plane directly
        with mesh:
            d8, c8 = distributed_nks_topk(mesh, jnp.asarray(groups),
                                          jnp.asarray(mask), jnp.asarray(ids),
                                          k=3)
        dp, cp = plane.nks_topk(jnp.asarray(groups), jnp.asarray(mask),
                                jnp.asarray(ids), k=3)
        np.testing.assert_allclose(np.asarray(d8), np.asarray(d1), rtol=1e-5,
                                   err_msg=f"query={query}")
        np.testing.assert_array_equal(np.asarray(dp), np.asarray(d8),
                                      err_msg=f"query={query}")
        np.testing.assert_array_equal(np.asarray(cp), np.asarray(c8),
                                      err_msg=f"query={query}")
    print("distributed_nks ok")


def test_compressed_psum():
    mesh = make_local_mesh(data=1, model=1, pod=8)
    rng = np.random.default_rng(0)
    g_all = jnp.asarray(rng.standard_normal((8, 32)), jnp.float32)

    def body(g):
        buf = {"g": jnp.zeros_like(g)}
        red, _ = compressed_psum({"g": g}, buf, "pod")
        return red["g"]

    fn = jax.shard_map(body, mesh=mesh, in_specs=(P("pod", None),),
                       out_specs=P("pod", None), check_vma=False)
    with mesh:
        out = fn(g_all)
    true_mean = np.asarray(g_all).mean(axis=0)
    got = np.asarray(out)[0]
    amax = np.abs(np.asarray(g_all)).max()
    assert np.abs(got - true_mean).max() <= amax / 127.0 + 1e-6
    # every shard holds the same reduced value
    np.testing.assert_allclose(np.asarray(out), np.tile(got, (8, 1)), rtol=1e-6)
    print("compressed_psum ok")


def test_pipeline_forward():
    mesh = make_local_mesh(data=1, model=1, pod=8)
    n_stages, m, dim = 8, 16, 32
    rng = np.random.default_rng(2)
    w_all = jnp.asarray(rng.standard_normal((n_stages, dim, dim)) * 0.2,
                        jnp.float32)
    x = jnp.asarray(rng.standard_normal((m, dim)), jnp.float32)

    def stage_fn_factory(w_local):
        def stage_fn(h, t):
            del t
            return jnp.tanh(h @ w_local[0])
        return stage_fn

    def body(w_local, mb):
        out = pipeline_forward(stage_fn_factory(w_local), w_local, mb,
                               axis_name="pod")
        return out[None]                      # add the stage axis for out_specs

    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P("pod", None, None), P(None, None)),
                       out_specs=P("pod", None, None), check_vma=False)
    with mesh:
        out = fn(w_all, x)                    # (8, M, dim) per stage
    got = np.asarray(out)[-1]                 # last stage's outputs
    # sequential reference
    ref = np.asarray(x)
    for s in range(n_stages):
        ref = np.tanh(ref @ np.asarray(w_all[s]))
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-5)
    print("pipeline_forward ok")


def test_search_step_lowering():
    """The distributed NKS serve step lowers+compiles on a (data, model) mesh."""
    mesh = make_local_mesh(data=8, model=1)
    from repro.core.distributed import search_step_specs
    structs, specs = search_step_specs(q=4, r_total=1024, d=64, k=5)
    with mesh:
        def fn(g, m_, i):
            return distributed_nks_topk(mesh, g, m_, i, k=5)
        from jax.sharding import NamedSharding
        shardings = tuple(NamedSharding(mesh, s) for s in specs)
        lowered = jax.jit(fn, in_shardings=shardings).lower(*structs)
        compiled = lowered.compile()
        assert compiled.cost_analysis() is not None
    print("search_step lowering ok")


def test_flash_attention_shardmap():
    """The shard_map-wrapped Pallas flash path (interpret) == the jnp scan,
    on a real (data, model) mesh — validates the TPU wiring end to end."""
    import jax
    from repro.models import hints
    from repro.models.common import blockwise_attention

    mesh = make_local_mesh(data=4, model=2)
    b, s, h, hd = 4, 64, 2, 16
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (b, s, h, hd), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, h, hd), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, h, hd), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))

    def attn(q, k, v):
        return blockwise_attention(q, k, v, pos, pos, causal=True,
                                   window=None, block=16)

    want = np.asarray(attn(q, k, v))                  # jnp path (no flash)
    os.environ["REPRO_FLASH_INTERPRET"] = "1"
    hints.enable_hints_mesh(mesh, ("data",), "model")
    try:
        with mesh:
            got = np.asarray(jax.jit(attn)(q, k, v))
    finally:
        del os.environ["REPRO_FLASH_INTERPRET"]
        hints.disable_hints()
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)
    print("flash shard_map ok")


if __name__ == "__main__":
    test_distributed_nks()
    test_compressed_psum()
    test_pipeline_forward()
    test_search_step_lowering()
    test_flash_attention_shardmap()
    print("ALL MULTIDEV OK")
