"""Shared model components (pure-function JAX, param pytrees, no framework).

Conventions:
  * activations bf16, parameters fp32 (cast at use — mixed precision),
    softmax/log-sum-exp accumulation fp32;
  * attention is **blockwise online-softmax** over KV chunks (lax.scan):
    O(S * C) live memory instead of O(S^2), which is what lets prefill_32k
    and train_4k fit per-device HBM without a custom kernel;
  * GQA everywhere: q heads grouped over n_kv_heads; n_heads need not divide
    the TP axis (GSPMD pads uneven shards).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.models.hints import hint, tp_size

Params = dict[str, Any]
ACT_DTYPE = jnp.bfloat16


# --------------------------------------------------------------------- init
def dense_init(key, shape, scale: float = 0.02, dtype=jnp.float32):
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def split_keys(key, n: int):
    return list(jax.random.split(key, n))


# -------------------------------------------------------------------- norms
def rmsnorm(x, w, eps: float = 1e-5):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def layernorm(x, w, b, eps: float = 1e-5):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(x.dtype)


def init_norm(key, d: int, kind: str) -> Params:
    if kind == "rmsnorm":
        return {"w": jnp.ones((d,), jnp.float32)}
    return {"w": jnp.ones((d,), jnp.float32), "b": jnp.zeros((d,), jnp.float32)}


def apply_norm(p: Params, x, kind: str, eps: float):
    if kind == "rmsnorm":
        return rmsnorm(x, p["w"], eps)
    return layernorm(x, p["w"], p["b"], eps)


# --------------------------------------------------------------------- RoPE
def rope_angles(positions, head_dim: int, theta: float):
    """positions (..., S) int32 -> (cos, sin) each (..., S, head_dim/2) fp32."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin):
    """x (..., S, H, hd); cos/sin (..., S, hd/2) broadcast over heads."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[..., None, :]
    s = sin[..., None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1).astype(x.dtype)


# ---------------------------------------------------------------- attention
@dataclasses.dataclass(frozen=True)
class AttnSpec:
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_model: int
    qk_norm: bool = False
    bias: bool = False
    causal: bool = True
    window: int | None = None       # sliding-window width (tokens) or None
    rope_theta: float | None = 10_000.0


def init_attention(key, spec: AttnSpec) -> Params:
    """Head-axis-explicit weight layout (D, H, hd): the head axis is a real
    tensor axis so TP sharding is head-aligned (GSPMD pads uneven H/TP)."""
    ks = split_keys(key, 4)
    h, kv, hd, d = spec.n_heads, spec.n_kv_heads, spec.head_dim, spec.d_model
    p: Params = {
        "wq": dense_init(ks[0], (d, h, hd)),
        "wk": dense_init(ks[1], (d, kv, hd)),
        "wv": dense_init(ks[2], (d, kv, hd)),
        "wo": dense_init(ks[3], (h, hd, d)),
    }
    if spec.bias:
        p["bq"] = jnp.zeros((h, hd), jnp.float32)
        p["bk"] = jnp.zeros((kv, hd), jnp.float32)
        p["bv"] = jnp.zeros((kv, hd), jnp.float32)
    if spec.qk_norm:
        p["qn"] = jnp.ones((hd,), jnp.float32)
        p["kn"] = jnp.ones((hd,), jnp.float32)
    return p


def _project_qkv(p: Params, spec: AttnSpec, x, positions):
    """x (B,S,D) -> q (B,S,H,hd), k/v (B,S,Kv,hd), rope applied."""
    h, kv, hd = spec.n_heads, spec.n_kv_heads, spec.head_dim
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(x.dtype))
    if spec.bias:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    if spec.qk_norm:
        q = rmsnorm(q, p["qn"])
        k = rmsnorm(k, p["kn"])
    if spec.rope_theta is not None:
        cos, sin = rope_angles(positions, hd, spec.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    # pin heads to the TP axis — GSPMD loses this through the attention scan
    q = hint(q, "dp", None, "tp", None)
    k = hint(k, "dp", None, "tp", None)
    v = hint(v, "dp", None, "tp", None)
    return q, k, v


def _try_flash(q, k, v, g: int, *, causal: bool, window: int | None):
    """Dispatch to the fused Pallas flash kernel when viable (TPU backend, or
    interpret mode under REPRO_FLASH_INTERPRET=1 for tests). Returns None to
    fall through to the jnp scan."""
    import os
    interpret = os.environ.get("REPRO_FLASH_INTERPRET") == "1"
    if jax.default_backend() != "tpu" and not interpret:
        return None
    import functools

    from repro.kernels.flash_attention import flash_attention
    from repro.models import hints as hints_mod

    kf = jnp.repeat(k, g, axis=2) if g > 1 else k
    vf = jnp.repeat(v, g, axis=2) if g > 1 else v
    b, s, h, hd = q.shape
    if kf.shape[1] != s:
        return None                       # flash path assumes self-attention
    fn = functools.partial(flash_attention, causal=causal, window=window,
                           interpret=interpret,
                           bq=min(512, s), bk=min(512, s))
    ctx = hints_mod.active()
    mesh = (ctx or {}).get("mesh")
    if mesh is None:
        return fn(q, kf, vf)
    from jax.sharding import PartitionSpec as P
    dp, tp = ctx["dp"], ctx["tp"]
    dp_n = hints_mod._axis_size(dp)
    tp_n = hints_mod._axis_size(tp)
    if b % dp_n or h % tp_n:
        return None
    spec = P(dp, None, tp, None)
    sm = jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)
    return sm(q, kf, vf)


def blockwise_attention(q, k, v, q_pos, kv_pos, *, causal: bool,
                        window: int | None, kv_mask=None, block: int = 1024):
    """Online-softmax attention over KV blocks.

    q (B,S,H,hd); k,v (B,T,Kv,hd); q_pos (B,S); kv_pos (B,T).
    Returns (B,S,H,hd).

    Numerics: dots run in the input dtype (bf16) with fp32 accumulation
    (``preferred_element_type`` — MXU-native); softmax statistics in fp32.
    Memory: the KV loop is an index-carried scan with ``dynamic_slice``
    gathers and masks computed inline from the loop counter — passing stacked
    per-block masks as scan inputs lets XLA hoist one pred[nblk,B,S,Kv,g,C]
    tensor out of the loop (~4 GB/device at 32k; EXPERIMENTS.md §Perf iter 3).
    """
    b, s, h, hd = q.shape
    t = k.shape[1]
    kv_heads = k.shape[2]
    g = h // kv_heads

    # GQA head expansion (§Perf iter 6): when kv_heads doesn't divide TP but
    # the q-head count does, the grouped (Kv, g) layout can't shard — the
    # whole score computation replicates across the model axis (measured 16x
    # on qwen3 prefill). Expanding K/V to per-q-head layout costs a g-fold
    # K/V copy (sharded h/TP ways, so per-device bytes stay ~flat) and makes
    # every attention tensor shard on the head dim. Decode (s == 1) keeps
    # the grouped layout: expanding would multiply cache reads by g.
    tp = tp_size()
    if s > 1 and g > 1 and kv_heads % tp != 0 and h % tp == 0:
        k = hint(jnp.repeat(k, g, axis=2), "dp", None, "tp", None)
        v = hint(jnp.repeat(v, g, axis=2), "dp", None, "tp", None)
        kv_heads, g = h, 1

    # Fused flash kernel (§Perf iter 7) on TPU: scores/probabilities stay in
    # VMEM instead of round-tripping HBM every KV block (the single largest
    # memory-term contributor measured on prefill_32k). pallas_call is opaque
    # to GSPMD, so it is shard_map-wrapped over (dp: batch, tp: heads); falls
    # through to the jnp scan when shapes don't divide the mesh or on CPU.
    if s > 1 and kv_mask is None:
        out = _try_flash(q, k, v, g, causal=causal, window=window)
        if out is not None:
            return out

    qg = q.reshape(b, s, kv_heads, g, hd)
    scale = jnp.float32(1.0 / float(hd) ** 0.5)
    f32 = jnp.float32

    def qk(qq, kk):
        # (B,S,Kv,g,hd) x (B,C,Kv,hd) -> (B,Kv,S,g,C), fp32 accumulation
        return jax.lax.dot_general(
            qq, kk, (((4,), (3,)), ((0, 2), (0, 2))),
            preferred_element_type=f32)

    def pv(p_att, vv):
        # (B,Kv,S,g,C) x (B,C,Kv,hd) -> (B,Kv,S,g,hd)
        return jax.lax.dot_general(
            p_att.astype(vv.dtype), vv, (((4,), (1,)), ((0, 1), (0, 2))),
            preferred_element_type=f32)

    def finish(out):
        return out.transpose(0, 2, 1, 3, 4).reshape(b, s, h, hd).astype(q.dtype)

    if s == 1 or t <= 4 * block:
        # Direct path: decode (one query over the whole cache — keeps the KV
        # seq dim shardable) and short sequences (train_4k): no scan carries,
        # no stacked KV copies, one fused softmax.
        sc = qk(qg, k) * scale                           # (B,Kv,S,g,T)
        valid = (kv_mask if kv_mask is not None else (kv_pos >= 0))[:, None, :]
        if causal:
            valid = valid & (kv_pos[:, None, :] <= q_pos[:, :, None])
        if window is not None:
            valid = valid & (kv_pos[:, None, :] > q_pos[:, :, None] - window)
        sc = jnp.where(valid[:, None, :, None, :], sc, f32(-1e30))
        p_att = jax.nn.softmax(sc, axis=-1)
        return finish(pv(p_att, v))

    nblk = -(-t // block)
    pad = nblk * block - t
    kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    posp = jnp.pad(kv_pos, ((0, 0), (0, pad)), constant_values=-1)
    maskp = jnp.pad(kv_mask, ((0, 0), (0, pad)), constant_values=False) \
        if kv_mask is not None else None

    neg = f32(-1e30)

    def step(carry, i):
        m_run, l_run, acc = carry
        k_c = jax.lax.dynamic_slice_in_dim(kp, i * block, block, 1)
        v_c = jax.lax.dynamic_slice_in_dim(vp, i * block, block, 1)
        p_c = jax.lax.dynamic_slice_in_dim(posp, i * block, block, 1)
        sc = qk(qg, k_c) * scale                         # (B,Kv,S,g,C)
        valid = p_c[:, None, :] >= 0
        if maskp is not None:
            valid = valid & jax.lax.dynamic_slice_in_dim(
                maskp, i * block, block, 1)[:, None, :]
        if causal:
            valid = valid & (p_c[:, None, :] <= q_pos[:, :, None])
        if window is not None:
            valid = valid & (p_c[:, None, :] > q_pos[:, :, None] - window)
        sc = jnp.where(valid[:, None, :, None, :], sc, neg)
        m_new = jnp.maximum(m_run, sc.max(axis=-1))
        alpha = jnp.exp(m_run - m_new)
        p_att = jnp.exp(sc - m_new[..., None])
        l_new = l_run * alpha + p_att.sum(axis=-1)
        acc_new = acc * alpha[..., None] + pv(p_att, v_c)
        return (m_new, l_new, acc_new), None

    m0 = hint(jnp.full((b, kv_heads, s, g), -jnp.inf, f32),
              "dp", "tp", None, None)
    l0 = hint(jnp.zeros((b, kv_heads, s, g), f32), "dp", "tp", None, None)
    a0 = hint(jnp.zeros((b, kv_heads, s, g, hd), f32),
              "dp", "tp", None, None, None)
    (m_f, l_f, acc), _ = jax.lax.scan(step, (m0, l0, a0),
                                      jnp.arange(nblk, dtype=jnp.int32))
    out = acc / jnp.maximum(l_f, 1e-30)[..., None]       # (B,Kv,S,g,hd)
    return finish(out)


def self_attention(p: Params, spec: AttnSpec, x, positions, *,
                   cache: Params | None = None, block: int = 1024):
    """Full self-attention (train/prefill when cache is None; one-step decode
    when cache holds {"k","v","pos"}). Returns (out (B,S,D), new_cache)."""
    b, s, _ = x.shape
    q, k, v = _project_qkv(p, spec, x, positions)
    if cache is None:
        out = blockwise_attention(q, k, v, positions, positions,
                                  causal=spec.causal, window=spec.window,
                                  block=block)
        new_cache = {"k": k, "v": v}
    else:
        pos = cache["pos"]                               # scalar int32
        k_all = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                             (0, pos, 0, 0))
        v_all = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                             (0, pos, 0, 0))
        t = k_all.shape[1]
        kv_pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None], (b, t))
        kv_mask = kv_pos[0] <= pos                       # (t,)
        out = blockwise_attention(q, k_all, v_all, positions, kv_pos,
                                  causal=spec.causal, window=spec.window,
                                  kv_mask=jnp.broadcast_to(kv_mask[None], (b, t)),
                                  block=block)
        new_cache = {"k": k_all, "v": v_all}
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return y, new_cache


def cross_kv(p: Params, spec: AttnSpec, kv_src):
    """Project cross-attention keys/values from memory tokens (B,T,D) —
    cached once per request in serving."""
    b, t, _ = kv_src.shape
    kv, hd = spec.n_kv_heads, spec.head_dim
    k = jnp.einsum("btd,dhk->bthk", kv_src, p["wk"].astype(kv_src.dtype))
    v = jnp.einsum("btd,dhk->bthk", kv_src, p["wv"].astype(kv_src.dtype))
    if spec.qk_norm:
        k = rmsnorm(k, p["kn"])
    return k, v


def cross_attention(p: Params, spec: AttnSpec, x, kv_src=None, *, k=None,
                    v=None, block: int = 1024):
    """Cross-attention: queries from x (B,S,D), keys/values from kv_src
    (B,T,D) or precomputed (k, v) — no RoPE, no causality."""
    b, s, _ = x.shape
    h, kv_h, hd = spec.n_heads, spec.n_kv_heads, spec.head_dim
    if k is None:
        k, v = cross_kv(p, spec, kv_src)
    t = k.shape[1]
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    if spec.qk_norm:
        q = rmsnorm(q, p["qn"])
    pos_q = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    pos_k = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None], (b, t))
    out = blockwise_attention(q, k, v, pos_q, pos_k, causal=False, window=None,
                              block=block)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))


# ---------------------------------------------------------------------- MLP
def init_mlp(key, d: int, f: int, kind: str) -> Params:
    ks = split_keys(key, 3)
    if kind == "swiglu":
        return {"w1": dense_init(ks[0], (d, f)), "w3": dense_init(ks[1], (d, f)),
                "w2": dense_init(ks[2], (f, d))}
    return {"w1": dense_init(ks[0], (d, f)), "b1": jnp.zeros((f,), jnp.float32),
            "w2": dense_init(ks[1], (f, d)), "b2": jnp.zeros((d,), jnp.float32)}


def _hint_hidden(h):
    return hint(h, "dp", "tp") if h.ndim == 2 else hint(h, "dp", None, "tp")


def apply_mlp(p: Params, x, kind: str):
    if kind == "swiglu":
        h = jax.nn.silu(x @ p["w1"].astype(x.dtype)) * (x @ p["w3"].astype(x.dtype))
        return _hint_hidden(h) @ p["w2"].astype(x.dtype)
    h = jax.nn.gelu(x @ p["w1"].astype(x.dtype) + p["b1"].astype(x.dtype))
    return _hint_hidden(h) @ p["w2"].astype(x.dtype) + p["b2"].astype(x.dtype)


# ------------------------------------------------------------- embed / head
VOCAB_ALIGN = 128   # pad vocab to a TP- and MXU-aligned multiple (Megatron-style)


def padded_vocab(vocab: int) -> int:
    return ((vocab + VOCAB_ALIGN - 1) // VOCAB_ALIGN) * VOCAB_ALIGN


def init_embed(key, vocab: int, d: int, tie: bool) -> Params:
    """Embedding table padded to VOCAB_ALIGN; padded logit columns are masked
    to -inf in unembed so losses/samplers never see them."""
    ks = split_keys(key, 2)
    vp = padded_vocab(vocab)
    p = {"tok": dense_init(ks[0], (vp, d))}
    if not tie:
        p["head"] = dense_init(ks[1], (d, vp))
    return p


def embed_tokens(p: Params, tokens):
    return p["tok"].astype(ACT_DTYPE)[tokens]


def unembed(p: Params, x, vocab: int):
    if "head" in p:
        logits = x @ p["head"].astype(x.dtype)
    else:
        logits = x @ p["tok"].astype(x.dtype).T
    vp = logits.shape[-1]
    if vp != vocab:
        mask = (jnp.arange(vp) >= vocab) * jnp.asarray(-1e30, logits.dtype)
        logits = logits + mask
    return logits
