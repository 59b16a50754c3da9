"""Pallas TPU kernel: fused flash attention (forward).

§Perf iteration 7: the HLO census shows the XLA-level online-softmax scan
writes scores/probabilities to HBM every KV block — 8.4 TB/device of the
qwen3 prefill_32k memory term (78%). Fusing the whole inner loop into one
Pallas kernel keeps sc/p_att in VMEM; HBM traffic drops to Q/K/V/O reads and
writes (the flash-attention contract).

Layout: grid (B*H, S/bq); each program owns a (bq, hd) query tile and loops
over KV blocks of size bk with fp32 running max/denominator/accumulator held
in VMEM scratch. Causality is handled by masking per block (programs whose
whole KV block is in the future still execute — Pallas grids are dense — but
contribute nothing; the MXU work is bounded by bq*bk*hd per step).

Weak-scaling notes vs the jnp path it replaces:
  * dots in input dtype (bf16) with fp32 accumulation;
  * GQA: callers expand K/V to per-q-head layout (models.common does this
    for the TP case already); the kernel is MHA-shaped (B, S, H, hd);
  * the jnp scan in models.common remains the CPU/interpret fallback and
    the oracle for this kernel's tests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(q_ref, k_ref, v_ref, o_ref, *, bq: int, bk: int, t: int,
            causal: bool, window: int | None, scale: float):
    # q_ref: (bq, hd); k_ref/v_ref: (T, hd); o_ref: (bq, hd)
    qi = pl.program_id(1)
    q = q_ref[...].astype(jnp.float32) * scale          # promoted once
    hd = q.shape[-1]
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)

    nblk = -(-t // bk)          # ceil: padded KV is masked via kv_pos < t

    def body(i, carry):
        m_run, l_run, acc = carry
        k_c = k_ref[pl.ds(i * bk, bk), :]
        v_c = v_ref[pl.ds(i * bk, bk), :]
        sc = jax.lax.dot_general(
            q.astype(k_c.dtype), k_c, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # (bq, bk)
        kv_pos = i * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        valid = kv_pos < t
        if causal:
            valid = valid & (kv_pos <= q_pos)
        if window is not None:
            valid = valid & (kv_pos > q_pos - window)
        sc = jnp.where(valid, sc, jnp.float32(-1e30))
        m_new = jnp.maximum(m_run, sc.max(axis=-1))
        alpha = jnp.exp(m_run - m_new)
        p = jnp.exp(sc - m_new[:, None])
        l_new = l_run * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[:, None] + jax.lax.dot_general(
            p.astype(v_c.dtype), v_c, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m0 = jnp.full((bq,), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    a0 = jnp.zeros((bq, hd), jnp.float32)
    # causal: skip blocks strictly after this query tile
    hi = nblk if not causal else jnp.minimum(
        nblk, (qi + 1) * bq // bk + 1).astype(jnp.int32)
    m_f, l_f, acc = jax.lax.fori_loop(0, hi, body, (m0, l0, a0))
    o_ref[...] = (acc / jnp.maximum(l_f, 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int | None = None,
                    bq: int = 512, bk: int = 512,
                    interpret: bool = False) -> jax.Array:
    """q (B,S,H,hd); k,v (B,T,H,hd) [per-q-head layout] -> (B,S,H,hd).

    T and S are padded to the block sizes internally; padded KV is masked.
    """
    b, s, h, hd = q.shape
    t = k.shape[1]
    bq = min(bq, max(8, s))
    bk = min(bk, max(128, t))
    gs = pl.cdiv(s, bq)
    tpad = pl.cdiv(t, bk) * bk - t
    spad = gs * bq - s
    # flatten (B,H) into the grid's first axis; seq-major layout per head
    qf = jnp.pad(q, ((0, 0), (0, spad), (0, 0), (0, 0))
                 ).transpose(0, 2, 1, 3).reshape(b * h, gs * bq, hd)
    kf = jnp.pad(k, ((0, 0), (0, tpad), (0, 0), (0, 0))
                 ).transpose(0, 2, 1, 3).reshape(b * h, t + tpad, hd)
    vf = jnp.pad(v, ((0, 0), (0, tpad), (0, 0), (0, 0))
                 ).transpose(0, 2, 1, 3).reshape(b * h, t + tpad, hd)

    kern = functools.partial(_kernel, bq=bq, bk=bk, t=t, causal=causal,
                             window=window, scale=1.0 / float(hd) ** 0.5)
    out = pl.pallas_call(
        kern,
        grid=(b * h, gs),
        in_specs=[
            pl.BlockSpec((None, bq, hd), lambda bh, i: (bh, i, 0)),
            pl.BlockSpec((None, t + tpad, hd), lambda bh, i: (bh, 0, 0)),
            pl.BlockSpec((None, t + tpad, hd), lambda bh, i: (bh, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, bq, hd), lambda bh, i: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, gs * bq, hd), q.dtype),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, h, gs * bq, hd)[:, :, :s].transpose(0, 2, 1, 3)
