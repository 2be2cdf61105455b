"""Pallas TPU flash-attention forward kernel.

Tiling (v5e): grid ``(B·Hkv·G, L/bq, S/bk)`` — the kv dim is the minor
(sequential) grid axis, so the running max / denominator / accumulator live
in VMEM scratch across kv tiles and the output block is written once on the
last tile.  Block shapes keep the working set in VMEM
(bq·hd + bk·hd (k) + bk·hd (v) + bq·bk (scores) floats ≈ 0.9 MB at
bq=bk=512, hd=128) and every matmul dim is a multiple of 128 (MXU-aligned).

GQA runs grouped: q rows carry ``B·Hkv·G`` heads while k/v carry ``B·Hkv`` —
the k/v index map divides the head coordinate by G, so KV tiles are never
replicated in HBM.  Causal masking, sliding windows and logit softcap are
fused into the tile loop; fully-masked tiles are skipped via ``pl.when``
(grid-level early-out — the causal 2× FLOP saving).

Oracle: :func:`repro.kernels.ref.flash_attention_ref` (== models.flash,
itself validated against the dense softmax).  Validated with
``interpret=True`` on CPU; the TPU path is structural.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.3819763e38
_LANES = 128                     # TPU vector lane width (scratch minor dim)


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, causal: bool, window: int, softcap: float,
            bq: int, bk: int, nk: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # tile relevance (grid-level causal/window skipping)
    q_lo = iq * bq
    q_hi = q_lo + bq - 1
    k_lo = ik * bk
    k_hi = k_lo + bk - 1
    relevant = jnp.bool_(True)
    if causal:
        relevant &= k_lo <= q_hi
    if window:
        relevant &= k_hi > q_lo - window

    @pl.when(relevant)
    def _tile():
        q = q_ref[0].astype(jnp.float32)                    # [bq, hd]
        k = k_ref[0].astype(jnp.float32)                    # [bk, hd]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.bool_(True)
        if causal:
            mask &= kpos <= qpos
        if window:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, :1]                               # [bq, 1]
        l_prev = l_ref[:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_next = jnp.maximum(m_prev, m_cur)
        corr = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - m_next)                             # [bq, bk]
        l_ref[...] = jnp.broadcast_to(
            corr * l_prev + jnp.sum(p, axis=1, keepdims=True), l_ref.shape)
        m_ref[...] = jnp.broadcast_to(m_next, m_ref.shape)
        v = v_ref[0].astype(jnp.float32)                    # [bk, hd]
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _finalize():
        l_fin = jnp.maximum(l_ref[:, :1], 1e-37)
        o_ref[0] = (acc_ref[...] / l_fin).astype(o_ref.dtype)


def flash_attention_fwd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, window: int = 0,
                        softcap: float = 0.0, block_q: int = 512,
                        block_k: int = 512,
                        interpret: Optional[bool] = None) -> jax.Array:
    """q: [B,L,H,hd]; k,v: [B,S,Hkv,hd] → [B,L,H,hd]."""
    b, l, h, hd = q.shape
    s_len, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    bq = min(block_q, l)
    bk = min(block_k, s_len)
    if l % bq or s_len % bk:
        raise ValueError(f"L={l}, S={s_len} must tile by ({bq},{bk})")
    nq, nk = l // bq, s_len // bk
    if interpret is None:
        interpret = jax.default_backend() == "cpu"

    # [B,L,H,hd] -> [B·Hkv·G, L, hd];  [B,S,Hkv,hd] -> [B·Hkv, S, hd]
    qf = jnp.moveaxis(q.reshape(b, l, hkv, g, hd), 1, 3).reshape(b * hkv * g, l, hd)
    kf = jnp.moveaxis(k, 1, 2).reshape(b * hkv, s_len, hd)
    vf = jnp.moveaxis(v, 1, 2).reshape(b * hkv, s_len, hd)

    kernel = functools.partial(
        _kernel, scale=1.0 / (hd ** 0.5), causal=causal, window=int(window),
        softcap=float(softcap), bq=bq, bk=bk, nk=nk)

    out = pl.pallas_call(
        kernel,
        grid=(b * hkv * g, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, bk, hd), lambda bh, iq, ik, g=g: (bh // g, ik, 0)),
            pl.BlockSpec((1, bk, hd), lambda bh, iq, ik, g=g: (bh // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hkv * g, l, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, _LANES), jnp.float32),       # running max
            pltpu.VMEM((bq, _LANES), jnp.float32),       # running denom
            pltpu.VMEM((bq, hd), jnp.float32),           # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_attention_fwd",
    )(qf, kf, vf)

    return jnp.moveaxis(out.reshape(b, hkv, g, l, hd), 3, 1).reshape(b, l, h, hd)
