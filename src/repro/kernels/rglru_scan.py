"""Pallas TPU kernel for the RG-LRU linear recurrence (arXiv:2402.19427).

    h_t = a_t ⊙ h_{t-1} + b_t          (per-lane decays, a_t = exp(log_a_t))

Grid ``(B, W/bw, L/bl)`` with the sequence axis minor/sequential; the carry
``h`` lives in VMEM scratch across sequence tiles.  Within a tile the
recurrence is computed in **log-depth** via the doubling (Hillis–Steele)
scan on the associative pairs (a, b) — log2(bl) vectorized steps instead of
bl sequential ones; the composition is

    (a₁,b₁) ∘ (a₂,b₂) = (a₁a₂, b₁a₂ + b₂).

The sequential dependency is inherently per-lane (every lane has its own
decay), so the TPU-native implementation is VPU-vectorized over [bl, bw]
tiles with the HBM→VMEM streaming done by the grid — there is no MXU work
to recover here; the kernel's win is IO locality + log-depth.

Oracle: :func:`repro.models.rglru.scan_ref` (associative_scan).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(log_a_ref, b_ref, h_ref, carry_ref, *, bl: int):
    il = pl.program_id(2)

    @pl.when(il == 0)
    def _init():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    f32 = jnp.float32
    a = jnp.exp(log_a_ref[0].astype(f32))                # [bl, bw]
    bv = b_ref[0].astype(f32)

    # doubling scan: after step d, (a, bv)[t] composes the last 2·d inputs
    d = 1
    while d < bl:
        a_sh = jnp.pad(a, ((d, 0), (0, 0)), constant_values=1.0)[:bl]
        b_sh = jnp.pad(bv, ((d, 0), (0, 0)))[:bl]
        bv = b_sh * a + bv
        a = a_sh * a
        d *= 2

    h0 = carry_ref[0:1, :]                               # [1, bw]
    h = bv + a * h0                                      # [bl, bw]
    h_ref[0] = h.astype(h_ref.dtype)
    carry_ref[...] = jnp.broadcast_to(h[bl - 1:bl, :], carry_ref.shape)


def rglru_scan(log_a: jax.Array, b: jax.Array, *, block_l: int = 256,
               block_w: int = 256,
               interpret: Optional[bool] = None) -> jax.Array:
    """log_a, b: [B, L, W] → h: [B, L, W] (recurrence over axis 1, fp32)."""
    bt, l, w = log_a.shape
    bl = min(block_l, l)
    bw = min(block_w, w)
    if l % bl or w % bw:
        raise ValueError(f"L={l}, W={w} must tile by ({bl},{bw})")
    if interpret is None:
        interpret = jax.default_backend() == "cpu"

    kernel = functools.partial(_kernel, bl=bl)
    return pl.pallas_call(
        kernel,
        grid=(bt, w // bw, l // bl),
        in_specs=[
            pl.BlockSpec((1, bl, bw), lambda ib, iw, il: (ib, il, iw)),
            pl.BlockSpec((1, bl, bw), lambda ib, iw, il: (ib, il, iw)),
        ],
        out_specs=pl.BlockSpec((1, bl, bw), lambda ib, iw, il: (ib, il, iw)),
        out_shape=jax.ShapeDtypeStruct((bt, l, w), jnp.float32),
        scratch_shapes=[pltpu.VMEM((8, bw), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="rglru_scan",
    )(log_a, b)
