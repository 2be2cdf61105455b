"""Pallas TPU kernel for the Mamba2 SSD chunked scan (arXiv:2405.21060 §6).

Grid ``(B, H, L/Q)`` with the chunk axis minor/sequential: the [P, N] SSM
state lives in VMEM scratch and is carried across chunk tiles, so the HBM
traffic per chunk is exactly the operand/output tiles — the jnp path's
[Q, Q, H] segment-decay tensors (the 2 GB/layer intermediates the dry-run
exposes) never exist.

Per tile (head h, chunk c), all in fp32:
    cum   = cumsum(dt·A)                              [Q, 1]
    y     = ((C Bᵀ) ⊙ tril(exp(cum_i − cum_j)) ⊙ dt_j) X      (intra, MXU)
          + exp(cum) ⊙ (C h_prevᵀ)                            (inter)
    h     = exp(cum_Q)·h_prev + Xᵀ(B ⊙ exp(cum_Q − cum)·dt)   (state update)

Block shapes: X [Q, P], B/C [Q, N], scores [Q, Q] — Q=chunk=256, P=64,
N=128 ⇒ ≈ 0.6 MB working set, all matmul dims MXU-aligned.  The wrapper
moves heads ahead of the sequence (X → [B, H, L, P], dt → [B, H, L, 1]) so
the last two dims of every block are ``(Q, P)``, ``(Q, 1)`` or ``(Q, N)``:
the TPU lowering needs them (8, 128)-aligned or equal to the array's.

``dA = dt·A`` is precomputed by the wrapper so the kernel takes no scalar
operands.  Oracle: :func:`repro.models.ssm.ssd_chunked`.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, da_ref, b_ref, c_ref, y_ref, h_ref, *, q: int,
            n: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    f32 = jnp.float32
    x = x_ref[0, 0].astype(f32)                          # [Q, P]
    dt = dt_ref[0, 0].astype(f32)                        # [Q, 1]  (lane dim 1)
    da = da_ref[0, 0].astype(f32)                        # [Q, 1]
    bmat = b_ref[0].astype(f32)                          # [Q, N]
    cmat = c_ref[0].astype(f32)                          # [Q, N]

    # within-chunk cumulative sum of dt·A, lane-broadcast to [Q, N]: a
    # log-depth doubling scan over sublanes (the TPU lowering has no
    # cumsum), then an aligned 2-D transpose gives cum_j as a row
    cum_b = jnp.broadcast_to(da, (q, n))
    d = 1
    while d < q:
        cum_b = cum_b + jnp.pad(cum_b, ((d, 0), (0, 0)))[:q]
        d *= 2
    cum = cum_b[:, 0:1]                                  # [Q, 1]
    cum_row = cum_b.T[0:1, :]                            # [1, Q]
    # intra-chunk dual form
    seg = cum - cum_row                                  # [Q, Q] = cum_i - cum_j
    ii = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    decay = jnp.where(jj <= ii, jnp.exp(seg), 0.0)
    cb = jax.lax.dot_general(cmat, bmat, (((1,), (1,)), ((), ())),
                             preferred_element_type=f32)          # [Q, Q]
    # ⊙ dt_j folded into the rows of X
    y = jax.lax.dot(cb * decay, x * dt, preferred_element_type=f32)  # [Q, P]

    # inter-chunk: exp(cum_i)·C_i·h_prev
    h_prev = h_ref[...]                                  # [P, N]
    y += jnp.exp(cum) * jax.lax.dot_general(
        cmat, h_prev, (((1,), (1,)), ((), ())), preferred_element_type=f32)

    y_ref[0, 0] = y.astype(y_ref.dtype)

    # state: h = γ·h_prev + Xᵀ (B ⊙ w),   w = exp(cum_Q − cum)·dt; both
    # kept N lanes wide, so every broadcast is over sublanes or lanes only
    cum_end = cum_b[q - 1:q, :]                          # [1, N]
    gamma = jnp.exp(cum_end)
    w = jnp.exp(cum_end - cum_b) * dt                    # [Q, N]
    s_new = jax.lax.dot_general(x, bmat * w, (((0,), (0,)), ((), ())),
                                preferred_element_type=f32)       # [P, N]
    h_ref[...] = h_prev * gamma + s_new


def ssd_scan(x: jax.Array, dt: jax.Array, a: jax.Array, bmat: jax.Array,
             cmat: jax.Array, chunk: int,
             interpret: Optional[bool] = None) -> jax.Array:
    """x: [Bt,L,H,P]  dt: [Bt,L,H]  a: [H] (<0)  B,C: [Bt,L,N] → y: [Bt,L,H,P]."""
    bt, l, h, p = x.shape
    n = bmat.shape[-1]
    q = min(chunk, l)
    if l % q:
        raise ValueError(f"L={l} must be a multiple of chunk={q}")
    nc = l // q
    if interpret is None:
        interpret = jax.default_backend() == "cpu"

    # heads ahead of the sequence: [Bt,L,H,·] → [Bt,H,L,·]
    xh = jnp.moveaxis(x, 2, 1)                            # [Bt,H,L,P]
    dth = jnp.moveaxis(dt, 2, 1)[..., None]               # [Bt,H,L,1]
    dah = dth * a[None, :, None, None]                    # precomputed dt·A

    kernel = functools.partial(_kernel, q=q, n=n)
    y = pl.pallas_call(
        kernel,
        grid=(bt, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, q, p), lambda b, ih, ic: (b, ih, ic, 0)),
            pl.BlockSpec((1, 1, q, 1), lambda b, ih, ic: (b, ih, ic, 0)),
            pl.BlockSpec((1, 1, q, 1), lambda b, ih, ic: (b, ih, ic, 0)),
            pl.BlockSpec((1, q, n), lambda b, ih, ic: (b, ic, 0)),
            pl.BlockSpec((1, q, n), lambda b, ih, ic: (b, ic, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, q, p), lambda b, ih, ic: (b, ih, ic, 0)),
        out_shape=jax.ShapeDtypeStruct((bt, h, l, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="ssd_scan",
    )(xh, dth, dah, bmat, cmat)
    return jnp.moveaxis(y, 1, 2)
