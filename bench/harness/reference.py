"""The plain reference: the configuration's model in float32, from its
published equations, with nothing of the program imported.

It reads the weights the benchmark drew (``harness.stage.make_weights``)
by their names in the program's parameter tree.  Two conventions of that
tree are the reference's too: a norm's stored weight is an offset from 1
(``x · rsqrt(mean x² + eps) · (1 + w)``), and layers are stacked along a
leading axis of ``blocks.s0``.

* ``attn`` layers: llama-family pre-norm block, grouped-query attention
  with rotary embeddings (half-split rotation, base ``rope_theta``) and a
  gated-SiLU MLP (Touvron et al. 2023; Yi, arXiv:2403.04652).
* ``ssm`` layers: Mamba-2's block (arXiv:2405.21060, single group, scalar
  A per head): projections to z, x, B, C, dt; causal depthwise convolution
  of x, B and C with SiLU; the SSD recurrence in its quadratic matrix form
  y_t = Σ_{s≤t} (C_t·B_s) · exp(Σ_{s<r≤t} dt_r·A) · dt_s · x_s + D·x_t;
  output gated by SiLU(z).

Every matrix product runs at ``Precision.HIGHEST``.  The model runs one
layer at a time, each layer's weights cast to float32 only while it runs,
so that it fits beside the served weights.  With ``control`` set, both
inputs of every weight product are rounded to float8 (``fp8``): the
control, one precision below the bfloat16 that the configurations state.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def fp8(a: jax.Array) -> jax.Array:
    """Round to float8 e4m3 with one scale for the tensor, and back."""
    s = jnp.max(jnp.abs(a)) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (a / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _ident(a):
    return a


def _mm(a, w, q):
    return jnp.matmul(q(a), q(w.astype(F32)), precision=HI)


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w.astype(F32))


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=F32) / half)
    ang = pos.astype(F32)[:, None] * freqs                      # [S, half]
    c, s = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _attn_layer(x, p, model, q):
    b, s, d = x.shape
    h, kv = model["n_heads"], model["n_kv_heads"]
    hd = model.get("head_dim") or d // h
    eps, theta = model["rms_eps"], model["rope_theta"]
    a = p["attn"]
    y = _norm(x, p["ln1"], eps)
    pos = jnp.arange(s)
    qh = _rope(_mm(y, a["wq"], q).reshape(b, s, h, hd), pos, theta)
    k = _rope(_mm(y, a["wk"], q).reshape(b, s, kv, hd), pos, theta)
    v = _mm(y, a["wv"], q).reshape(b, s, kv, hd)
    qh = qh.reshape(b, s, kv, h // kv, hd)
    sc = jnp.einsum("bqkgd,bskd->bkgqs", qh, k, precision=HI) / np.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    sc = jnp.where(causal, sc, -jnp.inf)
    o = jnp.einsum("bkgqs,bskd->bqkgd", jax.nn.softmax(sc, -1), v,
                   precision=HI).reshape(b, s, h * hd)
    x = x + _mm(o, a["wo"], q)
    m = p["mlp"]
    y = _norm(x, p["ln2"], eps)
    f = jax.nn.silu(_mm(y, m["w_gate"], q)) * _mm(y, m["w_up"], q)
    return x + _mm(f, m["w_down"], q)


def _conv(u, w, bias):
    """Causal depthwise convolution; w[-1] weighs the current position."""
    k, s = w.shape[0], u.shape[1]
    up = jnp.pad(u, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(up[:, i:i + s] * w[i].astype(F32) for i in range(k)) \
        + bias.astype(F32)


def _ssm_layer(x, p, model, q):
    b, s, d = x.shape
    sp = p["ssm"]
    y = _norm(x, p["ln1"], model["rms_eps"])
    z = _mm(y, sp["wz"], q)
    xs = jax.nn.silu(_conv(_mm(y, sp["wx"], q), sp["conv_x_w"], sp["conv_x_b"]))
    bs = jax.nn.silu(_conv(_mm(y, sp["wb"], q), sp["conv_b_w"], sp["conv_b_b"]))
    cs = jax.nn.silu(_conv(_mm(y, sp["wc"], q), sp["conv_c_w"], sp["conv_c_b"]))
    dt = jax.nn.softplus(_mm(y, sp["wdt"], q) + sp["dt_bias"].astype(F32))
    a = -jnp.exp(sp["A_log"].astype(F32))                       # [H]
    nh = a.shape[0]
    xh = xs.reshape(b, s, nh, -1)                               # [B,S,H,P]
    cum = jnp.cumsum(dt * a, axis=1)                            # [B,S,H]
    seg = cum[:, :, None, :] - cum[:, None, :, :]               # [B,T,S,H]
    causal = jnp.tril(jnp.ones((s, s), bool))[None, :, :, None]
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    cb = jnp.einsum("btn,bsn->bts", cs, bs, precision=HI)
    mix = cb[..., None] * decay * dt[:, None, :, :]             # [B,T,S,H]
    out = jnp.einsum("btsh,bshp->bthp", mix, xh, precision=HI)
    out = out + sp["D"].astype(F32)[None, None, :, None] * xh
    out = out.reshape(b, s, -1) * jax.nn.silu(z)
    return x + _mm(out, sp["w_out"], q)


_LAYERS = {"attn": _attn_layer, "ssm": _ssm_layer}


@partial(jax.jit, static_argnames=("kind", "hp", "quantized"))
def _layer(x, p, *, kind, hp, quantized):
    return _LAYERS[kind](x, p, dict(hp), fp8 if quantized else _ident)


@partial(jax.jit, static_argnames=("eps", "vocab", "tied", "quantized"))
def _head(x, final_norm, head, *, eps, vocab, tied, quantized):
    q = fp8 if quantized else _ident
    x = _norm(x, final_norm, eps)
    w = head[:vocab].T if tied else head[:, :vocab]
    return _mm(x, w, q)


def logits(params, model: Dict[str, Any], tokens: np.ndarray, first: int,
           control: bool = False) -> np.ndarray:
    """Logits over the real vocabulary at positions ``first`` onwards of
    ``tokens`` [B, S]: float32 [B, S - first, vocab]."""
    pattern = model.get("layer_pattern", ["attn"])
    if len(pattern) != 1 or pattern[0] not in _LAYERS:
        raise ValueError(f"the reference has no layer pattern {pattern}")
    kind = pattern[0]
    hp = tuple(sorted((k, v) for k, v in model.items()
                      if not isinstance(v, (dict, list))))
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], jnp.asarray(tokens), axis=0).astype(F32)
        blocks = params["blocks"]["s0"]
        for i in range(model["n_layers"]):
            p = jax.tree.map(lambda a: a[i], blocks)
            x = _layer(x, p, kind=kind, hp=hp, quantized=control)
        tied = bool(model.get("tie_embeddings", False))
        head = params["embed"] if tied else params["lm_head"]
        out = _head(x[:, first:], params["final_norm"], head,
                    eps=float(model["rms_eps"]), vocab=int(model["vocab"]),
                    tied=tied, quantized=control)
        return np.asarray(out)
