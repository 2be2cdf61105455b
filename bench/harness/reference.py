"""The plain reference: the configuration's model in float32, from its
published equations, with nothing of the program imported.

It reads the weights the benchmark drew (``harness.stage.make_weights``)
by their names in the program's parameter tree.  Two conventions of that
tree are the reference's too: a norm's stored weight is an offset from 1
(``x · rsqrt(mean x² + eps) · (1 + w)``), and the layer pattern's
whole periods are stacked along a leading axis of ``blocks.s<slot>``,
the layers after the last whole period kept as ``rem.r<i>``.

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

A configuration with layers of its own names its own reference module in
its file (``"reference"``, resolved by ``spec.reference_of``), which
exposes ``logits`` as this one does.  It builds on the public pieces here:
``fp8``, ``matmul``, ``norm``, ``rope``, ``causal_conv``, ``ssd``,
``attention``, ``gated_mlp``, the two layers, and ``logits`` itself, whose
``layers`` table maps each kind of the layer pattern to its layer.
"""

from __future__ import annotations

import json
from functools import partial
from typing import Any, Callable, Dict, Mapping

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


Rounding = Callable[[jax.Array], jax.Array]
Layer = Callable[[jax.Array, Dict[str, Any], Dict[str, Any], Rounding],
                 jax.Array]


def matmul(a, w, q: Rounding):
    """``a @ w`` in float32 at ``HIGHEST``, both inputs through ``q``: the
    identity, or ``fp8`` for the control."""
    return jnp.matmul(q(a), q(w.astype(F32)), precision=HI)


def norm(x, w, eps):
    """RMS norm with the stored weight an offset from 1."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w.astype(F32))


def rope(x, pos, theta):
    """Rotary embedding of ``x`` [B, S, H, D] at positions ``pos`` [S]:
    the half-split rotation, frequencies ``theta ** (-i / (D / 2))``."""
    half = x.shape[-1] // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=F32) / half)
    ang = pos.astype(F32)[:, None] * freqs                      # [S, half]
    c, s = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def attention(y, a, model, q: Rounding):
    """Causal grouped-query attention with rotary embeddings over the
    normed input ``y`` [B, S, D], through the output projection: the
    ``attn`` leaves ``wq wk wv wo``, heads ``n_heads`` / ``n_kv_heads`` of
    ``head_dim`` (or ``d_model / n_heads``)."""
    b, s, d = y.shape
    h, kv = model["n_heads"], model["n_kv_heads"]
    hd = model.get("head_dim") or d // h
    theta = model["rope_theta"]
    pos = jnp.arange(s)
    qh = rope(matmul(y, a["wq"], q).reshape(b, s, h, hd), pos, theta)
    k = rope(matmul(y, a["wk"], q).reshape(b, s, kv, hd), pos, theta)
    v = matmul(y, a["wv"], q).reshape(b, s, kv, hd)
    qh = qh.reshape(b, s, kv, h // kv, hd)
    sc = jnp.einsum("bqkgd,bskd->bkgqs", qh, k, precision=HI) / np.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    sc = jnp.where(causal, sc, -jnp.inf)
    o = jnp.einsum("bkgqs,bskd->bqkgd", jax.nn.softmax(sc, -1), v,
                   precision=HI).reshape(b, s, h * hd)
    return matmul(o, a["wo"], q)


def gated_mlp(y, m, q: Rounding):
    """The gated-SiLU MLP: ``w_down(silu(w_gate y) * w_up y)``."""
    f = jax.nn.silu(matmul(y, m["w_gate"], q)) * matmul(y, m["w_up"], q)
    return matmul(f, m["w_down"], q)


def attn_layer(x, p, model, q: Rounding):
    """The llama-family pre-norm block: attention, then the MLP."""
    eps = model["rms_eps"]
    x = x + attention(norm(x, p["ln1"], eps), p["attn"], model, q)
    return x + gated_mlp(norm(x, p["ln2"], eps), p["mlp"], q)


def causal_conv(u, w, bias):
    """Causal depthwise convolution; w[-1] weighs the current position."""
    k, s = w.shape[0], u.shape[1]
    up = jnp.pad(u, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(up[:, i:i + s] * w[i].astype(F32) for i in range(k)) \
        + bias.astype(F32)


def ssd(xh, dt, a, bs, cs):
    """The SSD recurrence in its quadratic matrix form, for heads that
    share one group's B and C: ``xh`` [B, S, H, P], ``dt`` [B, S, H],
    ``a`` [H], ``bs`` and ``cs`` [B, S, N].  Returns y [B, S, H, P] with
    y_t = Σ_{s≤t} (C_t·B_s) · exp(Σ_{s<r≤t} dt_r·A) · dt_s · x_s, before
    the skip term ``D·x_t``.  Several groups are one call per group."""
    s = xh.shape[1]
    cum = jnp.cumsum(dt * a, axis=1)                            # [B,S,H]
    seg = cum[:, :, None, :] - cum[:, None, :, :]               # [B,T,S,H]
    causal = jnp.tril(jnp.ones((s, s), bool))[None, :, :, None]
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    cb = jnp.einsum("btn,bsn->bts", cs, bs, precision=HI)
    mix = cb[..., None] * decay * dt[:, None, :, :]             # [B,T,S,H]
    return jnp.einsum("btsh,bshp->bthp", mix, xh, precision=HI)


def ssm_layer(x, p, model, q: Rounding):
    """Mamba-2's block, single group."""
    b, s, d = x.shape
    sp = p["ssm"]
    y = norm(x, p["ln1"], model["rms_eps"])
    z = matmul(y, sp["wz"], q)
    xs = jax.nn.silu(causal_conv(matmul(y, sp["wx"], q), sp["conv_x_w"],
                                 sp["conv_x_b"]))
    bs = jax.nn.silu(causal_conv(matmul(y, sp["wb"], q), sp["conv_b_w"],
                                 sp["conv_b_b"]))
    cs = jax.nn.silu(causal_conv(matmul(y, sp["wc"], q), sp["conv_c_w"],
                                 sp["conv_c_b"]))
    dt = jax.nn.softplus(matmul(y, sp["wdt"], q) + sp["dt_bias"].astype(F32))
    a = -jnp.exp(sp["A_log"].astype(F32))                       # [H]
    xh = xs.reshape(b, s, a.shape[0], -1)                       # [B,S,H,P]
    out = ssd(xh, dt, a, bs, cs)
    out = out + sp["D"].astype(F32)[None, None, :, None] * xh
    out = out.reshape(b, s, -1) * jax.nn.silu(z)
    return x + matmul(out, sp["w_out"], q)


LAYERS: Mapping[str, Layer] = {"attn": attn_layer, "ssm": ssm_layer}


@partial(jax.jit, static_argnames=("layer", "hp", "quantized"))
def _layer(x, p, *, layer, hp, quantized):
    return layer(x, p, json.loads(hp), fp8 if quantized else _ident)


@partial(jax.jit, static_argnames=("eps", "vocab", "tied", "quantized"))
def _head(x, final_norm, head, *, eps, vocab, tied, quantized):
    q = fp8 if quantized else _ident
    x = norm(x, final_norm, eps)
    w = head[:vocab].T if tied else head[:, :vocab]
    return matmul(x, w, q)


def _layer_params(params, pattern, n_layers: int, i: int):
    """Layer ``i``'s weights: full periods of the pattern are stacked along
    the leading axis of ``blocks.s<slot>``, the layers after the last full
    period are ``rem.r<j>``."""
    full = n_layers // len(pattern) * len(pattern)
    if i >= full:
        return params["rem"][f"r{i - full}"]
    return jax.tree.map(lambda a: a[i // len(pattern)],
                        params["blocks"][f"s{i % len(pattern)}"])


def logits(params, model: Dict[str, Any], tokens: np.ndarray, first: int,
           control: bool = False,
           layers: Mapping[str, Layer] = LAYERS) -> np.ndarray:
    """Logits over the real vocabulary at positions ``first`` onwards of
    ``tokens`` [B, S]: float32 [B, S - first, vocab].  ``layers`` gives the
    layer of each kind in the configuration's ``layer_pattern``, cycled
    over ``n_layers`` as the program cycles it."""
    pattern = model.get("layer_pattern", ["attn"])
    missing = sorted(set(pattern) - set(layers))
    if missing:
        raise ValueError(f"the reference has no layer of kind {missing}")
    hp = json.dumps(model, sort_keys=True)
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], jnp.asarray(tokens), axis=0).astype(F32)
        for i in range(model["n_layers"]):
            p = _layer_params(params, pattern, model["n_layers"], i)
            x = _layer(x, p, layer=layers[pattern[i % len(pattern)]], hp=hp,
                       quantized=control)
        tied = bool(model.get("tie_embeddings", False))
        head = params["embed"] if tied else params["lm_head"]
        out = _head(x[:, first:], params["final_norm"], head,
                    eps=float(model["rms_eps"]), vocab=int(model["vocab"]),
                    tied=tied, quantized=control)
        return np.asarray(out)
