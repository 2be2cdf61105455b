"""Mixture-of-Experts layer (DeepSeek-MoE fine-grained + DBRX-style).

Design (TPU-native, expert-parallel friendly):
  * Router: fp32 logits → top-k expert ids + normalized weights.
  * Dispatch: **sort-based with static capacity** — assignments are sorted by
    expert id and scattered into an ``[E, C, D]`` buffer (`mode=drop` handles
    capacity overflow), so every shape is static and jit-able.  With the
    expert axis sharded over the mesh's ``model`` axis this lowers to the
    all-to-all-class collectives an EP implementation performs on TPU —
    exactly what the roofline's collective term should see.
  * Experts: one batched einsum ``[E,C,D]×[E,D,F]`` → the MXU-dense grouped
    matmul (fine-grained experts keep F ≥ 128-aligned for v5e).
  * Combine: gather back per assignment, weighted sum over k.
  * Shared experts (DeepSeek): dense gated-MLP applied to every token.

This is the structural analogue of the paper's Map/Fan-In primitives at the
token level: route (fan-out) → expert compute → combine (fan-in), with the
capacity buffer playing the coordination-point role.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models import mlp
from repro.models.common import ModelConfig, dense_init, split_keys


def capacity(n_tokens: int, cfg: ModelConfig) -> int:
    m = cfg.moe
    assert m is not None
    cap = int(math.ceil(n_tokens * m.top_k * m.capacity_factor / m.num_experts))
    return max(8, ((cap + 127) // 128) * 128)      # MXU-aligned rows


def init(key, cfg: ModelConfig) -> Dict[str, Any]:
    m = cfg.moe
    assert m is not None
    d, f, e = cfg.d_model, m.d_expert, m.num_experts
    ks = split_keys(key, ["router", "gate", "up", "down", "shared"])

    def estack(k, din, dout):
        keys = jax.random.split(k, e)
        return jnp.stack([dense_init(keys[i], din, dout, cfg.pdtype) for i in range(e)])

    p: Dict[str, Any] = {
        "router": dense_init(ks["router"], d, e, cfg.pdtype),
        "w_gate": estack(ks["gate"], d, f),      # [E, D, F]
        "w_up": estack(ks["up"], d, f),          # [E, D, F]
        "w_down": jnp.swapaxes(estack(ks["down"], d, f), 1, 2),  # [E, F, D]
    }
    if m.num_shared:
        p["shared"] = mlp.init(ks["shared"], cfg, d_ff=f * m.num_shared)
    return p


def route(params: Dict[str, Any], cfg: ModelConfig, x2d: jax.Array
          ) -> Tuple[jax.Array, jax.Array]:
    """x2d: [T, D] → (expert_ids [T,k], weights [T,k]); router math in fp32."""
    m = cfg.moe
    logits = (x2d.astype(jnp.float32) @ params["router"].astype(jnp.float32))
    weights, ids = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), m.top_k)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return ids, weights


def apply(params: Dict[str, Any], cfg: ModelConfig, x: jax.Array) -> jax.Array:
    """x: [B, L, D] → [B, L, D].  Dispatches to the shard_map expert-parallel
    path when traced under a mesh context (and experts divide the model axis);
    otherwise the dense sort-based path below — which doubles as the oracle."""
    from repro.parallel.mesh_ctx import current_ctx
    ctx = current_ctx()
    m = cfg.moe
    assert m is not None
    if ctx is not None and m.num_experts % ctx.model_size == 0:
        return apply_ep(params, cfg, x, ctx)
    return apply_ref(params, cfg, x)


def apply_ref(params: Dict[str, Any], cfg: ModelConfig, x: jax.Array) -> jax.Array:
    """Single-device reference: global sort-based dispatch."""
    m = cfg.moe
    assert m is not None
    b, l, d = x.shape
    t = b * l
    ct = cfg.cdtype
    x2d = x.reshape(t, d)

    ids, weights = route(params, cfg, x2d)                   # [T,k]
    k = m.top_k
    e = m.num_experts
    cap = capacity(t, cfg)

    # ---- sort assignments by expert ------------------------------------------
    flat_expert = ids.reshape(t * k)                          # [A]
    order = jnp.argsort(flat_expert)                          # stable
    sorted_expert = flat_expert[order]
    token_of = order // k                                     # source token per assignment
    # position within the expert's capacity block
    expert_start = jnp.searchsorted(sorted_expert, jnp.arange(e), side="left")
    pos_in_expert = jnp.arange(t * k) - expert_start[sorted_expert]

    # ---- scatter into the [E, C, D] dispatch buffer (drop on overflow) --------
    buf = jnp.zeros((e, cap, d), ct)
    src = x2d[token_of].astype(ct)                            # [A, D]
    buf = buf.at[sorted_expert, pos_in_expert].set(src, mode="drop")

    # ---- grouped expert FFN (one batched einsum per projection) ----------------
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, params["w_gate"].astype(ct)))
    u = jnp.einsum("ecd,edf->ecf", buf, params["w_up"].astype(ct))
    out_buf = jnp.einsum("ecf,efd->ecd", g * u, params["w_down"].astype(ct))

    # ---- combine: gather per assignment, weighted sum over k -------------------
    dropped = pos_in_expert >= cap
    gathered = out_buf[sorted_expert, jnp.clip(pos_in_expert, 0, cap - 1)]  # [A, D]
    gathered = jnp.where(dropped[:, None], 0.0, gathered)
    # un-sort back to (token, k) order
    unsort = jnp.zeros_like(order).at[order].set(jnp.arange(t * k))
    per_assign = gathered[unsort].reshape(t, k, d)
    y = jnp.einsum("tkd,tk->td", per_assign, weights.astype(ct))

    if m.num_shared:
        y = y + mlp.apply(params["shared"], cfg, x2d).reshape(t, d)
    return y.reshape(b, l, d)


# ==========================================================================
# Expert-parallel path (shard_map over the production mesh)
# ==========================================================================
#
# Token activations are sharded over the batch axes and *replicated* over the
# model axis; experts are sharded over the model axis.  Dispatch is therefore
# collective-free — each model rank selects, from its replicated token copy,
# the assignments targeting its local experts — and combine is one psum over
# the model axis.  This is the paper's majority-rule placement at token
# granularity: work lands where its experts live, and only the combined
# [T, D] output crosses the "cloud" (axis) boundary.


def apply_ep(params: Dict[str, Any], cfg: ModelConfig, x: jax.Array, ctx) -> jax.Array:
    m = cfg.moe
    b, l, d = x.shape
    ct = cfg.cdtype
    e = m.num_experts
    e_loc = e // ctx.model_size
    x2d = x.reshape(b * l, d)

    batch = tuple(ctx.batch_axes)
    P_ = jax.sharding.PartitionSpec

    def shard(x2d_loc, router, w_gate, w_up, w_down):
        t_loc = x2d_loc.shape[0]
        k = m.top_k
        # fp32 routing on the local (replicated-over-model) token block
        logits = x2d_loc.astype(jnp.float32) @ router.astype(jnp.float32)
        weights, ids = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)

        cap = int(math.ceil(t_loc * k * m.capacity_factor / e))
        cap = max(8, ((cap + 7) // 8) * 8)

        flat_expert = ids.reshape(t_loc * k)
        order = jnp.argsort(flat_expert)
        sorted_expert = flat_expert[order]
        token_of = order // k
        expert_start = jnp.searchsorted(sorted_expert, jnp.arange(e), side="left")
        pos = jnp.arange(t_loc * k) - expert_start[sorted_expert]

        # my expert range on this model rank
        rank = jax.lax.axis_index(ctx.model_axis)
        lo = rank * e_loc
        local_e = sorted_expert - lo
        valid = (local_e >= 0) & (local_e < e_loc) & (pos < cap)
        idx_e = jnp.where(valid, local_e, e_loc)          # row e_loc = trash
        idx_c = jnp.where(valid, pos, 0)

        buf = jnp.zeros((e_loc + 1, cap, d), ct)
        buf = buf.at[idx_e, idx_c].set(x2d_loc[token_of].astype(ct))
        buf = buf[:e_loc]

        g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w_gate.astype(ct)))
        u = jnp.einsum("ecd,edf->ecf", buf, w_up.astype(ct))
        out_buf = jnp.einsum("ecf,efd->ecd", g * u, w_down.astype(ct))

        gathered = out_buf[jnp.clip(idx_e, 0, e_loc - 1), idx_c]
        gathered = jnp.where(valid[:, None], gathered, 0.0)
        unsort = jnp.zeros_like(order).at[order].set(jnp.arange(t_loc * k))
        per_assign = gathered[unsort].reshape(t_loc, k, d)
        y_partial = jnp.einsum("tkd,tk->td", per_assign, weights.astype(ct))
        # combine: sum each token's k expert outputs across model ranks —
        # in compute dtype (§Perf: halves the EP all-reduce wire vs f32)
        return jax.lax.psum(y_partial.astype(ct), ctx.model_axis)

    y = jax.shard_map(
        shard,
        mesh=ctx.mesh,
        in_specs=(P_(batch, None), P_(), P_(ctx.model_axis, None, None),
                  P_(ctx.model_axis, None, None), P_(ctx.model_axis, None, None)),
        out_specs=P_(batch, None),
        check_vma=False,
    )(x2d, params["router"], params["w_gate"], params["w_up"], params["w_down"])

    if m.num_shared:
        y = y + mlp.apply(params["shared"], cfg, x2d.astype(ct))
    return y.reshape(b, l, d)


def aux_loss(params: Dict[str, Any], cfg: ModelConfig, x: jax.Array) -> jax.Array:
    """Load-balancing auxiliary loss (Switch-style): E[f_e · p_e] · E."""
    m = cfg.moe
    x2d = x.reshape(-1, x.shape[-1])
    logits = x2d.astype(jnp.float32) @ params["router"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                   # [T, E]
    _, ids = jax.lax.top_k(probs, m.top_k)
    counts = jnp.sum(jax.nn.one_hot(ids, m.num_experts, dtype=jnp.float32), axis=(0, 1))
    frac = counts / jnp.sum(counts)
    imp = jnp.mean(probs, axis=0)
    return m.num_experts * jnp.sum(frac * imp)
