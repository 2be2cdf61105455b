"""Grouped-query attention: full/sliding-window, softcap, RoPE, KV-cache decode.

One implementation serves all attention archs in the pool:
  * GQA with any kv-head count (yi kv=4 … phi3v kv=32=MHA);
  * optional QKV bias (qwen1.5);
  * optional logit softcap + sliding window (gemma2 local layers);
  * decode path against a ring-buffer KV cache (serve_step).

The jnp path here is the oracle & dry-run path; on real TPU the inner
``_sdpa`` call is replaced by the Pallas flash kernel
(:mod:`repro.kernels.flash_attention`) selected via ``use_pallas``.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.common import (ModelConfig, apply_rope, dense_init, rope_angles,
                                 softcap, split_keys)

NEG_INF = -2.3819763e38   # keep finite (matches flash-kernel masking)
FLASH_MIN_LEN = 2048      # below this the dense tile is cheaper than the scan


def _heads_constraint(x: jax.Array) -> jax.Array:
    """Pin [B,L,H,hd] activations to head-sharding over the model axis —
    under sequence-sharded boundaries GSPMD otherwise replicates the whole
    attention computation on every model rank (observed +60% compute term)."""
    from repro.parallel.mesh_ctx import constrain, current_ctx
    ctx = current_ctx()
    if ctx is None:
        return x
    return constrain(x, tuple(ctx.batch_axes), None, ctx.model_axis, None)


# ==========================================================================
# Params
# ==========================================================================


def init(key, cfg: ModelConfig, *, cross: bool = False) -> Dict[str, Any]:
    d, hd = cfg.d_model, cfg.hd
    ks = split_keys(key, ["q", "k", "v", "o"])
    p = {
        "wq": dense_init(ks["q"], d, cfg.n_heads * hd, cfg.pdtype),
        "wk": dense_init(ks["k"], d, cfg.n_kv_heads * hd, cfg.pdtype),
        "wv": dense_init(ks["v"], d, cfg.n_kv_heads * hd, cfg.pdtype),
        "wo": dense_init(ks["o"], cfg.n_heads * hd, d, cfg.pdtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.n_heads * hd,), cfg.pdtype)
        p["bk"] = jnp.zeros((cfg.n_kv_heads * hd,), cfg.pdtype)
        p["bv"] = jnp.zeros((cfg.n_kv_heads * hd,), cfg.pdtype)
    return p


# ==========================================================================
# Core scaled-dot-product (the part the Pallas kernel replaces)
# ==========================================================================


def _sdpa(q: jax.Array, k: jax.Array, v: jax.Array, mask: Optional[jax.Array],
          cap: float) -> jax.Array:
    """q: [B,L,H,hd]  k,v: [B,S,Hkv,hd]  mask: broadcastable to [B,L,S].

    GQA is computed grouped (no KV replication): the [B,Hkv,G,L,S] logits
    layout is what the Pallas flash kernel mirrors block-wise.
    """
    b, l, h, hd = q.shape
    s, hkv = k.shape[1], k.shape[2]
    group = h // hkv
    q = q.reshape(b, l, hkv, group, hd)
    logits = jnp.einsum("blkgd,bskd->bkgls", q, k,
                        preferred_element_type=jnp.float32)
    logits = logits / jnp.sqrt(hd).astype(jnp.float32)
    logits = softcap(logits, cap)
    if mask is not None:
        m = jnp.broadcast_to(mask, (b, l, s))[:, None, None, :, :]
        logits = jnp.where(m, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgls,bskd->blkgd", probs, v)
    return out.reshape(b, l, h, hd)


def make_causal_mask(l: int, s: int, *, window: int = 0,
                     offset: int = 0) -> jax.Array:
    """[l, s] boolean mask. ``offset`` = absolute position of query row 0
    minus key column 0 (decode: offset = pos). window=0 ⇒ full causal."""
    qpos = jnp.arange(l)[:, None] + offset
    kpos = jnp.arange(s)[None, :]
    m = kpos <= qpos
    if window:
        m &= kpos > qpos - window
    return m


# ==========================================================================
# Forward (prefill / train)
# ==========================================================================


def apply(params: Dict[str, Any], cfg: ModelConfig, x: jax.Array,
          positions: jax.Array, *, window: int = 0,
          kv_override: Optional[Tuple[jax.Array, jax.Array]] = None,
          causal: bool = True) -> jax.Array:
    """x: [B,L,D] -> [B,L,D]. ``kv_override`` supplies cross-attention memory."""
    b, l, d = x.shape
    hd = cfg.hd
    ct = cfg.cdtype
    q = x @ params["wq"].astype(ct)
    if "bq" in params:
        q = q + params["bq"].astype(ct)
    q = q.reshape(b, l, cfg.n_heads, hd)

    if kv_override is None:
        k = x @ params["wk"].astype(ct)
        v = x @ params["wv"].astype(ct)
        if "bk" in params:
            k = k + params["bk"].astype(ct)
            v = v + params["bv"].astype(ct)
        k = k.reshape(b, l, cfg.n_kv_heads, hd)
        v = v.reshape(b, l, cfg.n_kv_heads, hd)
        cos, sin = rope_angles(positions, hd, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        if causal and l >= FLASH_MIN_LEN and l % 512 == 0:
            # blockwise flash path: O(L) memory, custom flash backward
            from repro.models.flash import flash_attention
            q = _heads_constraint(q)
            k = _heads_constraint(k)
            v = _heads_constraint(v)
            out = flash_attention(q, k, v, causal=True, window=window,
                                  softcap=cfg.attn_softcap)
            return out.reshape(b, l, cfg.n_heads * hd) @ params["wo"].astype(ct)
        mask = make_causal_mask(l, l, window=window)[None] if causal else None
    else:
        k, v = kv_override                      # [B,S,Hkv,hd] already projected
        mask = None

    out = _sdpa(q, k, v, mask, cfg.attn_softcap)
    return out.reshape(b, l, cfg.n_heads * hd) @ params["wo"].astype(ct)


def apply_with_kv(params: Dict[str, Any], cfg: ModelConfig, x: jax.Array,
                  positions: jax.Array, *, window: int = 0
                  ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Prefill variant: same as :func:`apply` (causal self-attn) but also
    returns the post-RoPE (k, v) so the caller can seed a decode cache."""
    b, l, d = x.shape
    hd, ct = cfg.hd, cfg.cdtype
    q = x @ params["wq"].astype(ct)
    k = x @ params["wk"].astype(ct)
    v = x @ params["wv"].astype(ct)
    if "bq" in params:
        q = q + params["bq"].astype(ct)
        k = k + params["bk"].astype(ct)
        v = v + params["bv"].astype(ct)
    q = q.reshape(b, l, cfg.n_heads, hd)
    k = k.reshape(b, l, cfg.n_kv_heads, hd)
    v = v.reshape(b, l, cfg.n_kv_heads, hd)
    cos, sin = rope_angles(positions, hd, cfg.rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    if l >= FLASH_MIN_LEN and l % 512 == 0:
        from repro.models.flash import flash_attention
        out = flash_attention(q, k, v, causal=True, window=window,
                              softcap=cfg.attn_softcap)
    else:
        mask = make_causal_mask(l, l, window=window)[None]
        out = _sdpa(q, k, v, mask, cfg.attn_softcap)
    out = out.reshape(b, l, cfg.n_heads * hd) @ params["wo"].astype(ct)
    return out, (k, v)


def project_kv(params: Dict[str, Any], cfg: ModelConfig, mem: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Project encoder memory once for cross-attention reuse across decode steps."""
    b, s, _ = mem.shape
    ct = cfg.cdtype
    k = (mem @ params["wk"].astype(ct)).reshape(b, s, cfg.n_kv_heads, cfg.hd)
    v = (mem @ params["wv"].astype(ct)).reshape(b, s, cfg.n_kv_heads, cfg.hd)
    return k, v


# ==========================================================================
# Decode (one token against a KV cache)
# ==========================================================================


def init_cache(cfg: ModelConfig, batch: int, max_len: int, *,
               window: int = 0, dtype=None) -> Dict[str, jax.Array]:
    """Ring-buffer cache. Local layers allocate only ``window`` slots —
    the memory win that makes gemma2/recurrentgemma long-context decodable."""
    slots = min(window, max_len) if window else max_len
    dt = dtype or cfg.cdtype
    return {
        "k": jnp.zeros((batch, slots, cfg.n_kv_heads, cfg.hd), dt),
        "v": jnp.zeros((batch, slots, cfg.n_kv_heads, cfg.hd), dt),
    }


def decode_step(params: Dict[str, Any], cfg: ModelConfig, x: jax.Array,
                cache: Dict[str, jax.Array], pos: jax.Array, *,
                window: int = 0) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """x: [B,1,D]; pos: scalar absolute position. Returns (out [B,1,D], cache)."""
    b, l, _ = x.shape
    hd, ct = cfg.hd, cfg.cdtype
    q = (x @ params["wq"].astype(ct))
    k = (x @ params["wk"].astype(ct))
    v = (x @ params["wv"].astype(ct))
    if "bq" in params:
        q = q + params["bq"].astype(ct)
        k = k + params["bk"].astype(ct)
        v = v + params["bv"].astype(ct)
    q = q.reshape(b, l, cfg.n_heads, hd)
    k = k.reshape(b, l, cfg.n_kv_heads, hd)
    v = v.reshape(b, l, cfg.n_kv_heads, hd)
    cos, sin = rope_angles(pos[None], hd, cfg.rope_theta)
    q = apply_rope(q, cos[None], sin[None])
    k = apply_rope(k, cos[None], sin[None])

    from repro.parallel.mesh_ctx import current_ctx
    ctx = current_ctx()
    if (ctx is not None and ctx.shard_kv_seq
            and cache["k"].shape[1] % ctx.model_size == 0):
        out, ck, cv = _decode_seqshard(cfg, q, k, v, cache["k"], cache["v"],
                                       pos, window, ctx)
        out = out.reshape(b, l, cfg.n_heads * hd) @ params["wo"].astype(ct)
        return out, {"k": ck, "v": cv}

    slots = cache["k"].shape[1]
    slot = (pos % slots).astype(jnp.int32)
    ck = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                      (0, slot, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                      (0, slot, 0, 0))

    # validity of each slot at this absolute position (ring-buffer aware):
    # a slot is attendable iff it holds a position in [pos-window, pos]
    # (window=0 ⇒ [0, pos]; unwritten slots have age > pos and mask out).
    idx = jnp.arange(slots)
    age = pos - _slot_position(idx, slot, slots, pos)
    valid = (age >= 0) & (age <= pos)
    if window:
        valid &= age < window
    mask = jnp.broadcast_to(valid[None, None, :], (b, 1, slots))
    out = _sdpa(q, ck.astype(ct), cv.astype(ct), mask, cfg.attn_softcap)
    out = out.reshape(b, l, cfg.n_heads * hd) @ params["wo"].astype(ct)
    return out, {"k": ck, "v": cv}


def _slot_position(idx: jax.Array, cur_slot: jax.Array, slots: int,
                   pos: jax.Array) -> jax.Array:
    """Absolute position stored in each ring slot right after writing ``pos``."""
    delta = (cur_slot - idx) % slots
    return pos - delta


# ==========================================================================
# Flash-decoding (§Perf, beyond-paper): KV ring sharded over the model axis
# on the SEQUENCE dim with a two-phase softmax.  Per decode step the only
# cross-device traffic is the [B,H] max + [B,H] denominator + [B,H,hd]
# numerator psums — versus the [B,H,S] logits all-reduce the head-dim-sharded
# baseline pays (≈3 orders of magnitude less wire at S=32k).
# ==========================================================================


def _decode_seqshard(cfg: ModelConfig, q, k_new, v_new, cache_k, cache_v,
                     pos, window: int, ctx):
    b, l, h, hd = q.shape
    hkv = cfg.n_kv_heads
    g = h // hkv
    slots = cache_k.shape[1]
    m_ax = ctx.model_axis
    batch = tuple(ctx.batch_axes)
    P_ = jax.sharding.PartitionSpec
    cap = cfg.attn_softcap
    f32 = jnp.float32

    def shard(qs, kn, vn, ck, cv, pos):
        bl = qs.shape[0]                # local batch (sharded over batch axes)
        s_loc = ck.shape[1]
        rank = jax.lax.axis_index(m_ax)
        gslot = (pos % slots).astype(jnp.int32)
        owner = gslot // s_loc
        lslot = gslot % s_loc
        # row-granular conditional write: non-owners write back the existing
        # row (a full-tensor where() would force a cache copy per layer)
        cur_k = jax.lax.dynamic_slice(ck, (0, lslot, 0, 0), kn.shape)
        cur_v = jax.lax.dynamic_slice(cv, (0, lslot, 0, 0), vn.shape)
        is_owner = (rank == owner)
        ck = jax.lax.dynamic_update_slice(
            ck, jnp.where(is_owner, kn.astype(ck.dtype), cur_k), (0, lslot, 0, 0))
        cv = jax.lax.dynamic_update_slice(
            cv, jnp.where(is_owner, vn.astype(cv.dtype), cur_v), (0, lslot, 0, 0))

        # ring validity of this shard's columns at absolute position `pos`
        idx = rank * s_loc + jnp.arange(s_loc)              # global slots
        kpos = pos - (gslot - idx) % slots
        valid = (kpos >= 0) & (kpos <= pos)
        if window:
            valid &= kpos > pos - window

        qg = qs.reshape(bl, l, hkv, g, hd)
        logits = jnp.einsum("blkgd,bskd->bkgls", qg, ck.astype(qs.dtype),
                            preferred_element_type=f32) / jnp.sqrt(hd).astype(f32)
        logits = softcap(logits, cap)
        logits = jnp.where(valid[None, None, None, None, :], logits, NEG_INF)

        m_loc = jnp.max(logits, axis=-1)                    # [B,Hkv,G,1]
        m_glob = jax.lax.pmax(m_loc, m_ax)
        p = jnp.exp(logits - m_glob[..., None])
        den = jax.lax.psum(jnp.sum(p, axis=-1), m_ax)       # [B,Hkv,G,1]
        num = jax.lax.psum(
            jnp.einsum("bkgls,bskd->bkgld", p.astype(cv.dtype), cv,
                       preferred_element_type=f32), m_ax)   # [B,Hkv,G,1,hd]
        out = (num / den[..., None]).astype(qs.dtype)
        return jnp.moveaxis(out, 3, 1).reshape(bl, l, h, hd), ck, cv

    return jax.shard_map(
        shard,
        mesh=ctx.mesh,
        in_specs=(P_(batch), P_(batch), P_(batch),
                  P_(batch, m_ax), P_(batch, m_ax), P_()),
        out_specs=(P_(batch), P_(batch, m_ax), P_(batch, m_ax)),
        check_vma=False,
    )(q, k_new, v_new, cache_k, cache_v, pos)
