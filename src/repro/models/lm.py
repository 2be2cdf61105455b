"""LM assembly: one module serving all 10 assigned architectures.

Layer stacking: ``cfg.layer_pattern`` is cycled across ``n_layers``; the
full pattern repetitions are **scanned** (``lax.scan`` over stacked params,
HLO size independent of depth — essential for the 88-layer dry-runs), the
remainder layers are applied unrolled.  Each pattern slot ("attn", "local",
"ssm", "rglru") owns one stacked parameter tree.

Entry points
  * :func:`init` / :func:`init_shapes` — parameters (real / abstract).
  * :func:`forward` — tokens (+ modality stubs) → logits. train + prefill.
  * :func:`loss_fn` — next-token CE (+ MoE aux), the train_step objective.
  * :func:`prefill` — forward that also seeds a decode cache.
  * :func:`decode_step` — one token against the cache (the serve_step).
  * enc-dec (seamless-m4t): :func:`encode` feeds cross-attention.

Activation sharding: block boundaries constrain to
``[batch-axes, None, None]``; everything inside propagates from the parameter
shardings (:mod:`repro.parallel.sharding`).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention, mlp, moe, rglru, ssm
from repro.models.common import (ModelConfig, dense_init, embed_init, rms_norm,
                                 softcap, split_keys)
from repro.parallel.mesh_ctx import constrain, constrain_batch as _cb, current_ctx


# ==========================================================================
# Per-slot block init
# ==========================================================================


def _block_init(key, cfg: ModelConfig, kind: str, *, cross: bool = False) -> Dict[str, Any]:
    d = cfg.d_model
    ks = split_keys(key, ["a", "b", "c", "d"])
    p: Dict[str, Any] = {"ln1": jnp.zeros((d,), cfg.pdtype)}
    if kind in ("attn", "local"):
        p["attn"] = attention.init(ks["a"], cfg)
        if cfg.d_ff:
            p["ln2"] = jnp.zeros((d,), cfg.pdtype)
            if cfg.moe is not None:
                p["moe"] = moe.init(ks["b"], cfg)
            else:
                p["mlp"] = mlp.init(ks["b"], cfg)
        if cfg.post_norms:
            p["ln1b"] = jnp.zeros((d,), cfg.pdtype)
            if cfg.d_ff:
                p["ln2b"] = jnp.zeros((d,), cfg.pdtype)
        if cross:
            p["lnx"] = jnp.zeros((d,), cfg.pdtype)
            p["xattn"] = attention.init(ks["c"], cfg, cross=True)
    elif kind == "ssm":
        p["ssm"] = ssm.init(ks["a"], cfg)
    elif kind == "rglru":
        p["rec"] = rglru.init(ks["a"], cfg)
        if cfg.d_ff:
            p["ln2"] = jnp.zeros((d,), cfg.pdtype)
            p["mlp"] = mlp.init(ks["b"], cfg)
    else:
        raise ValueError(f"unknown block kind {kind}")
    return p


def _stack_init(key, cfg: ModelConfig, kind: str, n: int, *, cross: bool = False):
    keys = jax.random.split(key, n)
    trees = [_block_init(keys[i], cfg, kind, cross=cross) for i in range(n)]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def groups_of(cfg: ModelConfig, n_layers: Optional[int] = None) -> Tuple[int, int]:
    """(full pattern repetitions, remainder layers)."""
    n = cfg.n_layers if n_layers is None else n_layers
    p = len(cfg.layer_pattern)
    return n // p, n % p


def init(key, cfg: ModelConfig) -> Dict[str, Any]:
    g, rem = groups_of(cfg)
    ks = split_keys(key, ["embed", "blocks", "rem", "head", "enc", "front"])
    cross = cfg.enc_dec
    params: Dict[str, Any] = {
        "embed": embed_init(ks["embed"], cfg.padded_vocab, cfg.d_model, cfg.pdtype),
        "final_norm": jnp.zeros((cfg.d_model,), cfg.pdtype),
    }
    bkeys = split_keys(ks["blocks"], [f"s{i}" for i in range(len(cfg.layer_pattern))])
    params["blocks"] = {
        f"s{i}": _stack_init(bkeys[f"s{i}"], cfg, kind, g, cross=cross)
        for i, kind in enumerate(cfg.layer_pattern)}
    if rem:
        rkeys = jax.random.split(ks["rem"], rem)
        params["rem"] = {
            f"r{i}": _block_init(rkeys[i], cfg, cfg.pattern_of(g * len(cfg.layer_pattern) + i),
                                 cross=cross)
            for i in range(rem)}
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(ks["head"], cfg.d_model, cfg.padded_vocab,
                                       cfg.pdtype)
    if cfg.enc_dec:
        ek = split_keys(ks["enc"], ["blocks", "norm"])
        params["encoder"] = {
            "blocks": _stack_init(ek["blocks"], cfg, "attn", cfg.n_enc_layers),
            "norm": jnp.zeros((cfg.d_model,), cfg.pdtype),
        }
    if cfg.n_patches:          # vlm: patch-embedding projection (frontend stub)
        params["w_patch"] = dense_init(ks["front"], 1024, cfg.d_model, cfg.pdtype)
    if cfg.frame_input:        # audio: frame-embedding projection (frontend stub)
        params["w_frame"] = dense_init(ks["front"], 1024, cfg.d_model, cfg.pdtype)
    return params


def init_shapes(cfg: ModelConfig, seed: int = 0):
    """Abstract (ShapeDtypeStruct) parameter tree — no allocation (dry-run)."""
    return jax.eval_shape(functools.partial(init, cfg=cfg), jax.random.PRNGKey(seed))


# ==========================================================================
# Compute-dtype copy of the weights (the served path casts once)
# ==========================================================================

_ATTN_CAST = frozenset({"wq", "wk", "wv", "wo", "bq", "bk", "bv"})
# Leaves the forward and decode read only through ``.astype(cfg.cdtype)``,
# by the key of the module that owns them.  Left out on purpose: norm
# scales (``rms_norm`` reads them at float32), and ``A_log``, ``dt_bias``
# and ``D``, which ``ssm.decode_step`` reads at float32.
_CAST_LEAVES = {
    "ssm": frozenset({"wz", "wx", "wb", "wc", "wdt", "w_out",
                      "conv_x_w", "conv_x_b", "conv_b_w", "conv_b_b",
                      "conv_c_w", "conv_c_b"}),
    "attn": _ATTN_CAST,
    "xattn": _ATTN_CAST,
    "mlp": frozenset({"w_gate", "w_up", "w_down"}),
}
_CAST_TOP = frozenset({"embed", "lm_head"})      # the tied or untied head


def _cast_by_rule(path) -> bool:
    keys = [getattr(k, "key", None) for k in path]
    if len(keys) == 1:
        return keys[0] in _CAST_TOP
    return keys[-1] in _CAST_LEAVES.get(keys[-2], ())


def compute_params(params, cfg: ModelConfig):
    """The tree with every leaf that the model reads only as
    ``leaf.astype(cfg.cdtype)`` already in ``cfg.cdtype``.

    Those are the SSM block's projections and depthwise-conv filters and
    biases, attention's (and cross-attention's) projections and biases,
    the MLP's matrices, and the embedding table and untied head.  Casting
    them ahead gives the programs the same operands, bit for bit, so a
    served path can cast once per weight set instead of on every call.
    Every other leaf is returned as it is: the norm scales, which
    ``rms_norm`` reads at float32; ``A_log``, ``dt_bias`` and ``D``, which
    ``ssm.decode_step`` reads at float32 (prefill casts ``D``, decode does
    not); and whatever the rule does not name (MoE, RG-LRU, front ends),
    which stays correct and only keeps its per-call cast.

    Returns ``params`` itself when every leaf the rule names is already in
    ``cfg.cdtype``, so a tree served at its own dtype gains no copy.
    Abstract leaves (``ShapeDtypeStruct``) map to abstract leaves of
    ``cfg.cdtype`` with their sharding kept.
    """
    ct = cfg.cdtype
    named = [x for path, x in jax.tree_util.tree_leaves_with_path(params)
             if _cast_by_rule(path)]
    if all(x.dtype == ct for x in named):
        return params

    def cast(path, x):
        if not _cast_by_rule(path) or x.dtype == ct:
            return x
        if isinstance(x, jax.ShapeDtypeStruct):
            return jax.ShapeDtypeStruct(x.shape, ct, sharding=x.sharding)
        return x.astype(ct)

    return jax.tree_util.tree_map_with_path(cast, params)


# ==========================================================================
# Block application (train / prefill)
# ==========================================================================


def _block_apply(cfg: ModelConfig, kind: str, p: Dict[str, Any], x: jax.Array,
                 positions: jax.Array, memory: Optional[jax.Array],
                 collect_kv: bool):
    """Returns (x, aux_loss, cache_contrib or None)."""
    window = cfg.window if kind == "local" else 0
    aux = jnp.zeros((), jnp.float32)
    kv = None
    if kind in ("attn", "local"):
        h = rms_norm(x, p["ln1"], cfg.rms_eps)
        if collect_kv:
            a, (k_new, v_new) = attention.apply_with_kv(p["attn"], cfg, h,
                                                        positions, window=window)
            kv = {"k": k_new, "v": v_new}
        else:
            a = attention.apply(p["attn"], cfg, h, positions, window=window)
        if cfg.post_norms:
            a = rms_norm(a, p["ln1b"], cfg.rms_eps)
        x = _cb(x + a)
        if "xattn" in p:
            assert memory is not None
            h = rms_norm(x, p["lnx"], cfg.rms_eps)
            mk, mv = attention.project_kv(p["xattn"], cfg, memory)
            xa = attention.apply(p["xattn"], cfg, h, positions,
                                 kv_override=(mk, mv))
            x = _cb(x + xa)
            if collect_kv:
                kv["mk"], kv["mv"] = mk, mv
        if cfg.d_ff:
            h = rms_norm(x, p["ln2"], cfg.rms_eps)
            if cfg.moe is not None:
                f = moe.apply(p["moe"], cfg, h)
                aux = aux + moe.aux_loss(p["moe"], cfg, h)
            else:
                f = mlp.apply(p["mlp"], cfg, h)
            if cfg.post_norms:
                f = rms_norm(f, p["ln2b"], cfg.rms_eps)
            x = _cb(x + f)
    elif kind == "ssm":
        h = rms_norm(x, p["ln1"], cfg.rms_eps)
        if collect_kv:
            y, state = ssm.apply_with_state(p["ssm"], cfg, h)
            kv = state
        else:
            y = ssm.apply(p["ssm"], cfg, h)
        x = _cb(x + y)
    elif kind == "rglru":
        h = rms_norm(x, p["ln1"], cfg.rms_eps)
        if collect_kv:
            y, state = rglru.apply_with_state(p["rec"], cfg, h)
            kv = state
        else:
            y = rglru.apply(p["rec"], cfg, h)
        x = _cb(x + y)
        if cfg.d_ff:
            h = rms_norm(x, p["ln2"], cfg.rms_eps)
            x = _cb(x + mlp.apply(p["mlp"], cfg, h))
    return x, aux, kv


_REMAT_POLICIES = {
    "none": None,
    "dots": lambda: jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
    "full": lambda: jax.checkpoint_policies.nothing_saveable,
}


def _maybe_remat(cfg: ModelConfig, fn):
    if cfg.remat == "none":
        return fn
    policy = _REMAT_POLICIES[cfg.remat]()
    return jax.checkpoint(fn, policy=policy, prevent_cse=False)


def _run_blocks(params: Dict[str, Any], cfg: ModelConfig, x: jax.Array,
                positions: jax.Array, memory: Optional[jax.Array],
                collect_kv: bool):
    """Scan the stacked pattern groups, then the unrolled remainder.

    Returns (x, total_aux, caches) where caches[slot] is stacked over groups
    (plus caches[f"r{i}"] for remainder layers) when ``collect_kv``.
    """
    pattern = cfg.layer_pattern

    def group_body(carry, gp):
        x, aux = carry
        kvs = {}
        for i, kind in enumerate(pattern):
            x, a, kv = _block_apply(cfg, kind, gp[f"s{i}"], x, positions,
                                    memory, collect_kv)
            aux = aux + a
            if collect_kv:
                kvs[f"s{i}"] = kv
        return (x, aux), (kvs if collect_kv else None)

    body = _maybe_remat(cfg, group_body)
    aux0 = jnp.zeros((), jnp.float32)
    if cfg.scan_layers:
        (x, aux), kvs = jax.lax.scan(body, (x, aux0), params["blocks"])
    else:
        g = jax.tree.leaves(params["blocks"])[0].shape[0]
        kv_list = []
        x_aux = (x, aux0)
        for gi in range(g):
            gp = jax.tree.map(lambda a: a[gi], params["blocks"])
            x_aux, kvs_i = body(x_aux, gp)
            kv_list.append(kvs_i)
        x, aux = x_aux
        kvs = (jax.tree.map(lambda *xs: jnp.stack(xs), *kv_list)
               if collect_kv and kv_list else None)

    caches: Dict[str, Any] = dict(kvs or {}) if collect_kv else {}
    g = jax.tree.leaves(params["blocks"])[0].shape[0]
    for i, (name, rp) in enumerate(sorted(params.get("rem", {}).items())):
        kind = cfg.pattern_of(g * len(pattern) + i)
        x, a, kv = _block_apply(cfg, kind, rp, x, positions, memory, collect_kv)
        aux = aux + a
        if collect_kv:
            caches[name] = kv
    return x, aux, caches


# ==========================================================================
# Embedding / head
# ==========================================================================


def _embed(params, cfg: ModelConfig, tokens: jax.Array,
           patches: Optional[jax.Array], frames: Optional[jax.Array]):
    ct = cfg.cdtype
    x = jnp.take(params["embed"], tokens, axis=0).astype(ct)
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, ct)
    if cfg.n_patches and patches is not None:
        pe = (patches.astype(ct) @ params["w_patch"].astype(ct))
        x = jnp.concatenate([pe, x], axis=1)
    return _cb(x)


def _logits(params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    ct = cfg.cdtype
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    logits = x @ head.astype(ct)
    logits = softcap(logits.astype(jnp.float32), cfg.logit_softcap)
    ctx = current_ctx()
    if ctx is not None:
        spec = [tuple(ctx.batch_axes)] + [None] * (logits.ndim - 2) + [ctx.model_axis]
        logits = constrain(logits, *spec)
    return logits


# ==========================================================================
# Forward / loss (train + prefill paths)
# ==========================================================================


def encode(params, cfg: ModelConfig, frames: jax.Array) -> jax.Array:
    """Encoder for enc-dec archs; ``frames`` are frontend-stub embeddings."""
    ct = cfg.cdtype
    x = _cb(frames.astype(ct) @ params["w_frame"].astype(ct))
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    enc = params["encoder"]

    def body(carry, gp):
        x, _ = carry
        h = rms_norm(x, gp["ln1"], cfg.rms_eps)
        a = attention.apply(gp["attn"], cfg, h, positions, causal=False)
        x = _cb(x + a)
        h = rms_norm(x, gp["ln2"], cfg.rms_eps)
        x = _cb(x + mlp.apply(gp["mlp"], cfg, h))
        return (x, carry[1]), None

    body = _maybe_remat(cfg, body)
    (x, _), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), enc["blocks"])
    return rms_norm(x, enc["norm"], cfg.rms_eps)


def forward(params, cfg: ModelConfig, tokens: jax.Array, *,
            patches: Optional[jax.Array] = None,
            frames: Optional[jax.Array] = None) -> Tuple[jax.Array, jax.Array]:
    """tokens [B, Lt] → (logits [B, L, Vp], aux).  L = Lt + n_patches."""
    memory = encode(params, cfg, frames) if cfg.enc_dec else None
    x = _embed(params, cfg, tokens, patches, None)
    b, l, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(l)[None], (b, l))
    x, aux, _ = _run_blocks(params, cfg, x, positions, memory, collect_kv=False)
    return _logits(params, cfg, x), aux


def loss_fn(params, cfg: ModelConfig, batch: Dict[str, jax.Array]
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Next-token cross-entropy over ``batch["tokens"]/["labels"]/["mask"]``."""
    logits, aux = forward(params, cfg, batch["tokens"],
                          patches=batch.get("patches"),
                          frames=batch.get("frames"))
    labels = batch["labels"]
    if cfg.n_patches:                      # vlm: loss only over the text tail
        logits = logits[:, cfg.n_patches:, :]
    # Sharded-vocab CE: take_along_axis/log_softmax over a model-sharded vocab
    # would all-gather full logits (≈13 GB/device at 50k vocab — §Perf iter 0).
    # Stable logsumexp + one-hot contraction keep everything vocab-local; only
    # [B, L] partials cross the model axis.
    m = jnp.max(logits, axis=-1, keepdims=True)
    lse = m[..., 0] + jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1))
    vocab_iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2)
    label_logit = jnp.sum(jnp.where(vocab_iota == labels[..., None], logits, 0.0),
                          axis=-1)
    ll = label_logit - lse
    mask = batch.get("mask")
    if mask is None:
        mask = jnp.ones_like(ll)
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    ce = -jnp.sum(ll * mask) / denom
    loss = ce + cfg.aux_loss_weight * aux
    return loss, {"ce": ce, "aux": aux,
                  "tokens": denom.astype(jnp.float32)}


# ==========================================================================
# Serving: prefill → cache, decode_step (the serve_step of decode_* cells)
# ==========================================================================


def _attn_slots(cfg: ModelConfig, kind: str, max_len: int) -> int:
    """Local layers only allocate a window-sized ring (the memory win that
    makes gemma2/recurrentgemma long contexts decodable)."""
    return min(cfg.window, max_len) if (kind == "local" and cfg.window) else max_len


def _ring_from_prefill(k: jax.Array, slots: int) -> jax.Array:
    """[B,L,Hkv,hd] → ring cache [B,slots,Hkv,hd].

    Ring invariant: position ``p`` lives in slot ``p % slots``.  For L > slots
    the kept window starts at p0 = L−slots, so the kept rows are rolled by
    ``p0 % slots`` to land in their slots.
    """
    l = k.shape[1]
    if l <= slots:
        return jnp.pad(k, ((0, 0), (0, slots - l), (0, 0), (0, 0)))
    p0 = l - slots
    return jnp.roll(k[:, -slots:], p0 % slots, axis=1)


def prefill(params, cfg: ModelConfig, tokens: jax.Array, *, max_len: int,
            patches: Optional[jax.Array] = None,
            frames: Optional[jax.Array] = None):
    """Run the full prompt, seed the decode cache.

    Returns (cache, last_logits [B, Vp]).  ``max_len`` sizes the KV rings of
    full-attention layers (prompt + decode budget).
    """
    memory = encode(params, cfg, frames) if cfg.enc_dec else None
    x = _embed(params, cfg, tokens, patches, None)
    b, l, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(l)[None], (b, l))
    x, _, raw = _run_blocks(params, cfg, x, positions, memory, collect_kv=True)

    pattern = cfg.layer_pattern
    g = jax.tree.leaves(params["blocks"])[0].shape[0]

    def to_cache(kind: str, kv, stacked: bool):
        if kind in ("attn", "local"):
            slots = _attn_slots(cfg, kind, max_len)
            ring = (jax.vmap(lambda a: _ring_from_prefill(a, slots)) if stacked
                    else (lambda a: _ring_from_prefill(a, slots)))
            out = {"k": ring(kv["k"]), "v": ring(kv["v"])}
            if "mk" in kv:
                out["mk"], out["mv"] = kv["mk"], kv["mv"]
            return out
        return kv                                  # ssm / rglru state dicts

    cache: Dict[str, Any] = {"blocks": {}, "rem": {}}
    for name, kv in raw.items():
        if name[0] == "s":
            kind = pattern[int(name[1:])]
            cache["blocks"][name] = to_cache(kind, kv, stacked=True)
        else:
            kind = cfg.pattern_of(g * len(pattern) + int(name[1:]))
            cache["rem"][name] = to_cache(kind, kv, stacked=False)
    if not cache["rem"]:
        del cache["rem"]
    cache["pos"] = jnp.asarray(l, jnp.int32)
    logits = _logits(params, cfg, x[:, -1:, :])[:, 0, :]
    return cache, logits


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Dict[str, Any]:
    """Empty decode cache (the SDS stand-in of the decode_* dry-run cells)."""
    g, rem = groups_of(cfg)
    ct = cfg.cdtype

    def one(kind: str):
        if kind in ("attn", "local"):
            slots = _attn_slots(cfg, kind, max_len)
            c = {"k": jnp.zeros((batch, slots, cfg.n_kv_heads, cfg.hd), ct),
                 "v": jnp.zeros((batch, slots, cfg.n_kv_heads, cfg.hd), ct)}
            if cfg.enc_dec:
                s_enc = max(1, max_len // 8)
                c["mk"] = jnp.zeros((batch, s_enc, cfg.n_kv_heads, cfg.hd), ct)
                c["mv"] = jnp.zeros((batch, s_enc, cfg.n_kv_heads, cfg.hd), ct)
            return c
        if kind == "ssm":
            return ssm.init_state(cfg, batch)
        if kind == "rglru":
            return rglru.init_state(cfg, batch)
        raise ValueError(kind)

    def stack(tree, n):
        return jax.tree.map(lambda a: jnp.broadcast_to(a[None], (n,) + a.shape), tree)

    cache: Dict[str, Any] = {"blocks": {
        f"s{i}": stack(one(kind), g) for i, kind in enumerate(cfg.layer_pattern)}}
    if rem:
        cache["rem"] = {f"r{i}": one(cfg.pattern_of(g * len(cfg.layer_pattern) + i))
                        for i in range(rem)}
    cache["pos"] = jnp.asarray(max_len - 1, jnp.int32)
    return cache


def _block_decode(cfg: ModelConfig, kind: str, p, x, gc, pos):
    """One block, one token. x: [B,1,D] → (x, new_cache)."""
    window = cfg.window if kind == "local" else 0
    nc = dict(gc)
    if kind in ("attn", "local"):
        h = rms_norm(x, p["ln1"], cfg.rms_eps)
        a, kvc = attention.decode_step(p["attn"], cfg, h,
                                       {"k": gc["k"], "v": gc["v"]}, pos,
                                       window=window)
        nc["k"], nc["v"] = kvc["k"], kvc["v"]
        if cfg.post_norms:
            a = rms_norm(a, p["ln1b"], cfg.rms_eps)
        x = x + a
        if "xattn" in p:
            h = rms_norm(x, p["lnx"], cfg.rms_eps)
            xa = attention.apply(p["xattn"], cfg, h, positions=None,
                                 kv_override=(gc["mk"], gc["mv"]), causal=False)
            x = x + xa
        if cfg.d_ff:
            h = rms_norm(x, p["ln2"], cfg.rms_eps)
            f = moe.apply(p["moe"], cfg, h) if cfg.moe is not None \
                else mlp.apply(p["mlp"], cfg, h)
            if cfg.post_norms:
                f = rms_norm(f, p["ln2b"], cfg.rms_eps)
            x = x + f
    elif kind == "ssm":
        h = rms_norm(x, p["ln1"], cfg.rms_eps)
        y, st = ssm.decode_step(p["ssm"], cfg, h, gc)
        nc = st
        x = x + y
    elif kind == "rglru":
        h = rms_norm(x, p["ln1"], cfg.rms_eps)
        y, st = rglru.decode_step(p["rec"], cfg, h, gc)
        nc = st
        x = x + y
        if cfg.d_ff:
            h = rms_norm(x, p["ln2"], cfg.rms_eps)
            x = x + mlp.apply(p["mlp"], cfg, h)
    return x, nc


def decode_step(params, cfg: ModelConfig, token: jax.Array, cache: Dict[str, Any]
                ) -> Tuple[jax.Array, Dict[str, Any]]:
    """One decode step for the whole batch.  token: [B,1] → logits [B, Vp]."""
    pos = cache["pos"]
    x = _embed(params, cfg, token, None, None)
    pattern = cfg.layer_pattern

    def body(x, xs):
        gp, gc = xs
        ncs = {}
        for i, kind in enumerate(pattern):
            x, nc = _block_decode(cfg, kind, gp[f"s{i}"], x, gc[f"s{i}"], pos)
            ncs[f"s{i}"] = nc
        return x, ncs

    x, new_blocks = jax.lax.scan(body, x, (params["blocks"], cache["blocks"]))
    new_cache: Dict[str, Any] = {"blocks": new_blocks}
    if "rem" in cache:
        g = jax.tree.leaves(params["blocks"])[0].shape[0]
        new_cache["rem"] = {}
        for i, (name, rp) in enumerate(sorted(params["rem"].items())):
            kind = cfg.pattern_of(g * len(pattern) + i)
            x, nc = _block_decode(cfg, kind, rp, x, cache["rem"][name], pos)
            new_cache["rem"][name] = nc
    new_cache["pos"] = pos + 1
    logits = _logits(params, cfg, x)[:, 0, :]
    return logits, new_cache

