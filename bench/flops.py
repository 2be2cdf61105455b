"""Operations and bytes of the served model's programs, from its shapes.

The yardstick of ``stage_mfu.*`` and ``decode_roofline.*``.  It reads the
``model`` block of a configuration file and counts what the model needs,
not what an implementation happens to compute: causal attention counts the
keys at or before each query, a decode step the keys it has, the head the
real vocabulary and the rows it reads.  A multiply-add is two operations.

Layer kinds: ``attn`` (grouped-query attention with a gated-SiLU MLP, the
llama family) and ``ssm`` (Mamba-2's SSD block, single group, with its
chunked dual form inside a chunk of ``ssm.chunk`` positions).

The default counts of every cell (``spec.counts_of``).  A configuration
with layers this module does not count names a counts module of its own
in its file (``"counts"``); ``harness/layers.py`` calls its
``request_flops(model, request)`` and ``mean_decode_bytes(model,
request)``, and it may build on the functions here.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2}

Model = Dict[str, Any]


def kinds(m: Model):
    """The kind of each of the model's layers: ``layer_pattern`` cycled
    over ``n_layers``.  Raises ``ValueError`` for a model with layers this
    module does not count (a kind other than ``attn`` and ``ssm``, or
    experts), which would otherwise be counted as something else."""
    pattern = m.get("layer_pattern", ["attn"])
    other = sorted(set(pattern) - {"attn", "ssm"})
    if m.get("moe"):
        other.append("moe")
    if other:
        raise ValueError(f"bench/flops.py counts no {other} layers: the "
                         f"configuration names counts of its own")
    return [pattern[i % len(pattern)] for i in range(m["n_layers"])]


def _hd(m: Model) -> int:
    return m.get("head_dim") or m["d_model"] // m["n_heads"]


def _ssm_dims(m: Model):
    s, d = m["ssm"], m["d_model"]
    di = s["expand"] * d
    return di, di // s["head_dim"], s["head_dim"], s["d_state"], s["d_conv"]


def layer_split(m: Model, kind: str) -> Tuple[int, int]:
    """Parameters of one layer of ``kind``, as (matrices, vectors): the
    projections, convolution filters and their biases, against the
    per-channel vectors the math reads in float32 (norm scales, ``A_log``,
    ``D``, ``dt_bias``)."""
    d = m["d_model"]
    if kind == "attn":
        hd, h, kv = _hd(m), m["n_heads"], m["n_kv_heads"]
        return (d * h * hd + 2 * d * kv * hd + h * hd * d      # wq wk wv wo
                + 3 * d * m["d_ff"],                           # w_gate w_up w_down
                2 * d)                                         # ln1 ln2
    if kind == "ssm":
        di, nh, _, n, k = _ssm_dims(m)
        return (2 * d * di + 2 * d * n + d * nh                # wz wx wb wc wdt
                + (k + 1) * (di + 2 * n)                       # convs and biases
                + di * d,                                      # w_out
                3 * nh + d)                                    # A_log D dt_bias ln1
    raise ValueError(f"unknown layer kind {kind!r}")


def layer_params(m: Model, kind: str) -> int:
    """Parameters of one layer of ``kind``."""
    return sum(layer_split(m, kind))


def decode_weight_bytes(m: Model, batch: int) -> int:
    """Weight bytes one decode step reads: every layer, the final norm, the
    head over the real vocabulary, and the embedding rows of the batch
    (the whole table when the head is tied to it).

    A step needs each weight in the dtype it multiplies in: matrices count
    at the narrower of ``param_dtype`` and ``compute_dtype``, since a wider
    master copy is the implementation's choice and not the model's need.
    Per-channel vectors count at ``param_dtype``, as the math reads them
    in float32."""
    d, v = m["d_model"], m["vocab"]
    mats, vecs = map(sum, zip(*(layer_split(m, k) for k in kinds(m))))
    mats += v * d
    if not m.get("tie_embeddings", False):
        mats += batch * d
    vecs += d                                                  # final norm
    pb = DTYPE_BYTES[m.get("param_dtype", "float32")]
    cb = min(pb, DTYPE_BYTES[m.get("compute_dtype", "bfloat16")])
    return mats * cb + vecs * pb


def _layer_prefill_flops(m: Model, kind: str, length: int) -> float:
    d, L = m["d_model"], length
    if kind == "attn":
        hd, h, kv = _hd(m), m["n_heads"], m["n_kv_heads"]
        proj = 2 * L * d * (h * hd + 2 * kv * hd) + 2 * L * h * hd * d
        attn = 2 * 2 * h * hd * (L * (L + 1) / 2)          # QKᵀ and PV, causal
        mlp = 2 * L * 3 * d * m["d_ff"]
        return proj + attn + mlp
    di, nh, p, n, k = _ssm_dims(m)
    q = min(m["ssm"]["chunk"], L)
    proj = 2 * L * d * (2 * di + 2 * n + nh) + 2 * L * di * d
    conv = 2 * L * k * (di + 2 * n)
    # within a chunk: C·Bᵀ and the masked mix of x over earlier positions;
    # across chunks: each chunk's state, then its read-out per position
    pairs = (L // q) * q * (q + 1) / 2 + (L % q) * (L % q + 1) / 2
    ssd = 2 * pairs * n + 2 * pairs * nh * p + 2 * 2 * L * nh * p * n
    return proj + conv + ssd


def _layer_decode_flops(m: Model, kind: str, pos: int) -> float:
    d = m["d_model"]
    if kind == "attn":
        hd, h, kv = _hd(m), m["n_heads"], m["n_kv_heads"]
        return (2 * d * (h * hd + 2 * kv * hd) + 2 * h * hd * d
                + 2 * 2 * h * hd * (pos + 1) + 2 * 3 * d * m["d_ff"])
    di, nh, p, n, k = _ssm_dims(m)
    return (2 * d * (2 * di + 2 * n + nh) + 2 * di * d
            + 2 * k * (di + 2 * n) + 2 * 2 * nh * p * n)


def prefill_flops(m: Model, batch: int, length: int) -> float:
    """A prefill of ``batch`` prompts of ``length``, with the logits of the
    last position."""
    per_seq = sum(_layer_prefill_flops(m, k, length) for k in kinds(m))
    return batch * (per_seq + 2 * m["d_model"] * m["vocab"])


def decode_flops(m: Model, batch: int, pos: int) -> float:
    """One decode step of ``batch`` tokens at absolute position ``pos``."""
    per_tok = sum(_layer_decode_flops(m, k, pos) for k in kinds(m))
    return batch * (per_tok + 2 * m["d_model"] * m["vocab"])


def decode_state_bytes(m: Model, batch: int, pos: int) -> int:
    """Cache bytes one decode step at ``pos`` moves: the keys and values it
    attends to plus the row it writes, or the recurrent state it reads and
    writes back."""
    cb = DTYPE_BYTES[m.get("compute_dtype", "bfloat16")]
    total = 0
    for kind in kinds(m):
        if kind == "attn":
            total += 2 * (pos + 2) * m["n_kv_heads"] * _hd(m) * cb
        else:
            di, nh, p, n, k = _ssm_dims(m)
            total += 2 * (nh * p * n * 4 + (k - 1) * (di + 2 * n) * cb)
    return batch * total


def decode_bytes(m: Model, batch: int, pos: int) -> int:
    return decode_weight_bytes(m, batch) + decode_state_bytes(m, batch, pos)


def request_flops(m: Model, request: Dict[str, int]) -> float:
    """One ``qa`` call: prefill of the prompts, then ``new_tokens - 1``
    decode steps (the first token comes from the prefill's logits)."""
    b, L, t = request["prompts"], request["prompt_len"], request["new_tokens"]
    return prefill_flops(m, b, L) + sum(decode_flops(m, b, L + i)
                                        for i in range(t - 1))


def mean_decode_bytes(m: Model, request: Dict[str, int]) -> float:
    b, L, t = request["prompts"], request["prompt_len"], request["new_tokens"]
    steps = [decode_bytes(m, b, L + i) for i in range(t - 1)]
    return sum(steps) / len(steps)
