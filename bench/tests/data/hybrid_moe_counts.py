"""Operations and bytes of the test configuration ``hybrid-moe.json``:
``bench/flops.py`` for the model without its experts, plus each expert
layer's router, shared expert and routed experts.

A token runs ``top_k`` routed experts and the shared one.  A decode step
of ``batch`` tokens reads the router, the shared expert and the routed
experts its tokens chose: under uniform routing E·(1 − (1 − k/E)^batch)
of them on average."""

import flops


def _dense(m):
    return dict(m, d_ff=0, moe=None)


def _moe_layers(m):
    return sum(k == "attn" for k in flops.kinds(_dense(m)))


def _expert(m):
    return 3 * m["d_model"] * m["moe"]["d_expert"]


def layer_split(m, kind):
    """Parameters of one layer of ``kind``, as (matrices, vectors)."""
    mats, vecs = flops.layer_split(_dense(m), kind)
    if kind == "attn":
        e = m["moe"]
        mats += m["d_model"] * e["num_experts"] \
            + _expert(m) * (e["num_experts"] + e["num_shared"])
    return mats, vecs


def _token_flops(m):
    e = m["moe"]
    return 2 * m["d_model"] * e["num_experts"] \
        + 2 * _expert(m) * (e["top_k"] + e["num_shared"])


def request_flops(m, request):
    b, L, t = request["prompts"], request["prompt_len"], request["new_tokens"]
    tokens = b * L + b * (t - 1)
    return flops.request_flops(_dense(m), request) \
        + _moe_layers(m) * tokens * _token_flops(m)


def mean_decode_bytes(m, request):
    e, b = m["moe"], request["prompts"]
    n, k = e["num_experts"], e["top_k"]
    chosen = n * (1 - (1 - k / n) ** b)
    per_layer = m["d_model"] * n + _expert(m) * (e["num_shared"] + chosen)
    cb = min(flops.DTYPE_BYTES[m["param_dtype"]],
             flops.DTYPE_BYTES[m["compute_dtype"]])
    return flops.mean_decode_bytes(_dense(m), request) \
        + _moe_layers(m) * per_layer * cb
