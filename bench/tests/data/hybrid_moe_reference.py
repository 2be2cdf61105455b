"""Plain reference of the test configuration ``hybrid-moe.json``: Mamba-2
layers and grouped-query attention layers whose MLP is a mixture of
experts, built on the public pieces of ``harness/reference.py``.

The expert layer: the router's softmax over all experts, the ``top_k``
largest renormalised to sum to 1, each chosen expert a gated-SiLU MLP
weighted by its share, plus the shared expert (a gated-SiLU MLP of width
``d_expert * num_shared``) on every token.  Every expert runs on every
token and the unchosen ones are weighted by 0: no capacity, nothing
dropped.
"""

import jax
import jax.numpy as jnp

from harness import reference as ref


def routed(y, p, model, q):
    """The routed experts' weighted sum over the normed input ``y``."""
    moe = model["moe"]
    probs = jax.nn.softmax(ref.matmul(y, p["router"], q), -1)   # [B,S,E]
    top, ids = jax.lax.top_k(probs, moe["top_k"])
    top = top / jnp.sum(top, -1, keepdims=True)
    share = jnp.sum(jax.nn.one_hot(ids, moe["num_experts"]) * top[..., None],
                    axis=-2)                                    # [B,S,E]
    return sum(share[..., e:e + 1] * ref.gated_mlp(
        y, {k: p[k][e] for k in ("w_gate", "w_up", "w_down")}, q)
        for e in range(moe["num_experts"]))


def attn_moe_layer(x, p, model, q, shared=True):
    """Attention, then the expert layer in the MLP's place."""
    eps = model["rms_eps"]
    x = x + ref.attention(ref.norm(x, p["ln1"], eps), p["attn"], model, q)
    y = ref.norm(x, p["ln2"], eps)
    out = routed(y, p["moe"], model, q)
    if shared:
        out = out + ref.gated_mlp(y, p["moe"]["shared"], q)
    return x + out


def logits(params, model, tokens, first, control=False):
    return ref.logits(params, model, tokens, first, control,
                      layers={"ssm": ref.ssm_layer, "attn": attn_moe_layer})
