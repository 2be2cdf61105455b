"""Prefill/decode serving engine.

``make_prefill_step`` / ``make_decode_step`` are the functions the
``prefill_*`` / ``decode_*`` / ``long_*`` dry-run cells lower.  The decode
step processes one token for the whole batch against the sharded KV cache
(:func:`repro.parallel.sharding.cache_shardings`).  ``greedy_generate`` is
the served path: a workflow stage or ``repro.launch.serve`` calls it.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.models import lm
from repro.models.common import ModelConfig


def make_prefill_step(cfg: ModelConfig, *, max_len: int):
    def prefill_step(params, inputs: Dict[str, jax.Array]):
        cache, logits = lm.prefill(params, cfg, inputs["tokens"],
                                   max_len=max_len,
                                   patches=inputs.get("patches"),
                                   frames=inputs.get("frames"))
        return cache, logits
    return prefill_step


def make_decode_step(cfg: ModelConfig):
    def decode_step(params, token: jax.Array, cache):
        return lm.decode_step(params, cfg, token, cache)
    return decode_step


# The served path's programs: jitted once per (cfg, max_len, shapes), so
# repeated requests of one shape reuse one executable.
prefill = jax.jit(lm.prefill, static_argnames=("cfg", "max_len"))
decode = jax.jit(lm.decode_step, static_argnames=("cfg",))


def greedy_generate(params, cfg: ModelConfig, prompt: jax.Array, steps: int, *,
                    max_len: Optional[int] = None) -> jax.Array:
    """Greedy decoding loop: jitted prefill, then ``steps - 1`` jitted
    decode steps.  prompt: [B, L] → generated ids [B, steps]."""
    b, l = prompt.shape
    max_len = max_len or (l + steps)
    cache, logits = prefill(params, cfg, prompt, max_len=max_len)

    # argmax over the real vocabulary: the padded tail of the head is not
    # a token
    toks = [jnp.argmax(logits[:, :cfg.vocab], axis=-1)[:, None]]
    for _ in range(steps - 1):
        logits, cache = decode(params, cfg, toks[-1], cache)
        toks.append(jnp.argmax(logits[:, :cfg.vocab], axis=-1)[:, None])
    return jnp.concatenate(toks, axis=1)
