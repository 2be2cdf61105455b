"""Prefill/decode serving engine.

``make_prefill_step`` / ``make_decode_step`` are the functions the
``prefill_*`` / ``decode_*`` / ``long_*`` dry-run cells lower.  The decode
step processes one token for the whole batch against the sharded KV cache
(:func:`repro.parallel.sharding.cache_shardings`).  ``greedy_generate`` is
the served path: a workflow stage or ``repro.launch.serve`` calls it.

The served programs ``prefill`` and ``decode`` take the caller's weights,
which may be a float32 master copy of a model that computes in bfloat16.
The model casts each matrix to ``cfg.cdtype`` where it reads it, so handed
the master copy, every call would cast every matrix again.  Instead the
engine builds the compute-dtype copy once per weight set
(:func:`repro.models.lm.compute_params`: the projections, conv filters and
biases, embedding and head) and hands that to the unchanged jitted programs
on every later call.  The leaves the model reads at float32 stay as they
are: the norm scales (``rms_norm``) and the SSM's ``A_log``, ``dt_bias``
and ``D`` (``ssm.decode_step``).  The operands of every matmul are the same
bits either way, so the served tokens are too.  Weights already in the
compute dtype are served as they are, with no copy.
"""

from __future__ import annotations

import threading
import weakref
from collections import OrderedDict
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


class _Entry:
    """One weight set's compute tree, valid while the caller's very leaf
    objects live (weak references: the entry pins no caller weights)."""

    def __init__(self, leaves, tree, nbytes: int):
        self.refs = [weakref.ref(x) for x in leaves]
        self.tree = tree               # None: the caller's tree serves as is
        self.nbytes = nbytes

    def holds(self, leaves) -> bool:
        return all(r() is x for r, x in zip(self.refs, leaves))

    def alive(self) -> bool:
        return all(r() is not None for r in self.refs)


class WeightCache:
    """Compute-dtype copies of the weight sets callers serve, built once
    each, under a lock, and at most ``capacity`` kept (least recently used
    dropped first).

    ``casts_built`` counts the copies built (a weight set already in the
    compute dtype gets an entry but no copy), ``cache_hits`` the calls
    served from an entry, ``bytes_held`` the device bytes of the copies the
    cache keeps."""

    capacity = 4

    def __init__(self):
        self._lock = threading.Lock()
        self._entries: "OrderedDict[tuple, _Entry]" = OrderedDict()
        self.casts_built = 0
        self.cache_hits = 0

    def get(self, params, cfg: ModelConfig):
        """The tree the programs are handed for ``params``."""
        leaves, treedef = jax.tree.flatten(params)
        key = (cfg.compute_dtype, treedef, tuple(map(id, leaves)))
        with self._lock:
            entry = self._entries.get(key)
            if entry is not None and entry.holds(leaves):
                self._entries.move_to_end(key)
                self.cache_hits += 1
            else:
                entry = self._build(key, params, leaves, cfg)
        return params if entry.tree is None else entry.tree

    def _drop_dead(self) -> None:
        for k in [k for k, e in self._entries.items() if not e.alive()]:
            del self._entries[k]

    def _build(self, key, params, leaves, cfg: ModelConfig) -> _Entry:
        self._drop_dead()
        with jax.profiler.TraceAnnotation("serve.cast_weights"):
            tree = jax.block_until_ready(lm.compute_params(params, cfg))
        if tree is params:
            entry = _Entry(leaves, None, 0)
        else:
            nbytes = sum(c.nbytes for c, x in zip(jax.tree.leaves(tree), leaves)
                         if c is not x)
            entry = _Entry(leaves, tree, nbytes)
            self.casts_built += 1
        self._entries[key] = entry
        while len(self._entries) > self.capacity:
            self._entries.popitem(last=False)
        return entry

    def __len__(self) -> int:
        return len(self._entries)

    @property
    def bytes_held(self) -> int:
        with self._lock:
            self._drop_dead()
            return sum(e.nbytes for e in self._entries.values())


def _abstract(params):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                       sharding=getattr(x, "sharding", None)),
        params)


class Served:
    """A jitted model program ``fn(params, cfg, ...)`` that runs on the
    compute tree of the weights it is handed.  ``lower`` lowers what a call
    runs: the same program at the compute tree's dtypes, each leaf's
    sharding kept."""

    def __init__(self, fn, static_argnames, cache: WeightCache):
        self.jitted = jax.jit(fn, static_argnames=static_argnames)
        self.cache = cache

    def __call__(self, params, cfg: ModelConfig, *args, **kwargs):
        return self.jitted(self.cache.get(params, cfg), cfg, *args, **kwargs)

    def lower(self, params, cfg: ModelConfig, *args, **kwargs):
        return self.jitted.lower(lm.compute_params(_abstract(params), cfg),
                                 cfg, *args, **kwargs)


# The served path's programs: jitted once per (cfg, max_len, shapes), so
# repeated requests of one shape reuse one executable; the device trace
# names them ``jit_prefill`` and ``jit_decode_step``.
weights = WeightCache()
prefill = Served(lm.prefill, ("cfg", "max_len"), weights)
decode = Served(lm.decode_step, ("cfg",), weights)


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
