"""The model stage of a cell: the program's model at the configuration's
sizes, weights made here from the seed, and the two user functions of the
QA workflow (``sort`` builds an instance's prompts, ``qa`` serves them).

The weights are the benchmark's, not the program's: one jitted call draws
every leaf of the program's parameter tree from the seed, in the dtype it
is served in.  The plain reference reads the same arrays.
"""

from __future__ import annotations

import math
import threading
import time
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import lm
from repro.models.common import ModelConfig, SSMConfig
from repro.serve import engine


def model_config(model: Dict[str, Any]) -> ModelConfig:
    """The program's config object from the ``model`` block of a
    configuration file."""
    kw = dict(model)
    if "layer_pattern" in kw:
        kw["layer_pattern"] = tuple(kw["layer_pattern"])
    if kw.get("ssm") is not None:
        kw["ssm"] = SSMConfig(**kw["ssm"])
    return ModelConfig(**kw)


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size (``PRNGKey`` keeps 32 bits)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


# leaf name → how the benchmark draws it (f32 normal unless noted)
_PROJECTIONS = {"wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "lm_head",
                "wz", "wx", "wb", "wc", "wdt", "w_out"}
_NORMS = {"ln1", "ln2", "final_norm"}


def _leaf(name: str, shape, key) -> jax.Array:
    normal = lambda s: jax.random.normal(key, shape, jnp.float32) * s  # noqa: E731
    uniform = lambda lo, hi: jax.random.uniform(  # noqa: E731
        key, shape, jnp.float32, lo, hi)
    if name in _PROJECTIONS:
        return normal(1.0 / math.sqrt(shape[-2]))
    if name == "embed":
        return normal(0.02)
    if name in _NORMS:
        return normal(0.1)
    if name.startswith("conv_") and name.endswith("_w"):
        return normal(0.1)
    if name.startswith("conv_") and name.endswith("_b"):
        return normal(0.02)
    if name == "A_log":
        return jnp.log(uniform(1.0, 16.0))
    if name == "D":
        return uniform(0.5, 1.5)
    if name == "dt_bias":           # softplus⁻¹ of dt, log-uniform on [1e-3, 1e-1]
        dt = jnp.exp(uniform(math.log(1e-3), math.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    raise KeyError(f"no rule to draw parameter {name!r}")


def make_weights(cfg: ModelConfig, seed: int):
    """Every parameter from the seed, on the device, in one jitted call."""
    shapes = jax.eval_shape(lambda k: lm.init(k, cfg), jax.random.PRNGKey(0))
    paths, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        keys = jax.random.split(key, len(paths))
        leaves = [_leaf(path[-1].key, sds.shape, k).astype(sds.dtype)
                  for (path, sds), k in zip(paths, keys)]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.block_until_ready(jax.jit(build)(seed_key(seed)))


def prompts_of(seed: int, instance: int, n: int, length: int,
               vocab: int) -> List[List[int]]:
    """An instance's prompts: ``n`` questions of ``length`` token ids."""
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, instance])
    return rng.integers(0, vocab, (n, length)).tolist()


class Stage:
    """The QA workflow's two user functions, bound to one model.

    ``qa`` serves an instance's prompts with the program's jitted prefill
    and decode and greedy tokens over the real vocabulary.  Every call is
    logged (instance, start, end in ms on the runner's clock) and its output
    kept, so the check can hold the committed output to what the stage
    computed."""

    def __init__(self, model: Dict[str, Any], params, request: Dict[str, int],
                 seed: int):
        self.model = model                        # the configuration's block
        self.cfg = model_config(model)
        self.params, self.seed = params, seed
        self.prompts = int(request["prompts"])
        self.prompt_len = int(request["prompt_len"])
        self.new_tokens = int(request["new_tokens"])
        self.lock = threading.Lock()
        self.calls: List[tuple] = []              # (instance, t0_ms, t1_ms)
        self.outputs: Dict[int, List[List[int]]] = {}

    @property
    def max_len(self) -> int:
        return self.prompt_len + self.new_tokens

    def sort(self, event: Dict[str, int]) -> Dict[str, Any]:
        i = int(event["instance"])
        with jax.profiler.TraceAnnotation("bench.sort"):
            prompts = prompts_of(self.seed, i, self.prompts, self.prompt_len,
                                 self.cfg.vocab)
        return {"instance": i, "prompts": prompts}

    def generate(self, prompt: jax.Array) -> jax.Array:
        """Greedy tokens [B, new_tokens] for a prompt batch [B, L]."""
        cfg, params, vocab = self.cfg, self.params, self.cfg.vocab
        with jax.profiler.TraceAnnotation("bench.qa.prefill"):
            cache, logits = engine.prefill(params, cfg, prompt,
                                           max_len=self.max_len)
            toks = [jnp.argmax(logits[:, :vocab], axis=-1)[:, None]]
        with jax.profiler.TraceAnnotation("bench.qa.decode_loop"):
            for _ in range(self.new_tokens - 1):
                logits, cache = engine.decode(params, cfg, toks[-1], cache)
                toks.append(jnp.argmax(logits[:, :vocab], axis=-1)[:, None])
            return jnp.concatenate(toks, axis=1)

    def qa(self, msg: Dict[str, Any]) -> Dict[str, Any]:
        t0 = time.monotonic() * 1e3
        i = int(msg["instance"])
        with jax.profiler.TraceAnnotation("bench.qa.to_device"):
            prompt = jnp.asarray(np.asarray(msg["prompts"], np.int32))
        out = self.generate(prompt)
        with jax.profiler.TraceAnnotation("bench.qa.to_host"):
            tokens = np.asarray(out).tolist()
        t1 = time.monotonic() * 1e3
        with self.lock:
            self.calls.append((i, t0, t1))
            self.outputs[i] = tokens
        return {"instance": i, "tokens": tokens}

    def warm_up(self) -> None:
        """Compile (or load from the cache) every program one ``qa`` call
        runs, at this cell's shapes and no others."""
        zeros = jnp.zeros((self.prompts, self.prompt_len), jnp.int32)
        np.asarray(self.generate(zeros))
