"""The model stage of a cell: the program's model at the configuration's
sizes, weights made here from the seed, and the two user functions of the
QA workflow (``sort`` builds an instance's prompts, ``qa`` serves them).

The weights are the benchmark's, not the program's: one jitted call draws
every leaf of the program's parameter tree from the seed, in the dtype it
is served in.  The plain reference reads the same arrays.  A configuration
file's ``draw`` table names rules for leaves the built-in table lacks.
"""

from __future__ import annotations

import dataclasses
import math
import threading
import time
import typing
from typing import Any, Dict, List, Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import lm
from repro.models.common import ModelConfig
from repro.serve import engine


def _typed(hint, value):
    """``value`` from a JSON file as the field typed ``hint`` holds it: a
    dict becomes the dataclass it names, a list the tuple."""
    if value is None:
        return None
    args = [a for a in typing.get_args(hint) if a is not type(None)]
    if typing.get_origin(hint) is typing.Union and len(args) == 1:
        hint = args[0]
    if dataclasses.is_dataclass(hint) and isinstance(value, Mapping):
        return _build(hint, value)
    if (hint is tuple or typing.get_origin(hint) is tuple) \
            and isinstance(value, list):
        return tuple(value)
    return value


def _build(cls, raw: Mapping[str, Any]):
    hints = typing.get_type_hints(cls)
    return cls(**{k: _typed(hints[k], v) if k in hints else v
                  for k, v in raw.items()})


def model_config(model: Dict[str, Any]) -> ModelConfig:
    """The program's config object from the ``model`` block of a
    configuration file.  Every dataclass-typed field (``moe``, ``ssm``,
    ``rglru``, any later sub-config) is built from its dict, and a list
    becomes a tuple where the field is one, by the fields' type hints."""
    return _build(ModelConfig, model)


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size (``PRNGKey`` keeps 32 bits)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


# leaf name → how the benchmark draws it (f32 normal unless noted)
_PROJECTIONS = {"wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "lm_head",
                "wz", "wx", "wb", "wc", "wdt", "w_out"}
_NORMS = {"ln1", "ln2", "final_norm"}


def _drawn(rule: Mapping[str, Any], shape, normal, uniform) -> jax.Array:
    """A leaf by one rule of a configuration's ``draw`` table."""
    (kind, arg), = rule.items()
    if kind == "normal":
        return normal(1.0 / math.sqrt(shape[-2]) if arg == "fan_in"
                      else float(arg))
    if kind == "uniform":
        return uniform(float(arg[0]), float(arg[1]))
    if kind == "log_uniform":
        return jnp.exp(uniform(math.log(arg[0]), math.log(arg[1])))
    if kind == "const":
        return jnp.full(shape, float(arg), jnp.float32)
    raise ValueError(f"unknown draw rule {rule!r}")


def _rule(path, draw: Mapping[str, Any]) -> Optional[Mapping[str, Any]]:
    """The ``draw`` entry for a leaf: its ``parent/leaf`` suffix first,
    then its name."""
    keys = [str(getattr(k, "key", k)) for k in path]
    for n in (2, 1):
        if len(keys) >= n and "/".join(keys[-n:]) in draw:
            return draw["/".join(keys[-n:])]
    return None


def _leaf(path, shape, key, draw: Mapping[str, Any]) -> jax.Array:
    normal = lambda s: jax.random.normal(key, shape, jnp.float32) * s  # noqa: E731
    uniform = lambda lo, hi: jax.random.uniform(  # noqa: E731
        key, shape, jnp.float32, lo, hi)
    rule = _rule(path, draw)
    if rule is not None:
        return _drawn(rule, shape, normal, uniform)
    name = path[-1].key
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


def make_weights(cfg: ModelConfig, seed: int,
                 draw: Optional[Mapping[str, Any]] = None):
    """Every parameter from the seed, on the device, in one jitted call.

    ``draw`` (a configuration file's table) maps a leaf's name, or the
    ``parent/leaf`` end of its path, to a rule: ``{"normal": std}``,
    ``{"normal": "fan_in"}`` (std 1/sqrt(shape[-2])), ``{"uniform": [lo,
    hi]}``, ``{"log_uniform": [lo, hi]}`` or ``{"const": v}``.  It is read
    before the built-in rules; a leaf that neither names raises
    ``KeyError``.  Each leaf's key is split off in the tree's path order,
    so a table changes no other leaf's draw."""
    draw = draw or {}
    shapes = jax.eval_shape(lambda k: lm.init(k, cfg), jax.random.PRNGKey(0))
    paths, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        keys = jax.random.split(key, len(paths))
        leaves = [_leaf(path, sds.shape, k, draw).astype(sds.dtype)
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
