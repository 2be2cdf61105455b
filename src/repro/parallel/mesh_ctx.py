"""Mesh context: which mesh/axes the model code is being traced under.

Model code (attention/moe/ssm) is mesh-agnostic jnp; where a distribution
decision matters (sharding constraints, the shard_map expert-parallel path)
it consults the ambient :class:`MeshCtx`.  Smoke tests and the pure-jnp
oracles run with no context set — every mesh-aware branch must degrade to
plain jnp in that case.
"""

from __future__ import annotations

import contextlib
import contextvars
from dataclasses import dataclass, field
from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclass(frozen=True)
class MeshCtx:
    """The distribution environment of the current trace.

    ``batch_axes`` — mesh axes the global batch shards over (``("pod","data")``
    on the multi-pod mesh, ``("data",)`` single-pod).
    ``model_axis`` — the TP/EP axis.
    ``fsdp_axes`` — axes parameters shard over (§Perf knob: extending FSDP
    over the pod axis halves per-pod parameter memory at the price of
    cross-pod all-gathers — the "egress" trade of the paper's placement rule).
    """

    mesh: Mesh
    batch_axes: Tuple[str, ...] = ("data",)
    model_axis: str = "model"
    fsdp_axes: Tuple[str, ...] = ("data",)
    # §Perf knobs (defaults = paper-faithful baseline; see EXPERIMENTS.md §Perf)
    seq_shard_activations: bool = False   # sequence-shard norm/ffn activations
    shard_kv_seq: bool = False            # flash-decoding style KV seq sharding
    gather_dtype: str = ""                # cast params before FSDP all-gather

    @property
    def model_size(self) -> int:
        return self.mesh.shape[self.model_axis]

    @property
    def batch_size(self) -> int:
        n = 1
        for a in self.batch_axes:
            n *= self.mesh.shape[a]
        return n

    def axis_size(self, name: str) -> int:
        return self.mesh.shape[name]

    @property
    def all_axes(self) -> Tuple[str, ...]:
        return tuple(self.mesh.axis_names)


_CTX: contextvars.ContextVar[Optional[MeshCtx]] = contextvars.ContextVar(
    "repro_mesh_ctx", default=None)


def current_ctx() -> Optional[MeshCtx]:
    return _CTX.get()


def set_mesh_ctx(ctx: Optional[MeshCtx]) -> None:
    _CTX.set(ctx)


@contextlib.contextmanager
def mesh_context(ctx: Optional[MeshCtx]):
    """Enter a mesh context (and the mesh itself, for pjit name resolution)."""
    token = _CTX.set(ctx)
    try:
        if ctx is not None:
            with ctx.mesh:
                yield ctx
        else:
            yield None
    finally:
        _CTX.reset(token)


def constrain_batch(x: jax.Array) -> jax.Array:
    """Block-boundary activation layout: batch-sharded on dim0; with
    ``seq_shard_activations`` (§Perf knob) also sequence-sharded on dim1 over
    the model axis — divides the per-device layer-scan carry (the dominant
    train-cell memory term) by |model|.

    Also the fix for GSPMD 'creative' repartitions: mixed-offset splits
    (mamba's w_in z|x|B|C|dt) would otherwise be sharded over the model axis
    at unaligned offsets, generating collective-permute storms inside the
    layer scan (observed: 9.5k permutes / 59 GiB on mamba2 train_4k).
    """
    ctx = current_ctx()
    if ctx is None:
        return x
    spec: list = [tuple(ctx.batch_axes)] + [None] * (x.ndim - 1)
    if ctx.seq_shard_activations and x.ndim >= 3:
        spec[1] = ctx.model_axis
    return constrain(x, *spec)


def constrain(x: jax.Array, *spec) -> jax.Array:
    """``with_sharding_constraint`` against the ambient mesh (no-op without).

    ``spec`` entries are mesh-axis names / tuples / None, with divisibility
    guarding: an axis that does not divide the dim is dropped rather than
    erroring, so one rule set serves every architecture in the pool.
    """
    ctx = current_ctx()
    if ctx is None:
        return x
    from repro.parallel.sharding import safe_spec
    p = safe_spec(x.shape, spec, ctx.mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(ctx.mesh, p))
