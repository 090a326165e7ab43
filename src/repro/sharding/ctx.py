"""Logical-axis sharding context.

Model code annotates activations with *logical* axis names
(``"batch"``, ``"seq"``, ``"heads"``, ``"ff"``, ``"experts"``, ``"vocab"`` …).
A :class:`ShardingRules` context maps logical names to mesh axes and applies
``jax.lax.with_sharding_constraint``; with no context active (CPU unit tests)
annotations are no-ops, keeping the model code mesh-agnostic.
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
from typing import Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "ShardingRules",
    "use_sharding_rules",
    "shard_activation",
    "current_rules",
    "DEFAULT_RULES",
]


# logical axis -> mesh axis (or tuple of mesh axes, or None)
DEFAULT_RULES: dict[str, object] = {
    "batch": ("pod", "data"),
    "seq": None,
    # Megatron-style sequence parallelism: the residual stream between blocks
    # shards its seq axis over `model` (XLA inserts all-gather before qkv /
    # reduce-scatter after wo).  Only applied when cfg.sequence_parallel.
    "seq_sp": "model",
    "heads": "model",
    "kv_heads": "model",
    "ff": "model",
    "experts": "model",
    "vocab": "model",
    "d_model": None,
    "embed_shard": "data",  # the FSDP-ish storage axis for weights
    "state": "model",
}


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    mesh: Mesh
    rules: dict[str, object]

    def spec(self, logical: Sequence[object]) -> P:
        axes = []
        for name in logical:
            if name is None:
                axes.append(None)
                continue
            mapped = self.rules.get(str(name))
            if mapped is None:
                axes.append(None)
            elif isinstance(mapped, tuple):
                present = tuple(a for a in mapped if a in self.mesh.axis_names)
                axes.append(present if present else None)
            else:
                axes.append(mapped if mapped in self.mesh.axis_names else None)
        return P(*axes)

    def sharding(self, logical: Sequence[object]) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec(logical))


_CTX: contextvars.ContextVar[ShardingRules | None] = contextvars.ContextVar(
    "sharding_rules", default=None
)


def current_rules() -> ShardingRules | None:
    return _CTX.get()


@contextlib.contextmanager
def use_sharding_rules(mesh: Mesh, rules: dict[str, object] | None = None):
    token = _CTX.set(ShardingRules(mesh, dict(DEFAULT_RULES if rules is None else rules)))
    try:
        yield
    finally:
        _CTX.reset(token)


def shard_activation(x: jax.Array, logical: Sequence[object]) -> jax.Array:
    """Constrain ``x`` to the logical spec if a sharding context is active."""
    ctx = _CTX.get()
    if ctx is None:
        return x
    if len(logical) != x.ndim:
        # tolerate rank-mismatch from broadcasting helpers: skip rather than crash
        return x
    return jax.lax.with_sharding_constraint(x, ctx.sharding(logical))
