"""Logical-axis sharding rules: model code names axes, rules map them to mesh axes.

Models annotate every parameter/activation dimension with a *logical* name
("embed", "heads", "batch", ...). A `ShardingRules` table maps logical
names to mesh axes (or None = replicated). This decouples model code from
the parallelism layout — change the rules, not the model, to go from pure
DP to FSDP+TP+SP. (The reference delegates this entirely to torch FSDP /
vLLM internals; here it is a first-class framework concept, in the style
of GSPMD logical axis annotations.)
"""

from __future__ import annotations

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

P = PartitionSpec

# Default layout: batch split over (dp, fsdp); params sharded ZeRO-3-style
# over fsdp on their "embed"-ish dim and Megatron-style over tp on their
# "heads"/"mlp" dim; sequence split over sp for context parallelism;
# experts over ep.
DEFAULT_RULES: dict[str, object] = {
    "batch": ("dp", "fsdp"),
    "seq": "sp",
    "embed": "fsdp",
    "mlp": "tp",
    "heads": "tp",
    "kv_heads": "tp",
    "head_dim": None,
    "vocab": "tp",
    "layers": None,
    "stage": "pp",
    "expert": "ep",
    "norm": None,
}


class ShardingRules(dict):
    """Mapping logical axis name -> mesh axis (str), tuple of mesh axes, or None."""

    def spec(self, logical_axes: tuple[str | None, ...]) -> PartitionSpec:
        parts = []
        for ax in logical_axes:
            if ax is None:
                parts.append(None)
            else:
                if ax not in self:
                    raise KeyError(f"no sharding rule for logical axis {ax!r}")
                parts.append(self[ax])
        return P(*parts)

    def sharding(self, mesh: Mesh, logical_axes: tuple[str | None, ...]) -> NamedSharding:
        return NamedSharding(mesh, self.spec(logical_axes))


def default_rules(**overrides) -> ShardingRules:
    rules = ShardingRules(DEFAULT_RULES)
    rules.update(overrides)
    return rules


def tree_specs(rules: ShardingRules, logical_tree) -> object:
    """Map a pytree of logical-axis tuples to a pytree of PartitionSpecs."""
    return jax.tree.map(
        lambda axes: rules.spec(axes),
        logical_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(a, (str, type(None))) for a in x),
    )


def tree_shardings(mesh: Mesh, rules: ShardingRules, logical_tree) -> object:
    """Shardings to place a pytree by. Trailing replicated dims are left
    out of each spec: the same layout, in the form in which a jitted
    step hands its outputs back, so that a state placed by these meets
    the step's cache on the second call too (`P(None)` and `P()` are
    different cache keys: a second trace and compile of the program)."""
    def sharding(spec):
        parts = list(spec)
        while parts and parts[-1] is None:
            parts.pop()
        return NamedSharding(mesh, P(*parts))

    return jax.tree.map(
        sharding, tree_specs(rules, logical_tree),
        is_leaf=lambda x: isinstance(x, PartitionSpec),
    )


def constrain(x, mesh: Mesh, rules: ShardingRules, logical_axes: tuple[str | None, ...]):
    """with_sharding_constraint by logical axes (no-op outside jit)."""
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, rules.spec(logical_axes)))

