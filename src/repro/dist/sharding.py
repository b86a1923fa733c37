"""Name-based sharding rules: logical axis names -> mesh PartitionSpecs.

Parameters and inputs carry *logical* axis names (``models/nn.py`` Param
trees: "embed", "mlp", "batch", ...). A rule set is an ordered tuple of
``Rule(logical, mesh_axes)`` entries mapping a logical name to candidate
mesh axes; ``spec_for`` resolves one array's names into a PartitionSpec
with three semantics (exercised by ``tests/test_dist.py``):

  * **priority** — rules are applied in order, so e.g. "batch" claims the
    data axes before "kv_seq" can, and "kv_heads" beats "kv_seq" to the
    model axis;
  * **divisibility fallback** — a dimension only takes a mesh axis if its
    size is divisible by the axis (an 8-way KV-head dim on a 16-way model
    axis stays replicated and the axis remains available for later rules);
  * **no axis reuse** — each mesh axis is consumed at most once per array;
    a rule with several candidates takes every still-free, still-dividing
    axis jointly (e.g. "kv_seq" over ("data", "model") when batch=1 frees
    the data axis).

``DEFAULT_RULES`` is the dense/TP layout; ``EP_RULES`` flips MoE expert
weights to expert-parallel (experts sharded over the model axis, full
d_ff per expert).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models.nn import Param


@dataclasses.dataclass(frozen=True)
class Rule:
    """One logical axis -> candidate mesh axes (tried in order)."""

    logical: str
    mesh_axes: Tuple[str, ...]


DEFAULT_RULES: Tuple[Rule, ...] = (
    Rule("batch", ("pod", "data")),
    Rule("heads", ("model",)),
    Rule("kv_heads", ("model",)),
    Rule("vocab", ("model",)),
    Rule("mlp", ("model",)),
    Rule("expert", ()),                 # replicated: TP slices d_ff instead
    Rule("kv_seq", ("data", "model")),
    # "embed", "qkv", "layers", None carry no rule -> replicated.
)

EP_RULES: Tuple[Rule, ...] = (
    Rule("batch", ("pod", "data")),
    Rule("heads", ("model",)),
    Rule("kv_heads", ("model",)),
    Rule("vocab", ("model",)),
    Rule("expert", ("model",)),         # expert-parallel: experts sharded,
    Rule("mlp", ()),                    # full d_ff kept per expert
    Rule("kv_seq", ("data", "model")),
)


def spec_for(shape: Sequence[int], names: Sequence[Optional[str]], mesh,
             rules: Tuple[Rule, ...] = DEFAULT_RULES) -> P:
    """Resolve one array's logical names into a PartitionSpec on ``mesh``."""
    assert len(shape) == len(names), (tuple(shape), tuple(names))
    sizes = mesh.shape
    rule_for = {r.logical: (i, r) for i, r in enumerate(rules)}
    entries: list = [None] * len(shape)
    used: set = set()
    order = sorted((d for d in range(len(shape)) if names[d] in rule_for),
                   key=lambda d: (rule_for[names[d]][0], d))
    for d in order:
        _, rule = rule_for[names[d]]
        chosen = []
        prod = 1
        for ax in rule.mesh_axes:
            if ax not in sizes or ax in used:
                continue
            if shape[d] % (prod * sizes[ax]) == 0:
                chosen.append(ax)
                prod *= sizes[ax]
        if chosen:
            used.update(chosen)
            entries[d] = chosen[0] if len(chosen) == 1 else tuple(chosen)
    return P(*entries)


def _is_param(x) -> bool:
    return isinstance(x, Param)


def tree_specs(tree, mesh, rules: Optional[Tuple[Rule, ...]] = None):
    """Param tree -> matching tree of PartitionSpecs (leaves at Params)."""
    rules = DEFAULT_RULES if rules is None else rules
    return jax.tree.map(
        lambda p: spec_for(p.value.shape, p.axes, mesh, rules), tree,
        is_leaf=_is_param)


def tree_shardings(tree, mesh, rules: Optional[Tuple[Rule, ...]] = None):
    """Param tree -> matching tree of NamedShardings on ``mesh``."""
    rules = DEFAULT_RULES if rules is None else rules
    return jax.tree.map(
        lambda p: NamedSharding(
            mesh, spec_for(p.value.shape, p.axes, mesh, rules)),
        tree, is_leaf=_is_param)
