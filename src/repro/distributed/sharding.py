"""Logical-axis sharding rules (DP / TP / EP / SP / ZeRO) for the framework.

Model code annotates arrays with *logical* axis names; this module maps them
onto mesh axes per a rules table, filtered by what the active mesh actually
provides and by divisibility (a logical dim not divisible by its mesh-axis
extent falls back to replication — GSPMD could pad, but even sharding keeps
the collective schedule predictable at 1000+ nodes).

Baseline rules (see DESIGN.md §6):
  batch   -> ("pod", "data")     data parallelism (pod axis = outer DP)
  heads / kv_heads / ffn / vocab / experts / ssm_heads -> "model"   (TP / EP)
  seq_ctx -> "data"              context parallelism for long-context decode
  everything else  -> replicated

ZeRO-1: optimizer states / master params additionally shard their largest
replicated dim over ("pod", "data") via ``add_zero_axes``.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

Axes = tuple  # tuple[str | None | tuple[str, ...], ...]


DEFAULT_RULES: dict[str, Any] = {
    "batch": ("pod", "data"),
    "heads": "model",
    "kv_heads": "model",
    "ffn": "model",
    "vocab": "model",
    "experts": "model",
    "ssm_heads": "model",
    "chains": ("pod", "data"),  # sampler-engine chain axis (DP-like)
    "seq_ctx": "data",      # context parallelism (long-context decode)
    "seq_sp": "model",      # sequence parallelism on the residual stream
    # replicated logical axes
    "seq": None,
    "cache_seq": None,   # decode KV cache seq (arch override -> "model"/"data")
    "embed": None,
    "embed_tp": "model",  # input-embedding d-sharding (gather stays local)
    "vocab_rep": None,    # input-embedding vocab axis (replicated)
    "head_dim": None,
    "ssm_state": None,
    "conv": None,
    "layers": None,
    "expert_cap": None,
    "frames": None,
    "patches": None,
}


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    rules: tuple = tuple(sorted(DEFAULT_RULES.items()))

    def as_dict(self) -> dict:
        return dict(self.rules)

    def replace(self, **updates) -> "ShardingRules":
        d = self.as_dict()
        d.update(updates)
        return ShardingRules(rules=tuple(sorted(d.items())))


# --- active-rules context ----------------------------------------------------
# Model code calls shard(x, logical_axes) without threading rules; launchers
# install per-arch rule patches (cfg.sharding_overrides) around tracing.

_ACTIVE_RULES: list = [ShardingRules()]


def get_rules() -> ShardingRules:
    return _ACTIVE_RULES[-1]


class use_rules:
    """Context manager installing sharding rules for the enclosed trace."""

    def __init__(self, rules: ShardingRules):
        self.rules = rules

    def __enter__(self):
        _ACTIVE_RULES.append(self.rules)
        return self.rules

    def __exit__(self, *exc):
        _ACTIVE_RULES.pop()
        return False


def rules_for_config(cfg) -> ShardingRules:
    """Base rules + per-arch overrides (cfg.sharding_overrides tuple)."""
    overrides = dict(getattr(cfg, "sharding_overrides", ()) or ())
    return ShardingRules().replace(**overrides) if overrides else ShardingRules()


def active_mesh():
    """The abstract mesh from ``jax.set_mesh``; None when not set."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty:
        return None
    return mesh


def _mesh_axis_size(mesh, axis) -> int:
    return dict(zip(mesh.axis_names, mesh.axis_sizes))[axis]


def _manual_axes(mesh) -> set:
    """Mesh axes currently in Manual mode (inside a shard_map region)."""
    types = getattr(mesh, "axis_types", None)
    if types is None:
        return set()
    manual = jax.sharding.AxisType.Manual
    return {n for n, t in zip(mesh.axis_names, types) if t == manual}


def _filter_entry(entry, mesh, dim_size: int | None, used: set = frozenset()):
    """Resolve one logical axis to mesh axes present, unused & divisible.

    Axes that are Manual in the current context (inside a shard_map over
    them) are skipped — constraints may only name Auto axes there.
    """
    if entry is None:
        return None
    names = entry if isinstance(entry, tuple) else (entry,)
    manual = _manual_axes(mesh)
    kept = []
    extent = 1
    for name in names:
        if name not in mesh.axis_names or name in used or name in manual:
            continue
        size = _mesh_axis_size(mesh, name)
        if dim_size is not None and dim_size % (extent * size) != 0:
            continue
        kept.append(name)
        extent *= size
    if not kept:
        return None
    return tuple(kept) if len(kept) > 1 else kept[0]


def spec_for(
    logical_axes: Axes,
    rules: ShardingRules = ShardingRules(),
    shape: tuple | None = None,
    mesh=None,
) -> P | None:
    """Map logical axes -> PartitionSpec under the active mesh (None = no mesh)."""
    mesh = mesh or active_mesh()
    if mesh is None:
        return None
    table = rules.as_dict()
    entries = []
    used: set = set()
    for i, ax in enumerate(logical_axes):
        entry = table.get(ax) if ax is not None else None
        dim = None if shape is None else shape[i]
        # a mesh axis may appear at most once in a spec: skip used names
        resolved = _filter_entry(entry, mesh, dim, used)
        if resolved is not None:
            names = resolved if isinstance(resolved, tuple) else (resolved,)
            used.update(names)
        entries.append(resolved)
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


def shard_over_chains(body, mesh, n: int):
    """``body`` as a ``shard_map`` over the mesh axes the "chains" rule
    gives an axis of ``n`` independent chains (or serving slots), every
    argument and output split along its leading axis — or None where the
    rule gives none: no mesh, or ``n`` not divisible by those axes (the
    chains then run replicated rather than padded).  Chains never
    communicate, so the sharded program is collective-free and
    bit-identical to the unsharded one.  The caller jits it: run
    eagerly, shard_map rejects an output the body builds from constants
    alone (the empty ``samples`` of ``collect="last"``)."""
    if mesh is None:
        return None
    spec = spec_for(("chains",), shape=(n,), mesh=mesh)
    if spec is None or len(spec) == 0 or spec[0] is None:
        return None
    p = P(spec[0])
    return jax.shard_map(
        body, mesh=mesh, in_specs=p, out_specs=p, check_vma=False
    )


def shard(x, logical_axes: Axes, rules: ShardingRules | None = None):
    """with_sharding_constraint by logical axes; no-op without a mesh."""
    spec = spec_for(logical_axes, rules or get_rules(), shape=jnp.shape(x))
    if spec is None:
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def add_zero_axes(
    logical_axes: Axes,
    shape: tuple,
    rules: ShardingRules = ShardingRules(),
    mesh=None,
    zero_axes: tuple = ("pod", "data"),
) -> Axes:
    """ZeRO-1: extend a param's axes so optimizer state also shards over DP.

    Picks the first replicated dim divisible by the full DP extent and maps
    it to a synthetic logical axis bound to ``zero_axes``.
    """
    mesh = mesh or active_mesh()
    if mesh is None:
        return logical_axes
    table = rules.as_dict()
    dp = 1
    for name in zero_axes:
        if name in mesh.axis_names:
            dp *= _mesh_axis_size(mesh, name)
    if dp <= 1:
        return logical_axes
    out = list(logical_axes)
    for i, ax in enumerate(out):
        entry = table.get(ax) if ax is not None else None
        if entry is None and shape[i] % dp == 0:
            out[i] = "_zero"
            return tuple(out)
    return logical_axes


ZERO_RULES_PATCH = {"_zero": ("pod", "data")}


def rules_with_zero(rules: ShardingRules = ShardingRules()) -> ShardingRules:
    return rules.replace(**ZERO_RULES_PATCH)


def named_sharding(mesh, spec: P) -> NamedSharding:
    return NamedSharding(mesh, spec)


def tree_specs(axes_tree, rules: ShardingRules, shapes_tree=None, mesh=None):
    """Map a pytree of LogicalAxes leaves to PartitionSpecs."""
    from repro.models.layers import LogicalAxes

    def _names(a):
        return a.names if isinstance(a, LogicalAxes) else tuple(a)

    if shapes_tree is None:
        return jax.tree.map(lambda a: spec_for(_names(a), rules, mesh=mesh), axes_tree)
    return jax.tree.map(
        lambda a, s: spec_for(_names(a), rules, shape=s.shape, mesh=mesh),
        axes_tree,
        shapes_tree,
    )
