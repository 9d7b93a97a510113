"""Sharding rules: how arrays map onto mesh axes.

Reference counterpart: device placement was *manual* (`group2ctx` symbol attrs
→ `AssignContext`, `src/executor/graph_executor.cc:909-915`) and gradient
aggregation was a separate KVStore code path.  TPU-native design: placement is
declarative — a `PartitionSpec` per array, chosen by regex rules over the
parameter name — and XLA/GSPMD inserts every collective.

`ShardingRules` is the single knob a model author touches:

    rules = ShardingRules([
        (r".*dense.*weight", P("fsdp", "tp")),
        (r".*embed.*",       P("tp", "fsdp")),
        (r".*",              P()),            # replicate the rest
    ])
"""
from __future__ import annotations

import re

import jax
from jax.sharding import NamedSharding, PartitionSpec

from .mesh import get_mesh

__all__ = ["ShardingRules", "param_sharding", "shard_array", "auto_shard",
           "constraint", "PartitionSpec", "match_partition_rules",
           "make_shard_and_gather_fns"]

P = PartitionSpec


class ShardingRules:
    """Ordered (regex, PartitionSpec) rules; first match wins."""

    def __init__(self, rules):
        self.rules = [(re.compile(pat), spec) for pat, spec in rules]

    def spec_for(self, name) -> PartitionSpec:
        for pat, spec in self.rules:
            if pat.fullmatch(name):
                return spec
        return PartitionSpec()


def _filter_spec(spec, mesh, shape=None):
    """Drop axes absent from the mesh (so one rule set serves many meshes)
    and, when ``shape`` is known, axes that do not evenly divide the dim
    (replicate instead of failing — e.g. a vocab of 97 with tp=2)."""
    sizes = dict(mesh.mesh.shape)

    def keep(i, entry):
        if entry is None:
            return None
        entries = entry if isinstance(entry, (tuple, list)) else (entry,)
        kept = []
        for e in entries:
            if e not in sizes:
                continue
            if shape is not None:
                factor = sizes[e]
                for prev in kept:
                    factor *= sizes[prev]
                if shape[i] % factor:
                    continue
            kept.append(e)
        if not kept:
            return None
        return tuple(kept) if len(kept) > 1 else kept[0]

    return PartitionSpec(*(keep(i, e) for i, e in enumerate(spec)))


def param_sharding(spec, mesh=None, shape=None) -> NamedSharding:
    mesh = mesh or get_mesh()
    return NamedSharding(mesh.mesh, _filter_spec(spec, mesh, shape))


def shard_array(x, spec, mesh=None):
    """Place ``x`` with the given PartitionSpec (host→device reshard)."""
    return jax.device_put(x, param_sharding(spec, mesh, shape=x.shape))


def auto_shard(named_arrays, rules: ShardingRules, mesh=None):
    """Shard a {name: array} dict by rules; returns new dict."""
    mesh = mesh or get_mesh()
    return {k: shard_array(v, rules.spec_for(k), mesh)
            for k, v in named_arrays.items()}


def match_partition_rules(rules, named_arrays):
    """Resolve a PartitionSpec per named array (fmengine-style regex
    matching): ``rules`` is a :class:`ShardingRules` or a plain list of
    ``(regex, PartitionSpec)`` pairs; scalars and size-1 arrays always
    replicate (a spec axis on a 0-d/1-element array is meaningless), and
    names no rule matches replicate too (the same default
    :meth:`ShardingRules.spec_for` uses).  Returns ``{name: spec}``."""
    if not isinstance(rules, ShardingRules):
        rules = ShardingRules(list(rules or []))
    specs = {}
    for name, arr in named_arrays.items():
        shape = tuple(getattr(arr, "shape", ()))
        if len(shape) == 0 or all(d <= 1 for d in shape):
            specs[name] = PartitionSpec()
        else:
            specs[name] = rules.spec_for(name)
    return specs


def make_shard_and_gather_fns(partition_specs, mesh=None):
    """Per-name shard/gather callables over a spec dict (the
    ``make_shard_and_gather_fns`` pattern of SNIPPETS.md [2], adapted to
    the dict-of-arrays currency this framework uses).

    ``shard_fns[name](x)`` places a host/committed array onto the mesh
    with the spec's NamedSharding (axes the mesh lacks or that do not
    divide the dim are dropped by :func:`param_sharding` — replicate,
    never fail).  ``gather_fns[name](x)`` fetches the fully-assembled
    host copy back (checkpointing / parity checks).  Returns
    ``(shard_fns, gather_fns)``."""
    import numpy as np

    mesh = mesh or get_mesh()
    shard_fns, gather_fns = {}, {}
    for name, spec in partition_specs.items():
        def _shard(x, _spec=spec):
            return jax.device_put(
                x, param_sharding(_spec, mesh, shape=tuple(np.shape(x))))

        def _gather(x):
            return np.asarray(jax.device_get(x))

        shard_fns[name] = _shard
        gather_fns[name] = _gather
    return shard_fns, gather_fns


def constraint(x, *spec_entries, mesh=None):
    """In-jit sharding constraint (activation sharding).  Safe no-op outside
    a mesh or for axes the mesh lacks."""
    from .mesh import current_mesh

    mesh = mesh or current_mesh()
    if mesh is None:
        return x
    # inside a shard_map body the mesh axes being mapped are "manual":
    # GSPMD constraints over them are both illegal and meaningless (the
    # body already sees its per-device shard), so drop those entries —
    # this is what lets mesh-aware model code (e.g. transformer blocks
    # with dp/sp/tp activation constraints) run unchanged as a pipeline
    # stage under shard_map
    manual = set(jax.sharding.get_abstract_mesh().manual_axes)
    if manual:
        def strip(e):
            if isinstance(e, (tuple, list)):
                kept = tuple(a for a in e if a not in manual)
                return kept if kept else None
            return None if e in manual else e
        spec_entries = tuple(strip(e) for e in spec_entries)
    spec = _filter_spec(PartitionSpec(*spec_entries), mesh, shape=x.shape)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh.mesh, spec))
