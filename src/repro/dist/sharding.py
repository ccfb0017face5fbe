"""Logical-axis distribution layer: rules, ambient context, constraints.

Contract (the one every model/launch module codes against)
----------------------------------------------------------
Model code never names physical mesh axes.  It names *logical* axes —
``"batch"``, ``"fsdp"``, ``"tp"``, ``"layers"``, ``"act_seq"``,
``"cache_seq"`` — and an :class:`AxisRules` maps each logical name to a
physical mesh axis (a ``str``), a tuple of mesh axes (sharded over their
product, e.g. multi-pod batch over ``("pod", "data")``), or ``None``
(replicated).  Logical names absent from the mapping resolve to ``None``,
so model code may annotate axes that only some topologies shard (e.g.
``"cache_seq"``) without every rule set having to enumerate them.

The pieces:

- :data:`SINGLE_POD_RULES` / :data:`MULTI_POD_RULES` — the production
  mappings (see ``launch/mesh.py`` for the physical topologies).
- :func:`axes_to_spec` — logical-axes tuple -> ``PartitionSpec``.
- :func:`is_axes` — pytree leaf predicate for logical-axes tuples, so an
  axes pytree mirrors its param pytree (NamedTuples stay containers).
- :func:`use_rules` — context manager installing *ambient* rules; nestable,
  the innermost wins, exceptions restore the outer rules.
- :func:`shard` — ``with_sharding_constraint`` under the ambient rules.
  **Single-device degrade:** with no ambient rules, mesh-less rules, a
  one-device mesh, or a fully-replicated resulting spec, it returns its
  input untouched — which is why unit tests and CPU smoke runs execute the
  exact same model code with zero mesh setup.
- :func:`param_shardings` — axes pytree -> ``NamedSharding`` pytree for
  ``jit`` in/out shardings, checkpoint restore, and elastic resharding.

``make_compat_mesh`` builds every mesh in this repo with
``axis_types=AxisType.Auto`` on each axis, the GSPMD auto-sharding this
layer relies on.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Mapping, Sequence

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec

# A logical axis maps to: one mesh axis, several (sharded over their
# product), or None (replicated).
MeshAxes = Any  # str | tuple[str, ...] | None


@dataclasses.dataclass(frozen=True)
class AxisRules:
    """A logical->physical axis mapping, optionally bound to a mesh.

    ``mesh=None`` rule sets are pure mappings (the module-level constants):
    usable with :func:`axes_to_spec` but not placeable.  Binding happens in
    ``launch/mesh.py::rules_for`` which re-wraps the mapping with the live
    mesh.  Instances are frozen; derive variants with :func:`with_overrides`.
    """

    rules: Mapping[str, MeshAxes]
    mesh: Mesh | None = None


SINGLE_POD_RULES = AxisRules(rules={
    "batch": "data",      # data parallelism
    "fsdp": "data",       # ZeRO-3 style param/optimizer sharding, same axis
    "tp": "model",        # tensor parallelism (heads / ff / vocab)
    "layers": None,       # scanned layer stacks stay replicated over L
    "act_seq": None,      # sequence stays local unless sequence_parallel
})

# Multi-pod: the batch additionally shards over the DCN-crossing "pod" axis
# (gradient all-reduce is the only cross-pod collective); everything else is
# identical to single-pod.
MULTI_POD_RULES = AxisRules(rules={
    **SINGLE_POD_RULES.rules,
    "batch": ("pod", "data"),
})


def is_axes(obj) -> bool:
    """Leaf predicate for logical-axes pytrees.

    True exactly for *plain* tuples whose members are all ``str`` or ``None``
    — including the empty tuple ``()`` (a scalar's axes).  NamedTuples are
    pytree containers holding axes tuples, so they must NOT be leaves; the
    ``type(obj) is tuple`` check (not ``isinstance``) excludes them, and any
    non-str member (dicts, ints, nested tuples) disqualifies the tuple.
    """
    return type(obj) is tuple and all(
        a is None or isinstance(a, str) for a in obj)


def axes_to_spec(axes: Sequence[str | None], rules: AxisRules) -> PartitionSpec:
    """Map a logical-axes tuple through ``rules`` to a ``PartitionSpec``.

    ``None`` entries and logical names absent from the mapping both resolve
    to ``None`` (replicated) — see the module docstring for why absence is
    deliberately legal.
    """
    return PartitionSpec(
        *(None if a is None else rules.rules.get(a) for a in axes))


def with_overrides(rules: AxisRules, **overrides: MeshAxes) -> AxisRules:
    """A new AxisRules with some logical axes remapped; the input is not
    mutated (rule sets are shared module-level constants)."""
    return AxisRules(rules={**rules.rules, **overrides}, mesh=rules.mesh)


# --------------------------------------------------------------------------
# ambient rules
# --------------------------------------------------------------------------

# A stack, not a slot: lowering one cell may nest rule scopes (e.g. decode
# artifacts overriding weight sharding inside the cell-wide scope).  Tracing
# happens on the caller's thread, so a module-level stack suffices.
_AMBIENT: list[AxisRules] = []


def current_rules() -> AxisRules | None:
    """The innermost ambient rules, or None outside any ``use_rules`` scope."""
    return _AMBIENT[-1] if _AMBIENT else None


class use_rules:
    """Context manager installing ``rules`` as the ambient rule set.

    Re-entrant and nestable: each ``__enter__`` pushes, each ``__exit__``
    pops exactly one frame (also on exceptions), so nested scopes restore
    the outer rules.  The instance may be constructed eagerly and entered
    later (``launch/train.py`` builds the context before the run loop).
    """

    def __init__(self, rules: AxisRules):
        self._rules = rules

    def __enter__(self) -> AxisRules:
        _AMBIENT.append(self._rules)
        return self._rules

    def __exit__(self, exc_type, exc, tb) -> bool:
        _AMBIENT.pop()
        return False


def shard(x, *logical_axes: str | None):
    """Constrain ``x`` to the sharding its logical axes imply ambiently.

    Identity when there is nothing to constrain against: no ambient rules,
    rules without a mesh, a single-device mesh, or a spec that came out
    fully replicated.  Skipping the fully-replicated constraint (rather than
    emitting a trivial one) keeps auto-sharding free to propagate through
    annotated-but-unsharded intermediates.
    """
    rules = current_rules()
    if rules is None or rules.mesh is None or rules.mesh.size <= 1:
        return x
    spec = axes_to_spec(logical_axes, rules)
    if all(entry is None for entry in spec):
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(rules.mesh, spec))


def param_shardings(axes_tree, rules: AxisRules):
    """Map an axes pytree to a ``NamedSharding`` pytree (leaf-for-leaf).

    Leaves are located with :func:`is_axes`, so the axes pytree must mirror
    the param pytree container-for-container with plain axes tuples at the
    leaves (this is what every ``*_param_axes`` / ``*_cache_axes`` returns).
    """
    if rules.mesh is None:
        raise ValueError(
            "param_shardings needs mesh-bound rules; wrap the mapping via "
            "launch.mesh.rules_for(mesh, ...) first")

    def one(axes):
        if not is_axes(axes):
            raise TypeError(
                f"axes tree leaf {axes!r} is not a logical-axes tuple")
        return NamedSharding(rules.mesh, axes_to_spec(axes, rules))

    return jax.tree.map(one, axes_tree, is_leaf=is_axes)


# --------------------------------------------------------------------------
# mesh construction
# --------------------------------------------------------------------------

def make_compat_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str],
                     *, devices=None) -> Mesh:
    """``jax.make_mesh`` with every axis ``AxisType.Auto`` — the GSPMD
    auto-sharding this layer relies on.  Every mesh in the repo
    (production, dry-run, tests) comes from here."""
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_names),
                         devices=devices)
