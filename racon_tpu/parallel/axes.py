"""Logical axis rules: named kernel-grid axes resolved to mesh axes.

Every device kernel in this repo works on arrays whose dimensions carry
one of four *logical* meanings, independent of which kernel or tier is
running:

* ``windows`` — the batch of independent POA problems (the consensus
  kernels' leading dim; the reference's per-GPU batch striping axis);
* ``query``   — the batch of independent alignment jobs/tasks (the
  aligner kernels' leading dim — same data-parallel role as ``windows``,
  named separately so the two phases can be steered independently);
* ``depth``   — the per-window layer dim (sequences stacked on a
  backbone);
* ``lane``    — the 128-lane base/column dims (backbone positions, DP
  columns, packed words).  Lane dims feed Mosaic tilings and masked
  reductions and must stay whole on every device.

A *rule set* maps each logical axis to a mesh axis name (or ``None`` =
replicated), the T5X ``logical_axis_rules`` pattern.
``resolve_spec`` turns a tuple of logical names — one per array dim —
into a ``jax.sharding.PartitionSpec`` against a concrete mesh, which is
how the partitioner (parallel/partitioner.py) derives pjit sharding
constraints and shard_map specs without any kernel knowing mesh axis
names.

Only the stdlib + jax.sharding types are imported here; no backend is
touched, so the module is importable before device configuration.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

from jax.sharding import PartitionSpec

#: Mesh axis names, in mesh-shape order.  ``data`` carries the
#: embarrassingly parallel batch axes (windows/query); ``model`` exists
#: for rule experiments that split a non-batch dim (depth) — size 1 on
#: the default mesh, so the default rules below are a no-op over it.
MESH_AXES: Tuple[str, ...] = ("data", "model")

#: The logical axis vocabulary.  Unknown names are a hard error in
#: resolve_spec — a typo'd axis must not silently replicate.
LOGICAL_AXES: Tuple[str, ...] = ("windows", "query", "depth", "lane")

#: One (logical axis, mesh axis | None) pair per logical axis.
Rules = Tuple[Tuple[str, Optional[str]], ...]

#: Default rules: both batch axes data-parallel, depth on the (size-1 by
#: default) model axis, lane dims always replicated/whole.
DEFAULT_RULES: Rules = (
    ("windows", "data"),
    ("query", "data"),
    ("depth", "model"),
    ("lane", None),
)

_RULES: Rules = DEFAULT_RULES


def get_rules() -> Rules:
    """The active rule set (module-level registry; DEFAULT_RULES unless
    overridden)."""
    return _RULES


def set_rules(rules: Rules) -> None:
    """Install a new active rule set (validated lazily against the mesh
    by the partitioner).  Used by tests and rule experiments."""
    global _RULES
    _RULES = tuple(rules)


def rules_key() -> Rules:
    """Hashable identity of the active rules — part of the partitioner's
    memoization key so a rule override never serves a stale mesh wrap."""
    return _RULES


def validate_rules(rules: Rules, mesh_axes: Sequence[str]) -> None:
    """Every rule must name a known logical axis and an existing mesh
    axis (or None); duplicate logical names are an error."""
    seen = set()
    for logical, mesh_axis in rules:
        if logical not in LOGICAL_AXES:
            raise ValueError(
                f"unknown logical axis {logical!r}; known: {LOGICAL_AXES}")
        if logical in seen:
            raise ValueError(f"duplicate rule for logical axis {logical!r}")
        seen.add(logical)
        if mesh_axis is not None and mesh_axis not in mesh_axes:
            raise ValueError(
                f"rule {logical!r} -> {mesh_axis!r}: mesh has no such "
                f"axis (axes: {tuple(mesh_axes)})")


def resolve_spec(logical_axes: Sequence[Optional[str]],
                 rules: Rules,
                 mesh_axes: Sequence[str]) -> PartitionSpec:
    """One PartitionSpec entry per array dim from its logical axis names.

    ``None`` entries (and logical axes whose rule maps to ``None``)
    resolve to a replicated dim.  Scalar/0-d arrays pass ``()`` and get
    the empty spec."""
    table = dict(rules)
    out = []
    for name in logical_axes:
        if name is None:
            out.append(None)
            continue
        if name not in LOGICAL_AXES:
            raise ValueError(
                f"unknown logical axis {name!r}; known: {LOGICAL_AXES}")
        mesh_axis = table.get(name)
        if mesh_axis is not None and mesh_axis not in mesh_axes:
            raise ValueError(
                f"rule {name!r} -> {mesh_axis!r} names a mesh axis "
                f"absent from this mesh (axes: {tuple(mesh_axes)})")
        out.append(mesh_axis)
    return PartitionSpec(*out)
