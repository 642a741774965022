"""Activation sharding constraints (the §Perf H1 fix).

Without these, GSPMD propagation through reshape/scan picks degenerate
layouts — e.g. sharding the *contracted* head_dim of MQA attention, turning
every score block into an all-reduce (EXPERIMENTS.md §Perf records the
before/after).  ``constrain(x, ...)`` applies a PartitionSpec only when a
mesh is set (``jax.set_mesh``) and the dims divide; the pseudo-axis ``"dp"`` expands to
``("pod", "data")`` on multi-pod meshes.  On meshless CPU smoke runs every
constraint is a no-op.
"""
from __future__ import annotations

from typing import Tuple, Union

import jax
from jax.sharding import PartitionSpec as P

Axis = Union[None, str, Tuple[str, ...]]


def _current_mesh():
    """The mesh set by ``jax.set_mesh``, or None outside one."""
    m = jax.sharding.get_abstract_mesh()
    return m if m.axis_names else None


def constrain(x: jax.Array, *axes: Axis) -> jax.Array:
    """with_sharding_constraint that degrades gracefully.

    Each entry is None / axis name / tuple of names; axes missing from the
    ambient mesh, or not dividing the dim size, drop to None.
    """
    mesh = _current_mesh()
    if mesh is None:
        return x
    names = set(mesh.axis_names)

    def axis_size(a) -> int:
        if isinstance(a, tuple):
            n = 1
            for b in a:
                n *= mesh.shape[b]
            return n
        return mesh.shape[a]

    spec = []
    for dim, a in enumerate(axes):
        if a == "dp":
            a = ("pod", "data") if "pod" in names else ("data",)
        if a is None:
            spec.append(None)
            continue
        tup = a if isinstance(a, tuple) else (a,)
        if not all(b in names for b in tup):
            spec.append(None)
            continue
        if x.shape[dim] % axis_size(tup) != 0:
            spec.append(None)
            continue
        spec.append(a if isinstance(a, tuple) else a)
    return jax.lax.with_sharding_constraint(x, P(*spec))
