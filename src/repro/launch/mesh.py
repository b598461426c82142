"""Production mesh definitions.

Single pod: 16x16 = 256 chips (v5e pod), axes (data, model).
Multi-pod:  2x16x16 = 512 chips, axes (pod, data, model) — the ``pod`` axis
is the federated-collaborator axis: AE-compressed updates are the only
traffic that crosses it (DESIGN.md §3.1).

Defined as FUNCTIONS so importing this module never touches jax device
state; the dry-run sets XLA_FLAGS for 512 host devices before first init.
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType, Mesh


def auto_mesh(shape: Sequence[int], axes: Sequence[str]) -> Mesh:
    """``jax.make_mesh`` with every axis ``Auto``: the step functions place
    arrays with ``with_sharding_constraint`` and partial-manual
    ``shard_map``, which only accept Auto axes (``make_mesh`` defaults to
    Explicit)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)
