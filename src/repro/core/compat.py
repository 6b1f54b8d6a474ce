"""The JAX mesh and manual-sharding API the repo is written against (0.9).

Policy: every mesh the program builds, and every manual shard_map body,
goes through ONE symbol defined here, so a future API move is a change
to this file alone.

``make_mesh(shape, names)`` / ``make_abstract_mesh(shape, names)``
    JAX 0.9's ``jax.make_mesh`` defaults to ``AxisType.Explicit``, under
    which a jitted ``pallas_call`` or a sharded ``dynamic_update_slice``
    is refused unless every operand's sharding is spelled out.  The
    planner's GSPMD paths rely on the compiler propagating shardings, so
    every mesh declares its axes ``Auto``.

``shard_map_unchecked(f, **kw)``
    ``jax.shard_map`` with the varying-manual-axes checker off, for
    bodies that thread rank-local state through a nominally replicated
    out_spec (error-feedback residuals) or combine shards by hand (the
    lane-sharded Pallas launch).
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax import lax
from jax.sharding import AxisType

shard_map = jax.shard_map
axis_size = lax.axis_size
pcast = lax.pcast


def shard_map_unchecked(f, **kw):
    """``jax.shard_map`` with the VMA checker off."""
    return jax.shard_map(f, check_vma=False, **kw)


def _auto(names: Sequence[str]) -> tuple:
    return (AxisType.Auto,) * len(names)


def make_mesh(shape: Sequence[int], names: Sequence[str], *, devices=None):
    """``jax.make_mesh`` with every axis ``Auto`` (see module doc)."""
    if len(shape) != len(names):
        raise ValueError(f"shape/names length mismatch: {shape} vs {names}")
    return jax.make_mesh(tuple(int(s) for s in shape), tuple(names),
                         axis_types=_auto(names), devices=devices)


def make_abstract_mesh(shape: Sequence[int], names: Sequence[str]):
    """``jax.sharding.AbstractMesh`` with every axis ``Auto``, e.g.
    ``make_abstract_mesh((16, 16), ("data", "model"))``."""
    if len(shape) != len(names):
        raise ValueError(f"shape/names length mismatch: {shape} vs {names}")
    return jax.sharding.AbstractMesh(tuple(int(s) for s in shape),
                                     tuple(names), axis_types=_auto(names))
