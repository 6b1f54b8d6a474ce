"""Production mesh construction.

A FUNCTION (not module-level constant) so importing this module never
touches jax device state — required because the dry-run must set XLA_FLAGS
before the first jax device query.
"""
from __future__ import annotations

import jax

from repro.core.compat import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 (data, model) per pod; (2, 16, 16) (pod, data, model) across.

    The "pod" axis only carries data parallelism: cross-pod traffic is one
    gradient all-reduce per step (DESIGN.md §5).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(model: int = 1):
    """Debug mesh over however many devices exist (tests, CPU examples)."""
    n = len(jax.devices())
    assert n % model == 0, (n, model)
    return make_mesh((n // model, model), ("data", "model"))
