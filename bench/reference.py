"""The plain reference: what a served Spatter request must return.

Written from the semantics alone, in numpy, importing nothing of the
program.  The data is drawn as the request's ``seed`` defines it: one
``numpy.random.default_rng(seed)`` stream per pattern, the table first
(``footprint x row_width`` standard normals, float32), then, for a
scatter, one value row per lane.

- gather: output lane ``k`` is the table row its absolute index names;
- store scatter: a zero table in which each written row holds the value
  of the LAST lane that writes it, and unwritten rows stay zero.

Outputs are compared by sha256 of their bytes, so equality is bit for
bit (-0.0 and +0.0 differ).  ``precision="bfloat16"`` is the control:
the same reference with its data rounded to bfloat16 first, the step
below the configuration's float32.  It must never pass for the program.
"""
from __future__ import annotations

import hashlib

import numpy as np

from bench import work


def absolute_indices(p: dict) -> np.ndarray:
    """Flat (count * index_len,) int64 element indices, op-major."""
    index = np.asarray(work.parse_index(p["pattern"]), np.int64)
    base = np.arange(p["count"], dtype=np.int64) * p["delta"]
    return (base[:, None] + index[None, :]).reshape(-1)


def to_bfloat16(x: np.ndarray) -> np.ndarray:
    """Round float32 to the nearest bfloat16 (ties to even), as float32."""
    u = x.view(np.uint32).astype(np.uint64)
    u = (u + 0x7FFF + ((u >> 16) & 1)) & 0xFFFF0000
    return u.astype(np.uint32).view(np.float32)


def output(p: dict, seed: int, row_width: int = 1,
           precision: str = "float32") -> np.ndarray:
    """The reference output of one pattern of a request with ``seed``."""
    if precision not in ("float32", "bfloat16"):
        raise ValueError(f"unknown precision {precision!r}")
    rng = np.random.default_rng(seed)
    idx = absolute_indices(p)
    f = work.footprint(work.parse_index(p["pattern"]), p["delta"],
                       p["count"])
    table = rng.standard_normal((f, row_width), dtype=np.float32)
    if p["kernel"] == "gather":
        if precision == "bfloat16":
            table = to_bfloat16(table)
        return table[idx]
    vals = rng.standard_normal((idx.size, row_width), dtype=np.float32)
    if precision == "bfloat16":
        vals = to_bfloat16(vals)
    last = np.full(f, -1, np.int64)
    np.maximum.at(last, idx, np.arange(idx.size, dtype=np.int64))
    rows = np.flatnonzero(last >= 0)
    out = np.zeros((f, row_width), np.float32)
    out[rows] = vals[last[rows]]
    return out


def sha256(a: np.ndarray) -> str:
    return hashlib.sha256(np.ascontiguousarray(a).tobytes()).hexdigest()


def digest(p: dict, seed: int, row_width: int = 1,
           precision: str = "float32") -> str:
    return sha256(output(p, seed, row_width, precision))
