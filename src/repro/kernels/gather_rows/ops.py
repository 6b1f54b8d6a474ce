"""Public jit'd wrappers for the line-gather kernel.

Lays the table out as lane-dense 128-lane lines (DESIGN.md §2.3), expands
each row index into the line (or element) indices the kernel fetches,
picks the VMEM-resident regime for small tables and the DMA regime
otherwise — both from the table's tiled bytes — and defaults to interpret
mode off-TPU.  The block size defaults to the deterministic per-geometry
autotuner (``kernels.autotune``); passing one explicitly bypasses the
search.  ``gather_rows_batched`` runs a whole pattern batch (a planner
bucket) as one kernel launch (DESIGN.md §2.2); ``gather_rows`` is its
B=1 case.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import kernel
from .. import autotune

LANES = kernel.LANES
# VMEM on v5e is 128 MiB/core but a kernel's default scoped window is
# 16 MiB and the pipeline double-buffers the table block: stage tables
# whole only when their tiled bytes are at most this.
_VMEM_TABLE_BYTES = 4 * 1024 * 1024
# legacy fixed block — served when the autotuner is disabled()
# (autotune.LEGACY mirrors it; a drift test pins them equal)
_DEFAULT_BLOCK = autotune.INDEX_BLOCK


def _should_interpret(interpret: bool | None) -> bool:
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def table_lines(v: int, d: int) -> int:
    """Lines of the lane-dense layout of a (V, D) table (D padded to a
    lane multiple when D >= 128; narrower rows packed element by
    element)."""
    if d < LANES:
        return -(-v * d // LANES)
    return v * (_round_up(d, LANES) // LANES)


def _legal_block(block: int, n: int) -> tuple[int, int]:
    """(block, padded n): a multiple of 1024 indices per step — the SMEM
    index block's tiling — never beyond the padded index count."""
    unit = autotune.INDEX_BLOCK
    block = max(unit, _round_up(block, unit))
    block = min(block, _round_up(max(1, n), unit))
    return block, _round_up(max(1, n), block)


@functools.partial(jax.jit, static_argnames=("hbm", "block", "interpret"))
def _gather_rows_batched(table, idx, hbm: bool, block: int, interpret: bool):
    bsz, n = idx.shape
    _, v, d = table.shape
    dtype = table.dtype
    if dtype.itemsize != 4:
        table = table.astype(jnp.float32)
    word = table.dtype
    # the kernel moves and selects 32-bit words, never float values, so
    # every bit pattern (-0.0 included) arrives unchanged
    table = jax.lax.bitcast_convert_type(table, jnp.int32)
    idx = jnp.clip(idx.astype(jnp.int32), 0, v - 1)
    narrow = d < LANES
    r = table_lines(v, d)
    if narrow:
        flat = table.reshape(bsz, v * d)
        lines = jnp.pad(flat, ((0, 0), (0, r * LANES - v * d)))
        fetch = (idx[..., None] * d + jnp.arange(d, dtype=jnp.int32))
    else:
        dp = _round_up(d, LANES)
        lines = jnp.pad(table, ((0, 0), (0, 0), (0, dp - d)))
        m = dp // LANES
        fetch = idx[..., None] * m + jnp.arange(m, dtype=jnp.int32)
    lines = lines.reshape(bsz, r, LANES)
    fetch = fetch.reshape(bsz, -1)
    n_f = fetch.shape[1]
    blk, n_pad = _legal_block(block, n_f)
    fetch = jnp.pad(fetch, ((0, 0), (0, n_pad - n_f)))      # line 0: harmless
    out = kernel.gather_lines(lines, fetch, block=blk, narrow=narrow,
                              hbm=hbm, interpret=interpret)
    out = out.reshape(bsz, -1)[:, :n_f * (1 if narrow else LANES)]
    out = out.reshape(bsz, n, -1)[..., :d]
    return jax.lax.bitcast_convert_type(out, word).astype(dtype)


def gather_rows_batched(table: jax.Array, idx: jax.Array, *,
                        mode: str = "auto",
                        block: int | None = None,
                        interpret: bool | None = None) -> jax.Array:
    """Batched gather: (B, V, D) tables, (B, N) idx -> (B, N, D).

    One kernel launch for the whole pattern batch (a planner bucket).  The
    regime choice sizes VMEM per b-step, so it uses one pattern's tiled
    table bytes, not the whole stack's.  ``block`` sets the indices per
    grid step in either regime; left ``None`` it comes from the
    autotuner, keyed on the geometry the kernel actually sees (the local
    shard under a lane-sharded launch) — a pure function of shapes, so
    one jit signature per geometry and ``misses`` stays an exact compile
    count upstream.  Out-of-range indices are clamped to the table.
    """
    if table.ndim != 3 or idx.ndim != 2 or table.shape[0] != idx.shape[0]:
        raise ValueError(f"expected (B,V,D) table and (B,N) idx, got "
                         f"{table.shape} / {idx.shape}")
    interp = _should_interpret(interpret)
    bsz, n = idx.shape
    _, v, d = table.shape
    if mode == "auto":
        tiled = autotune.tiled_bytes(table_lines(v, d), LANES, "float32")
        mode = "vmem" if tiled <= _VMEM_TABLE_BYTES else "dma"
    if mode not in ("vmem", "dma"):
        raise ValueError(f"unknown gather mode {mode!r}")
    if block is None:
        block = autotune.choose(autotune.TileKey(
            op="gather", batch=bsz, lanes=n, rows=v, width=d,
            dtype=table.dtype.name,
            platform="interpret" if interp else "tpu")).block \
            or _DEFAULT_BLOCK
    return _gather_rows_batched(table, idx, mode == "dma", block, interp)


def gather_rows(table: jax.Array, idx: jax.Array, *, mode: str = "auto",
                block: int | None = None,
                interpret: bool | None = None) -> jax.Array:
    """Gather rows of ``table`` (V, D) at positions ``idx`` (N,) -> (N, D).

    The B=1 case of the batched kernel — one code path for both.
    """
    if table.ndim != 2 or idx.ndim != 1:
        raise ValueError(f"expected (V,D) table and (N,) idx, got "
                         f"{table.shape} / {idx.shape}")
    return gather_rows_batched(table[None], idx[None], mode=mode,
                               block=block, interpret=interpret)[0]
