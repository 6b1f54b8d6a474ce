"""Public jit'd wrappers for the line-gather kernel.

Lays the table out as lane-dense 128-lane lines (DESIGN.md §2.3), expands
each row index into the line (or element) indices the kernel fetches,
picks the VMEM-resident regime for small tables and the DMA regime
otherwise — both from the table's tiled bytes — and defaults to interpret
mode off-TPU.  For narrow rows in the DMA regime it finds each grid
step's line window on the device (``_step_bases``, DESIGN.md §2.4);
``window_steps`` is the same rule on the host, for the trace's counter.
The block size defaults to the deterministic per-geometry autotuner
(``kernels.autotune``); passing one explicitly bypasses the search.
``gather_rows_batched`` runs a whole pattern batch (a planner bucket) as
one kernel launch (DESIGN.md §2.2); ``gather_rows`` is its B=1 case.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

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


def window_lines(block: int, lines: int) -> int:
    """Lines in a narrow DMA step's window: ``block / 32`` (32 lines, 16
    KiB, at block 1024), four times the ``block / 128`` lines that
    ``block`` consecutive elements span, so strided and offset patterns
    fit too; never more than the table's ``lines``, padded to 8."""
    return min(block // 32, _round_up(lines, 8))


def _step_bases(fetch, block: int, window: int, lines: int):
    """Per grid step of a narrow launch over ``fetch`` (B, N) element
    indices: the first line of a ``window``-line window (a multiple of 8,
    at most ``lines - window``) that holds every line the step touches,
    or -1 where the step's lines spread wider (B, N // block)."""
    bsz = fetch.shape[0]
    step = jax.lax.shift_right_logical(fetch, kernel.SHIFT).reshape(
        bsz, -1, block)
    lo, hi = step.min(axis=-1), step.max(axis=-1)
    base = jnp.minimum(lo & -8, lines - window)
    return jnp.where(hi - base < window, base, -1)


def _legal_block(block: int, n: int) -> tuple[int, int]:
    """(block, padded n): a multiple of 1024 indices per step — the SMEM
    index block's tiling — never beyond the padded index count."""
    unit = autotune.INDEX_BLOCK
    block = max(unit, _round_up(block, unit))
    block = min(block, _round_up(max(1, n), unit))
    return block, _round_up(max(1, n), block)


def _launch_operands(table, idx, hbm: bool, block: int):
    """What the kernel takes for a gather of ``idx`` (B, N) from
    ``table`` (B, V, D): the table as int32 lines (B, R, 128), the line
    or element indices it fetches (B, N_pad), indices per step, and
    for narrow rows in HBM each step's window base (``_step_bases``)
    and the window's lines."""
    bsz, _ = idx.shape
    _, v, d = table.shape
    if table.dtype.itemsize != 4:
        table = table.astype(jnp.float32)
    # the kernel moves and selects 32-bit words, never float values, so
    # every bit pattern (-0.0 included) arrives unchanged
    table = jax.lax.bitcast_convert_type(table, jnp.int32)
    idx = jnp.clip(idx.astype(jnp.int32), 0, v - 1)
    narrow = d < LANES
    r = table_lines(v, d)
    if narrow:
        r = _round_up(r, 8)              # whole tiles: windows stay aligned
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
    # the tail repeats the last index, so a ragged last step stays as
    # narrow as its real indices
    fetch = jnp.pad(fetch, ((0, 0), (0, n_pad - n_f)),
                    mode="edge" if n_f else "constant")
    bases, window = None, 0
    if narrow and hbm:
        window = window_lines(blk, r)
        bases = _step_bases(fetch, blk, window, r)
    return lines, fetch, blk, bases, window


@functools.partial(jax.jit, static_argnames=("hbm", "block", "interpret"))
def _gather_rows_batched(table, idx, hbm: bool, block: int, interpret: bool):
    bsz, n = idx.shape
    d = table.shape[2]
    dtype = table.dtype
    word = jnp.float32 if dtype.itemsize != 4 else dtype
    narrow = d < LANES
    lines, fetch, blk, bases, window = _launch_operands(table, idx, hbm,
                                                        block)
    out = kernel.gather_lines(lines, fetch, block=blk, narrow=narrow,
                              hbm=hbm, interpret=interpret, bases=bases,
                              window=window)
    width = d if narrow else _round_up(d, LANES)      # words out per row
    out = out.reshape(bsz, -1)[:, :n * width].reshape(bsz, n, width)
    out = out[..., :d]
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
    mode, block = _launch_plan(bsz, n, v, d, table.dtype.name, mode, block,
                               interp)
    return _gather_rows_batched(table, idx, mode == "dma", block, interp)


def _launch_plan(bsz: int, n: int, v: int, d: int, dtype: str, mode: str,
                 block: int | None, interp: bool) -> tuple[str, int]:
    """(regime, indices per grid step) of a launch of this geometry."""
    if mode == "auto":
        tiled = autotune.tiled_bytes(table_lines(v, d), LANES, "float32")
        mode = "vmem" if tiled <= _VMEM_TABLE_BYTES else "dma"
    if mode not in ("vmem", "dma"):
        raise ValueError(f"unknown gather mode {mode!r}")
    if block is None:
        block = autotune.choose(autotune.TileKey(
            op="gather", batch=bsz, lanes=n, rows=v, width=d, dtype=dtype,
            platform="interpret" if interp else "tpu")).block \
            or _DEFAULT_BLOCK
    return mode, block


def window_steps(idx, rows: int, width: int, *, dtype: str = "float32",
                 mode: str = "auto",
                 block: int | None = None) -> tuple[int, int]:
    """(window steps, grid steps) of the gather launch over ``idx``
    ((B, N) or (N,) row indices into a table of ``rows`` x ``width``):
    how many of its grid steps fetch one line window rather than one
    line per element.  The fits rule of ``_step_bases``, in numpy, with
    the regime and block the launch would use; (0, 0) where the launch
    has no window path (wide rows, or the VMEM regime)."""
    idx = np.asarray(idx)
    idx = idx.reshape(-1, idx.shape[-1])
    bsz, n = idx.shape
    mode, block = _launch_plan(bsz, n, rows, width, dtype, mode, block,
                               _should_interpret(None))
    if mode != "dma" or width >= LANES:
        return 0, 0
    fetch = idx.astype(np.int32, copy=False)
    if width > 1:
        fetch = (np.clip(fetch, 0, rows - 1)[..., None] * width
                 + np.arange(width, dtype=np.int32)).reshape(bsz, -1)
    n_f = fetch.shape[1]
    blk, n_pad = _legal_block(block, n_f)
    # each step's extremes; the clip and the line shift are monotone, so
    # they apply after the reduction, and the edge-padded tail of a
    # ragged last step has the extremes of its real indices
    full = n_f // blk * blk
    parts = [fetch[:, :full].reshape(bsz, -1, blk)]
    if full < n_pad:
        parts.append(fetch[:, full:][:, None, :])
    lo = np.concatenate([p.min(axis=-1) for p in parts if p.size], axis=1)
    hi = np.concatenate([p.max(axis=-1) for p in parts if p.size], axis=1)
    top = rows * width - 1
    lo = np.clip(lo, 0, top) >> kernel.SHIFT
    hi = np.clip(hi, 0, top) >> kernel.SHIFT
    lines = _round_up(table_lines(rows, width), 8)
    window = window_lines(blk, lines)
    base = np.minimum(lo & -8, lines - window)
    return int((hi - base < window).sum()), int(lo.size)


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
