"""Public jit'd wrappers for the MXU scatter kernel.

``scatter_add_rows_batched`` / ``scatter_store_rows_batched`` run a whole
pattern batch (a planner bucket) as ONE kernel launch (DESIGN.md §2.2);
the per-pattern entry points are the B=1 case of the same kernel.  Rows
narrower than a 128-lane line are scattered element by element into the
lane-dense flat table; wider rows are padded to a lane multiple
(DESIGN.md §2.3).  Store mode expects its index buffer pre-deduped on
the host (dropped lanes routed out of range — Pattern.keep_last_mask),
so the kernel is a single pass with no sort and no coverage-count
launch; it moves 32-bit words, so every stored bit pattern arrives
unchanged.  Block sizes default to the deterministic per-geometry autotuner
(``kernels.autotune``); passing a block explicitly bypasses the search.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import kernel
from .. import autotune

LANES = kernel.LANES
# legacy fixed tiles — served when the autotuner is disabled()
# (autotune.LEGACY mirrors these; a drift test pins them equal)
_DEFAULT_BLOCK_V = 128
_DEFAULT_BLOCK_N = autotune.INDEX_BLOCK


def _oob() -> int:
    return jnp.iinfo(jnp.int32).max


def _should_interpret(interpret: bool | None) -> bool:
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _pick_blocks(v: int, n: int, bsz: int, d: int, dtype,
                 block_v: int | None, block_n: int | None,
                 interpret: bool):
    """Resolve block sizes: explicit args win, the rest are autotuned."""
    if block_v is None or block_n is None:
        choice = autotune.choose(autotune.TileKey(
            op="scatter", batch=bsz, lanes=n, rows=v, width=d,
            dtype=jnp.dtype(dtype).name,
            platform="interpret" if interpret else "tpu"))
        if block_v is None:
            block_v = choice.block_v or _DEFAULT_BLOCK_V
        if block_n is None:
            block_n = choice.block or _DEFAULT_BLOCK_N
    return block_v, block_n


def _legal_blocks(rows: int, n_e: int, block_v: int, block_n: int):
    """(block_v, block_n, n_pad) legal for the (8, 128) tiling: ``block_v``
    a multiple of 8 no larger than the padded output rows, ``block_n`` a
    multiple of 1024 indices or one chunk of all ``n_pad`` of them."""
    n_pad = _round_up(max(1, n_e), LANES)
    if block_n >= n_pad:
        block_n = n_pad                                # one chunk
    else:
        block_n = _round_up(block_n, autotune.INDEX_BLOCK)
        n_pad = _round_up(n_e, block_n)
    block_v = _round_up(min(block_v, _round_up(rows, 8)), 8)
    return block_v, block_n, n_pad


def _layout(idx, vals, v: int, block_v: int, block_n: int):
    """Lay a scatter out for the kernel: (idx, vals, out_rows, block_v,
    block_n, narrow) with lane-dense indices, lines or padded rows, and
    blocks legalized for the (8, 128) tiling."""
    bsz, n, d = vals.shape
    idx = idx.astype(jnp.int32)
    idx = jnp.where((idx >= 0) & (idx < v), idx, _oob())
    narrow = d < LANES
    if narrow:
        # element k = row * d + e; dropped rows stay out of every line
        e = jnp.arange(d, dtype=jnp.int32)
        idx = jnp.where(idx[..., None] == _oob(), _oob(),
                        idx[..., None] * d + e).reshape(bsz, n * d)
        vals = vals.reshape(bsz, n * d)
        rows = -(-v * d // LANES)
    else:
        vals = jnp.pad(vals, ((0, 0), (0, 0), (0, _round_up(d, LANES) - d)))
        rows = v
    n_e = idx.shape[1]
    block_v, block_n, n_pad = _legal_blocks(rows, n_e, block_v, block_n)
    idx = jnp.pad(idx, ((0, 0), (0, n_pad - n_e)), constant_values=_oob())
    pad_vals = ((0, 0), (0, n_pad - n_e)) + (((0, 0),) if not narrow else ())
    vals = jnp.pad(vals, pad_vals)
    if narrow:
        vals = vals.reshape(bsz, n_pad // LANES, LANES)
    idx = idx.reshape(bsz, n_pad // LANES, LANES)
    return idx, vals, _round_up(rows, block_v), block_v, block_n, narrow


def _to_lines(table, out_rows: int):
    """(B, V, D) -> the kernel's output layout with ``out_rows`` rows."""
    bsz, v, d = table.shape
    if d < LANES:
        flat = table.reshape(bsz, v * d)
        flat = jnp.pad(flat, ((0, 0), (0, out_rows * LANES - v * d)))
        return flat.reshape(bsz, out_rows, LANES)
    return jnp.pad(table, ((0, 0), (0, out_rows - v),
                           (0, _round_up(d, LANES) - d)))


def _from_lines(out, v: int, d: int):
    """Inverse of ``_to_lines``: back to (B, V, D)."""
    bsz = out.shape[0]
    if d < LANES:
        return out.reshape(bsz, -1)[:, :v * d].reshape(bsz, v, d)
    return out[:, :v, :d]


# ---------------------------------------------------------------------------
# scatter-add
# ---------------------------------------------------------------------------

@functools.partial(jax.jit,
                   static_argnames=("v", "block_v", "block_n", "interpret"))
def _scatter_add_batched(idx, vals, v: int, block_v: int, block_n: int,
                         interpret: bool):
    dtype = vals.dtype
    d = vals.shape[2]
    idx, lines, rows, block_v, block_n, narrow = _layout(
        idx, vals.astype(jnp.float32), v, block_v, block_n)
    out = kernel.scatter_lines(idx, lines, rows, narrow=narrow,
                               block_v=block_v, block_n=block_n,
                               interpret=interpret)
    return _from_lines(out, v, d).astype(dtype)


def scatter_add_rows_batched(idx: jax.Array, vals: jax.Array, v: int, *,
                             block_v: int | None = None,
                             block_n: int | None = None,
                             interpret: bool | None = None) -> jax.Array:
    """Batched scatter-add: idx (B, N), vals (B, N, D) -> (B, V, D).

    One kernel launch for the whole pattern batch.  Out-of-range indices
    are dropped (matching ``.at[].add(mode="drop")``).
    """
    if vals.ndim != 3 or idx.ndim != 2 or idx.shape != vals.shape[:2]:
        raise ValueError(f"bad shapes idx={idx.shape} vals={vals.shape}")
    interp = _should_interpret(interpret)
    bsz, n, d = vals.shape
    block_v, block_n = _pick_blocks(v, n, bsz, d, vals.dtype,
                                    block_v, block_n, interp)
    return _scatter_add_batched(idx, vals, v, block_v, block_n, interp)


def scatter_add_rows(idx: jax.Array, vals: jax.Array, v: int, *,
                     block_v: int | None = None,
                     block_n: int | None = None,
                     interpret: bool | None = None) -> jax.Array:
    """Scatter-add ``vals`` (N, D) at row indices ``idx`` (N,) into (V, D).

    The B=1 case of the batched kernel — one code path for both.
    """
    if vals.ndim != 2 or idx.ndim != 1 or idx.shape[0] != vals.shape[0]:
        raise ValueError(f"bad shapes idx={idx.shape} vals={vals.shape}")
    return scatter_add_rows_batched(idx[None], vals[None], v,
                                    block_v=block_v, block_n=block_n,
                                    interpret=interpret)[0]


# ---------------------------------------------------------------------------
# single-pass store
# ---------------------------------------------------------------------------

def _word_dtype(dtype):
    """The 32-bit type a store moves: narrower floats widen to float32."""
    return dtype if jnp.dtype(dtype).itemsize == 4 else jnp.float32


@functools.partial(jax.jit,
                   static_argnames=("block_v", "block_n", "with_covered",
                                    "interpret"))
def _scatter_store_batched(dst, idx, vals, block_v: int, block_n: int,
                           with_covered: bool, interpret: bool):
    dtype = dst.dtype
    word = _word_dtype(dtype)
    _, v, d = dst.shape

    def words(x):
        # the kernel moves int32 words, never float values
        return jax.lax.bitcast_convert_type(x.astype(word), jnp.int32)

    idx, lines, rows, block_v, block_n, narrow = _layout(
        idx, words(vals), v, block_v, block_n)
    out = kernel.scatter_lines(idx, lines, rows,
                               dst=_to_lines(words(dst), rows),
                               narrow=narrow, block_v=block_v,
                               block_n=block_n, with_cov=with_covered,
                               interpret=interpret)
    cov = None
    if with_covered:
        out, cov = out
    out = jax.lax.bitcast_convert_type(_from_lines(out, v, d),
                                       word).astype(dtype)
    return (out, _from_lines(cov, v, d)) if with_covered else out


def scatter_store_rows_batched(dst: jax.Array, idx: jax.Array,
                               vals: jax.Array, *,
                               block_v: int | None = None,
                               block_n: int | None = None,
                               with_covered: bool = False,
                               interpret: bool | None = None):
    """Batched store: dst (B, V, D), idx (B, N), vals (B, N, D) -> (B, V, D).

    One single-pass kernel launch for the whole pattern batch.  Contract:
    each in-range index value occurs at most once per batch row (the host
    keep mask already dropped duplicate writes by routing them out of
    range); out-of-range indices are dropped.  With ``with_covered`` the
    same single launch also returns the (B, V, D) int32 coverage map (1
    where this call wrote) — the lane-sharded combine's ballot.
    """
    if (vals.ndim != 3 or idx.ndim != 2 or dst.ndim != 3
            or idx.shape != vals.shape[:2] or dst.shape[2] != vals.shape[2]):
        raise ValueError(f"bad shapes dst={dst.shape} idx={idx.shape} "
                         f"vals={vals.shape}")
    interp = _should_interpret(interpret)
    bsz, n, d = vals.shape
    block_v, block_n = _pick_blocks(dst.shape[1], n, bsz, d, dst.dtype,
                                    block_v, block_n, interp)
    return _scatter_store_batched(dst, idx, vals, block_v, block_n,
                                  with_covered, interp)


def scatter_store_rows(dst: jax.Array, idx: jax.Array, vals: jax.Array, *,
                       block_v: int | None = None,
                       block_n: int | None = None,
                       interpret: bool | None = None) -> jax.Array:
    """Store ``vals`` (N, D) into ``dst`` (V, D) at rows ``idx`` (N,).

    The B=1 case of the batched kernel — one code path for both.
    """
    if (vals.ndim != 2 or idx.ndim != 1 or dst.ndim != 2
            or idx.shape[0] != vals.shape[0] or dst.shape[1] != vals.shape[1]):
        raise ValueError(f"bad shapes dst={dst.shape} idx={idx.shape} "
                         f"vals={vals.shape}")
    return scatter_store_rows_batched(dst[None], idx[None], vals[None],
                                      block_v=block_v, block_n=block_n,
                                      interpret=interpret)[0]
