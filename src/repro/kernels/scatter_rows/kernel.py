"""MXU one-hot scatter kernel — the TPU-native Spatter scatter backend.

CPU/GPU scatter relies on hardware write combining / atomics; the TPU has
neither at kernel level.  The TPU-native reformulation (DESIGN.md §2) turns
scatter into dense compute: for each 128-lane slice of a chunk of
(index, value) pairs, build a (block_v, 128) one-hot membership matrix
for the output tile and contract it with the slice's values on the MXU:

    out[vb] += onehot(idx_slice in vb) @ vals_slice

The output tile revisits are *consecutive* (chunk is the innermost grid
dim), so the accumulator stays resident in VMEM across the whole sweep —
the analogue of keeping the scatter target cache-resident in the paper's
CPU backend.  Duplicate indices are handled by construction (they add).
Index and value chunks are ordinary pipelined blocks: the pipeline
fetches chunk c+1 while chunk c contracts.

Row layout (DESIGN.md §2.3).  The chip moves (8, 128) tiles.  Rows of
width >= 128 (a lane multiple) are scattered as rows: the one-hot picks
the row, the values are the (128, D) slice.  Narrower rows are scattered
as *elements* into the lane-dense flat table (R, 128): element ``k``
goes to line ``k >> 7``, lane ``k & 127``, so the contraction is
``onehot(line) @ (onehot(lane) * value)`` — both one-hots built from the
index vector, the second transposed for free by contracting on its
last dim.  Index vectors are VMEM blocks, never SMEM slices.

Store mode is the same single pass: the host-precomputed last-write-wins
mask (Pattern.keep_last_mask, DESIGN.md §2.1) routes dropped lanes out
of range before launch, so every surviving lane is its element's unique
write — the kernel initializes each output tile from ``dst`` and
overwrites exactly the covered elements with the contraction.  A store
moves 32-bit words, not float values: ``select_words`` contracts each
byte on its own, so every bit pattern arrives unchanged.  Add mode sums
float values at HIGHEST precision.  With ``with_cov``
the same launch also emits the int32 coverage of every output element —
the lane-sharded combine (core/plan._lane_body) psums it to decide which
elements any shard wrote.

The kernel is batch-NATIVE (DESIGN.md §2.2): the grid leads with the
pattern-batch dim, so a planner bucket is ONE launch and the
single-pattern entry points in ops.py are the B=1 case.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
SHIFT = 7
_HIGHEST = jax.lax.Precision.HIGHEST
_NT = (((1,), (1,)), ((), ()))      # contract the last dims: A @ B.T


def select_words(contract, words):
    """Select int32 ``words`` through a one-hot contraction, bit for bit.

    ``contract(x)`` applies the one-hot to ``x``, an f32 array shaped
    like ``words``.  Each byte goes through on its own: a byte is exact
    in bf16, and a sum with at most one nonzero term is that term, so
    every bit pattern (-0.0, inf, NaN, subnormals) comes back unchanged
    at any matmul precision.  Contracting the float values instead would
    turn -0.0 into +0.0 and spread ``0 * inf = NaN`` over the tile."""
    out = None
    for s in range(0, 32, 8):
        byte = ((words >> s) & 0xFF).astype(jnp.float32)
        part = contract(byte).astype(jnp.int32) << s
        out = part if out is None else out | part
    return out


def _scatter_kernel(block_v: int, narrow: bool, store: bool, with_cov: bool,
                    idx_ref, vals_ref, *refs):
    if store:
        dst_ref, out_ref, *cov = refs
    else:
        (out_ref,) = refs
    vb = pl.program_id(1)
    c = pl.program_id(2)
    width = out_ref.shape[-1]

    @pl.when(c == 0)
    def _init():
        out_ref[...] = dst_ref[...] if store else jnp.zeros_like(out_ref)
        if with_cov:
            cov[0][...] = jnp.zeros_like(cov[0])

    rows = jax.lax.broadcasted_iota(jnp.int32, (block_v, LANES), 0)
    sub = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)

    def body(q, carry):
        written, count = carry
        k = idx_ref[0, pl.ds(q, 1), :]                        # (1, 128)
        line = jax.lax.shift_right_logical(k, SHIFT) if narrow else k
        hit = (rows == line - vb * block_v).astype(jnp.float32)
        if narrow:
            sel = sub == (k & (LANES - 1))                   # [lane, elem]
            v = vals_ref[0, pl.ds(q, 1), :]

            def contract(lhs, x, **kw):
                rhs = jnp.where(sel, x, jnp.zeros_like(x)).astype(lhs.dtype)
                return jax.lax.dot_general(
                    lhs, rhs, _NT, preferred_element_type=jnp.float32, **kw)
        else:
            v = vals_ref[0, pl.ds(q * LANES, LANES), :]

            def contract(lhs, x, **kw):
                return jax.lax.dot(lhs, x.astype(lhs.dtype),
                                   preferred_element_type=jnp.float32, **kw)
        if not store:
            return written + contract(hit, v, precision=_HIGHEST), count
        # store: v holds int32 words; move them bytewise (bf16 is exact)
        hit_b = hit.astype(jnp.bfloat16)
        written |= select_words(functools.partial(contract, hit_b), v)
        if narrow:
            count += jax.lax.dot_general(
                hit, sel.astype(jnp.float32), _NT,
                preferred_element_type=jnp.float32)
        else:
            count += jnp.sum(hit, axis=1, keepdims=True)
        return written, count

    n_slices = idx_ref.shape[1]
    init = (jnp.zeros((block_v, width), out_ref.dtype),
            jnp.zeros((block_v, width if narrow else 1), jnp.float32))
    written, count = jax.lax.fori_loop(0, n_slices, body, init)
    if not store:
        out_ref[0] += written
        return
    covered = jnp.broadcast_to(count > 0, (block_v, width))
    out_ref[0] = jnp.where(covered, written, out_ref[0])
    if with_cov:
        cov[0][0] = jnp.maximum(cov[0][0], covered.astype(jnp.int32))


def scatter_lines(idx: jax.Array, vals: jax.Array, out_rows: int, *,
                  dst: jax.Array | None = None, narrow: bool, block_v: int,
                  block_n: int, with_cov: bool = False, interpret: bool):
    """One launch for the whole pattern batch.

    ``idx`` (B, N // 128, 128) lane-dense; out-of-range entries are
    dropped.  Wide: ``idx`` are rows, ``vals`` (B, N, D) and the output
    (B, out_rows, D).  Narrow: ``idx`` are flat element indices, ``vals``
    lane-dense (B, N // 128, 128) and the output the flat lines
    (B, out_rows, 128).  With ``dst`` the scatter stores into it (each
    in-range index at most once per batch row — the host keep mask's
    contract); without, it sums into zeros.  The caller guarantees
    ``out_rows % block_v == 0``, ``block_v % 8 == 0``, ``N % block_n
    == 0`` and ``block_n`` a multiple of 1024 unless it equals N.
    """
    bsz, nl, _ = idx.shape
    n = nl * LANES
    width = LANES if narrow else vals.shape[-1]
    grid = (bsz, out_rows // block_v, n // block_n)
    lanes = pl.BlockSpec((1, block_n // LANES, LANES),
                         lambda b, vb, c: (b, c, 0))
    tile = pl.BlockSpec((1, block_v, width), lambda b, vb, c: (b, vb, 0))
    in_specs = [lanes, lanes if narrow else
                pl.BlockSpec((1, block_n, width), lambda b, vb, c: (b, c, 0))]
    operands = [idx, vals]
    store = dst is not None
    if store:
        in_specs.append(tile)
        operands.append(dst)
    out_shape = jax.ShapeDtypeStruct((bsz, out_rows, width), vals.dtype)
    out_specs = tile
    if with_cov:
        out_shape = (out_shape, jax.ShapeDtypeStruct(out_shape.shape,
                                                     jnp.int32))
        out_specs = (tile, tile)
    return pl.pallas_call(
        functools.partial(_scatter_kernel, block_v, narrow, store, with_cov),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(*operands)
