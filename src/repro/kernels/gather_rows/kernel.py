"""Line gather kernel — the TPU-native Spatter gather backend.

The chip moves (8, 128) tiles, so the kernel never addresses a row
narrower or wider than one 128-lane line (DESIGN.md §2.3): ops.py hands
it the table as lane-dense lines ``(B, R, 128)`` and one index per line
to fetch.  A row of width D >= 128 is D/128 consecutive lines; a
narrower row is gathered element by element — element ``k`` of the flat
table lives in line ``k >> 7``, lane ``k & 127``, and the kernel selects
that lane after fetching the line.

Two regimes, mirroring the paper's cache-resident vs memory-resident split
(DESIGN.md §2):

  * ``dma``  — the table stays in HBM (``pl.ANY``) and the kernel
    issues one async line copy per index: all ``block`` copies of a grid
    step start before the first is waited on, so the step keeps
    ``block`` fetches in flight — the TPU analogue of the HW prefetcher's
    outstanding-miss depth studied in paper Fig 4.  Narrow rows add a
    window path: a step whose elements all lie in ``W`` consecutive
    lines (the wrapper says which, per step) fetches those lines with
    one DMA and copies each element's line out of VMEM, as the ``vmem``
    regime does (DESIGN.md §2.4).
  * ``vmem`` — small tables are staged whole into VMEM and lines are
    copied out with dynamic sublane loads (the "cache-resident" regime:
    once the table is in VMEM, arbitrary reuse is free).

The line of each index reaches the kernel as a per-step SMEM block (the
CUDA backend's shared-memory index staging, paper §3.2): each step reads
its ``block`` lines as scalars, so SMEM use does not grow with the
pattern.  ``gather_lines`` computes them (``k >> 7`` for a narrow row,
less the step's first window line on the window path).  The lane select
needs the element indices as a vector; it reads them from a VMEM block.

The kernel is batch-NATIVE (DESIGN.md §2.2): the grid leads with the
pattern-batch dim, so a whole planner bucket is ONE launch; the
single-pattern entry point in ops.py is the B=1 case.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SHIFT = 7              # log2(LANES): element k sits in line k >> SHIFT
STEPS = 1024           # grid steps per SMEM block of window bases
UNROLL = 32            # rows per iteration of a VMEM line copy loop


def _select_lanes(stage, idx_vmem, out_ref):
    """out[0, g, l] = stage[g*128 + l, idx[g, l] & 127] per 128-line group.

    The transpose puts element j of a group in lane j, so the one-hot
    select reduces over sublanes and the result lands lane-dense.  The
    lines are int32 words: an integer sum of one word and zeros is that
    word, where a float sum would turn -0.0 into +0.0."""
    sub = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
    for g in range(stage.shape[0] // LANES):
        lines_t = stage[g * LANES:(g + 1) * LANES, :].T     # [lane, elem]
        lane = idx_vmem[0, g:g + 1, :] & (LANES - 1)         # (1, 128)
        picked = jnp.where(sub == lane, lines_t, jnp.zeros_like(lines_t))
        out_ref[0, g:g + 1, :] = jnp.sum(picked, axis=0, keepdims=True)


def _gather_kernel(block: int, narrow: bool, hbm: bool, *refs):
    windowed = narrow and hbm
    if windowed:
        idx_smem, base_smem, idx_vmem, table_ref, out_ref, *scratch = refs
    elif narrow:
        idx_smem, idx_vmem, table_ref, out_ref, *scratch = refs
    else:
        idx_smem, table_ref, out_ref, *scratch = refs
    stage = scratch[0] if narrow else out_ref.at[0]
    b = pl.program_id(0)

    def copy_lines(src):
        """stage[r] = src[line r], a dynamic sublane load each; UNROLL
        rows an iteration, so every store's sublane is static."""
        def copy(q, c):
            for u in range(UNROLL):
                r = q * UNROLL + u
                stage[pl.ds(r, 1), :] = src[pl.ds(idx_smem[r], 1), :]
            return c

        jax.lax.fori_loop(0, block // UNROLL, copy, 0)

    def dma_lines():
        sem = scratch[-1]

        def start(r, c):
            pltpu.make_async_copy(table_ref.at[b, idx_smem[r]],
                                  stage.at[r], sem).start()
            return c

        def wait(r, c):
            # every copy moves one line: each wait retires one of them
            pltpu.make_async_copy(table_ref.at[b, 0], stage.at[0],
                                  sem).wait()
            return c

        jax.lax.fori_loop(0, block, start, 0)
        jax.lax.fori_loop(0, block, wait, 0)

    if windowed:
        window, sem = scratch[1], scratch[-1]
        # first line of the window, or -1
        base = base_smem[pl.program_id(1) % STEPS]

        def window_lines():
            cp = pltpu.make_async_copy(
                table_ref.at[b, pl.ds(pl.multiple_of(base, 8),
                                      window.shape[0])], window, sem)
            cp.start()
            cp.wait()
            copy_lines(window)

        jax.lax.cond(base >= 0, window_lines, dma_lines)
    elif hbm:
        dma_lines()
    else:
        copy_lines(table_ref.at[0])
    if narrow:
        _select_lanes(stage, idx_vmem, out_ref)


def gather_lines(table: jax.Array, idx: jax.Array, *, block: int,
                 narrow: bool, hbm: bool, interpret: bool,
                 bases: jax.Array | None = None,
                 window: int = 0) -> jax.Array:
    """One launch for the whole pattern batch over lane-dense lines.

    ``table`` (B, R, 128); ``idx`` (B, N) in range, N a multiple of
    ``block`` and ``block`` a multiple of 1024 (the SMEM index block's
    tiling).  Wide: ``idx`` are line indices and the result is the
    fetched lines (B, N, 128).  Narrow: ``idx`` are flat element indices
    and the result is the elements, lane-dense (B, N // 128, 128).

    Narrow in HBM, ``bases`` (B, N // block) int32 gives each grid
    step's window: the first of ``window`` lines (a multiple of 8, and
    ``+ window <= R``) that hold all of the step's elements, or -1 where
    they do not fit and the step fetches one line per element.
    """
    bsz, n = idx.shape
    _, r, _ = table.shape
    assert n % block == 0, (n, block)
    nblk = n // block
    in_specs = [pl.BlockSpec((block,), lambda b, i: (b * nblk + i,),
                             memory_space=pltpu.SMEM)]
    # the kernel reads the line of each index as it is
    lines = jax.lax.shift_right_logical(idx, SHIFT) if narrow else idx
    operands = []
    scratch = []
    if narrow and hbm:
        assert bases.shape == (bsz, nblk), (bases.shape, bsz, nblk)
        assert window % 8 == 0 and 0 < window <= r, (window, r)
        # on the window path, counted from the window's first line
        first = jnp.maximum(bases, 0)[..., None]
        lines = (lines.reshape(bsz, nblk, block) - first).reshape(bsz, n)
        # SMEM blocks of one index block's tiling, each the bases of
        # STEPS grid steps: refetched once every STEPS steps
        per_b = -(-nblk // STEPS)
        in_specs.append(pl.BlockSpec(
            (STEPS,), lambda b, i: (b * per_b + i // STEPS,),
            memory_space=pltpu.SMEM))
        operands.append(jnp.pad(bases, ((0, 0), (0, per_b * STEPS - nblk)),
                                constant_values=-1).reshape(-1))
    operands.insert(0, lines.reshape(bsz * n))
    if narrow:
        dense = pl.BlockSpec((1, block // LANES, LANES),
                             lambda b, i: (b, i, 0))
        in_specs.append(dense)
        operands.append(idx.reshape(bsz, n // LANES, LANES))
        scratch.append(pltpu.VMEM((block, LANES), table.dtype))
        if hbm:
            scratch.append(pltpu.VMEM((window, LANES), table.dtype))
        out_shape, out_spec = (bsz, n // LANES, LANES), dense
    else:
        out_shape = (bsz, n, LANES)
        out_spec = pl.BlockSpec((1, block, LANES), lambda b, i: (b, i, 0))
    if hbm:
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        scratch.append(pltpu.SemaphoreType.DMA(()))
    else:
        in_specs.append(pl.BlockSpec((1, r, LANES), lambda b, i: (b, 0, 0)))
    operands.append(table)
    return pl.pallas_call(
        functools.partial(_gather_kernel, block, narrow, hbm),
        grid=(bsz, nblk),
        in_specs=in_specs,
        out_specs=out_spec,
        scratch_shapes=scratch,
        out_shape=jax.ShapeDtypeStruct(out_shape, table.dtype),
        interpret=interpret,
    )(*operands)
