"""Gather / scatter backends — the TPU adaptation of Spatter's backend set.

Paper backends -> this repo (DESIGN.md §2):

    OpenMP (compiler-vectorized)  ->  "xla"     jnp.take / .at[] — XLA's native
                                               gather/scatter lowering, i.e. what
                                               "the compiler" does with the access.
    CUDA (shared-mem index buf)   ->  "pallas"  explicit scalar-prefetch DMA kernel
                                               (index buffer in SMEM drives the DMA).
    Scalar (#pragma novec)        ->  "scalar"  lax.fori_loop of dynamic_slice,
                                               one row per step — the no-vector
                                               baseline.
    (no analogue on CPU/GPU)      ->  "onehot"  gather as one-hot MXU matmul — the
                                               TPU-only trick of turning data
                                               movement into dense compute.

All backends share one contract:

    gather(src, idx)            src: (F, R) table, idx: (N,) int32 -> (N, R)
    scatter(dst, idx, vals)     vals: (N, R) -> dst' (F, R); mode "store"|"add"

The *row* (R) is the TPU element unit (DESIGN.md §2): Spatter's 8-byte double
becomes a lane-aligned row here.  R=1 recovers the paper's scalar semantics.

Store-mode duplicate handling (DESIGN.md §2.1): the paper's parallel scatter
leaves duplicate-index order unspecified; we pin it to last-write-wins so
backends are cross-checkable.  The keep mask that implements it is a pure
function of the (static) index buffer, so it is computed ONCE on the host
(``keep_last_mask``) at build/plan time and threaded through every store
scatter as a regular operand — the timed executable contains no sort, no
dedup, nothing but the access under test (paper §3.5 measurement
discipline).
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

BACKENDS = ("xla", "onehot", "scalar", "pallas")

# Guard for the one-hot backend: a (N, F) one-hot with F beyond this is a
# mistake, not a benchmark (it would build a >2^31-element intermediate).
_ONEHOT_MAX_FOOTPRINT = 1 << 22


# ---------------------------------------------------------------------------
# Gather
# ---------------------------------------------------------------------------

def gather_xla(src: jax.Array, idx: jax.Array) -> jax.Array:
    """XLA-native gather — what the compiler emits for indexed loads."""
    return jnp.take(src, idx, axis=0)


def _select_rows(oh: jax.Array, x: jax.Array) -> jax.Array:
    """``oh @ x`` for a one-hot ``oh`` with at most one 1 per row, bit for
    bit: rows move as 32-bit words, byte by byte
    (``scatter_rows.kernel.select_words``), so -0.0, inf and NaN payloads
    survive where a float contraction would not."""
    from repro.kernels.scatter_rows.kernel import select_words
    word = x.dtype if x.dtype.itemsize == 4 else jnp.float32
    words = lax.bitcast_convert_type(x.astype(word), jnp.int32)
    ohb = oh.astype(jnp.bfloat16)
    out = select_words(lambda b: jnp.matmul(
        ohb, b.astype(jnp.bfloat16), preferred_element_type=jnp.float32),
        words)
    return lax.bitcast_convert_type(out, word).astype(x.dtype)


def gather_onehot(src: jax.Array, idx: jax.Array) -> jax.Array:
    """Gather as one-hot matmul: out = onehot(idx) @ src.  MXU-resident on TPU."""
    f = src.shape[0]
    if f > _ONEHOT_MAX_FOOTPRINT:
        raise ValueError(f"onehot backend: footprint {f} too large")
    return _select_rows(jax.nn.one_hot(idx, f, dtype=jnp.bfloat16), src)


def gather_scalar(src: jax.Array, idx: jax.Array) -> jax.Array:
    """One row per loop step — the paper's non-vectorized Scalar backend."""
    n = idx.shape[0]
    r = src.shape[1]
    out = jnp.zeros((n, r), dtype=src.dtype)

    def body(i, out):
        row = lax.dynamic_slice(src, (idx[i], 0), (1, r))
        return lax.dynamic_update_slice(out, row, (i, 0))

    return lax.fori_loop(0, n, body, out)


def gather_pallas(src: jax.Array, idx: jax.Array) -> jax.Array:
    """Scalar-prefetch DMA gather (Pallas TPU kernel, interpret=True on CPU)."""
    from repro.kernels.gather_rows import ops as gather_ops
    return gather_ops.gather_rows(src, idx)


# ---------------------------------------------------------------------------
# Scatter
# ---------------------------------------------------------------------------

def keep_last_mask(idx: np.ndarray) -> np.ndarray:
    """Host-side last-write-wins keep mask: True at the last occurrence of
    each distinct index value, False elsewhere.

    Pattern indices are static at build/plan time, so this runs ONCE in
    numpy — never inside a timed executable (DESIGN.md §2.1).
    """
    idx = np.asarray(idx)
    n = idx.shape[0]
    if n == 0:
        return np.zeros((0,), bool)
    order = np.argsort(idx, kind="stable")       # stable: ties keep position
    sidx = idx[order]
    is_last = np.concatenate([sidx[1:] != sidx[:-1], np.ones((1,), bool)])
    keep = np.zeros((n,), bool)
    keep[order[is_last]] = True
    return keep


def _keep_last_traced(idx: jax.Array, footprint: int) -> jax.Array:
    """Sort-free traced fallback for ad-hoc store scatters without a
    precomputed mask: scatter-max each lane's position into a (F,) table,
    keep the lanes that hold their row's max.  O(N + F), no sort primitive.

    The engine/planner hot paths never hit this — they pass the host mask.
    """
    n = idx.shape[0]
    pos = jnp.arange(n, dtype=jnp.int32)
    last = jnp.zeros((footprint,), jnp.int32).at[idx].max(pos, mode="drop")
    return last[idx] == pos


def _store_keep(keep, idx: jax.Array, footprint: int) -> jax.Array:
    """Resolve a store scatter's keep mask: the caller-provided operand, the
    host mask when indices are concrete, else the traced fallback."""
    if keep is not None:
        return keep
    if isinstance(idx, jax.core.Tracer):   # indices unknown at trace time
        return _keep_last_traced(idx, footprint)
    return jnp.asarray(keep_last_mask(np.asarray(idx)))


def scatter_xla(dst: jax.Array, idx: jax.Array, vals: jax.Array,
                mode: str = "store", keep: jax.Array | None = None
                ) -> jax.Array:
    if mode == "add":
        return dst.at[idx].add(vals)
    f = dst.shape[0]
    keep = _store_keep(keep, idx, f)
    # route dropped writes out of range; drop-mode scatter discards them
    safe_idx = jnp.where(keep, idx, f)
    return dst.at[safe_idx].set(vals, mode="drop")


def scatter_onehot(dst: jax.Array, idx: jax.Array, vals: jax.Array,
                   mode: str = "store", keep: jax.Array | None = None
                   ) -> jax.Array:
    f = dst.shape[0]
    if f > _ONEHOT_MAX_FOOTPRINT:
        raise ValueError(f"onehot backend: footprint {f} too large")
    if mode == "add":
        # HIGHEST: the chip's DEFAULT f32 dot is one bf16 pass
        oh = jax.nn.one_hot(idx, f, dtype=vals.dtype)      # (N, F)
        return dst + jnp.matmul(oh.T, vals, precision=lax.Precision.HIGHEST)
    keep = _store_keep(keep, idx, f)
    # dropped lanes go out of range: an all-zero one-hot row
    oh = jax.nn.one_hot(jnp.where(keep, idx, f), f, dtype=jnp.bfloat16)
    covered = jnp.any(oh > 0, axis=0)[:, None]               # (F, 1)
    return jnp.where(covered, _select_rows(oh.T, vals), dst)


def scatter_scalar(dst: jax.Array, idx: jax.Array, vals: jax.Array,
                   mode: str = "store", keep: jax.Array | None = None
                   ) -> jax.Array:
    # sequential loop: store order IS last-write-wins; no mask needed
    del keep
    n = idx.shape[0]
    r = dst.shape[1]

    def body(i, dst):
        row = lax.dynamic_slice(vals, (i, 0), (1, r))
        if mode == "add":
            cur = lax.dynamic_slice(dst, (idx[i], 0), (1, r))
            row = row + cur
        return lax.dynamic_update_slice(dst, row, (idx[i], 0))

    return lax.fori_loop(0, n, body, dst)


def scatter_pallas(dst: jax.Array, idx: jax.Array, vals: jax.Array,
                   mode: str = "store", keep: jax.Array | None = None
                   ) -> jax.Array:
    from repro.kernels.scatter_rows import ops as scatter_ops
    if mode == "add":
        return dst + scatter_ops.scatter_add_rows(idx, vals, dst.shape[0])
    # store: one single-pass kernel launch; dropped lanes are routed out of
    # range so the kernel's one-hot never matches them
    keep = _store_keep(keep, idx, dst.shape[0])
    safe_idx = jnp.where(keep, idx, jnp.iinfo(jnp.int32).max)
    return scatter_ops.scatter_store_rows(dst, safe_idx, vals)


# ---------------------------------------------------------------------------
# Dispatch tables
# ---------------------------------------------------------------------------

GATHER_FNS: dict[str, Callable] = {
    "xla": gather_xla,
    "onehot": gather_onehot,
    "scalar": gather_scalar,
    "pallas": gather_pallas,
}

SCATTER_FNS: dict[str, Callable] = {
    "xla": scatter_xla,
    "onehot": scatter_onehot,
    "scalar": scatter_scalar,
    "pallas": scatter_pallas,
}


def gather(src: jax.Array, idx: jax.Array, *, backend: str = "xla") -> jax.Array:
    return GATHER_FNS[backend](src, idx)


def scatter(dst: jax.Array, idx: jax.Array, vals: jax.Array, *,
            mode: str = "store", backend: str = "xla",
            keep: jax.Array | None = None) -> jax.Array:
    return SCATTER_FNS[backend](dst, idx, vals, mode, keep)


# ---------------------------------------------------------------------------
# Batched dispatch (suite planner, core/plan.py): one launch runs a whole
# shape bucket of patterns.  Leading dim is the pattern-batch dim.  The
# pallas backend gets batch-native kernels — a real grid over
# (pattern-batch x tiles) with the index buffers scalar-prefetched once —
# instead of jax.vmap over a per-pattern pallas_call.
# ---------------------------------------------------------------------------

def gather_batched(src: jax.Array, idx: jax.Array, *,
                   backend: str = "xla") -> jax.Array:
    """src: (B, F, R), idx: (B, N) -> (B, N, R); one launch for B patterns."""
    if backend == "pallas":
        from repro.kernels.gather_rows import ops as gather_ops
        return gather_ops.gather_rows_batched(src, idx)
    return jax.vmap(lambda s, i: gather(s, i, backend=backend))(src, idx)


def scatter_batched(dst: jax.Array, idx: jax.Array, vals: jax.Array, *,
                    mode: str = "store", backend: str = "xla",
                    keep: jax.Array | None = None) -> jax.Array:
    """dst: (B, F, R), idx: (B, N), vals: (B, N, R) -> (B, F, R).

    ``keep`` is the (B, N) host-precomputed last-write-wins mask for store
    mode (plan._assemble_bucket computes it over the padded index buffer);
    without it each pattern falls back to per-row resolution.
    """
    if backend == "pallas":
        from repro.kernels.scatter_rows import ops as scatter_ops
        if mode == "add":
            return dst + scatter_ops.scatter_add_rows_batched(
                idx, vals, dst.shape[1])
        if keep is None:
            keep = jax.vmap(
                lambda i: _store_keep(None, i, dst.shape[1]))(idx)
        safe_idx = jnp.where(keep, idx, jnp.iinfo(jnp.int32).max)
        return scatter_ops.scatter_store_rows_batched(dst, safe_idx, vals)
    if mode == "add":
        return jax.vmap(
            lambda d, i, v: scatter(d, i, v, mode="add", backend=backend)
        )(dst, idx, vals)
    if keep is None:
        keep = jax.vmap(lambda i: _store_keep(None, i, dst.shape[1]))(idx)
    return jax.vmap(
        lambda d, i, v, k: scatter(d, i, v, mode="store", backend=backend,
                                   keep=k)
    )(dst, idx, vals, keep)
