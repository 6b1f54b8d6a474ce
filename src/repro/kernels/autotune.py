"""Deterministic per-bucket-shape tile autotuner for the Pallas kernels.

The G/S kernels used to run every bucket shape with one hardcoded tiling
(``block_n=64`` gather / ``128x128`` scatter).  That is the paper's §3.2
lesson in reverse: gather/scatter throughput is a function of how the
access geometry maps onto the memory hierarchy, so a 4Ki-lane bucket and
a 64-lane bucket should not share a tile.  This module picks the tiling
per *kernel-visible geometry* (a ``TileKey``) with a small, fully
deterministic search:

  * Candidates are powers of two bracketed by the geometry itself (a
    block never exceeds the padded dim it tiles).
  * Each candidate is scored with a closed-form cost model — grid steps
    times per-step work plus a per-step launch overhead — instead of
    wall-clock probes.  Interpret mode (CPU) weighs the per-step
    interpreter overhead very heavily, so the search collapses the grid
    to as few steps as the block caps allow; compiled TPU mode weighs
    VMEM residency and MXU-shaped tiles instead.
  * Ties break toward the FIRST candidate in ascending enumeration
    order, so the choice is reproducible across processes and platforms
    by construction (no timing, no RNG, no dict-order dependence).

Choices are memoized process-wide and can be exported/seeded in a wire
format (``to_wire``/``seed_wire``): ``DiskTier`` persists the choices
recorded while serializing an executable and re-seeds them on restore,
so a warm restart never re-runs the search (``stats()["searched"]``
stays 0).  The choice is a pure function of the TileKey — it must NOT
enter ``ExecKey`` (one key still holds exactly one trace, and
``ExecutorCache.misses`` stays an exact compile count; pinned by the
key-purity lint).

``disabled()`` restores the legacy fixed tiles — the benchmark's
before/after section runs under it to measure what the search buys.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading


@dataclasses.dataclass(frozen=True)
class TileKey:
    """Kernel-visible geometry a tile choice is keyed on.

    Shapes are what the kernel actually sees at trace time — under a
    lane-sharded ``shard_map`` launch these are the per-device LOCAL
    shard dims, so an 8-way lane split of a 4Ki-lane bucket tunes for
    512 lanes, not 4Ki.
    """
    op: str             # "gather" | "scatter"
    batch: int          # pattern-batch dim
    lanes: int          # index lanes per pattern (pre block padding)
    rows: int           # table / destination rows (incl. scratch row)
    width: int          # row width D
    dtype: str
    platform: str       # "interpret" | "tpu"


@dataclasses.dataclass(frozen=True)
class TileChoice:
    """A concrete tiling; fields irrelevant to the op stay 0."""
    block: int = 0      # indices per grid step (gather) / chunk (scatter)
    block_v: int = 0    # scatter output-tile rows (lines when narrow)

    def to_wire(self) -> list[int]:
        return [self.block, self.block_v]

    @staticmethod
    def from_wire(v) -> "TileChoice":
        block, block_v = (int(x) for x in v)
        return TileChoice(block=block, block_v=block_v)


# Index blocks are multiples of this: the SMEM index block's tiling, and
# 8 sublanes x 128 lanes for the lane-dense VMEM index block.
INDEX_BLOCK = 1024
_LANES = 128

# legacy fixed tiles — what the kernels shipped with before the search
LEGACY = {
    "gather": TileChoice(block=INDEX_BLOCK),
    "scatter": TileChoice(block=INDEX_BLOCK, block_v=128),
}

# cost-model constants.  STEP_OVH is the per-grid-step launch overhead in
# "element-work" units: interpret mode executes each grid step as a
# Python-level interpreter iteration, so its overhead dwarfs the per-
# element work and the search minimizes step count; compiled TPU steps
# are cheap, so tile shape (VMEM fit, MXU occupancy) dominates instead.
_STEP_OVH = {"interpret": 1 << 17, "tpu": 64}
# per-candidate feasibility caps, TILED bytes of tile-resident data
_TILE_BYTES_CAP = {"interpret": 1 << 26, "tpu": 1 << 21}
# one-hot membership matrix cap (block_v * 128 elements per slice)
_ONEHOT_CAP = {"interpret": 1 << 22, "tpu": 1 << 15}
_MAX_BLOCK = 4096
# SMEM holds the index block double-buffered: 8 KiB indices is 64 KiB
_MAX_INDEX_BLOCK = 8192


def _pow2s(lo: int, hi: int):
    """Powers of two in [lo, hi], ascending (hi included via bracketing)."""
    out = []
    b = lo
    while b <= hi:
        out.append(b)
        b *= 2
    return out


def _next_pow2(n: int) -> int:
    return 1 << (max(1, n) - 1).bit_length()


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def _itemsize(dtype: str) -> int:
    import numpy as np
    return int(np.dtype(dtype).itemsize)


def tiled_bytes(rows: int, width: int, dtype: str) -> int:
    """Bytes a (rows, width) array occupies in the chip's tiled layout:
    the last dim pads to 128 lanes and the one before it to a sublane
    tile (8 rows of 32-bit; packed dtypes pack more rows per tile)."""
    item = _itemsize(dtype)
    sub = 8 * max(1, 4 // item)
    return (_ceil_div(max(1, rows), sub) * sub
            * _ceil_div(max(1, width), _LANES) * _LANES * item)


def _gather_fetches(key: TileKey) -> int:
    """Lines or elements the gather kernel fetches per pattern: one per
    element below a line's width, one per line at or above it."""
    per_row = (key.width if key.width < _LANES
               else _ceil_div(key.width, _LANES))
    return max(1, key.lanes * per_row)


def _search_gather(key: TileKey) -> TileChoice:
    """Indices per grid step for either gather regime.  On the chip a
    step holds three blocks of ``block`` 128-lane lines (the staging
    block and the double-buffered output, 512 B per index): 1024
    indices take 1.5 MiB and 2048 would exceed the tile cap, so one index
    block is the only fit.  Interpret mode trades steps for block size."""
    if key.platform == "tpu":
        return TileChoice(block=INDEX_BLOCK)
    ovh = _STEP_OVH[key.platform]
    fetches = _gather_fetches(key)
    hi = min(_MAX_INDEX_BLOCK, _next_pow2(fetches))
    best, best_cost = INDEX_BLOCK, None
    for bn in _pow2s(INDEX_BLOCK, max(INDEX_BLOCK, hi)):
        cost = key.batch * _ceil_div(fetches, bn) * (ovh + bn)
        if best_cost is None or cost < best_cost:
            best, best_cost = bn, cost
    return TileChoice(block=best)


def _search_scatter(key: TileKey) -> TileChoice:
    ovh = _STEP_OVH[key.platform]
    onehot_cap = _ONEHOT_CAP[key.platform]
    cap = _TILE_BYTES_CAP[key.platform]
    narrow = key.width < _LANES
    # the kernel's geometry: output lines (narrow) or rows, and lanes
    rows = (_ceil_div(key.rows * key.width, _LANES) if narrow else key.rows)
    width = _LANES if narrow else _ceil_div(key.width, _LANES) * _LANES
    lanes = key.lanes * (key.width if narrow else 1)
    hi_v = min(_MAX_BLOCK, _next_pow2(rows))
    hi_n = min(_MAX_BLOCK, _next_pow2(lanes))
    best, best_cost = None, None
    for bv in _pow2s(8, max(8, hi_v)):
        if bv * _LANES > onehot_cap:
            continue
        for bn in _pow2s(INDEX_BLOCK, max(INDEX_BLOCK, hi_n)):
            # residency: out + dst tiles and the staged idx/vals chunks,
            # all double-buffered, plus the per-slice one-hot
            vals_b = (tiled_bytes(bn // _LANES, _LANES, key.dtype) if narrow
                      else tiled_bytes(bn, width, key.dtype))
            tile_b = (4 * tiled_bytes(bv, width, key.dtype)
                      + 2 * (vals_b + tiled_bytes(bn // _LANES, _LANES,
                                                  "int32"))
                      + tiled_bytes(bv, _LANES, key.dtype))
            if tile_b > cap:
                continue
            steps = (key.batch * _ceil_div(max(1, rows), bv)
                     * _ceil_div(max(1, lanes), bn))
            work = bv * bn + (bv + bn) * width
            cost = steps * (ovh + work)
            if best_cost is None or cost < best_cost:
                best, best_cost = (bv, bn), cost
    if best is None:
        return TileChoice(block=INDEX_BLOCK, block_v=8)
    return TileChoice(block=best[1], block_v=best[0])


_SEARCHERS = {
    "gather": _search_gather,
    "scatter": _search_scatter,
}

_LOCK = threading.Lock()
_MEMO: dict[TileKey, TileChoice] = {}
_STATS = {"searched": 0, "hits": 0, "seeded": 0}
_DISABLED = 0
_RECORDERS: list[dict] = []


def choose(key: TileKey) -> TileChoice:
    """The tile choice for ``key``: memo hit, or one deterministic search.

    Under ``disabled()`` returns the legacy fixed tiles without touching
    the memo (the before/after benchmark's "before" leg).
    """
    if key.op not in _SEARCHERS:
        raise ValueError(f"unknown autotune op {key.op!r}")
    if _DISABLED:
        return LEGACY[key.op]
    with _LOCK:
        choice = _MEMO.get(key)
        if choice is None:
            choice = _SEARCHERS[key.op](key)
            _MEMO[key] = choice
            _STATS["searched"] += 1
        else:
            _STATS["hits"] += 1
        for rec in _RECORDERS:
            rec[key] = choice
        return choice


def lookup(key: TileKey) -> TileChoice | None:
    with _LOCK:
        return _MEMO.get(key)


def stats() -> dict:
    with _LOCK:
        return dict(_STATS)


def reset() -> None:
    """Drop the memo and zero the counters (tests only)."""
    with _LOCK:
        _MEMO.clear()
        for k in _STATS:
            _STATS[k] = 0


@contextlib.contextmanager
def disabled():
    """Serve the legacy fixed tiles for the duration of the block."""
    global _DISABLED
    with _LOCK:
        _DISABLED += 1
    try:
        yield
    finally:
        with _LOCK:
            _DISABLED -= 1


@contextlib.contextmanager
def recording():
    """Collect every choice served inside the block: ``{TileKey: choice}``.

    ``DiskTier.store`` wraps executable serialization with this — tracing
    the executable calls ``choose`` for exactly the tiles it bakes in, so
    the recorded dict is precisely what the disk entry must re-seed.
    """
    rec: dict[TileKey, TileChoice] = {}
    with _LOCK:
        _RECORDERS.append(rec)
    try:
        yield rec
    finally:
        with _LOCK:
            # by identity, not ==: nested recorders (DiskTier.store inside
            # a benchmark's recording block) can hold equal dicts
            for i, r in enumerate(_RECORDERS):
                if r is rec:
                    del _RECORDERS[i]
                    break


# -- wire format (DiskTier header) ------------------------------------------

def _key_to_wire(key: TileKey) -> str:
    return (f"{key.op}:{key.batch}:{key.lanes}:{key.rows}:{key.width}:"
            f"{key.dtype}:{key.platform}")


def _key_from_wire(s: str) -> TileKey:
    op, batch, lanes, rows, width, dtype, platform = s.split(":")
    if op not in _SEARCHERS:
        raise ValueError(f"unknown autotune op {op!r}")
    return TileKey(op=op, batch=int(batch), lanes=int(lanes), rows=int(rows),
                   width=int(width), dtype=dtype, platform=platform)


def to_wire(entries: dict) -> dict:
    """``{TileKey: TileChoice}`` -> JSON-safe ``{str: [int, ...]}``."""
    return {_key_to_wire(k): v.to_wire() for k, v in sorted(
        entries.items(), key=lambda kv: _key_to_wire(kv[0]))}


def seed_wire(wire: dict | None) -> int:
    """Seed the memo from a wire dict (disk restore); returns entries
    adopted.  Existing memo entries win — a live search result and a
    disk header can only disagree if the model changed, and the running
    process's own choice is the one its traces bake in."""
    if not wire:
        return 0
    n = 0
    with _LOCK:
        for ks, v in wire.items():
            try:
                key = _key_from_wire(ks)
                choice = TileChoice.from_wire(v)
            except (ValueError, TypeError):
                continue
            if key not in _MEMO:
                _MEMO[key] = choice
                _STATS["seeded"] += 1
                n += 1
    return n
