"""GSEngine — pattern -> executable gather/scatter, with paper-style timing.

The engine materializes a Pattern's absolute indices, builds the requested
backend's jitted callable, and times it the way the paper does: minimum
over K runs (§3.5), reporting the paper's useful-bytes bandwidth alongside
the modeled v5e number (bandwidth.py).

Sharding: the count dimension is the paper's OpenMP-thread / CUDA-block
dimension; ``sharded()`` splits it over a mesh axis with shard_map, each
shard gathering into its own output block (no false sharing by
construction — paper §3.1's per-thread dst buffers).
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from . import backends as B
from . import bandwidth as bw
from .pattern import Pattern


def gs_shardings(mesh: Mesh, axis: str, kind: str, *, batched: bool = False):
    """(in_shardings, out_sharding) for a gather/scatter executable.

    Compat shim over the placement layer (``plan.Placement`` /
    ``runtime.sharding.gs_specs`` — DESIGN.md §11), which owns the axis
    rules for every sharded path.  ``axis`` plays the 1-D role the
    pre-placement code gave it: the pattern-batch axis when ``batched``
    (each device runs whole patterns), the lane axis otherwise (the
    paper's OpenMP-thread split ``GSEngine.sharded`` uses).

    Scatter executables take four operands (dst, idx, vals, keep): the
    host-precomputed last-write-wins keep mask rides with the indices.
    """
    from .plan import Placement
    if batched:
        placement = Placement(mesh, batch_axis=axis, lane_axis=None)
    else:
        placement = Placement(mesh, batch_axis=None, lane_axis=axis)
    return placement.shardings(kind, batched=batched)


def make_host_buffers(pattern: Pattern, row_width: int, seed: int = 0):
    """Host-side buffers for one pattern: (src, abs_idx, vals, keep).

    ``src`` is the (footprint, row_width) float32 table, ``abs_idx`` the
    flattened (count*index_len,) int32 absolute indices, ``vals`` the
    scatter payload (None for gathers), and ``keep`` the last-write-wins
    keep mask over ``abs_idx`` (None for gathers).  The mask is computed
    HERE, on the host, in closed form from the pattern
    (``Pattern.keep_last_mask``, no sort) — static-index preprocessing
    never enters a timed executable (paper §3.5).  GSEngine builds its device
    buffers from this function and the suite planner (plan.py) from the
    same draws (``draw_host_buffers``) and mask, so batched and
    per-pattern execution see bit-identical inputs.
    """
    src, abs_idx, vals = draw_host_buffers(pattern, row_width, seed)
    if pattern.kind == "gather":
        return src, abs_idx, None, None
    return src, abs_idx, vals, pattern.keep_last_mask()


def draw_host_buffers(pattern: Pattern, row_width: int, seed: int = 0):
    """``make_host_buffers`` without the keep mask: (src, abs_idx, vals),
    the seeded draws and the absolute indices.  The planner computes the
    mask apart (store mode only), under its own span."""
    rng = np.random.default_rng(seed)
    f = pattern.footprint()
    abs_idx = pattern.absolute_indices().reshape(-1)
    src = rng.standard_normal((f, row_width), dtype=np.float32)
    if pattern.kind == "gather":
        return src, abs_idx, None
    vals = rng.standard_normal((abs_idx.shape[0], row_width),
                               dtype=np.float32)
    return src, abs_idx, vals


@dataclasses.dataclass(frozen=True)
class RunResult:
    pattern: Pattern
    backend: str
    elem_bytes: int
    row_width: int
    runs: int
    time_s: float                 # min over runs (paper §3.5)
    measured_gbs: float           # paper formula over measured CPU time
    modeled_gbs: float            # paper formula over modeled v5e time
    tile_efficiency: float
    out_digest: str | None = None   # sha256 of the computed output
                                    # (run_plan(digest=True)); timing-free,
                                    # so it is the bit-identity witness for
                                    # repeated serving requests

    def row(self) -> dict:
        return {
            "name": self.pattern.name,
            "kind": self.pattern.kind,
            "type": self.pattern.classify(),
            "backend": self.backend,
            "delta": self.pattern.delta,
            "idx_len": self.pattern.index_len,
            "count": self.pattern.count,
            "time_s": self.time_s,
            "measured_cpu_gbs": self.measured_gbs,
            "modeled_v5e_gbs": self.modeled_gbs,
            "tile_eff": self.tile_efficiency,
            "digest": self.out_digest,
        }


SCATTER_MODES = ("store", "add")


class GSEngine:
    """Executable form of one Spatter pattern.

    ``mode`` selects the scatter write semantics ("store" last-write-wins,
    the paper's default, or "add" accumulation); gathers ignore it.
    """

    def __init__(self, pattern: Pattern, *, backend: str = "xla",
                 dtype=jnp.float32, row_width: int = 1, seed: int = 0,
                 mode: str = "store"):
        if backend not in B.BACKENDS:
            raise ValueError(f"unknown backend {backend!r}")
        if mode not in SCATTER_MODES:
            raise ValueError(f"unknown mode {mode!r}; "
                             f"expected one of {SCATTER_MODES}")
        self.pattern = pattern
        self.backend = backend
        self.dtype = jnp.dtype(dtype)
        self.row_width = row_width
        self.mode = mode
        self._seed = seed
        self._abs_idx = pattern.absolute_indices().reshape(-1)   # (count*L,)
        self._built = None

    # -- buffers -------------------------------------------------------------
    @property
    def elem_bytes(self) -> int:
        return self.dtype.itemsize * self.row_width

    def footprint_shape(self) -> tuple[int, int]:
        return (self.pattern.footprint(), self.row_width)

    def make_buffers(self):
        """Device operands: (src, idx, None, None) for gathers,
        (None, idx, vals, keep) for scatters.  The scatter dst is NOT
        allocated here — the executable donates it, so ``build()`` hands
        out a fresh zero dst per call; materializing one here too would
        be a dead device allocation the size of the footprint."""
        host_src, host_idx, host_vals, host_keep = make_host_buffers(
            self.pattern, self.row_width, seed=self._seed)
        idx = jnp.asarray(host_idx, jnp.int32)
        if self.pattern.kind == "gather":
            return jnp.asarray(host_src, self.dtype), idx, None, None
        vals = jnp.asarray(host_vals, self.dtype)
        return None, idx, vals, jnp.asarray(host_keep)

    # -- executables ---------------------------------------------------------
    def build(self):
        """Returns (fn, args) where fn(*args) performs the whole pattern.

        Scatter args carry the host-precomputed keep mask as a regular
        operand: the jitted hot path contains only the access itself.

        The scatter executable DONATES its dst operand (argnum 0), so a
        scatter's args are single-use: every ``build()`` call hands out a
        fresh zero dst, and only the executable plus the non-donated
        operands are cached.  Caching the dst itself made the second
        ``run()`` (or ``sharded()`` after ``run()``) die with "buffer has
        been deleted or donated" — the repeated-execution regime the
        serving layer depends on.
        """
        if self._built is None:
            backend, mode = self.backend, self.mode
            if self.pattern.kind == "gather":
                src, idx, _, _ = self.make_buffers()

                @jax.jit
                def fn(src, idx):
                    return B.gather(src, idx, backend=backend)

                self._built = (fn, (src, idx))
            else:
                _, idx, vals, keep = self.make_buffers()

                @partial(jax.jit, donate_argnums=(0,))
                def fn(dst, idx, vals, keep):
                    return B.scatter(dst, idx, vals, mode=mode,
                                     backend=backend, keep=keep)

                self._built = (fn, (idx, vals, keep))
        fn, args = self._built
        if self.pattern.kind == "scatter":
            args = (jnp.zeros(self.footprint_shape(), self.dtype),) + args
        return fn, args

    def sharded(self, mesh, axis: str = "data"):
        """Shard the count dimension over ``axis`` (paper's thread dim).

        The lane-only degenerate form of the placement layer: ``mesh``
        may be a raw ``Mesh`` (its ``axis`` becomes the lane axis) or a
        lane-only ``plan.Placement``; batch-sharded placements belong to
        the suite planner (a single pattern has no batch dim).
        """
        from .plan import Placement
        fn, args = self.build()
        if isinstance(mesh, Placement):
            placement = mesh
            if placement.batch_axis is not None:
                raise ValueError(
                    "GSEngine.sharded is per-pattern: the placement must "
                    f"be lane-only, got {placement.placement}")
        else:
            placement = Placement(mesh, batch_axis=None, lane_axis=axis)
        n_shards = placement.lane_shards
        total = self._abs_idx.shape[0]
        if total % n_shards:
            raise ValueError(f"count*index_len={total} not divisible by "
                             f"{n_shards} shards")
        in_shardings, out_shardings = placement.shardings(
            self.pattern.kind, batched=False)
        backend, mode = self.backend, self.mode
        if self.pattern.kind == "gather":
            def raw(src, idx):
                return B.gather(src, idx, backend=backend)
        else:
            # mode must match build()'s: a fixed "add" here made sharded and
            # unsharded runs disagree whenever a pattern writes an index twice
            def raw(dst, idx, vals, keep):
                return B.scatter(dst, idx, vals, mode=mode,
                                 backend=backend, keep=keep)
        sharded_fn = jax.jit(raw, in_shardings=in_shardings,
                             out_shardings=out_shardings)
        return sharded_fn, args

    # -- paper-style timing ---------------------------------------------------
    def run(self, runs: int = 10) -> RunResult:
        fn, args = self.build()
        if self.pattern.kind == "scatter":
            # donation consumes dst; rebuild per run
            dst, idx, vals, keep = args
            out = fn(dst, idx, vals, keep)
            jax.block_until_ready(out)          # compile & warm
            times = []
            for _ in range(runs):
                d = jnp.zeros_like(out)
                jax.block_until_ready(d)
                t0 = time.perf_counter()
                out = fn(d, idx, vals, keep)
                jax.block_until_ready(out)
                times.append(time.perf_counter() - t0)
        else:
            out = fn(*args)
            jax.block_until_ready(out)
            times = []
            for _ in range(runs):
                t0 = time.perf_counter()
                out = fn(*args)
                jax.block_until_ready(out)
                times.append(time.perf_counter() - t0)
        t = min(times)                           # paper §3.5: min of K
        tm = bw.tpu_tile_model(self.pattern, self.elem_bytes)
        return RunResult(
            pattern=self.pattern, backend=self.backend,
            elem_bytes=self.elem_bytes, row_width=self.row_width,
            runs=runs, time_s=t,
            measured_gbs=bw.paper_bandwidth(self.pattern, t,
                                            self.elem_bytes) / 1e9,
            modeled_gbs=tm.modeled_gbs,
            tile_efficiency=tm.tile_efficiency,
        )
