"""Compile the main path's kernels and executables for a TPU v5e.

Nothing here runs on a chip: the TPU compiler, which ships with jaxlib,
compiles for a described ``v5e:2x2`` topology.  That catches what the
Pallas interpreter cannot — Mosaic's tiling and SMEM rules, VMEM and HBM
capacity, kernels that cannot be partitioned — at about a second per
compile.  The topology is described inside a fixture (never at import),
and every test skips when it cannot be described.

The kernels pick interpret mode from ``jax.default_backend()``, which is
the CPU here; the ``mosaic`` fixture steers them to the compiled path for
these tests only.  The persistent compilation cache is off around the
compiles: entries written for a described chip cannot be read back.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.core import SuitePlan, appdb
from repro.core import plan as plan_mod
from repro.core.compat import make_mesh
from repro.kernels.gather_rows import ops as gops
from repro.kernels.scatter_rows import ops as sops

HBM_BYTES = 16 * 2**30           # one v5e chip
B_NAMES = ("LULESH-G7", "LULESH-G0", "LULESH-S1", "LULESH-S3")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mosaic(topo):
    """Compiled Pallas kernels, no persistent cache, for this module."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        for ops in (gops, sops):
            mp.setattr(ops, "_should_interpret",
                       lambda i: False if i is None else i)
        yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *avals):
    return jax.jit(fn).lower(*avals).compile()


def _mosaic_text(compiled) -> str:
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "kernel compiled in interpret mode"
    return text


# (rows, width, mode): tables in VMEM and in HBM, at width 1 and 128
GATHER_CASES = [(4096, 1, "vmem"), (1 << 22, 1, "dma"),
                (1000, 128, "vmem"), (1 << 16, 128, "dma")]


@pytest.mark.parametrize("v,d,mode", GATHER_CASES)
def test_gather_compiles(mosaic, one_chip, v, d, mode):
    table = _sds((2, v, d), jnp.float32, one_chip)
    idx = _sds((2, 3000), jnp.int32, one_chip)
    c = _compile(lambda t, i: gops.gather_rows_batched(t, i, mode=mode),
                 table, idx)
    _mosaic_text(c)


def test_gather_auto_regime_from_tiled_bytes(mosaic, one_chip):
    # 2^20 float32 rows of width 1 are 4 MiB logically but 512 MiB once
    # each row pads to 128 lanes; the lane-dense layout keeps them at
    # 4 MiB, so auto mode may stage them in VMEM and still compile
    table = _sds((2, 1 << 20, 1), jnp.float32, one_chip)
    idx = _sds((2, 4096), jnp.int32, one_chip)
    c = _compile(lambda t, i: gops.gather_rows_batched(t, i), table, idx)
    _mosaic_text(c)
    assert c.memory_analysis().temp_size_in_bytes < 64 * 2**20


@pytest.mark.parametrize("d", [1, 128])
@pytest.mark.parametrize("op", ["add", "store", "store_covered"])
def test_scatter_compiles(mosaic, one_chip, op, d):
    v, n = 4097, 3000
    idx = _sds((2, n), jnp.int32, one_chip)
    vals = _sds((2, n, d), jnp.float32, one_chip)
    dst = _sds((2, v, d), jnp.float32, one_chip)
    if op == "add":
        c = _compile(lambda i, x: sops.scatter_add_rows_batched(i, x, v),
                     idx, vals)
    else:
        cov = op == "store_covered"
        c = _compile(lambda t, i, x: sops.scatter_store_rows_batched(
            t, i, x, with_covered=cov), dst, idx, vals)
    _mosaic_text(c)


def _paper_plan(names=B_NAMES):
    pats = [p for p in appdb.scale_counts(appdb.ALL_PATTERNS, 1.0)
            if p.name in names]
    return SuitePlan.build(pats)


def test_xla_buckets_at_paper_scale_fit_hbm(mosaic, one_chip):
    # request B of chip_smoke.py: Table 5 LULESH proxies, 2^25 lanes each
    for key, builder, avals in plan_mod.enumerate_executables(
            _paper_plan(), backend="xla", mode="store"):
        avals = tuple(_sds(a.shape, a.dtype, one_chip) for a in avals)
        ma = builder().lower(*avals).compile().memory_analysis()
        total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                 + ma.temp_size_in_bytes)
        assert total < HBM_BYTES, (key, total)


@pytest.mark.parametrize("shape", [(1, 4), (4, 1)])
def test_placed_pallas_runs_the_kernel_per_shard(mosaic, topo, shape):
    # a lane split and a batch split on the 2x2 chip mesh: each device
    # runs the Mosaic kernel on its shard, and the table is never
    # all-gathered
    b, l = shape
    if l == 1:
        pl = plan_mod.Placement(make_mesh((b,), ("data",),
                                          devices=topo.devices),
                                batch_axis="data", lane_axis=None)
    else:
        pl = plan_mod.Placement(make_mesh((l,), ("lane",),
                                          devices=topo.devices),
                                batch_axis=None, lane_axis="lane")
    plan = _paper_plan(("LULESH-G7", "LULESH-S3"))
    for key, builder, avals in plan_mod.enumerate_executables(
            plan, backend="pallas", mode="store", placement=pl):
        in_sh, _ = pl.shardings(key.kind)
        avals = tuple(_sds(a.shape, a.dtype, s)
                      for a, s in zip(avals, in_sh))
        compiled = builder().lower(*avals).compile()
        text = _mosaic_text(compiled)
        assert "all-gather" not in text, key
        devs = {d for s in compiled.input_shardings[0] for d in s.device_set}
        assert len(devs) == 4, key


def test_named_sharding_helper_is_auto(topo):
    mesh = make_mesh((2, 2), ("data", "lane"), devices=topo.devices)
    assert all(t == jax.sharding.AxisType.Auto for t in mesh.axis_types)
    NamedSharding(mesh, P("data"))            # builds under Auto axes
