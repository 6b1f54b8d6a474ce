"""Sharding-rule resolution + HLO roofline parser tests (single device),
plus a subprocess mini-dryrun on 8 fake devices."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core.compat import make_abstract_mesh
from repro.launch.roofline import (Collective, analyze_module,
                                   parse_computations, _shape_bytes)

REPO = os.path.join(os.path.dirname(__file__), "..")


# ---------------------------------------------------------------------------
# logical rules
# ---------------------------------------------------------------------------

class TestLogicalSpec:
    def _mesh(self):
        # fake mesh objects need real devices; use a 1-device mesh with
        # axis sizes read from shape, so build an abstract mesh instead
        return make_abstract_mesh((16, 16), ("data", "model"))

    def test_divisible(self):
        from repro.runtime.sharding import logical_to_spec
        spec = logical_to_spec(("vocab", "embed"), (128256, 4096),
                               self._mesh())
        assert spec == P("model", None)

    def test_divisibility_fallback(self):
        from repro.runtime.sharding import logical_to_spec
        # kv_heads=2 cannot shard over model=16 -> replicated
        spec = logical_to_spec(("embed", "kv_heads", "head_dim"),
                               (4096, 2, 128), self._mesh())
        assert spec == P(None, None, None)

    def test_batch_multi_axis(self):
        from repro.runtime.sharding import logical_to_spec
        mesh3 = make_abstract_mesh((2, 16, 16), ("pod", "data", "model"))
        spec = logical_to_spec(("batch", "seq", "embed"), (256, 4096, 4096),
                               mesh3)
        assert spec[0] == ("pod", "data")

    def test_no_axis_reuse(self):
        from repro.runtime.sharding import logical_to_spec
        spec = logical_to_spec(("heads", "mlp"), (32, 128), self._mesh())
        used = [s for s in spec if s]
        assert len(used) == 1          # "model" used once only


class TestZero1:
    def test_moments_fully_sharded(self):
        from repro.runtime.train import zero1_shardings
        mesh = make_abstract_mesh((16, 16), ("data", "model"))
        axes = {"w": ("layers", "experts", "embed", "expert_mlp")}
        avals = {"w": jax.ShapeDtypeStruct((60, 384, 7168, 2048),
                                           jnp.float32)}
        sh = zero1_shardings(axes, avals, mesh)
        spec = sh["w"].spec
        assert spec[1] == "model"       # experts keep their axis
        assert "data" in spec           # + ZeRO over data on a divisible dim


# ---------------------------------------------------------------------------
# HLO parser
# ---------------------------------------------------------------------------

HLO_SAMPLE = textwrap.dedent("""\
    HloModule jit_f, entry_computation_layout={()->f32[]}

    %body (p: (s32[], f32[64,64])) -> (s32[], f32[64,64]) {
      %p = (s32[], f32[64,64]{1,0}) parameter(0)
      %i = s32[] get-tuple-element(%p), index=0
      %x = f32[64,64]{1,0} get-tuple-element(%p), index=1
      %dot.1 = f32[64,64]{1,0} dot(%x, %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}
      %ar = f32[64,64]{1,0} all-reduce(%dot.1), replica_groups={{0,1,2,3}}, to_apply=%add
      %one = s32[] constant(1)
      %i2 = s32[] add(%i, %one)
      ROOT %t = (s32[], f32[64,64]{1,0}) tuple(%i2, %ar)
    }

    %cond (p: (s32[], f32[64,64])) -> pred[] {
      %p.1 = (s32[], f32[64,64]{1,0}) parameter(0)
      %i.1 = s32[] get-tuple-element(%p.1), index=0
      %n = s32[] constant(12)
      ROOT %lt = pred[] compare(%i.1, %n), direction=LT
    }

    ENTRY %main () -> f32[] {
      %c0 = s32[] constant(0)
      %x0 = f32[64,64]{1,0} constant(0)
      %init = (s32[], f32[64,64]{1,0}) tuple(%c0, %x0)
      %w = (s32[], f32[64,64]{1,0}) while(%init), condition=%cond, body=%body
      %xf = f32[64,64]{1,0} get-tuple-element(%w), index=1
      ROOT %s = f32[] reduce(%xf, %c0), dimensions={0,1}, to_apply=%add
    }
    """)


class TestHloParser:
    def test_shape_bytes(self):
        assert _shape_bytes("f32[64,64]{1,0}") == 64 * 64 * 4
        assert _shape_bytes("(s32[], f32[8,2]{1,0})") == 4 + 64
        assert _shape_bytes("bf16[10]") == 20

    def test_while_trip_multiplier(self):
        cost = analyze_module(HLO_SAMPLE, world=4)
        # dot: 2*64*64*64 flops, x12 trips from the cond constant
        assert cost.flops == 12 * 2 * 64 ** 3
        # one all-reduce per trip
        ar = [c for c in cost.collectives if c.kind == "all-reduce"]
        assert len(ar) == 1 and ar[0].count == 12 and ar[0].group_size == 4

    def test_ring_factors(self):
        c = Collective("all-reduce", 1000, 4, 1)
        assert np.isclose(c.ring_bytes(), 2 * 1000 * 3 / 4)
        c = Collective("all-gather", 1000, 4, 2)
        assert np.isclose(c.ring_bytes(), 2 * 1000 * 3 / 4)
        c = Collective("reduce-scatter", 250, 4, 1)
        assert np.isclose(c.ring_bytes(), 250 * 3)

    def test_backend_config_trip_count(self):
        hlo = HLO_SAMPLE.replace(
            "condition=%cond, body=%body",
            'condition=%cond, body=%body, backend_config='
            '{"known_trip_count":{"n":"99"}}')
        cost = analyze_module(hlo, world=4)
        assert cost.flops == 99 * 2 * 64 ** 3


# ---------------------------------------------------------------------------
# mini dry-run on 8 fake devices (subprocess: needs its own XLA_FLAGS)
# ---------------------------------------------------------------------------

MINI_DRYRUN = textwrap.dedent("""\
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, os.path.join(%r, "src"))
    import dataclasses, jax, jax.numpy as jnp
    from repro.configs import get_smoke_config
    from repro.models.zoo import Model
    from repro.optim import AdamWConfig
    from repro.runtime.train import assemble_train
    from repro.launch.roofline import analyze_module
    from repro.core.compat import make_mesh

    mesh = make_mesh((2, 4), ("data", "model"))
    cfg = dataclasses.replace(get_smoke_config("llama3-8b"),
                              n_heads=4, n_kv_heads=4, d_model=64,
                              vocab=512, attn_chunk=16)
    model = Model(cfg)
    specs = {"tokens": jax.ShapeDtypeStruct((8, 32), jnp.int32),
             "labels": jax.ShapeDtypeStruct((8, 32), jnp.int32)}
    fn, (ap, ao), _ = assemble_train(model, mesh, AdamWConfig(),
                                     abstract_batch=specs)
    lowered = fn.lower(ap, ao, specs)
    compiled = lowered.compile()
    print("MEM", compiled.memory_analysis().temp_size_in_bytes)
    cost = analyze_module(compiled.as_text(), world=8)
    assert cost.flops > 0, "parser found no flops"
    assert len(cost.collectives) > 0, "no collectives in sharded train step"
    print("FLOPS", cost.flops)
    print("OK")
    """) % os.path.abspath(REPO)


def test_mini_dryrun_8dev():
    r = subprocess.run([sys.executable, "-c", MINI_DRYRUN],
                       capture_output=True, text=True, timeout=420)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout
