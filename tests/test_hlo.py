"""core/hlo — the shared HLO/StableHLO shape+byte walker (DESIGN.md §15).

The walker is the single source of truth for "how many bytes does this
lowered signature move": launch/roofline.py (HLO-style ``f32[4,9]``
specs), core/tracing.py (``hlo_stats`` counters), and analysis/cost.py
(MLIR ``tensor<...>`` signatures) all import from it — pinned here by
identity asserts so the dedup cannot silently regress into copies.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp

from repro.core import hlo

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


# ---------------------------------------------------------------------------
# HLO-style specs (the roofline's input format)
# ---------------------------------------------------------------------------

def test_shape_bytes_hlo_specs():
    assert hlo.shape_bytes("f32[4,9]") == 4 * 9 * 4
    assert hlo.shape_bytes("s32[16]") == 64
    assert hlo.shape_bytes("pred[8]") == 8
    assert hlo.shape_bytes("bf16[2,3]") == 12
    assert hlo.shape_bytes("f32[]") == 4          # rank-0 scalar


def test_shape_dims():
    assert hlo.shape_dims("f32[4,9,1]") == [4, 9, 1]
    assert hlo.shape_dims("s32[]") == []


# ---------------------------------------------------------------------------
# MLIR tensor types (the StableHLO signature format)
# ---------------------------------------------------------------------------

def test_tensor_bytes_mlir():
    assert hlo.tensor_bytes("4x9x1xf32") == 4 * 9 * 1 * 4
    assert hlo.tensor_bytes("16xi32") == 64
    assert hlo.tensor_bytes("8xi1") == 8
    assert hlo.tensor_bytes("f32") == 4           # rank-0
    assert hlo.tensor_bytes("2x4xbf16") == 16


def test_tensor_bytes_unknown_or_dynamic_is_zero():
    # dynamic dims and exotic element types are unaccountable, not fatal
    assert hlo.tensor_bytes("?xf32") == 0
    assert hlo.tensor_bytes("4xcomplex-ish") == 0


# ---------------------------------------------------------------------------
# @main signature accounting against a REAL lowering
# ---------------------------------------------------------------------------

def test_main_io_bytes_matches_avals():
    def f(table, idx):
        return table[idx]

    table = jax.ShapeDtypeStruct((128,), jnp.float32)
    idx = jax.ShapeDtypeStruct((32,), jnp.int32)
    text = jax.jit(f).lower(table, idx).as_text()
    got = hlo.main_io_bytes(text)
    assert got["arg_bytes"] == 128 * 4 + 32 * 4
    assert got["result_bytes"] == 32 * 4
    assert got["total"] == got["arg_bytes"] + got["result_bytes"]


def test_main_signature_skips_bracket_soup_inside_quotes():
    # sharded modules annotate args with mhlo.sharding strings like
    # "{devices=[4,2]<=[8]}" — unbalanced brackets INSIDE quotes that a
    # naive depth counter trips over
    text = textwrap.dedent("""
        module @jit_f attributes {mhlo.num_partitions = 8 : i32} {
          func.func public @main(
              %arg0: tensor<4x8xf32> {mhlo.sharding = "{devices=[4,2]<=[8]}"},
              %arg1: tensor<16xi32>) -> (tensor<16xf32>
              {mhlo.sharding = "{replicated}"}) {
            %0 = stablehlo.constant dense<0> : tensor<16xf32>
            return %0 : tensor<16xf32>
          }
        }
    """)
    got = hlo.main_io_bytes(text)
    assert got["arg_bytes"] == 4 * 8 * 4 + 16 * 4
    assert got["result_bytes"] == 16 * 4


def test_hlo_stats_census():
    text = jax.jit(lambda x: jnp.sort(x)).lower(
        jax.ShapeDtypeStruct((64,), jnp.float32)).as_text()
    stats = hlo.hlo_stats(text)
    assert stats["num_partitions"] == 1
    assert stats["aliased_params"] == 0
    assert isinstance(stats["shardings"], set)


def test_hlo_stats_reads_shardy_annotations():
    # a sharded module spells each value's sharding against a named mesh:
    # one {} per dim listing the axes it is split over ({} = unsplit,
    # ? = open to the partitioner); hlo_stats turns each into the shard
    # count per dim
    text = textwrap.dedent("""
        module @jit_f attributes {mhlo.num_partitions = 8 : i32} {
          sdy.mesh @mesh = <["data"=4, "lane"=2]>
          func.func public @main(
              %arg0: tensor<8x256xf32> {sdy.sharding = #sdy.sharding<@mesh, [{"data"}, {"lane"}]>},
              %arg1: tensor<8x4x16xf32> {sdy.sharding = #sdy.sharding<@mesh, [{"data", "lane"}, {}, {}]>},
              %arg2: tensor<8x256xi32> {sdy.sharding = #sdy.sharding<@mesh, [{}, {"lane", ?}]>},
              %arg3: tensor<8x256xi32> {sdy.sharding = #sdy.sharding<@mesh, [{?}, {}]>})
              -> (tensor<8x256xf32> {sdy.sharding = #sdy.sharding<@mesh, [{"data"}, {"lane"}]>}) {
            return %arg0 : tensor<8x256xf32>
          }
        }
    """)
    stats = hlo.hlo_stats(text)
    assert stats["num_partitions"] == 8
    assert stats["shardings"] == {"devices=[4,2]", "devices=[8,1,1]",
                                  "devices=[1,2]", "devices=[1,1]"}


# ---------------------------------------------------------------------------
# dedup pins: every consumer resolves to THIS walker
# ---------------------------------------------------------------------------

def test_consumers_share_the_walker():
    from repro.core import tracing
    from repro.launch import roofline
    assert tracing.hlo_stats is hlo.hlo_stats
    assert roofline._shape_bytes is hlo.shape_bytes
    assert roofline._shape_dims is hlo.shape_dims
    assert roofline._DTYPE_BYTES is hlo.DTYPE_BYTES


def test_hlo_module_is_jax_free():
    # the module body is stdlib-only (it lives under the eager
    # repro.core package, so load it by path to test the file itself —
    # the same drift guard analysis/report.py and serve/client.py carry)
    path = os.path.join(SRC, "repro", "core", "hlo.py")
    code = (
        "import importlib.util, sys\n"
        f"spec = importlib.util.spec_from_file_location('hlo', {path!r})\n"
        "mod = importlib.util.module_from_spec(spec)\n"
        "spec.loader.exec_module(mod)\n"
        "assert mod.tensor_bytes('4xf32') == 16\n"
        "assert 'jax' not in sys.modules, 'hlo imported jax'\n")
    subprocess.run([sys.executable, "-c", code], check=True,
                   env=dict(os.environ))
