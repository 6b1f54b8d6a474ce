"""Optimizer / checkpoint / data-pipeline / supervisor tests."""
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import Checkpointer, CheckpointManager
from repro.core.compat import make_mesh
from repro.data import TokenPipeline
from repro.optim import (AdamWConfig, adamw_update, clip_by_global_norm,
                         init_opt_state, int8_compress, int8_decompress,
                         warmup_cosine)


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------

def _numpy_adamw(cfg, p, g, m, v, step):
    m = cfg.b1 * m + (1 - cfg.b1) * g
    v = cfg.b2 * v + (1 - cfg.b2) * g * g
    mh = m / (1 - cfg.b1 ** step)
    vh = v / (1 - cfg.b2 ** step)
    lr = cfg.lr if not callable(cfg.lr) else cfg.lr(jnp.int32(step))
    p2 = p - lr * (mh / (np.sqrt(vh) + cfg.eps) + cfg.weight_decay * p)
    return p2, m, v


def test_adamw_matches_reference():
    cfg = AdamWConfig(lr=1e-2, weight_decay=0.01)
    rng = np.random.default_rng(0)
    p = {"w": jnp.asarray(rng.standard_normal((5, 3)), jnp.float32)}
    g = {"w": jnp.asarray(rng.standard_normal((5, 3)), jnp.float32)}
    state = init_opt_state(p)
    p_ref = np.asarray(p["w"]).copy()
    m = np.zeros_like(p_ref)
    v = np.zeros_like(p_ref)
    for step in range(1, 4):
        p, state = adamw_update(cfg, p, g, state)
        p_ref, m, v = _numpy_adamw(cfg, p_ref, np.asarray(g["w"]), m, v, step)
        np.testing.assert_allclose(np.asarray(p["w"]), p_ref, rtol=1e-5,
                                   atol=1e-6)
    assert int(state["step"]) == 3


def test_adamw_bf16_params_fp32_moments():
    cfg = AdamWConfig(lr=1e-2)
    p = {"w": jnp.ones((4,), jnp.bfloat16)}
    g = {"w": jnp.full((4,), 0.1, jnp.bfloat16)}
    state = init_opt_state(p)
    p2, s2 = adamw_update(cfg, p, g, state)
    assert p2["w"].dtype == jnp.bfloat16
    assert s2["m"]["w"].dtype == jnp.float32


def test_clip_by_global_norm():
    tree = {"a": jnp.full((10,), 3.0), "b": jnp.full((10,), 4.0)}
    clipped, norm = clip_by_global_norm(tree, 1.0)
    assert np.isclose(float(norm), np.sqrt(10 * 9 + 10 * 16))
    _, norm2 = clip_by_global_norm(clipped, 1.0)
    assert float(norm2) <= 1.0 + 1e-5


def test_warmup_cosine():
    lr = warmup_cosine(1e-3, warmup=10, total=100)
    assert float(lr(jnp.int32(0))) == 0.0
    assert np.isclose(float(lr(jnp.int32(10))), 1e-3, rtol=1e-2)
    assert float(lr(jnp.int32(100))) < 2e-4


def test_int8_roundtrip():
    x = jnp.asarray(np.random.default_rng(0).standard_normal(1000),
                    jnp.float32)
    q, s = int8_compress(x)
    back = int8_decompress(q, s)
    err = np.abs(np.asarray(back) - np.asarray(x)).max()
    assert err <= float(s) * 0.5 + 1e-6        # quantization bound


def test_compressed_psum_shardmap():
    from repro.optim.grad_utils import compressed_psum
    mesh = make_mesh((1,), ("data",))
    from jax.sharding import PartitionSpec as P

    from repro.core.compat import shard_map_unchecked

    def f(g):
        out, res = compressed_psum(g, "data")
        return out, res

    g = jnp.asarray(np.random.default_rng(1).standard_normal((8, 4)),
                    jnp.float32)
    out, res = shard_map_unchecked(f, mesh=mesh, in_specs=P(),
                                   out_specs=P())(g)
    np.testing.assert_allclose(np.asarray(out), np.asarray(g), atol=0.05)
    # error feedback residual equals quantization error
    np.testing.assert_allclose(np.asarray(g - out), np.asarray(res),
                               atol=1e-6)


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------

def _tree(seed=0):
    rng = np.random.default_rng(seed)
    return {"params": {"w": jnp.asarray(rng.standard_normal((8, 4)),
                                        jnp.float32),
                       "b": jnp.asarray(rng.standard_normal(4), jnp.float32)},
            "step": jnp.int32(7)}


def test_checkpoint_roundtrip(tmp_path):
    ck = Checkpointer(str(tmp_path))
    t = _tree()
    ck.save(7, t)
    assert ck.latest_step() == 7
    like = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), t)
    back = ck.restore(7, like)
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_atomic_no_tmp_left(tmp_path):
    ck = Checkpointer(str(tmp_path))
    ck.save(1, _tree())
    assert not any(d.endswith(".tmp") for d in os.listdir(tmp_path))


def test_checkpoint_shape_mismatch(tmp_path):
    ck = Checkpointer(str(tmp_path))
    ck.save(1, {"w": jnp.zeros((4,))})
    with pytest.raises(ValueError):
        ck.restore(1, {"w": jax.ShapeDtypeStruct((5,), jnp.float32)})


def test_manager_async_and_prune(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep_n=2)
    for s in (10, 20, 30):
        mgr.save_async(s, _tree(s))
    mgr.wait()
    assert mgr.latest_step() == 30
    steps = sorted(int(d[5:]) for d in os.listdir(tmp_path)
                   if d.startswith("step_"))
    assert steps == [20, 30]
    mgr.close()


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------

def test_pipeline_deterministic():
    p1 = TokenPipeline(vocab=100, seq_len=16, global_batch=4, seed=3)
    p2 = TokenPipeline(vocab=100, seq_len=16, global_batch=4, seed=3)
    b1, b2 = p1.batch(5), p2.batch(5)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    assert not np.array_equal(p1.batch(6)["tokens"], b1["tokens"])


def test_pipeline_shards_partition_batch():
    p = TokenPipeline(vocab=100, seq_len=8, global_batch=8, seed=0)
    full = p.batch(2)["tokens"]
    parts = [p.shard_batch(2, h, 4)["tokens"] for h in range(4)]
    np.testing.assert_array_equal(np.concatenate(parts), full)


def test_pipeline_labels_shift():
    p = TokenPipeline(vocab=50, seq_len=8, global_batch=2, seed=0)
    b = p.batch(0)
    np.testing.assert_array_equal(b["labels"][:, :-1], b["tokens"][:, 1:])


def test_pipeline_tokens_in_range():
    p = TokenPipeline(vocab=64, seq_len=128, global_batch=4, seed=1)
    t = p.batch(0)["tokens"]
    assert t.min() >= 0 and t.max() < 64


# ---------------------------------------------------------------------------
# supervisor (crash restart, straggler detection, sigterm)
# ---------------------------------------------------------------------------

def test_supervisor_crash_restart(tmp_path):
    from repro.runtime.supervisor import SupervisorConfig, TrainSupervisor
    crashed = {"done": False}

    def build(ckpt):
        start = ckpt.latest_step() or 0
        state = {"x": jnp.float32(start)}
        if start:
            state = ckpt.restore(start, jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state))

        def step_fn(state, i):
            if i == 7 and not crashed["done"]:
                crashed["done"] = True
                raise RuntimeError("simulated node failure")
            return {"x": state["x"] + 1}, {"loss": float(state["x"])}

        return state, step_fn, start

    sup = TrainSupervisor(SupervisorConfig(ckpt_dir=str(tmp_path),
                                           ckpt_every=5, max_restarts=2))
    state = sup.run(build, 12)
    # crash at 7 -> restart from ckpt step 5 -> steps 5..11 rerun
    assert crashed["done"]
    assert float(state["x"]) == 12.0
    sup.ckpt.close()


def test_supervisor_straggler_detection(tmp_path):
    from repro.runtime.supervisor import SupervisorConfig, TrainSupervisor

    def build(ckpt):
        def step_fn(state, i):
            if i == 8:
                time.sleep(0.25)       # straggler
            else:
                time.sleep(0.01)
            return state, {"loss": 1.0}
        return {}, step_fn, 0

    sup = TrainSupervisor(SupervisorConfig(ckpt_dir=str(tmp_path),
                                           ckpt_every=100,
                                           straggler_factor=5.0))
    sup.run(build, 10)
    assert 8 in sup.straggler_events
    sup.ckpt.close()


def test_elastic_restore_across_meshes():
    """Checkpoint written under one mesh restores onto a different mesh
    (elastic restart) — subprocess with 8 fake devices."""
    import subprocess, sys, textwrap, os
    code = textwrap.dedent("""
        import os, tempfile
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys; sys.path.insert(0, %r)
        from repro.core.compat import make_mesh
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint import Checkpointer

        tree = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
        mesh_a = make_mesh((8, 1), ("data", "model"))
        tree_a = jax.device_put(tree, {"w": NamedSharding(mesh_a,
                                                          P("data", None))})
        with tempfile.TemporaryDirectory() as d:
            ck = Checkpointer(d)
            ck.save(1, tree_a)
            # restore onto a DIFFERENT topology: 2x4, sharded other way
            mesh_b = make_mesh((2, 4), ("data", "model"))
            sh_b = {"w": NamedSharding(mesh_b, P(None, "model"))}
            like = {"w": jax.ShapeDtypeStruct((8, 8), jnp.float32)}
            back = ck.restore(1, like, shardings=sh_b)
            assert np.allclose(np.asarray(back["w"]), np.asarray(tree["w"]))
            assert back["w"].sharding == sh_b["w"]
        print("OK")
    """) % os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                        "src"))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout


def test_checkpoint_bf16_roundtrip(tmp_path):
    """bf16 leaves survive save/restore (stored as uint16 views — numpy
    cannot cast foreign ml_dtypes; regression for the train-driver resume
    crash)."""
    ck = Checkpointer(str(tmp_path))
    t = {"w": jnp.asarray([1.5, -2.25, 3.0], jnp.bfloat16),
         "s": jnp.float32(2.0)}
    ck.save(3, t)
    like = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), t)
    back = ck.restore(3, like)
    assert back["w"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(back["w"], np.float32),
                                  np.asarray(t["w"], np.float32))
