"""int8-compressed cross-pod DP vs exact DP (subprocess, 4 fake devices)."""
import os
import subprocess
import sys
import textwrap

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))

CODE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys; sys.path.insert(0, %r)
    from repro.core.compat import make_mesh
    import dataclasses, jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_smoke_config
    from repro.data import TokenPipeline
    from repro.models.zoo import Model
    from repro.optim import AdamWConfig, init_opt_state
    from repro.runtime.dp_compressed import (make_compressed_dp_step,
                                             init_residuals)

    cfg = dataclasses.replace(get_smoke_config("llama3-8b"),
                              dtype="float32", remat="none")
    model = Model(cfg)
    mesh = make_mesh((4,), ("data",))
    opt_cfg = AdamWConfig(lr=1e-3, weight_decay=0.0)
    pipe = TokenPipeline(vocab=cfg.vocab, seq_len=16, global_batch=8,
                         seed=0)

    params0 = model.init(jax.random.PRNGKey(0))
    exact = jax.jit(make_compressed_dp_step(model, opt_cfg, mesh,
                                            compress=False))
    comp = jax.jit(make_compressed_dp_step(model, opt_cfg, mesh,
                                           compress=True))

    pe = pc = params0
    oe = init_opt_state(params0)
    oc = init_opt_state(params0)
    res = init_residuals(params0)
    losses_e, losses_c = [], []
    for i in range(24):
        batch = {k: jnp.asarray(v) for k, v in pipe.batch(i).items()}
        pe, oe, _, me = exact(pe, oe, init_residuals(params0), batch)
        pc, oc, res, mc = comp(pc, oc, res, batch)
        losses_e.append(float(me["loss"]))
        losses_c.append(float(mc["loss"]))

    # losses track closely; params drift stays bounded (error feedback)
    diffs = [abs(a - b) for a, b in zip(losses_e, losses_c)]
    assert max(diffs) < 0.05, diffs
    drift = max(float(jnp.abs(a - b).max())
                for a, b in zip(jax.tree.leaves(pe), jax.tree.leaves(pc)))
    assert drift < 0.05, drift
    # both learn (windowed means: single-step compares are noise-prone)
    assert sum(losses_c[-4:]) / 4 < sum(losses_c[:4]) / 4 - 0.3
    print("OK", max(diffs), drift)
""") % REPO


def test_compressed_dp_matches_exact():
    r = subprocess.run([sys.executable, "-c", CODE], capture_output=True,
                       text=True, timeout=420)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout
