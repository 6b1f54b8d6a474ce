"""Pipeline parallelism vs sequential oracle (subprocess, 4 fake devices)."""
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
    import jax, jax.numpy as jnp, numpy as np
    from repro.runtime.pipeline import pipeline_apply

    mesh = make_mesh((4,), ("pipe",))
    L, B, D = 8, 8, 16
    rng = np.random.default_rng(0)
    params = {"w": jnp.asarray(rng.standard_normal((L, D, D)) * 0.2,
                               jnp.float32),
              "b": jnp.asarray(rng.standard_normal((L, D)) * 0.1,
                               jnp.float32)}
    x = jnp.asarray(rng.standard_normal((B, D)), jnp.float32)

    def block(lp, x):
        return jnp.tanh(x @ lp["w"] + lp["b"])

    # sequential oracle
    def seq(params, x):
        def one(x, lp):
            return block(lp, x), None
        y, _ = jax.lax.scan(one, x, params)
        return y

    y_ref = seq(params, x)
    y_pipe = jax.jit(lambda p, x: pipeline_apply(
        mesh, block, p, x, n_micro=4))(params, x)
    assert np.allclose(y_pipe, y_ref, atol=1e-5), \
        float(jnp.abs(y_pipe - y_ref).max())

    # gradient: GPipe backward through ppermute transposition
    g_ref = jax.grad(lambda p: seq(p, x).sum())(params)
    g_pipe = jax.grad(lambda p: pipeline_apply(
        mesh, block, p, x, n_micro=4).sum())(params)
    for a, b in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g_pipe)):
        assert np.allclose(a, b, atol=1e-4), float(jnp.abs(a - b).max())

    # different microbatch counts agree
    y2 = jax.jit(lambda p, x: pipeline_apply(
        mesh, block, p, x, n_micro=8))(params, x)
    assert np.allclose(y2, y_ref, atol=1e-5)
    print("OK")
""") % REPO


def test_pipeline_matches_sequential():
    r = subprocess.run([sys.executable, "-c", CODE], capture_output=True,
                       text=True, timeout=420)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout
