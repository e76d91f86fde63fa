"""GPipe pipeline over a real multi-device mesh == sequential execution."""

import os
import subprocess
import sys
import textwrap

import pytest


@pytest.mark.slow
def test_gpipe_matches_sequential_on_4_devices():
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp
        import numpy as np
        from repro.runtime.pipeline import (pipeline_forward,
                                            split_layers_into_stages)

        from repro.launch.mesh import make_mesh
        S, L, D = 4, 8, 16
        mesh = make_mesh((S,), ("pod",))
        key = jax.random.PRNGKey(0)
        ws = jax.random.normal(key, (L, D, D)) * (0.5 / D ** 0.5)

        def layer(w, x):
            return jnp.tanh(x @ w) + x

        def stage_fn(stage_ws, x):   # stage_ws: (L/S, D, D)
            def body(x, w):
                return layer(w, x), None
            x, _ = jax.lax.scan(body, x, stage_ws)
            return x

        n_micro, mb = 6, 3
        x = jax.random.normal(jax.random.PRNGKey(1), (n_micro, mb, D))

        # sequential reference: all L layers in order
        def seq(x):
            def body(x, w):
                return layer(w, x), None
            y, _ = jax.lax.scan(body, x, ws)
            return y
        ref = jax.vmap(seq)(x)

        stage_ws = split_layers_into_stages(ws, S)
        out = pipeline_forward(stage_fn, mesh, "pod", stage_ws, x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)
        print("GPIPE_OK")
    """)
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, env=env, cwd=os.getcwd(), timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "GPIPE_OK" in out.stdout
