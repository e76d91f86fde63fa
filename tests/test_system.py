"""End-to-end behaviour tests for the system.

The heavyweight checks actually *execute* (not just compile) sharded
training steps on multi-device meshes in subprocesses, exercising the same
sharding rules the 512-chip dry-run lowers.
"""

import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.data.pipeline import SyntheticLMDataset
from repro.launch.train import train
from repro.models import transformer
from repro.models.layers import Ctx
from repro.optim import adamw
from repro.training import make_train_step


def test_end_to_end_training_learns(tmp_path):
    """QAT ternary training on structured synthetic data reduces loss a lot
    (the data is 80% deterministic, so a learning model must beat uniform)."""
    _, losses = train("bitnet-0.73b", steps=60, batch=8, seq_len=64,
                      ckpt_dir=str(tmp_path), ckpt_every=30, reduced=True,
                      lr=3e-3, log_every=1000)
    assert losses[-1] < losses[0] * 0.8, (losses[0], losses[-1])
    # and a checkpoint landed
    assert any(name.startswith("step_") for name in os.listdir(tmp_path))


def test_int8_forward_training_tracks_fake_quant():
    cfg = get_config("bitnet-0.73b").reduced()
    opt = adamw(lr=1e-3)
    data = SyntheticLMDataset(cfg, batch=2, seq_len=32, seed=0)
    results = {}
    for name, int8 in (("fq", False), ("i8", True)):
        ctx = Ctx(mode="qat", attn_q_chunk=16, attn_kv_chunk=16,
                  qat_int8_fwd=int8)
        step = jax.jit(make_train_step(cfg, ctx, opt, loss_chunk=0))
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        state = opt.init(params)
        for i in range(3):
            params, state, m = step(params, state, data.batch_at(i))
        results[name] = float(m["loss"])
    assert abs(results["fq"] - results["i8"]) < 5e-3, results


@pytest.mark.slow
def test_multi_device_sharded_train_executes():
    """Run (not just compile) 2 sharded train steps on an 8-device mesh."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from repro.configs import get_config
        from repro.data.pipeline import SyntheticLMDataset
        from repro.models import transformer
        from repro.models.layers import Ctx
        from repro.optim import adamw
        from repro.runtime import sharding as shd
        from repro.training import make_train_step

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        cfg = get_config("granite-3-2b").reduced(
            n_layers=2, d_model=64, n_heads=4, n_kv_heads=4,
            d_ff=128, vocab_size=128)
        ctx = Ctx(mode="qat", attn_q_chunk=16, attn_kv_chunk=16,
                  constrain=shd.make_constrain(mesh, cfg, 4))
        opt = adamw(lr=1e-3)
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        state = opt.init(params)
        p_sh = shd.shard_params(mesh, params, fsdp=False)
        with mesh:
            params = jax.tree_util.tree_map(jax.device_put, params, p_sh)
            step = jax.jit(make_train_step(cfg, ctx, opt, loss_chunk=16))
            data = SyntheticLMDataset(cfg, batch=4, seq_len=32, seed=0)
            for i in range(2):
                params, state, m = step(params, state, data.batch_at(i))
            loss = float(m["loss"])
        assert loss == loss and loss > 0, loss
        print("MULTIDEV_OK", loss)
    """)
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, env=env, cwd=os.getcwd(), timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "MULTIDEV_OK" in out.stdout


@pytest.mark.slow
def test_multi_device_compressed_ddp_executes():
    """Compressed-DDP shard_map step runs on 8 devices and reduces loss."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from repro.configs import get_config
        from repro.data.pipeline import SyntheticLMDataset
        from repro.models import transformer
        from repro.models.layers import Ctx
        from repro.optim import adamw
        from repro.optim.compression import init_error_state
        from repro.training.steps import make_train_step_ddp

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("data",))
        cfg = get_config("qwen1.5-0.5b").reduced(
            n_layers=2, d_model=64, n_heads=4, d_ff=128, vocab_size=128)
        ctx = Ctx(mode="qat", attn_q_chunk=16, attn_kv_chunk=16)
        opt = adamw(lr=3e-3)
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        state = opt.init(params)
        err = init_error_state(params)
        step = jax.jit(make_train_step_ddp(cfg, ctx, opt, mesh,
                                           compress=True, loss_chunk=0))
        data = SyntheticLMDataset(cfg, batch=8, seq_len=32, seed=0)
        losses = []
        for i in range(8):
            params, state, err, m = step(params, state, err, data.batch_at(i))
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0], losses
        print("DDP_OK", losses[0], "->", losses[-1])
    """)
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, env=env, cwd=os.getcwd(), timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "DDP_OK" in out.stdout
