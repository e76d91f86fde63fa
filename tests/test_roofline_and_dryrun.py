"""Validation of the roofline/dry-run machinery itself.

 * analytic.param_counts must agree with real initialized parameter counts
   (else every roofline number would drift from the actual models);
 * HLO cost_analysis of a single packed matmul must match the analytic
   flops/bytes (validates the pipeline where no control flow interferes);
 * the dry-run driver compiles a real cell on the production mesh in a
   subprocess (512 fake devices never touch this process's jax).
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import analytic
from repro.configs import ARCHS, PAPER_ARCH, get_config
from repro.core import bitlinear, ternary
from repro.models import transformer


@pytest.mark.parametrize("arch", ARCHS + [PAPER_ARCH])
def test_param_counts_match_real_init(arch):
    cfg = get_config(arch).reduced(n_layers=2, d_model=64, n_heads=2,
                                   d_ff=96 if get_config(arch).d_ff else 0,
                                   vocab_size=64)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    real = sum(x.size for x in jax.tree_util.tree_leaves(params))
    pred, _ = analytic.param_counts(cfg)
    # analytic model skips norm scales / ssm vectors / conv / biases:
    # agreement within 12% at tiny widths (slack shrinks as d_model grows)
    assert abs(real - pred) / real < 0.12, (real, pred)


def test_hlo_cost_matches_analytic_for_single_matmul():
    m, n, k = 64, 640, 512
    w = jax.random.normal(jax.random.PRNGKey(0), (n, k))
    p = bitlinear.pack({"w": w}, 5)
    x = jax.random.normal(jax.random.PRNGKey(1), (m, n))

    f = jax.jit(lambda x, p: bitlinear.apply_packed(p, x, g=5,
                                                    out_dtype=jnp.float32))
    ca = f.lower(x, p).compile().cost_analysis()
    flops = ca.get("flops", 0.0)
    analytic_flops = 2 * m * n * k
    # the integer dot dominates; quant/unpack adds elementwise work
    assert flops >= analytic_flops * 0.9
    assert flops <= analytic_flops * 2.5


def test_bitnet_param_count_matches_paper():
    """49M embed + 680M decoder (paper §4.1)."""
    cfg = get_config("bitnet-0.73b")
    total, _ = analytic.param_counts(cfg)
    assert abs(total - 0.73e9) / 0.73e9 < 0.01
    embed = cfg.vocab_size * cfg.d_model
    assert abs(embed - 49e6) / 49e6 < 0.01


def test_kv8_decode_matches_full_precision_cache():
    """KV8 cache decode tracks the bf16-cache decode closely."""
    from repro.models.layers import Ctx
    cfg = get_config("granite-3-2b").reduced()
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    ctx = Ctx(mode="qat", attn_q_chunk=8, attn_kv_chunk=8)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0,
                                cfg.vocab_size)
    tok = jax.random.randint(jax.random.PRNGKey(2), (2, 1), 0,
                             cfg.vocab_size)

    def run(kv_quant):
        cache = transformer.init_cache(cfg, 2, 24, jnp.float32,
                                       kv_quant=kv_quant)
        _, cache = transformer.prefill_step(cfg, params, prompt, ctx, cache)
        logits, _ = transformer.decode_step(cfg, params, tok, ctx, cache,
                                            jnp.asarray(12, jnp.int32))
        return logits

    full = run(False)
    quant = run(True)
    np.testing.assert_allclose(np.asarray(full), np.asarray(quant),
                               atol=0.05, rtol=0.05)


@pytest.mark.slow
def test_dryrun_cell_compiles_on_production_mesh(tmp_path):
    """One real dry-run cell end-to-end in a subprocess (512 fake devices)."""
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "xlstm-350m",
         "--shape", "decode_32k", "--out", str(tmp_path)],
        capture_output=True, text=True, env=env, cwd=os.getcwd(),
        timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr
    with open(tmp_path / "xlstm-350m_decode_32k_16x16.json") as f:
        r = json.load(f)
    assert r["ok"]
    assert r["memory"]["peak_bytes_est"] < 16 * 2**30  # fits v5e HBM
