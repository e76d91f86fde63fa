"""CPU checks of ``chip_smoke.py``: it refuses to run without a TPU, and its
phases pass at a reduced size.

The phases are called directly here, at a tiny width with the Pallas
kernels interpreted; ``main()``, which always checks for a TPU first, is
exercised only for its refusal.  Serve-phase lengths are the
real ones (prompts 64-128, 32 new tokens, 8 requests on 4 slots): the
scheduling, and so ``mid_flight_admissions``, depends on lengths, not on
width.  Subprocesses here stay on the CPU.
"""

from __future__ import annotations

import os
import re
import shutil
import subprocess
import sys

import jax
import pytest

import chip_smoke
from repro.configs import PAPER_ARCH, get_config
from repro.launch import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")
TINY = dict(n_layers=2, d_model=128, n_heads=4, d_ff=256, vocab_size=256)


def _tiny_cfg():
    return get_config(PAPER_ARCH).reduced(**TINY)


@pytest.fixture(scope="module")
def tiny():
    cfg = _tiny_cfg()
    return cfg, chip_smoke.make_params(cfg, 0)


def _run_cpu(args, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable] + args, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_smoke_refuses_without_tpu():
    out = _run_cpu([SMOKE], ROOT)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "no TPU" in out.stderr


def test_smoke_refuses_outside_the_repo(tmp_path):
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    out = _run_cpu(["chip_smoke.py"], str(tmp_path))
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout


def test_kernel_phase_tiny():
    cases = chip_smoke.kernel_cases(_tiny_cfg(), slots=2, max_seq=64,
                                    chunk=16, prompt=32)
    _, detail = chip_smoke.run_kernels(cases, interpret=True)
    assert all(c.name in detail for c in cases)


def test_kernel_phase_catches_a_wrong_kernel():
    case = chip_smoke.kernel_cases(_tiny_cfg(), slots=2, max_seq=64,
                                   chunk=16, prompt=32)[5]
    assert case.name == "decode_attention_paged"
    # a kernel reading the pages in the wrong order must fail the phase
    wrong = chip_smoke.KernelCase(
        case.name, lambda q, k, v, bt, n, interpret: case.kernel(
            q, k, v, bt[:, ::-1], n, interpret=interpret),
        case.ref, case.make_args, case.tol)
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.run_kernels([wrong], interpret=True)


def test_model_phase_tiny(tiny):
    cfg, packed = tiny
    _, detail = chip_smoke.run_model(cfg, packed, prompt=32, steps=4)
    assert "contiguous:rel_rms" in detail and "paged:rel_rms" in detail


def test_serve_phase_tiny(tiny):
    cfg, packed = tiny
    _, detail = chip_smoke.run_serve(cfg, packed)
    for name in chip_smoke.serve_variants(cfg):
        assert f"{name}:tokens=256" in detail


def test_mesh_phase_on_four_cpu_devices():
    """The --chips 4 phase, rehearsed on four forced host devices."""
    script = (
        "import chip_smoke\n"
        "cfg = chip_smoke.get_config(chip_smoke.PAPER_ARCH).reduced(**%r)\n"
        "_, detail = chip_smoke.run_mesh(cfg, chip_smoke.make_params(cfg, 0))\n"
        "print(detail)\n" % TINY)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=ROOT)
    out = subprocess.run([sys.executable, "-c", script], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "contiguous:identical_tokens=256" in out.stdout
    assert "paged:identical_tokens=256" in out.stdout


def test_compile_cache_dir_from_env(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself: no other directory is set in code
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_dir_defaults_to_checkout(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.enable_compile_cache()
        assert path == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_keys_on_program_metadata(monkeypatch, tmp_path):
    """An entry compiled before a ``jax.named_scope`` changed must not be
    found again (its profile would show the old scopes), and the key must
    not depend on where the checkout lies."""
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    flags = ("jax_compilation_cache_include_metadata_in_key",
             "jax_hlo_source_file_canonicalization_regex")
    before = [getattr(jax.config, f) for f in flags]
    try:
        jax.config.update(flags[0], False)
        compile_cache.enable_compile_cache()
        assert getattr(jax.config, flags[0]) is True
        strip = getattr(jax.config, flags[1])
        here = os.path.join(ROOT, "src", "repro", "models", "transformer.py")
        assert re.sub(strip, "", here) == os.path.join(
            "src", "repro", "models", "transformer.py")
    finally:
        for f, v in zip(flags, before):
            jax.config.update(f, v)
