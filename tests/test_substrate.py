"""Substrate tests: optimizer, gradient compression, data, checkpointing,
fault tolerance, serving engine."""

import os
import signal
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.checkpoint import CheckpointManager
from repro.checkpoint.manager import install_sigterm_handler
from repro.configs import get_config
from repro.data.pipeline import SyntheticLMDataset
from repro.models import transformer
from repro.models.layers import Ctx
from repro.optim import adamw
from repro.optim.adamw import apply_updates
from repro.optim import compression
from repro.runtime.fault import StepTimer, Watchdog, with_retries
from repro.serving import Request, ServingEngine
from repro.training import make_train_step


# ---------------------------------------------------------------------------
# Optimizer
# ---------------------------------------------------------------------------

def test_adamw_descends_quadratic():
    opt = adamw(lr=0.1, weight_decay=0.0, grad_clip=None)
    params = {"w": jnp.asarray([3.0, -2.0])}
    state = opt.init(params)
    for _ in range(200):
        grads = {"w": 2 * params["w"]}
        updates, state = opt.update(grads, state, params)
        params = apply_updates(params, updates)
    assert float(jnp.max(jnp.abs(params["w"]))) < 1e-2


def test_adamw_weight_decay_only_on_matrices():
    opt = adamw(lr=0.1, weight_decay=1.0, grad_clip=None)
    params = {"w": jnp.ones((2, 2)), "scale": jnp.ones((2,))}
    state = opt.init(params)
    zero = {"w": jnp.zeros((2, 2)), "scale": jnp.zeros((2,))}
    updates, _ = opt.update(zero, state, params)
    assert float(jnp.sum(jnp.abs(updates["w"]))) > 0  # decayed
    assert float(jnp.sum(jnp.abs(updates["scale"]))) == 0  # vector: no decay


def test_grad_clip_bounds_update_norm():
    opt = adamw(lr=1.0, grad_clip=1.0, weight_decay=0.0)
    params = {"w": jnp.zeros((4,))}
    state = opt.init(params)
    huge = {"w": jnp.full((4,), 1e9)}
    updates, state = opt.update(huge, state, params)
    assert np.isfinite(np.asarray(updates["w"])).all()


# ---------------------------------------------------------------------------
# Gradient compression (error feedback)
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), scale=st.floats(1e-3, 1e3))
def test_error_feedback_invariant(seed, scale):
    """EF invariant: transmitted + error == grad + carried error (exactly)."""
    rng = np.random.default_rng(seed)
    g = jnp.asarray(rng.standard_normal(64) * scale, jnp.float32)
    err = jnp.asarray(rng.standard_normal(64) * 0.01 * scale, jnp.float32)
    deq, new_err = compression.compress_decompress(g, err)
    np.testing.assert_allclose(np.asarray(deq + new_err),
                               np.asarray(g + err), rtol=1e-5, atol=1e-6)


def test_compression_error_shrinks_with_feedback():
    """Over repeated rounds, EF keeps the *accumulated* bias bounded (vs
    biased drift without feedback)."""
    rng = np.random.default_rng(0)
    g_true = jnp.asarray(rng.standard_normal(128), jnp.float32)
    err = jnp.zeros_like(g_true)
    total_sent = jnp.zeros_like(g_true)
    for step in range(50):
        deq, err = compression.compress_decompress(g_true, err)
        total_sent = total_sent + deq
    # mean transmitted ~= true grad
    np.testing.assert_allclose(np.asarray(total_sent / 50),
                               np.asarray(g_true), atol=1e-2)


def test_compressed_psum_single_device():
    """shard_map psum path on a 1-device mesh (degenerate reduction)."""
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("data",))
    g = jnp.linspace(-1, 1, 32)
    err = jnp.zeros_like(g)

    def f(g, err):
        return compression.compressed_psum(g, err, "data")

    out, new_err = jax.shard_map(f, mesh=mesh, in_specs=(P(), P()),
                                 out_specs=(P(), P()))(g, err)
    np.testing.assert_allclose(np.asarray(out), np.asarray(g), atol=0.02)


# ---------------------------------------------------------------------------
# Data pipeline
# ---------------------------------------------------------------------------

def test_data_deterministic_and_host_disjoint():
    cfg = get_config("qwen1.5-0.5b").reduced()
    d0 = SyntheticLMDataset(cfg, batch=2, seq_len=16, seed=1, host_id=0,
                            n_hosts=2)
    d0b = SyntheticLMDataset(cfg, batch=2, seq_len=16, seed=1, host_id=0,
                             n_hosts=2)
    d1 = SyntheticLMDataset(cfg, batch=2, seq_len=16, seed=1, host_id=1,
                            n_hosts=2)
    b0 = d0.batch_at(7)
    np.testing.assert_array_equal(np.asarray(b0["inputs"]),
                                  np.asarray(d0b.batch_at(7)["inputs"]))
    assert not np.array_equal(np.asarray(b0["inputs"]),
                              np.asarray(d1.batch_at(7)["inputs"]))


def test_data_has_learnable_structure():
    cfg = get_config("qwen1.5-0.5b").reduced(vocab_size=64)
    d = SyntheticLMDataset(cfg, batch=4, seq_len=64, seed=0, structure=1.0)
    b = d.batch_at(0)
    x = np.asarray(b["inputs"])
    y = np.asarray(b["labels"])
    np.testing.assert_array_equal((31 * x + 7) % 64, y)


# ---------------------------------------------------------------------------
# Checkpointing
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip_and_keep_n(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep_n=2)
    tree = {"a": jnp.arange(8, dtype=jnp.float32),
            "nested": {"b": jnp.ones((2, 3), jnp.bfloat16)}}
    for step in (1, 2, 3):
        mgr.save(step, jax.tree_util.tree_map(lambda x: x * step, tree),
                 blocking=True)
    assert mgr.latest_step() == 3
    assert mgr.all_steps() == [2, 3]  # keep_n
    restored = mgr.restore(3, tree)
    np.testing.assert_allclose(np.asarray(restored["a"]),
                               np.arange(8, dtype=np.float32) * 3)
    assert restored["nested"]["b"].dtype == jnp.bfloat16


def test_checkpoint_async_then_wait(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep_n=3)
    tree = {"w": jnp.ones((64, 64))}
    mgr.save(5, tree, blocking=False)
    mgr.wait()
    assert mgr.latest_step() == 5
    restored = mgr.restore(None, tree)
    np.testing.assert_allclose(np.asarray(restored["w"]), 1.0)


def test_checkpoint_elastic_reshard(tmp_path):
    """Save on one layout, restore onto explicit shardings (new 'mesh')."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    mgr = CheckpointManager(str(tmp_path))
    tree = {"w": jnp.arange(16.0).reshape(4, 4)}
    mgr.save(1, tree, blocking=True)
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("model",))
    sh = {"w": NamedSharding(mesh, P("model", None))}
    restored = mgr.restore(1, tree, shardings=sh)
    assert restored["w"].sharding == sh["w"]
    np.testing.assert_allclose(np.asarray(restored["w"]),
                               np.asarray(tree["w"]))


def test_checkpoint_resume_training_equivalence(tmp_path):
    """checkpoint -> restore -> continue == continuous run (bitwise-ish)."""
    cfg = get_config("bitnet-0.73b").reduced()
    ctx = Ctx(mode="qat", attn_q_chunk=8, attn_kv_chunk=8)
    opt = adamw(lr=1e-3)
    step_fn = jax.jit(make_train_step(cfg, ctx, opt))
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    state = opt.init(params)
    data = SyntheticLMDataset(cfg, batch=2, seq_len=16, seed=0)

    # run 4 steps straight
    p1, s1 = params, state
    for i in range(4):
        p1, s1, _ = step_fn(p1, s1, data.batch_at(i))

    # run 2, checkpoint, restore, run 2 more
    p2, s2 = params, state
    for i in range(2):
        p2, s2, _ = step_fn(p2, s2, data.batch_at(i))
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(2, {"params": p2, "opt": s2}, blocking=True)
    restored = mgr.restore(2, {"params": p2, "opt": s2})
    p3, s3 = restored["params"], restored["opt"]
    for i in range(2, 4):
        p3, s3, _ = step_fn(p3, s3, data.batch_at(i))

    flat1 = jax.tree_util.tree_leaves(p1)
    flat3 = jax.tree_util.tree_leaves(p3)
    for a, b in zip(flat1, flat3):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-6)


# ---------------------------------------------------------------------------
# Fault tolerance
# ---------------------------------------------------------------------------

def test_step_timer_flags_stragglers():
    t = StepTimer(threshold=2.0)
    for i in range(10):
        assert not t.record(i, 1.0)
    assert t.record(10, 5.0)          # 5x the EMA -> straggler
    assert len(t.stats.stragglers) == 1


def test_watchdog_fires_on_hang():
    wd = Watchdog(deadline_s=0.05)
    with wd:
        time.sleep(0.15)
    assert wd.fired
    wd2 = Watchdog(deadline_s=10.0)
    with wd2:
        pass
    assert not wd2.fired


def test_with_retries_recovers():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise RuntimeError("transient")
        return "ok"

    assert with_retries(flaky, max_retries=3)() == "ok"
    assert calls["n"] == 3


def test_sigterm_preemption_flag():
    flag = install_sigterm_handler()
    assert not flag
    os.kill(os.getpid(), signal.SIGTERM)
    time.sleep(0.05)
    assert flag


# ---------------------------------------------------------------------------
# Serving engine
# ---------------------------------------------------------------------------

def test_serving_engine_end_to_end():
    cfg = get_config("bitnet-0.73b").reduced()
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    packed = transformer.pack_params(cfg, params)
    eng = ServingEngine(cfg, packed, max_seq=64, batch_slots=2)
    reqs = [Request(prompt=np.arange(5) % cfg.vocab_size, max_new_tokens=4),
            Request(prompt=np.arange(9) % cfg.vocab_size, max_new_tokens=6),
            Request(prompt=np.arange(3) % cfg.vocab_size, max_new_tokens=4)]
    eng.run(reqs)
    for r in reqs:
        assert r.done and r.ttft_s is not None
        assert len(r.output) == r.max_new_tokens
        assert (r.output >= 0).all() and (r.output < cfg.vocab_size).all()


def test_serving_greedy_matches_stepwise_reference():
    """Engine output == manual prefill+decode loop (same params, greedy)."""
    cfg = get_config("qwen1.5-0.5b").reduced()
    params = transformer.init_params(cfg, jax.random.PRNGKey(1))
    packed = transformer.pack_params(cfg, params)
    ctx = Ctx(mode="packed", group_size=cfg.group_size,
              attn_q_chunk=128, attn_kv_chunk=128)
    prompt = np.asarray([1, 2, 3, 4, 5], np.int32)

    eng = ServingEngine(cfg, packed, max_seq=32, batch_slots=1, ctx=ctx)
    req = Request(prompt=prompt, max_new_tokens=5)
    eng.run([req])

    cache = transformer.init_cache(cfg, 1, 32, jnp.bfloat16)
    logits, cache = transformer.prefill_step(cfg, packed,
                                             jnp.asarray(prompt[None]), ctx,
                                             cache)
    toks = [int(jnp.argmax(logits, -1)[0])]
    pos = len(prompt)
    for _ in range(4):
        logits, cache = transformer.decode_step(
            cfg, packed, jnp.asarray([[toks[-1]]], jnp.int32), ctx, cache,
            jnp.asarray(pos, jnp.int32))
        toks.append(int(jnp.argmax(logits, -1)[0]))
        pos += 1
    np.testing.assert_array_equal(req.output, np.asarray(toks))
