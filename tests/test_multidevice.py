"""Multi-device serving: the sharded engine is token-identical to the
single-device engine, pinned mode-by-mode.

The tentpole contract: ``ServingEngine(mesh=...)`` shards the fused decode
slot batch over the mesh's 'data' axis (scheduler pytree, block tables,
contiguous cache rows, decode-block outputs) and flash-decode KV attention
over 'model' (canonical split-K partials + ordered partial-softmax
combine) — and every token it emits equals the single-device engine's,
greedy AND temperature, in every serving mode: {contiguous, paged} x
{sharing on/off} x {host, device sched} x mesh shapes {(1,1), (2,1),
(1,2), (2,2)}, including non-divisible slot counts (3 slots on 2 devices
pad the slot axis) and non-divisible KV lengths through the split-K
combine.  Host/device ownership transitions — retire, page grant, CoW
split, degrade, re-promotion, retry replay — must survive sharding with
``audit()`` clean, and the device-resident scheduler must keep its
zero-steady-state-sync contract (``steady_state_syncs_per_block == 0.0``)
under sharding.

All multi-device tests run on CPU via
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` — which must be set
before jax initializes, so every mesh test runs in a subprocess (the
pytest process already holds a 1-device jax).  Each subprocess sweeps many
configurations to amortize its model build."""

from __future__ import annotations

import os
import subprocess
import sys

import jax
import pytest

from repro.launch.mesh import make_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(ROOT, "src")


def _run_sub(script: str, sentinel: str, devices: int = 4,
             timeout: int = 900) -> str:
    env = dict(
        os.environ,
        XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
        JAX_PLATFORMS="cpu",
        PYTHONPATH=SRC + os.pathsep + os.environ.get("PYTHONPATH", ""))
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0 and sentinel in out.stdout, (
        f"--- stdout ---\n{out.stdout[-4000:]}\n"
        f"--- stderr ---\n{out.stderr[-4000:]}")
    return out.stdout


# shared subprocess prologue: tiny model + an engine runner returning the
# per-request token lists (mixed greedy/temperature batch)
_PROLOGUE = """
import jax
import numpy as np
from repro.launch.mesh import make_mesh
from repro.configs import get_config
from repro.models import transformer
from repro.models.layers import Ctx
from repro.serving import Request, ServingEngine

cfg = get_config("qwen1.5-0.5b").reduced()
params = transformer.init_params(cfg, jax.random.PRNGKey(1))
packed = transformer.pack_params(cfg, params)
ctx = Ctx(mode="packed", group_size=cfg.group_size,
          attn_q_chunk=128, attn_kv_chunk=128)

def run_engine(prompts, max_new=5, temps=True, **kw):
    kw.setdefault("max_seq", 32)
    kw.setdefault("prefill_chunk", 4)
    kw.setdefault("decode_block", 4)
    eng = ServingEngine(cfg, packed, ctx=ctx, **kw)
    reqs = [Request(prompt=p, max_new_tokens=max_new,
                    temperature=(0.7 if temps and i % 2 else 0.0))
            for i, p in enumerate(prompts)]
    eng.run(reqs)
    return [r.output.tolist() for r in reqs], eng

PROMPTS = [np.asarray([1, 2, 3, 4, 5], np.int32),
           np.asarray([9, 8, 7], np.int32),
           np.asarray([4, 4, 2, 1, 1, 3, 2, 5, 6], np.int32),
           np.asarray([2, 7, 1], np.int32)]
"""


@pytest.mark.slow
def test_mesh_token_identity_sweep():
    """Mesh-vs-single-device greedy AND temperature token identity over
    {contiguous, paged} x {sharing on/off} x {host, device sched} x mesh
    shapes {(1,1), (2,1), (1,2), (2,2)} — plus the zero-steady-state-sync
    contract under the device-resident scheduler."""
    script = _PROLOGUE + """
MODES = (dict(),
         dict(paged=True, page_size=4, kv_pages=40),
         dict(paged=True, page_size=4, kv_pages=40,
              enable_prefix_sharing=True))
for mode in MODES:
    for dev in (True, False):
        base, _ = run_engine(PROMPTS, device_sched=dev, **mode)
        # the split-K decode formulation is itself token-identical on one
        # device (the sharded combine reproduces it bitwise)
        base_kv, _ = run_engine(PROMPTS, device_sched=dev, kv_splits=2,
                                **mode)
        assert base == base_kv, (mode, dev, "kv_splits single-device")
        for shape in ((1, 1), (2, 1), (1, 2), (2, 2)):
            mesh = make_mesh(shape, ("data", "model"))
            out, eng = run_engine(PROMPTS, device_sched=dev, mesh=mesh,
                                  shard_kv=shape[1] > 1, **mode)
            assert out == base, (mode, dev, shape, out, base)
            if dev:
                assert eng.stats["steady_state_syncs_per_block"] == 0.0, \\
                    (mode, shape, eng.stats)
            if eng.paged:
                assert eng.audit()["ok"]
print("IDENTITY_SWEEP_OK")
"""
    _run_sub(script, "IDENTITY_SWEEP_OK")


@pytest.mark.slow
def test_mesh_nondivisible_slots_and_kv():
    """3 requested slots on a 2-wide data axis pad the slot batch (padded
    lanes permanently disabled); max_seq=31 drives a non-divisible KV
    length through the split-K combine.  Tokens stay identical and the
    engine reports the requested capacity."""
    script = _PROLOGUE + """
prompts = PROMPTS + [np.asarray([5, 5, 5], np.int32)]
base, _ = run_engine(prompts, max_new=8, max_seq=31, batch_slots=3,
                     kv_splits=2)
mesh = make_mesh((2, 2), ("data", "model"))
out, eng = run_engine(prompts, max_new=8, max_seq=31, batch_slots=3,
                      mesh=mesh, shard_kv=True)
assert eng.slots == 4 and eng.requested_slots == 3, eng.slots
assert eng.slots_per_device == 2 and eng.mesh_shape == (2, 2)
assert out == base, (out, base)
# queueing semantics are those of the REQUESTED slot count: 5 requests on
# 3 usable slots force refills, never a 4th concurrent lane
assert eng.stats["mid_flight_admissions"] > 0
print("NONDIVISIBLE_OK")
"""
    _run_sub(script, "NONDIVISIBLE_OK")


@pytest.mark.slow
def test_mesh_prefix_sharing_grant_cow_audit():
    """Sharded prefix sharing: identical prompt prefixes land on BOTH data
    shards — per-shard trie namespacing must keep every grant (and CoW
    split) within the shard that wrote the pages, or shard-1 slots would
    alias garbage replicas.  Tokens stay identical, CoW fires, audit()
    stays clean across a resident second run (re-grant after sharded
    retire)."""
    script = _PROLOGUE + """
# donor covers 4 full pages; sharers diverge 2 tokens into page 3, so the
# share base (14) lands mid-page -> copy-on-write split of the boundary
donor = np.asarray(list(range(1, 18)), np.int32)
prompts = [donor] + [
    np.concatenate([donor[:14], np.asarray([90 + i, 80 + i], np.int32)])
    for i in range(7)]
kw = dict(batch_slots=4, paged=True, page_size=4, kv_pages=64,
          enable_prefix_sharing=True, prefill_chunk=2)

base, beng = run_engine(prompts, max_new=6, temps=False, **kw)
assert beng.stats["kv_cow_splits"] > 0  # the fixture really exercises CoW
mesh = make_mesh((2, 2), ("data", "model"))
out, eng = run_engine(prompts, max_new=6, temps=False, mesh=mesh,
                      shard_kv=True, **kw)
assert out == base, (out, base)
assert eng.stats["prefix_hits"] > 0 and eng.stats["kv_cow_splits"] > 0
assert eng.audit()["ok"]
# resident second run: sharded retire freed the slots; re-grants must stay
# namespaced to the readmitting slot's shard
from repro.serving import Request
reqs2 = [Request(prompt=p, max_new_tokens=6) for p in prompts[:4]]
eng.run(reqs2)
assert [r.output.tolist() for r in reqs2] == base[:4]
assert eng.audit()["ok"]
print("SHARING_COW_OK")
"""
    _run_sub(script, "SHARING_COW_OK")


@pytest.mark.slow
def test_mesh_splitk_combine_bitwise_real_mesh():
    """Kernel-level: decode_attention_splitk_sharded on a real multi-device
    mesh is bit-for-bit equal to single-device decode_attention_splitk with
    the same split count — prime and non-divisible KV lengths included."""
    script = """
import jax
import jax.numpy as jnp
import numpy as np
from repro.launch.mesh import make_mesh
from repro.kernels.decode_attention import ops as da_ops

for s in (257, 256, 101, 31):
    keys = jax.random.split(jax.random.PRNGKey(s), 3)
    q = jax.random.normal(keys[0], (1, 4, 1, 32), jnp.float32)
    k = jax.random.normal(keys[1], (1, 2, s, 32), jnp.float32)
    v = jax.random.normal(keys[2], (1, 2, s, 32), jnp.float32)
    clen = jnp.asarray(s - 3, jnp.int32)
    for mm in (2, 4):
        for K in (mm, 2 * mm):
            ref = da_ops.decode_attention_splitk(q, k, v, clen,
                                                 num_splits=K)
            mesh = make_mesh((mm,), ("model",))
            out = da_ops.decode_attention_splitk_sharded(
                q, k, v, clen, mesh=mesh, num_splits=K)
            assert np.array_equal(np.asarray(out), np.asarray(ref)), \\
                (s, mm, K)
print("SPLITK_MESH_BITWISE_OK")
"""
    _run_sub(script, "SPLITK_MESH_BITWISE_OK")


def test_mesh_smoke_2x2():
    """Fast multi-device smoke (the CI entry point): 2x2 mesh, paged +
    sharing, device-resident scheduling — token identity vs single device,
    zero steady-state syncs, audit clean."""
    script = _PROLOGUE + """
kw = dict(paged=True, page_size=4, kv_pages=40,
          enable_prefix_sharing=True)
base, _ = run_engine(PROMPTS, **kw)
mesh = make_mesh((2, 2), ("data", "model"))
out, eng = run_engine(PROMPTS, mesh=mesh, shard_kv=True, **kw)
assert out == base, (out, base)
assert eng.stats["steady_state_syncs_per_block"] == 0.0
assert eng.audit()["ok"]
assert eng.mesh_shape == (2, 2) and eng.slots_per_device == 2
print("MESH_SMOKE_2X2_OK")
"""
    _run_sub(script, "MESH_SMOKE_2X2_OK")


@pytest.mark.slow
def test_mesh_transient_faults_self_heal():
    """Seeded transient fault schedules on a SHARDED engine self-heal to
    all-OK/DEGRADED with tokens identical to the unsharded uninterrupted
    run (retry replay, degrade and mid-run re-promotion all cross the
    host/device ownership seam per-shard; audit_on_retire re-checks the
    refcount oracle at every transition)."""
    script = _PROLOGUE + """
from repro.serving import FaultInjector, Request, RequestStatus

KW = dict(max_seq=32, batch_slots=2, paged=True, page_size=4, kv_pages=24,
          enable_prefix_sharing=True)
REC = dict(max_retries=4, retry_backoff_s=0.0, retry_breaker_threshold=99,
           probe_cooldown_blocks=1, audit_on_retire=True)

def prompts(seed=0, n=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size,
                         size=int(rng.integers(3, 9))).astype(np.int32)
            for _ in range(n)]

def reqs(ps):
    return [Request(prompt=p, max_new_tokens=10) for p in ps]

beng = ServingEngine(cfg, packed, ctx=ctx, prefill_chunk=4,
                     decode_block=4, **KW)
brs = reqs(prompts())
beng.run(brs)
baseline = [r.output.tolist() for r in brs]

mesh = make_mesh((2, 2), ("data", "model"))
eng = ServingEngine(cfg, packed, ctx=ctx, prefill_chunk=4, decode_block=4,
                    mesh=mesh, shard_kv=True, **KW, **REC)
healed = retried = promoted = 0
for seed in range(4):
    fi = FaultInjector.random_schedule(seed, slots=2, n_faults=3,
                                       max_block=8, max_alloc=12,
                                       transient=True)
    eng.fault_injector = fi
    rs = reqs(prompts())
    eng.run(rs)
    for r, b in zip(rs, baseline):
        assert r.status in (RequestStatus.OK, RequestStatus.DEGRADED), \\
            (seed, r.status, r.error)
        assert r.output.tolist() == b, (seed, r.error)
    assert eng.audit()["ok"]
    healed += 1
    retried += eng.stats["retries_total"]
    promoted += eng.stats["repromotions"]
assert healed == 4 and retried > 0 and promoted > 0
print("MESH_FAULTS_HEAL_OK")
"""
    _run_sub(script, "MESH_FAULTS_HEAL_OK")


# -- in-process validation (no multi-device runtime needed) -----------------


def test_mesh_validation_errors():
    """Constructor contract: wrong axis names and bad split counts fail
    fast with actionable errors (runs on the 1-device pytest jax — a
    (1, 1) mesh is a real mesh)."""
    from repro.configs import get_config
    from repro.models import transformer
    from repro.serving import ServingEngine

    cfg = get_config("qwen1.5-0.5b").reduced()
    packed = transformer.pack_params(
        cfg, transformer.init_params(cfg, jax.random.PRNGKey(0)))
    bad = make_mesh((1, 1), ("x", "model"))
    with pytest.raises(ValueError, match="axis_names"):
        ServingEngine(cfg, packed, max_seq=16, mesh=bad)
    with pytest.raises(ValueError, match="kv_splits"):
        ServingEngine(cfg, packed, max_seq=16,
                      mesh=make_mesh((1, 1), ("data", "model")),
                      kv_splits=0)
    # a (1, 1) mesh engine is exactly the single-device engine's semantics
    eng = ServingEngine(cfg, packed, max_seq=16,
                        mesh=make_mesh((1, 1), ("data", "model")))
    assert eng.mesh_shape == (1, 1) and not eng.shard_slots \
        and not eng.shard_kv
