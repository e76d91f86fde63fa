#!/usr/bin/env python3
"""Bring-up smoke run of the ternary serving engine on TPU.

Drives bitnet-0.73b at its published widths (24 layers, d 1536, 24 heads,
FF 4096, vocab 32000; random weights from ``--seed``) through the entry
points a user calls, in one process, and checks every result:

  device   a TPU must be present (anything else exits non-zero here)
  kernels  every Pallas kernel the served path can select, compiled with
           ``interpret=False``, against its ``ref.py`` oracle
  model    prefill a 128-token prompt, then 16 cached ``decode_step``s, on a
           contiguous and on a paged cache, against one full ``forward``
           over the same 144 tokens (logits, not argmax)
  serve    ``ServingEngine`` answers 8 requests on 4 slots three ways:
           defaults, ``paged=True``, and the Pallas attention kernels;
           every request OK at full length, no fallback, mid-flight
           admissions, and a second round of the same requests on the
           same engine emits the same tokens

``--chips 4`` runs only the mesh phase instead: the ('data', 'model') mesh
engine with ``shard_kv=True`` on four chips against the single-device
engine with the same ``kv_splits``, token for token.

Each phase prints one line.  ``compile_s`` is the time JAX spent in
backend compilation (persistent-cache lookups included) during the phase
and ``run_s`` the rest of its wall time: smoke timings, not benchmark
metrics.  The last line is ``{"ok": true, "device": {...}}``; any failed
check exits non-zero before it.

Usage: python chip_smoke.py [--chips 4] [--seed 0]
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from typing import Callable

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import PAPER_ARCH, get_config  # noqa: E402
from repro.kernels.decode_attention import ops as da_ops  # noqa: E402
from repro.kernels.decode_attention import ref as da_ref  # noqa: E402
from repro.kernels.flash_prefill import ops as fp_ops  # noqa: E402
from repro.kernels.flash_prefill import ref as fp_ref  # noqa: E402
from repro.kernels.tlmm import ops as tlmm_ops  # noqa: E402
from repro.kernels.tlmm import ref as tlmm_ref  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models import transformer  # noqa: E402
from repro.models.layers import Ctx  # noqa: E402
from repro.serving import Request, RequestStatus, ServingEngine  # noqa: E402

# Written tolerances, as a fraction of the reference's largest magnitude.
# Attention kernels run their math in f32 against a "highest"-precision
# oracle; 2e-2 admits bf16-pass matmul rounding but not a wrong page, head
# or mask (those are O(1) of the output scale: q is drawn 3x wide so the
# softmax is peaked and outputs are O(1), not a near-zero mean over keys).
ATTN_TOL = 2e-2
# Cached prefill + decode vs one full forward, as the relative RMS error of
# all compared logit rows.  Summation order differs between the two, and the
# int8 absmax requantization of every linear's input turns a last-bit
# difference into a whole quantization step, so the paths never agree to
# f32 precision: reduced bitnet configs (2-24 layers, d 256-512) on CPU
# land at 0.15-1.9% with the bf16 cache, a decode shifted by one position
# at 3.0-10.7%, a wrong or stale cache at the order of 100%.
MODEL_TOL = 3e-2
# Serve-phase engine counters that must stay zero: a fallback or a
# degraded block would hide the device behind the host path.
ZERO_COUNTERS = ("sched_fallbacks", "degraded_blocks", "watchdog_trips")


class SmokeFailure(AssertionError):
    """A check of the smoke run failed."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# timing
# ---------------------------------------------------------------------------

class CompileClock:
    """Sums JAX's backend-compile durations (``jax.monitoring``) and counts
    persistent-cache hits and misses, so a phase can split its wall time
    into compile and run."""

    COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == self.COMPILE_EVENT:
            self.compile_s += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def phase(self, name: str, fn: Callable, *args, **kwargs):
        """Run one phase; print its line; return what it returned."""
        c0, h0, m0 = self.compile_s, self.cache_hits, self.cache_misses
        t0 = time.perf_counter()
        result, detail = fn(*args, **kwargs)
        wall = time.perf_counter() - t0
        comp = self.compile_s - c0
        print(f"[{name}] ok compile_s={comp:.3f} run_s={wall - comp:.3f} "
              f"cache_hits={self.cache_hits - h0} "
              f"cache_misses={self.cache_misses - m0} {detail}", flush=True)
        return result


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class KernelCase:
    """One served Pallas kernel at one shape: ``kernel(*args,
    interpret=...)`` vs ``ref(*args)``; ``make_args(key)`` draws the
    inputs (traceable, so ``jax.eval_shape`` gives the shapes alone)."""
    name: str
    kernel: Callable
    ref: Callable
    make_args: Callable
    tol: float


def _tlmm_case(name, cfg, m, n_in, n_out):
    g = cfg.group_size

    def make(key):
        ka, kc = jax.random.split(key)
        a = jax.random.randint(ka, (m, n_in), -127, 128, jnp.int32)
        codes = jax.random.randint(kc, (-(-n_in // g), n_out), 0, 3 ** g,
                                   jnp.int32)
        return a.astype(jnp.int8), codes.astype(jnp.uint8)

    return KernelCase(
        name, lambda a, c, interpret: tlmm_ops.tlmm(a, c, g=g, n=n_in,
                                                    interpret=interpret),
        lambda a, c: tlmm_ref.tlmm_ref(a, c, g, n_in), make, 0.0)


def _paged_chunk_ref(q, kp, vp, bt, off, kf, vf):
    """Gather each row's pages, overlay the chunk's fresh K/V at its
    offset, then run the contiguous chunk oracle."""
    k = da_ref.gather_pages_ref(kp, bt).astype(q.dtype)
    v = da_ref.gather_pages_ref(vp, bt).astype(q.dtype)
    put = jax.vmap(lambda row, new, o: jax.lax.dynamic_update_slice_in_dim(
        row, new.astype(row.dtype), o, axis=1))
    return fp_ref.chunk_attention_ref(q, put(k, kf, off), put(v, vf, off),
                                      off)


def kernel_cases(cfg, *, m: int = 8, slots: int = 8, max_seq: int = 1024,
                 page_size: int = 16, chunk: int = 32,
                 prompt: int = 128) -> list:
    """Every Pallas kernel the served path can select, at ``cfg``'s
    widths: ``tlmm`` for the fused QKV, o-proj, gate|up and down shapes at
    ``m`` rows, contiguous and paged decode attention (bf16 and int8
    pools), whole-prompt flash prefill, and contiguous and paged chunked
    prefill.  Activations are f32 and caches bf16, as the engine runs."""
    h, kv_h, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    d, ff = cfg.d_model, cfg.d_ff
    n_pages = max_seq // page_size
    pool = 1 + slots * n_pages
    f32, bf16 = jnp.float32, jnp.bfloat16

    def normal(key, shape, dtype=f32, scale=1.0):
        return (jax.random.normal(key, shape, f32) * scale).astype(dtype)

    def tables(key):
        perm = jax.random.permutation(key, jnp.arange(1, pool,
                                                      dtype=jnp.int32))
        return perm.reshape(slots, n_pages)

    def lens(key):
        return jax.random.randint(key, (slots,), 1, max_seq + 1, jnp.int32)

    def decode_args(key):
        kq, kk, kv, kl = jax.random.split(key, 4)
        return (normal(kq, (slots, h, 1, hd), scale=3.0),
                normal(kk, (slots, kv_h, max_seq, hd), bf16),
                normal(kv, (slots, kv_h, max_seq, hd), bf16), lens(kl))

    def paged_args(key):
        kq, kk, kv, kb, kl = jax.random.split(key, 5)
        shape = (pool, page_size, kv_h, hd)
        return (normal(kq, (slots, h, 1, hd), scale=3.0),
                normal(kk, shape, bf16), normal(kv, shape, bf16),
                tables(kb), lens(kl))

    def paged_quant_args(key):
        kq, kk, kv, ks, kvs, kb, kl = jax.random.split(key, 7)
        shape = (pool, page_size, kv_h, hd)

        def codes(k):
            return jax.random.randint(k, shape, -127, 128,
                                      jnp.int32).astype(jnp.int8)

        def scales(k):  # the null page never holds a live scale
            s = jax.random.uniform(k, shape[:3], f32, 0.0, 0.05)
            return s.at[0].set(0.0)

        return (normal(kq, (slots, h, 1, hd), scale=3.0), codes(kk),
                codes(kv), scales(ks), scales(kvs), tables(kb), lens(kl))

    def prefill_args(key):
        kq, kk, kv = jax.random.split(key, 3)
        return (normal(kq, (1, h, prompt, hd), scale=3.0),
                normal(kk, (1, kv_h, prompt, hd)),
                normal(kv, (1, kv_h, prompt, hd)))

    def offsets(key):
        return jax.random.randint(key, (slots,), 0, max_seq - chunk + 1,
                                  jnp.int32)

    def chunk_args(key):
        kq, kk, kv, ko = jax.random.split(key, 4)
        return (normal(kq, (slots, h, chunk, hd), scale=3.0),
                normal(kk, (slots, kv_h, max_seq, hd)),
                normal(kv, (slots, kv_h, max_seq, hd)), offsets(ko))

    def paged_chunk_args(key):
        kq, kk, kv, kb, ko, kf, kg = jax.random.split(key, 7)
        shape = (pool, page_size, kv_h, hd)
        return (normal(kq, (slots, h, chunk, hd), scale=3.0),
                normal(kk, shape, bf16), normal(kv, shape, bf16),
                tables(kb), offsets(ko),
                normal(kf, (slots, kv_h, chunk, hd)),
                normal(kg, (slots, kv_h, chunk, hd)))

    return [
        _tlmm_case("tlmm_qkv", cfg, m, d, cfg.q_dim + 2 * cfg.kv_dim),
        _tlmm_case("tlmm_o", cfg, m, cfg.q_dim, d),
        _tlmm_case("tlmm_gateup", cfg, m, d, 2 * ff),
        _tlmm_case("tlmm_down", cfg, m, ff, d),
        KernelCase("decode_attention", da_ops.decode_attention,
                   da_ref.decode_attention_ref, decode_args, ATTN_TOL),
        KernelCase("decode_attention_paged", da_ops.decode_attention_paged,
                   da_ref.paged_decode_attention_ref, paged_args, ATTN_TOL),
        KernelCase("decode_attention_paged_quant",
                   da_ops.decode_attention_paged_quant,
                   da_ref.paged_decode_attention_quant_ref,
                   paged_quant_args, ATTN_TOL),
        KernelCase("flash_prefill", fp_ops.flash_prefill,
                   fp_ref.attention_ref, prefill_args, ATTN_TOL),
        KernelCase("flash_chunk_prefill", fp_ops.flash_chunk_prefill,
                   fp_ref.chunk_attention_ref, chunk_args, ATTN_TOL),
        KernelCase("flash_chunk_prefill_paged",
                   fp_ops.flash_chunk_prefill_paged, _paged_chunk_ref,
                   paged_chunk_args, ATTN_TOL),
    ]


def abs_error_and_scale(got, want) -> tuple:
    """(max |got - want|, max |want|) in f64 on the host."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    check(got.shape == want.shape, f"shape {got.shape} != {want.shape}")
    check(bool(np.all(np.isfinite(got))), "non-finite output")
    return float(np.max(np.abs(got - want))), float(np.max(np.abs(want)))


def run_kernels(cases, *, interpret: bool, seed: int = 0):
    """Each case's kernel vs its oracle (computed at "highest" matmul
    precision); fails on the first case out of tolerance."""
    parts = []
    for i, case in enumerate(cases):
        args = case.make_args(jax.random.PRNGKey(seed * 1000 + i))
        kern = jax.jit(lambda *a, _k=case.kernel: _k(*a, interpret=interpret))
        got = jax.block_until_ready(kern(*args))
        # op by op, not jitted: fusion may keep a bf16 product in f32 and
        # skip the dequant rounding the int8 kernel reproduces
        with jax.default_matmul_precision("highest"):
            want = case.ref(*args)
        err, scale = abs_error_and_scale(got, want)
        check(err <= case.tol * scale,
              f"kernel {case.name}: max abs err {err:.3e} > "
              f"{case.tol} x {scale:.3e}")
        parts.append(f"{case.name}={err:.2e}/{case.tol * scale:.2e}")
    return None, "max_abs_err/tol " + " ".join(parts)


# ---------------------------------------------------------------------------
# model
# ---------------------------------------------------------------------------

def serving_ctx(cfg, **kw) -> Ctx:
    """The engine's default execution context, with overrides."""
    return Ctx(mode="packed", group_size=cfg.group_size, attn_q_chunk=128,
               attn_kv_chunk=128, **kw)


def make_params(cfg, seed: int):
    """Random weights from ``seed``, packed offline to base-3 codes (one
    jitted program, so the dense f32 weights never outlive it)."""
    return jax.jit(lambda key: transformer.pack_params(
        cfg, transformer.init_params(cfg, key)))(jax.random.PRNGKey(seed))


def run_model(cfg, packed, *, seed: int = 0, prompt: int = 128,
              steps: int = 16, page_size: int = 16):
    """Prefill ``prompt`` tokens then ``steps`` teacher-forced cached decode
    steps, on a contiguous and on a paged cache; every step's logits vs the
    same position of one full ``forward`` over ``prompt + steps`` tokens."""
    ctx = serving_ctx(cfg)
    total = prompt + steps
    toks = jax.random.randint(jax.random.PRNGKey(seed + 1), (1, total), 0,
                              cfg.vocab_size, jnp.int32)
    params = jax.jit(lambda p: transformer.predecode_packed(cfg, p))(packed)
    ref = jax.jit(lambda p, t: transformer.forward(cfg, p, t, ctx,
                                                   remat=False))(params, toks)
    ref = np.asarray(ref[0, prompt - 1:], np.float64)   # (steps + 1, vocab)

    n_pages = -(-total // page_size)
    perm = np.random.default_rng(seed).permutation(np.arange(1, n_pages + 1))
    bt = jnp.asarray(perm[None], jnp.int32)
    prefill = {
        "contiguous": jax.jit(lambda p, t, c: transformer.prefill_step(
            cfg, p, t, ctx, c), donate_argnums=(2,)),
        "paged": jax.jit(lambda p, t, c: transformer.prefill_chunk(
            cfg, p, t, ctx, c, offsets=jnp.zeros((1,), jnp.int32),
            admit_mask=jnp.ones((1,), jnp.bool_),
            last_index=jnp.full((1,), prompt - 1, jnp.int32),
            page_table=bt), donate_argnums=(2,)),
    }
    decode = {
        "contiguous": jax.jit(lambda p, t, c, n: transformer.decode_step(
            cfg, p, t, ctx, c, n), donate_argnums=(2,)),
        "paged": jax.jit(lambda p, t, c, n: transformer.decode_step(
            cfg, p, t, ctx, c, n, page_table=bt), donate_argnums=(2,)),
    }
    caches = {
        "contiguous": lambda: transformer.init_cache(cfg, 1, total),
        "paged": lambda: transformer.init_paged_cache(cfg, n_pages + 1,
                                                      page_size),
    }
    parts = []
    for kind in ("contiguous", "paged"):
        logits, cache = prefill[kind](params, toks[:, :prompt],
                                      caches[kind]())
        rows = [logits[0]]
        for i in range(steps):
            pos = jnp.full((1,), prompt + i, jnp.int32)
            logits, cache = decode[kind](params, toks[:, prompt + i:
                                                      prompt + i + 1],
                                         cache, pos)
            rows.append(logits[0])
        got = np.asarray(jnp.stack(rows), np.float64)
        check(bool(np.all(np.isfinite(got))), f"model {kind}: non-finite")
        rel = float(np.linalg.norm(got - ref) / np.linalg.norm(ref))
        check(rel <= MODEL_TOL, f"model {kind}: relative RMS logit error "
                                f"{rel:.3e} > {MODEL_TOL}")
        parts.append(f"{kind}:rel_rms={rel:.3e},max_abs="
                     f"{np.max(np.abs(got - ref)):.3e}")
    return None, (f"prompt={prompt} steps={steps} tol_rel_rms={MODEL_TOL} "
                  f"max_abs_logit={np.max(np.abs(ref)):.3e} "
                  + " ".join(parts))


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def make_requests(cfg, *, seed: int, n: int = 8, lo: int = 64,
                  hi: int = 128, max_new: int = 32,
                  temperature: float = 0.0) -> list:
    """``n`` requests with prompt lengths drawn from [lo, hi]; with
    ``temperature`` the odd-numbered ones sample instead of argmax."""
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        plen = int(rng.integers(lo, hi + 1))
        prompt = rng.integers(0, cfg.vocab_size, size=plen).astype(np.int32)
        reqs.append(Request(prompt=prompt, max_new_tokens=max_new,
                            temperature=temperature if i % 2 else 0.0))
    return reqs


def serve(cfg, packed, *, seed: int, slots: int = 4, lo: int = 64,
          hi: int = 128, max_new: int = 32, temperature: float = 0.0,
          runs: int = 1, **engine_kw):
    """One engine answers ``runs`` rounds of the same fresh requests; each
    round is held to the served-path contract and must emit the tokens of
    the first (later rounds reuse the compiled programs).  Returns
    (outputs, engine)."""
    eng = ServingEngine(cfg, packed, max_seq=hi + max_new,
                        batch_slots=slots, seed=seed, **engine_kw)
    first = None
    for _ in range(runs):
        reqs = make_requests(cfg, seed=seed, lo=lo, hi=hi, max_new=max_new,
                             temperature=temperature)
        eng.run(reqs)
        for i, r in enumerate(reqs):
            check(r.done and r.status == RequestStatus.OK,
                  f"request {i}: status {r.status} ({r.error})")
            check(len(r.output) == max_new,
                  f"request {i}: {len(r.output)} of {max_new} tokens")
            check(bool(np.all((r.output >= 0)
                              & (r.output < cfg.vocab_size))),
                  f"request {i}: token id out of range")
        for key in ZERO_COUNTERS:
            check(eng.stats[key] == 0,
                  f"engine stat {key}={eng.stats[key]}")
        check(eng.stats["mid_flight_admissions"] > 0,
              "no mid-flight admission: continuous batching never engaged")
        out = [r.output.tolist() for r in reqs]
        first = out if first is None else first
        check(out == first, "a repeated run emitted different tokens")
    return first, eng


def serve_variants(cfg) -> dict:
    """The three served configurations of the serve phase."""
    return {
        "default": {},
        "paged": {"paged": True},
        "pallas": {"ctx": serving_ctx(cfg, impl="pallas",
                                      attn_impl="pallas")},
    }


def run_serve(cfg, packed, *, seed: int = 0, **sizes):
    """Each variant: one engine, two rounds of the same requests (the first
    compiles)."""
    parts = []
    for name, kw in serve_variants(cfg).items():
        out, eng = serve(cfg, packed, seed=seed, runs=2, **kw, **sizes)
        parts.append(f"{name}:tokens={sum(map(len, out))},mid_flight="
                     f"{eng.stats['mid_flight_admissions']}")
    return None, ("requests=8 slots=4 all OK, zero fallbacks "
                  + " ".join(parts))


# ---------------------------------------------------------------------------
# mesh (--chips 4)
# ---------------------------------------------------------------------------

def _spans_mesh(tree, n_devices: int) -> bool:
    return all(len(leaf.sharding.device_set) == n_devices
               for leaf in jax.tree_util.tree_leaves(tree))


def run_mesh(cfg, packed, *, seed: int = 0, **sizes):
    """The (2, 2) ('data', 'model') mesh engine with ``shard_kv=True``
    against the single-device engine with the same ``kv_splits``, greedy
    and temperature requests, contiguous and paged: every token equal (the
    contract of tests/test_multidevice.py), and params and KV cache placed
    over all four devices."""
    mesh = make_mesh((2, 2), ("data", "model"))
    n = mesh.devices.size
    parts = []
    for name, kw in (("contiguous", {}), ("paged", {"paged": True})):
        base, _ = serve(cfg, packed, seed=seed, temperature=0.7,
                        kv_splits=2, **kw, **sizes)
        out, eng = serve(cfg, packed, seed=seed, temperature=0.7,
                         mesh=mesh, shard_kv=True, **kw, **sizes)
        check(_spans_mesh(eng.params, n), f"mesh {name}: params not "
                                          f"placed over {n} devices")
        check(_spans_mesh(eng._cache, n), f"mesh {name}: KV cache not "
                                          f"placed over {n} devices")
        diff = [i for i, (a, b) in enumerate(zip(out, base)) if a != b]
        check(not diff, f"mesh {name}: requests {diff} differ from the "
                        f"single-device engine: "
                        + "; ".join(f"{i}: mesh {out[i]} vs one device "
                                    f"{base[i]}" for i in diff))
        parts.append(f"{name}:identical_tokens={sum(map(len, out))}")
    return None, f"mesh=(2,2) shard_kv=True kv_splits=2 " + " ".join(parts)


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------

def require_tpu(chips: int):
    """The first check: a TPU with at least ``chips`` devices, else exit."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (JAX sees {devs[0].platform}); "
                 "nothing was run")
    if len(devs) < chips:
        sys.exit(f"chip_smoke: --chips {chips} but JAX sees {len(devs)}")
    return devs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip mesh phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devs = require_tpu(args.chips)
    cache = enable_compile_cache()
    clock = CompileClock()
    entries = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    print(f"[device] ok platform={devs[0].platform} "
          f"kind={devs[0].device_kind} count={len(devs)} "
          f"jax={jax.__version__} compile_cache={cache} "
          f"cache_entries={entries}", flush=True)

    cfg = get_config(PAPER_ARCH)
    packed = clock.phase("params", lambda: (make_params(cfg, args.seed),
                                            f"arch={cfg.name} seed="
                                            f"{args.seed}"))
    if args.chips == 4:
        clock.phase("mesh", run_mesh, cfg, packed, seed=args.seed)
    else:
        clock.phase("kernels", run_kernels, kernel_cases(cfg),
                    interpret=False, seed=args.seed)
        clock.phase("model", run_model, cfg, packed, seed=args.seed)
        clock.phase("serve", run_serve, cfg, packed, seed=args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
