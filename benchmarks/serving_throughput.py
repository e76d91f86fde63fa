"""Serving throughput: fused decode vs per-tick baseline vs paged KV cache.

Up to three engine configurations over the same mixed workload, per slot
count:

  * ``fused``    — decode_block-tick `lax.scan` with on-device sampling +
    chunked in-place prefill over a contiguous slots x max_seq KV cache:
    one jit dispatch + one host sync per `decode_block` tokens per lane;
  * ``per_tick`` — decode_block=1 and whole-prompt chunks, i.e. the PR-1
    engine's dispatch pattern (one dispatch + full host sync per token, one
    prefill call per prompt);
  * ``paged``    — the fused hot path over the paged KV cache (global page
    pool + per-slot block tables, ``--paged``): KV memory scales with live
    tokens, reported as pool utilization, live-token peak and the number of
    slots schedulable at the contiguous configuration's KV budget;
  * ``paged_shared`` — paged plus prefix sharing
    (``enable_prefix_sharing=True``; runs when ``--shared-prefix-len N``
    gives every prompt a common N-token template prefix): repeated
    prefixes alias refcounted pages through the block tables instead of
    being re-prefilled, reported as prefix hit rate, prefill tokens
    skipped, pages shared, and tok/s / TTFT / pool-utilization deltas vs
    plain paged.  NB the trade: the prefix-aware holdback serializes
    followers behind the first donor's prefill, so on this CPU host —
    where prefill is cheap relative to blocked decode — aggregate tok/s
    and TTFT can REGRESS at low slot counts even as prefill compute and
    the unique-page footprint drop (the deltas report all of it; the
    wins grow with slot count and with real accelerator prefill cost,
    which is the regime the paper's capacity argument targets).
  * ``*_faults`` — with ``--inject-faults`` (or ``--inject-faults
    static``), the fused (and paged) configuration reruns under a
    deterministic *persistent* injected-fault schedule (one page-alloc
    failure, one NaN lane, one corrupted readback via
    ``serving.FaultInjector``): the poisoned requests retire FAILED, every
    other request completes, and the row's ``requests_*`` counters +
    ``faults_injected`` report the containment.
  * ``*_chaos`` — with ``--inject-faults transient`` (``all`` runs both
    vocabularies), the same configurations rerun under a *self-clearing*
    schedule (a device dispatch outage longer than the retry budget, then
    a NaN lane and a corrupted readback after it clears) against the
    self-healing engine: device scheduling + budgeted request retry with
    progress replay + mid-run re-promotion.  The in-benchmark assertions
    require total recovery — zero FAILED/TIMEOUT, >= 1 retry, >= 1 canary
    probe, >= 1 re-promotion, device breaker closed at exit — and the
    recovery gauges (``requests_retried`` / ``retries_total`` /
    ``retry_backoff_s`` / ``retries_denied_breaker`` / ``repromotions`` /
    ``canary_probes`` / ``breaker_state`` / ``retry_breaker_state``)
    appear on every row of every mode.
  * ``*_arrival`` — with ``--arrival-trace``, an open-loop configuration
    per slot count: requests are submitted to the RESIDENT engine at
    seeded exponential inter-arrival gaps (``--arrival-gap-ms``) through
    the ``submit()``/``step()`` surface instead of one batch ``run()``,
    the paper's edge-deployment shape (the engine is already warm when a
    request lands).  TTFT is measured from each request's arrival —
    reported via the explicit ``ttft_from_arrival_*`` keys, which exist
    on every row (batch rows measure from submit too; there arrival
    coincides with run start).  With ``--inject-faults transient`` a
    ``fused_chaos_arrival`` row replays the chaos schedule over the
    trace (arrivals land mid-degrade) and asserts zero FAILED/TIMEOUT.
  * ``*_mesh`` — with ``--mesh DD,MM``, the fused (and paged) configuration
    reruns on a (data=DD, model=MM) device mesh: the decode slot batch is
    sharded over 'data' (each device owns slots/DD lanes of every fused
    dispatch; non-divisible counts pad the slot axis and keep the
    requested capacity) and flash-decode KV attention over 'model'
    (canonical split-K partials + on-mesh partial-softmax combine, bitwise
    vs single-device).  Every row carries the schema-5 multi-device gauges
    (``mesh`` / ``shard_slots`` / ``shard_kv`` / ``kv_splits`` /
    ``slots_per_device`` / ``requested_slots`` — null/identity on
    single-device rows).  On this CPU host the devices come from
    ``xla_force_host_platform_device_count`` so the rows measure the
    sharded program's dispatch shape, not interconnect speed; token
    streams are identical to the single-device rows by construction.
  * ``*_device`` — with ``--device-sched``, each of the above reruns with
    the device-resident scheduler: slot bookkeeping lives in device arrays
    threaded block-to-block and the host reads results one block behind,
    so steady-state blocks dispatch with zero host round-trips.  Every row
    reports ``host_syncs_per_block`` (gating readbacks per dispatched
    block) and ``steady_state_syncs_per_block`` (the same count restricted
    to steady-state intervals — 1.0 host-driven, 0.0 device-resident).
    NB the same CPU-host caveat as prefix sharing: with interpret-mode
    kernels and zero real dispatch latency there is nothing to hide, while
    the one-block-behind pipeline pays up to one extra fully-masked block
    per retiring lane — so tok/s can regress here even as the sync count
    drops to zero.  The sync columns are the claim; the tok/s win needs an
    accelerator whose dispatch+readback latency is comparable to a block.

Mixed prompt/generation lengths stress mid-flight admission; the report
separates aggregate tok/s from decode-only tok/s (prefill wall time
excluded) and gives the per-request TTFT distribution.  CPU wall times on
the reduced BitNet — shape of the scaling, not absolute TPU numbers (the
Pallas kernels run in interpret mode on this host).

``--page-size`` tuning: pages are the KV allocation *and* kernel-block
granularity.  Small pages (4-8 tokens) track live tokens tightly — best
when many short requests share a tight pool — but mean more scalar-prefetch
entries and smaller DMA blocks; large pages (32+) amortize the block walk
but strand up to ``page_size - 1`` dead tokens per slot and defer
admissions earlier at a fixed pool.  16 is a good default at these shapes;
on real TPUs prefer the largest page that still keeps pool utilization
under ~90% for your workload mix.

Run:  PYTHONPATH=src python -m benchmarks.serving_throughput
JSON: PYTHONPATH=src python -m benchmarks.serving_throughput \
          --paged --json BENCH_serving.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _preparse_mesh(argv):
    """``--mesh DD,MM`` needs ``--xla_force_host_platform_device_count``
    set BEFORE jax initializes, so the mesh shape is pulled out of argv
    ahead of the real argparse run (which still owns validation/help)."""
    for i, a in enumerate(argv):
        if a == "--mesh" and i + 1 < len(argv):
            val = argv[i + 1]
        elif a.startswith("--mesh="):
            val = a.split("=", 1)[1]
        else:
            continue
        dd, mm = (int(x) for x in val.split(","))
        return dd, mm
    return None


_MESH_SHAPE = _preparse_mesh(sys.argv[1:])
if _MESH_SHAPE is not None:
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        _n = _MESH_SHAPE[0] * _MESH_SHAPE[1]
        os.environ["XLA_FLAGS"] = (
            _flags + f" --xla_force_host_platform_device_count={_n}").strip()

import jax
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.models import transformer
from repro.serving import FaultInjector, Request, ServingEngine

# bump when row keys change shape/meaning so trajectory tooling can key on
# it; 2 = robustness gauges (requests_* / degraded_blocks / faults_injected
# / watchdog_trips / sched_fallbacks on every row) + --inject-faults modes;
# 3 = recovery gauges (requests_retried / retries_total / retry_backoff_s /
# retries_denied_breaker / repromotions / canary_probes / breaker_state /
# retry_breaker_state on every row) + --inject-faults {static,transient,all}
# vocabulary with self-healing *_chaos rows;
# 4 = continuous serving: TTFT is measured from each request's ARRIVAL
# (submit time) rather than run start, reported via the explicit
# ttft_from_arrival_* keys + scheduler_beats / idle_sleeps on every row,
# and --arrival-trace adds open-loop *_arrival rows (arrival_trace /
# arrival_gap_ms) driven through the resident submit()/step() surface;
# 5 = multi-device serving: mesh / shard_slots / shard_kv / kv_splits /
# slots_per_device / requested_slots on every row (mesh is null on
# single-device rows) and --mesh DD,MM adds *_mesh rows where the slot
# batch is sharded over 'data' and flash-decode KV over 'model' — token
# streams stay identical to the single-device rows by construction
# 6 = host_decode_s (host seconds dispatching and reading back decode
# blocks) and beat_max_ms / beat_max_stage replace decode_tok_s
SCHEMA_VERSION = 6


def make_requests(rng, n, vocab, max_prompt, max_new, shared_prefix_len=0):
    """Mixed workload: prompt lengths in [4, max_prompt], generation lengths
    in [max_new//2, max_new] — requests finish at different ticks, forcing
    mid-flight admissions.  With ``shared_prefix_len`` every prompt starts
    with the same template prefix (the prompt-caching workload shape:
    system prompt / few-shot header + per-request tail)."""
    lo = min(4, max_prompt)
    tmpl = rng.integers(0, vocab, size=shared_prefix_len)
    reqs = []
    for _ in range(n):
        plen = int(rng.integers(lo, max_prompt + 1))
        if shared_prefix_len:
            tail = max(1, plen - shared_prefix_len)  # >= 1 divergent token
            prompt = np.concatenate(
                [tmpl, rng.integers(0, vocab, size=tail)]).astype(np.int64)
        else:
            prompt = rng.integers(0, vocab, size=plen)
        reqs.append(Request(
            prompt=prompt,
            max_new_tokens=int(rng.integers(max(1, max_new // 2),
                                            max_new + 1))))
    return reqs


def _drive_arrival_trace(eng, reqs, arrivals_s):
    """Open-loop client over the resident engine: submit each request the
    moment the wall clock passes its trace offset, stepping the scheduler
    in between, sleeping through genuinely idle gaps (no arrivals due, no
    work or only retry backoff).  Returns the total wall time."""
    t0 = time.perf_counter()
    idx = 0
    while idx < len(reqs) or eng.has_work:
        now = time.perf_counter() - t0
        while idx < len(reqs) and arrivals_s[idx] <= now:
            eng.submit(reqs[idx])
            idx += 1
        if not eng.has_work:
            time.sleep(max(0.0, t0 + arrivals_s[idx]
                           - time.perf_counter()))
            continue
        out = eng.step()
        if out.idle_until is not None:
            wake = out.idle_until
            if idx < len(reqs):
                wake = min(wake, t0 + arrivals_s[idx])
            wait = wake - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
    eng.drain()
    return time.perf_counter() - t0


def run_one(cfg, packed, *, slots, decode_block, prefill_chunk, n_requests,
            max_prompt, max_new, seed, mode, paged=False, page_size=16,
            kv_pages=None, shared_prefix_len=0, prefix_sharing=False,
            device_sched=False, fault_injector=None, engine_kw=None,
            arrival_gap_ms=None):
    rng = np.random.default_rng(seed)
    reqs = make_requests(rng, n_requests, cfg.vocab_size, max_prompt, max_new,
                         shared_prefix_len=shared_prefix_len)
    max_seq = max(max_prompt, shared_prefix_len + 1) + max_new
    eng = ServingEngine(cfg, packed, max_seq=max_seq,
                        batch_slots=slots, decode_block=decode_block,
                        prefill_chunk=prefill_chunk, paged=paged,
                        page_size=page_size, kv_pages=kv_pages,
                        enable_prefix_sharing=prefix_sharing,
                        device_sched=device_sched,
                        fault_injector=fault_injector,
                        **(engine_kw or {}))
    # warmup: chunked prefill + fused decode compile O(1) shapes, so two
    # tiny requests cover every program the timed run can hit.  The fault
    # schedule is disarmed for warmup (ordinals reset per run, so an armed
    # warmup would fire the measured run's faults).
    if fault_injector is not None:
        fault_injector.armed = False
    eng.run([Request(prompt=rng.integers(0, cfg.vocab_size, size=5),
                     max_new_tokens=2) for _ in range(2)])
    if fault_injector is not None:
        fault_injector.armed = True
    if arrival_gap_ms is None:
        t0 = time.perf_counter()
        eng.run(reqs)
        wall = time.perf_counter() - t0
    else:
        # open-loop arrival trace: seeded exponential inter-arrival gaps
        # submitted through the resident submit()/step() surface (run()
        # resets the window + per-run fault ordinals itself; here we do
        # both explicitly since the client owns the loop)
        eng.reset_stats()
        if fault_injector is not None:
            fault_injector.reset_run()
        gaps = rng.exponential(arrival_gap_ms / 1e3, size=len(reqs))
        wall = _drive_arrival_trace(eng, reqs, np.cumsum(gaps))
    s = eng.stats
    total = s["total_new_tokens"]
    util = (s["decode_tokens"] / (s["decode_steps"] * slots)
            if s["decode_steps"] else 1.0)
    # faulted/rejected requests have no TTFT; the distribution covers
    # the requests that produced a first token
    ttfts = np.asarray([r.ttft_s for r in reqs if r.ttft_s is not None])
    if not len(ttfts):
        ttfts = np.asarray([float("nan")])
    out = {
        "mode": mode,
        "slots": slots,
        "decode_block": decode_block,
        "prefill_chunk": eng.prefill_chunk,
        "tok_s": total / wall,
        # host seconds dispatching decode blocks and reading them back
        "host_decode_s": s["host_s"]["block"] + s["host_s"]["readback"],
        "beat_max_ms": s["beat_max_s"] * 1e3,
        "beat_max_stage": s["beat_max_stage"],
        "decode_blocks": s["decode_blocks"],
        "decode_steps": s["decode_steps"],
        "slot_util": util,
        "mid_flight": s["mid_flight_admissions"],
        "max_chunks_between_decode_blocks":
            s["max_chunks_between_decode_blocks"],
        "ttft_mean_ms": float(np.mean(ttfts)) * 1e3,
        "ttft_p50_ms": float(np.percentile(ttfts, 50)) * 1e3,
        "ttft_p90_ms": float(np.percentile(ttfts, 90)) * 1e3,
        "ttft_p95_ms": float(np.percentile(ttfts, 95)) * 1e3,
        # continuous-serving gauges (schema 4).  TTFT is measured from
        # each request's ARRIVAL (submit time) in every mode — under a
        # batch run() arrival coincides with run start, under an arrival
        # trace it includes only the request's own queueing — and the
        # explicit *_from_arrival keys document that clock for tooling
        # that must not guess from the mode name.
        "arrival_trace": arrival_gap_ms is not None,
        "arrival_gap_ms": arrival_gap_ms,
        "ttft_from_arrival_mean_ms": float(np.mean(ttfts)) * 1e3,
        "ttft_from_arrival_p50_ms": float(np.percentile(ttfts, 50)) * 1e3,
        "ttft_from_arrival_p95_ms": float(np.percentile(ttfts, 95)) * 1e3,
        "scheduler_beats": s["scheduler_beats"],
        "idle_sleeps": s["idle_sleeps"],
        # host-sync accounting (the device-resident scheduler's headline
        # metric): gating readbacks per dispatched block, plus the count
        # restricted to steady-state intervals (no admission/retire between
        # consecutive dispatches) — 1.0 for the host-driven engine, 0.0 for
        # the device-resident one
        "device_sched": device_sched,
        "host_block_syncs": s["host_block_syncs"],
        "host_syncs_per_block": s["host_syncs_per_block"],
        "steady_state_blocks": s["steady_state_blocks"],
        "steady_state_syncs_per_block": s["steady_state_syncs_per_block"],
        # robustness gauges — always present in every row, fault mode or
        # not, so downstream tooling can assert on the keys unconditionally
        "requests_completed": s["requests_completed"],
        "requests_rejected": s["requests_rejected"],
        "requests_failed": s["requests_failed"],
        "requests_timed_out": s["requests_timed_out"],
        "requests_cancelled": s["requests_cancelled"],
        "requests_degraded": s["requests_degraded"],
        "degraded_blocks": s["degraded_blocks"],
        "faults_injected": s["faults_injected"],
        "watchdog_trips": s["watchdog_trips"],
        "sched_fallbacks": s["sched_fallbacks"],
        "integrity_faults": s["integrity_faults"],
        # recovery gauges (schema 3) — budgeted retry with progress replay,
        # mid-run re-promotion, and the two circuit breakers; like the
        # robustness gauges they are present on every row unconditionally
        # multi-device gauges (schema 5) — null/identity on single-device
        # rows so tooling can assert on the keys unconditionally
        "mesh": (list(eng.mesh_shape) if eng.mesh is not None else None),
        "shard_slots": eng.shard_slots,
        "shard_kv": eng.shard_kv,
        "kv_splits": eng.kv_splits,
        "slots_per_device": eng.slots_per_device,
        "requested_slots": eng.requested_slots,
        "requests_retried": s["requests_retried"],
        "retries_total": s["retries_total"],
        "retry_backoff_s": s["retry_backoff_s"],
        "retries_denied_breaker": s["retries_denied_breaker"],
        "repromotions": s["repromotions"],
        "canary_probes": s["canary_probes"],
        "breaker_state": s["breaker_state"],
        "retry_breaker_state": s["retry_breaker_state"],
    }
    if paged:
        # schedulable slots at the contiguous configuration's KV budget:
        # contiguous provisioning pins ceil(max_seq / page) pages per slot
        # regardless of request length; paged admission only reserves each
        # request's worst case, so the same budget schedules budget /
        # mean(reservation) slots.  All derived metrics use the engine's
        # ACTUAL page size (it clamps to max_seq) and its own reservation
        # formula, so they cannot drift from the admission policy.
        ps = s["kv_page_size"]
        budget_pages = slots * -(-max_seq // ps)
        mean_res = float(np.mean([eng.worst_case_pages(r) for r in reqs]))
        out.update({
            "kv_page_size": ps,
            "kv_pool_pages": s["kv_pool_pages"],
            "kv_pages_peak": s["kv_pages_peak"],
            "kv_pool_util_peak": s["kv_pool_util_peak"],
            "kv_live_tokens_peak": s["kv_live_tokens_peak"],
            "kv_tokens_peak": s["kv_pages_peak"] * ps,
            "kv_tokens_contiguous": slots * max_seq,
            "admissions_deferred_pages": s["admissions_deferred_pages"],
            "fixed_budget_pages": budget_pages,
            "mean_reserved_pages_per_request": mean_res,
            "schedulable_slots_contiguous": slots,
            "schedulable_slots_paged": int(budget_pages // mean_res),
            # prefix-sharing gauges (zero when sharing is off — always
            # present so the CI smoke can assert on the keys)
            "prefix_hit_rate": s["prefix_hit_rate"],
            "prefill_tokens_skipped": s["prefill_tokens_skipped"],
            "kv_pages_shared": s["kv_pages_shared"],
            "kv_pages_shared_peak": s["kv_pages_shared_peak"],
            "kv_cow_splits": s["kv_cow_splits"],
            "kv_prefix_cached_pages": s["kv_prefix_cached_pages"],
            "prefix_evictions": s["prefix_evictions"],
            "admissions_held_for_prefix": s["admissions_held_for_prefix"],
        })
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n-requests", type=int, default=16)
    ap.add_argument("--max-prompt", type=int, default=48)
    ap.add_argument("--max-new", type=int, default=64)
    ap.add_argument("--slots", type=int, nargs="+", default=[1, 2, 4, 8])
    ap.add_argument("--decode-block", type=int, default=8)
    ap.add_argument("--prefill-chunk", type=int, default=56)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--skip-baseline", action="store_true",
                    help="only run the fused configuration")
    ap.add_argument("--paged", action="store_true",
                    help="also run the paged-KV configuration (page pool + "
                         "block tables) and report pool utilization")
    ap.add_argument("--page-size", type=int, default=16,
                    help="paged mode: tokens per KV page (allocation and "
                         "kernel-block granularity; small pages track live "
                         "tokens tightly, large pages amortize the block "
                         "walk — see the module docstring)")
    ap.add_argument("--kv-pages", type=int, default=None,
                    help="paged mode: total pool pages incl. the null page "
                         "(default: full provisioning, "
                         "slots*ceil(max_seq/page_size)+1)")
    ap.add_argument("--shared-prefix-len", type=int, default=0,
                    help="give every prompt this common template prefix "
                         "(the prompt-caching workload) and, with --paged, "
                         "also run the prefix-sharing engine "
                         "(enable_prefix_sharing=True) to report TTFT and "
                         "pool-utilization deltas vs plain paged")
    ap.add_argument("--inject-faults", nargs="?", const="static",
                    choices=("static", "transient", "all"), default=None,
                    help="also rerun the fused (and, with --paged, paged) "
                         "configuration under a deterministic fault "
                         "schedule.  'static' (the default when the flag "
                         "is given bare): persistent faults with retries "
                         "OFF (one page-alloc failure + one NaN lane + one "
                         "corrupted readback; modes suffixed _faults) — "
                         "the engine must finish every other request and "
                         "the row reports the requests_* status counters.  "
                         "'transient': a self-clearing schedule (device "
                         "dispatch outage + NaN lane + corrupted readback) "
                         "against the self-healing engine (budgeted retry "
                         "with progress replay, device scheduling, mid-run "
                         "re-promotion; modes suffixed _chaos) — every "
                         "request must terminate OK/DEGRADED with at "
                         "least one retry, one canary probe and one "
                         "re-promotion.  'all': both.")
    ap.add_argument("--arrival-trace", action="store_true",
                    help="also run an open-loop arrival-trace configuration "
                         "per slot count (mode fused_arrival): requests are "
                         "submitted to the RESIDENT engine at seeded "
                         "exponential inter-arrival gaps via submit()/step() "
                         "instead of one batch run(), and TTFT is reported "
                         "from each request's arrival.  With "
                         "--inject-faults transient (or all) a "
                         "fused_chaos_arrival row reruns the trace under "
                         "the self-clearing fault schedule + the "
                         "self-healing engine and asserts zero "
                         "FAILED/TIMEOUT")
    ap.add_argument("--arrival-gap-ms", type=float, default=25.0,
                    help="arrival-trace mode: mean exponential inter-"
                         "arrival gap in milliseconds")
    ap.add_argument("--device-sched", action="store_true",
                    help="also run each configuration with the device-"
                         "resident scheduler (slot bookkeeping threaded "
                         "through device arrays, one-block-behind host "
                         "readback; modes suffixed _device) and report the "
                         "per-block host-sync counts next to tok/s")
    ap.add_argument("--mesh", type=str, default=None, metavar="DD,MM",
                    help="also run each base configuration on a "
                         "(data=DD, model=MM) device mesh (modes suffixed "
                         "_mesh): the decode slot batch is sharded over "
                         "'data' and flash-decode KV attention over 'model' "
                         "(canonical split-K partials + on-mesh partial-"
                         "softmax combine).  On CPU hosts the devices are "
                         "forced via xla_force_host_platform_device_count "
                         "(set before jax initializes by pre-parsing this "
                         "flag), so the rows measure the sharded program's "
                         "dispatch shape, not real interconnect speed.  "
                         "Token streams are identical to the single-device "
                         "rows by construction — the in-benchmark assert "
                         "checks the per-device slot count and, with "
                         "--device-sched, the zero-steady-state-sync "
                         "contract under sharding")
    ap.add_argument("--json", type=str, default=None,
                    help="write results to this JSON file")
    args = ap.parse_args()
    enable_compile_cache()

    mesh = None
    if args.mesh:
        dd, mm = (int(x) for x in args.mesh.split(","))
        if dd < 1 or mm < 1:
            ap.error("--mesh axes must be >= 1")
        if dd * mm > jax.device_count():
            ap.error(f"--mesh {dd},{mm} needs {dd * mm} devices, have "
                     f"{jax.device_count()} (is XLA_FLAGS overriding "
                     "the forced host device count?)")
        mesh = make_mesh((dd, mm), ("data", "model"))

    cfg = get_config("bitnet-0.73b").reduced(
        n_layers=2, d_model=128, n_heads=4, d_ff=256, vocab_size=256)
    params = transformer.init_params(cfg, jax.random.PRNGKey(args.seed))
    packed = transformer.pack_params(cfg, params)
    common = dict(n_requests=args.n_requests, max_prompt=args.max_prompt,
                  max_new=args.max_new, seed=args.seed,
                  shared_prefix_len=args.shared_prefix_len)

    rows, speedup, paged_vs_fused, sharing_deltas = [], {}, {}, {}
    device_vs_host, mesh_vs_single = {}, {}
    cols = ("mode,slots,tok_s,host_decode_s,slot_util,mid_flight,"
            "ttft_p50_ms,ttft_p95_ms,decode_blocks,host_syncs_blk")
    print(cols)
    for slots in args.slots:
        fused = run_one(cfg, packed, slots=slots,
                        decode_block=args.decode_block,
                        prefill_chunk=args.prefill_chunk, mode="fused",
                        **common)
        configs = [fused]
        if args.device_sched:
            fused_dev = run_one(cfg, packed, slots=slots,
                                decode_block=args.decode_block,
                                prefill_chunk=args.prefill_chunk,
                                mode="fused_device", device_sched=True,
                                **common)
            configs.append(fused_dev)
            device_vs_host[str(slots)] = {
                "fused": fused_dev["tok_s"] / fused["tok_s"]}
        if not args.skip_baseline:
            per_tick = run_one(cfg, packed, slots=slots, decode_block=1,
                               prefill_chunk=args.max_prompt + args.max_new,
                               mode="per_tick", **common)
            configs.append(per_tick)
            speedup[str(slots)] = fused["tok_s"] / per_tick["tok_s"]
        if args.paged:
            paged = run_one(cfg, packed, slots=slots,
                            decode_block=args.decode_block,
                            prefill_chunk=args.prefill_chunk, mode="paged",
                            paged=True, page_size=args.page_size,
                            kv_pages=args.kv_pages, **common)
            configs.append(paged)
            paged_vs_fused[str(slots)] = paged["tok_s"] / fused["tok_s"]
            if args.device_sched:
                paged_dev = run_one(cfg, packed, slots=slots,
                                    decode_block=args.decode_block,
                                    prefill_chunk=args.prefill_chunk,
                                    mode="paged_device", paged=True,
                                    page_size=args.page_size,
                                    kv_pages=args.kv_pages,
                                    device_sched=True, **common)
                configs.append(paged_dev)
                device_vs_host[str(slots)]["paged"] = (
                    paged_dev["tok_s"] / paged["tok_s"])
            if args.shared_prefix_len:
                shared = run_one(cfg, packed, slots=slots,
                                 decode_block=args.decode_block,
                                 prefill_chunk=args.prefill_chunk,
                                 mode="paged_shared", paged=True,
                                 page_size=args.page_size,
                                 kv_pages=args.kv_pages,
                                 prefix_sharing=True, **common)
                configs.append(shared)
                if args.device_sched:
                    shared_dev = run_one(cfg, packed, slots=slots,
                                         decode_block=args.decode_block,
                                         prefill_chunk=args.prefill_chunk,
                                         mode="paged_shared_device",
                                         paged=True,
                                         page_size=args.page_size,
                                         kv_pages=args.kv_pages,
                                         prefix_sharing=True,
                                         device_sched=True, **common)
                    configs.append(shared_dev)
                    device_vs_host[str(slots)]["paged_shared"] = (
                        shared_dev["tok_s"] / shared["tok_s"])
                sharing_deltas[str(slots)] = {
                    "tok_s_delta": shared["tok_s"] - paged["tok_s"],
                    "host_decode_s_delta":
                        shared["host_decode_s"] - paged["host_decode_s"],
                    "ttft_p50_ms_delta":
                        shared["ttft_p50_ms"] - paged["ttft_p50_ms"],
                    "ttft_p95_ms_delta":
                        shared["ttft_p95_ms"] - paged["ttft_p95_ms"],
                    "kv_pages_peak_delta":
                        shared["kv_pages_peak"] - paged["kv_pages_peak"],
                    "kv_pool_util_peak_delta":
                        shared["kv_pool_util_peak"]
                        - paged["kv_pool_util_peak"],
                    "prefill_tokens_skipped":
                        shared["prefill_tokens_skipped"],
                    "prefix_hit_rate": shared["prefix_hit_rate"],
                }
        if mesh is not None:
            # sharded reruns of the base configurations: slot batch over
            # 'data', flash-decode KV over 'model'.  Tokens are identical
            # to the single-device rows by construction (the split-K
            # combine is bitwise and the scheduler semantics are those of
            # the requested slot count), so the rows exist to measure the
            # sharded dispatch shape and to pin the per-device slot count
            # + steady-state sync contract in the emitted JSON.
            mesh_kw = dict(mesh=mesh, shard_kv=mm > 1)
            fused_mesh = run_one(cfg, packed, slots=slots,
                                 decode_block=args.decode_block,
                                 prefill_chunk=args.prefill_chunk,
                                 mode="fused_mesh",
                                 device_sched=args.device_sched,
                                 engine_kw=mesh_kw, **common)
            assert fused_mesh["mesh"] == [dd, mm], fused_mesh
            assert fused_mesh["requested_slots"] == slots, fused_mesh
            if dd > 1:
                assert (fused_mesh["slots_per_device"] * dd
                        == -(-slots // dd) * dd), fused_mesh
            if args.device_sched:
                assert (fused_mesh["steady_state_syncs_per_block"]
                        == 0.0), fused_mesh
            configs.append(fused_mesh)
            base_cmp = fused_dev if args.device_sched else fused
            mesh_vs_single[str(slots)] = {
                "fused": fused_mesh["tok_s"] / base_cmp["tok_s"]}
            if args.paged:
                paged_mesh = run_one(cfg, packed, slots=slots,
                                     decode_block=args.decode_block,
                                     prefill_chunk=args.prefill_chunk,
                                     mode="paged_mesh", paged=True,
                                     page_size=args.page_size,
                                     kv_pages=args.kv_pages,
                                     prefix_sharing=bool(
                                         args.shared_prefix_len),
                                     device_sched=args.device_sched,
                                     engine_kw=mesh_kw, **common)
                assert paged_mesh["mesh"] == [dd, mm], paged_mesh
                configs.append(paged_mesh)
                pcmp = paged_dev if args.device_sched else paged
                mesh_vs_single[str(slots)]["paged"] = (
                    paged_mesh["tok_s"] / pcmp["tok_s"])
        if args.inject_faults in ("static", "all"):
            # deterministic schedule: an admission-time page-alloc fault, a
            # NaN lane mid-decode, and one corrupted readback.  Alloc
            # faults need the paged engine; the NaN/corrupt guards fire in
            # every mode.  The run must COMPLETE — every request ends with
            # a terminal status and the survivors finish OK.
            def _schedule():
                return (FaultInjector()
                        .fail_alloc(2)
                        .inject_nan(lane=min(1, slots - 1), block=1)
                        .corrupt_readback(3))
            fault_cfgs = [("fused_faults", {})]
            if args.paged:
                fault_cfgs.append(
                    ("paged_faults",
                     dict(paged=True, page_size=args.page_size,
                          kv_pages=args.kv_pages)))
            for fmode, fkw in fault_cfgs:
                frow = run_one(cfg, packed, slots=slots,
                               decode_block=args.decode_block,
                               prefill_chunk=args.prefill_chunk,
                               mode=fmode, fault_injector=_schedule(),
                               **fkw, **common)
                assert (frow["requests_completed"]
                        + frow["requests_failed"]
                        + frow["requests_degraded"]) == args.n_requests, (
                    "fault run did not terminate every request")
                configs.append(frow)
        if args.inject_faults in ("transient", "all"):
            # self-healing chaos: a transient schedule (a device dispatch
            # outage longer than the dispatch retry budget, a NaN lane and
            # a corrupted readback after the outage clears) against the
            # recovery-enabled engine — device scheduling so the outage
            # degrades to the host path, budgeted retries with progress
            # replay so poisoned requests re-queue, and a 1-block probe
            # cooldown so the canary re-promotes the moment the outage
            # clears.  The contract is total recovery: no FAILED, no
            # TIMEOUT, at least one retry, one canary and one
            # re-promotion actually exercised.
            def _chaos():
                return (FaultInjector()
                        .dispatch_outage(1, 3)
                        .inject_nan(lane=min(1, slots - 1), block=5)
                        .corrupt_readback(6))
            chaos_kw = dict(max_retries=3, retry_backoff_s=0.0,
                            probe_cooldown_blocks=1)
            chaos_cfgs = [("fused_chaos", {})]
            if args.paged:
                chaos_cfgs.append(
                    ("paged_chaos",
                     dict(paged=True, page_size=args.page_size,
                          kv_pages=args.kv_pages)))
            for cmode, ckw in chaos_cfgs:
                crow = run_one(cfg, packed, slots=slots,
                               decode_block=args.decode_block,
                               prefill_chunk=args.prefill_chunk,
                               mode=cmode, fault_injector=_chaos(),
                               device_sched=True, engine_kw=chaos_kw,
                               **ckw, **common)
                assert crow["requests_failed"] == 0, crow
                assert crow["requests_timed_out"] == 0, crow
                assert (crow["requests_completed"]
                        + crow["requests_degraded"]) == args.n_requests, (
                    "chaos run did not self-heal every request")
                assert crow["requests_retried"] >= 1, crow
                assert crow["canary_probes"] >= 1, crow
                assert crow["repromotions"] >= 1, crow
                assert crow["breaker_state"] == "closed", crow
                configs.append(crow)
        if args.arrival_trace:
            trace_cfgs = [("fused_arrival", {})]
            if args.inject_faults in ("transient", "all"):
                # the batch chaos schedule, replayed over the open-loop
                # trace: the outage degrades the run mid-trace, later
                # arrivals land on the degraded engine, and recovery must
                # still terminate every request OK/DEGRADED
                trace_cfgs.append(("fused_chaos_arrival", dict(
                    fault_injector=(FaultInjector()
                                    .dispatch_outage(1, 3)
                                    .inject_nan(lane=min(1, slots - 1),
                                                block=5)
                                    .corrupt_readback(6)),
                    device_sched=True,
                    engine_kw=dict(max_retries=3, retry_backoff_s=0.0,
                                   probe_cooldown_blocks=1))))
            for tmode, tkw in trace_cfgs:
                trow = run_one(cfg, packed, slots=slots,
                               decode_block=args.decode_block,
                               prefill_chunk=args.prefill_chunk,
                               mode=tmode,
                               arrival_gap_ms=args.arrival_gap_ms,
                               **tkw, **common)
                assert trow["arrival_trace"], trow
                assert trow["ttft_from_arrival_p95_ms"] >= 0.0, trow
                if "chaos" in tmode:
                    assert trow["requests_failed"] == 0, trow
                    assert trow["requests_timed_out"] == 0, trow
                    assert (trow["requests_completed"]
                            + trow["requests_degraded"]
                            ) == args.n_requests, (
                        "chaos arrival trace did not self-heal every "
                        "request")
                configs.append(trow)
        for r in configs:
            rows.append(r)
            print(f"{r['mode']},{r['slots']},{r['tok_s']:.1f},"
                  f"{r['host_decode_s']:.3f},{r['slot_util']:.2f},"
                  f"{r['mid_flight']},{r['ttft_p50_ms']:.0f},"
                  f"{r['ttft_p95_ms']:.0f},{r['decode_blocks']},"
                  f"{r['host_syncs_per_block']:.2f}")
        if args.device_sched:
            dv = device_vs_host[str(slots)]
            pairs = ", ".join(f"{k} {v:.2f}x" for k, v in dv.items())
            print(f"# slots={slots}: device-resident scheduler tok/s vs "
                  f"host-driven: {pairs}")
        if str(slots) in mesh_vs_single:
            mv = mesh_vs_single[str(slots)]
            pairs = ", ".join(f"{k} {v:.2f}x" for k, v in mv.items())
            print(f"# slots={slots}: ({dd},{mm}) mesh tok/s vs matching "
                  f"single-device row: {pairs} "
                  f"({fused_mesh['slots_per_device']} slots/device)")
        if str(slots) in speedup:
            print(f"# slots={slots}: fused vs per-tick speedup "
                  f"{speedup[str(slots)]:.2f}x")
        if args.paged:
            print(f"# slots={slots}: paged KV peak {paged['kv_tokens_peak']}"
                  f" tokens vs contiguous {paged['kv_tokens_contiguous']}"
                  f" (pool util {paged['kv_pool_util_peak']:.2f}); at this "
                  f"KV budget paged schedules "
                  f"{paged['schedulable_slots_paged']} slots vs "
                  f"{paged['schedulable_slots_contiguous']}")
            if args.shared_prefix_len:
                d = sharing_deltas[str(slots)]
                print(f"# slots={slots}: prefix sharing skipped "
                      f"{d['prefill_tokens_skipped']} prefill tokens "
                      f"(hit rate {d['prefix_hit_rate']:.2f}); tok/s "
                      f"{d['tok_s_delta']:+.0f}, TTFT p50 "
                      f"{d['ttft_p50_ms_delta']:+.0f} ms, pages peak "
                      f"{d['kv_pages_peak_delta']:+d}, pool util "
                      f"{d['kv_pool_util_peak_delta']:+.2f} vs plain paged")

    if args.json:
        payload = {
            "benchmark": "serving_throughput",
            "schema_version": SCHEMA_VERSION,
            "host": {"backend": jax.default_backend(),
                     "interpret_kernels": jax.default_backend() != "tpu"},
            "workload": {**common, "decode_block": args.decode_block,
                         "prefill_chunk": args.prefill_chunk,
                         "page_size": args.page_size if args.paged else None,
                         "mesh": [dd, mm] if mesh is not None else None},
            "results": rows,
            "speedup_fused_vs_per_tick": speedup,
            "speedup_paged_vs_fused": paged_vs_fused,
            "speedup_device_vs_host_sched": device_vs_host,
            "speedup_mesh_vs_single_device": mesh_vs_single,
            "prefix_sharing_deltas": sharing_deltas,
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"# wrote {args.json}")


if __name__ == "__main__":
    main()
