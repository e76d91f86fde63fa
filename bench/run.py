#!/usr/bin/env python3
"""Runs one cell of the benchmark once and prints its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is the ``workloads`` entry of ``BENCHMARK.json`` named
``--workload``.  Its configuration (``bench/configs/<config>.json``), its
traffic mix (``bench/traffic/<traffic>.json``), its engine settings and
limits (``bench/cells/<workload>.json``) and its per-layer metrics
(``bench/metrics/<metric>.py``) are found by name, so a new cell or metric
adds files and entries and edits none.

Set-up makes the weights on the chip from the seed, builds the serving
engine with the cell's settings and warms up the programs the cell's
traffic uses; then the window opens for ``--seconds``.  With ``--trace 1``
a profiler trace of a few seconds of the window gives the per-layer
metrics; without it, the end-to-end ones.  After the window every request
sent in it is finished and checked against the plain reference (see
``bench/check.py``).  The last line of standard output is one JSON object;
the numbers compared, each beside its limit, are the last lines of
standard error and the last key of that object.  Without a TPU, or with
fewer chips than the cell asks for, it exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)  # importing bench puts the program's src/ on it

import jax  # noqa: E402
import numpy as np  # noqa: E402

from bench import archs, check, serve, trace_scopes, weights  # noqa: E402
from bench.clock import CompileClock  # noqa: E402
from bench.peaks import peaks  # noqa: E402
from bench.traffic import Traffic  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.serving import Request, ServingEngine  # noqa: E402

TRACE_LEAD_S = 2.0   # the trace starts this far into the window ...
TRACE_MAX_S = 5.0    # ... and lasts at most this long


def load_cell(workload: str, benchmark: str = None) -> dict:
    """Everything that names one cell, read from the files by name."""
    benchmark = benchmark or os.path.join(ROOT, "BENCHMARK.json")
    with open(benchmark) as f:
        spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"run.py: no workload {workload!r} in {benchmark}")
    w = cells[workload]

    def read(*parts):
        with open(os.path.join(BENCH, *parts)) as f:
            return json.load(f)

    metrics = [m for m in spec["per_layer"]
               if workload in m.get("workloads", [workload])]
    return {"workload": w, "cfg": read("configs", w["config"] + ".json"),
            "mix": read("traffic", w["traffic"] + ".json"),
            "cell": read("cells", workload + ".json"),
            "end_to_end": [m for m in spec["end_to_end"]
                           if workload in m.get("workloads", [workload])],
            "per_layer": metrics}


class Tracer:
    """Takes one profiler trace of part of the window, from inside it."""

    def __init__(self, engine, seconds: float):
        self.engine = engine
        self.lead = min(TRACE_LEAD_S, seconds / 4)
        self.length = min(TRACE_MAX_S, seconds / 2)
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.t0 = self.t1 = None
        self.stats0 = self.stats1 = None
        self._span = None

    def span(self, name):
        return jax.profiler.TraceAnnotation(name)

    def _counters(self):
        st = self.engine.stats
        return {k: st[k] for k in ("decode_tokens", "decode_blocks",
                                   "prefill_chunks", "admissions")}

    def start(self):
        """Start the profiler before the window opens: starting it takes
        long enough to stall the window, and the trace is read only
        between the ``bench.traced`` span's ends."""
        jax.profiler.start_trace(self.dir)

    def tick(self, now, t0):
        if self.t0 is None and now >= t0 + self.lead:
            self._span = jax.profiler.TraceAnnotation("bench.traced")
            self._span.__enter__()
            self.stats0 = self._counters()
            self.t0 = time.perf_counter()
        elif (self.t0 is not None and self.t1 is None
              and now >= self.t0 + self.length):
            self.stop()

    def stop(self):
        if self.t1 is not None:
            return
        if self.t0 is not None:
            self.t1 = time.perf_counter()
            self.stats1 = self._counters()
            self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.t1 = self.t1 or time.perf_counter()
        self.engine = None  # the engine is freed before the reference runs

    def reduce(self, scopes: tuple) -> dict:
        """The trace reduced by ``trace_scopes``, ops attributed to the
        innermost of ``scopes``; the trace's files are removed."""
        try:
            paths = glob.glob(os.path.join(self.dir, "plugins", "profile",
                                           "*", "*.xplane.pb"))
            if not paths:
                raise RuntimeError("the profiler wrote no trace")
            return trace_scopes.reduce(trace_scopes.load(paths[0], scopes),
                                       scopes=scopes)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def traced_run(tracer, summary, win, cfg, engine_cfg, peaks) -> dict:
    """What the per-layer metrics read, over the traced part of the window."""
    lo, hi = tracer.t0, tracer.t1
    contexts, prompts = [], []
    for s in win.served:
        if s.first_t is not None and lo <= s.first_t < hi:
            prompts.append(len(s.prompt))
    for s in win.served:
        for j, t in enumerate(s.token_t):
            if j and lo <= t < hi:  # token j > 0 is decoded at context
                contexts.append(len(s.prompt) + j)
    return {"summary": summary, "cfg": cfg, "engine": engine_cfg,
            "stats": {k: tracer.stats1[k] - tracer.stats0[k]
                      for k in tracer.stats0},
            "decode_contexts": contexts, "prompt_lengths": prompts,
            "peaks": peaks}


def read_per_layer(per_layer: list, run: dict) -> dict:
    """Each per-layer metric its reader finds something to read in."""
    metrics = {}
    for m in per_layer:
        mod = importlib.import_module(f"bench.metrics.{m['name']}")
        value = mod.read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics


def trace_breakdown(summary: dict) -> dict:
    """The ops, idle gaps and name scopes that took the traced window."""
    return {"device_ops": [list(x) for x in summary["device_ops"]],
            "idle_gaps": summary["idle_gaps"],
            "device_scopes": trace_scopes.top_scopes(summary["scopes"])}


def warm_up(engine, cell: dict, vocab: int) -> None:
    """Compile every program the cell's traffic drives: one request long
    enough for an admission wave, a decode block and a retirement."""
    eng = cell["engine"]
    n = min(eng["prefill_chunk"] + 1, eng["max_seq"] // 2)
    prompt = (np.arange(n) % vocab).astype(np.int32)
    reqs = [Request(prompt=prompt, max_new_tokens=eng["decode_block"] + 2,
                    temperature=0.0, seed=0) for _ in range(2)]
    engine.run(reqs)
    engine.reset_stats()


def run_cell(spec: dict, seed: int, seconds: float, trace: bool,
             clock=None, t_start: float = None,
             controls: bool = False) -> dict:
    """One run of one cell on the default device; returns the result.
    ``controls`` adds, under ``"controls"``, each control's checks with its
    tokens standing in for the served ones, and whether they passed."""

    t_start = T_START if t_start is None else t_start
    cfg, cell = spec["cfg"], spec["cell"]
    dev = jax.devices()[0]
    params = weights.make_weights(cfg, seed)
    jax.block_until_ready(params)
    engine = ServingEngine(weights.model_config(cfg), params, seed=seed,
                           **cell["engine"])
    warm_up(engine, cell, cfg["vocab_size"])
    traffic = Traffic(spec["mix"], cfg["vocab_size"], seed)
    setup_s = time.perf_counter() - t_start

    tracer = Tracer(engine, seconds) if trace else None
    if tracer:
        tracer.start()
    programs0 = clock.programs() if clock else 0
    win = serve.drive(engine, traffic, seconds,
                      span=tracer.span if tracer else serve.no_span,
                      on_tick=tracer.tick if tracer else None)
    if tracer:
        tracer.stop()
    compiles = (clock.programs() - programs0) if clock else 0
    stats = dict(engine.stats)
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    e2e = serve.end_to_end(win)
    del engine
    gc.collect()

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": jax.device_count(), "memory_peak_bytes": mem}
    metrics, breakdown = {}, None
    if tracer:
        summary = tracer.reduce(archs.scopes(cfg))
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        run = traced_run(tracer, summary, win, cfg, cell["engine"],
                         peaks(dev.device_kind) if dev.platform == "tpu"
                         else spec.get("peaks"))
        metrics = read_per_layer(spec["per_layer"], run)
        breakdown = trace_breakdown(summary)
    else:
        for m in spec["end_to_end"]:
            value = setup_s if m["name"] == "setup_s" else e2e[m["name"]]
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    checks, stand_ins = check.check(cfg, params, win.served, cell, traffic,
                                    seed, controls=controls)
    lateness = np.asarray(win.lateness_s or [0.0])
    attempted = len(win.served)
    return {
        "correct": check.passed(checks) and compiles == 0,
        "attempted": attempted,
        "failed": checks["failed_requests"]["value"],
        "metrics": metrics,
        "device": device,
        **({"breakdown": breakdown} if breakdown else {}),
        **({"controls": {name: {"correct": check.passed(c), "checks": c}
                         for name, c in stand_ins.items()}}
           if controls else {}),
        "info": {"compiles_in_window": compiles, "setup_s": setup_s,
                 "requests_in_ttft": e2e["n_ttft"],
                 "requests_in_tpot": e2e["n_tpot"],
                 "tokens_in_window": e2e["n_tokens"],
                 "lateness_p95_ms": float(np.percentile(lateness, 95)) * 1e3,
                 "lateness_max_ms": float(lateness.max()) * 1e3,
                 "engine": {k: stats[k] for k in (
                     "admissions", "prefill_chunks", "decode_blocks",
                     "decode_tokens", "sched_fallbacks", "degraded_blocks",
                     "host_s", "beat_max_s", "beat_max_stage")}},
        "checks": checks,
    }


def require_chips(chips: int):
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise SystemExit(f"run.py: JAX found no device: {e}")
    if devs[0].platform != "tpu":
        raise SystemExit(f"run.py: no TPU (JAX sees {devs[0].platform}); "
                         "nothing was run")
    if len(devs) < chips:
        raise SystemExit(f"run.py: the cell needs {chips} chips, JAX sees "
                         f"{len(devs)}")


def configure_cache() -> None:
    """The persistent compilation cache in the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), keeping every program however
    fast it compiled, so a second run compiles nothing."""
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def print_result(result: dict) -> None:
    info = result.pop("info")
    print(f"[run] {json.dumps(info)}", file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"[check] {name}={c['value']} limit={c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        raise SystemExit("run.py: --seed must be a whole number >= 0")

    spec = load_cell(args.workload)
    require_chips(spec["workload"]["chips"])
    configure_cache()
    clock = CompileClock()
    result = run_cell(spec, args.seed, args.seconds, bool(args.trace),
                      clock=clock)
    print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
