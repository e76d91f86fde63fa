"""bench/trace_scopes.py on synthetic traces, on the serving engine's own
spans recorded on the CPU, and on the traces recorded on a TPU v5e."""

import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import archs, trace_reduce, trace_scopes, weights
from bench.tests import helpers
from repro.serving import Request, ServingEngine
from repro.serving.engine import STAGES

HERE = os.path.dirname(__file__)
OLD = os.path.join(HERE, "trace_v5e_edge.json")
SCOPED = os.path.join(HERE, "trace_v5e_scoped.json")

# -- synthetic traces ---------------------------------------------------------

MS = 1_000_000  # nanoseconds


def synthetic():
    """One device: a wave program, then a decode block whose loop holds a
    body op with a nested op; host spans of two beats around them."""
    ops = [["fusion.1", 1 * MS, 4 * MS],          # the wave's one op
           ["while.5", 11 * MS, 10 * MS],         # the block's loop ...
           ["fusion.7", 12 * MS, 2 * MS],         # ... its body ops
           ["while.9", 15 * MS, 5 * MS],          # a nested loop ...
           ["fusion.8", 16 * MS, 3 * MS],         # ... and its body op
           ["copy.2", 21 * MS, 1 * MS]]           # after the loop
    mods = [["jit__prefill_chunks(7)", 1 * MS, 4 * MS],
            ["jit__decode_block_dev(9)", 10 * MS, 12 * MS]]
    paths = {"jit__decode_block_dev(9)": {
        "while.5": "jit(_decode_block_dev)/while",
        "fusion.7": "jit(_decode_block_dev)/while/body/attention/qkv/dot",
        "fusion.8": "jit(_decode_block_dev)/while/body/while/body/down/x",
        "copy.2": "jit(_decode_block_dev)/predecode/vmap/copy"}}
    host = [["bench.traced", 0, 30 * MS],
            ["engine.wave", 0, MS // 2],          # dispatched nothing
            ["engine.wave", MS // 2, MS // 4],    # dispatches fusion.1
            ["bench.step", 5 * MS, 5 * MS],
            ["engine.readback", 5 * MS + MS // 2, 4 * MS]]
    meta = [{}, {}, {"requests": "3 4"}, {}, {"retired": "2"}]
    return {"devices": [{"name": "/device:TPU:0", "modules": mods,
                         "ops": ops}],
            "host": host, "host_meta": meta, "op_paths": paths}


def test_self_time_is_the_interval_less_nested_ops():
    own = {o[0]: o[3] for o in
           trace_scopes.self_times(synthetic()["devices"][0]["ops"],
                                   0, 30 * MS)}
    assert own == {"fusion.1": 4 * MS, "while.5": 10 * MS - 2 * MS - 5 * MS,
                   "fusion.7": 2 * MS, "while.9": 5 * MS - 3 * MS,
                   "fusion.8": 3 * MS, "copy.2": 1 * MS}
    # within a window that cuts the loop, only the part inside counts
    cut = {o[0]: o[4] for o in
           trace_scopes.self_times(synthetic()["devices"][0]["ops"],
                                   0, 17 * MS)}
    assert cut["while.5"] == (17 - 11) * MS - 2 * MS - 2 * MS
    assert cut["fusion.8"] == 1 * MS and cut["copy.2"] == 0


def test_device_ops_rank_self_time_so_loops_fall_back():
    r = trace_scopes.reduce(synthetic())
    names = [n for n, _ in r["device_ops"]]
    assert names[0] == "fusion.1"
    assert sum(t for _, t in r["device_ops"]) == pytest.approx(
        r["busy_s"])  # self times tile the busy time exactly
    inclusive = trace_reduce.reduce(synthetic())["device_ops"]
    assert inclusive[0][0] == "while.5"


def test_scopes_go_to_the_innermost_scope_on_the_path():
    r = trace_scopes.reduce(synthetic())
    assert r["scopes"]["_decode_block_dev"] == pytest.approx(
        {"qkv": 0.002, "down": 0.003, "predecode": 0.001,
         "unscoped": 0.003 + 0.002})
    # the wave program has no paths: all its time is unscoped
    assert r["scopes"]["_prefill_chunks"] == pytest.approx(
        {"unscoped": 0.004})
    assert trace_scopes.top_scopes(r["scopes"], 2) == [
        ["_decode_block_dev/unscoped", pytest.approx(0.005)],
        ["_prefill_chunks/unscoped", pytest.approx(0.004)]]


def test_scopes_count_executions_that_began_in_the_window():
    t = synthetic()
    t["host"][0] = ["bench.traced", 10 * MS + 1, 20 * MS]
    r = trace_scopes.reduce(t)
    assert set(r["scopes"]) == set()  # both programs began before it


def test_waves_pair_with_the_span_that_dispatched_them():
    r = trace_scopes.reduce(synthetic())
    # the span that carries requests ended at 0.75 ms, the wave ran at 1
    assert r["waves"] == [{"queue_s": pytest.approx(0.25e-3),
                           "requests": [3, 4]}]
    # a wave that ran before any span began is passed over
    t = synthetic()
    t["devices"][0]["modules"].insert(
        0, ["jit__prefill_chunks(7)", 0, MS // 4])
    t["host"][0][1] = -MS
    assert [w["requests"] for w in trace_scopes.reduce(t)["waves"]] == \
        [[3, 4]]


def test_gaps_are_named_by_the_innermost_span_over_most_of_them():
    r = trace_scopes.reduce(synthetic())
    gaps = dict((round(s * 1e3, 3), n) for n, s in r["idle_gaps"])
    # 5-11 ms: bench.step covers 5 ms of it, engine.readback 4
    assert gaps[6.0] == "engine.readback"
    # 0-1 ms: two engine.wave spans cover 0.75 ms; the longer one 0.5
    assert gaps[1.0] == "engine.wave"
    # 22-30 ms: nothing covers it
    assert gaps[8.0] == "host.other"
    # the widest-span naming would have said bench.step
    old = dict((round(s * 1e3, 3), n) for n, s in
               trace_reduce.reduce(synthetic())["idle_gaps"])
    assert old[6.0] == "bench.step"


def test_busy_window_and_modules_are_trace_reduce_s():
    for t in (synthetic(), json.load(open(OLD))):
        a, b = trace_reduce.reduce(t), trace_scopes.reduce(t)
        for k in ("devices", "window_s", "busy_s", "modules"):
            assert a[k] == b[k]


def test_scope_of():
    assert trace_scopes.scope_of("jit(f)/while/body/attention/x") == \
        "attention"
    assert trace_scopes.scope_of("jit(f)/lm_head/down_proj") == "lm_head"
    assert trace_scopes.scope_of("") == trace_scopes.UNSCOPED
    # a scope the default tuple lacks counts only where it is passed
    assert trace_scopes.scope_of("jit(f)/ssm/x") == trace_scopes.UNSCOPED
    assert trace_scopes.scope_of("jit(f)/down/ssm/x",
                                 trace_scopes.SCOPES + ("ssm",)) == "ssm"


def test_scopes_passed_to_reduce_take_their_ops():
    t = synthetic()
    t["op_paths"]["jit__decode_block_dev(9)"]["copy.2"] = \
        "jit(_decode_block_dev)/ssm/scan/copy"
    r = trace_scopes.reduce(t, scopes=trace_scopes.SCOPES + ("ssm",))
    assert r["scopes"]["_decode_block_dev"] == pytest.approx(
        {"qkv": 0.002, "down": 0.003, "ssm": 0.001,
         "unscoped": 0.003 + 0.002})
    assert "ssm" not in trace_scopes.reduce(t)["scopes"]["_decode_block_dev"]


# -- the six accepted metrics read the same on the accepted fixture ----------

# read at the parent of this module's PR, with the run below
OLD_VALUES = {"device_idle_share": 0.04719999999999169,
              "lane_occupancy": 87.5, "prefill_wave_ms": None,
              "decode_tick_ms": 8.21979575,
              "decode_block_roofline": 5.547331551114553,
              "step_mfu": 2.0033473465648854}


def old_run(reduction):
    import importlib

    from bench.peaks import peaks
    with open(os.path.join(helpers.ROOT, "bench", "configs",
                           "bitnet-0.73b.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(helpers.ROOT, "bench", "cells",
                           "bitnet-0.73b.edge.json")) as f:
        cell = json.load(f)
    with open(OLD) as f:
        trace = json.load(f)
    run = {"summary": reduction.reduce(trace), "cfg": cfg,
           "engine": cell["engine"],
           "stats": {"decode_tokens": 14, "decode_blocks": 2,
                     "prefill_chunks": 0, "admissions": 0},
           "decode_contexts": list(range(150, 164)),
           "prompt_lengths": [100], "peaks": peaks("TPU v5 lite")}
    return {m: importlib.import_module(f"bench.metrics.{m}").read(run)
            for m in OLD_VALUES}


@pytest.mark.parametrize("reduction", [trace_reduce, trace_scopes],
                         ids=["trace_reduce", "trace_scopes"])
def test_accepted_metrics_read_the_same_values(reduction):
    got = old_run(reduction)
    for name, want in OLD_VALUES.items():
        assert got[name] == (want if want is None else pytest.approx(
            want, rel=1e-12)), name


# -- the engine's spans and the compiled programs' paths, on the CPU ----------

def record(tmp_path, fn):
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    return glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                         "*.xplane.pb"))[0]


def test_engine_stages_are_spans_in_beat_order(tmp_path):
    cfg = helpers.tiny_cfg()
    engine = ServingEngine(weights.model_config(cfg),
                           weights.make_weights(cfg, 7),
                           **helpers.CONTIGUOUS)

    def requests():
        rng = np.random.default_rng(0)
        return [Request(prompt=rng.integers(1, 200, size=n).astype(np.int32),
                        max_new_tokens=7, temperature=0.0)
                for n in (5, 20, 11)]

    engine.run(requests())  # compiles outside the trace
    reqs = requests()
    t = trace_scopes.load(record(tmp_path, lambda: engine.run(reqs)))
    spans = sorted((s, d, n[len("engine."):], m) for (n, s, d), m
                   in zip(t["host"], t["host_meta"])
                   if n.startswith("engine."))
    assert {n for _, _, n, _ in spans} == set(STAGES)
    # the stages of a beat follow each other, in beat order
    for (s0, d0, n0, _), (s1, _, n1, _) in zip(spans, spans[1:]):
        assert s0 + d0 <= s1
        assert n1 == "police" or STAGES.index(n0) < STAGES.index(n1)
    # a request's spans share its arrival ordinal
    ords = {r._ordinal for r in reqs}

    def tagged(stage, key):
        return [int(x) for _, _, n, m in spans if n == stage and key in m
                for x in m[key].split()]

    assert set(tagged("wave", "requests")) == ords
    assert sorted(tagged("first_sync", "requests")) == sorted(ords)
    assert sorted(tagged("readback", "retired")) == sorted(ords)
    # no device plane on the CPU: nothing to reduce beyond the host
    assert t["devices"] == [] and t["op_paths"] == {}


def test_paths_come_from_the_stored_programs(tmp_path):
    @jax.jit
    def f(x):
        with jax.named_scope("qkv"):
            y = jnp.tanh(x @ x) * 2
        with jax.named_scope("down"):
            y = y @ x
        return y + 1

    x = jnp.ones((16, 16))
    f(x).block_until_ready()
    with open(record(tmp_path, lambda: f(x).block_until_ready()),
              "rb") as fh:
        paths = trace_scopes.hlo_op_paths(fh.read())
    (prog,) = [k for k in paths if trace_reduce.module_name(k) == "f"]
    scopes = {trace_scopes.scope_of(p) for p in paths[prog].values()}
    assert {"qkv", "down"} <= scopes
    assert all(p.startswith("jit(f)/") for p in paths[prog].values()
               if trace_scopes.scope_of(p) != trace_scopes.UNSCOPED)


def test_a_scope_of_an_architecture_s_own_comes_from_the_programs(tmp_path):
    """A scope passed beside the default ones gets its ops' paths; left
    out, its fusion takes the path of the default scope it holds."""
    known = trace_scopes.SCOPES + ("ssm",)

    @jax.jit
    def f(x):
        with jax.named_scope("qkv"):
            y = jnp.tanh(x @ x) * 2
        with jax.named_scope("ssm"):
            y = jnp.cumsum(y @ x, axis=0)
        return y + 1

    x = jnp.ones((16, 16))
    f(x).block_until_ready()
    with open(record(tmp_path, lambda: f(x).block_until_ready()),
              "rb") as fh:
        xspace = fh.read()
    paths = trace_scopes.hlo_op_paths(xspace, known)
    (prog,) = [k for k in paths if trace_reduce.module_name(k) == "f"]
    assert {"qkv", "ssm"} <= {trace_scopes.scope_of(p, known)
                              for p in paths[prog].values()}
    default = trace_scopes.hlo_op_paths(xspace)[prog]
    assert "ssm" not in {trace_scopes.scope_of(p) for p in default.values()}


# -- a trace recorded on a TPU v5e with the scopes and spans -----------------

@pytest.fixture(scope="module")
def scoped():
    with open(SCOPED) as f:
        trace = json.load(f)
    return trace, trace_scopes.reduce(trace)


def test_chip_trace_scopes_cover_the_decode_block(scoped):
    _, r = scoped
    # the block after the wave; the one it waited behind began earlier
    block = r["modules"]["_decode_block_dev"]
    assert block["count"] == 1
    by = r["scopes"]["_decode_block_dev"]
    assert set(trace_scopes.SCOPES) <= set(by)
    total = sum(by.values())
    # the ops' self times tile the execution, less the gaps between ops
    assert 0.95 * block["seconds"] <= total <= block["seconds"]
    assert by[trace_scopes.UNSCOPED] < 0.1 * total
    assert max(by, key=by.get) == "predecode"


def test_chip_trace_wave_pairs_with_its_span(scoped):
    trace, r = scoped
    (wave,) = r["waves"]
    assert wave["queue_s"] > 0
    synced = [m["requests"] for (n, _, _), m
              in zip(trace["host"], trace["host_meta"])
              if n == "engine.first_sync" and "requests" in m]
    assert [str(o) for o in wave["requests"]] == synced


def test_chip_trace_new_metrics_read_it(scoped):
    import importlib

    _, r = scoped
    with open(os.path.join(helpers.ROOT, "bench", "cells",
                           "bitnet-0.73b.edge.json")) as f:
        engine = json.load(f)["engine"]
    run = {"summary": r, "engine": engine}
    got = {m: importlib.import_module(f"bench.metrics.{m}").read(run)
           for m in ("predecode_ms", "decode_linear_ms",
                     "decode_attention_ms", "lm_head_ms", "wave_queue_ms")}
    assert all(v is not None and v > 0 for v in got.values()), got
    # the expansion outweighs a tick's linears; a tick's parts fit in it
    tick_ms = (r["modules"]["_decode_block_dev"]["seconds"] * 1e3
             / engine["decode_block"])
    assert got["predecode_ms"] > got["decode_linear_ms"]
    assert (got["decode_linear_ms"] + got["decode_attention_ms"]
            + got["lm_head_ms"]) < tick_ms


def test_chip_trace_breakdown_names_what_can_be_fixed(scoped):
    _, r = scoped
    assert not any(n.startswith("while") for n, _ in r["device_ops"][:5])
    assert all(n.startswith("engine.") for n, sec in r["idle_gaps"]
               if sec > 1e-3)


# -- run.py's reduction of a traced run, on the chip fixture -----------------

LISTED = ("device_idle_share", "lane_occupancy", "prefill_wave_ms",
          "decode_tick_ms", "decode_block_roofline", "step_mfu")
SCOPED_METRICS = ("decode_linear_ms", "decode_attention_ms", "lm_head_ms",
                  "wave_queue_ms")


def traced_metrics(summary):
    """The per-layer metrics of BENCHMARK.json as run.py reads them from a
    reduced trace of the bitnet-0.73b.edge cell, with a window that
    decoded 14 tokens at contexts 101-114 after a 100-token prompt."""
    from types import SimpleNamespace

    from bench import run
    from bench.peaks import peaks

    spec = run.load_cell("bitnet-0.73b.edge")
    tracer = SimpleNamespace(
        t0=0.0, t1=1.0,
        stats0={"decode_tokens": 0, "decode_blocks": 0,
                "prefill_chunks": 0, "admissions": 0},
        stats1={"decode_tokens": 14, "decode_blocks": 2,
                "prefill_chunks": 1, "admissions": 1})
    served = SimpleNamespace(prompt=np.zeros(100, np.int32), first_t=0.5,
                             token_t=[0.5 + 0.01 * j for j in range(15)])
    r = run.traced_run(tracer, summary, SimpleNamespace(served=[served]),
                       spec["cfg"], spec["cell"]["engine"],
                       peaks("TPU v5 lite"))
    return run.read_per_layer(spec["per_layer"], r)


def test_run_reduces_a_traced_run_with_trace_scopes(monkeypatch, tmp_path):
    """``Tracer.reduce`` loads the profiler's file with ``trace_scopes``;
    on the chip fixture that feeds the four scoped metrics, and the six
    listed before them read what ``trace_reduce`` gave them."""
    from bench import run

    with open(SCOPED) as f:
        trace = json.load(f)
    tracer = run.Tracer(None, 10.0)
    profile = os.path.join(tracer.dir, "plugins", "profile", "1")
    os.makedirs(profile)
    open(os.path.join(profile, "host.xplane.pb"), "wb").close()
    loaded = []
    monkeypatch.setattr(trace_scopes, "load",
                        lambda path, scopes: loaded.append((path, scopes))
                        or trace)
    summary = tracer.reduce(archs.scopes(run.load_cell(
        "bitnet-0.73b.edge")["cfg"]))
    assert loaded and loaded[0][0].endswith("host.xplane.pb")
    assert loaded[0][1] == trace_scopes.SCOPES
    assert not os.path.exists(tracer.dir)

    got = traced_metrics(summary)
    before = traced_metrics(trace_reduce.reduce(trace))
    assert set(before) <= set(LISTED)
    assert {m: got[m] for m in before} == before  # exactly, bit for bit
    assert set(got) == set(before) | set(SCOPED_METRICS)
    for m in SCOPED_METRICS:
        assert got[m]["unit"] == "ms" and got[m]["value"] > 0, m
    tick = got["decode_tick_ms"]["value"]
    assert sum(got[m]["value"] for m in SCOPED_METRICS[:3]) < tick

    breakdown = run.trace_breakdown(summary)
    assert set(breakdown) == {"device_ops", "idle_gaps", "device_scopes"}
    assert breakdown["device_scopes"] == [
        list(x) for x in trace_scopes.top_scopes(summary["scopes"])]
    assert breakdown["device_scopes"][0][0].startswith("_decode_block_dev/")
    assert all(len(v) <= 10 for v in breakdown.values())
