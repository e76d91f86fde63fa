"""trace_reduce on 20 ms recorded on a TPU v5e (bitnet-0.73b.edge, two fused
decode blocks back to back), checked against a count made another way."""

import json
import os

import numpy as np
import pytest

from bench import trace_reduce

TRACE = os.path.join(os.path.dirname(__file__), "trace_v5e_edge.json")


@pytest.fixture(scope="module")
def trace():
    with open(TRACE) as f:
        return json.load(f)


def timeline(trace):
    """Busy nanoseconds on a 1 us grid over the window."""
    lo, hi = trace_reduce.window(trace)
    grid = np.zeros((hi - lo) // 1000 + 1, bool)
    for _, s, d in trace["devices"][0]["ops"]:
        a = max(int(s), lo) - lo
        b = min(int(s + d), hi) - lo
        if b > a:
            grid[a // 1000:-(-b // 1000)] = True
    return grid, lo, hi


def test_busy_is_the_union_of_op_intervals(trace):
    r = trace_reduce.reduce(trace)
    grid, lo, hi = timeline(trace)
    assert r["window_s"] == pytest.approx(0.020)
    assert r["devices"] == 1
    # the grid rounds each interval out to whole microseconds
    assert r["busy_s"] == pytest.approx(grid.sum() * 1e-6, abs=2e-4)
    assert 0.9 * r["window_s"] < r["busy_s"] <= r["window_s"]


def test_module_time_counts_executions_that_began_inside(trace):
    r = trace_reduce.reduce(trace)
    # the block that began before the window is not counted; the one that
    # began inside counts whole, past the window's end
    assert r["modules"] == {"_decode_block_dev": {
        "seconds": pytest.approx(65758366e-9), "count": 1}}


def test_idle_gaps_are_named_by_the_host_span_over_them(trace):
    r = trace_reduce.reduce(trace)
    gaps = r["idle_gaps"]
    assert 0 < len(gaps) <= 10
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps),
                                          reverse=True)
    grid, lo, hi = timeline(trace)
    assert sum(g[1] for g in gaps) <= (grid.size - grid.sum()) * 1e-6 + 1e-4
    # every gap here falls while the host sat in bench.step
    assert {g[0] for g in gaps} == {"bench.step"}


def test_ops_are_ranked_by_device_time(trace):
    r = trace_reduce.reduce(trace)
    secs = [t for _, t in r["device_ops"]]
    assert len(secs) == 10 and secs == sorted(secs, reverse=True)
    assert all(" " not in name for name, _ in r["device_ops"])


def test_names():
    assert trace_reduce.module_name("jit__prefill_chunks(901)") == \
        "_prefill_chunks"
    assert trace_reduce.op_name(
        "%fusion.12 = f32[8]{0} fusion(f32[8]{0} %p)") == "fusion.12"


def test_a_trace_without_the_window_span_is_refused():
    with pytest.raises(ValueError):
        trace_reduce.reduce({"devices": [], "host": []})
