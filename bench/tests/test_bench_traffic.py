"""The traffic generator and the end-to-end arithmetic."""

import collections

import numpy as np
import pytest

from bench import serve
from bench.tests import helpers
from bench.traffic import Traffic


def stream(mix, seed, n=32):
    t = Traffic(mix, 256, seed)
    return [t.request(i) for i in range(n)], t


@pytest.mark.parametrize("mix", [helpers.CLOSED, helpers.OPEN])
def test_same_seed_same_requests(mix):
    a, ta = stream(mix, 2**31 + 11)
    b, tb = stream(mix, 2**31 + 11)
    for x, y in zip(a, b):
        assert x.max_new == y.max_new
        np.testing.assert_array_equal(x.prompt, y.prompt)
    if mix["loop"] == "open":
        assert [ta.arrival(i) for i in range(32)] == \
            [tb.arrival(i) for i in range(32)]


@pytest.mark.parametrize("mix", [helpers.CLOSED, helpers.OPEN])
def test_every_seed_serves_the_same_sizes(mix):
    a, ta = stream(mix, 1)
    b, tb = stream(mix, 2)
    block = mix["block"]
    sizes = lambda reqs, lo: sorted((len(r.prompt), r.max_new)
                                    for r in reqs[lo:lo + block])
    for lo in (0, 3, block):  # any block of consecutive requests
        assert sizes(a, lo) == sizes(b, lo)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    if mix["loop"] == "open":
        # every block of gaps is the same set: arrival times agree at the
        # end of each block
        for i in range(block - 1, 32, block):
            assert ta.arrival(i) == pytest.approx(tb.arrival(i))
        rate = block / ta.arrival(block - 1)
        assert rate == pytest.approx(mix["rate_per_s"], rel=1e-6)


def test_a_round_of_as_many_clients_as_sizes_holds_each_size_once():
    mix = dict(helpers.CLOSED, clients=8)
    rounds = {}
    for seed in (1, 2, 3000000007):
        t = Traffic(mix, 256, seed)
        rounds[seed] = [sorted((len(t.request(0, k, j).prompt),
                                t.request(0, k, j).max_new)
                               for k in range(8)) for j in range(3)]
        assert len(set(rounds[seed][0])) == 8
    assert rounds[1] == rounds[2] == rounds[3000000007]


def test_closed_loop_clients_must_fit_the_block():
    with pytest.raises(ValueError):
        Traffic(dict(helpers.CLOSED, clients=9), 256, 1)


def test_lengths_stay_in_bounds():
    reqs, _ = stream(helpers.OPEN, 5, n=64)
    assert all(4 <= len(r.prompt) <= 48 for r in reqs)
    assert all(2 <= r.max_new <= 14 for r in reqs)


def served(index, due, first, last, n, max_new=None):
    s = serve.Served(index, np.zeros(4, np.int32), max_new or n, due)
    if n:
        s.first_t, s.last_t = first, last
        s.tokens = [1] * n
        s.token_t = list(np.linspace(first, last, n))
    return s


def test_percentiles_are_over_all_requests_and_rates_over_the_window():
    # 20 requests due at 0..19 s, first token 10 ms x i after its due time
    reqs = [served(i, float(i), i + 0.01 * i, i + 0.01 * i + 0.5, 6)
            for i in range(20)]
    # one request whose first token came after the window: not in TTFT
    reqs.append(served(20, 19.5, 20.5, 21.0, 6))
    # one unfinished: in TTFT, not in TPOT
    reqs.append(served(21, 19.0, 19.2, 19.9, 3, max_new=8))
    times = [t for s in reqs for t in s.token_t]
    win = serve.Window(0.0, 20.0, reqs, times, [])
    e2e = serve.end_to_end(win)
    ttft = [0.01 * i for i in range(20)] + [0.2]
    assert e2e["n_ttft"] == 21
    assert e2e["ttft_p95_ms"] == pytest.approx(
        np.percentile(ttft, 95) * 1e3)
    assert e2e["n_tpot"] == 20  # the last-but-one ends at 19.69 s
    assert e2e["tpot_p95_ms"] == pytest.approx(100.0)
    in_window = sum(1 for t in times if t < 20.0)
    assert e2e["output_tok_s"] == pytest.approx(in_window / 20.0)
