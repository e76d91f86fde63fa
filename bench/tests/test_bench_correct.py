"""The harness run end to end on the CPU at test size, without the look for
a chip: sound runs come out correct, the plain reference agrees with the
engine, and the int4 control reads above the limit."""

import time

import pytest

from bench import check, run
from bench.tests import helpers


def one_run(spec, seed, trace=False, controls=False):
    return run.run_cell(spec, seed, 3.0, trace, t_start=time.perf_counter(),
                        controls=controls)


@pytest.mark.parametrize("mix,engine", [
    (helpers.CLOSED, helpers.CONTIGUOUS), (helpers.OPEN, helpers.PAGED)],
    ids=["closed-contiguous", "open-paged"])
def test_sound_run_is_correct(mix, engine):
    r = one_run(helpers.tiny_spec(mix, engine), 2**31 + 3)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert list(r["checks"])[-1] == "tokens_compared"
    assert set(r["metrics"]) == {"ttft_p95_ms", "tpot_p95_ms",
                                 "output_tok_s", "setup_s"}


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_reference_agrees_with_the_engine_and_the_control_does_not(seed):
    """Served greedy tokens lie within the limit of the reference's best;
    the int4 control's tokens, standing in for the served ones, do not, and
    ``check.passed`` refuses them."""
    r = one_run(helpers.tiny_spec(), seed, controls=True)
    c = r["checks"]
    assert c["max_logit_gap"]["value"] <= helpers.TINY_LIMIT, c
    assert r["correct"], c
    int4 = r["controls"]["int4"]
    assert int4["checks"]["max_logit_gap"]["value"] > helpers.TINY_LIMIT
    assert not int4["correct"], int4
    assert int4["checks"]["tokens_compared"] == c["tokens_compared"]
    assert 0 <= r["controls"]["bf16"]["checks"]["max_logit_gap"]["value"] \
        < float("inf")


def test_traced_run_reports_per_layer_metrics():
    r = one_run(helpers.tiny_spec(helpers.OPEN, helpers.PAGED), 7,
                trace=True)
    assert r["correct"], r["checks"]
    # the CPU has no device plane: nothing device-timed is reported
    assert "device_idle_share" not in r["metrics"]
    assert r["device"]["window_s"] > 0
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps",
                                   "device_scopes"}


def test_delivery_faults_count_short_and_refused_requests():
    from repro.serving import RequestStatus
    spec = helpers.tiny_spec()
    r = one_run(spec, 4)
    assert r["checks"]["failed_requests"]["value"] == 0
    assert check.passed({"x": {"value": 1, "limit": 0}}) is False
    assert check.passed({"tokens_compared": {"value": 5, "limit": 10}}) \
        is False
    assert RequestStatus.OK.value == "ok"
