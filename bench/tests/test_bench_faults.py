"""The harness run with the timed path broken underneath: ``correct`` comes
out false for each fault a served cell can have."""

import time

import jax.numpy as jnp
import pytest

from bench import run
from bench.tests import helpers
from repro.models import transformer

SOUND = transformer.decode_step


def altered_token(*args, **kwargs):
    """Every decoded token is shifted to the next id where it is made."""
    logits, cache = SOUND(*args, **kwargs)
    return jnp.roll(logits, 1, axis=-1), cache


def state_unchanged(cfg, params, inputs, ctx, cache, *a, **kw):
    """The decode step returns the cache it was given: no KV is written."""
    logits, _ = SOUND(cfg, params, inputs, ctx, cache, *a, **kw)
    return logits, cache


@pytest.mark.parametrize("fault", [altered_token, state_unchanged],
                         ids=lambda f: f.__name__)
def test_fault_makes_the_run_incorrect(monkeypatch, fault):
    monkeypatch.setattr(transformer, "decode_step", fault)
    spec = helpers.tiny_spec(helpers.OPEN, helpers.PAGED)
    r = run.run_cell(spec, 2**31 + 5, 1.5, False,
                     t_start=time.perf_counter())
    assert not r["correct"]
    assert r["checks"]["max_logit_gap"]["value"] > helpers.TINY_LIMIT
