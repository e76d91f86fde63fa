"""A configuration's architecture is found by its ``reference`` name: the
served weights, the program's config and the work counts of the dense
configurations are those they were before the lookup existed, and a new
architecture brings its own by adding a module."""

import dataclasses
import hashlib
import json
import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import archs, counts, trace_scopes, weights
from bench.archs import ternary_dense
from bench.tests import helpers
from repro.configs.base import ModelConfig

BENCH = os.path.join(helpers.ROOT, "bench")


def config(name):
    if name == "tiny":
        return helpers.tiny_cfg()
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def path_text(path):
    return "/".join(str(p.key) for p in path)


# -- what the dense configurations were served before the lookup -------------

# The program's config object, field by field, as ``bench/weights.py``
# built it for a dense configuration before it asked the architecture.
MODEL = {
    "tiny": dict(name="tiny", n_layers=2, d_model=64, n_heads=4,
                 n_kv_heads=2, head_dim=16, d_ff=128, vocab_size=256,
                 tie_embeddings=False),
    "bitnet-0.73b": dict(name="bitnet-0.73b", n_layers=24, d_model=1536,
                         n_heads=24, n_kv_heads=24, head_dim=64, d_ff=4096,
                         vocab_size=32000, tie_embeddings=True),
    "granite-3-2b": dict(name="granite-3-2b", n_layers=40, d_model=2048,
                         n_heads=32, n_kv_heads=8, head_dim=64, d_ff=8192,
                         vocab_size=49155, tie_embeddings=True),
}

# Digests of the served tree's leaf paths, shapes and dtypes (``path shape
# dtype``, one line a leaf, in flattening order), read before the lookup.
SHAPES = {
    "tiny": (19, 197_944, "9aa50e98aa4a93af9951bd86b746f47d"
                          "55591dbcdf385552ed2f5ffb0e344ee4"),
    "bitnet-0.73b": (18, 337_681_056, "c61d513f3c9313163ffa0aa4c16c4da4"
                                      "afcf45c14dc2442bf0209d2500d53bc0"),
    "granite-3-2b": (18, 925_008_992, "bfc313b5ae79ba08b86230b0ef292d61"
                                      "e1e8950355a8ecf9526ddefc324820c1"),
}

# The tiny model's weights drawn on the CPU from seed 0, and from a seed
# past 32 bits: each leaf's path, shape, dtype and bytes, read before the
# lookup.
TINY_WEIGHTS = {0: "9060f51f6c805e82f6c1108c7cd0b613"
                   "2580fd0aa2a1f12509dae7c4ad158457"}
TINY_BYTES_ONLY = {2**33 + 5: "b56c98f798dde41e4e9cc0c89b6c9639"
                              "9a7ee82a54ea63ed373a6476e40b7084"}


@pytest.mark.parametrize("name", list(MODEL))
def test_model_config_is_the_parents(name):
    cfg = config(name)
    want = ModelConfig(family="dense", block_kind="attn",
                       rope_theta=cfg["rope_theta"],
                       norm_eps=cfg["rms_norm_eps"],
                       group_size=cfg["pack_group"], **MODEL[name])
    assert dataclasses.asdict(weights.model_config(cfg)) == \
        dataclasses.asdict(want)


@pytest.mark.parametrize("name", list(SHAPES))
def test_served_shapes_are_the_parents(name):
    leaves = jax.tree_util.tree_flatten_with_path(
        weights.served_shapes(config(name)))[0]
    text = "\n".join(f"{path_text(p)} {tuple(s.shape)} {s.dtype}"
                     for p, s in leaves)
    size = sum(int(np.prod(s.shape)) * np.dtype(s.dtype).itemsize
               for _, s in leaves)
    assert (len(leaves), size, hashlib.sha256(text.encode()).hexdigest()) \
        == SHAPES[name]


def test_tiny_weights_are_the_parents():
    for seed, want in TINY_WEIGHTS.items():
        h = hashlib.sha256()
        for p, x in jax.tree_util.tree_flatten_with_path(
                weights.make_weights(helpers.tiny_cfg(), seed))[0]:
            a = np.asarray(x)
            h.update(f"{path_text(p)} {a.shape} {a.dtype}\n".encode())
            h.update(a.tobytes())
        assert h.hexdigest() == want, seed
    for seed, want in TINY_BYTES_ONLY.items():
        h = hashlib.sha256()
        for x in jax.tree_util.tree_leaves(
                weights.make_weights(helpers.tiny_cfg(), seed)):
            h.update(np.asarray(x).tobytes())
        assert h.hexdigest() == want, seed


def test_tiny_counts_are_the_parents():
    cfg = helpers.tiny_cfg()
    assert counts.weight_pass_bytes(cfg) == 81_561.6
    assert [counts.decode_token(cfg, c) for c in (1, 100, 2047)] == [
        (180_736, 512), (231_424, 25_856), (1_228_288, 524_288)]
    assert [counts.prompt_ops(cfg, n) for n in (1, 3, 128)] == [
        180_736, 478_208, 23_134_208]


# -- the lookup --------------------------------------------------------------

def configs_in_use():
    names = sorted(f[:-len(".json")]
                   for f in os.listdir(os.path.join(BENCH, "configs")))
    return [config(n) for n in names] + [helpers.tiny_cfg()]


@pytest.mark.parametrize("cfg", configs_in_use(), ids=lambda c: c["name"])
def test_each_configuration_names_an_architecture_and_its_reference(cfg):
    r = cfg["reference"]
    assert os.path.isfile(os.path.join(BENCH, "archs", r + ".py"))
    assert os.path.isfile(os.path.join(BENCH, "references", r + ".py"))
    assert archs.arch(cfg) is ternary_dense


def test_an_unknown_architecture_names_the_file_it_expects():
    cfg = dict(helpers.tiny_cfg(), reference="no_such_arch")
    with pytest.raises(LookupError, match="bench/archs/no_such_arch.py"):
        archs.arch(cfg)
    with pytest.raises(LookupError, match="bench/archs/no_such_arch.py"):
        weights.model_config(cfg)
    with pytest.raises(LookupError, match="bench/archs/no_such_arch.py"):
        counts.decode_token(cfg, 1)


STAND_IN = "stand_in_hybrid"
OUT_FAN_IN = 7       # the stand-in's own fan-in of ``out_proj``
FILLED = {"conv_w": 0.25, "conv_b": -1.0, "A_log": 0.5, "D": 2.0,
          "dt_bias": -0.5}


def stand_in(with_leaf=True):
    """A hybrid architecture: tiny's decoder with SSM heads beside the
    attention (the program's ``hymba`` block), its own fan-ins for the
    SSM's ternary projections, its own draw of the SSM's dense vectors,
    counts of its own and a name scope, ``ssm``, beside the default ones."""
    mod = types.ModuleType(f"bench.archs.{STAND_IN}")
    mod.drawn = []

    def model_config(cfg):
        return dataclasses.replace(ternary_dense.model_config(cfg),
                                   family="hybrid", block_kind="hymba",
                                   ssm_state=8)

    def fan_in(cfg):
        d = cfg["hidden_size"]
        return {**ternary_dense.fan_in(cfg), "in_proj": d, "bc_proj": d,
                "dt_proj": d, "out_proj": OUT_FAN_IN}

    def leaf(names, shape, dtype, key):
        if names[-1] not in FILLED:
            return None
        mod.drawn.append(names[-1])
        return jnp.full(shape, FILLED[names[-1]], dtype)

    mod.model_config, mod.fan_in = model_config, fan_in
    mod.SCOPES = ("ssm",)
    if with_leaf:
        mod.leaf = leaf
    mod.weight_pass_bytes = lambda cfg: 1234.5
    mod.decode_token = lambda cfg, context: (context * 10, context * 3)
    mod.prompt_ops = lambda cfg, length: length * 7
    return mod


def stand_in_cfg():
    return dict(helpers.tiny_cfg(), name="tiny-hybrid", reference=STAND_IN)


def test_a_stand_in_architecture_serves_its_own_config_and_weights(
        monkeypatch):
    mod = stand_in()
    monkeypatch.setitem(sys.modules, mod.__name__, mod)
    cfg = stand_in_cfg()
    assert archs.arch(cfg) is mod
    mcfg = weights.model_config(cfg)
    assert (mcfg.block_kind, mcfg.ssm_state) == ("hymba", 8)
    w = weights.make_weights(cfg, 11)
    ssm = w["layers"]["ssm"]
    for name, value in FILLED.items():
        assert np.all(np.asarray(ssm[name]) == value), name
    assert sorted(set(mod.drawn)) == sorted(FILLED)
    # the generic rules drew the SSM's ternary projections, with the
    # stand-in's fan-ins: past 7 inputs every code is that of 5 zeros
    out = np.asarray(ssm["out_proj"]["codes"])
    assert np.all(out[:, 2:] == (3 ** 5 - 1) // 2)
    assert np.any(out[:, :2] != (3 ** 5 - 1) // 2)
    gamma = np.asarray(ssm["out_proj"]["gamma"])
    assert np.all(np.abs(gamma * np.sqrt(OUT_FAN_IN) / 0.8 - 1) <= 0.1 + 1e-6)
    # the leaves the dense architecture also has follow the same rules
    assert np.all(np.asarray(w["layers"]["ln1"]["w"]) == 1)
    assert np.all(np.asarray(w["layers"]["attn"]["q"]["codes"]) < 3 ** 5)


def test_a_stand_in_architecture_gives_the_counts(monkeypatch):
    mod = stand_in()
    monkeypatch.setitem(sys.modules, mod.__name__, mod)
    cfg = stand_in_cfg()
    assert counts.weight_pass_bytes(cfg) == 1234.5
    assert counts.decode_token(cfg, 5) == (50, 15)
    assert counts.prompt_ops(cfg, 4) == 28
    # the metrics that read the counts read the stand-in's
    from bench.metrics import step_mfu
    run = {"cfg": cfg, "summary": {"window_s": 1.0},
           "decode_contexts": [5, 6], "prompt_lengths": [4],
           "peaks": {"int8_ops": 100.0}}
    assert step_mfu.read(run) == pytest.approx(100.0 * (50 + 60 + 28) / 100)


def test_a_stand_in_architecture_adds_its_scopes(monkeypatch):
    mod = stand_in()
    monkeypatch.setitem(sys.modules, mod.__name__, mod)
    assert archs.scopes(stand_in_cfg()) == trace_scopes.SCOPES + ("ssm",)
    # the dense architecture's programs carry the default scopes alone
    assert archs.scopes(helpers.tiny_cfg()) == trace_scopes.SCOPES


def test_a_leaf_no_rule_covers_is_refused(monkeypatch):
    mod = stand_in(with_leaf=False)
    monkeypatch.setitem(sys.modules, mod.__name__, mod)
    with pytest.raises(ValueError, match="no rule to draw weight"):
        weights.make_weights(stand_in_cfg(), 0)
