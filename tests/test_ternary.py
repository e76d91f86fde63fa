"""Unit + property tests for the ternary quant/pack substrate."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import bitlinear, params as tparams, ternary


def test_ternarize_values_and_scale():
    w = jnp.array([[0.9, -0.8], [0.05, 0.0]], jnp.float32)
    wt, gamma = ternary.ternarize(w)
    assert wt.dtype == jnp.int8
    assert set(np.unique(np.asarray(wt))).issubset({-1, 0, 1})
    np.testing.assert_allclose(gamma, np.mean(np.abs(w)), rtol=1e-6)


def test_pack_unpack_roundtrip_basic():
    key = jax.random.PRNGKey(0)
    wt = jax.random.randint(key, (37, 8), -1, 2).astype(jnp.int8)
    for g in (2, 3, 4, 5):
        codes = ternary.pack_ternary(wt, g)
        assert codes.dtype == jnp.uint8
        assert codes.shape == (int(np.ceil(37 / g)), 8)
        back = ternary.unpack_ternary(codes, g, 37)
        np.testing.assert_array_equal(np.asarray(back), np.asarray(wt))


@settings(max_examples=30, deadline=None)
@given(
    n=st.integers(1, 97),
    k=st.integers(1, 17),
    g=st.integers(2, 5),
    seed=st.integers(0, 2**31 - 1),
)
def test_pack_unpack_roundtrip_property(n, k, g, seed):
    rng = np.random.default_rng(seed)
    wt = rng.integers(-1, 2, size=(n, k)).astype(np.int8)
    codes = ternary.pack_ternary(jnp.asarray(wt), g)
    back = np.asarray(ternary.unpack_ternary(codes, g, n))
    np.testing.assert_array_equal(back, wt)


@settings(max_examples=20, deadline=None)
@given(
    m=st.integers(1, 9),
    n=st.integers(1, 64),
    k=st.integers(1, 33),
    g=st.integers(2, 5),
    seed=st.integers(0, 2**31 - 1),
)
def test_lut_matmul_matches_dense_oracle(m, n, k, g, seed):
    """Paper-faithful LUT matmul == dense ternary matmul (any shape/group)."""
    rng = np.random.default_rng(seed)
    a = rng.integers(-127, 128, size=(m, n)).astype(np.int8)
    wt = rng.integers(-1, 2, size=(n, k)).astype(np.int8)
    codes = ternary.pack_ternary(jnp.asarray(wt), g)
    ref = np.asarray(ternary.ternary_matmul_ref(jnp.asarray(a), jnp.asarray(wt)))
    lut = np.asarray(ternary.ternary_matmul_lut_ref(jnp.asarray(a), codes, g))
    np.testing.assert_array_equal(lut, ref)


def test_packed_xla_matmul_matches_oracle():
    rng = np.random.default_rng(7)
    a = rng.integers(-127, 128, size=(4, 70)).astype(np.int8)
    wt = rng.integers(-1, 2, size=(70, 24)).astype(np.int8)
    codes = ternary.pack_ternary(jnp.asarray(wt), 5)
    ref = ternary.ternary_matmul_ref(jnp.asarray(a), jnp.asarray(wt))
    out = ternary.ternary_matmul_packed_xla(jnp.asarray(a), codes, 5, 70)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("n_in,n_outs,row_multiple,ones", [
    (40, (24,), 1, False),
    (37, (24,), 64, False),
    (96, (64, 16, 16), 64, False),
    (96, (48, 48), 64, False),
    # |acc| = 127 * n_in > 2**24: past where an f32 accumulator stays exact
    ((1 << 24) // 127 + 7_001, (8,), 64, True),
], ids=["single", "padded_n_in", "fused_qkv", "fused_gate_up", "wide_n_in"])
def test_predecoded_int8_contraction_is_exact(n_in, n_outs, row_multiple,
                                              ones):
    """The resident int8 weights (predecode / predecode_fused) contract to
    exactly what the packed XLA path and the dense int32 oracle give."""
    g = 5
    rng = np.random.default_rng(n_in + len(n_outs))
    x = (np.ones((3, n_in), np.float32) if ones
         else rng.normal(size=(3, n_in)).astype(np.float32) * 3.0)
    parts = []
    for k in n_outs:
        wt = (np.ones((n_in, k), np.int8) if ones
              else rng.integers(-1, 2, size=(n_in, k)).astype(np.int8))
        parts.append({"codes": ternary.pack_ternary(jnp.asarray(wt), g,
                                                    row_multiple),
                      "gamma": jnp.float32(rng.uniform(0.5, 1.5))})
    dec = (bitlinear.predecode(parts[0], g=g) if len(parts) == 1
           else bitlinear.predecode_fused(parts, g=g))
    assert dec["wt"].dtype == jnp.int8
    got = np.asarray(bitlinear.apply_predecoded(dec, jnp.asarray(x),
                                                out_dtype=jnp.float32))
    for impl in (bitlinear.IMPL_XLA, bitlinear.IMPL_REF):
        want = np.concatenate([np.asarray(bitlinear.apply_packed(
            p, jnp.asarray(x), g=g, impl=impl, out_dtype=jnp.float32))
            for p in parts], axis=-1)
        np.testing.assert_array_equal(got, want)


def test_absmax_quant_bounds_and_recon():
    x = jax.random.normal(jax.random.PRNGKey(1), (5, 33)) * 4.0
    q, s = ternary.absmax_quant(x)
    assert q.dtype == jnp.int8
    assert int(jnp.max(jnp.abs(q))) <= 127
    recon = q.astype(jnp.float32) * s
    assert float(jnp.max(jnp.abs(recon - x))) <= float(jnp.max(s)) * 0.51


def test_ste_gradients_flow():
    w = jnp.ones((8, 4)) * 0.3

    def loss(w):
        return jnp.sum(ternary.ternarize_ste(w) ** 2)

    gw = jax.grad(loss)(w)
    assert float(jnp.sum(jnp.abs(gw))) > 0.0  # STE passes gradient

    x = jnp.linspace(-2, 2, 24).reshape(2, 12)
    ga = jax.grad(lambda x: jnp.sum(ternary.absmax_quant_ste(x) ** 2))(x)
    assert float(jnp.sum(jnp.abs(ga))) > 0.0


def test_enumeration_matrix_columns_are_codes():
    c = np.asarray(ternary.enumeration_matrix(3))
    assert c.shape == (3, 27)
    # column 0 is all -1s shifted: code 0 -> digits (0,0,0) -> weights (-1,-1,-1)
    np.testing.assert_array_equal(c[:, 0], [-1, -1, -1])
    np.testing.assert_array_equal(c[:, 26], [1, 1, 1])
    # every column distinct
    assert len({tuple(col) for col in c.T}) == 27


def test_bits_per_weight_matches_paper_claims():
    assert ternary.bits_per_weight(5) == pytest.approx(1.6)
    # paper: G=3, 5-bit index -> 1.67 bits/weight
    assert ternary.index_bits(3) == 5
    assert 5 / 3 == pytest.approx(1.6667, abs=1e-3)


def test_bitlinear_qat_vs_packed_consistency():
    key = jax.random.PRNGKey(3)
    p = bitlinear.init(key, 64, 32, bias=True)
    x = jax.random.normal(jax.random.PRNGKey(4), (6, 64))
    y_qat = bitlinear.apply(p, x, mode="qat")
    packed = bitlinear.pack(p)
    y_ref = bitlinear.apply_packed(packed, x, impl="ref", out_dtype=jnp.float32)
    y_xla = bitlinear.apply_packed(packed, x, impl="xla", out_dtype=jnp.float32)
    # qat fake-quant and packed integer paths compute the same math
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_xla), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(y_qat), np.asarray(y_ref),
                               rtol=2e-2, atol=2e-2)


def test_bitlinear_grad_through_qat():
    p = bitlinear.init(jax.random.PRNGKey(0), 16, 8)
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 16))
    g = jax.grad(lambda p: jnp.sum(bitlinear.apply_qat(p, x) ** 2))(p)
    assert float(jnp.sum(jnp.abs(g["w"]))) > 0


def test_tiling_selection_fits_budget_and_alignment():
    t = tparams.select_tlmm_tiling(4096, 8192, 8192, g=5,
                                   vmem_budget=8 * 1024 * 1024)
    assert t.vmem_bytes <= 8 * 1024 * 1024
    assert t.bn % (5 * 128 // np.gcd(5, 128)) == 0
    assert t.bk % 128 == 0
    # decode shape: single token
    t1 = tparams.select_tlmm_tiling(1, 8192, 8192, g=5)
    assert t1.bm == 1


def test_compression_ratio_vs_bf16():
    # 1.6 bits/weight vs 16 -> 10x
    r = tparams.compression_ratio(8192, 8192, g=5)
    assert r == pytest.approx(10.0, rel=1e-2)


def test_int8_fwd_qat_matches_fake_quant():
    """int8-MXU forward (custom VJP) == fake-quant bf16 forward + STE grads."""
    key = jax.random.PRNGKey(5)
    p = bitlinear.init(key, 32, 16)
    x = jax.random.normal(jax.random.PRNGKey(6), (4, 32))
    y_fq = bitlinear.apply_qat(p, x)
    y_i8 = bitlinear.apply_qat(p, x, int8_fwd=True)
    np.testing.assert_allclose(np.asarray(y_fq), np.asarray(y_i8),
                               rtol=1e-4, atol=1e-4)

    def loss_fq(p, x):
        return jnp.sum(jnp.sin(bitlinear.apply_qat(p, x)))

    def loss_i8(p, x):
        return jnp.sum(jnp.sin(bitlinear.apply_qat(p, x, int8_fwd=True)))

    gp_fq, gx_fq = jax.grad(loss_fq, argnums=(0, 1))(p, x)
    gp_i8, gx_i8 = jax.grad(loss_i8, argnums=(0, 1))(p, x)
    np.testing.assert_allclose(np.asarray(gx_fq), np.asarray(gx_i8),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(gp_fq["w"]), np.asarray(gp_i8["w"]),
                               rtol=1e-3, atol=1e-3)
