"""BitLinear — the paper's ternary linear layer, as a composable JAX module.

Two execution modes, matching the paper's offline/online split:

* ``qat``   — training path (BitNet b1.58 recipe): master weights in bf16/f32,
              forward applies absmean-ternary fake-quant to W and absmax-int8
              fake-quant to activations, both with straight-through estimators.
* ``packed``— inference path: weights are *base-3 packed uint8 codes* (the
              offline preprocessing stage of TLMM); forward quantizes the
              activation to int8, runs the ternary matmul (XLA unpack+dot, the
              Pallas decode-to-MXU kernel, or the paper-faithful LUT kernel),
              and dequantizes with act_scale * gamma fused into the epilogue.

Params are plain dict pytrees so they shard with NamedSharding directly.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import ternary

# impl choices for the packed path
IMPL_XLA = "xla"          # in-graph unpack + int8 dot (dry-run / roofline path)
IMPL_PALLAS = "pallas"    # kernels/tlmm decode-to-MXU Pallas kernel
IMPL_LUT = "pallas_lut"   # kernels/tlmm_lut paper-faithful table lookup
IMPL_REF = "ref"          # dense ternary oracle (tests)


def init(key: jax.Array, n_in: int, n_out: int, *, bias: bool = False,
         dtype=jnp.float32) -> dict:
    """Initialize a QAT-mode BitLinear: master weights + optional bias."""
    scale = 1.0 / jnp.sqrt(jnp.asarray(n_in, jnp.float32))
    p = {"w": (jax.random.normal(key, (n_in, n_out), jnp.float32) * scale
               ).astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((n_out,), dtype)
    return p


ROW_MULTIPLE = 64  # packed rows pad to this so they shard on any model axis


def pack(params: dict, g: int = ternary.DEFAULT_G,
         row_multiple: int = ROW_MULTIPLE) -> dict:
    """Offline preprocessing: master weights -> base-3 packed codes + scale.

    The group size ``g`` is static metadata and is NOT stored in the pytree
    (it would become a traced array under jit) — callers pass it statically.
    Rows pad to ``row_multiple`` (WBMU-style alignment) for mesh sharding.
    """
    wt, gamma = ternary.ternarize(params["w"])
    packed = {
        "codes": ternary.pack_ternary(wt, g, row_multiple),
        "gamma": gamma.astype(jnp.float32),
    }
    if "b" in params:
        packed["b"] = params["b"]
    return packed


def apply_qat(params: dict, x: jax.Array, *, quantize_acts: bool = True,
              int8_fwd: bool = False) -> jax.Array:
    """Training forward: fake-quant W (ternary) and x (int8), dense matmul.

    int8_fwd=True executes the forward contraction on the integer path
    (int8×int8→int32, dequant in the epilogue) — identical math to the
    fake-quant bf16 dot up to float associativity, but on TPU it runs at the
    MXU's 2× int8 rate.  Backward stays bf16 with the usual STEs (§Perf
    cell A, beyond-paper optimization)."""
    if int8_fwd:
        y = _int8_ste_matmul(x, params["w"])
    else:
        w = ternary.ternarize_ste(params["w"])
        if quantize_acts:
            x = ternary.absmax_quant_ste(x)
        y = jnp.dot(x, w.astype(x.dtype))
    if "b" in params:
        y = y + params["b"].astype(y.dtype)
    return y


@jax.custom_vjp
def _int8_ste_matmul(x: jax.Array, w: jax.Array) -> jax.Array:
    """(..., n) × (n, k): int8 forward, STE backward.

    Forward: absmax-int8 x, absmean-ternary w, int8 dot, scale epilogue.
    Backward (STE through both quantizers): dx = g·(γ·Wt)ᵀ, dW = x̂ᵀ·g.
    """
    y, _ = _int8_fwd(x, w)
    return y


def _int8_fwd(x, w):
    lead = x.shape[:-1]
    xf = x.reshape(-1, x.shape[-1])
    xq, xs = ternary.absmax_quant(xf)
    wt, gamma = ternary.ternarize(w)
    acc = jnp.dot(xq.astype(jnp.int8), wt,
                  preferred_element_type=jnp.int32)
    y = (acc.astype(jnp.float32) * xs * gamma).astype(x.dtype)
    return y.reshape(lead + (w.shape[-1],)), (x, w)


def _int8_bwd(res, g):
    x, w = res
    wt, gamma = ternary.ternarize(w)
    w_deq = (wt.astype(jnp.float32) * gamma).astype(x.dtype)
    xq, xs = ternary.absmax_quant(x)
    x_deq = (xq.astype(jnp.float32) * xs).astype(x.dtype)
    dx = jnp.einsum("...k,nk->...n", g, w_deq)
    dw = jnp.einsum("...n,...k->nk", x_deq, g).astype(w.dtype)
    return dx, dw


_int8_ste_matmul.defvjp(_int8_fwd, _int8_bwd)


def apply_packed(params: dict, x: jax.Array, *, g: int = ternary.DEFAULT_G,
                 impl: str = IMPL_XLA, out_dtype=jnp.bfloat16) -> jax.Array:
    """Inference forward on packed ternary weights.

    x: (..., n_in) float -> (..., n_out) out_dtype.
    Activation absmax-int8 quant and the gamma*scale dequant are fused around
    the integer matmul (the paper's TLMM-FUSE streaming boundary).
    """
    codes, gamma = params["codes"], params["gamma"]
    n_in = x.shape[-1]
    lead = x.shape[:-1]
    xf = x.reshape(-1, x.shape[-1])
    x_q, x_scale = ternary.absmax_quant(xf)

    if impl == IMPL_REF:
        wt = ternary.unpack_ternary(codes, g, n_in)
        acc = ternary.ternary_matmul_ref(x_q, wt)
    elif impl == IMPL_XLA:
        acc = ternary.ternary_matmul_packed_xla(x_q, codes, g, n_in)
    elif impl == IMPL_PALLAS:
        from repro.kernels.tlmm import ops as tlmm_ops
        acc = tlmm_ops.tlmm(x_q, codes, g=g, n=n_in)
    elif impl == IMPL_LUT:
        from repro.kernels.tlmm_lut import ops as lut_ops
        acc = lut_ops.tlmm_lut(x_q, codes, g=g)
    else:
        raise ValueError(f"unknown impl {impl!r}")

    y = acc.astype(jnp.float32) * x_scale * gamma
    if "b" in params:
        y = y + params["b"].astype(jnp.float32)
    return y.astype(out_dtype).reshape(lead + (codes.shape[-1],))


def predecode(params: dict, *, g: int = ternary.DEFAULT_G) -> dict:
    """Decode packed base-3 codes into a dense int8 ternary matrix.

    The serving engine calls this once, when it is built, and keeps the
    int8 matrices resident: nothing about the weights changes between
    dispatches, so neither the decode block nor the admission wave
    re-expands them.  The returned dict routes ``linear_apply`` through
    :func:`apply_predecoded`, whose math is bit-identical to
    ``apply_packed``'s XLA path (the same integer contraction and float
    epilogue, minus the unpack).
    """
    out = {"wt": ternary.unpack_ternary(params["codes"], g),
           "gamma": params["gamma"]}
    if "b" in params:
        out["b"] = params["b"]
    return out


def predecode_fused(parts: list, *, g: int = ternary.DEFAULT_G) -> dict:
    """Fuse several packed linears that share the same input into ONE
    pre-decoded matrix (n_in, sum n_out) with a per-column scale vector.

    One activation quant + one GEMM per tick instead of one per projection
    (QKV fusion, gate|up fusion — the classic serving-decode op-count cut).
    Bit-identical to applying the parts separately: the shared input row has
    a single absmax scale either way, each output column keeps its own
    gamma, and every column's contraction is unchanged.
    """
    decoded = [predecode(p, g=g) for p in parts]
    wt = jnp.concatenate([d["wt"] for d in decoded], axis=1)
    gamma = jnp.concatenate([
        jnp.broadcast_to(d["gamma"], (d["wt"].shape[1],)) for d in decoded])
    out = {"wt": wt, "gamma": gamma}
    if any("b" in d for d in decoded):
        out["b"] = jnp.concatenate([
            d["b"] if "b" in d else jnp.zeros((d["wt"].shape[1],),
                                              jnp.float32)
            for d in decoded])
    return out


def apply_predecoded(params: dict, x: jax.Array, *,
                     out_dtype=jnp.bfloat16) -> jax.Array:
    """Inference forward on pre-decoded int8 ternary weights (see predecode).

    Bit-identical to ``apply_packed``'s XLA path: the same absmax int8
    activations, an exact int8 x int8 -> int32 contraction, the same
    epilogue.  The operands stay int8, which the MXU takes at its int8
    rate; widening them first would take the contraction off that path.
    """
    wt, gamma = params["wt"], params["gamma"]
    lead = x.shape[:-1]
    x_q, x_scale = ternary.absmax_quant(x.reshape(-1, x.shape[-1]))
    n_pad = wt.shape[0]
    if x_q.shape[-1] < n_pad:  # same zero-pad as the packed XLA path
        x_q = jnp.pad(x_q, [(0, 0), (0, n_pad - x_q.shape[-1])])
    acc = jax.lax.dot_general(x_q, wt, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    y = acc.astype(jnp.float32) * x_scale * gamma
    if "b" in params:
        y = y + params["b"].astype(jnp.float32)
    return y.astype(out_dtype).reshape(lead + (wt.shape[-1],))


def apply(params: dict, x: jax.Array, *, mode: str = "qat",
          impl: str = IMPL_XLA, g: int = ternary.DEFAULT_G,
          out_dtype=None) -> jax.Array:
    if mode == "qat":
        return apply_qat(params, x)
    if mode == "packed":
        return apply_packed(params, x, g=g, impl=impl,
                            out_dtype=out_dtype or jnp.bfloat16)
    if mode == "dense":  # unquantized baseline (paper's FP comparisons)
        y = jnp.dot(x, params["w"].astype(x.dtype))
        if "b" in params:
            y = y + params["b"].astype(y.dtype)
        return y
    raise ValueError(f"unknown mode {mode!r}")
