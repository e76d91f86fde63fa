"""Ternary (1.58-bit) quantization and base-3 packing — the paper's W1.58A8 scheme.

TeLLMe consumes BitNet b1.58 models: weights in {-1, 0, +1} with a single
per-tensor FP scale (absmean quantization, BitNet b1.58 recipe), activations in
int8 with a per-token absmax scale.

Packing: groups of ``G`` ternary values along the *reduction* dimension are
encoded as one base-3 integer.  The paper uses G=3 -> 5-bit indices (1.67
bits/weight) sized for URAM words; on TPU we default to G=5 -> one uint8 per 5
weights (1.6 bits/weight), which is byte-addressable and closer to the 1.58-bit
ideal.  Both are supported; all pack/unpack code is generic in G.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

# Default group size on TPU: 3^5 = 243 <= 255 fits a uint8 exactly.
DEFAULT_G = 5
# The paper's FPGA group size (3^3 = 27 -> 5-bit indices packed into URAM words).
PAPER_G = 3

_POW3 = np.array([1, 3, 9, 27, 81, 243, 729], dtype=np.int32)


def num_codes(g: int) -> int:
    """Number of distinct base-3 codes for a group of size g (paper: N_TB)."""
    return 3 ** g


def index_bits(g: int) -> int:
    """Bit width of one group index (paper: B_idx = ceil(log2 3^G))."""
    return int(np.ceil(np.log2(3.0 ** g)))


def bits_per_weight(g: int, container_bits: int = 8) -> float:
    """Effective bits/weight when each group index lives in its own container.

    With g=5, container=8: 1.6 bits/weight.  The paper packs 5-bit (g=3)
    indices into 72-bit URAM words -> 1.67 bits/weight.
    """
    return container_bits / g


# ---------------------------------------------------------------------------
# Ternary weight quantization (BitNet b1.58 absmean recipe)
# ---------------------------------------------------------------------------

def absmean_scale(w: jax.Array, eps: float = 1e-5) -> jax.Array:
    """Per-tensor absmean scale: gamma = mean(|W|)."""
    return jnp.maximum(jnp.mean(jnp.abs(w.astype(jnp.float32))), eps)


def ternarize(w: jax.Array, eps: float = 1e-5) -> Tuple[jax.Array, jax.Array]:
    """BitNet b1.58 weight quant: W_t = clip(round(W / gamma), -1, 1).

    Returns (ternary int8 in {-1,0,1}, scalar f32 scale gamma).
    """
    gamma = absmean_scale(w, eps)
    wt = jnp.clip(jnp.round(w.astype(jnp.float32) / gamma), -1.0, 1.0)
    return wt.astype(jnp.int8), gamma


def ternarize_ste(w: jax.Array, eps: float = 1e-5) -> jax.Array:
    """Fake-quant ternarization with straight-through estimator (training path).

    Forward: gamma * ternary(W).  Backward: identity (gradient flows to W).
    """
    gamma = absmean_scale(w, eps)
    wt = jnp.clip(jnp.round(w.astype(jnp.float32) / gamma), -1.0, 1.0) * gamma
    wt = wt.astype(w.dtype)
    return w + jax.lax.stop_gradient(wt - w)


# ---------------------------------------------------------------------------
# INT8 activation quantization (per-token ABSMAX, the paper's RMS-MAX output)
# ---------------------------------------------------------------------------

def absmax_quant(x: jax.Array, axis: int = -1, eps: float = 1e-5
                 ) -> Tuple[jax.Array, jax.Array]:
    """Per-token absmax int8 quantization.

    Returns (int8 values, f32 scale with the quantized axis kept at size 1)
    such that x ~= values * scale.
    """
    xf = x.astype(jnp.float32)
    amax = jnp.maximum(jnp.max(jnp.abs(xf), axis=axis, keepdims=True), eps)
    scale = amax / 127.0
    q = jnp.clip(jnp.round(xf / scale), -127, 127)
    return q.astype(jnp.int8), scale


def absmax_quant_ste(x: jax.Array, axis: int = -1, eps: float = 1e-5) -> jax.Array:
    """Fake-quant absmax int8 with STE (training path)."""
    q, scale = absmax_quant(x, axis=axis, eps=eps)
    xq = (q.astype(jnp.float32) * scale).astype(x.dtype)
    return x + jax.lax.stop_gradient(xq - x)


# ---------------------------------------------------------------------------
# Base-3 group packing (the TLMM weight-index encoding)
# ---------------------------------------------------------------------------

def pad_to_group(n: int, g: int) -> int:
    """Padded reduction length (paper: d' padded to multiples of T*G)."""
    return ((n + g - 1) // g) * g


def pack_ternary(wt: jax.Array, g: int = DEFAULT_G,
                 row_multiple: int = 1) -> jax.Array:
    """Pack ternary weights into base-3 group indices along axis 0.

    wt: int8 {-1,0,1} of shape (n, ...) -> uint8 codes of shape (rows, ...)
    with rows = ceil(n/g) rounded up to ``row_multiple``.  Each code is
    sum_{i<g} (w_i + 1) * 3^i, i.e. digits in {0,1,2}.  Zero-padding (digit 1
    == weight 0) is the paper's WBMU buffer padding (§3.4.2): it makes the
    packed reduction dim evenly divisible — there for URAM bank alignment,
    here so the packed rows shard cleanly on the mesh's model axis.
    """
    if g > 5:
        raise ValueError("g > 5 does not fit a uint8 container")
    n = wt.shape[0]
    n_pad = pad_to_group(n, g * row_multiple)
    if n_pad != n:
        pad_width = [(0, n_pad - n)] + [(0, 0)] * (wt.ndim - 1)
        wt = jnp.pad(wt, pad_width)  # pads with 0 == ternary zero
    digits = (wt.astype(jnp.int32) + 1)  # {0,1,2}
    grouped = digits.reshape((n_pad // g, g) + wt.shape[1:])
    pow3 = jnp.asarray(_POW3[:g]).reshape((1, g) + (1,) * (wt.ndim - 1))
    codes = jnp.sum(grouped * pow3, axis=1)
    return codes.astype(jnp.uint8)


def unpack_ternary(codes: jax.Array, g: int = DEFAULT_G,
                   n: int | None = None) -> jax.Array:
    """Inverse of pack_ternary: uint8 codes -> int8 {-1,0,1} along axis 0.

    n: original (unpadded) reduction length; defaults to codes.shape[0]*g.
    """
    c = codes.astype(jnp.int32)
    digs = []
    for _ in range(g):
        digs.append((c % 3) - 1)
        c = c // 3
    w = jnp.stack(digs, axis=1)  # (groups, g, ...)
    w = w.reshape((codes.shape[0] * g,) + codes.shape[1:])
    if n is not None:
        w = w[:n]
    return w.astype(jnp.int8)


def enumeration_matrix(g: int, dtype=jnp.int8) -> jax.Array:
    """C in {-1,0,1}^{g x 3^g}: column c holds the digits of code c.

    The paper's 'precompute adder tree' is exactly  tables = A_groups @ C :
    row i of (A grouped) dotted with column c of C gives the partial sum the
    FPGA stores at table entry c.  Computing it as a matmul is the MXU-native
    formulation of the precompute unit.
    """
    codes = np.arange(3 ** g, dtype=np.int64)
    digits = np.empty((g, 3 ** g), dtype=np.int8)
    for i in range(g):
        digits[i] = (codes % 3) - 1
        codes = codes // 3
    return jnp.asarray(digits, dtype=dtype)


# ---------------------------------------------------------------------------
# Reference ternary matmuls (oracles; also the XLA in-graph inference path)
# ---------------------------------------------------------------------------

def ternary_matmul_ref(a_q: jax.Array, wt: jax.Array) -> jax.Array:
    """Dense oracle: int8 activations (m, n) x ternary int8 (n, k) -> int32."""
    return jnp.dot(a_q.astype(jnp.int32), wt.astype(jnp.int32),
                   preferred_element_type=jnp.int32)


def ternary_matmul_packed_xla(a_q: jax.Array, codes: jax.Array, g: int,
                              n: int | None = None) -> jax.Array:
    """XLA in-graph path: unpack base-3 codes then int8 dot.

    This is what the dry-run lowers (so HLO byte counts reflect packed weights
    in HBM); on real TPU the Pallas `tlmm` kernel replaces it and keeps the
    unpacked weights in registers.  Activations are zero-padded up to the
    (row_multiple-padded) packed length rather than slicing the weights, so
    the contraction dim stays shardable.
    """
    n_pad = codes.shape[0] * g
    wt = unpack_ternary(codes, g)
    a = a_q
    if a.shape[-1] < n_pad:
        widths = [(0, 0)] * (a.ndim - 1) + [(0, n_pad - a.shape[-1])]
        a = jnp.pad(a, widths)
    return ternary_matmul_ref(a, wt)


def ternary_matmul_lut_ref(a_q: jax.Array, codes: jax.Array, g: int) -> jax.Array:
    """Paper-faithful table-lookup matmul oracle (Method 3, full table).

    Stage 1 (precompute): tables[m, group, c] = sum over the group of
      a[m, group*g + i] * digit_i(c)  ==  A_grouped @ C.
    Stage 2 (lookup): out[m, k] = sum_group tables[m, group, codes[group, k]].
    """
    m, n = a_q.shape
    n_groups = codes.shape[0]
    n_pad = n_groups * g
    a = a_q.astype(jnp.int32)
    if n_pad != n:
        a = jnp.pad(a, ((0, 0), (0, n_pad - n)))
    a_grouped = a.reshape(m, n_groups, g)
    c_mat = enumeration_matrix(g, dtype=jnp.int32)  # (g, 3^g)
    tables = jnp.einsum("mng,gc->mnc", a_grouped, c_mat)  # (m, groups, 3^g)
    # Lookup: gather along the code axis per (group, k).
    looked = jnp.take_along_axis(
        tables[:, :, :],  # (m, groups, 3^g)
        codes.astype(jnp.int32)[None, :, :],  # (1, groups, k)
        axis=2,
    )  # (m, groups, k)
    return jnp.sum(looked, axis=1, dtype=jnp.int32)
