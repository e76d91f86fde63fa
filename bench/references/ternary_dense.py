"""Plain float32 reference of a dense pre-norm decoder with W1.58A8 linears.

The model the configurations in ``bench/configs`` name: token embedding;
per layer RMSNorm, ternary q/k/v projections, rotary embedding on
contiguous halves of each head, causal softmax attention (grouped heads:
query head h reads key/value head ``h // (heads / kv_heads)``), ternary
o-projection and residual; RMSNorm, ternary gate and up, SiLU(gate) * up,
ternary down and residual; a final RMSNorm and a float32 head (the
embedding table itself when tied).  Each ternary linear quantizes its input
per token to ``qmax`` levels by its absolute maximum, multiplies by the
{-1, 0, +1} matrix and rescales by the input's scale and the matrix's gamma.

Written from that description alone: it imports nothing of the serving
program.  Weights arrive as the benchmark drew them (``bench/weights.py``):
base-3 codes, five ternary weights to a byte, least significant digit
first, digit ``d`` meaning weight ``d - 1``.  Every product runs at
``Precision.HIGHEST``, so the integer-valued contractions are exact and the
rest is float32.  ``CONTROLS`` names the lower precisions that serve as
controls.  Whole sequences only, with no cache: the reference
recomputes every position from the prompt and the tokens served.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 256  # attention is computed for this many queries at a time
INT8 = 127         # the configurations' 8-bit activations
INT4 = 7
# The controls, each this reference at a precision below the one the
# configurations state, as keyword arguments of ``Reference.logits``: 4-bit
# activations in place of 8-bit, and a bfloat16 residual stream and head in
# place of float32.
CONTROLS = {"int4": {"qmax": INT4}, "bf16": {"bf16": True}}


def decode_codes(codes: jax.Array, n_in: int, group: int) -> jax.Array:
    """(rows, n_out) uint8 base-3 codes -> (n_in, n_out) float32 ternary."""
    c = codes.astype(jnp.int32)
    digits = [(c // (3 ** i)) % 3 - 1 for i in range(group)]
    w = jnp.stack(digits, axis=1).reshape(codes.shape[0] * group,
                                          codes.shape[1])
    return w[:n_in].astype(jnp.float32)


def ternary_linear(x, p, n_in: int, group: int, qmax: int):
    amax = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-5)
    scale = amax / qmax
    xq = jnp.clip(jnp.round(x / scale), -qmax, qmax)
    w = decode_codes(p["codes"], n_in, group)
    return jnp.dot(xq, w, precision=HIGHEST) * scale * p["gamma"]


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def rope(x, positions, theta):
    """x: (S, heads, hd), rotated on contiguous halves."""
    hd = x.shape[-1]
    inv = theta ** (-2.0 * jnp.arange(hd // 2, dtype=jnp.float32) / hd)
    ang = positions.astype(jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v):
    """Causal attention. q: (S, H, hd); k, v: (S, KV, hd) -> (S, H * hd)."""
    s, h, hd = q.shape
    kv_h = k.shape[1]
    group = h // kv_h
    k = jnp.repeat(k, group, axis=1)  # query head j reads kv head j // group
    v = jnp.repeat(v, group, axis=1)
    nb = s // QUERY_BLOCK
    qb = q.reshape(nb, QUERY_BLOCK, h, hd)

    def block(args):
        i, qi = args
        sc = jnp.einsum("qhd,khd->hqk", qi, k, precision=HIGHEST) / np.sqrt(hd)
        qpos = i * QUERY_BLOCK + jnp.arange(QUERY_BLOCK)
        mask = jnp.arange(s)[None, None, :] <= qpos[None, :, None]
        p = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)

    out = jax.lax.map(block, (jnp.arange(nb), qb))
    return out.reshape(s, h * hd)


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


@functools.partial(jax.jit,
                   static_argnames=("cfg_items", "n_out", "qmax", "bf16"))
def _logits(weights, tokens, lo, *, cfg_items, n_out, qmax, bf16):
    cfg = dict(cfg_items)
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, kv_h = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    ff, g, eps = cfg["intermediate_size"], cfg["pack_group"], cfg["rms_norm_eps"]
    s = tokens.shape[0]
    positions = jnp.arange(s)
    x = weights["embed"]["tok"][tokens].astype(jnp.float32)
    rnd = _bf16 if bf16 else (lambda y: y)

    def layer(x, p):
        a = rmsnorm(x, p["ln1"]["w"], eps)
        lin = functools.partial(ternary_linear, group=g, qmax=qmax)
        q = lin(a, p["attn"]["q"], d).reshape(s, h, hd)
        k = lin(a, p["attn"]["k"], d).reshape(s, kv_h, hd)
        v = lin(a, p["attn"]["v"], d).reshape(s, kv_h, hd)
        q = rope(q, positions, cfg["rope_theta"])
        k = rope(k, positions, cfg["rope_theta"])
        x = rnd(x + lin(attention(q, k, v), p["attn"]["o"], h * hd))
        a = rmsnorm(x, p["ln2"]["w"], eps)
        m = (jax.nn.silu(lin(a, p["mlp"]["gate"], d))
             * lin(a, p["mlp"]["up"], d))
        return rnd(x + lin(m, p["mlp"]["down"], ff)), None

    x, _ = jax.lax.scan(layer, rnd(x), weights["layers"])
    x = jax.lax.dynamic_slice_in_dim(x, lo, n_out, axis=0)
    x = rmsnorm(x, weights["final_norm"]["w"], eps)
    head = (weights["embed"]["tok"].T if cfg["tie_word_embeddings"]
            else weights["lm_head"]["w"])
    if bf16:
        return jnp.dot(x.astype(jnp.bfloat16), head.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
    return jnp.dot(x, head, precision=HIGHEST)


class Reference:
    """Logits of the plain model over whole sequences of one padded length.

    ``seq_len`` bounds a prompt plus its outputs and ``n_out`` the outputs
    of one request.  One compiled program serves every request of a cell:
    sequences are padded on the right, which causality keeps out of the
    positions read, to a whole number of query blocks with room for the
    ``n_out`` rows read after the prompt."""

    def __init__(self, cfg: dict, weights: dict, seq_len: int, n_out: int):
        self.cfg_items = tuple(sorted(
            (k, v) for k, v in cfg.items()
            if isinstance(v, (int, float, bool)) and not isinstance(v, list)))
        self.weights = weights
        self.seq_len = -(-(seq_len + n_out) // QUERY_BLOCK) * QUERY_BLOCK
        self.n_out = n_out

    def logits(self, prompt, served, qmax: int = INT8,
               bf16: bool = False) -> np.ndarray:
        """(len(served), vocab) float32: row j scores the token served at
        output position j, given the prompt and the tokens served before."""
        seq = np.concatenate([np.asarray(prompt), np.asarray(served)[:-1]])
        if len(seq) + self.n_out > self.seq_len or len(served) > self.n_out:
            raise ValueError(f"sequence of {len(seq)} with {len(served)} "
                             f"outputs exceeds the reference's "
                             f"{self.seq_len} / {self.n_out}")
        tokens = np.zeros((self.seq_len,), np.int32)
        tokens[:len(seq)] = seq
        lo = len(prompt) - 1
        out = _logits(self.weights, jnp.asarray(tokens),
                      jnp.asarray(lo, jnp.int32), cfg_items=self.cfg_items,
                      n_out=self.n_out, qmax=qmax, bf16=bf16)
        return np.asarray(out)[:len(served)]


def gaps(ref_logits: np.ndarray, tokens) -> np.ndarray:
    """For each row, how far the given token's logit lies below the row's
    best: 0 where the token is the reference's own greedy choice."""
    tokens = np.asarray(tokens)
    rows = np.arange(len(tokens))
    return ref_logits.max(axis=-1) - ref_logits[rows, tokens]
