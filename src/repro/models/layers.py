"""Shared model layers: RMSNorm, RoPE (paper eq. 4/5/6), SwiGLU, MoE, embeds.

All linear projections are BitLinear (ternary W1.58A8) when cfg.ternary, so the
paper's technique is a first-class feature of every architecture.  Layers are
pure functions over dict pytrees; a ``Ctx`` carries the (static) execution
mode.  Whether a given linear is ternary is decided statically by the caller
(routers and the LM head stay dense, as in BitNet practice).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core import bitlinear, ternary


@dataclasses.dataclass(frozen=True)
class Ctx:
    """Execution context threaded through the model (all fields static)."""
    mode: str = "qat"        # qat (train fake-quant) | packed (inference) | dense
    impl: str = "xla"        # xla | pallas | pallas_lut | ref   (packed matmul)
    group_size: int = 5      # base-3 pack group (static; matches cfg)
    attn_impl: str = "xla"   # xla (causal-skip scan) | xla_naive | pallas
    attn_q_chunk: int = 512
    attn_kv_chunk: int = 512
    moe_token_chunk: int = 0  # scan MoE dispatch over token chunks (0 = off)
    kv_quant: bool = False    # int8 KV cache (beyond-paper: W1.58A8+KV8)
    # flash-decoding over the KV sequence: 0 = off; K >= 1 routes decode
    # attention through the canonical K-chunk partial-softmax formulation
    # (kernels.decode_attention.ops.splitk_partials/combine) whose result
    # is bitwise invariant to how the chunks are distributed.  With
    # kv_shard_axis set (a mesh axis name, used inside shard_map) each of
    # the axis's ``kv_shard_size`` devices computes K / size chunks and the
    # partials are all_gather'd in chunk order before the shared combine.
    kv_splits: int = 0
    kv_shard_axis: object = None   # mesh axis name (str) or None
    kv_shard_size: int = 1         # static size of kv_shard_axis
    remat_policy: str = "nothing"  # nothing | dots (save matmul outputs)
    qat_int8_fwd: bool = False  # run QAT forward on the int8 MXU path
    act_dtype: str = "float32"
    # optional sharding-constraint hook: (x, kind) -> x  (kind: "residual" |
    # "logits" | "expert_buf"); installed by the launcher, identity otherwise
    constrain: object = None

    def c(self, x, kind: str):
        return self.constrain(x, kind) if self.constrain is not None else x

    @property
    def dtype(self):
        return jnp.dtype(self.act_dtype)


# ---------------------------------------------------------------------------
# Linear dispatch
# ---------------------------------------------------------------------------

def linear_init(key, n_in, n_out, *, bias=False, dtype=jnp.float32):
    return bitlinear.init(key, n_in, n_out, bias=bias, dtype=dtype)


def linear_apply(p: dict, x: jax.Array, ctx: Ctx, *,
                 ternary_w: bool = True) -> jax.Array:
    if "wt" in p:  # pre-decoded ternary (serving decode hot loop)
        return bitlinear.apply_predecoded(p, x, out_dtype=x.dtype)
    if "codes" in p:  # packed inference params
        return bitlinear.apply_packed(p, x, g=ctx.group_size, impl=ctx.impl,
                                      out_dtype=x.dtype)
    if ctx.mode == "qat" and ternary_w:
        return bitlinear.apply_qat(p, x, int8_fwd=ctx.qat_int8_fwd)
    return bitlinear.apply(p, x, mode="dense")


def linear_pack(p: dict, g: int, *, ternary_w: bool = True) -> dict:
    """Offline packing of one linear (dense layers pass through)."""
    return bitlinear.pack(p, g) if ternary_w else dict(p)


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------

def rmsnorm_init(d: int, dtype=jnp.float32) -> dict:
    return {"w": jnp.ones((d,), dtype)}


def rmsnorm(p: dict, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * p["w"].astype(jnp.float32)
            ).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE — both of the paper's formulations (§3.3.3)
# ---------------------------------------------------------------------------

def rope_angles(positions: jax.Array, hd: int, theta: float) -> jax.Array:
    """(s,) or (b, s) int positions -> (s, hd/2) or (b, s, hd/2) angles.

    The batched form carries ragged per-request decode positions (each slot
    in a continuous batch sits at its own cache offset)."""
    t = jnp.arange(hd // 2, dtype=jnp.float32)
    inv_freq = theta ** (-2.0 * t / hd)
    return positions.astype(jnp.float32)[..., None] * inv_freq


def apply_rope(x: jax.Array, angles: jax.Array, style: str) -> jax.Array:
    """x: (..., s, n_heads, hd); angles: (s, hd/2) or (b, s, hd/2).

    style="consecutive" — paper eq. 5 (rotate contiguous halves; the
    streaming-friendly form TeLLMe uses after the eq. 6 weight permutation).
    style="interleaved" — paper eq. 4 (canonical LLaMA pairing).
    """
    cos = jnp.cos(angles)[..., :, None, :].astype(x.dtype)  # (..., s, 1, hd/2)
    sin = jnp.sin(angles)[..., :, None, :].astype(x.dtype)
    hd = x.shape[-1]
    if style == "consecutive":
        x1 = x[..., : hd // 2]
        x2 = x[..., hd // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               axis=-1)
    elif style == "interleaved":
        x1 = x[..., 0::2]
        x2 = x[..., 1::2]
        out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
        return out.reshape(x.shape)
    raise ValueError(style)


def rope_weight_permutation(hd: int) -> jnp.ndarray:
    """Paper eq. 6: per-head index exchange converting interleaved-RoPE
    weights to consecutive-RoPE weights losslessly.

    Returns perm with perm[2t] = t, perm[2t+1] = hd/2 + t; applying
    W[..., perm] to interleaved weights yields weights whose consecutive-RoPE
    output (reordered by the same perm) matches the interleaved-RoPE output.
    """
    perm = jnp.zeros((hd,), jnp.int32)
    t = jnp.arange(hd // 2)
    perm = perm.at[2 * t].set(t)
    perm = perm.at[2 * t + 1].set(hd // 2 + t)
    return perm


# ---------------------------------------------------------------------------
# SwiGLU MLP (gate/up/down — the three TLMM sizes of §3.2.1)
# ---------------------------------------------------------------------------

def mlp_init(key, d_model: int, d_ff: int, *, dtype=jnp.float32) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "gate": linear_init(k1, d_model, d_ff, dtype=dtype),
        "up": linear_init(k2, d_model, d_ff, dtype=dtype),
        "down": linear_init(k3, d_ff, d_model, dtype=dtype),
    }


def mlp_apply(p: dict, x: jax.Array, ctx: Ctx, *, ternary_w=True) -> jax.Array:
    with jax.named_scope("gate_up"):
        if "gateup" in p:  # fused projection (pre-decoded serving hot path)
            gu = linear_apply(p["gateup"], x, ctx, ternary_w=ternary_w)
            g, u = jnp.split(gu, 2, axis=-1)
        else:
            g = linear_apply(p["gate"], x, ctx, ternary_w=ternary_w)
            u = linear_apply(p["up"], x, ctx, ternary_w=ternary_w)
        h = jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32)
    with jax.named_scope("down"):
        return linear_apply(p["down"], h.astype(x.dtype), ctx,
                            ternary_w=ternary_w)


def mlp_pack(p: dict, g: int) -> dict:
    return {name: linear_pack(p[name], g) for name in ("gate", "up", "down")}


# ---------------------------------------------------------------------------
# MoE (capacity + scatter dispatch; experts are ternary)
# ---------------------------------------------------------------------------

def moe_init(key, d_model: int, d_ff: int, n_experts: int, *,
             dtype=jnp.float32) -> dict:
    kr, kg, ku, kd = jax.random.split(key, 4)
    scale = 1.0 / jnp.sqrt(jnp.asarray(d_model, jnp.float32))

    def expert_bank(k, n_in, n_out):
        return (jax.random.normal(k, (n_experts, n_in, n_out), jnp.float32)
                * scale).astype(dtype)

    return {
        "router": linear_init(kr, d_model, n_experts, dtype=dtype),
        "gate_w": expert_bank(kg, d_model, d_ff),
        "up_w": expert_bank(ku, d_model, d_ff),
        "down_w": expert_bank(kd, d_ff, d_model),
    }


def _expert_matmul(w: jax.Array, x: jax.Array, ctx: Ctx) -> jax.Array:
    """Batched per-expert matmul with QAT ternary fake-quant on the bank.

    w: (E, n_in, n_out) master weights; x: (E, C, n_in).
    """
    if ctx.mode == "qat":
        w = jax.vmap(ternary.ternarize_ste)(w)
        x = ternary.absmax_quant_ste(x)
    return jnp.einsum("ecd,edf->ecf", x, w.astype(x.dtype))


def _expert_matmul_packed(codes: jax.Array, gamma: jax.Array, n_in: int,
                          g: int, x: jax.Array) -> jax.Array:
    """Packed bank: codes (E, rows, n_out), gamma (E,), x (E, C, n_in).

    Activations zero-pad up to rows*g (padded rows hold zero weights)."""
    xq, xs = ternary.absmax_quant(x)
    n_pad = codes.shape[1] * g
    if xq.shape[-1] < n_pad:
        xq = jnp.pad(xq, ((0, 0), (0, 0), (0, n_pad - xq.shape[-1])))
    wt = jax.vmap(lambda c: ternary.unpack_ternary(c, g))(codes)
    acc = jnp.einsum("ecd,edf->ecf", xq.astype(jnp.int32),
                     wt.astype(jnp.int32))
    return acc.astype(jnp.float32) * xs * gamma[:, None, None]


def moe_apply(p: dict, x: jax.Array, *, top_k: int, capacity_factor: float,
              ctx: Ctx) -> jax.Array:
    """Top-k MoE with capacity + scatter dispatch (drops on overflow).

    x: (n_tokens, d_model) — caller flattens (b, s).  When
    ctx.moe_token_chunk is set and n is large, dispatch runs as a scan over
    token chunks: the (E, capacity, d) buffers are bounded by the chunk, not
    the full 1M-token prefill (which would be a 32 GiB/device dispatch
    buffer — measured).
    """
    tc = ctx.moe_token_chunk
    if tc and x.shape[0] > tc and x.shape[0] % tc == 0:
        xc = x.reshape(x.shape[0] // tc, tc, x.shape[1])

        def body(_, xi):
            return None, _moe_apply_dense_or_packed(
                p, xi, top_k=top_k, capacity_factor=capacity_factor, ctx=ctx)

        _, ys = jax.lax.scan(body, None, xc)
        return ys.reshape(x.shape)
    return _moe_apply_dense_or_packed(p, x, top_k=top_k,
                                      capacity_factor=capacity_factor,
                                      ctx=ctx)


def _moe_apply_dense_or_packed(p: dict, x: jax.Array, *, top_k: int,
                               capacity_factor: float, ctx: Ctx) -> jax.Array:
    n, d = x.shape
    packed = "gate_codes" in p
    n_experts = (p["gate_codes"].shape[0] if packed else p["gate_w"].shape[0])
    logits = linear_apply(p["router"], x, ctx, ternary_w=False)
    gates, idx = jax.lax.top_k(logits.astype(jnp.float32), top_k)    # (n, k)
    gates = jax.nn.softmax(gates, axis=-1)

    capacity = max(int(n * top_k / n_experts * capacity_factor), top_k)
    flat_idx = idx.reshape(-1)                                       # (n*k,)
    onehot = jax.nn.one_hot(flat_idx, n_experts, dtype=jnp.int32)    # (n*k, E)
    pos_in_expert = jnp.cumsum(onehot, axis=0) - onehot              # exclusive
    pos = jnp.sum(pos_in_expert * onehot, axis=-1)                   # (n*k,)
    keep = pos < capacity
    safe_pos = jnp.where(keep, pos, capacity - 1)

    tok_idx = jnp.repeat(jnp.arange(n), top_k)
    buf = jnp.zeros((n_experts, capacity, d), x.dtype)
    buf = buf.at[flat_idx, safe_pos].add(
        jnp.where(keep[:, None], x[tok_idx], 0))
    buf = ctx.c(buf, "expert_buf")  # expert-parallel layout constraint

    if packed:
        g = ctx.group_size
        h_g = _expert_matmul_packed(p["gate_codes"], p["gate_gamma"], d, g, buf)
        h_u = _expert_matmul_packed(p["up_codes"], p["up_gamma"], d, g, buf)
        h = (jax.nn.silu(h_g) * h_u).astype(x.dtype)
        out_buf = _expert_matmul_packed(p["down_codes"], p["down_gamma"],
                                        h.shape[-1], g, h).astype(x.dtype)
    else:
        h_g = _expert_matmul(p["gate_w"], buf, ctx).astype(jnp.float32)
        h_u = _expert_matmul(p["up_w"], buf, ctx).astype(jnp.float32)
        h = (jax.nn.silu(h_g) * h_u).astype(x.dtype)
        out_buf = _expert_matmul(p["down_w"], h, ctx)

    gathered = out_buf[flat_idx, safe_pos]                           # (n*k, d)
    gathered = jnp.where(keep[:, None], gathered, 0)
    weighted = gathered * gates.reshape(-1)[:, None].astype(gathered.dtype)
    return jnp.zeros_like(x).at[tok_idx].add(weighted)


def moe_pack(p: dict, g: int) -> dict:
    """Offline base-3 packing of the expert banks."""
    out = {"router": dict(p["router"])}
    for name in ("gate", "up", "down"):
        w = p[f"{name}_w"]  # (E, n_in, n_out)
        wts, gammas = jax.vmap(ternary.ternarize)(w)
        out[f"{name}_codes"] = jax.vmap(
            lambda wt: ternary.pack_ternary(wt, g, bitlinear.ROW_MULTIPLE)
        )(wts)
        out[f"{name}_gamma"] = gammas
    return out


# ---------------------------------------------------------------------------
# Embedding / LM head
# ---------------------------------------------------------------------------

def embed_init(key, vocab: int, d_model: int, dtype=jnp.float32) -> dict:
    return {"tok": (jax.random.normal(key, (vocab, d_model), jnp.float32)
                    * 0.02).astype(dtype)}


def embed_apply(p: dict, tokens: jax.Array) -> jax.Array:
    return jnp.take(p["tok"], tokens, axis=0)
