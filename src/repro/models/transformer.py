"""Decoder-only model assembly for every assigned architecture.

One scanned block body per ``cfg.block_kind`` (attn | hymba | xlstm_pair);
layer parameters are stacked along a leading L axis and the stack is consumed
by ``jax.lax.scan`` — one compiled layer body regardless of depth, which keeps
80-layer 72B dry-run compiles tractable and is the idiomatic JAX production
pattern (MaxText does the same).

Four entry points mirror the paper's phases:
  * ``forward``       — full-sequence logits (training; QAT ternary path)
  * ``prefill_step``  — full prompt -> last-token logits + filled KV cache
  * ``prefill_chunk`` — one admission wave: per-slot prompt chunks ->
    masked in-place KV writes at per-row offsets of the shared multi-slot
    cache, each attending its already-written prefix (chunked
    continuous-batching admission)
  * ``decode_step``   — one token + cache -> next logits + updated cache
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import bitlinear
from repro.models import attention, layers, ssm, xlstm
from repro.models.layers import Ctx


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _attn_init(key, cfg: ModelConfig, dtype) -> dict:
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {
        "q": layers.linear_init(kq, cfg.d_model, cfg.q_dim,
                                bias=cfg.qkv_bias, dtype=dtype),
        "k": layers.linear_init(kk, cfg.d_model, cfg.kv_dim,
                                bias=cfg.qkv_bias, dtype=dtype),
        "v": layers.linear_init(kv, cfg.d_model, cfg.kv_dim,
                                bias=cfg.qkv_bias, dtype=dtype),
        "o": layers.linear_init(ko, cfg.q_dim, cfg.d_model, dtype=dtype),
    }


def _layer_init(key, cfg: ModelConfig, dtype) -> dict:
    if cfg.block_kind == "xlstm_pair":
        k1, k2 = jax.random.split(key)
        return {
            "ln1": layers.rmsnorm_init(cfg.d_model, dtype),
            "mlstm": xlstm.mlstm_init(k1, cfg.d_model, cfg.n_heads, cfg.hd,
                                      dtype),
            "ln2": layers.rmsnorm_init(cfg.d_model, dtype),
            "slstm": xlstm.slstm_init(k2, cfg.d_model, cfg.n_heads, cfg.hd,
                                      dtype),
        }
    p = {
        "ln1": layers.rmsnorm_init(cfg.d_model, dtype),
        "ln2": layers.rmsnorm_init(cfg.d_model, dtype),
    }
    ka, ks, km = jax.random.split(key, 3)
    p["attn"] = _attn_init(ka, cfg, dtype)
    if cfg.block_kind == "hymba":
        p["ssm"] = ssm.ssm_init(ks, cfg.d_model, cfg.n_heads, cfg.hd,
                                cfg.ssm_state, cfg.ssm_conv, dtype)
    if cfg.n_experts:
        p["moe"] = layers.moe_init(km, cfg.d_model, cfg.d_ff, cfg.n_experts,
                                   dtype=dtype)
    elif cfg.d_ff:
        p["mlp"] = layers.mlp_init(km, cfg.d_model, cfg.d_ff, dtype=dtype)
    return p


def n_scan_layers(cfg: ModelConfig) -> int:
    if cfg.block_kind == "xlstm_pair":
        assert cfg.n_layers % 2 == 0
        return cfg.n_layers // 2
    return cfg.n_layers


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.float32) -> dict:
    ke, kl, kh = jax.random.split(key, 3)
    n_scan = n_scan_layers(cfg)
    layer_keys = jax.random.split(kl, n_scan)
    per_layer = [_layer_init(k, cfg, dtype) for k in layer_keys]
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *per_layer)
    params = {
        "layers": stacked,
        "final_norm": layers.rmsnorm_init(cfg.d_model, dtype),
    }
    if cfg.frontend == "token":
        params["embed"] = layers.embed_init(ke, cfg.vocab_size, cfg.d_model,
                                            dtype)
    if not cfg.tie_embeddings or cfg.frontend != "token":
        params["lm_head"] = layers.linear_init(kh, cfg.d_model,
                                               cfg.vocab_size, dtype=dtype)
    return params


def pack_params(cfg: ModelConfig, params: dict) -> dict:
    """Offline stage: base-3 pack every ternary linear (vmapped over layers)."""
    g = cfg.group_size

    def pack_layer(p):
        out = {"ln1": p["ln1"], "ln2": p["ln2"]}
        if "mlstm" in p:
            out["mlstm"] = xlstm.mlstm_pack(p["mlstm"], g)
            out["slstm"] = xlstm.slstm_pack(p["slstm"], g)
            return out
        out["attn"] = {k: layers.linear_pack(v, g)
                       for k, v in p["attn"].items()}
        if "ssm" in p:
            out["ssm"] = ssm.ssm_pack(p["ssm"], g)
        if "moe" in p:
            out["moe"] = layers.moe_pack(p["moe"], g)
        if "mlp" in p:
            out["mlp"] = layers.mlp_pack(p["mlp"], g)
        return out

    packed = {
        "layers": jax.vmap(pack_layer)(params["layers"]),
        "final_norm": params["final_norm"],
    }
    if "embed" in params:
        packed["embed"] = params["embed"]
    if "lm_head" in params:
        packed["lm_head"] = dict(params["lm_head"])
    return packed


def predecode_packed(cfg: ModelConfig, params: dict) -> dict:
    """Decode every packed linear's base-3 codes into dense int8 ternary
    weights (vmapped over the stacked layer axis).

    The serving engine calls this once, when it is built, and serves every
    admission wave and decode block from the resident result.  Outputs are
    bit-identical to running on the packed params (see
    ``bitlinear.predecode``).  MoE expert banks keep their own packed
    format and are left untouched.
    """
    g = cfg.group_size

    def walk(p):
        if isinstance(p, dict):
            if "codes" in p:
                return bitlinear.predecode(p, g=g)
            return {k: walk(v) for k, v in p.items()}
        return p

    def fusable(d, names):
        return all(n in d and "codes" in d[n] for n in names)

    def layer(p):
        out = {}
        for k, v in p.items():
            if k == "attn" and fusable(v, ("q", "k", "v")):
                # QKV fusion: one quant + one GEMM per tick instead of three
                out["attn"] = {
                    "qkv": bitlinear.predecode_fused(
                        [v["q"], v["k"], v["v"]], g=g),
                    "o": walk(v["o"]),
                }
            elif k == "mlp" and fusable(v, ("gate", "up")):
                out["mlp"] = {
                    "gateup": bitlinear.predecode_fused(
                        [v["gate"], v["up"]], g=g),
                    "down": walk(v["down"]),
                }
            else:
                out[k] = walk(v)
        return out

    out = {k: v for k, v in params.items() if k != "layers"}
    with jax.named_scope("predecode"):
        out["layers"] = jax.vmap(layer)(params["layers"])
    return out


# ---------------------------------------------------------------------------
# KV cache / recurrent state
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16, kv_quant: bool = False) -> dict:
    n_scan = n_scan_layers(cfg)

    def stack(tree):
        return jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x, (n_scan,) + x.shape), tree)

    if cfg.block_kind == "xlstm_pair":
        return stack({
            "mlstm": xlstm.mlstm_init_state(batch, cfg.n_heads, cfg.hd),
            "slstm": xlstm.slstm_init_state(batch, cfg.n_heads, cfg.hd),
        })
    kv_dtype = jnp.int8 if kv_quant else dtype
    cache = {
        "k": jnp.zeros((n_scan, batch, max_len, cfg.n_kv_heads, cfg.hd),
                       kv_dtype),
        "v": jnp.zeros((n_scan, batch, max_len, cfg.n_kv_heads, cfg.hd),
                       kv_dtype),
    }
    if kv_quant:
        # per (token, head) absmax scales — the paper's A8 recipe applied to
        # the cache stream (beyond-paper optimization; §Perf cell C)
        cache["k_scale"] = jnp.zeros(
            (n_scan, batch, max_len, cfg.n_kv_heads), jnp.float32)
        cache["v_scale"] = jnp.zeros(
            (n_scan, batch, max_len, cfg.n_kv_heads), jnp.float32)
    if cfg.block_kind == "hymba":
        cache["ssm"] = stack(ssm.ssm_init_state(
            batch, cfg.n_heads, cfg.hd, cfg.ssm_state, cfg.ssm_conv,
            cfg.n_heads * cfg.hd, dtype))
    return cache


def init_paged_cache(cfg: ModelConfig, num_pages: int, page_size: int,
                     dtype=jnp.bfloat16, kv_quant: bool = False) -> dict:
    """Paged KV cache: a global pool of ``num_pages`` fixed-size pages of
    ``page_size`` tokens each, shared by every serving slot and addressed
    through per-slot block tables (see ``attention.paged_update_kv_cache``).

    Page 0 is the reserved null page (never owned by a slot; the target of
    every dead write).  Requires attention blocks — recurrent state (SSM /
    xLSTM) is O(1) per slot and has nothing to page.

    With ``kv_quant`` the K/V pools store int8 and two extra small pools
    hold the per-(token, head) absmax scales — same layout minus the head
    dim, paged by the same block tables, so the W1.58A8+KV8 recipe
    composes with paging (the int8 pool read is the bandwidth win; scales
    are ~1/hd of it)."""
    if cfg.block_kind != "attn":
        raise NotImplementedError(
            f"paged KV cache requires block_kind='attn' "
            f"(got {cfg.block_kind!r})")
    n_scan = n_scan_layers(cfg)
    kv_dtype = jnp.int8 if kv_quant else dtype
    shape = (n_scan, num_pages, page_size, cfg.n_kv_heads, cfg.hd)
    cache = {"k": jnp.zeros(shape, kv_dtype),
             "v": jnp.zeros(shape, kv_dtype)}
    if kv_quant:
        sshape = (n_scan, num_pages, page_size, cfg.n_kv_heads)
        cache["k_scale"] = jnp.zeros(sshape, jnp.float32)
        cache["v_scale"] = jnp.zeros(sshape, jnp.float32)
    return cache


def copy_paged_page(cache: dict, src, dst) -> dict:
    """Copy pool page ``src`` onto ``dst`` across every layer and KV plane
    of a paged cache (see ``attention.copy_kv_page``) — the serving
    engine's copy-on-write split of a partially shared prefix page.  The
    page axis is 1 (axis 0 is the stacked layer axis)."""
    return {name: attention.copy_kv_page(pool, src, dst, page_axis=1)
            for name, pool in cache.items()}


# ---------------------------------------------------------------------------
# Attention sub-layer (shared by attn and hymba blocks)
# ---------------------------------------------------------------------------

def _attn_apply(cfg: ModelConfig, ctx: Ctx, p: dict, x: jax.Array,
                cache: Optional[dict], positions: jax.Array,
                phase: str, cache_len,
                chunk_mask=None,
                page_table=None) -> Tuple[jax.Array, Optional[dict]]:
    b, t, _ = x.shape
    with jax.named_scope("qkv"):
        if "qkv" in p:  # fused projection (pre-decoded serving hot path)
            qkv = layers.linear_apply(p["qkv"], x, ctx)
            q, k, v = jnp.split(qkv, [cfg.q_dim, cfg.q_dim + cfg.kv_dim],
                                axis=-1)
        else:
            q = layers.linear_apply(p["q"], x, ctx)
            k = layers.linear_apply(p["k"], x, ctx)
            v = layers.linear_apply(p["v"], x, ctx)
        q = q.reshape(b, t, cfg.n_heads, cfg.hd)
        k = k.reshape(b, t, cfg.n_kv_heads, cfg.hd)
        v = v.reshape(b, t, cfg.n_kv_heads, cfg.hd)
    with jax.named_scope("attention"):
        o, new_cache = _attend(cfg, ctx, q, k, v, cache, positions, phase,
                               cache_len, chunk_mask, page_table)
        o = o.transpose(0, 2, 1, 3).reshape(b, t, cfg.q_dim)
    with jax.named_scope("o_proj"):
        return layers.linear_apply(p["o"], o, ctx), new_cache


def _attend(cfg: ModelConfig, ctx: Ctx, q, k, v, cache: Optional[dict],
            positions: jax.Array, phase: str, cache_len, chunk_mask,
            page_table) -> Tuple[jax.Array, Optional[dict]]:
    """Rope, the KV write and the attention of one phase, for ``q``, ``k``
    and ``v`` of shape (b, t, heads, hd).  Returns the attention output as
    (b, heads, t, hd) and the updated cache (None without a cache)."""
    t = q.shape[1]
    angles = layers.rope_angles(positions, cfg.hd, cfg.rope_theta)
    q = layers.apply_rope(q, angles, cfg.rope_style)
    k = layers.apply_rope(k, angles, cfg.rope_style)

    quantized = cache is not None and "k_scale" in cache

    def q_kv(x):  # (b, t, kv_h, hd) -> int8 values + (b, t, kv_h) scale
        amax = jnp.maximum(jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1),
                           1e-5)
        scale = amax / 127.0
        xq = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                      -127, 127).astype(jnp.int8)
        return xq, scale

    new_cache = None
    if phase == "full":
        if cache is not None:  # prefill: persist KV
            if quantized:
                kq, ks = q_kv(k)
                vq, vs = q_kv(v)
                kc, vc = attention.update_kv_cache(cache["k"], cache["v"],
                                                   kq, vq, 0)
                new_cache = {
                    "k": kc, "v": vc,
                    "k_scale": jax.lax.dynamic_update_slice_in_dim(
                        cache["k_scale"], ks, 0, axis=1),
                    "v_scale": jax.lax.dynamic_update_slice_in_dim(
                        cache["v_scale"], vs, 0, axis=1),
                }
            else:
                kc, vc = attention.update_kv_cache(cache["k"], cache["v"],
                                                   k, v, 0)
                new_cache = {"k": kc, "v": vc}
        o = attention.prefill_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), causal=True, window=cfg.swa_window,
            impl=ctx.attn_impl, q_chunk=ctx.attn_q_chunk,
            kv_chunk=ctx.attn_kv_chunk)
    elif phase == "chunk":
        # batched chunked in-place prefill (continuous-batching admission):
        # every row b with chunk_mask[b] writes its chunk's KV into its OWN
        # cache row at per-row offset cache_len[b] and attends the row's
        # already-written [0, offset[b]) prefix.  Masked rows (lanes that
        # are decoding or idle this wave) leave their cache row untouched
        # and produce don't-care outputs — one dispatch advances every
        # pending admission.  The chunk's own K/V stay fresh (not round-
        # tripped through the cache dtype) so within-chunk numerics match
        # monolithic prefill.
        offsets = cache_len  # (b,) per-row admission offsets
        admit = chunk_mask   # (b,) bool: row is admitting this wave

        if page_table is not None:
            # paged: scatter the chunk's KV into (block_id, offset) of the
            # page pool (masked rows route to the null page), then attend
            # the block-table prefix + the chunk's own fresh K/V — the
            # fresh operands play the contiguous path's overlay role, so
            # within-chunk numerics match monolithic prefill.
            if quantized:
                kq, ks = q_kv(k)
                vq, vs = q_kv(v)
                kc, vc = attention.paged_update_kv_cache(
                    cache["k"], cache["v"], kq, vq, page_table, offsets,
                    write_mask=admit)
                ks_c, vs_c = attention.paged_update_kv_scales(
                    cache["k_scale"], cache["v_scale"], ks, vs, page_table,
                    offsets, write_mask=admit)
                new_cache = {"k": kc, "v": vc,
                             "k_scale": ks_c, "v_scale": vs_c}
                kc_r, vc_r, ks_r, vs_r = jax.lax.optimization_barrier(
                    (kc, vc, ks_c, vs_c))
                o = attention.paged_chunk_prefill_attention_quant(
                    q.transpose(0, 2, 1, 3), kc_r, vc_r, ks_r, vs_r,
                    page_table, offsets, k.transpose(0, 2, 1, 3),
                    v.transpose(0, 2, 1, 3), window=cfg.swa_window)
                return o, new_cache
            kc, vc = attention.paged_update_kv_cache(
                cache["k"], cache["v"], k, v, page_table, offsets,
                write_mask=admit)
            new_cache = {"k": kc, "v": vc}
            kc_r, vc_r = jax.lax.optimization_barrier((kc, vc))
            o = attention.paged_chunk_prefill_attention(
                q.transpose(0, 2, 1, 3), kc_r, vc_r, page_table, offsets,
                k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
                window=cfg.swa_window,
                impl="pallas" if ctx.attn_impl == "pallas" else "xla")
            return o, new_cache

        def write_row(row_c, new, off, m):
            cur = jax.lax.dynamic_slice_in_dim(row_c, off, t, axis=0)
            upd = jnp.where(m, new.astype(row_c.dtype), cur)
            return jax.lax.dynamic_update_slice_in_dim(row_c, upd, off,
                                                       axis=0)

        def overlay_row(row_c, new, off):
            return jax.lax.dynamic_update_slice_in_dim(
                row_c, new.astype(row_c.dtype), off, axis=0)

        if quantized:
            kq, ks = q_kv(k)
            vq, vs = q_kv(v)
            kc = jax.vmap(write_row)(cache["k"], kq, offsets, admit)
            vc = jax.vmap(write_row)(cache["v"], vq, offsets, admit)
            ks_c = jax.vmap(write_row)(cache["k_scale"], ks, offsets, admit)
            vs_c = jax.vmap(write_row)(cache["v_scale"], vs, offsets, admit)
            new_cache = {"k": kc, "v": vc, "k_scale": ks_c, "v_scale": vs_c}
            k_read = kc.astype(k.dtype) * ks_c[..., None].astype(k.dtype)
            v_read = vc.astype(v.dtype) * vs_c[..., None].astype(v.dtype)
        else:
            kc = jax.vmap(write_row)(cache["k"], k, offsets, admit)
            vc = jax.vmap(write_row)(cache["v"], v, offsets, admit)
            new_cache = {"k": kc, "v": vc}
            k_read = kc.astype(k.dtype)
            v_read = vc.astype(v.dtype)
        # overlay each row's chunk span with the fresh full-precision values
        # (masked rows' attention outputs are don't-care)
        k_read = jax.vmap(overlay_row)(k_read, k, offsets)
        v_read = jax.vmap(overlay_row)(v_read, v, offsets)
        o = attention.chunk_prefill_attention(
            q.transpose(0, 2, 1, 3), k_read.transpose(0, 2, 1, 3),
            v_read.transpose(0, 2, 1, 3), offsets, window=cfg.swa_window,
            impl="pallas" if ctx.attn_impl == "pallas" else "xla")
    else:  # decode step: t == 1
        if page_table is not None:
            # paged: append the token's KV at (block_id, offset); writes
            # whose position resolves past the block table (an inactive
            # lane parked at max_seq) land in the null page.  Attention
            # streams only the slot's owned pages (Pallas) or gathers
            # them (XLA).
            if quantized:
                kq, ks = q_kv(k)
                vq, vs = q_kv(v)
                kc, vc = attention.paged_update_kv_cache(
                    cache["k"], cache["v"], kq, vq, page_table, cache_len)
                ks_c, vs_c = attention.paged_update_kv_scales(
                    cache["k_scale"], cache["v_scale"], ks, vs, page_table,
                    cache_len)
                new_cache = {"k": kc, "v": vc,
                             "k_scale": ks_c, "v_scale": vs_c}
                kc_r, vc_r, ks_r, vs_r = jax.lax.optimization_barrier(
                    (kc, vc, ks_c, vs_c))
                o = attention.paged_decode_attention_quant(
                    q.transpose(0, 2, 1, 3), kc_r, vc_r, ks_r, vs_r,
                    page_table, cache_len + 1, window=cfg.swa_window,
                    impl="pallas" if ctx.attn_impl == "pallas" else "xla",
                    kv_splits=ctx.kv_splits, kv_axis=ctx.kv_shard_axis,
                    kv_axis_size=ctx.kv_shard_size)
                return o, new_cache
            kc, vc = attention.paged_update_kv_cache(
                cache["k"], cache["v"], k, v, page_table, cache_len)
            new_cache = {"k": kc, "v": vc}
            k_read, v_read = jax.lax.optimization_barrier((kc, vc))
            o = attention.paged_decode_attention(
                q.transpose(0, 2, 1, 3), k_read, v_read, page_table,
                cache_len + 1, window=cfg.swa_window,
                impl="pallas" if ctx.attn_impl == "pallas" else "xla",
                kv_splits=ctx.kv_splits, kv_axis=ctx.kv_shard_axis,
                kv_axis_size=ctx.kv_shard_size)
            return o, new_cache
        if quantized:
            kq, ks = q_kv(k)
            vq, vs = q_kv(v)
            kc, vc = attention.update_kv_cache(cache["k"], cache["v"], kq,
                                               vq, cache_len)
            ks_c = attention.update_cache_slice(cache["k_scale"], ks,
                                                cache_len, axis=1)
            vs_c = attention.update_cache_slice(cache["v_scale"], vs,
                                                cache_len, axis=1)
            new_cache = {"k": kc, "v": vc, "k_scale": ks_c, "v_scale": vs_c}
            kc_r, vc_r, ks_r, vs_r = jax.lax.optimization_barrier(
                (kc, vc, ks_c, vs_c))
            # dequantize at read (the Pallas decode kernel fuses this into
            # the stream; the int8 HBM read is the bandwidth win)
            k_read = (kc_r.astype(jnp.bfloat16)
                      * ks_r[..., None].astype(jnp.bfloat16))
            v_read = (vc_r.astype(jnp.bfloat16)
                      * vs_r[..., None].astype(jnp.bfloat16))
        else:
            kc, vc = attention.update_kv_cache(cache["k"], cache["v"], k, v,
                                               cache_len)
            new_cache = {"k": kc, "v": vc}
            # barrier: XLA:CPU lowers bf16 dots via f32 and would otherwise
            # hoist the convert over the whole stacked cache (an extra
            # cache-sized f32 buffer); TPU bf16 MXU never converts.
            k_read, v_read = jax.lax.optimization_barrier((kc, vc))
        o = attention.decode_attention(
            q.transpose(0, 2, 1, 3), k_read.transpose(0, 2, 1, 3),
            v_read.transpose(0, 2, 1, 3), cache_len + 1,
            window=cfg.swa_window,
            impl="pallas" if ctx.attn_impl == "pallas" else "xla",
            kv_splits=ctx.kv_splits, kv_axis=ctx.kv_shard_axis,
            kv_axis_size=ctx.kv_shard_size)
    return o, new_cache


# ---------------------------------------------------------------------------
# Block bodies
# ---------------------------------------------------------------------------

def _block_apply(cfg: ModelConfig, ctx: Ctx, x: jax.Array, p: dict,
                 cache: Optional[dict], positions: jax.Array, phase: str,
                 cache_len,
                 chunk_mask=None,
                 page_table=None) -> Tuple[jax.Array, Optional[dict]]:
    new_cache = {}
    if cfg.block_kind == "xlstm_pair":
        want_state = cache is not None
        h = layers.rmsnorm(p["ln1"], x, cfg.norm_eps)
        if phase == "full":
            out = xlstm.mlstm_forward(p["mlstm"], h, ctx,
                                      n_heads=cfg.n_heads, head_dim=cfg.hd,
                                      chunk=cfg.ssm_chunk or 128,
                                      return_state=want_state)
            if want_state:
                out, new_cache["mlstm"] = out
            x = x + out
        else:
            o, new_cache["mlstm"] = xlstm.mlstm_step(
                p["mlstm"], h, cache["mlstm"], ctx, n_heads=cfg.n_heads,
                head_dim=cfg.hd)
            x = x + o
        h = layers.rmsnorm(p["ln2"], x, cfg.norm_eps)
        if phase == "full":
            out = xlstm.slstm_forward(p["slstm"], h, ctx,
                                      n_heads=cfg.n_heads, head_dim=cfg.hd,
                                      return_state=want_state)
            if want_state:
                out, new_cache["slstm"] = out
            x = x + out
        else:
            o, new_cache["slstm"] = xlstm.slstm_step(
                p["slstm"], h, cache["slstm"], ctx, n_heads=cfg.n_heads,
                head_dim=cfg.hd)
            x = x + o
        return x, (new_cache if new_cache else None)

    # attn | hymba
    h = layers.rmsnorm(p["ln1"], x, cfg.norm_eps)
    attn_cache = None
    if cache is not None:
        attn_cache = {k_: cache[k_] for k_ in
                      ("k", "v", "k_scale", "v_scale") if k_ in cache}
    attn_out, kv_cache = _attn_apply(cfg, ctx, p["attn"], h, attn_cache,
                                     positions, phase, cache_len,
                                     chunk_mask, page_table)
    if kv_cache is not None:
        new_cache.update(kv_cache)
    if cfg.block_kind == "hymba":
        # parallel attention + SSM heads, outputs averaged (Hymba fusion)
        if phase == "full":
            out = ssm.ssm_forward(p["ssm"], h, ctx, n_heads=cfg.n_heads,
                                  head_dim=cfg.hd, state=cfg.ssm_state,
                                  chunk=cfg.ssm_chunk,
                                  return_state=cache is not None)
            if cache is not None:
                ssm_out, new_cache["ssm"] = out
            else:
                ssm_out = out
        else:
            ssm_out, new_ssm = ssm.ssm_step(p["ssm"], h, cache["ssm"], ctx,
                                            n_heads=cfg.n_heads,
                                            head_dim=cfg.hd,
                                            state=cfg.ssm_state)
            new_cache["ssm"] = new_ssm
        attn_out = 0.5 * (attn_out + ssm_out.astype(attn_out.dtype))
    x = x + attn_out
    h = layers.rmsnorm(p["ln2"], x, cfg.norm_eps)
    if cfg.n_experts:
        b, t, d = h.shape
        out = layers.moe_apply(p["moe"], h.reshape(b * t, d),
                               top_k=cfg.top_k,
                               capacity_factor=cfg.capacity_factor, ctx=ctx)
        x = x + out.reshape(b, t, d)
    elif cfg.d_ff:
        x = x + layers.mlp_apply(p["mlp"], h, ctx)
    return x, (new_cache if new_cache else None)


# ---------------------------------------------------------------------------
# Model entry points
# ---------------------------------------------------------------------------

def _embed_in(cfg: ModelConfig, params: dict, inputs: jax.Array,
              ctx: Ctx) -> jax.Array:
    with jax.named_scope("embed"):
        if cfg.frontend == "token":
            x = layers.embed_apply(params["embed"], inputs)
        else:  # audio/vlm stub: inputs are precomputed frame/patch embeddings
            x = inputs
        return x.astype(ctx.dtype)


def _lm_head(cfg: ModelConfig, params: dict, x: jax.Array,
             ctx: Ctx) -> jax.Array:
    with jax.named_scope("lm_head"):
        x = layers.rmsnorm(params["final_norm"], x, cfg.norm_eps)
        if cfg.tie_embeddings and "embed" in params:
            logits = jnp.einsum("btd,vd->btv", x,
                                params["embed"]["tok"].astype(x.dtype))
        else:
            logits = layers.linear_apply(params["lm_head"], x, ctx,
                                         ternary_w=cfg.ternary_head)
        return ctx.c(logits, "logits")


def _run_layers(cfg: ModelConfig, ctx: Ctx, params: dict, x: jax.Array,
                cache: Optional[dict], positions: jax.Array, phase: str,
                cache_len, remat: bool = True, chunk_mask=None,
                page_table=None):
    def body(carry, xs):
        layer_p, layer_cache = xs
        carry = ctx.c(carry, "residual")  # SP/TP layout between blocks
        y, new_cache = _block_apply(cfg, ctx, carry, layer_p, layer_cache,
                                    positions, phase, cache_len,
                                    chunk_mask, page_table)
        return y, new_cache

    if remat:
        policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                  if ctx.remat_policy == "dots"
                  else jax.checkpoint_policies.nothing_saveable)
        body = jax.checkpoint(body, policy=policy)
    x, new_cache = jax.lax.scan(body, x, (params["layers"], cache))
    return x, new_cache


def forward_features(cfg: ModelConfig, params: dict, inputs: jax.Array,
                     ctx: Ctx, remat: bool = True) -> jax.Array:
    """Backbone only: final hidden states (b, s, d_model)."""
    x = _embed_in(cfg, params, inputs, ctx)
    s = x.shape[1]
    positions = jnp.arange(s)
    x, _ = _run_layers(cfg, ctx, params, x, None, positions, "full", None,
                       remat)
    return x


def forward(cfg: ModelConfig, params: dict, inputs: jax.Array, ctx: Ctx,
            remat: bool = True) -> jax.Array:
    """Training/eval forward: all-position logits (b, s, vocab)."""
    x = forward_features(cfg, params, inputs, ctx, remat)
    return _lm_head(cfg, params, x, ctx)


def lm_head_loss_chunked(cfg: ModelConfig, params: dict, x: jax.Array,
                         labels: jax.Array, ctx: Ctx,
                         chunk: int = 512) -> jax.Array:
    """Fused unembedding + cross-entropy, scanned over sequence chunks.

    Never materializes the (b, s, vocab) logits tensor: with 150k-vocab
    archs at per-device batch 4 × seq 4096 the f32 logits chain alone is
    several GiB/device (measured in §Perf) — chunking bounds it to
    (b, chunk, vocab) and jax.checkpoint recomputes per chunk on backward.
    """
    b, s, d = x.shape
    chunk = min(chunk, s)
    if s % chunk:
        chunk = s
    n_chunks = s // chunk
    # pin the layout before chunking: without this, SPMD can leave x sharded
    # on d_model and then fails to partition the scan's chunk slicing
    x = ctx.c(x, "residual")
    xc = jnp.moveaxis(x.reshape(b, n_chunks, chunk, d), 1, 0)
    lc = jnp.moveaxis(labels.reshape(b, n_chunks, chunk), 1, 0)

    @jax.checkpoint
    def body(carry, xs_):
        xcur, lcur = xs_
        logits = _lm_head(cfg, params, xcur, ctx).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lcur[..., None], axis=-1)[..., 0]
        return carry + jnp.sum(lse - gold), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (xc, lc))
    return total / (b * s)


def prefill_step(cfg: ModelConfig, params: dict, inputs: jax.Array, ctx: Ctx,
                 cache: dict, remat: bool = False,
                 lengths: Optional[jax.Array] = None):
    """Prompt -> (last-token logits (b, vocab), filled cache).

    ``lengths`` (optional, (b,) int32) supports ragged right-padded batches:
    row i's logits are taken at position ``lengths[i] - 1`` (its last real
    token) instead of the padded final position.  Causality guarantees real
    positions never attend to the padded tail; the tail's KV entries are
    masked out downstream by the per-slot decode length.
    """
    x = _embed_in(cfg, params, inputs, ctx)
    s = x.shape[1]
    positions = jnp.arange(s)
    x, new_cache = _run_layers(cfg, ctx, params, x, cache, positions, "full",
                               None, remat)
    if lengths is None:
        last = x[:, -1:]
    else:
        idx = (jnp.asarray(lengths, jnp.int32) - 1)[:, None, None]
        last = jnp.take_along_axis(
            x, jnp.broadcast_to(idx, (x.shape[0], 1, x.shape[2])), axis=1)
    logits = _lm_head(cfg, params, last, ctx)
    return logits[:, 0], new_cache


def prefill_chunk(cfg: ModelConfig, params: dict, inputs: jax.Array, ctx: Ctx,
                  cache: dict, *, offsets, admit_mask, last_index,
                  page_table=None):
    """One admission *wave* of a continuous batch -> (logits (b, vocab), cache).

    ``inputs`` is (b, C) — one prompt chunk per shared-cache row, where b is
    the cache's batch (slot count).  Row i with ``admit_mask[i]`` sits at
    absolute positions ``[offsets[i], offsets[i] + C)`` of its own cache
    row: its chunk KV is written *in place* at that offset and the chunk
    attends to the row's already-written ``[0, offsets[i])`` prefix plus its
    own causal triangle.  Masked rows leave their cache row untouched and
    produce don't-care logits — one dispatch advances every in-progress
    admission without disturbing decoding lanes.

    ``offsets``/``admit_mask``/``last_index`` are traced (b,) vectors, so
    ONE compiled shape (fixed C) serves every mix of prompt lengths and
    offsets — the O(1)-jit-cache property the serving engine's chunked
    admission relies on.

    ``last_index[i]`` is the chunk-local index of row i's last real prompt
    token; its logits are returned (only meaningful on a row's final
    chunk).  A right-padded final chunk is safe for the same reason padded
    prefill is: causality keeps real positions from attending the padded
    tail, and the tail's cache entries sit at positions >= the request's
    live length.

    With ``page_table`` ((b, n_pages) int32), ``cache`` is a paged pool from
    ``init_paged_cache`` instead of contiguous rows: row i's chunk KV is
    scattered to ``(page_table[i, pos // page_size], pos % page_size)`` and
    the prefix is attended through the block table (masked rows' writes are
    routed to the null page).  A row's FIRST chunk may sit at a nonzero
    offset over a pre-populated table — the serving engine's prefix sharing
    aliases cached prefix pages into the table and starts prefill at the
    first divergent token; the attended ``[0, offset)`` prefix then streams
    from pages this slot never wrote.

    Requires attention blocks — recurrent kinds (SSM/xLSTM) integrate every
    input token into their state, which cannot be resumed chunk-to-chunk
    without carrying the state; the engine prefills those at full length.
    """
    if cfg.block_kind != "attn":
        raise NotImplementedError(
            "chunked prefill requires block_kind='attn' "
            f"(got {cfg.block_kind!r})")
    x = _embed_in(cfg, params, inputs, ctx)
    b, c = inputs.shape[0], x.shape[1]
    offsets = jnp.asarray(offsets, jnp.int32)
    admit = jnp.asarray(admit_mask, jnp.bool_)
    positions = offsets[:, None] + jnp.arange(c)[None, :]  # (b, C)
    pt = (None if page_table is None
          else jnp.asarray(page_table, jnp.int32))
    x, new_cache = _run_layers(cfg, ctx, params, x, cache, positions, "chunk",
                               offsets, remat=False, chunk_mask=admit,
                               page_table=pt)
    idx = jnp.asarray(last_index, jnp.int32)[:, None, None]
    last = jnp.take_along_axis(
        x, jnp.broadcast_to(idx, (b, 1, x.shape[2])), axis=1)
    logits = _lm_head(cfg, params, last, ctx)
    return logits[:, 0], new_cache


def decode_step(cfg: ModelConfig, params: dict, inputs: jax.Array, ctx: Ctx,
                cache: dict, cache_len: jax.Array, page_table=None):
    """One token (b, 1) + cache + live length -> (logits (b, vocab), cache).

    ``cache_len`` is a scalar (all rows at the same offset) or a (b,) vector
    of per-request live lengths: each row writes its KV at its own offset,
    rotates its query/key by its own position, and attends only its own
    [0, cache_len[i]] prefix — the ragged decode step continuous batching
    needs.

    With ``page_table`` ((b, n_pages) int32), ``cache`` is a paged pool from
    ``init_paged_cache``: each row appends at
    ``(page_table[i, cache_len[i] // page_size], cache_len[i] % page_size)``
    and attends only the pages it owns.
    """
    x = _embed_in(cfg, params, inputs, ctx)
    cl = jnp.asarray(cache_len)
    positions = cl[..., None] + jnp.arange(1)  # (1,) or (b, 1)
    pt = (None if page_table is None
          else jnp.asarray(page_table, jnp.int32))
    x, new_cache = _run_layers(cfg, ctx, params, x, cache, positions, "step",
                               cl, remat=False, page_table=pt)
    logits = _lm_head(cfg, params, x, ctx)
    return logits[:, 0], new_cache
