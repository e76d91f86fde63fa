"""Decode attention — the paper's DA unit (§3.7), TPU-adapted.

Decode attention is a single query token against a long KV cache: memory-
bandwidth-bound on the cache stream, negligible compute.  Exactly as the
paper de-fuses QKᵀ (K-cache stream) from the V aggregation (V-cache stream)
and keeps the score vector on-chip, this kernel streams the cache in (bkv, d)
blocks through VMEM, maintains the online-softmax state (m, l, acc) in VMEM
scratch, and never writes scores to HBM.  Positions ≥ cache_len (ring-buffer
slack, paddings) are masked via a scalar-prefetched length.

A split-KV (flash-decoding) wrapper in ops.py shards the sequence dimension —
the long-context path a 2-port DDR FPGA cannot take but a TPU pod can.

The *paged* variant streams the KV cache out of a global page pool instead of
a contiguous per-slot row: each slot owns an ordered list of fixed-size pages
(``page_size`` tokens), named by a per-slot block table.  The block table is
scalar-prefetched, and the BlockSpec index map dereferences it — grid step
``(bi, ki)`` DMAs pool page ``block_tables[bi, ki]`` with all its kv heads
(a block over one head of the (page_size, kv_h, d) page is refused by
Mosaic: its last two dims would be (1, d)).  The grid still spans the full
static table width, but compute is issued only for owned pages: dead table
entries point at the reserved null page, whose (cheap, repeated-block)
fetch is followed by a ``pl.when`` skip of all work.
This removes both the contiguous path's pad-copy (pool pages are block-
aligned by construction) and the dead-tail compute of short slots in a
long-`max_seq` cache.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
                   *, scale: float, bkv: int):
    bi = pl.program_id(0)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    cache_len = len_ref[bi]  # per-request live length (ragged batch)
    k_start = ki * bkv
    # Skip blocks entirely beyond the live cache (no work issued).
    @pl.when(k_start < cache_len)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)        # (1, d)
        k = k_ref[0, 0].astype(jnp.float32)        # (bkv, d)
        v = v_ref[0, 0].astype(jnp.float32)        # (bkv, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        k_ids = k_start + jax.lax.broadcasted_iota(jnp.int32, (1, bkv), 1)
        mask = k_ids < cache_len
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def decode_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                            cache_len: jax.Array, *, scale: float, bkv: int,
                            interpret: bool) -> jax.Array:
    """q: (b, h, 1, d); k, v: (b, kv_h, s, d); cache_len: int32 scalar or
    (b,) per-request lengths (scalar-prefetched; each batch program masks to
    its own live length).

    Returns (b, h, 1, d)."""
    b, h, _, d = q.shape
    kv_h, s = k.shape[1], k.shape[2]
    assert h % kv_h == 0 and s % bkv == 0
    group = h // kv_h
    grid = (b, h, s // bkv)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, d), lambda bi, hi, ki, len_ref: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, bkv, d),
                         lambda bi, hi, ki, len_ref: (bi, hi // group, ki, 0)),
            pl.BlockSpec((1, 1, bkv, d),
                         lambda bi, hi, ki, len_ref: (bi, hi // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, d),
                               lambda bi, hi, ki, len_ref: (bi, hi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, d), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
    )
    lens = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32).reshape(-1),
                            (b,))
    return pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, bkv=bkv),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, 1, d), q.dtype),
        interpret=interpret,
    )(lens, q, k, v)


def _paged_decode_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, *rest,
                         scale: float, page_size: int, group: int,
                         quant: bool):
    """One grid step processes one pool page of one slot, for every head.

    The page loaded by this step was chosen by the BlockSpec index map from
    the scalar-prefetched block table; this body only needs the *logical*
    page index ``ki`` to recover absolute token positions and the live-length
    mask.  Pages at or beyond the slot's live length issue no compute.

    Blocks: q (1, group, kv_h, d) — query head ``g * group + j`` at
    ``[j, g]``; k/v (1, page_size, kv_h, d) — the whole page, all kv heads.
    Scores are formed on the VPU as ``sum_d q * k`` over a
    (page_size, kv_h, d) tile (one query per head: the MXU would sit
    idle at M = 1), so every head of the page is handled in one pass.

    With ``quant`` the pools are int8 and two more refs carry the page's
    per-(token, head) scales (1, page_size, kv_h).  Dequant goes through a
    bfloat16 intermediate (int8 value × bf16 scale, then widened to f32) so
    the result matches the contiguous KV8 path, which dequantizes in bf16
    before handing the cache to the non-quant kernel."""
    if quant:
        ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, acc_ref, m_ref, l_ref = rest
    bi = pl.program_id(0)
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    cache_len = len_ref[bi]
    k_start = ki * page_size

    @pl.when(k_start < cache_len)  # dead pages: no work
    def _compute():
        k = k_ref[0].astype(jnp.float32)             # (page_size, kv_h, d)
        v = v_ref[0].astype(jnp.float32)
        if quant:
            # bf16-op semantics, spelled out so fusion cannot skip the
            # product rounding: f32 multiply of the exact inputs, then an
            # explicit (lossy, hence preserved) round to bf16
            ks = ks_ref[0].astype(jnp.bfloat16).astype(jnp.float32)
            vs = vs_ref[0].astype(jnp.bfloat16).astype(jnp.float32)
            k = (k * ks[:, :, None]).astype(jnp.bfloat16).astype(jnp.float32)
            v = (v * vs[:, :, None]).astype(jnp.bfloat16).astype(jnp.float32)
        k_ids = k_start + jax.lax.broadcasted_iota(jnp.int32, k.shape[:2] +
                                                   (1,), 0)
        mask = k_ids < cache_len                     # (page_size, kv_h, 1)
        for j in range(group):
            q = q_ref[0, j].astype(jnp.float32)      # (kv_h, d)
            s = jnp.sum(q[None] * k, axis=-1, keepdims=True) * scale
            s = jnp.where(mask, s, NEG_INF)
            m_prev = m_ref[j]                        # (kv_h, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=0))
            p = jnp.where(mask, jnp.exp(s - m_new[None]), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[j] = l_ref[j] * alpha + jnp.sum(p, axis=0)
            acc_ref[j] = acc_ref[j] * alpha + jnp.sum(p * v, axis=0)
            m_ref[j] = m_new

    @pl.when(ki == pl.num_programs(1) - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _paged_decode_call(q, pools, scale_pools, block_tables, cache_len, *,
                       scale: float, interpret: bool) -> jax.Array:
    """Shared launcher of the bf16/f32 and the int8 paged decode kernels.

    The grid is (slot, page): each step DMAs one whole pool page — every kv
    head of it — so a page is fetched once per slot, and the block's last
    two dims equal the pool's (kv_h, d), which Mosaic accepts for any
    kv_h.  q is regrouped to (b, group, kv_h, d) outside the kernel so
    each GQA group slice is a (kv_h, d) tile."""
    b, h, _, d = q.shape
    page_size, kv_h = pools[0].shape[1], pools[0].shape[2]
    n_pages = block_tables.shape[1]
    assert h % kv_h == 0
    group = h // kv_h
    qg = q.reshape(b, kv_h, group, d).transpose(0, 2, 1, 3)

    def page_idx(bi, ki, bt_ref, len_ref):
        # the paged gather: the index map dereferences the block table, so
        # this step's DMA fetches pool page block_tables[bi, ki]
        return (bt_ref[bi, ki], 0, 0, 0)

    def scale_idx(bi, ki, bt_ref, len_ref):
        return (bt_ref[bi, ki], 0, 0)

    def slot_idx(bi, ki, bt_ref, len_ref):
        return (bi, 0, 0, 0)

    kv_spec = pl.BlockSpec((1, page_size, kv_h, d), page_idx)
    scale_spec = pl.BlockSpec((1, page_size, kv_h), scale_idx)
    q_spec = pl.BlockSpec((1, group, kv_h, d), slot_idx)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # block tables + live lengths
        grid=(b, n_pages),
        in_specs=[q_spec, kv_spec, kv_spec] + [scale_spec] * len(scale_pools),
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((group, kv_h, d), jnp.float32),
            pltpu.VMEM((group, kv_h, 1), jnp.float32),
            pltpu.VMEM((group, kv_h, 1), jnp.float32),
        ],
    )
    bt = jnp.asarray(block_tables, jnp.int32)
    lens = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32).reshape(-1),
                            (b,))
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, scale=scale,
                          page_size=page_size, group=group,
                          quant=bool(scale_pools)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qg.shape, q.dtype),
        interpret=interpret,
    )(bt, lens, qg, *pools, *scale_pools)
    return out.transpose(0, 2, 1, 3).reshape(b, h, 1, d)


def paged_decode_attention_pallas(q: jax.Array, k_pool: jax.Array,
                                  v_pool: jax.Array, block_tables: jax.Array,
                                  cache_len: jax.Array, *, scale: float,
                                  interpret: bool) -> jax.Array:
    """q: (b, h, 1, d); k_pool, v_pool: (num_pages, page_size, kv_h, d) —
    the global KV page pool; block_tables: (b, n_pages) int32 page ids (dead
    entries must name a valid page — the engine parks them on the reserved
    null page 0); cache_len: int32 scalar or (b,) live lengths.

    Returns (b, h, 1, d).  No padding is ever required: the pool's page axis
    is the block axis, so every block is full-size by construction."""
    return _paged_decode_call(q, (k_pool, v_pool), (), block_tables,
                              cache_len, scale=scale, interpret=interpret)


def paged_decode_attention_quant_pallas(
        q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
        k_scale_pool: jax.Array, v_scale_pool: jax.Array,
        block_tables: jax.Array, cache_len: jax.Array, *, scale: float,
        interpret: bool) -> jax.Array:
    """Paged decode attention over an int8 KV pool.

    q: (b, h, 1, d); k_pool, v_pool: (num_pages, page_size, kv_h, d) int8;
    k_scale_pool, v_scale_pool: (num_pages, page_size, kv_h) f32 per-(token,
    head) dequant scales; block_tables: (b, n_pages) int32; cache_len: int32
    scalar or (b,) live lengths.  Scale pages ride the same scalar-prefetched
    block-table indirection as the KV pages — one extra small DMA per page.

    Returns (b, h, 1, d)."""
    return _paged_decode_call(q, (k_pool, v_pool),
                              (k_scale_pool, v_scale_pool), block_tables,
                              cache_len, scale=scale, interpret=interpret)
