"""Fused prefill attention — the paper's RPA unit (§3.6), TPU-adapted.

The paper's reversed-reordered prefill attention is online-softmax fused
attention (eq. 11 == Flash-Attention-2 with block 1) scheduled so that
causal-masked work is *never issued* and the S = QKᵀ matrix never exists in
off-chip memory.  The reversal itself exists to keep AXI bursts
address-incremental — an FPGA artifact.  On TPU the same two goals map to:

  * online softmax with per-q-block running (m, l, acc) carried in VMEM
    scratch across the kv grid dimension (never materialize S in HBM);
  * *block skipping*: grid cells with kv_block > q_block are masked out with
    ``pl.when`` so fully-masked tiles issue zero MXU work — the TPU
    equivalent of "the mask never generates work".

GQA is handled in the BlockSpec index maps (q head h reads kv head
h // group), so no KV replication is materialized.

The naive baseline from the paper's Fig. 6b (compute all N² scores, then
mask) is ``naive_attention`` in ref.py and is benchmarked in
benchmarks/attention_ablation.py (paper §4.4.2: 1.88×; we reproduce ≈2×).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, bq: int, bkv: int, causal: bool,
                  window: int | None):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # Block-skip: with causal masking, tiles strictly above the diagonal are
    # never computed (the RPA "no redundant masked computation" property).
    # With a sliding window, tiles entirely left of the window are skipped too.
    q_start = qi * bq
    k_start = ki * bkv
    run = True
    if causal:
        run = jnp.logical_and(run, k_start <= q_start + bq - 1)
    if window is not None:
        # newest q in block attends back `window-1`; skip fully-stale kv tiles
        run = jnp.logical_and(run, k_start + bkv - 1 >= q_start - window + 1)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)   # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)   # (bkv, d)
        v = v_ref[0, 0].astype(jnp.float32)   # (bkv, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        q_ids = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
        k_ids = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
        mask = jnp.ones((bq, bkv), jnp.bool_)
        if causal:
            mask = jnp.logical_and(mask, k_ids <= q_ids)
        if window is not None:
            mask = jnp.logical_and(mask, k_ids > q_ids - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                                  # (bq, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # guard fully-masked rows (can happen in the diagonal block's top rows)
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == pl.num_programs(3) - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _chunk_kernel(off_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
                  *, scale: float, bq: int, bkv: int, window: int | None):
    """Chunked-prefill variant: the q grid covers one prompt chunk per batch
    row, each row's absolute positions starting at its scalar-prefetched
    ``offset[b]``; the kv grid covers the whole cache row.  Same
    online-softmax state machine as ``_flash_kernel``, but the block-skip
    predicate is *dynamic* (it depends on the admission offset), so
    fully-masked kv tiles are skipped at run time via ``pl.when`` instead of
    being pruned from the grid."""
    bi = pl.program_id(0)
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    offset = off_ref[bi]  # per-row admission offset (ragged wave)
    q_start = offset + qi * bq          # absolute position of first query row
    k_start = ki * bkv
    # dynamic block-skip: kv tiles entirely in the chunk's causal future (or
    # entirely left of the sliding window) issue no MXU work
    run = k_start <= q_start + bq - 1
    if window is not None:
        run = jnp.logical_and(run, k_start + bkv - 1 >= q_start - window + 1)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)   # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)   # (bkv, d)
        v = v_ref[0, 0].astype(jnp.float32)   # (bkv, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        q_ids = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
        k_ids = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
        mask = k_ids <= q_ids
        if window is not None:
            mask = jnp.logical_and(mask, k_ids > q_ids - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == pl.num_programs(3) - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _paged_chunk_kernel(bt_ref, off_ref, q_ref, kp_ref, vp_ref, kf_ref,
                        vf_ref, o_ref, acc_ref, m_ref, l_ref, *, scale: float,
                        bq: int, page_size: int, n_pages: int, group: int,
                        window: int | None):
    """Paged chunked-prefill attention.  The kv grid axis is split in two
    logical phases: steps ``ki < n_pages`` stream the slot's already-written
    ``[0, offset)`` KV prefix straight out of the page pool (the BlockSpec
    index map dereferences the scalar-prefetched block table, so only owned
    pages are fetched), and steps ``ki >= n_pages`` walk the chunk's own
    fresh K/V tiles (full-precision operands, matching the contiguous path's
    fresh-chunk overlay) under the causal triangle.  Pool pages at or beyond
    the prefix — and fresh tiles above the diagonal — issue no MXU work.

    Each step covers every head: the pool block is a whole page
    (1, page_size, kv_h, d) — a one-head block would end in (1, d), which
    Mosaic refuses — so a page is fetched once per (slot, q tile), and the
    body loops over the query heads (head ``hq`` reads kv head
    ``hq // group``)."""
    bi = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    n_heads = q_ref.shape[1]

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    offset = off_ref[bi]
    q_start = offset + qi * bq  # absolute position of this q tile's first row

    def online_update(hq, k, v, k_ids, extra_mask):
        """Shared online-softmax step for query head ``hq``.  k, v:
        (tile, d) f32; k_ids: (1, tile) absolute key positions; extra_mask:
        (bq, tile) or scalar True."""
        q = q_ref[0, hq].astype(jnp.float32)                   # (bq, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        q_ids = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (bq, k.shape[0]), 0)
        mask = jnp.logical_and(k_ids <= q_ids, extra_mask)
        if window is not None:
            mask = jnp.logical_and(mask, k_ids > q_ids - window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[hq]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[hq] = l_ref[hq] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[hq] = acc_ref[hq] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[hq] = m_new

    # -- phase 1: pool pages holding the [0, offset) prefix ------------------
    k_start_pool = ki * page_size
    run_pool = jnp.logical_and(ki < n_pages, k_start_pool < offset)
    if window is not None:
        run_pool = jnp.logical_and(
            run_pool, k_start_pool + page_size - 1 >= q_start - window + 1)

    @pl.when(run_pool)
    def _pool():
        k_ids = k_start_pool + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1)
        for hq in range(n_heads):
            g = hq // group
            k = kp_ref[0, :, g, :].astype(jnp.float32)    # (page_size, d)
            v = vp_ref[0, :, g, :].astype(jnp.float32)
            # prefix-only: positions >= offset live in the fresh operand (or
            # are stale page slack) and must not be read from the pool
            online_update(hq, k, v, k_ids, k_ids < offset)

    # -- phase 2: the chunk's own fresh K/V tiles (causal triangle) ----------
    fi = ki - n_pages
    run_fresh = jnp.logical_and(ki >= n_pages, fi <= qi)  # tile block-skip
    if window is not None:
        run_fresh = jnp.logical_and(
            run_fresh, (fi + 1) * bq - 1 >= qi * bq - window + 1)

    @pl.when(run_fresh)
    def _fresh():
        k_ids = offset + fi * bq + jax.lax.broadcasted_iota(
            jnp.int32, (1, bq), 1)
        for hq in range(n_heads):
            g = hq // group
            k = kf_ref[0, g].astype(jnp.float32)          # (bq, d)
            v = vf_ref[0, g].astype(jnp.float32)
            online_update(hq, k, v, k_ids, True)

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_chunk_prefill_paged_pallas(q: jax.Array, k_pool: jax.Array,
                                     v_pool: jax.Array,
                                     block_tables: jax.Array,
                                     offset: jax.Array, k_fresh: jax.Array,
                                     v_fresh: jax.Array, *, scale: float,
                                     window: int | None, bq: int,
                                     interpret: bool) -> jax.Array:
    """q: (b, h, t, d) chunk queries; k_pool, v_pool:
    (num_pages, page_size, kv_h, d) global page pool; block_tables:
    (b, n_pages) int32; offset: (b,) int32 admission offsets; k_fresh,
    v_fresh: (b, kv_h, t, d) the chunk's own full-precision K/V.
    Returns (b, h, t, d)."""
    b, h, t, d = q.shape
    page_size, kv_h = k_pool.shape[1], k_pool.shape[2]
    n_pages = block_tables.shape[1]
    assert h % kv_h == 0 and t % bq == 0
    nf = t // bq
    grid = (b, t // bq, n_pages + nf)

    def pool_idx(bi, qi, ki, bt_ref, off_ref):
        # fresh-phase steps clamp to a valid page so the (unused) DMA target
        # stays in bounds
        return (bt_ref[bi, jnp.minimum(ki, n_pages - 1)], 0, 0, 0)

    def fresh_idx(bi, qi, ki, bt_ref, off_ref):
        return (bi, 0, jnp.clip(ki - n_pages, 0, nf - 1), 0)

    def q_idx(bi, qi, ki, bt_ref, off_ref):
        return (bi, 0, qi, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # block tables + offsets
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, h, bq, d), q_idx),
            pl.BlockSpec((1, page_size, kv_h, d), pool_idx),
            pl.BlockSpec((1, page_size, kv_h, d), pool_idx),
            pl.BlockSpec((1, kv_h, bq, d), fresh_idx),
            pl.BlockSpec((1, kv_h, bq, d), fresh_idx),
        ],
        out_specs=pl.BlockSpec((1, h, bq, d), q_idx),
        scratch_shapes=[
            pltpu.VMEM((h, bq, d), jnp.float32),
            pltpu.VMEM((h, bq, 1), jnp.float32),
            pltpu.VMEM((h, bq, 1), jnp.float32),
        ],
    )
    bt = jnp.asarray(block_tables, jnp.int32)
    off = jnp.broadcast_to(jnp.asarray(offset, jnp.int32).reshape(-1), (b,))
    return pl.pallas_call(
        functools.partial(_paged_chunk_kernel, scale=scale, bq=bq,
                          page_size=page_size, n_pages=n_pages,
                          group=h // kv_h, window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, t, d), q.dtype),
        interpret=interpret,
    )(bt, off, q, k_pool, v_pool, k_fresh, v_fresh)


def flash_chunk_prefill_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                               offset: jax.Array, *, scale: float,
                               window: int | None, bq: int, bkv: int,
                               interpret: bool) -> jax.Array:
    """q: (b, h, t, d) chunk queries; k, v: (b, kv_h, S, d) full cache rows;
    offset: (b,) int32 per-row offsets (scalar-prefetched) -> (b, h, t, d).
    """
    b, h, t, d = q.shape
    kv_h, S = k.shape[1], k.shape[2]
    assert h % kv_h == 0 and t % bq == 0 and S % bkv == 0
    group = h // kv_h
    grid = (b, h, t // bq, S // bkv)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d),
                         lambda bi, hi, qi, ki, off_ref: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, bkv, d),
                         lambda bi, hi, qi, ki, off_ref:
                         (bi, hi // group, ki, 0)),
            pl.BlockSpec((1, 1, bkv, d),
                         lambda bi, hi, qi, ki, off_ref:
                         (bi, hi // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda bi, hi, qi, ki, off_ref:
                               (bi, hi, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
    )
    off = jnp.broadcast_to(jnp.asarray(offset, jnp.int32).reshape(-1), (b,))
    return pl.pallas_call(
        functools.partial(_chunk_kernel, scale=scale, bq=bq, bkv=bkv,
                          window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, t, d), q.dtype),
        interpret=interpret,
    )(off, q, k, v)


def flash_prefill_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                         scale: float, causal: bool, window: int | None,
                         bq: int, bkv: int, interpret: bool) -> jax.Array:
    """q: (b, h, s, d); k, v: (b, kv_h, s, d) -> (b, h, s, d)."""
    b, h, s, d = q.shape
    kv_h = k.shape[1]
    assert h % kv_h == 0 and s % bq == 0 and s % bkv == 0
    group = h // kv_h
    grid = (b, h, s // bq, s // bkv)
    return pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, bq=bq, bkv=bkv,
                          causal=causal, window=window),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, bkv, d),
                         lambda bi, hi, qi, ki: (bi, hi // group, ki, 0)),
            pl.BlockSpec((1, 1, bkv, d),
                         lambda bi, hi, qi, ki: (bi, hi // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
