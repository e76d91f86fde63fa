"""Public wrappers for decode attention: streaming kernel + split-KV variant."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from repro.kernels import default_interpret
from repro.kernels.decode_attention import kernel

NEG_INF = -1e30


@functools.partial(jax.jit, static_argnames=("bkv", "interpret"))
def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     cache_len: jax.Array, *, bkv: int = 256,
                     interpret: bool | None = None) -> jax.Array:
    """Single-token GQA attention against a (possibly partially filled) cache.

    q: (b, h, 1, d); k, v: (b, kv_h, s, d); cache_len: int32 scalar array or
    (b,) per-request live lengths (ragged continuous batch).
    """
    if interpret is None:
        interpret = default_interpret()
    b, h, _, d = q.shape
    s = k.shape[2]
    scale = 1.0 / float(d) ** 0.5
    bkv = min(bkv, s)
    # Never pad the cache stream if a reasonable divisor block size exists:
    # inside the serving engine's fused decode scan, a pad is a full
    # KV-cache copy per tick.  Candidates are 8-aligned (Mosaic block dims)
    # and >= 64; real cache geometries (powers of two) always have one.
    # Otherwise padding beats a degenerate block size — keep the requested
    # bkv and pad the tail, as before.
    if s % bkv:
        cand = bkv - bkv % 8
        while cand > 64 and s % cand:
            cand -= 8
        if cand >= 8 and s % cand == 0:
            bkv = cand
    pad = (-s) % bkv
    if pad:
        widths = ((0, 0), (0, 0), (0, pad), (0, 0))
        k = jnp.pad(k, widths)
        v = jnp.pad(v, widths)
    return kernel.decode_attention_pallas(
        q, k, v, jnp.asarray(cache_len), scale=scale, bkv=bkv,
        interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def decode_attention_paged(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                           block_tables: jax.Array, cache_len: jax.Array, *,
                           interpret: bool | None = None) -> jax.Array:
    """Single-token GQA attention against a *paged* KV cache.

    q: (b, h, 1, d); k_pool, v_pool: (num_pages, page_size, kv_h, d) — the
    global page pool shared by every slot; block_tables: (b, n_pages) int32
    page ids per slot (dead entries must point at the reserved null page so
    their DMA target is valid — they are skipped before any compute);
    cache_len: int32 scalar or (b,) per-slot live lengths.

    Unlike the contiguous path there is never a pad copy: the pool's page
    axis *is* the block axis, so every KV block is full-size by construction,
    and compute is issued only for pages a slot owns (a slot with 40 live
    tokens in a 4096-token ``max_seq`` does attention work for 3 16-token
    pages, not 4096 rows — the dead grid steps fetch the null page and skip).
    """
    if interpret is None:
        interpret = default_interpret()
    d = q.shape[3]
    scale = 1.0 / float(d) ** 0.5
    return kernel.paged_decode_attention_pallas(
        q, k_pool, v_pool, jnp.asarray(block_tables, jnp.int32),
        jnp.asarray(cache_len), scale=scale, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def decode_attention_paged_quant(q: jax.Array, k_pool: jax.Array,
                                 v_pool: jax.Array, k_scale_pool: jax.Array,
                                 v_scale_pool: jax.Array,
                                 block_tables: jax.Array,
                                 cache_len: jax.Array, *,
                                 interpret: bool | None = None) -> jax.Array:
    """Single-token GQA attention against a *paged int8* KV cache.

    Same contract as ``decode_attention_paged`` plus the two per-(token,
    head) scale pools (num_pages, page_size, kv_h) f32.  Dequantization
    happens inside the kernel after each page DMA (int8 × bf16 scale,
    widened to f32), so HBM traffic stays int8 and the numerics match the
    contiguous KV8 path's bf16 dequant exactly.
    """
    if interpret is None:
        interpret = default_interpret()
    d = q.shape[3]
    scale = 1.0 / float(d) ** 0.5
    return kernel.paged_decode_attention_quant_pallas(
        q, k_pool, v_pool, k_scale_pool, v_scale_pool,
        jnp.asarray(block_tables, jnp.int32), jnp.asarray(cache_len),
        scale=scale, interpret=interpret)


def splitk_partials(q: jax.Array, k: jax.Array, v: jax.Array,
                    cache_len, *, n_splits: int, chunk: int,
                    split0=0, window: int | None = None):
    """Per-chunk partial-softmax pieces ``(m, l, acc)`` for a contiguous run
    of ``n_splits`` KV chunks starting at global chunk index ``split0``.

    q: (b, h, 1, d); k, v: (b, kv_h, n_splits * chunk, d) — the local slice
    of the (padded) sequence.  ``split0`` may be a traced scalar (e.g.
    ``lax.axis_index`` inside ``shard_map``).  This is the canonical
    formulation shared by the single-device and mesh paths: a device
    computing chunks [i, i + n_local) produces bit-identical partials to
    the same chunk rows of a single-device ``n_splits=K`` call, because
    each output element is the same elementwise dot over ``d`` and the
    chunk axis is only ever batched, never reduced, here.

    Returns m, l: (b, h, n_splits, 1, 1) f32; acc: (b, h, n_splits, 1, d)
    f32, with the chunk axis at position 2.

    Each chunk is computed by an identical-shape program (``lax.map`` over
    the chunk axis) rather than one einsum batched over all local chunks:
    XLA's dot strategy — and with it the f32 accumulation order — can
    change with the chunk-batch extent (observed for odd ``chunk``), which
    would break the cross-shard bitwise contract.  The sequential map costs
    nothing at serving split counts (K <= 8) and the per-chunk dots are the
    same flops either way.
    """
    b, h, _, d = q.shape
    kv_h = k.shape[1]
    scale = 1.0 / float(d) ** 0.5
    kc = k.reshape(b, kv_h, n_splits, chunk, d)
    vc = v.reshape(b, kv_h, n_splits, chunk, d)
    kc = jnp.repeat(kc, h // kv_h, axis=1).astype(jnp.float32)
    vc = jnp.repeat(vc, h // kv_h, axis=1).astype(jnp.float32)
    base = (split0 + jnp.arange(n_splits)) * chunk
    pos = base[:, None] + jnp.arange(chunk)[None, :]          # (splits, chunk)
    qf = q.astype(jnp.float32)
    cl = jnp.asarray(cache_len)

    def one_chunk(xs):
        kci, vci, posi = xs               # (b,h,chunk,d) x2, (chunk,)
        sc = jnp.einsum("bhqd,bhkd->bhqk", qf, kci) * scale   # (b,h,1,chunk)
        if cl.ndim == 1:  # per-request lengths -> (b, 1, 1, 1)
            clb = cl[:, None, None, None]
            mask = posi[None, None, None, :] < clb
            if window is not None:
                mask &= posi[None, None, None, :] >= clb - window
        else:
            mask = (posi < cl)[None, None, None, :]
            if window is not None:
                mask &= (posi >= cl - window)[None, None, None, :]
        sc = jnp.where(mask, sc, NEG_INF)
        mi = jnp.max(sc, axis=-1, keepdims=True)              # (b,h,1,1)
        p = jnp.where(mask, jnp.exp(sc - mi), 0.0)
        li = jnp.sum(p, axis=-1, keepdims=True)
        ai = jnp.einsum("bhqk,bhkd->bhqd", p, vci)            # (b,h,1,d)
        return mi, li, ai

    m, l, acc = jax.lax.map(
        one_chunk, (jnp.moveaxis(kc, 2, 0), jnp.moveaxis(vc, 2, 0), pos))
    # lax.map stacks on axis 0 -> (c, b, h, 1, ·); chunk axis to position 2
    return (jnp.moveaxis(m, 0, 2), jnp.moveaxis(l, 0, 2),
            jnp.moveaxis(acc, 0, 2))


def splitk_combine(m: jax.Array, l: jax.Array, acc: jax.Array,
                   dtype) -> jax.Array:
    """Merge per-chunk partial-softmax pieces over the chunk axis (axis 2):
    global max, rescale partial numerators/denominators, normalize.  The
    merge is bitwise invariant to how the chunk axis was produced (one
    device or an ordered ``all_gather`` across a mesh axis) because every
    reduction runs over the identical K-length axis in chunk order."""
    m_g = jnp.max(m, axis=2, keepdims=True)
    alpha = jnp.exp(m - m_g)
    l_g = jnp.sum(l * alpha, axis=2)                          # (b,h,1,1)
    acc_g = jnp.sum(acc * alpha, axis=2)                      # (b,h,1,d)
    return (acc_g / jnp.maximum(l_g, 1e-30)).astype(dtype)


def validate_num_splits(num_splits: int, axis_size: int, *,
                        axis_name: str = "model") -> None:
    """A mesh-sharded splitk needs each device of ``axis_name`` to own an
    equal contiguous run of chunks — fail loudly instead of letting the
    per-device reshape produce a silent shape mismatch."""
    if num_splits < 1:
        raise ValueError(f"num_splits must be >= 1, got {num_splits}")
    if axis_size and num_splits % axis_size:
        raise ValueError(
            f"num_splits={num_splits} is not a multiple of the "
            f"'{axis_name}' mesh axis size {axis_size}: each device must "
            f"own an equal run of KV chunks.  Pass num_splits as a "
            f"multiple of {axis_size} (e.g. num_splits="
            f"{axis_size * max(1, -(-num_splits // axis_size))}).")


@functools.partial(jax.jit, static_argnames=("n_splits", "num_splits",
                                             "mesh_axis_size", "bkv",
                                             "interpret"))
def decode_attention_splitk(q: jax.Array, k: jax.Array, v: jax.Array,
                            cache_len: jax.Array, *, n_splits: int = 4,
                            num_splits: int | None = None,
                            mesh_axis_size: int | None = None,
                            bkv: int = 256,
                            interpret: bool | None = None) -> jax.Array:
    """Flash-decoding: shard the KV sequence into independent chunks,
    compute per-chunk partial (acc, m, l) via log-sum-exp pieces, combine.

    This is the TPU long-context move the paper's single DDR channel cannot
    make — chunks map onto sequence-sharded devices or onto parallel grid
    work.  Implemented with the jnp oracle math per chunk so it also serves
    as the sequence-parallel reference for the sharded serve path.

    ``n_splits`` is advisory: non-divisible geometries follow the same
    pad-avoidance rule as ``decode_attention`` (prefer a nearby split count
    that divides ``s`` — a tail pad is a full K/V copy per call — while it
    keeps at least half the requested parallelism; a split-resistant length
    pads the tail instead, masked by ``cache_len``).

    ``num_splits`` is *exact*: the chunk count is used as given (padding
    the tail when it does not divide ``s``), which is what a mesh needs —
    the divisor-candidate fallback would silently change the chunk count a
    `model`-axis shard_map partitioned against.  Pass ``mesh_axis_size`` to
    validate the split count against the mesh axis with a clear error.
    """
    b, h, _, d = q.shape
    s = k.shape[2]
    if num_splits is not None:
        n_splits = int(num_splits)
        validate_num_splits(n_splits, mesh_axis_size or 0)
        if s % n_splits:
            chunk_p = -(-s // n_splits)
            pad = n_splits * chunk_p - s
            widths = ((0, 0), (0, 0), (0, pad), (0, 0))
            k = jnp.pad(k, widths)
            v = jnp.pad(v, widths)
            s = s + pad
    else:
        if mesh_axis_size:
            # the divisor-candidate fallback below may *change* the split
            # count — unacceptable against a fixed mesh axis
            validate_num_splits(n_splits, mesh_axis_size)
            if s % n_splits:
                raise ValueError(
                    f"KV length {s} is not divisible by n_splits="
                    f"{n_splits} under a mesh axis of size "
                    f"{mesh_axis_size}; pass num_splits= explicitly to "
                    f"pin the chunk count (the tail is padded + masked).")
        if s % n_splits:
            # nearby split count that divides s, floored at half the
            # requested parallelism (decode_attention's divisor rule)
            cand = n_splits
            floor = max(1, n_splits // 2)
            while cand > floor and s % cand:
                cand -= 1
            if s % cand == 0:
                n_splits = cand
            else:  # no acceptable divisor: keep parallelism, pad + mask
                chunk_p = -(-s // n_splits)
                pad = n_splits * chunk_p - s
                widths = ((0, 0), (0, 0), (0, pad), (0, 0))
                k = jnp.pad(k, widths)
                v = jnp.pad(v, widths)
                s = s + pad
    chunk = s // n_splits
    m, l, acc = splitk_partials(q, k, v, cache_len,
                                n_splits=n_splits, chunk=chunk)
    return splitk_combine(m, l, acc, q.dtype)


def decode_attention_splitk_sharded(q: jax.Array, k: jax.Array, v: jax.Array,
                                    cache_len, *, mesh,
                                    axis_name: str = "model",
                                    num_splits: int | None = None
                                    ) -> jax.Array:
    """Mesh-aware flash-decoding: KV storage stays replicated across
    ``axis_name``, compute is split — each device slices its own contiguous
    run of ``num_splits / axis_size`` chunks, computes partials, and the
    per-chunk (m, l, acc) are ``all_gather``'d along the chunk axis *in
    axis order* (an ordered concatenation, unlike ``psum`` whose reduction
    order is unspecified) before every device runs the identical combine.
    Bit-for-bit equal to ``decode_attention_splitk(..., num_splits=K)`` on
    one device.

    Test/reference wrapper: it builds a fresh shard_map per call (no jit
    cache reuse) — the serving engine plumbs the same partials/combine
    through its own shard_map'd decode block instead.
    """
    ax = int(mesh.shape[axis_name])
    n_splits = int(num_splits) if num_splits else max(ax, 1)
    validate_num_splits(n_splits, ax, axis_name=axis_name)
    s = k.shape[2]
    chunk = -(-s // n_splits)
    pad = n_splits * chunk - s
    if pad:
        widths = ((0, 0), (0, 0), (0, pad), (0, 0))
        k = jnp.pad(k, widths)
        v = jnp.pad(v, widths)
    n_local = n_splits // ax

    def body(q, k, v, cl):
        i = jax.lax.axis_index(axis_name)
        k_loc = jax.lax.dynamic_slice_in_dim(
            k, i * (n_local * chunk), n_local * chunk, axis=2)
        v_loc = jax.lax.dynamic_slice_in_dim(
            v, i * (n_local * chunk), n_local * chunk, axis=2)
        m, l, acc = splitk_partials(q, k_loc, v_loc, cl,
                                    n_splits=n_local, chunk=chunk,
                                    split0=i * n_local)
        m = jax.lax.all_gather(m, axis_name, axis=2, tiled=True)
        l = jax.lax.all_gather(l, axis_name, axis=2, tiled=True)
        acc = jax.lax.all_gather(acc, axis_name, axis=2, tiled=True)
        return splitk_combine(m, l, acc, q.dtype)

    reps = tuple(P() for _ in range(4))
    fn = jax.shard_map(body, mesh=mesh, in_specs=reps, out_specs=P(),
                       check_vma=False)
    return jax.jit(fn)(q, k, v, jnp.asarray(cache_len))
