"""Step functions: the units that pjit lowers for training and serving.

``make_train_step``   — QAT ternary training step (fwd + bwd + AdamW).
``make_prefill_fn``   — prompt -> last logits + KV cache  (serve prefill).
``make_decode_fn``    — one token + cache -> logits + cache (serve decode).

These are pure functions of (cfg, ctx, optimizer); the launcher decides
shardings by attaching NamedShardings to the arguments (dry-run) or placing
real arrays (execution).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import transformer
from repro.models.layers import Ctx
from repro.optim import compression
from repro.optim.adamw import Optimizer, apply_updates


def softmax_xent(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean cross-entropy; stable under a vocab-sharded logits layout."""
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)


def make_train_step(cfg: ModelConfig, ctx: Ctx, optimizer: Optimizer,
                    microbatches: int = 1, loss_chunk: int = 512):
    """One optimizer step.  With microbatches > 1, gradients accumulate over
    a scan of microbatches (sequential — the standard memory/throughput
    trade on big models).  loss_chunk > 0 fuses unembedding+xent per
    sequence chunk (never materializes full logits); 0 disables."""

    def loss_fn(params, batch):
        if loss_chunk:
            x = transformer.forward_features(cfg, params, batch["inputs"],
                                             ctx)
            return transformer.lm_head_loss_chunked(
                cfg, params, x, batch["labels"], ctx, chunk=loss_chunk)
        logits = transformer.forward(cfg, params, batch["inputs"], ctx)
        return softmax_xent(logits, batch["labels"])

    def train_step(params, opt_state, batch):
        if microbatches == 1:
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        else:
            def split(x):
                return x.reshape((microbatches, x.shape[0] // microbatches)
                                 + x.shape[1:])
            mb = jax.tree_util.tree_map(split, batch)

            def acc_body(carry, mb_i):
                loss_acc, g_acc = carry
                loss_i, g_i = jax.value_and_grad(loss_fn)(params, mb_i)
                g_acc = jax.tree_util.tree_map(jnp.add, g_acc, g_i)
                return (loss_acc + loss_i, g_acc), None

            g0 = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (loss, grads), _ = jax.lax.scan(acc_body, (0.0, g0), mb)
            loss = loss / microbatches
            grads = jax.tree_util.tree_map(lambda g: g / microbatches, grads)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = apply_updates(params, updates)
        return params, opt_state, {"loss": loss}

    return train_step


def make_train_step_ddp(cfg: ModelConfig, ctx: Ctx, optimizer: Optimizer,
                        mesh, *, compress: bool = True,
                        loss_chunk: int = 512):
    """Pure data-parallel training step via shard_map with explicit gradient
    all-reduce, optionally int8 error-feedback compressed.

    The right layout for small archs (§Perf cell B): weights replicated,
    every mesh axis is batch; the only collective is the gradient reduction,
    whose payload compression cuts 4x (f32 -> int8 + EF state).  The error
    state rides in opt_state-like fashion as an explicit argument.
    """
    import dataclasses

    from jax.sharding import PartitionSpec as P

    # inside shard_map every axis is manual: sharding constraints are
    # meaningless (and rejected) — drop the hook for the per-shard body
    ctx = dataclasses.replace(ctx, constrain=None)
    axes = tuple(mesh.axis_names)

    def loss_fn(params, batch):
        if loss_chunk:
            x = transformer.forward_features(cfg, params, batch["inputs"],
                                             ctx)
            return transformer.lm_head_loss_chunked(
                cfg, params, x, batch["labels"], ctx, chunk=loss_chunk)
        logits = transformer.forward(cfg, params, batch["inputs"], ctx)
        return softmax_xent(logits, batch["labels"])

    def per_shard(params, opt_state, err, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        if compress:
            flat_g, tdef = jax.tree_util.tree_flatten(grads)
            flat_e = jax.tree_util.tree_leaves(err)
            out_g, out_e = [], []
            for g, e in zip(flat_g, flat_e):
                rg, re = compression.compressed_psum(g, e, axes)
                out_g.append(rg)
                out_e.append(re)
            grads = jax.tree_util.tree_unflatten(tdef, out_g)
            err = jax.tree_util.tree_unflatten(tdef, out_e)
        else:
            n = jax.lax.psum(jnp.ones((), jnp.float32), axes)
            grads = jax.tree_util.tree_map(
                lambda g: jax.lax.psum(g, axes) / n, grads)
        loss = jax.lax.pmean(loss, axes)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = apply_updates(params, updates)
        return params, opt_state, err, {"loss": loss}

    batch_spec = jax.tree_util.tree_map(
        lambda _: P(axes), {"inputs": 0, "labels": 0})
    rep = P()

    def spec_like(tree):
        return jax.tree_util.tree_map(lambda _: rep, tree)

    def train_step(params, opt_state, err, batch):
        return jax.shard_map(
            per_shard, mesh=mesh,
            in_specs=(spec_like(params), spec_like(opt_state),
                      spec_like(err), batch_spec),
            out_specs=(spec_like(params), spec_like(opt_state),
                       spec_like(err), {"loss": rep}),
            check_vma=False,
        )(params, opt_state, err, batch)

    return train_step


def make_prefill_fn(cfg: ModelConfig, ctx: Ctx):
    def prefill_fn(params, inputs, cache):
        return transformer.prefill_step(cfg, params, inputs, ctx, cache)
    return prefill_fn


def make_decode_fn(cfg: ModelConfig, ctx: Ctx):
    def decode_fn(params, inputs, cache, cache_len):
        return transformer.decode_step(cfg, params, inputs, ctx, cache,
                                       cache_len)
    return decode_fn
