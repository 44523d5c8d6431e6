"""Train / prefill / decode step factories.

The returned functions are pure (params, opt_state, batch) -> ... and are
meant to be jitted by the caller (launcher, dry-run, tests).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ..config import ModelConfig, OptimConfig
from ..core.topology import Layout
from ..models import registry as model_registry
from ..models import transformer
from ..optim import make_optimizer


def _split_microbatches(batch, m: int):
    """(B, ...) leaves -> (m, B/m, ...); batch order is preserved so the
    concatenation of microbatches is exactly the original global batch."""
    def split(a):
        if a.shape[0] % m:
            raise ValueError(
                f"batch dim {a.shape[0]} not divisible by microbatches {m}")
        return a.reshape(m, a.shape[0] // m, *a.shape[1:])
    return jax.tree.map(split, batch)


def make_train_step(cfg: ModelConfig, layout: Layout, opt_cfg: OptimConfig):
    """One optimizer step per call, in one of three schedules derived from
    the layout's ParallelPlan bookkeeping:

      * pp == 1, microbatches == 1: the single-shot seed path.
      * pp == 1, microbatches  > 1: ``lax.scan`` over microbatches with
        f32 gradient accumulation.  Each microbatch is weighted by its
        valid-token count, so the aggregate loss/gradient equals the
        single-shot path's global token mean even when padding is spread
        unevenly across microbatches.
      * pp > 1: the 1F1B pipelined forward handles microbatching inside
        ``transformer.forward`` (see core/pipeline.py); one backward pass
        differentiates the whole schedule.

    With ``layout.effective_zero_stage() >= 2`` the f32 accumulation buffer
    is additionally kept on the ZeRO shard specs (reduce-scattered over dp
    every microbatch), so per-device gradient memory is 1/dp of the
    parameter count instead of a full replica — the optimizer then updates
    its state shard without any further gradient movement.
    """
    abstract = transformer.abstract_params(cfg, layout)
    update = make_optimizer(opt_cfg, layout, param_tree=abstract)
    m = max(layout.microbatches, 1)
    pipelined = layout.n_stages > 1
    stack = model_registry.get_stack(cfg.family)

    zshards = None
    if layout.effective_zero_stage() >= 2:
        from ..core.params import tree_map_params
        from ..optim.optimizers import zero_partition_spec
        zshards = tree_map_params(
            lambda p: layout.sharding(zero_partition_spec(p, layout)),
            abstract)

    def _scatter(gtree):
        if zshards is None:
            return gtree
        return jax.tree.map(jax.lax.with_sharding_constraint, gtree, zshards)

    def loss_fn(p, b):
        loss, metrics = transformer.forward(cfg, layout, p, b, mode="train")
        return loss, metrics

    def train_step(params, opt_state, batch):
        if pipelined or m == 1:
            # single backward pass (the pipeline microbatches internally)
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
            grads = _scatter(grads)
        else:
            mbs = _split_microbatches(batch, m)
            g0 = _scatter(jax.tree.map(
                lambda a: jnp.zeros(a.shape, jnp.float32), params))

            def body(acc, mb):
                gacc, lacc, macc, wacc = acc
                # weight = the forward pass's loss-mask total: sum of per-mb
                # (mean * count) over the total count reproduces the global
                # token mean.  Each family's BlockStack declares its own
                # mask accounting (VLM counts every text position).
                w = stack.mb_weight(cfg, mb)
                (l, met), g = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, mb)
                # ZeRO-2: each microbatch's grads reduce-scatter onto the dp
                # shard before accumulation, so gacc never fully materializes
                gacc = _scatter(jax.tree.map(
                    lambda a, b: a + w * b.astype(jnp.float32), gacc, g))
                macc = jax.tree.map(lambda a, b: a + w * b, macc, met)
                return (gacc, lacc + w * l, macc, wacc + w), None

            met0 = {"xent": jnp.zeros((), jnp.float32),
                    "aux": jnp.zeros((), jnp.float32)}
            if cfg.mtp:
                met0["mtp"] = jnp.zeros((), jnp.float32)
            (gsum, lsum, msum, wsum), _ = jax.lax.scan(
                body, (g0, jnp.zeros((), jnp.float32), met0,
                       jnp.zeros((), jnp.float32)), mbs)
            wsum = jnp.maximum(wsum, 1.0)
            loss = lsum / wsum
            metrics = jax.tree.map(lambda a: a / wsum, msum)
            grads = jax.tree.map(
                lambda g, p: (g / wsum).astype(p.dtype), gsum, params)
        params2, opt_state2, opt_metrics = update(params, grads, opt_state)
        metrics = dict(metrics, loss=loss, **opt_metrics)
        return params2, opt_state2, metrics

    return train_step


def make_forward_loss(cfg: ModelConfig, layout: Layout):
    def fwd(params, batch):
        return transformer.forward(cfg, layout, params, batch, mode="train")
    return fwd


def make_prefill_step(cfg: ModelConfig, layout: Layout):
    def prefill_step(params, batch):
        return transformer.forward(cfg, layout, params, batch, mode="prefill")
    return prefill_step


def make_decode_step(cfg: ModelConfig, layout: Layout):
    def decode_step(params, batch, cache):
        logits, new_cache = transformer.forward(cfg, layout, params, batch,
                                                mode="decode", cache=cache)
        return logits, new_cache
    return decode_step
