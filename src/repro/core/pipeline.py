"""Pipeline-parallel schedule over the 'pp' mesh axis.

Stage partitioning lives in the BlockStack registry
(``models/registry.py``: plan → contiguous stage ranges, homogeneous slabs
or selector-switched union slots); this module owns the schedule itself —
the pipelined tick loop, the stage-boundary transfer, and the analytic
bubble model.  The model supplies the per-stage compute (``stage_fn``) and
the loss head (``collect_fn``).

Design (composes with the paper's 3-D cube, Megatron-style — arXiv
2104.04473):

  * The layer plan is cut into ``pp`` contiguous stages.  Stage s's block
    parameters are stacked with a leading stage dim sharded over the 'pp'
    mesh axis, so each pipeline group holds only its own slots.  Embedding
    (and any modality frontend) is consumed at stage 0 and the LM head at
    the last stage (their tables stay replicated along 'pp'; the cube still
    shards them).
  * The schedule runs ``T = m + pp - 1`` ticks for ``m`` microbatches.  At
    every tick all stages compute concurrently (a ``vmap`` over the stage
    dim — each stage applying *its* parameter slots, each on a different
    microbatch), then the pipeline state moves stage s -> s+1 through a
    ``ppermute`` point-to-point transfer.  Stage 0 injects microbatch
    ``min(t, m-1)``; the last stage emits microbatch ``t - (pp-1)``.
  * The pipeline state is a PYTREE per microbatch, not just the residual:
    ``x`` (activations), read-only ``ctx`` carries that must stay attached
    to their microbatch across stages (the audio encoder states consumed by
    every cross-attention block), and ``aux`` accumulators that stages add
    to (MoE router losses).  All three shift together.
  * The whole loop is a differentiable ``lax.scan``: reverse-mode grads
    replay the ticks backward with the transposed ppermute, i.e. the
    backward pipeline.  With per-block remat this is the 1F1B-equivalent
    synchronous schedule; its bubble is the classic ``(pp-1)/m`` idle
    fraction, which the analytic cost model reports.

Inside a stage every linear still runs the paper's direction-exchange 3-D
algorithm — the shard_map islands vmap cleanly over the stage dim.

Sharding contract:

  * entry:  stage parameters arrive stacked as (pp, slots, ...) with dim 0
    sharded over 'pp' and the trailing dims on the paper's weight specs.
    Embedding / head / frontend / shared tables arrive replicated along
    'pp' (cube-sharded as usual).
  * inside: every pipeline-state leaf is (pp, ...) with dim 0 on 'pp' and
    the rest on its declared spec (activations: the act spec; ctx carries:
    the stack's ``ctx_specs``; aux: replicated scalars).  ``shift_stages``
    is the only place state crosses the 'pp' axis (ppermute) and it
    preserves every leaf's spec.
  * exit:   the collected accumulator leaves replicated over 'pp' (every
    stage group holds the scalars); gradients inherit the parameter specs
    above — optimizer-state placement on top of them (ZeRO over dp) is the
    optimizer's business, not the pipeline's.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from .topology import Layout, bubble_fraction, pipeline_efficiency

F32 = jnp.float32


def state_spec(layout: Layout, leaf_spec: P) -> P:
    """PartitionSpec of one (pp, ...) pipeline-state leaf."""
    return P("pp", *(leaf_spec or ()))


# ---------------------------------------------------------------------------
# Point-to-point stage boundary transfer
# ---------------------------------------------------------------------------
def shift_stages(layout: Layout, state, specs):
    """Move the pipeline-state pytree stage s -> s+1 along 'pp' via
    collective-permute.

    Every leaf is (pp, ...) with the leading dim sharded over 'pp';
    ``specs`` is a matching pytree of the per-leaf specs *without* the pp
    dim.  The last stage's slice is dropped (consumed by the loss head);
    stage 0's slot becomes zeros (overwritten by the next injection).
    """
    pp = layout.n_stages
    if pp == 1:
        return state
    perm = [(s, s + 1) for s in range(pp - 1)]
    leaves, treedef = jax.tree.flatten(state)
    spec_leaves = [state_spec(layout, sp) for sp in jax.tree.leaves(
        specs, is_leaf=lambda s: s is None or isinstance(s, P))]
    assert len(spec_leaves) == len(leaves), (len(spec_leaves), len(leaves))

    def body(*blks):
        return tuple(lax.ppermute(b, "pp", perm) for b in blks)

    out = shard_map(body, mesh=layout.mesh, in_specs=tuple(spec_leaves),
                    out_specs=tuple(spec_leaves), check_vma=False)(*leaves)
    return treedef.unflatten(out)


# ---------------------------------------------------------------------------
# The schedule
# ---------------------------------------------------------------------------
def pipeline_schedule(layout: Layout, *, x_mbs, stage_params,
                      stage_fn: Callable, collect_fn: Callable,
                      collect_init, act_p: P, ctx_mbs=None, ctx_specs=None,
                      aux_init=None):
    """Run the synchronous pipelined loop.

    x_mbs:        (m, B_mb, S, H) embedded microbatches (stage-0 feed)
    ctx_mbs:      pytree of (m, ...) read-only per-microbatch context
                  arrays that ride along (e.g. audio encoder states);
                  ``ctx_specs`` gives each leaf's spec (without pp/m dims)
    aux_init:     pytree of f32 scalars — per-microbatch accumulators reset
                  at injection and summed into by the stages
    stage_params: pytree with a leading (pp, ...) dim per leaf
    stage_fn:     (x, ctx, aux, one-stage params) -> (x, aux)
    collect_fn:   (acc, x_last, ctx_last, aux_last, mb_index) -> acc;
                  mb_index < 0 marks warm-up ticks whose output is pipeline
                  garbage
    Returns the final accumulator after m + pp - 1 ticks.
    """
    pp = layout.n_stages
    m = x_mbs.shape[0]
    ctx_mbs = {} if ctx_mbs is None else ctx_mbs
    ctx_specs = {} if ctx_specs is None else ctx_specs
    aux_init = {} if aux_init is None else aux_init
    wsc = lax.with_sharding_constraint

    specs = {"x": act_p, "ctx": ctx_specs,
             "aux": jax.tree.map(lambda _: P(), aux_init)}

    def buf(a):
        return jnp.zeros((pp,) + a.shape[1:], a.dtype)

    state0 = {
        "x": buf(x_mbs),
        "ctx": jax.tree.map(buf, ctx_mbs),
        "aux": jax.tree.map(lambda s: jnp.zeros((pp,), F32), aux_init),
    }

    def constrain(state):
        return jax.tree.map(
            lambda a, sp: wsc(a, layout.sharding(state_spec(layout, sp))),
            state, specs,
            is_leaf=lambda s: s is None or isinstance(s, P))

    state0 = constrain(state0)

    def inject(state, t):
        """Feed microbatch min(t, m-1) (+ fresh aux zeros) into stage 0."""
        mb = jnp.minimum(t, m - 1)

        def put(bufa, feed):
            inj = lax.dynamic_index_in_dim(feed, mb, 0, keepdims=True)
            return lax.dynamic_update_slice_in_dim(
                bufa, inj.astype(bufa.dtype), 0, axis=0)

        state = dict(state)
        state["x"] = put(state["x"], x_mbs)
        state["ctx"] = jax.tree.map(put, state["ctx"], ctx_mbs)
        state["aux"] = jax.tree.map(lambda a: a.at[0].set(0.0), state["aux"])
        return constrain(state)

    def tick(carry, t):
        state, acc = carry
        state = inject(state, t)
        out_x, out_aux = jax.vmap(stage_fn)(state["x"], state["ctx"],
                                            state["aux"], stage_params)
        out = constrain({"x": out_x, "ctx": state["ctx"], "aux": out_aux})
        acc = collect_fn(acc, out["x"][pp - 1],
                         jax.tree.map(lambda a: a[pp - 1], out["ctx"]),
                         jax.tree.map(lambda a: a[pp - 1], out["aux"]),
                         t - (pp - 1))
        state = shift_stages(layout, out, specs)
        return (state, acc), None

    (_, acc), _ = lax.scan(tick, (state0, collect_init),
                           jnp.arange(m + pp - 1))
    return acc


# ---------------------------------------------------------------------------
# Analytic schedule model (shared by dryrun / benchmarks; the formulas live
# in core.topology so every layer reports the same numbers)
# ---------------------------------------------------------------------------
def pipeline_report(n_stages: int, microbatches: int) -> dict:
    m = max(microbatches, 1)
    return {
        "n_stages": n_stages,
        "microbatches": m,
        "ticks": m + n_stages - 1,
        "bubble_fraction": bubble_fraction(n_stages, m),
        "efficiency": pipeline_efficiency(n_stages, m),
    }
