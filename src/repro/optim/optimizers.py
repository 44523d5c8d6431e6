"""Optimizers (own implementation — no optax dependency).

AdamW with ZeRO-style distributed state partitioning and Adafactor
(factored second moment, no first moment) for the parameter-heavy MoE
archs where full Adam state cannot fit.

Sharding contract (driven by ``Layout.zero_stage``, set via
``ParallelPlan.zero_stage``):

  * entry:  ``params`` and ``grads`` arrive with the *parameter* specs from
    the model (cube/pp-sharded, replicated over the data axes).  Gradients
    have already been summed over dp by the backward pass.
  * state:  with ``effective_zero_stage() >= 1`` each Adam moment (and the
    f32 update temporaries) lives under ``zero_partition_spec`` — the
    parameter's own spec *extended by the data axes* ('pod', 'dp') on the
    largest evenly-divisible dim, so each data replica stores and updates
    a 1/(pod*dp) shard.  Gradients are
    reduce-scattered onto that shard before the elementwise update: a
    ``with_sharding_constraint`` from the dp-replicated spec to the
    dp-extended one lowers the dp all-reduce into a reduce-scatter.
    With stage 0 the state simply mirrors the parameter specs (replicated
    over dp).  A dim divisible by neither stays on the parameter spec
    (falls back to replication for that leaf).
  * exit:   updated parameters are constrained back to the parameter specs
    — the all-gather that rebuilds the full value on every replica — and
    the new moments stay on their ZeRO shard.  Optimizer state therefore
    NEVER round-trips through the replicated layout.

Adafactor's factored row/col stats are O(sum of dims), not O(params); they
stay on the parameter-derived specs at every stage (sharding them over dp
would save little and complicate the factored update).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..config import OptimConfig
from ..core.params import Param, is_param, tree_map_params
from ..core.topology import Layout

F32 = jnp.float32


class OptState(NamedTuple):
    step: Any
    m: Any          # first moment (AdamW) or None
    v: Any          # second moment (AdamW) / factored stats (Adafactor)


# ---------------------------------------------------------------------------
# schedules
# ---------------------------------------------------------------------------
def make_schedule(cfg: OptimConfig) -> Callable:
    def sched(step):
        step = step.astype(F32)
        warm = jnp.minimum(step / jnp.maximum(cfg.warmup, 1), 1.0)
        if cfg.schedule == "cosine":
            t = jnp.clip((step - cfg.warmup) /
                         jnp.maximum(cfg.total_steps - cfg.warmup, 1), 0, 1)
            decay = 0.5 * (1 + jnp.cos(jnp.pi * t))
        elif cfg.schedule == "linear":
            t = jnp.clip((step - cfg.warmup) /
                         jnp.maximum(cfg.total_steps - cfg.warmup, 1), 0, 1)
            decay = 1 - t
        else:
            decay = jnp.ones(())
        return cfg.lr * warm * decay
    return sched


def clip_by_global_norm(grads, max_norm: float):
    leaves = jax.tree.leaves(grads)
    gn = jnp.sqrt(sum(jnp.sum(g.astype(F32) ** 2) for g in leaves))
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(gn, 1e-9))
    # scale in the grad's own dtype: keeps the op a single fused elementwise
    # kernel instead of materializing an f32 copy of every gradient
    return jax.tree.map(lambda g: g * scale.astype(g.dtype), grads), gn


# ---------------------------------------------------------------------------
# state spec helpers (ZeRO: extend the param spec with 'dp' when possible)
# ---------------------------------------------------------------------------
def zero_partition_spec(p: Param, layout: Layout) -> P:
    """The ZeRO shard spec for one parameter's optimizer state: the param's
    own spec with the data axes ('pod', 'dp'; sizes > 1 only) attached to
    the largest dim they divide evenly — so the state shards over the full
    data degree pod*dp that plan validation and the memory model promise.
    Returns the unmodified param spec when the data degree is 1, when the
    spec already uses a data axis, or when no dim divides (that leaf stays
    replicated)."""
    spec = tuple(p.spec) if p.spec is not None else (None,) * len(p.shape)
    spec = list(spec) + [None] * (len(p.shape) - len(spec))
    data_axes = tuple(a for a in ("pod", "dp") if layout.size(a) > 1)
    d = math.prod(layout.size(a) for a in data_axes)
    if d <= 1:
        return p.spec
    used = set()
    for e in spec:
        for a in (e if isinstance(e, (tuple, list)) else (e,)):
            if a:
                used.add(a)
    if used.intersection(data_axes):
        return p.spec
    # attach the data axes to the largest evenly-divisible dim
    order = sorted(range(len(p.shape)), key=lambda i: -p.shape[i])
    for i in order:
        e = spec[i]
        cur = math.prod(layout.size(a) for a in
                        ((e,) if isinstance(e, str) else (e or ())))
        if p.shape[i] % (cur * d) == 0:
            if e is None:
                spec[i] = data_axes if len(data_axes) > 1 else data_axes[0]
            elif isinstance(e, str):
                spec[i] = (e, *data_axes)
            else:
                spec[i] = tuple(e) + data_axes
            return P(*spec)
    return p.spec


def opt_state_abstract(param_tree, layout: Layout, cfg: OptimConfig):
    """Abstract Param tree for the optimizer state (for dry-runs and as a
    checkpoint-restore template; specs follow the layout's ZeRO stage)."""
    zero = layout.effective_zero_stage() >= 1

    def moment(p: Param):
        spec = zero_partition_spec(p, layout) if zero else p.spec
        return Param(p.shape, spec, dtype=F32, init="zeros")

    if cfg.name == "adafactor":
        def vstat(p: Param):
            if len(p.shape) < 2 or p.size < 4096:
                return Param(p.shape, p.spec, dtype=F32, init="zeros")
            # factored: row/col stats drop the last / second-to-last dims
            row_shape = p.shape[:-1]
            col_shape = p.shape[:-2] + p.shape[-1:]
            rspec = P(*((p.spec or (None,) * len(p.shape))[:-1]))
            cspec_parts = tuple(p.spec or (None,) * len(p.shape))
            cspec = P(*(cspec_parts[:-2] + cspec_parts[-1:]))
            return {"row": Param(row_shape, rspec, dtype=F32, init="zeros"),
                    "col": Param(col_shape, cspec, dtype=F32, init="zeros")}
        return OptState(
            step=Param((), P(), dtype=jnp.int32, init="zeros"),
            m=None,
            v=tree_map_params(vstat, param_tree))
    return OptState(
        step=Param((), P(), dtype=jnp.int32, init="zeros"),
        m=tree_map_params(moment, param_tree),
        v=tree_map_params(moment, param_tree))


def adamw_init(param_tree, layout: Layout, cfg: OptimConfig):
    from ..core.params import init_params
    return init_params(opt_state_abstract(param_tree, layout, cfg),
                       jax.random.key(0), layout=layout)


adafactor_init = adamw_init


# ---------------------------------------------------------------------------
# updates
# ---------------------------------------------------------------------------
_BIG_LEAF_BYTES = 2 ** 28        # update leaves above this are scanned


def _scanned_update(p, args, one):
    """Apply ``one(p_slice, *arg_slices) -> (new_p_slice, aux_tree)`` over
    dim0 slices of a big (layer-stacked) leaf under lax.scan: the f32 update
    temporaries live for one layer slice instead of the whole stack."""
    import jax as _jax

    def body(_, xs):
        return None, one(xs[0], *xs[1:])

    _, out = _jax.lax.scan(body, None, (p, *args))
    return out



def make_optimizer(cfg: OptimConfig, layout: Layout, param_tree=None):
    """param_tree (abstract Params) enables the ZeRO update path: the moment
    update is computed on the dp-sharded view (grads arrive via a GSPMD
    reduce-scatter), the new moments stay on their shard, and only the
    updated parameter is re-gathered (see the module docstring contract)."""
    sched = make_schedule(cfg)
    wsc = jax.lax.with_sharding_constraint
    zspecs = None
    if param_tree is not None and layout.effective_zero_stage() >= 1:
        from ..core.params import tree_map_params
        zspecs = tree_map_params(
            lambda p: zero_partition_spec(p, layout), param_tree)

    def _z(tree):
        if zspecs is None:
            return tree
        return jax.tree.map(
            lambda a, sp: wsc(a, layout.sharding(sp)), tree, zspecs)

    def adamw_update(params, grads, state: OptState):
        step = state.step + 1
        lr = sched(step)
        b1, b2 = cfg.b1, cfg.b2
        grads, gnorm = clip_by_global_norm(grads, cfg.clip_norm)
        grads = _z(grads)   # reduce-scatter the grads onto the ZeRO shards

        def upd_one(p, g, m, v):
            gf = g.astype(F32)
            m2 = b1 * m + (1 - b1) * gf
            v2 = b2 * v + (1 - b2) * gf * gf
            mh = m2 / (1 - b1 ** step.astype(F32))
            vh = v2 / (1 - b2 ** step.astype(F32))
            delta = mh / (jnp.sqrt(vh) + cfg.eps)
            if p.ndim >= 2 and cfg.weight_decay:
                delta = delta + cfg.weight_decay * p.astype(F32)
            return (p.astype(F32) - lr * delta).astype(p.dtype), m2, v2

        def upd(p, g, m, v):
            if p.ndim >= 3 and p.shape[0] > 1 and p.size * 4 > _BIG_LEAF_BYTES:
                return _scanned_update(p, (g, m, v), upd_one)
            return upd_one(p, g, m, v)

        params_z = _z(params)
        out = jax.tree.map(upd, params_z, grads, state.m, state.v)
        new_p = jax.tree.map(lambda t: t[0], out,
                             is_leaf=lambda x: isinstance(x, tuple))
        new_m = jax.tree.map(lambda t: t[1], out,
                             is_leaf=lambda x: isinstance(x, tuple))
        new_v = jax.tree.map(lambda t: t[2], out,
                             is_leaf=lambda x: isinstance(x, tuple))
        # the moments stay on their ZeRO shard across steps; only the
        # parameter is all-gathered back to its own (dp-replicated) spec
        new_m = _z(new_m)
        new_v = _z(new_v)
        if param_tree is not None:
            from ..core.params import tree_map_params
            pspecs = tree_map_params(lambda p: p.spec, param_tree)
            new_p = jax.tree.map(
                lambda a, sp: wsc(a, layout.sharding(sp)), new_p, pspecs)
        return new_p, OptState(step, new_m, new_v), {"lr": lr, "gnorm": gnorm}

    def adafactor_update(params, grads, state: OptState):
        step = state.step + 1
        lr = sched(step)
        grads, gnorm = clip_by_global_norm(grads, cfg.clip_norm)
        d = 1 - cfg.b2  # decay toward paper's 1 - t^-0.8 simplified

        def upd_one(p, g, v):
            gf = g.astype(F32)
            g2 = gf * gf + 1e-30
            if isinstance(v, dict):
                row = cfg.b2 * v["row"] + d * jnp.mean(g2, axis=-1)
                col = cfg.b2 * v["col"] + d * jnp.mean(g2, axis=-2)
                rc = row[..., None] / jnp.mean(row, axis=-1, keepdims=True)[..., None]
                inv = jax.lax.rsqrt(rc * col[..., None, :] + cfg.eps)
                new_v = {"row": row, "col": col}
            else:
                vhat = cfg.b2 * v + d * g2
                inv = jax.lax.rsqrt(vhat + cfg.eps)
                new_v = vhat
            rms = jnp.sqrt(jnp.mean((gf * inv) ** 2) + 1e-30)
            scale = lr / jnp.maximum(1.0, rms)
            decay = (cfg.weight_decay * lr) if (p.ndim >= 2 and cfg.weight_decay) else 0.0
            return (p.astype(F32) * (1 - decay) - scale * (gf * inv)
                    ).astype(p.dtype), new_v

        def upd(p, g, v):
            if (p.ndim >= 3 and p.shape[0] > 1 and p.size * 4 > _BIG_LEAF_BYTES
                    and isinstance(v, dict)):
                def one(ps, gs, rs, cs):
                    return upd_one(ps, gs, {"row": rs, "col": cs})
                np_, nv = _scanned_update(p, (g, v["row"], v["col"]), one)
                return np_, nv
            if p.ndim >= 3 and p.shape[0] > 1 and p.size * 4 > _BIG_LEAF_BYTES:
                return _scanned_update(p, (g, v), upd_one)
            return upd_one(p, g, v)

        vdict = lambda x: isinstance(x, dict) and set(x) == {"row", "col"}
        out = jax.tree.map(upd, params, grads, state.v,
                           is_leaf=lambda x: vdict(x))
        new_p = jax.tree.map(lambda t: t[0], out,
                             is_leaf=lambda x: isinstance(x, tuple))
        new_v = jax.tree.map(lambda t: t[1], out,
                             is_leaf=lambda x: isinstance(x, tuple))
        return new_p, OptState(step, None, new_v), {"lr": lr, "gnorm": gnorm}

    return adafactor_update if cfg.name == "adafactor" else adamw_update
