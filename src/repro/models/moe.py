"""Mixture-of-Experts FFN, expert-parallel over ('dp', in_ax).

Adaptation of the paper's cube to MoE (DESIGN.md §6): the token dimension is
exchanged across the expert-parallel group with all-to-all, the contraction
dim of every expert matmul stays split over ``out_ax`` (psum — the same role
it plays in Algorithm 1), and the expert dim is sharded over the axes whose
devices hold *different* tokens ('dp' and in_ax), which is exactly the set an
all-to-all may exchange without corrupting the psum groups.

Dispatch is capacity-based (sort-free ranking via stable argsort) so the
buffers have static shapes; overflow tokens are dropped (standard).
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from ..config import ModelConfig
from ..core.linear3d import act_spec, act_spec_decode
from ..core.params import Param
from ..core.topology import Dirs, Layout

F32 = jnp.float32


def ep_axes(layout: Layout, dirs: Dirs, n_experts: int) -> Tuple[str, ...]:
    """Largest expert-parallel group out of ('dp', in_ax) dividing n_experts."""
    if layout.strategy == "3d":
        tok_ax = dirs.in_ax
    elif layout.strategy == "2d":
        tok_ax = "y"
    else:
        tok_ax = None
    # any axis whose devices hold DIFFERENT tokens may carry the all-to-all
    # ('dp', 'x', in_ax); the contraction-psum axis (out_ax) may not.
    cands = [("dp", "x", tok_ax), ("dp", tok_ax), ("dp", "x"), ("dp",),
             ("x", tok_ax), (tok_ax,), ("x",)]
    for cand in cands:
        axes = tuple(a for a in cand if a is not None and layout.size(a) > 1)
        n = 1
        for a in axes:
            n *= layout.size(a)
        if axes and n > 1 and n_experts % n == 0:
            return axes
    return ()


def _contract_ax(layout: Layout, dirs: Dirs) -> Optional[str]:
    if layout.strategy == "3d":
        return dirs.out_ax
    return "z"


def moe_params(layout: Layout, cfg: ModelConfig, dirs: Dirs, fsdp=False):
    m = cfg.moe
    d, f, E = cfg.d_model, m.expert_ff, m.n_experts
    ep = ep_axes(layout, dirs, E)
    co = _contract_ax(layout, dirs)
    e_spec = ep if len(ep) > 1 else (ep[0] if ep else None)
    gated = cfg.act in ("silu", "gelu")
    one_d = layout.strategy == "1d"
    # storage-only FSDP: when expert parallelism does not consume 'dp',
    # shard the free FFN dim over it; the compute islands declare the
    # gathered layout, so XLA all-gathers per layer inside the scan.
    sdp = "dp" if ("dp" not in ep and layout.size("dp") > 1
                   and f % layout.size("dp") == 0
                   and not layout.inference_opt) else None
    if one_d:   # Megatron pattern: intermediate split over the model axis
        w1_spec, w2_spec = P(e_spec, None, (co, sdp) if sdp else co), \
            P(e_spec, (co, sdp) if sdp else co, None)
    else:       # cube pattern: contraction split over out_ax
        w1_spec, w2_spec = P(e_spec, co, sdp), P(e_spec, sdp, co)
    p = {
        "w_router": Param((d, E), P(co if not one_d else None, None),
                          dtype=jnp.float32),
        "w1": Param((E, d, f), w1_spec),
        "w2": Param((E, f, d), w2_spec),
    }
    if gated:
        p["w3"] = Param((E, d, f), w1_spec)
    if m.n_shared:
        from .blocks import mlp_params
        p["shared"] = mlp_params(layout, cfg, dirs, d_ff=m.n_shared * f, fsdp=fsdp)
    return p


def moe_apply(layout: Layout, cfg: ModelConfig, dirs: Dirs, x, p,
              decode: bool = False):
    """x: (B, S, H) in block entry layout -> (y, aux_loss)."""
    m = cfg.moe
    E, k = m.n_experts, m.top_k
    ep = ep_axes(layout, dirs, E)
    co = _contract_ax(layout, dirs)
    one_d = layout.strategy == "1d"
    gated = "w3" in p
    act = jax.nn.silu if cfg.act == "silu" else (
        lambda u: jax.nn.gelu(u, approximate=True))

    xspec = act_spec_decode(layout, dirs) if decode else act_spec(layout, dirs)
    e_spec = ep if len(ep) > 1 else (ep[0] if ep else None)
    if one_d:
        wr_spec = P(None, None)
        w1_spec, w2_spec = P(e_spec, None, co), P(e_spec, co, None)
    else:
        wr_spec = P(co, None)
        w1_spec, w2_spec = P(e_spec, co, None), P(e_spec, None, co)
    tok_ax = None if one_d or decode else (dirs.in_ax if layout.strategy == "3d" else "y")
    tok_axes = tuple(a for a in (*layout.batch_axes, *layout.seq_axes,
                                 *((tok_ax,) if tok_ax else ()))
                     if layout.size(a) > 1)

    def body(x, wr, w1, w2, w3):
        b, s, hl = x.shape
        T = b * s
        t = x.reshape(T, hl)
        # ---- router: contraction over the hidden split -> psum over out_ax
        # (the Algorithm-1 reduction role) ----
        logits = jnp.einsum("th,he->te", t.astype(F32), wr)
        if not one_d and layout.size(co) > 1:
            logits = lax.psum(logits, co)
        probs = jax.nn.softmax(logits, axis=-1)
        gates, sel = lax.top_k(probs, k)                       # (T, k)
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

        # ---- dispatch (static capacity) ----
        cap = max(1, int(math.ceil(T * k * m.capacity_factor / E)))
        e_flat = sel.reshape(-1)                               # (T*k,)
        order = jnp.argsort(e_flat, stable=True)
        sorted_e = e_flat[order]
        rank_sorted = (jnp.arange(T * k)
                       - jnp.searchsorted(sorted_e, sorted_e, side="left"))
        keep_sorted = rank_sorted < cap
        slot_sorted = sorted_e * cap + rank_sorted             # (T*k,)
        src_tok = order // k
        buf = jnp.zeros((E * cap, hl), x.dtype)
        buf = buf.at[jnp.where(keep_sorted, slot_sorted, E * cap)].set(
            t[src_tok], mode="drop")
        buf = buf.reshape(E, cap, hl)

        # ---- expert-parallel all-to-all ----
        if ep:
            buf = lax.all_to_all(buf, ep, split_axis=0, concat_axis=1,
                                 tiled=True)                   # (E_loc, cap*n_ep, hl)

        # ---- expert FFN, chunked over the capacity dim: bounds the f32
        # intermediates (and their backward cotangents) to one token chunk ----
        def ffn_chunk(_, buf_c):
            h1 = jnp.einsum("ech,ehf->ecf", buf_c, w1,
                            preferred_element_type=F32).astype(x.dtype)
            h3 = (jnp.einsum("ech,ehf->ecf", buf_c, w3,
                             preferred_element_type=F32).astype(x.dtype)
                  if gated else None)
            if not one_d and layout.size(co) > 1:
                h1 = lax.psum(h1, co)
                if gated:
                    h3 = lax.psum(h3, co)
            h = (act(h1.astype(F32)) * h3.astype(F32)).astype(x.dtype) \
                if gated else act(h1.astype(F32)).astype(x.dtype)
            o = jnp.einsum("ecf,efh->ech", h, w2,
                           preferred_element_type=F32).astype(x.dtype)
            if one_d and layout.size(co) > 1:
                o = lax.psum(o, co)                    # Megatron row-parallel
            return None, o

        e_loc, t_e = buf.shape[0], buf.shape[1]
        tc = t_e
        for cand in (2048, 1024, 512):
            if t_e % cand == 0 and t_e > cand:
                tc = cand
                break
        if tc < t_e:
            bufc = buf.reshape(e_loc, t_e // tc, tc, hl).swapaxes(0, 1)
            _, out = lax.scan(jax.checkpoint(ffn_chunk), None, bufc)
            out = out.swapaxes(0, 1).reshape(e_loc, t_e, hl)
        else:
            _, out = ffn_chunk(None, buf)
        if ep:
            out = lax.all_to_all(out, ep, split_axis=1, concat_axis=0,
                                 tiled=True)                   # (E, cap, hl)
        out = out.reshape(E * cap, hl)

        # ---- combine ----
        rank = jnp.zeros((T * k,), jnp.int32).at[order].set(rank_sorted)
        keep = jnp.zeros((T * k,), bool).at[order].set(keep_sorted)
        slots = jnp.where(keep, e_flat * cap + rank, E * cap)
        vals = jnp.take(out, slots, axis=0, mode="fill", fill_value=0)
        y = jnp.sum(vals.reshape(T, k, hl) * gates[..., None].astype(x.dtype),
                    axis=1).reshape(b, s, hl)

        # ---- aux losses (load balance + router z) ----
        me = jnp.mean(probs, axis=0)                           # (E,)
        ce = jnp.mean(jax.nn.one_hot(sel[:, 0], E, dtype=F32), axis=0)
        if tok_axes:
            me = lax.pmean(me, tok_axes)
            ce = lax.pmean(ce, tok_axes)
        lb = E * jnp.sum(me * ce) * m.router_aux_weight
        z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2) * m.router_z_weight
        if tok_axes:
            z = lax.pmean(z, tok_axes)
        return y, (lb + z).astype(F32)

    w3_arg = p["w3"] if gated else jnp.zeros((1, 1, 1), x.dtype)
    in_specs = (xspec, wr_spec, w1_spec, w2_spec,
                w1_spec if gated else P(None, None, None))
    y, aux = shard_map(body, mesh=layout.mesh, in_specs=in_specs,
                           out_specs=(xspec, P()), check_vma=False)(
        x, p["w_router"], p["w1"], p["w2"], w3_arg)

    if "shared" in p:
        from .blocks import mlp_apply
        y = y + mlp_apply(layout, cfg, dirs, x, p["shared"], decode=decode)
    return y, aux
