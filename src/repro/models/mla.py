"""DeepSeek-V3 Multi-head Latent Attention (MLA), 3-D parallel.

The low-rank structure maps onto the cube as two chained linears
(DESIGN.md §4): the *down* projections use ``matmul3d_noswap`` (contraction
psum over out_ax, tiny replicated latent output), the *up* projections use
``matmul3d_repc`` (replicated contraction, zero-comm scatter) — together one
direction exchange, so MLA + output projection keeps the block's swap count
even, exactly like a standard attention block.

Decode uses the compressed KV cache with absorbed up-projection weights
(score/value computed in the 512-dim latent space), which is what makes the
decode_32k x batch-128 cache fit.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from ..config import ModelConfig
from ..core import ops3d
from ..core.linear3d import plinear, rmsnorm, weight_param, wsc
from ..core.params import Param
from ..core.topology import Dirs, Layout
from .blocks import _gather_axes, _head_axes, apply_rope, attention

F32 = jnp.float32


def _m(cfg: ModelConfig):
    m = cfg.mla
    return m, cfg.n_heads, m.qk_nope_dim, m.qk_rope_dim, m.v_head_dim


def mla_params(layout: Layout, cfg: ModelConfig, dirs: Dirs):
    m, nh, dn, dr, dv = _m(cfg)
    d = cfg.d_model
    if layout.strategy == "3d":
        down = lambda f: Param((d, f), P(dirs.out_ax, None))
        up_cols = (dirs.in_ax if layout.inference_opt
                   else (dirs.in_ax, "x"))
        up = lambda r, f: Param((r, f), P(None, up_cols))
    elif layout.strategy == "2d":
        down = lambda f: Param((d, f), P("z", None))
        up = lambda r, f: Param((r, f), P(None, "z"))
    else:
        down = lambda f: Param((d, f), P(None, None))
        up = lambda r, f: Param((r, f), P(None, "z"))
    return {
        "w_dq": down(m.q_lora_rank),
        "q_ln": Param((m.q_lora_rank,), P(None), init="ones"),
        "w_uq": up(m.q_lora_rank, nh * (dn + dr)),
        "w_dkv": down(m.kv_lora_rank + dr),
        "kv_ln": Param((m.kv_lora_rank,), P(None), init="ones"),
        "w_ukv": up(m.kv_lora_rank, nh * (dn + dv)),
        "w_o": weight_param(layout, dirs.swap(), nh * dv, d, kind="second"),
    }


def _down(layout: Layout, dirs: Dirs, x, w, decode: bool):
    if layout.strategy == "3d":
        if decode:
            return ops3d.matmul3d_decode(layout, dirs.in_ax, dirs.out_ax, x, w,
                                         shard_f=False)
        return ops3d.matmul3d_noswap(layout, dirs.in_ax, dirs.out_ax, x, w)
    # baselines: GSPMD (XLA inserts the contraction all-reduce)
    return jnp.einsum("bsh,hf->bsf", x, w,
                      preferred_element_type=F32).astype(x.dtype)


def _up(layout: Layout, dirs: Dirs, x, w, decode: bool):
    if layout.strategy == "3d":
        if decode:
            return ops3d.matmul3d_repc_decode(layout, dirs.in_ax, dirs.out_ax, x, w)
        return ops3d.matmul3d_repc(layout, dirs.in_ax, dirs.out_ax, x, w)
    return jnp.einsum("bsr,rf->bsf", x, w,
                      preferred_element_type=F32).astype(x.dtype)


def mla_apply(layout: Layout, cfg: ModelConfig, dirs: Dirs, x, p, positions,
              *, decode=False, cache=None, collect_kv=False, page=None):
    """x in block entry layout; returns (out, new_cache).

    ``collect_kv`` (prefill only): additionally return the compressed
    latent stream ``(c_kv, k_rope)`` — post-norm / post-rope, exactly the
    values ``_mla_decode`` caches — so the serving engine can hand a whole
    prefilled prompt off to the paged decode cache in one step."""
    m, nh, dn, dr, dv = _m(cfg)
    B, S = x.shape[0], x.shape[1]
    hx = layout.size(_head_axes(layout, dirs)[1])
    nh_loc = nh // hx

    # ---- q path ----
    qc = _down(layout, dirs, x, p["w_dq"], decode)            # (B,S,q_lora) repl.
    qc = rmsnorm(qc, p["q_ln"])
    q = _up(layout, dirs, qc, p["w_uq"], decode)              # (B,S,nh(dn+dr)/si)
    q = q.reshape(B, S, -1, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_base)

    # ---- kv path ----
    ckr = _down(layout, dirs, x, p["w_dkv"], decode)          # (B,S,kv_lora+dr)
    c_kv, k_rope = ckr[..., :m.kv_lora_rank], ckr[..., m.kv_lora_rank:]
    c_kv = rmsnorm(c_kv, p["kv_ln"])
    k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_base)[:, :, 0]

    if decode:
        pvec = positions[:, 0] if positions.ndim > 1 else positions
        if page is not None:
            out, new_cache = _mla_decode_paged(layout, cfg, dirs, q_nope,
                                               q_rope, c_kv, k_rope,
                                               p["w_ukv"], cache, pvec, page)
        else:
            out, new_cache = _mla_decode(layout, cfg, dirs, q_nope, q_rope,
                                         c_kv, k_rope, p["w_ukv"], cache,
                                         pvec)
        out = out.reshape(B, S, -1)
    else:
        kv = _up(layout, dirs, c_kv, p["w_ukv"], decode)      # (B,S,nh(dn+dv)/si)
        kv = kv.reshape(B, S, -1, dn + dv)
        k_nope, v = kv[..., :dn], kv[..., dn:]
        # k_rope: noswap output has seq split over in_ax; attention layout
        # wants out_ax — reshard (tiny: dr floats per token), then broadcast
        seq_ax = _head_axes(layout, dirs)[0]
        if layout.strategy == "3d":
            kr_spec = P(layout.batch_spec(),
                        ops3d._seq_spec(layout, seq_ax), None)
            k_rope = wsc(k_rope, layout.sharding(kr_spec))
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                      (*k_nope.shape[:3], dr))], axis=-1)
        q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
        # materialized path: n_kv == n_heads (every head has its own k/v)
        out = attention(layout, _with_full_kv(cfg), dirs, q_full, k, v,
                        causal=True)
        out = out.reshape(B, S, -1)
        new_cache = (c_kv, k_rope) if collect_kv else None

    y, _ = plinear(layout, dirs.swap(), out, p["w_o"], kind="second",
                   decode=decode)
    return y, new_cache


def _with_full_kv(cfg: ModelConfig):
    import dataclasses
    return dataclasses.replace(cfg, n_kv=cfg.n_heads)


def mla_cache_init(layout: Layout, cfg: ModelConfig, dirs: Dirs, batch: int,
                   length: int):
    m = cfg.mla
    seq_ax, _ = _head_axes(layout, dirs)
    gax = _gather_axes(layout, seq_ax)
    bs = layout.batch_spec()
    return {
        "c_kv": Param((batch, length, m.kv_lora_rank), P(bs, gax or None, None),
                      init="zeros"),
        "k_rope": Param((batch, length, m.qk_rope_dim), P(bs, gax or None, None),
                        init="zeros"),
        "pos": Param((batch, length), P(bs, gax or None), dtype=jnp.int32,
                     init="zeros"),
    }


def _mla_decode(layout: Layout, cfg: ModelConfig, dirs: Dirs, q_nope, q_rope,
                ckv_new, kr_new, w_ukv, cache, pos):
    """Absorbed-weight decode over the compressed cache."""
    m, nh, dn, dr, dv = _m(cfg)
    seq_ax, head_ax = _head_axes(layout, dirs)
    gax = _gather_axes(layout, seq_ax)
    nshards = math.prod(layout.size(a) for a in gax) if gax else 1
    hx = layout.size(head_ax)
    nh_loc = nh // hx
    scale = 1.0 / math.sqrt(dn + dr)
    bs = layout.batch_spec()

    qspec = P(bs, None, head_ax, None)
    lat_spec = P(bs, None, None)
    cspec = P(bs, gax or None, None)
    pspec = P(bs, gax or None)
    if layout.strategy == "3d":
        w_spec = P(None, head_ax if layout.inference_opt
                   else (head_ax, "x"))
    elif layout.strategy == "2d":
        w_spec = P(None, "z")
    else:
        w_spec = P(None, "z")

    def body(qn, qr, ckv_new, kr_new, cc, ckr, cpos, pos, w_ukv):
        b, l_loc = cpos.shape
        shard = 0
        for a in gax:
            shard = shard * layout.size(a) + lax.axis_index(a)
        L = l_loc * nshards
        slot = pos % L
        local = slot - shard * l_loc
        own = (local >= 0) & (local < l_loc)
        li = jnp.clip(local, 0, l_loc - 1)
        rows = jnp.arange(b)
        cc = cc.at[rows, li].set(jnp.where(own[:, None], ckv_new[:, 0], cc[rows, li]))
        ckr = ckr.at[rows, li].set(jnp.where(own[:, None], kr_new[:, 0], ckr[rows, li]))
        cpos = cpos.at[rows, li].set(jnp.where(own, pos, cpos[rows, li]))

        if layout.strategy == "3d" and layout.size("x") > 1 \
                and not layout.inference_opt:
            w_ukv = lax.all_gather(w_ukv, "x", axis=1, tiled=True)
        wk = w_ukv.reshape(m.kv_lora_rank, -1, dn + dv)
        w_uk, w_uv = wk[..., :dn], wk[..., dn:]               # (R, nh_loc, dn/dv)

        qc = jnp.einsum("bhd,rhd->bhr", qn[:, 0].astype(F32),
                        w_uk.astype(F32))                     # (b, nh_loc, R)
        s = jnp.einsum("bhr,blr->bhl", qc, cc.astype(F32)) + \
            jnp.einsum("bhd,bld->bhl", qr[:, 0].astype(F32), ckr.astype(F32))
        s = s * scale
        valid = (cpos >= 0) & (cpos <= pos[:, None])
        # slots never written have pos==0 from init; track via slot index vs pos
        written = jnp.arange(l_loc)[None, :] + shard * l_loc <= pos[:, None]
        s = jnp.where((valid & written)[:, None, :], s, -1e30)
        m_loc = jnp.max(s, axis=-1)
        mx = lax.pmax(m_loc, gax) if gax else m_loc
        pr = jnp.exp(s - mx[..., None])
        l_sum = jnp.sum(pr, axis=-1)
        oc = jnp.einsum("bhl,blr->bhr", pr, cc.astype(F32))
        if gax:
            l_sum = lax.psum(l_sum, gax)
            oc = lax.psum(oc, gax)
        oc = oc / jnp.maximum(l_sum, 1e-30)[..., None]
        o = jnp.einsum("bhr,rhd->bhd", oc, w_uv.astype(F32))  # (b, nh_loc, dv)
        return o[:, None].astype(qn.dtype), cc, ckr, cpos

    out, cc, ckr, cpos = shard_map(
        body, mesh=layout.mesh,
        in_specs=(qspec, qspec, lat_spec, lat_spec, cspec, cspec, pspec,
                  P(bs), w_spec),
        out_specs=(qspec, cspec, cspec, pspec),
        check_vma=False)(q_nope, q_rope, ckv_new, kr_new,
                         cache["c_kv"], cache["k_rope"], cache["pos"], pos,
                         w_ukv)
    return out, {"c_kv": cc, "k_rope": ckr, "pos": cpos}


def _mla_decode_paged(layout: Layout, cfg: ModelConfig, dirs: Dirs, q_nope,
                      q_rope, ckv_new, kr_new, w_ukv, cache, pos, page):
    """Absorbed-weight decode straight against the paged latent pool.

    The latent cache is exactly MQA with one kv head of dim (R + dr):
    K = concat(c_kv, k_rope) features, V = c_kv, q = concat(absorbed
    q_latent, q_rope) — so the same paged flash-decode kernel serves MLA,
    followed by the w_uv down-projection.  The pool's pos leaf starts at -1
    (unlike the contiguous cache), so the kernel's position mask alone
    covers unwritten, null-block and recycled entries.

    The pool is READ-ONLY here, exactly as in the dense path
    (blocks.attention_decode_paged): the kernel attends the written past
    through (table-column-sharded) residuals and the current latent token
    is folded into the softmax afterwards; the engine applies every
    layer's new entries in one batched scatter (kvcache.scatter_step).

    cache: this layer's pool slice {"c_kv": (phys, R), "k_rope": (phys, dr),
    "pos": (phys,)}; pos: (B,) int32.
    Returns (out, {"c_kv": (B, R), "k_rope": (B, dr), "pos": (B,)}).
    """
    from ..kernels.paged_decode import live_blocks, paged_flash_decode

    m, nh, dn, dr, dv = _m(cfg)
    seq_ax, head_ax = _head_axes(layout, dirs)
    gax = _gather_axes(layout, seq_ax)
    nshards = math.prod(layout.size(a) for a in gax) if gax else 1
    hx = layout.size(head_ax)
    scale = 1.0 / math.sqrt(dn + dr)
    bs = layout.batch_spec()
    blk = page.block
    lat_pool = P(None, None)

    # distribute the latent-pool attention by sharding table columns over
    # the cache-shard axes (null-block padding is masked anyway)
    tbl = page.tables
    if nshards > 1 and tbl.shape[1] % nshards:
        tbl = jnp.pad(tbl, ((0, 0), (0, nshards - tbl.shape[1] % nshards)))
    nb_loc = tbl.shape[1] // nshards

    qspec = P(bs, None, head_ax, None)
    nspec = P(bs, None, None)
    if layout.strategy == "3d":
        w_spec = P(None, head_ax if layout.inference_opt else (head_ax, "x"))
    else:
        w_spec = P(None, "z")

    def body(qn, qr, cn, krn, cc, ckr, cpos, tables, pos, active, w_ukv):
        if layout.strategy == "3d" and layout.size("x") > 1 \
                and not layout.inference_opt:
            w_ukv = lax.all_gather(w_ukv, "x", axis=1, tiled=True)
        wk = w_ukv.reshape(m.kv_lora_rank, -1, dn + dv)
        w_uk, w_uv = wk[..., :dn], wk[..., dn:]               # (R, nh_loc, dn/dv)
        qc = jnp.einsum("bhd,rhd->bhr", qn[:, 0].astype(F32),
                        w_uk.astype(F32))                     # (b, nh_loc, R)
        q_cat = jnp.concatenate([qc, qr[:, 0].astype(F32)], axis=-1)
        k_pool = jnp.concatenate([cc, ckr], axis=-1)[:, None, :]
        v_pool = cc[:, None, :]
        shard = 0
        for a in gax:
            shard = shard * layout.size(a) + lax.axis_index(a)
        tloc = (tables if nshards == 1 else
                lax.dynamic_slice_in_dim(tables, shard * nb_loc, nb_loc,
                                         axis=1))
        live = live_blocks(pos, active, block=blk, nb=nb_loc,
                           start=shard * nb_loc)
        acc, mx, ls = paged_flash_decode(q_cat, k_pool, v_pool, cpos,
                                         tloc, pos, block=blk, live=live,
                                         scale=scale, return_residuals=True)
        if nshards > 1:
            mg = lax.pmax(mx, gax)
            w = jnp.exp(mx - mg)
            acc = lax.psum(acc * w[..., None], gax)
            ls = lax.psum(ls * w, gax)
            mx = mg
        # fold the current latent token (always valid: age 0)
        kcur = jnp.concatenate([cn[:, 0], krn[:, 0]], axis=-1).astype(F32)
        s0 = jnp.einsum("bhr,br->bh", q_cat, kcur) * scale    # (b, nh_loc)
        m2 = jnp.maximum(mx, s0)
        wp, wc = jnp.exp(mx - m2), jnp.exp(s0 - m2)
        o = (acc * wp[..., None]
             + cn[:, 0, None, :].astype(F32) * wc[..., None])
        oc = o / jnp.maximum(ls * wp + wc, 1e-30)[..., None]  # (b, nh_loc, R)
        o = jnp.einsum("bhr,rhd->bhd", oc.astype(F32), w_uv.astype(F32))
        return o[:, None].astype(qn.dtype)

    out = shard_map(
        body, mesh=layout.mesh,
        in_specs=(qspec, qspec, nspec, nspec, lat_pool, lat_pool, P(None),
                  P(bs, None), P(bs), P(bs), w_spec),
        out_specs=qspec, check_vma=False)(
        q_nope, q_rope, ckv_new, kr_new, cache["c_kv"], cache["k_rope"],
        cache["pos"], tbl, pos, page.active, w_ukv)
    return out, {"c_kv": ckv_new[:, 0], "k_rope": kr_new[:, 0], "pos": pos}
