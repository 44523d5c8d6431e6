"""Attention + MLP blocks, parallel over the 3-D cube (or 1-D/2-D baselines).

Layouts inside a block (3-D strategy, entry dirs (in_ax=y, out_ax=z)):

    x          (B, S, H)      P(batch, y, z)
    q/k/v      (B, S, n, d)   P(batch, z, y, None)   after the qkv linear
    attn out   (B, S, n, d)   P(batch, z, y, None)   island gathers k/v over z
    out proj                  back to P(batch, y, z)

Every block contains an even number of 3-D linears, so the direction state is
restored at block exit (paper §3.2).
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from ..config import ModelConfig
from ..core import ops3d
from ..core.linear3d import (act_spec, act_spec_decode, bias_param, norm_param,
                             plinear, rmsnorm, layernorm, weight_param, wsc)
from ..core.params import Param
from ..core.topology import Dirs, Layout

F32 = jnp.float32
NEG_INF = -1e30


def _scoped(name: str):
    """Run the decorated sub-block under ``jax.named_scope(name)``:
    metadata only, so that each op of the compiled program names the
    sub-block (``attention``, ``mlp``, ``norm``) it came from."""
    def deco(fn):
        @functools.wraps(fn)
        def inner(*args, **kw):
            with jax.named_scope(name):
                return fn(*args, **kw)
        return inner
    return deco


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------
def rope_freqs(dh: int, base: float):
    return base ** (-jnp.arange(0, dh, 2, dtype=F32) / dh)


def apply_rope(x, positions, base: float):
    """x: (..., S, n, d); positions broadcastable to (..., S)."""
    dh = x.shape[-1]
    freqs = rope_freqs(dh, base)                        # (d/2,)
    ang = positions[..., None].astype(F32) * freqs      # (..., S, d/2)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(F32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Chunked online-softmax attention (pure jnp — also the Pallas kernel oracle)
# ---------------------------------------------------------------------------
def flash_attention_jnp(q, k, v, q_pos, k_pos, *, causal=True, window=0,
                        chunk=512, logit_scale=None):
    """q: (b, sq, nq, d), k/v: (b, sk, nkv, d); positions (b, sq) / (sk,).

    Returns (out, (m, l)) — the running max / normalizer are exposed so the
    decode path can combine partial results across cache shards.
    """
    b, sq, nq, d = q.shape
    sk, nkv = k.shape[1], k.shape[2]
    group = nq // nkv
    scale = logit_scale if logit_scale is not None else 1.0 / math.sqrt(d)
    qf = (q.astype(F32) * scale).reshape(b, sq, nkv, group, d)

    chunk = min(chunk, sk)
    while sk % chunk:           # largest divisor of sk not above the target
        chunk -= 1
    n_chunks = sk // chunk
    kc = k.reshape(b, n_chunks, chunk, nkv, k.shape[-1])
    vc = v.reshape(b, n_chunks, chunk, nkv, v.shape[-1])
    kp = k_pos.reshape(n_chunks, chunk)

    def step(carry, xs):
        m, l, o = carry
        kci, vci, kpi = xs
        s = jnp.einsum("bqhgd,bkhd->bqhgk", qf, kci.astype(F32))
        mask = jnp.ones((sq, chunk), bool) if not causal else \
            (q_pos[0][:, None] >= kpi[None, :])
        if causal:
            pass
        valid = kpi[None, :] >= 0
        if causal and window:
            mask = mask & (q_pos[0][:, None] - kpi[None, :] < window)
        mask = mask & valid
        s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        # bf16 probabilities into the PV product (f32 accumulation): halves
        # the dominant backward working set at large head counts
        o_new = o * corr[..., None] + jnp.einsum(
            "bqhgk,bkhd->bqhgd", p.astype(v.dtype), vci,
            preferred_element_type=F32)
        return (m_new, l_new, o_new), None

    dv = v.shape[-1]
    m0 = jnp.full((b, sq, nkv, group), NEG_INF, F32)
    l0 = jnp.zeros((b, sq, nkv, group), F32)
    o0 = jnp.zeros((b, sq, nkv, group, dv), F32)
    # checkpointed: the (sq, chunk) probability tensors are recomputed in the
    # backward pass instead of being stacked across all kv chunks
    (m, l, o), _ = lax.scan(jax.checkpoint(step), (m0, l0, o0),
                            (kc.swapaxes(0, 1), vc.swapaxes(0, 1), kp))
    out = (o / jnp.maximum(l, 1e-30)[..., None]).reshape(b, sq, nq, dv)
    return out.astype(q.dtype), (m, l, o)


# ---------------------------------------------------------------------------
# Attention islands
# ---------------------------------------------------------------------------
def _head_axes(layout: Layout, dirs: Dirs) -> Tuple[Optional[str], Optional[str]]:
    """(seq_ax, head_ax) for the post-qkv activation layout."""
    if layout.strategy == "3d":
        return dirs.out_ax, dirs.in_ax
    if layout.strategy == "2d":
        return "y", "z"
    return None, "z"


def _gather_axes(layout: Layout, seq_ax) -> Tuple[str, ...]:
    axes = tuple(a for a in (*layout.seq_axes, seq_ax)
                 if a is not None and layout.size(a) > 1)
    return axes


def attention(layout: Layout, cfg: ModelConfig, dirs: Dirs, q, k, v,
              *, causal=True, window=0, kv_seq: Optional[int] = None):
    """Training/prefill attention.  q/k/v: (B, S, n, d) in post-qkv layout.
    The island all-gathers k/v along the sequence split (the Algorithm-3
    C = AB^T gather pattern) and runs chunked online-softmax locally."""
    seq_ax, head_ax = _head_axes(layout, dirs)
    hx = layout.size(head_ax)
    kv_sharded = cfg.n_kv % hx == 0 and cfg.n_kv >= hx
    gax = _gather_axes(layout, seq_ax)
    S = q.shape[1] * math.prod(layout.size(a) for a in gax)
    Skv = k.shape[1] * math.prod(layout.size(a) for a in gax)

    qspec = P(layout.batch_spec(), gax or None, head_ax, None)
    kvspec = P(layout.batch_spec(), gax or None, head_ax if kv_sharded else None, None)

    def body(q, k, v):
        sq = q.shape[1]
        if gax:
            k = lax.all_gather(k, gax, axis=1, tiled=True)
            v = lax.all_gather(v, gax, axis=1, tiled=True)
        # global positions of the local q rows
        off = 0
        for a in gax:
            off = off * layout.size(a) + lax.axis_index(a)
        q_pos = off * sq + jnp.arange(sq)
        q_pos = jnp.broadcast_to(q_pos, (q.shape[0], sq))
        k_pos = jnp.arange(k.shape[1])
        if not kv_sharded and hx > 1:
            # kv replicated: slice the q-local head block's kv groups
            pass
        out, _ = flash_attention_jnp(q, k, v, q_pos, k_pos,
                                     causal=causal, window=window)
        return out

    if not kv_sharded and hx > 1:
        # kv heads replicated over head_ax; local q heads q[i0:i0+nloc] use
        # kv head (global_head // group): pass full kv, remap q heads via
        # a per-device head offset handled by gathering kv fully (it already
        # is) and slicing kv to the groups this shard's q heads use.
        group = cfg.n_heads // cfg.n_kv
        nloc = cfg.n_heads // hx

        def body(q, k, v):  # noqa: F811
            sq = q.shape[1]
            if gax:
                k = lax.all_gather(k, gax, axis=1, tiled=True)
                v = lax.all_gather(v, gax, axis=1, tiled=True)
            off = 0
            for a in gax:
                off = off * layout.size(a) + lax.axis_index(a)
            q_pos = off * sq + jnp.arange(sq)
            q_pos = jnp.broadcast_to(q_pos, (q.shape[0], sq))
            k_pos = jnp.arange(k.shape[1])
            hidx = lax.axis_index(head_ax) if head_ax else 0
            kv0 = (hidx * nloc) // group
            nkv_loc = max(1, nloc // group)
            k = lax.dynamic_slice_in_dim(k, kv0, nkv_loc, axis=2)
            v = lax.dynamic_slice_in_dim(v, kv0, nkv_loc, axis=2)
            out, _ = flash_attention_jnp(q, k, v, q_pos, k_pos,
                                         causal=causal, window=window)
            return out

    return shard_map(body, mesh=layout.mesh,
                         in_specs=(qspec, kvspec, kvspec),
                         out_specs=qspec, check_vma=False)(q, k, v)


class CacheSpecs(NamedTuple):
    k: P
    v: P
    pos: P


def cache_specs(layout: Layout, cfg: ModelConfig, dirs: Dirs):
    seq_ax, head_ax = _head_axes(layout, dirs)
    hx = layout.size(head_ax)
    kv_sharded = cfg.n_kv % hx == 0 and cfg.n_kv >= hx
    gax = _gather_axes(layout, seq_ax)
    kv = P(layout.batch_spec(), gax or None, head_ax if kv_sharded else None, None)
    pos = P(layout.batch_spec(), gax or None)
    return CacheSpecs(kv, kv, pos)


def kv_cache_init(layout: Layout, cfg: ModelConfig, dirs: Dirs, batch: int,
                  length: int):
    """Abstract KV cache (length = window size for SWA archs)."""
    sp = cache_specs(layout, cfg, dirs)
    nkv, dh = cfg.n_kv, cfg.head_dim
    return {
        "k": Param((batch, length, nkv, dh), sp.k, init="zeros"),
        "v": Param((batch, length, nkv, dh), sp.v, init="zeros"),
        "pos": Param((batch, length), sp.pos, dtype=jnp.int32, init="neg_ones"),
    }


class PageInfo(NamedTuple):
    """Decode-time paged-cache routing, threaded from the serving engine
    through ``transformer.forward(page=...)`` into the attention blocks via
    the frontend ctx (a constant closure input of the layer scan, never
    tree-mapped, so the static ``block`` int is safe here)."""
    tables: jax.Array          # (B, nb) int32 physical block id per view block
    active: jax.Array          # (B,) bool — inactive lanes write to trash
    block: int                 # static block size


def attention_decode_paged(layout: Layout, cfg: ModelConfig, dirs: Dirs,
                           q, k_new, v_new, cache, pos, page: PageInfo,
                           *, window=0):
    """One-token decode straight against the paged KV pool — the fused
    replacement for gather_view + attention_decode + scatter_decode.

    The pool is READ-ONLY here.  The paged flash-decode kernel streams the
    already-written past through the block table inside an attention
    island; the current token's (k, v) — not yet in the pool — is folded
    into the same online softmax afterwards via the kernel's residuals.
    The layer returns only its new entries; the engine writes every
    layer's entries back in ONE batched scatter (kvcache.scatter_step), so
    the heavyweight pool never flows through the layer scan as an output.

    The pool's physical dim is replicated across the mesh, so the kv
    *work* is distributed by sharding the block-table columns over the
    cache-shard axes (padding with the null block, which is masked anyway)
    and psum-combining the kernel's online-softmax residuals — the same
    combine the contiguous decode path uses for its sequence-sharded
    cache.  Head sharding is handled exactly like the contiguous path.

    Stale-entry safety without write-before-attend: a recycled entry of
    this slot's own table at the current ring position has age >= the ring
    length L, so it is masked — dense rings never wrap (cur < L) and
    windowed rings have L >= window.

    q: (B, 1, nq, d); k_new/v_new: (B, 1, nkv, d); cache: this layer's pool
    slice {"k": (phys, nkv, d), "v": ..., "pos": (phys,)}; pos: (B,) int32.
    Returns (out, {"k": (B, nkv, d), "v": (B, nkv, d), "pos": (B,)}).
    """
    from ..kernels.paged_decode import live_blocks, paged_flash_decode

    # the stacked pool leaves carry ONE sharding (built from the canonical
    # entry orientation), so the island pins itself to that orientation
    # instead of the per-layer alternating dirs: resharding q/out (a few KB)
    # is free, resharding the pool every other layer is not
    seq_ax, head_ax = _head_axes(layout, Dirs("y", "z"))
    hx = layout.size(head_ax)
    kv_sharded = cfg.n_kv % hx == 0 and cfg.n_kv >= hx
    gax = _gather_axes(layout, seq_ax)
    nshards = math.prod(layout.size(a) for a in gax) if gax else 1
    group = cfg.n_heads // cfg.n_kv
    nloc = cfg.n_heads // hx
    blk = page.block
    scale = 1.0 / math.sqrt(q.shape[-1])

    kspec = P(None, head_ax if kv_sharded else None, None)
    pspec = P(None)
    nspec = P(layout.batch_spec(), None, head_ax if kv_sharded else None,
              None)
    qspec = P(layout.batch_spec(), None, head_ax, None)

    # each cache shard attends its own slice of table columns; pad with the
    # null block so the column count divides evenly
    tbl = page.tables
    if nshards > 1 and tbl.shape[1] % nshards:
        tbl = jnp.pad(tbl, ((0, 0),
                            (0, nshards - tbl.shape[1] % nshards)))
    nb_loc = tbl.shape[1] // nshards

    def body(q, kn, vn, ck, cv, cpos, tables, pos, active):
        if not kv_sharded and hx > 1:
            hidx = lax.axis_index(head_ax) if head_ax else 0
            kv0 = (hidx * nloc) // group
            nkv_loc = max(1, nloc // group)
            ck = lax.dynamic_slice_in_dim(ck, kv0, nkv_loc, axis=1)
            cv = lax.dynamic_slice_in_dim(cv, kv0, nkv_loc, axis=1)
            kn = lax.dynamic_slice_in_dim(kn, kv0, nkv_loc, axis=2)
            vn = lax.dynamic_slice_in_dim(vn, kv0, nkv_loc, axis=2)
        shard = 0
        for a in gax:
            shard = shard * layout.size(a) + lax.axis_index(a)
        tloc = (tables if nshards == 1 else
                lax.dynamic_slice_in_dim(tables, shard * nb_loc, nb_loc,
                                         axis=1))
        live = live_blocks(pos, active, block=blk, nb=nb_loc,
                           start=shard * nb_loc)
        acc, m, l = paged_flash_decode(q[:, 0], ck, cv, cpos, tloc, pos,
                                       block=blk, live=live, window=window,
                                       return_residuals=True)
        if nshards > 1:
            mg = lax.pmax(m, gax)
            w = jnp.exp(m - mg)
            acc = lax.psum(acc * w[..., None], gax)
            l = lax.psum(l * w, gax)
            m = mg
        # fold the current token (always valid: age 0) into the softmax
        B, hloc = kn.shape[0], ck.shape[1]
        g = q.shape[2] // hloc
        qf = q[:, 0].astype(jnp.float32).reshape(B, hloc, g, -1)
        s0 = jnp.einsum("bhgd,bhd->bhg", qf,
                        kn[:, 0].astype(jnp.float32)) * scale
        s0 = s0.reshape(B, -1)
        m2 = jnp.maximum(m, s0)
        wp, wc = jnp.exp(m - m2), jnp.exp(s0 - m2)
        vb = jnp.broadcast_to(vn[:, 0, :, None].astype(jnp.float32),
                              (B, hloc, g, vn.shape[-1])).reshape(
                                  B, q.shape[2], -1)
        o = acc * wp[..., None] + vb * wc[..., None]
        ls = l * wp + wc
        out = o / jnp.maximum(ls, 1e-30)[..., None]
        return out[:, None].astype(q.dtype)

    out = shard_map(body, mesh=layout.mesh,
                    in_specs=(qspec, nspec, nspec, kspec, kspec, pspec,
                              P(layout.batch_spec(), None),
                              P(layout.batch_spec()),
                              P(layout.batch_spec())),
                    out_specs=qspec, check_vma=False)(
        q, k_new, v_new, cache["k"], cache["v"], cache["pos"], tbl, pos,
        page.active)
    return out, {"k": k_new[:, 0], "v": v_new[:, 0], "pos": pos}


def attention_extend(layout: Layout, cfg: ModelConfig, dirs: Dirs,
                     q, k_new, v_new, cache, positions, *, window=0):
    """Multi-token continuation attention: ``S`` fresh tokens per row at
    per-row position offsets attend to the already-written cache entries
    (a gathered per-slot view) plus causally to each other.  One entry
    powers both serving fast paths — prefix-hit tail prefill (attend the
    shared-prefix kv without recomputing it) and speculative verification
    (score gamma drafted tokens in one call) — see ``transformer.extend``.

    q/k_new/v_new: (B, S, n, d) rope'd at ``positions`` (B, S) int32
    (-1 marks padding rows — masked as both queries and keys).  ``cache``:
    {"k": (B, L, nkv, d), "v": ..., "pos": (B, L)} — entries with
    cpos < q_pos are attended (strictly less: a re-written boundary entry is
    counted once, on the self side), everything else (invalid, stale-future)
    is masked.  Unlike the decode paths nothing is written here; the engine
    scatters the returned per-layer (k, v) into the pool itself.

    Sharding: q keeps the post-qkv island layout (local sequence chunk per
    device); k_new/v_new and positions are all-gathered over the sequence
    axes like training attention; the cache view is small (one slot's
    blocks) and replicated inside the island, so the full softmax is
    computed locally and no cross-shard combine is needed.
    """
    seq_ax, head_ax = _head_axes(layout, dirs)
    hx = layout.size(head_ax)
    kv_sharded = cfg.n_kv % hx == 0 and cfg.n_kv >= hx
    gax = _gather_axes(layout, seq_ax)
    group = cfg.n_heads // cfg.n_kv
    nloc = cfg.n_heads // hx

    qspec = P(layout.batch_spec(), gax or None, head_ax, None)
    nkvspec = P(layout.batch_spec(), gax or None,
                head_ax if kv_sharded else None, None)
    cspec = P(layout.batch_spec(), None, head_ax if kv_sharded else None,
              None)
    pspec = P(layout.batch_spec(), gax or None)
    cpspec = P(layout.batch_spec(), None)

    def body(q, kn, vn, pos, ck, cv, cpos):
        b, sq, _, d = q.shape
        qpos = pos
        if gax:
            kn = lax.all_gather(kn, gax, axis=1, tiled=True)
            vn = lax.all_gather(vn, gax, axis=1, tiled=True)
            kpos = lax.all_gather(pos, gax, axis=1, tiled=True)
        else:
            kpos = pos
        if not kv_sharded and hx > 1:
            hidx = lax.axis_index(head_ax) if head_ax else 0
            kv0 = (hidx * nloc) // group
            nkv_loc = max(1, nloc // group)
            kn = lax.dynamic_slice_in_dim(kn, kv0, nkv_loc, axis=2)
            vn = lax.dynamic_slice_in_dim(vn, kv0, nkv_loc, axis=2)
            ck = lax.dynamic_slice_in_dim(ck, kv0, nkv_loc, axis=2)
            cv = lax.dynamic_slice_in_dim(cv, kv0, nkv_loc, axis=2)
        nkv_l = ck.shape[2]
        scale = 1.0 / math.sqrt(d)
        qf = (q.astype(F32) * scale).reshape(b, sq, nkv_l, nloc // nkv_l, d)
        ka = jnp.concatenate([ck.astype(F32), kn.astype(F32)], axis=1)
        va = jnp.concatenate([cv.astype(F32), vn.astype(F32)], axis=1)
        s = jnp.einsum("bqhgd,bkhd->bqhgk", qf, ka)
        # cache entries are valid only strictly before the row's FIRST
        # fresh position (qpos[:, 0]): anything at or past it is stale —
        # e.g. kv a previous speculative verify wrote then rejected — and
        # the fresh tokens themselves arrive via the self path below
        first = qpos[:, :1]
        mc = ((cpos >= 0)[:, None, :]
              & (cpos[:, None, :] < first[:, :, None])
              & (qpos >= 0)[:, :, None])
        ms = ((kpos >= 0)[:, None, :]
              & (kpos[:, None, :] <= qpos[:, :, None])
              & (qpos >= 0)[:, :, None])
        if window:
            mc = mc & (qpos[:, :, None] - cpos[:, None, :] < window)
            ms = ms & (qpos[:, :, None] - kpos[:, None, :] < window)
        mask = jnp.concatenate([mc, ms], axis=2)
        s = jnp.where(mask[:, :, None, None, :], s, NEG_INF)
        m = jnp.max(s, axis=-1)
        p = jnp.exp(s - m[..., None])
        l_s = jnp.sum(p, axis=-1)
        o = jnp.einsum("bqhgk,bkhd->bqhgd", p, va)
        out = (o / jnp.maximum(l_s, 1e-30)[..., None]).reshape(b, sq, nloc, d)
        return out.astype(q.dtype)

    return shard_map(body, mesh=layout.mesh,
                     in_specs=(qspec, nkvspec, nkvspec, pspec,
                               cspec, cspec, cpspec),
                     out_specs=qspec, check_vma=False)(
        q, k_new, v_new, positions, cache["k"], cache["v"], cache["pos"])


def attention_decode(layout: Layout, cfg: ModelConfig, dirs: Dirs,
                     q, k_new, v_new, cache: KVCache, pos, *, window=0):
    """One-token decode: write (k_new, v_new) at ``pos`` into the (possibly
    sequence-sharded) cache, then flash-decoding with a psum-combined
    softmax across cache shards.

    q: (B, 1, nq, d); k_new/v_new: (B, 1, nkv, d); pos: (B,) int32.
    """
    seq_ax, head_ax = _head_axes(layout, dirs)
    hx = layout.size(head_ax)
    kv_sharded = cfg.n_kv % hx == 0 and cfg.n_kv >= hx
    gax = _gather_axes(layout, seq_ax)
    nshards = math.prod(layout.size(a) for a in gax) if gax else 1
    group = cfg.n_heads // cfg.n_kv
    nloc = cfg.n_heads // hx

    qspec = P(layout.batch_spec(), None, head_ax, None)
    nkvspec = P(layout.batch_spec(), None, head_ax if kv_sharded else None, None)
    cspec = cache_specs(layout, cfg, dirs)

    def body(q, k_new, v_new, ck, cv, cpos, pos):
        b, l_loc = cpos.shape
        shard = 0
        for a in gax:
            shard = shard * layout.size(a) + lax.axis_index(a)
        # ring-buffer write index (full cache: slot == pos since L == seq_len)
        L = l_loc * nshards
        slot = pos % L
        local = slot - shard * l_loc
        own = (local >= 0) & (local < l_loc)
        li = jnp.clip(local, 0, l_loc - 1)
        rows = jnp.arange(b)
        upd = lambda c, n: c.at[rows, li].set(
            jnp.where(own[:, None, None], n[:, 0], c[rows, li]))
        ck, cv = upd(ck, k_new), upd(cv, v_new)
        cpos = cpos.at[rows, li].set(jnp.where(own, pos, cpos[rows, li]))

        if not kv_sharded and hx > 1:
            hidx = lax.axis_index(head_ax) if head_ax else 0
            kv0 = (hidx * nloc) // group
            nkv_loc = max(1, nloc // group)
            ck = lax.dynamic_slice_in_dim(ck, kv0, nkv_loc, axis=2)
            cv = lax.dynamic_slice_in_dim(cv, kv0, nkv_loc, axis=2)

        # local partial attention over this cache shard
        kp = jnp.where((cpos >= 0) & (cpos <= pos[:, None]), cpos, -1)
        if window:
            kp = jnp.where(pos[:, None] - kp < window, kp, -1)
        # flash over local shard; positions are per-batch here, so mask by
        # feeding q_pos per batch row (flash uses q_pos[0]; do mask manually)
        d = q.shape[-1]
        scale = 1.0 / math.sqrt(d)
        nkv_l = ck.shape[2]
        qf = (q.astype(F32) * scale).reshape(b, nkv_l, nloc // nkv_l, d)
        s = jnp.einsum("bhgd,bkhd->bhgk", qf, ck.astype(F32))
        s = jnp.where((kp >= 0)[:, None, None, :], s, NEG_INF)
        m_loc = jnp.max(s, axis=-1)
        if gax:
            m = lax.pmax(m_loc, gax)
        else:
            m = m_loc
        p = jnp.exp(s - m[..., None])
        l_loc_sum = jnp.sum(p, axis=-1)
        o_loc = jnp.einsum("bhgk,bkhd->bhgd", p, cv.astype(F32))
        if gax:
            l_sum = lax.psum(l_loc_sum, gax)
            o = lax.psum(o_loc, gax)
        else:
            l_sum, o = l_loc_sum, o_loc
        out = (o / jnp.maximum(l_sum, 1e-30)[..., None]).reshape(b, 1, nloc, d)
        return out.astype(q.dtype), ck if kv_sharded or hx == 1 else None, cv if kv_sharded or hx == 1 else None, cpos

    # NOTE: when kv is replicated over head_ax we sliced the cache inside the
    # body, so the updated cache must be recomputed outside; to keep one code
    # path we update the cache at the GSPMD level instead for that case.
    if kv_sharded or hx == 1:
        def body2(q, k_new, v_new, ck, cv, cpos, pos):
            out, ck2, cv2, cpos2 = body(q, k_new, v_new, ck, cv, cpos, pos)
            return out, ck2, cv2, cpos2
        out, ck, cv, cpos = shard_map(
            body2, mesh=layout.mesh,
            in_specs=(qspec, nkvspec, nkvspec, cspec.k, cspec.v, cspec.pos,
                      P(layout.batch_spec())),
            out_specs=(qspec, cspec.k, cspec.v, cspec.pos),
            check_vma=False)(q, k_new, v_new, cache["k"], cache["v"],
                             cache["pos"], pos)
        return out, {"k": ck, "v": cv, "pos": cpos}

    # kv replicated path: update cache with GSPMD ops, attend in an island
    L = cache["pos"].shape[1]
    slot = pos % L
    rows = jnp.arange(q.shape[0])
    ck = cache["k"].at[rows, slot].set(k_new[:, 0])
    cv = cache["v"].at[rows, slot].set(v_new[:, 0])
    cpos = cache["pos"].at[rows, slot].set(pos)
    ck = wsc(ck, layout.sharding(cspec.k))
    cv = wsc(cv, layout.sharding(cspec.v))
    cpos = wsc(cpos, layout.sharding(cspec.pos))

    def body4(q, ck, cv, cpos, pos):
        b, l_loc = cpos.shape
        hidx = lax.axis_index(head_ax) if head_ax else 0
        kv0 = (hidx * nloc) // group
        nkv_loc = max(1, nloc // group)
        ck = lax.dynamic_slice_in_dim(ck, kv0, nkv_loc, axis=2)
        cv = lax.dynamic_slice_in_dim(cv, kv0, nkv_loc, axis=2)
        kp = jnp.where((cpos >= 0) & (cpos <= pos[:, None]), cpos, -1)
        if window:
            kp = jnp.where(pos[:, None] - kp < window, kp, -1)
        d = q.shape[-1]
        scale = 1.0 / math.sqrt(d)
        qf = (q.astype(F32) * scale).reshape(b, nkv_loc, nloc // nkv_loc, d)
        s = jnp.einsum("bhgd,bkhd->bhgk", qf, ck.astype(F32))
        s = jnp.where((kp >= 0)[:, None, None, :], s, NEG_INF)
        m_loc = jnp.max(s, axis=-1)
        m = lax.pmax(m_loc, gax) if gax else m_loc
        p = jnp.exp(s - m[..., None])
        l_s = jnp.sum(p, axis=-1)
        o = jnp.einsum("bhgk,bkhd->bhgd", p, cv.astype(F32))
        if gax:
            l_s, o = lax.psum(l_s, gax), lax.psum(o, gax)
        return (o / jnp.maximum(l_s, 1e-30)[..., None]).reshape(
            b, 1, nloc, d).astype(q.dtype)

    out = shard_map(body4, mesh=layout.mesh,
                        in_specs=(qspec, cspec.k, cspec.v, cspec.pos,
                                  P(layout.batch_spec())),
                        out_specs=qspec, check_vma=False)(q, ck, cv, cpos, pos)
    return out, {"k": ck, "v": cv, "pos": cpos}


# ---------------------------------------------------------------------------
# Dense attention + MLP block parameters and application
# ---------------------------------------------------------------------------
def _act_fn(name: str):
    return {"silu": jax.nn.silu, "gelu": lambda x: jax.nn.gelu(x, approximate=True),
            "gelu_mlp": lambda x: jax.nn.gelu(x, approximate=True)}[name]


def attn_params(layout: Layout, cfg: ModelConfig, dirs: Dirs, fsdp=False):
    d, nh, nkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv, cfg.head_dim
    hx = layout.size(_head_axes(layout, dirs)[1])
    kv_sf = nkv % hx == 0 and nkv >= hx
    p = {
        "wq": weight_param(layout, dirs, d, nh * dh, kind="first", fsdp=fsdp),
        "wk": weight_param(layout, dirs, d, nkv * dh, kind="first", shard_f=kv_sf, fsdp=fsdp and kv_sf),
        "wv": weight_param(layout, dirs, d, nkv * dh, kind="first", shard_f=kv_sf, fsdp=fsdp and kv_sf),
        "wo": weight_param(layout, dirs.swap(), nh * dh, d, kind="second", fsdp=fsdp),
    }
    if cfg.qk_norm:
        p["q_norm"] = Param((dh,), P(None), init="ones")
        p["k_norm"] = Param((dh,), P(None), init="ones")
    return p


def mlp_params(layout: Layout, cfg: ModelConfig, dirs: Dirs, d_ff=None, fsdp=False):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    p = {"w_up": weight_param(layout, dirs, d, f, kind="first", fsdp=fsdp),
         "w_down": weight_param(layout, dirs.swap(), f, d, kind="second", fsdp=fsdp)}
    if cfg.act in ("silu", "gelu"):
        p["w_gate"] = weight_param(layout, dirs, d, f, kind="first", fsdp=fsdp)
    return p


@_scoped("attention")
def attn_apply(layout: Layout, cfg: ModelConfig, dirs: Dirs, x, p, positions,
               *, causal=True, window=0, decode=False, cache=None,
               kv_override=None, return_kv=False, page=None):
    """Self (or cross) attention sub-block. Returns (out, new_cache)."""
    d, nh, nkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv, cfg.head_dim
    hx = layout.size(_head_axes(layout, dirs)[1])
    kv_sf = nkv % hx == 0 and nkv >= hx
    B, S = x.shape[0], x.shape[1]

    q, d2 = plinear(layout, dirs, x, p["wq"], kind="first", decode=decode)
    q = q.reshape(B, S, -1, dh)
    if kv_override is None:
        k, _ = plinear(layout, dirs, x, p["wk"], kind="first", shard_f=kv_sf,
                       decode=decode)
        v, _ = plinear(layout, dirs, x, p["wv"], kind="first", shard_f=kv_sf,
                       decode=decode)
        k = k.reshape(B, S, -1, dh)
        v = v.reshape(B, S, -1, dh)
    else:
        k, v = kv_override

    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"])
        if kv_override is None:
            k = rmsnorm(k, p["k_norm"])
    if cfg.rope_base and kv_override is None:
        q = apply_rope(q, positions, cfg.rope_base)
        k = apply_rope(k, positions, cfg.rope_base)
    elif cfg.rope_base:
        q = apply_rope(q, positions, cfg.rope_base)

    new_cache = None
    if decode:
        if kv_override is None:
            pvec = positions[:, 0] if positions.ndim > 1 else positions
            if page is not None:
                out, new_cache = attention_decode_paged(
                    layout, cfg, dirs, q, k, v, cache, pvec, page,
                    window=window)
            else:
                out, new_cache = attention_decode(layout, cfg, dirs, q, k, v,
                                                  cache, pvec, window=window)
        else:
            # cross-attention decode: static kv (encoder states), full attn
            out = _cross_decode(layout, cfg, dirs, q, k, v)
    elif cache is not None and kv_override is None:
        # extend: S fresh tokens continuing past a gathered cache view —
        # the serving fast path for prefix-hit tails and speculative verify
        out = attention_extend(layout, cfg, dirs, q, k, v, cache, positions,
                               window=window)
        if return_kv:
            new_cache = (k, v)
    else:
        out = attention(layout, cfg, dirs, q, k, v, causal=causal, window=window)
        if return_kv:
            new_cache = (k, v)
    out = out.reshape(B, S, -1)
    y, _ = plinear(layout, d2, out, p["wo"], kind="second", decode=decode)
    return y, new_cache


def _cross_decode(layout, cfg, dirs, q, k, v):
    """Decode-time cross attention: q (B,1,n,d) vs static encoder kv."""
    seq_ax, head_ax = _head_axes(layout, dirs)
    hx = layout.size(head_ax)
    kv_sharded = cfg.n_kv % hx == 0 and cfg.n_kv >= hx
    gax = _gather_axes(layout, seq_ax)
    qspec = P(layout.batch_spec(), None, head_ax, None)
    kvspec = P(layout.batch_spec(), gax or None,
               head_ax if kv_sharded else None, None)

    def body(q, k, v):
        b = q.shape[0]
        d = q.shape[-1]
        nkv_l = k.shape[2]
        nloc = q.shape[2]
        scale = 1.0 / math.sqrt(d)
        qf = (q.astype(F32) * scale).reshape(b, nkv_l, nloc // nkv_l, d)
        s = jnp.einsum("bhgd,bkhd->bhgk", qf, k.astype(F32))
        m_loc = jnp.max(s, axis=-1)
        m = lax.pmax(m_loc, gax) if gax else m_loc
        p = jnp.exp(s - m[..., None])
        l_s = jnp.sum(p, axis=-1)
        o = jnp.einsum("bhgk,bkhd->bhgd", p, v.astype(F32))
        if gax:
            l_s, o = lax.psum(l_s, gax), lax.psum(o, gax)
        return (o / jnp.maximum(l_s, 1e-30)[..., None]).reshape(
            b, 1, nloc, d).astype(q.dtype)

    return shard_map(body, mesh=layout.mesh, in_specs=(qspec, kvspec, kvspec),
                         out_specs=qspec, check_vma=False)(q, k, v)


@_scoped("mlp")
def mlp_apply(layout: Layout, cfg: ModelConfig, dirs: Dirs, x, p, decode=False):
    act = _act_fn(cfg.act)
    up, d2 = plinear(layout, dirs, x, p["w_up"], kind="first", decode=decode)
    if "w_gate" in p:
        gate, _ = plinear(layout, dirs, x, p["w_gate"], kind="first", decode=decode)
        h = act(gate.astype(F32)) * up.astype(F32)
    else:
        h = act(up.astype(F32))
    h = h.astype(x.dtype)
    y, _ = plinear(layout, d2, h, p["w_down"], kind="second", decode=decode)
    return y


def make_norm_params(layout: Layout, cfg: ModelConfig, dirs: Dirs, d=None):
    d = d or cfg.d_model
    p = {"g": norm_param(layout, dirs, d)}
    if cfg.norm == "layernorm":
        p["b"] = norm_param(layout, dirs, d, init="zeros")
    return p


@_scoped("norm")
def apply_norm(cfg: ModelConfig, x, p):
    if cfg.norm == "layernorm":
        return layernorm(x, p["g"], p["b"])
    return rmsnorm(x, p["g"], zero_centered=cfg.zero_centered_norm)


def dense_block_params(layout: Layout, cfg: ModelConfig, dirs: Dirs,
                       d_ff=None, fsdp=False):
    return {
        "ln1": make_norm_params(layout, cfg, dirs),
        "attn": attn_params(layout, cfg, dirs, fsdp=fsdp),
        "ln2": make_norm_params(layout, cfg, dirs),
        "mlp": mlp_params(layout, cfg, dirs, d_ff=d_ff, fsdp=fsdp),
    }


def dense_block_apply(layout: Layout, cfg: ModelConfig, dirs: Dirs, x, p,
                      positions, *, decode=False, cache=None, window=None,
                      causal=True, return_kv=False, page=None):
    w = cfg.window if window is None else window
    h = apply_norm(cfg, x, p["ln1"])
    a, new_cache = attn_apply(layout, cfg, dirs, h, p["attn"], positions,
                              window=w, decode=decode, cache=cache,
                              causal=causal, return_kv=return_kv, page=page)
    x = x + a
    h = apply_norm(cfg, x, p["ln2"])
    x = x + mlp_apply(layout, cfg, dirs, h, p["mlp"], decode=decode)
    return x, new_cache
