"""Top-level model assembly: any assigned architecture -> init / train-loss /
prefill / decode functions, all 3-D parallel (or 1-D/2-D baseline).

This module is a thin, family-free driver over the BlockStack protocol
(``models/registry.py``): each family registers its layer plan, block kinds,
frontend and head hooks there, and ``forward`` / ``forward_pipelined`` only
orchestrate — embed, run the registered stack, apply the head.  Layer stacks
run under ``lax.scan`` with layer-stacked parameter trees, so compile time
and HLO size are O(1) in depth; heterogeneous plans (hybrid zamba2, xlstm
interleave, MoE first-k-dense) are split into homogeneous segments
statically by the registry's segment runner.

With ``layout.n_stages > 1`` the same plan runs pipelined (any family): the
registry cuts the plan into per-stage parameter slots and
``core/pipeline.py`` schedules them; per-microbatch context (audio encoder
states) and aux accumulators (MoE router losses) travel through the
pipeline alongside the activations.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..config import ModelConfig, ShapeConfig
from ..core import pipeline as pp_mod
from ..core.linear3d import (act_spec, act_spec_decode, embed_param, plinear,
                             weight_param, wsc)
from ..core.params import Param, abstract_arrays, init_params
from ..core.topology import Dirs, Layout
from . import blocks as B
from . import registry

F32 = jnp.float32


def entry_dirs() -> Dirs:
    return Dirs("y", "z")


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------
def abstract_params(cfg: ModelConfig, layout: Layout):
    stack = registry.get_stack(cfg.family)
    dirs = entry_dirs()
    d = cfg.d_model
    p: Dict[str, Any] = {"embed": embed_param(layout, dirs, cfg.vocab, d)}
    p.update(stack.frontend_params(layout, cfg, dirs))
    shared = stack.shared_params(layout, cfg, dirs)
    if shared:
        p["shared"] = shared

    if layout.n_stages > 1:
        reason = registry.pipeline_unsupported_reason(cfg, layout.n_stages)
        if reason:
            raise ValueError(reason)
        # (pp, slots, ...) stage slabs, stage dim sharded over 'pp' — each
        # pipeline group holds only its own slice of the depth
        p["stack"] = registry.pipeline_stack_params(stack, cfg, layout, dirs)
    else:
        p["stack"] = registry.stack_params(stack, cfg, layout, dirs)

    p["ln_f"] = B.make_norm_params(layout, cfg, dirs)
    p["head"] = weight_param(layout, dirs, d, cfg.vocab, kind="first",
                             init_scale=1.0)
    if cfg.mtp:
        p["mtp"] = {
            "ln_h": B.make_norm_params(layout, cfg, dirs),
            "ln_e": B.make_norm_params(layout, cfg, dirs),
            "proj": Param((2 * d, d), P(dirs.out_ax, None)),  # noswap proj
            "block": registry.attn_block_params(
                layout, cfg, dirs,
                d_ff=(cfg.moe.dense_ff if cfg.moe else cfg.d_ff)),
        }
    return p


def init(cfg: ModelConfig, layout: Layout, key):
    return init_params(abstract_params(cfg, layout), key, layout=layout)


def param_counts(cfg: ModelConfig):
    """(total, active) parameter counts from the real parameter tree
    (MoE: only top-k routed experts count as active)."""
    from ..core.params import count_params, is_param
    from ..core.topology import single_device_layout
    tree = abstract_params(cfg, single_device_layout())
    total = count_params(tree)
    active = total
    if cfg.moe:
        moe_blocks = tree.get("stack", {}).get("moe", {})
        routed = sum(p.size for k in ("w1", "w2", "w3")
                     for p in jax.tree.leaves(
                         moe_blocks.get("moe", {}).get(k), is_leaf=is_param)
                     if is_param(p))
        active = total - int(routed * (cfg.moe.n_experts - cfg.moe.top_k)
                             / cfg.moe.n_experts)
    return total, active


# ---------------------------------------------------------------------------
# Pipelined forward (pp > 1, train only — any registered family)
# ---------------------------------------------------------------------------
def forward_pipelined(cfg: ModelConfig, layout: Layout, params, batch):
    """Pipelined train forward: microbatched 1F1B-style schedule over the
    'pp' stage axis.  Numerically equivalent to the pp=1 path on the same
    global batch (equal-sized microbatches, token-count-weighted mean of
    per-microbatch means, aux losses carried through the stages)."""
    reason = registry.pipeline_unsupported_reason(cfg, layout.n_stages)
    if reason:
        raise ValueError(reason)
    stack = registry.get_stack(cfg.family)
    dirs = entry_dirs()
    m = max(layout.microbatches, 1)

    # frontend pinned to stage 0: embed (+ modality prelude) the whole batch
    # in the entry layout once (tables replicated along 'pp', cube-sharded
    # as usual), then split into the microbatch feed
    x, ctx = stack.frontend(layout, cfg, dirs, params, batch, mode="train")
    labels, mask = stack.labels(cfg, batch)
    Bg, S = x.shape[0], x.shape[1]
    if Bg % m:
        raise ValueError(f"global batch {Bg} not divisible by microbatches {m}")
    Bm = Bg // m
    x_mbs = x.reshape(m, Bm, S, -1)
    labs = labels.reshape(m, Bm, labels.shape[1])
    msks = mask.reshape(m, Bm, mask.shape[1])
    ctx_mbs = jax.tree.map(lambda a: a.reshape(m, Bm, *a.shape[1:]), ctx)
    positions = jnp.broadcast_to(jnp.arange(S), (Bm, S))

    info = registry.pipeline_info(stack, cfg, layout.n_stages)
    stage_fn = registry.make_stage_fn(stack, cfg, layout, dirs, info,
                                      positions, params.get("shared", {}),
                                      remat=cfg.remat)
    stage_params = {"stack": params["stack"]}
    if not info.homogeneous:
        stage_params["sel"] = jnp.asarray(info.selectors, jnp.int32)

    def collect_fn(acc, last, ctx_last, aux_last, mb_idx):
        # head pinned to the last stage; warm-up ticks (mb_idx < 0) carry
        # pipeline garbage and are masked out of the loss entirely.  Each
        # microbatch mean is re-weighted by its valid-token count so the
        # total is the global token mean, exactly as the pp=1 path computes
        xent_sum, aux_sum, w_sum = acc
        valid = (mb_idx >= 0).astype(F32)
        mb = jnp.clip(mb_idx, 0, m - 1)
        lab = lax.dynamic_index_in_dim(labs, mb, 0, keepdims=False)
        msk = lax.dynamic_index_in_dim(msks, mb, 0, keepdims=False) * valid
        h = B.apply_norm(cfg, last, params["ln_f"])
        w = jnp.sum(msk)
        mb_xent = chunked_head_loss(cfg, layout, dirs, h,
                                    jnp.maximum(lab, 0), msk, params["head"])
        return (xent_sum + w * mb_xent, aux_sum + w * aux_last["aux"],
                w_sum + w)

    xent_sum, aux_sum, w_sum = pp_mod.pipeline_schedule(
        layout, x_mbs=x_mbs, stage_params=stage_params, stage_fn=stage_fn,
        collect_fn=collect_fn,
        collect_init=(jnp.zeros((), F32), jnp.zeros((), F32),
                      jnp.zeros((), F32)),
        act_p=act_spec(layout, dirs), ctx_mbs=ctx_mbs,
        ctx_specs=stack.ctx_specs(layout, cfg, dirs),
        aux_init={"aux": jnp.zeros((), F32)})
    w_sum = jnp.maximum(w_sum, 1.0)
    xent, aux = xent_sum / w_sum, aux_sum / w_sum
    return xent + aux, {"xent": xent, "aux": aux}


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------
def forward(cfg: ModelConfig, layout: Layout, params, batch, *, mode: str,
            cache=None, page=None):
    """mode: 'train' -> (loss, metrics); 'prefill' -> (last_logits, cache);
    'decode' -> (logits, cache).

    ``page`` (decode only, a ``blocks.PageInfo``): decode straight against
    the paged KV pool — ``cache`` is then the pool tree (leaves
    (n_layers, phys, ...)) and the returned cache is the updated pool; no
    gathered view is ever materialized (see serve/engine.py)."""
    if layout.n_stages > 1:
        if mode != "train":
            from ..core.plan import pipeline_mode_error
            raise ValueError(pipeline_mode_error(layout.n_stages, mode))
        return forward_pipelined(cfg, layout, params, batch)
    stack = registry.get_stack(cfg.family)
    dirs = entry_dirs()
    decode = mode == "decode"
    remat = cfg.remat and mode == "train"

    # ---- frontend (embedding + modality prelude) ----
    x, ctx = stack.frontend(layout, cfg, dirs, params, batch, mode=mode)
    if decode and page is not None:
        ctx = dict(ctx)
        ctx["_page"] = page
    S = x.shape[1]
    if decode:
        positions = batch["pos"][:, None]                      # (B, 1)
    else:
        positions = jnp.broadcast_to(jnp.arange(S), (x.shape[0], S))

    # ---- body: the registered layer plan ----
    collect = mode == "prefill"
    x, new_cache, aux = registry.run_stack(
        stack, layout, cfg, dirs, x, params, positions, ctx=ctx,
        shared=params.get("shared", {}), mode=mode, cache=cache, remat=remat,
        collect_kv=collect)

    # ---- head ----
    x = B.apply_norm(cfg, x, params["ln_f"])

    if mode == "decode":
        logits, _ = plinear(layout, dirs, x, params["head"], kind="first",
                            decode=True)
        return logits[:, 0], new_cache

    if mode == "prefill":
        # last-position logits only (cheap head); new_cache carries the
        # per-layer rope'd (k, v) stacks (MLA: (c_kv, k_rope) latents) for
        # the serving hand-off
        last = x[:, -1:]
        last = wsc(last, layout.sharding(act_spec_decode(layout, dirs)))
        logits, _ = plinear(layout, dirs, last, params["head"], kind="first",
                            decode=True)
        return logits[:, 0], new_cache

    labels, mask = stack.labels(cfg, batch)
    loss = chunked_head_loss(cfg, layout, dirs, x, jnp.maximum(labels, 0),
                             mask, params["head"])
    metrics = {"xent": loss, "aux": aux}
    loss = loss + aux

    if cfg.mtp:
        mtp_loss = _mtp_loss(cfg, layout, dirs, params, x, batch, positions)
        loss = loss + 0.1 * mtp_loss
        metrics["mtp"] = mtp_loss
    return loss, metrics


def head_loss_chunks(cfg: ModelConfig, layout: Layout, S: int) -> int:
    # Seq-chunking factor for the LM head + loss: bounds the materialized
    # (tokens, V) logits (and their gathered cotangents in the Algorithm-2
    # backward islands) to roughly a 32k-vocab's worth (EXPERIMENTS.md §Perf).
    k = min(8, max(1, cfg.vocab // 32000, S // 1024))
    div = layout.size("y") * layout.size("z") * \
        math.prod(layout.size(a) for a in layout.seq_axes)
    while k > 1 and (S % k or (S // k) % div):
        k -= 1
    return k


def chunked_head_loss(cfg: ModelConfig, layout: Layout, dirs: Dirs, x,
                      labels, mask, w_head):
    # LM head + vocab-parallel cross entropy, chunked over the sequence under
    # a lax.scan (strictly sequential in fwd AND bwd) and checkpointed per
    # chunk: neither the logits nor their cotangents are ever live for more
    # than one chunk.  Tokens are interleaved position%K -> chunk so each
    # chunk keeps the balanced sequence sharding.
    B_, S = labels.shape
    K = head_loss_chunks(cfg, layout, S)

    @jax.checkpoint
    def chunk(x_c, lab_c, mask_c, w):
        logits, _ = plinear(layout, dirs, x_c, w, kind="first")
        lf = logits.astype(F32)
        m = jax.lax.stop_gradient(jnp.max(lf, axis=-1, keepdims=True))
        lse = jnp.log(jnp.sum(jnp.exp(lf - m), axis=-1)) + m[..., 0]
        picked = jnp.take_along_axis(lf, lab_c[..., None], axis=-1)[..., 0]
        nll = (lse - picked) * mask_c
        return jnp.sum(nll), jnp.sum(mask_c)

    if K == 1:
        tot, cnt = chunk(x, labels, mask, w_head)
        return tot / jnp.maximum(cnt, 1)
    c = S // K
    xs = (x.reshape(B_, c, K, -1).transpose(2, 0, 1, 3),
          labels.reshape(B_, c, K).transpose(2, 0, 1),
          mask.reshape(B_, c, K).transpose(2, 0, 1))

    def body(acc, inp):
        x_c, lab_c, mask_c = inp
        t, n = chunk(x_c, lab_c, mask_c, w_head)
        return (acc[0] + t, acc[1] + n), None

    (tot, cnt), _ = jax.lax.scan(body, (jnp.zeros((), F32), jnp.zeros((), F32)), xs)
    return tot / jnp.maximum(cnt, 1)


def _mtp_loss(cfg, layout, dirs, params, h, batch, positions):
    """DeepSeek multi-token prediction: predict t+2 from (h_t, emb_{t+1})."""
    from ..core import ops3d
    from ..core.linear3d import embed_lookup
    p = params["mtp"]
    tokens, labels = batch["tokens"], batch["labels"]
    nxt = jnp.concatenate([tokens[:, 1:], tokens[:, -1:]], axis=1)
    e = embed_lookup(layout, dirs, nxt, params["embed"])
    cat = jnp.concatenate([B.apply_norm(cfg, h, p["ln_h"]),
                           B.apply_norm(cfg, e, p["ln_e"])], axis=-1)
    if layout.strategy == "3d":
        z = ops3d.matmul3d_noswap(layout, dirs.in_ax, dirs.out_ax, cat, p["proj"])
        z = wsc(z, layout.sharding(act_spec(layout, dirs)))   # re-split hidden
    else:
        z = jnp.einsum("bsh,hf->bsf", cat, p["proj"],
                       preferred_element_type=F32).astype(cat.dtype)
    z, _, _ = registry.attn_block_apply(layout, cfg, dirs, z, p["block"],
                                        positions, ctx={}, shared={})
    z = B.apply_norm(cfg, z, params["ln_f"])
    lab2 = jnp.concatenate([labels[:, 1:], -jnp.ones_like(labels[:, -1:])],
                           axis=1)
    mask = (lab2 >= 0).astype(F32)
    return chunked_head_loss(cfg, layout, dirs, z, jnp.maximum(lab2, 0),
                             mask, params["head"])


# ---------------------------------------------------------------------------
# Serving prefill
# ---------------------------------------------------------------------------
def prefill(cfg: ModelConfig, layout: Layout, params, batch):
    """Batched whole-prompt prefill: the serving engine's chunked-prefill
    entry (one device call processes a whole padded prompt group instead of
    one token per global step).

    ``batch``: {"tokens": (B, S) int32 right-padded prompts, "length": (B,)
    int32 true prompt lengths (0 marks an inactive padding row)}.  Returns
    ``(logits, kv)``: per-row logits at the last *valid* position (B, V) —
    right-padding is safe under causal attention, garbage past a row's
    length never reaches positions before it — plus the collected per-kind
    kv streams ((n_layers, B, S, ...) stacked, rope'd; MLA: compressed
    latents) that ``registry.pack_prefill_cache`` shapes for the paged
    decode cache.  Only meaningful for 'paged' serve families
    (``registry.serve_cache_mode``); recurrent state has no chunked form.
    """
    if layout.n_stages > 1:
        from ..core.plan import pipeline_mode_error
        raise ValueError(pipeline_mode_error(layout.n_stages, "prefill"))
    stack = registry.get_stack(cfg.family)
    dirs = entry_dirs()
    x, ctx = stack.frontend(layout, cfg, dirs, params, batch, mode="prefill")
    S = x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(S), (x.shape[0], S))
    x, kv, _ = registry.run_stack(
        stack, layout, cfg, dirs, x, params, positions, ctx=ctx,
        shared=params.get("shared", {}), mode="prefill", cache=None,
        remat=False, collect_kv=True)
    x = B.apply_norm(cfg, x, params["ln_f"])
    idx = jnp.clip(batch["length"].astype(jnp.int32) - 1, 0, S - 1)
    last = jnp.take_along_axis(x, idx[:, None, None], axis=1)    # (B, 1, H)
    last = wsc(last, layout.sharding(act_spec_decode(layout, dirs)))
    logits, _ = plinear(layout, dirs, last, params["head"], kind="first",
                        decode=True)
    return logits[:, 0], kv


def extend(cfg: ModelConfig, layout: Layout, params, batch, view):
    """Multi-token continuation past an existing cache view: the serving
    fast path shared by prefix-hit tail prefill and speculative verify.

    ``batch``: {"tokens": (B, S) int32 right-padded fresh tokens,
    "offset": (B,) int32 absolute position of each row's first fresh token,
    "length": (B,) int32 count of valid fresh tokens (0 = inactive row)}.
    ``view``: a gathered per-kind cache tree as produced for decode
    ({kind: {"k", "v", "pos"}}); rows the view marks pos=-1 are ignored, so
    a cold row (offset 0 over a cleared view) degenerates to plain prefill.

    Returns ``(logits, kv, positions)``: full-vocab logits for every fresh
    position (B, S, V) — the verify step needs all of them, the tail-prefill
    step takes the last valid row — the collected kv streams for
    ``registry.pack_prefill_cache`` (padding rows carry position -1 and are
    dropped by the masked scatter), and the (B, S) absolute positions.
    """
    if layout.n_stages > 1:
        from ..core.plan import pipeline_mode_error
        raise ValueError(pipeline_mode_error(layout.n_stages, "extend"))
    if registry.serve_cache_mode(cfg) != "paged":
        raise ValueError(
            f"extend: family {cfg.family} serves with recurrent state, not a "
            "kv view; only 'paged' families support multi-token continuation")
    if cfg.mla is not None:
        raise NotImplementedError(
            "extend: MLA latent caches have no gathered-view continuation "
            "path yet; serve MLA models without --prefix-cache/--draft")
    stack = registry.get_stack(cfg.family)
    dirs = entry_dirs()
    x, ctx = stack.frontend(layout, cfg, dirs, params, batch, mode="prefill")
    S = x.shape[1]
    i = jnp.arange(S, dtype=jnp.int32)
    positions = jnp.where(i[None, :] < batch["length"][:, None],
                          batch["offset"][:, None] + i[None, :], -1)
    x, kv, _ = registry.run_stack(
        stack, layout, cfg, dirs, x, params, positions, ctx=ctx,
        shared=params.get("shared", {}), mode="extend", cache=view,
        remat=False, collect_kv=True)
    x = B.apply_norm(cfg, x, params["ln_f"])
    logits, _ = plinear(layout, dirs, x, params["head"], kind="first")
    return logits, kv, positions


# ---------------------------------------------------------------------------
# Caches
# ---------------------------------------------------------------------------
def abstract_cache(cfg: ModelConfig, layout: Layout, batch: int, length: int):
    stack = registry.get_stack(cfg.family)
    dirs = entry_dirs()
    return registry.stack_cache(stack, cfg, layout, dirs, batch, length)


# ---------------------------------------------------------------------------
# Dry-run input specs
# ---------------------------------------------------------------------------
def input_specs(cfg: ModelConfig, layout: Layout, shape: ShapeConfig):
    """ShapeDtypeStructs (with shardings) for every model input."""
    stack = registry.get_stack(cfg.family)
    dirs = entry_dirs()
    Bn, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32

    def sds(shp, dtype, spec):
        return jax.ShapeDtypeStruct(shp, dtype, sharding=layout.sharding(spec))

    tok_spec = _token_seq_spec(layout, dirs)
    if shape.kind == "decode":
        batch = {
            "token": sds((Bn, 1), i32, P(layout.batch_spec(), None)),
            "pos": sds((Bn,), i32, P(layout.batch_spec())),
        }
        cache = abstract_arrays(abstract_cache(cfg, layout, Bn, S), layout)
        return batch, cache

    batch = stack.inputs(cfg, layout, shape, sds, tok_spec)
    if shape.kind == "train":
        batch["labels"] = sds((Bn, stack.label_len(cfg, S)), i32, tok_spec)
    return (batch,)


def _token_seq_spec(layout: Layout, dirs: Dirs):
    if layout.strategy == "3d":
        seq = tuple(a for a in (*layout.seq_axes, dirs.in_ax)
                    if layout.size(a) > 1)
    elif layout.strategy == "2d":
        seq = tuple(a for a in (*layout.seq_axes, "y") if layout.size(a) > 1)
    else:
        seq = tuple(a for a in layout.seq_axes if layout.size(a) > 1)
    return P(layout.batch_spec(), seq or None)
