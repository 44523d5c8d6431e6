"""Speculative decoding over the paged pool: a small draft model proposes
γ tokens per engine step, the target verifies them in ONE batched
``transformer.extend`` call, and rejection sampling keeps the emitted
distribution exactly the target's.

Exactness argument (Leviathan et al. 2211.17192)
------------------------------------------------
Per row the engine feeds ``[t0, d_1..d_γ]`` — the last emitted token plus
the draft chain — through the target at positions ``pos..pos+γ``; the
target's logits at index j are its distribution p_j for the token AFTER
the j-th fed token.

  * temp = 0: ``d_{j+1}`` is accepted iff it equals ``argmax p_j`` and all
    earlier drafts were accepted; with ``a`` accepted the bonus token is
    ``argmax p_a``.  Every emitted token is therefore exactly the token
    greedy target decoding would have produced — bit-identical to the
    non-speculative engine.
  * temp > 0 (plain temperature; top-k / top-p stay on the non-speculative
    path): draft proposes ``d_{j+1} ~ q_j``; accept with probability
    ``min(1, p_j(d)/q_j(d))``; on the first rejection resample from the
    residual ``norm(max(0, p_j - q_j))``; with all γ accepted the bonus
    samples ``p_γ``.  The emitted marginal is p at every step.

State discipline
----------------
The draft holds a private *contiguous* cache of length ``max_len + γ`` (so
the decode ring never wraps onto in-flight draft entries) on its own —
typically single-device — layout.  Rejected drafts leave stale kv on both
sides: the draft loop rewinds its cache (positions >= the feed point are
invalidated) before every burst, and the target's ``attention_extend``
masks cached entries at or past each row's first fresh position.  Verify
writes land through a host-built physical map, so positions beyond a
slot's allocated blocks (or ``max_len``) fall to the trash block and the
device-side clamp on the accepted count guarantees such tokens are never
emitted.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..config import ModelConfig
from ..core.params import init_params
from ..core.topology import Layout
from ..models import registry, transformer
from . import kvcache

F32 = jnp.float32


def draft_unsupported_reason(target_cfg: ModelConfig,
                             draft_cfg: ModelConfig) -> Optional[str]:
    """Why this (target, draft) pair cannot speculate, or None."""
    for name, cfg in (("target", target_cfg), ("draft", draft_cfg)):
        if registry.serve_cache_mode(cfg) != "paged":
            return (f"speculative decoding: {name} {cfg.arch} serves with "
                    "recurrent state; both models need kv attention")
        if cfg.mla is not None:
            return (f"speculative decoding: {name} {cfg.arch} uses MLA "
                    "latents — the extend/verify path only covers dense kv")
    if target_cfg.vocab != draft_cfg.vocab:
        return (f"speculative decoding: vocab mismatch — target "
                f"{target_cfg.arch} has {target_cfg.vocab}, draft "
                f"{draft_cfg.arch} has {draft_cfg.vocab}; drafted token ids "
                "must index the target's distribution")
    if target_cfg.window:
        return (f"speculative decoding: target {target_cfg.arch} uses a "
                "sliding-window ring; multi-token verify would wrap onto "
                "live blocks")
    return None


@dataclasses.dataclass
class DraftSpec:
    """A draft model bound to an engine: config + params + layout plus the
    jitted prefill / propose device functions and the contiguous cache."""
    cfg: ModelConfig
    layout: Layout
    params: object
    gamma: int = 4
    cache_len: int = 0              # set by build(): max_len + gamma
    cache: object = None
    _prefill = None
    _propose = None
    _reset = None

    def build(self, batch_size: int, max_len: int, temperature: float):
        cfg, layout, gamma = self.cfg, self.layout, self.gamma
        self.cache_len = max_len + gamma
        dtype = next(x.dtype for x in jax.tree.leaves(self.params)
                     if jnp.issubdtype(x.dtype, jnp.floating))
        tree = kvcache.cache_with_dtype(
            transformer.abstract_cache(cfg, layout, batch_size,
                                       self.cache_len), dtype)
        self.cache = init_params(tree, jax.random.key(0), layout=layout)
        L = self.cache_len

        def prefill_step(params, cache, tokens, length):
            _, kv = transformer.prefill(
                cfg, layout, params, {"tokens": tokens, "length": length})
            p = jnp.arange(tokens.shape[1])[None, :]
            pos2d = jnp.where(p < length[:, None], p, -1)
            updates = registry.pack_prefill_cache(cfg, kv, pos2d)
            idx = jnp.where(pos2d >= 0, pos2d, L)        # padding drops off
            return kvcache.scatter_prefill_state(cache, updates, idx)

        def rewind(cache, cutoff):
            # invalidate every entry at or past the feed point: kv of
            # drafts a previous verify rejected must never be attended
            def r(leaf):
                if not jnp.issubdtype(leaf.dtype, jnp.integer):
                    return leaf
                cut = cutoff.reshape((1, -1) + (1,) * (leaf.ndim - 2))
                return jnp.where(leaf >= cut, -1, leaf)
            return jax.tree.map(r, cache)

        def propose(params, cache, tprev, t0, pos, key):
            """Burst γ+1 draft steps: re-feed the previous token at
            ``pos - 1`` then ``t0`` at ``pos`` (a fully accepted verify
            leaves the last accepted draft's kv missing — re-feeding the
            last two emitted tokens deterministically re-covers any such
            hole), then propose γ tokens.  Returns (cache, drafts (B, γ),
            qprobs (B, γ, V) — the draft's temperature-scaled
            distributions, only consumed when temperature > 0)."""
            cache = rewind(cache, pos - 1)
            keys = jax.random.split(key, gamma + 1)

            def step(carry, xs):
                cache, tok = carry
                j, k = xs
                logits, cache = transformer.forward(
                    cfg, layout, params, {"token": tok[:, None],
                                          "pos": pos - 1 + j},
                    mode="decode", cache=cache)
                lf = logits.astype(F32)
                if temperature > 0:
                    q = jax.nn.softmax(lf / temperature, axis=-1)
                    nxt = jax.random.categorical(k, lf / temperature, axis=-1)
                else:
                    q = jnp.zeros_like(lf)
                    nxt = jnp.argmax(lf, axis=-1)
                # the token after tprev is already known (t0) — the step-0
                # "proposal" is discarded below, but the NEXT step must be
                # fed t0 itself, not the draft's guess
                nxt = jnp.where(j == 0, t0, nxt.astype(jnp.int32))
                return (cache, nxt), (nxt, q)

            (cache, _), (drafts, qprobs) = lax.scan(
                step, (cache, tprev),
                (jnp.arange(gamma + 1, dtype=jnp.int32), keys))
            return cache, drafts.T[:, 1:], jnp.swapaxes(qprobs, 0, 1)[:, 1:]

        self._prefill = jax.jit(prefill_step, donate_argnums=(1,))
        self._propose = jax.jit(propose, donate_argnums=(1,))

        def reset_rows(cache, mask):
            def r(leaf):
                if not jnp.issubdtype(leaf.dtype, jnp.integer):
                    return leaf
                m = mask.reshape((1, -1) + (1,) * (leaf.ndim - 2))
                return jnp.where(m, -1, leaf)
            return jax.tree.map(r, cache)

        self._reset = jax.jit(reset_rows, donate_argnums=(0,))
        return self

    # thin wrappers so the engine never touches the jitted closures
    def prefill(self, tokens, length):
        self.cache = self._prefill(self.params, self.cache, tokens, length)

    def propose(self, tprev, t0, pos, key):
        self.cache, drafts, qprobs = self._propose(self.params, self.cache,
                                                   tprev, t0, pos, key)
        return drafts, qprobs

    def reset(self, mask):
        self.cache = self._reset(self.cache, mask)


def make_verify(cfg: ModelConfig, layout: Layout, block: int, gamma: int,
                s_pad: int, temperature: float):
    """The target-side verify step (jit it with pool donation): one
    ``extend`` over ``[t0, d_1..d_γ]`` padded to ``s_pad``, acceptance +
    bonus on device.

    Returns ``(accepted, emit, pool)``: ``accepted`` (B,) the number of
    drafts kept (clamped to ``limit``), ``emit`` (B, γ+1) the emitted
    tokens — ``d_1..d_a`` then the bonus — of which the first
    ``accepted + 1`` per row are valid.

    ``tokens`` (B, s_pad) is built host-side by the engine —
    ``[t0, d_1..d_γ, 0-pad]`` — NOT assembled on device from ``drafts``:
    on a multi-device mesh the partitioner of jax 0.4.37, where this was
    found, mis-reshards a concatenate whose consumer (the extend forward)
    imposes a sharded layout, summing the token ids across replicas (the
    same bug class as the cross-sharding label concat in the
    vision-language loss).  Whether the installed jax still does is not
    rechecked."""

    def verify(params, pool, tokens, drafts, qprobs, offset, length, tables,
               phys_map, limit, key):
        view = kvcache.gather_view(pool, tables, block)
        logits, kv, positions = transformer.extend(
            cfg, layout, params,
            {"tokens": tokens, "offset": offset, "length": length}, view)
        updates = registry.pack_prefill_cache(cfg, kv, positions)
        pool = kvcache.scatter_prefill(pool, updates, phys_map)
        # the extend logits come back sharded over (batch, seq, vocab) mesh
        # axes; only the first γ+1 positions matter and that slice is tiny,
        # so replicate it — the acceptance math below (argmax /
        # take_along_axis over both trailing axes) stays partitioner-trivial
        lf = jax.lax.with_sharding_constraint(
            logits[:, :gamma + 1].astype(F32),
            jax.sharding.NamedSharding(layout.mesh,
                                       jax.sharding.PartitionSpec()))
        if temperature > 0:
            p = jax.nn.softmax(lf / temperature, axis=-1)    # p_j
            kacc, kres = jax.random.split(key)
            u = jax.random.uniform(kacc, drafts.shape)       # (B, γ)
            p_d = jnp.take_along_axis(p[:, :gamma], drafts[..., None],
                                      axis=-1)[..., 0]
            q_d = jnp.take_along_axis(qprobs, drafts[..., None],
                                      axis=-1)[..., 0]
            ok = u * jnp.maximum(q_d, 1e-30) < p_d
            a_raw = jnp.sum(jnp.cumprod(ok.astype(jnp.int32), axis=1), axis=1)
            a = jnp.minimum(a_raw, limit)
            # residual resample at the rejection point; plain p_γ when all
            # γ drafts were accepted (qprobs has no γ-th entry).  When the
            # clamp — not a rejection — stopped the chain, the correct
            # bonus distribution is plain p_a too: zero q_a so the residual
            # degenerates to it.
            p_a = jnp.take_along_axis(
                p, a[:, None, None], axis=1)[:, 0]           # (B, V)
            q_a = jnp.take_along_axis(
                jnp.concatenate([qprobs, jnp.zeros_like(p[:, :1])], axis=1),
                a[:, None, None], axis=1)[:, 0]
            q_a = jnp.where((a_raw > limit)[:, None], 0.0, q_a)
            res = jnp.maximum(p_a - q_a, 0.0)
            res = res / jnp.maximum(jnp.sum(res, -1, keepdims=True), 1e-30)
            bonus = jax.random.categorical(
                kres, jnp.log(jnp.maximum(res, 1e-30)), axis=-1)
        else:
            g = jnp.argmax(lf, axis=-1).astype(jnp.int32)    # (B, S)
            ok = drafts == g[:, :gamma]
            a = jnp.sum(jnp.cumprod(ok.astype(jnp.int32), axis=1), axis=1)
            a = jnp.minimum(a, limit)
            bonus = jnp.take_along_axis(g, a[:, None], axis=1)[:, 0]
        emit = jnp.concatenate([drafts, jnp.zeros_like(drafts[:, :1])],
                               axis=1)
        emit = jnp.where(jnp.arange(gamma + 1)[None, :] == a[:, None],
                         bonus.astype(jnp.int32)[:, None], emit)
        return a, emit, pool

    return verify
