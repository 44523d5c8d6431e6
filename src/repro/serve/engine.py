"""Continuous-batching serving engine over the 3-D cube.

Architecture (docs/serving.md has the full picture):

  * ``scheduler.Scheduler``  — FIFO + priority queues, admission control,
    slot refill, prefill grouping (host-side policy).
  * ``kvcache.PagedKVCache`` — block-table paged KV pool for the 'paged'
    families (dense / MLA attention, per ``registry.serve_cache_mode``);
    'state' families (SSM / xLSTM / hybrid, modality frontends) keep the
    contiguous per-slot caches (O(1) recurrent state per slot).
  * ``sampling.make_sampler`` — on-device greedy / temperature / top-k /
    top-p under one engine-owned, per-step-split PRNG key: temperature = 0
    is bit-deterministic, temperature > 0 reproducible from ``seed``.
  * ``metrics.ServeMetrics`` — TTFT / TPOT / throughput / queue depth.

Engine steps come in two shapes.  A *prefill* step (paged families) pushes
a group of freshly admitted prompts through the jitted
``transformer.prefill`` — one device call per D prompts (D the layout's
batch-shard degree, 1 on one chip), each padded to its own power-of-two
bucket, instead of one call per token — scatters the returned kv into the
paged pool and emits each request's first token.  A *decode* step advances
every in-flight slot by one token: gather the block-table views, run the
decode forward, write the new entries back to their blocks, sample on
device.  Prefill and decode steps interleave: newly admitted work
prefills at the next step boundary while resident requests keep decoding.
'state' families (no chunked form for recurrent state) prefill
sequentially through the decode path, exactly one prompt token per step,
inside the same scheduler/metrics machinery.
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..config import ModelConfig
from ..core.params import init_params
from ..core.topology import Layout
from ..kernels.paged_decode import live_blocks
from ..models import blocks as B
from ..models import registry, transformer
from ..obs.trace import COMPILES, PROFILE
from . import kvcache, sampling, speculate
from .metrics import ServeMetrics
from .scheduler import Scheduler, pad_bucket

F32 = jnp.float32


@dataclasses.dataclass
class Request:
    uid: int
    prompt: List[int]
    max_new: int = 32
    priority: int = 0               # > 0 drains before the FIFO queue
    out: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    error: str = ""                 # admission-rejection reason (out stays [])
    # prompt tokens already fed on the sequential-prefill path (a real
    # dataclass field — not bolted on from outside)
    _fed: int = 0


class Engine:
    """Slot-based continuous batching: fixed decode batch of ``batch_size``
    slots, refilled from the scheduler queues as requests complete."""

    def __init__(self, cfg: ModelConfig, layout: Layout, params, *,
                 batch_size: int = 8, max_len: int = 512,
                 temperature: float = 0.0, top_k: int = 0, top_p: float = 0.0,
                 seed: int = 0, block_size: int = 16,
                 n_blocks: Optional[int] = None, prefill_chunk: int = 4096,
                 chunked_prefill: bool = True,
                 fused_decode: Optional[bool] = None,
                 prefix_cache: bool = False,
                 draft: Optional["speculate.DraftSpec"] = None,
                 tracer=None):
        self.cfg, self.layout, self.params = cfg, layout, params
        # observability: per-request lifecycle spans are emitted by the
        # metrics hooks; the engine itself adds the serve.* spans of each
        # step on the "engine" lane.  The default PROFILE tracer keeps
        # nothing in Python: its spans reach a profile only while one is
        # being captured (jax.profiler.start_trace).
        self.tracer = tracer if tracer is not None else PROFILE
        COMPILES.watch()
        self.B, self.max_len = batch_size, max_len
        self.temperature = temperature
        self.paged = registry.serve_cache_mode(cfg) == "paged"
        self.chunked = chunked_prefill and self.paged
        # fused paged decode (default on): attend straight against the pool
        # through the block tables (kernels/paged_decode.py) instead of
        # materializing gather_view + scattering the new view back
        self.fused = (fused_decode if fused_decode is not None
                      else True) and self.paged
        if prefix_cache:
            if not (self.paged and self.chunked):
                raise ValueError(
                    "prefix_cache requires a paged family with chunked "
                    "prefill (the shared blocks enter via the block tables)")
            if cfg.mla is not None:
                raise ValueError(
                    "prefix_cache: MLA latent caches have no extend path "
                    "yet; serve this model without --prefix-cache")
        self.prefix = bool(prefix_cache)
        if draft is not None:
            reason = speculate.draft_unsupported_reason(cfg, draft.cfg)
            if reason:
                raise ValueError(reason)
            if not self.chunked:
                raise ValueError("speculative decoding requires chunked "
                                 "prefill (the verify step extends the "
                                 "paged pool)")
            if temperature > 0 and (top_k or top_p):
                raise ValueError(
                    "speculative decoding keeps the sampled distribution "
                    "exact only for greedy or plain-temperature sampling; "
                    "drop top_k/top_p or --draft")
        self.sampler = sampling.make_sampler(temperature, top_k, top_p)
        self._key = jax.random.key(seed)
        self.scheduler = Scheduler(batch_size, max_len,
                                   chunk_tokens=prefill_chunk)
        self.metrics = ServeMetrics(tracer=self.tracer)

        self.pos = np.zeros(batch_size, np.int32)
        self.slots: List[Optional[Request]] = [None] * batch_size
        self.steps = 0

        dtype = next(x.dtype for x in jax.tree.leaves(params)
                     if jnp.issubdtype(x.dtype, jnp.floating))
        if self.paged:
            self.kv = kvcache.PagedKVCache(cfg, layout, batch_size, max_len,
                                           block=block_size,
                                           n_blocks=n_blocks, dtype=dtype,
                                           prefix_cache=self.prefix)
            self.pool = self.kv.init_pool()
            # rows of one prefill program: the layout's batch-shard degree
            # (1 on one chip), the fewest rows its batch sharding allows
            self._prefill_rows = math.prod(layout.size(a)
                                           for a in layout.batch_axes)
            self._build_paged()
            self.spec = (draft.build(batch_size, max_len, temperature)
                         if draft is not None else None)
            if self.spec is not None:
                self._verify = jax.jit(
                    speculate.make_verify(cfg, layout, self.kv.block,
                                          self.spec.gamma, self._spec_pad(),
                                          temperature),
                    donate_argnums=(1,))
        else:
            self.spec = None
            tree = kvcache.cache_with_dtype(
                transformer.abstract_cache(cfg, layout, batch_size, max_len),
                dtype)
            self.cache = init_params(tree, jax.random.key(0), layout=layout)
            self._build_contiguous()

    # ------------------------------------------------------------------
    # Jitted device steps
    # ------------------------------------------------------------------
    def _build_paged(self):
        cfg, layout, sampler = self.cfg, self.layout, self.sampler
        blk, L = self.kv.block, self.kv.view_len
        fused = self.fused

        def decode_step(params, pool, tok, pos, tables, active, key):
            if fused:
                # fused path: the blocks attend the (read-only) pool
                # directly through the block tables — no gathered view —
                # and return each layer's new (k, v) entries, written back
                # here in one batched scatter
                page = B.PageInfo(tables=tables, active=active, block=blk)
                logits, upd = transformer.forward(
                    cfg, layout, params, {"token": tok, "pos": pos},
                    mode="decode", cache=pool, page=page)
                rows = jnp.arange(tok.shape[0])
                slot = pos % L
                phys = tables[rows, slot // blk] * blk + slot % blk
                phys = jnp.where(active, phys, blk + rows % blk)
                pool = kvcache.scatter_step(pool, upd, phys)
                return sampler(logits.astype(F32), key), pool
            view = kvcache.gather_view(pool, tables, blk)
            logits, new_view = transformer.forward(
                cfg, layout, params, {"token": tok, "pos": pos},
                mode="decode", cache=view)
            rows = jnp.arange(tok.shape[0])
            slot = pos % L
            phys = tables[rows, slot // blk] * blk + slot % blk
            phys = jnp.where(active, phys, blk + rows % blk)   # idle -> trash
            pool = kvcache.scatter_decode(pool, new_view, slot, phys)
            return sampler(logits.astype(F32), key), pool

        def prefill_step(params, pool, tokens, length, phys_map, key):
            logits, kv = transformer.prefill(
                cfg, layout, params, {"tokens": tokens, "length": length})
            p = jnp.arange(tokens.shape[1])[None, :]
            pos2d = jnp.where(p < length[:, None], p, -1)
            updates = registry.pack_prefill_cache(cfg, kv, pos2d)
            pool = kvcache.scatter_prefill(pool, updates, phys_map)
            return sampler(logits.astype(F32), key), pool

        def extend_step(params, pool, tokens, offset, length, tables,
                        phys_map, key):
            # prefix-hit tail prefill: only the un-hit prompt tail runs the
            # forward, attending the shared blocks through the view
            view = kvcache.gather_view(pool, tables, blk)
            logits, kv, positions = transformer.extend(
                cfg, layout, params,
                {"tokens": tokens, "offset": offset, "length": length}, view)
            updates = registry.pack_prefill_cache(cfg, kv, positions)
            pool = kvcache.scatter_prefill(pool, updates, phys_map)
            idx = jnp.clip(length - 1, 0, tokens.shape[1] - 1)
            last = jnp.take_along_axis(logits, idx[:, None, None],
                                       axis=1)[:, 0]
            return sampler(last.astype(F32), key), pool

        self._decode = jax.jit(decode_step, donate_argnums=(1,))
        self._prefill = jax.jit(prefill_step, donate_argnums=(1,))
        self._extendf = jax.jit(extend_step, donate_argnums=(1,))
        self._copy = jax.jit(kvcache.copy_block, donate_argnums=(0,))
        self._clear = jax.jit(kvcache.clear_positions, donate_argnums=(0,))

    def _spec_pad(self) -> int:
        """Verify-batch padded length: γ+1 rounded to the prefill buckets
        (sharding-divisible on every supported mesh)."""
        return pad_bucket(self.spec.gamma + 1)

    def _build_contiguous(self):
        cfg, layout, sampler = self.cfg, self.layout, self.sampler

        def decode_step(params, cache, tok, pos, key):
            logits, cache = transformer.forward(
                cfg, layout, params, {"token": tok, "pos": pos},
                mode="decode", cache=cache)
            return sampler(logits.astype(F32), key), cache

        def reset_rows(cache, mask):
            # wipe a reused slot's state (recurrent carries, kv positions)
            # so a new request never sees its predecessor's context
            def r(leaf):
                empty = (jnp.full_like(leaf, -1)
                         if jnp.issubdtype(leaf.dtype, jnp.integer)
                         else jnp.zeros_like(leaf))
                m = mask.reshape((1, -1) + (1,) * (leaf.ndim - 2))
                return jnp.where(m, empty, leaf)
            return jax.tree.map(r, cache)

        self._decode = jax.jit(decode_step, donate_argnums=(1,))
        self._reset = jax.jit(reset_rows, donate_argnums=(0,))

    def _split_key(self):
        self._key, sub = jax.random.split(self._key)
        return sub

    # ------------------------------------------------------------------
    # Request lifecycle
    # ------------------------------------------------------------------
    def submit(self, req: Request):
        self.metrics.submit(req.uid)
        if not self.scheduler.submit(req):
            self.metrics.reject(req.uid)

    def _can_place(self, req: Request, slot: int) -> bool:
        if not self.paged:
            return True
        return self.kv.can_admit(len(req.prompt) + req.max_new,
                                 req.prompt if self.prefix else None)

    def _admit(self):
        free = [i for i in range(self.B) if self.slots[i] is None]
        placed = self.scheduler.fill(free, self._can_place)
        admitted = []
        for slot, req in placed:
            self.slots[slot] = req
            self.pos[slot] = 0
            req._fed = 0
            if self.paged:
                ok = self.kv.admit(slot, len(req.prompt) + req.max_new,
                                   req.prompt if self.prefix else None)
                if not ok:
                    # the free count moved between can_place and admit (an
                    # earlier same-tick admission shrank this prompt's
                    # prefix hit, so it now needs more private blocks):
                    # requeue at the head, no state half-applied
                    self.slots[slot] = None
                    self.scheduler.pending_prefill.remove(slot)
                    q = (self.scheduler.prio if req.priority > 0
                         else self.scheduler.fifo)
                    q.appendleft(req)
                    continue
            admitted.append((slot, req))
            self.metrics.admit(req.uid)
        placed = admitted
        if placed and self.paged:
            # invalidate recycled blocks before anything reads them (the
            # clear covers only the slots' PRIVATE blocks — shared prefix
            # blocks keep their content), then materialize any pending
            # copy-on-write divergence into the first private block
            idx = self.kv.clear_targets([s for s, _ in placed])
            self.pool = self._clear(self.pool, idx)
            if self.prefix:
                cow = self.kv.cow_rows([s for s, _ in placed])
                if cow is not None:
                    src, dst, keep = cow
                    self.pool = self._copy(self.pool, jnp.asarray(src),
                                           jnp.asarray(dst),
                                           jnp.asarray(keep))
                for s, _ in placed:
                    self.kv.cow_done(s)
            if self.spec is not None:
                mask = np.zeros((self.B,), bool)
                for s, _ in placed:
                    mask[s] = True
                self.spec.reset(jnp.asarray(mask))
        elif placed:
            mask = np.zeros((self.B,), bool)
            for s, _ in placed:
                mask[s] = True
            self.cache = self._reset(self.cache, jnp.asarray(mask))
        if not self.chunked:
            # sequential prefill starts feeding immediately, no prefill queue
            self.scheduler.pending_prefill.clear()
        if not placed and not self.scheduler.pending_prefill \
                and self.scheduler.has_queued() \
                and all(s is None for s in self.slots):
            # nothing running and the queue head can never be placed (needs
            # more blocks than the whole pool): reject instead of spinning
            req = (self.scheduler.prio or self.scheduler.fifo).popleft()
            req.error = ("request needs more KV blocks than the pool holds "
                         f"(prompt {len(req.prompt)} + max_new {req.max_new})")
            req.done = True
            self.metrics.reject(req.uid)

    def _finish(self, i: int):
        req = self.slots[i]
        req.done = True
        self.slots[i] = None
        if self.paged:
            self.kv.release(i)
        self.metrics.finish(req.uid)

    # ------------------------------------------------------------------
    # Steps
    # ------------------------------------------------------------------
    def step(self):
        """One engine step: admit waiting work, then either one chunked
        prefill group or one global decode tick.

        Spans, all on the ``engine`` track: ``serve.step`` (queue depth,
        occupied slots, and the process's compile count and seconds, at
        entry) holds in order ``serve.admit``, ``serve.prepare`` (the
        host builds the programs' inputs), ``serve.dispatch`` (the jitted
        calls, with any trace or compile), ``serve.wait`` (the one
        ``device_get`` of the sampled tokens) and ``serve.emit`` (tokens,
        metrics hooks, finished requests)."""
        tr = self.tracer
        with tr.span("serve.step", track="engine",
                     queue=self.scheduler.queue_depth(),
                     slots=sum(s is not None for s in self.slots),
                     compile_n=COMPILES.n, compile_s=COMPILES.s):
            with tr.span("serve.admit", track="engine"):
                self._admit()
            if self.chunked and self.scheduler.pending_prefill:
                self._prefill_tick()
                kind = "prefill"
            elif self.spec is not None:
                self._spec_tick()
                kind = "decode"
            else:
                self._decode_tick()
                kind = "decode"
            self.metrics.observe_step(self.scheduler.queue_depth(), kind)
        self.steps += 1

    def _prefill_tick(self):
        # with the prefix cache on, each slot only prefills its un-hit
        # tail: grouping / padding / the token budget all run on the tail
        # length, which is where the TTFT win comes from
        tr = self.tracer
        lens = {s: len(self.slots[s].prompt)
                - (self.kv.hit_len(s) if self.prefix else 0)
                for s in self.scheduler.pending_prefill}
        group, _ = self.scheduler.prefill_group(lens)
        # the group runs as consecutive programs of D rows (the layout's
        # batch-shard degree), each padded to its own rows' bucket: no
        # program spends positions on idle slots or on the group's longest
        # prompt.  Rows of similar length share a program.
        D = self._prefill_rows
        order = sorted(group, key=lambda s: lens[s])
        runs = [order[i:i + D] for i in range(0, len(order), D)]
        pads = [pad_bucket(max(lens[s] for s in run)) for run in runs]
        with tr.span("serve.prepare", track="engine", rows=len(group),
                     tokens=sum(lens[s] for s in group),
                     padded=D * sum(pads), runs=len(runs)):
            calls = [self._prefill_args(run, s_row)
                     for run, s_row in zip(runs, pads)]
            if self.spec is not None:
                # the draft keeps its own contiguous B-row cache (slot rows
                # in place, no pool to map into), so it still prefills the
                # FULL prompt of every group slot in one (B, bucket) call —
                # its cache has no prefix sharing, and the propose bursts
                # need the whole context resident
                d_pad = pad_bucket(max(len(self.slots[s].prompt)
                                       for s in group))
                dtok = np.zeros((self.B, d_pad), np.int32)
                dlen = np.zeros((self.B,), np.int32)
                for s in group:
                    p = self.slots[s].prompt
                    dtok[s, :len(p)] = p
                    dlen[s] = len(p)
                draft = (jnp.asarray(dtok), jnp.asarray(dlen))
        with tr.span("serve.dispatch", track="engine"):
            prog = self._extendf if self.prefix else self._prefill
            toks = []
            for args in calls:
                tok, self.pool = prog(self.params, self.pool, *args,
                                      self._split_key())
                toks.append(tok)
            if self.spec is not None:
                self.spec.prefill(*draft)
        with tr.span("serve.wait", track="engine"):
            got = np.concatenate([np.asarray(t)
                                  for t in jax.device_get(toks)])
            first = {s: int(got[i * D + j]) for i, run in enumerate(runs)
                     for j, s in enumerate(run)}
        with tr.span("serve.emit", track="engine"):
            for s in group:
                req = self.slots[s]
                self.pos[s] = len(req.prompt)
                req._fed = len(req.prompt)
                if self.prefix:
                    # publish this prompt's full blocks before any possible
                    # release below — completed requests still seed the
                    # index
                    self.kv.register_prefix(s)
                req.out.append(first[s])
                self.metrics.token(req.uid)
                if (len(req.out) >= req.max_new
                        or self.pos[s] >= self.max_len - 1):
                    self._finish(s)

    def _prefill_args(self, run: List[int], s_row: int) -> tuple:
        """Inputs of one prefill (or prefix-hit extend) program over the
        slots ``run``, padded to D rows of ``s_row`` tokens; padding rows
        carry length 0 and write only to the trash block."""
        D = self._prefill_rows
        tokens = np.zeros((D, s_row), np.int32)
        length = np.zeros((D,), np.int32)
        offset = np.zeros((D,), np.int32)
        for j, s in enumerate(run):
            p = self.slots[s].prompt
            hit = self.kv.hit_len(s) if self.prefix else 0
            tokens[j, :len(p) - hit] = p[hit:]
            offset[j] = hit
            length[j] = len(p) - hit
        if not self.prefix:
            phys_map = self.kv.prefill_phys_map(
                [(s, int(length[j])) for j, s in enumerate(run)], D, s_row)
            return jnp.asarray(tokens), jnp.asarray(length), phys_map
        phys_map = self.kv.extend_phys_map(
            [(s, int(offset[j]), int(length[j])) for j, s in enumerate(run)],
            D, s_row)
        tables = np.zeros((D, self.kv.blocks_per_slot), np.int32)
        tables[:len(run)] = self.kv.tables[run]
        return (jnp.asarray(tokens), jnp.asarray(offset), jnp.asarray(length),
                jnp.asarray(tables), phys_map)

    def _decode_tick(self):
        tr = self.tracer
        pending = set(self.scheduler.pending_prefill)
        rows = [i for i, req in enumerate(self.slots)
                if req is not None and i not in pending
                and (req._fed < len(req.prompt) or req.out)]
        if not rows:
            return
        walk = {}
        if self.fused:
            # the paged-decode kernel's walk: table blocks it visits (each
            # row up to its last live block) of the B x nb it spans
            nb = self.kv.blocks_per_slot
            walk = dict(walked=int(live_blocks(self.pos[rows], True,
                                               block=self.kv.block,
                                               nb=nb).sum()),
                        blocks=self.B * nb)
        with tr.span("serve.prepare", track="engine", rows=len(rows),
                     live=int(self.pos[rows].sum()), **walk):
            tok = np.zeros((self.B, 1), np.int32)
            active = np.zeros((self.B,), bool)
            for i in rows:
                req = self.slots[i]
                tok[i, 0] = (req.prompt[req._fed]      # sequential prefill
                             if req._fed < len(req.prompt) else req.out[-1])
                active[i] = True
            args = (jnp.asarray(tok), jnp.asarray(self.pos))
            if self.paged:
                args += (self.kv.tables_device(), jnp.asarray(active))
        with tr.span("serve.dispatch", track="engine"):
            if self.paged:
                nxt, self.pool = self._decode(self.params, self.pool, *args,
                                              self._split_key())
            else:
                nxt, self.cache = self._decode(self.params, self.cache,
                                               *args, self._split_key())
        with tr.span("serve.wait", track="engine"):
            nxt = np.asarray(jax.device_get(nxt))
        with tr.span("serve.emit", track="engine"):
            for i in rows:
                req = self.slots[i]
                self.pos[i] += 1
                if req._fed < len(req.prompt):
                    req._fed += 1
                    if req._fed < len(req.prompt):
                        continue
                req.out.append(int(nxt[i]))
                self.metrics.token(req.uid)
                if (len(req.out) >= req.max_new
                        or self.pos[i] >= self.max_len - 1):
                    self._finish(i)

    def _spec_tick(self):
        """One speculative decode round: the draft bursts γ proposals per
        active slot, the target verifies them in one batched extend, and
        each row emits ``accepted + 1`` tokens (accepted drafts + bonus).
        ``serve.dispatch`` holds the draft's proposals; the hop of the
        drafts through the host and the verify are inside ``serve.wait``."""
        tr = self.tracer
        gamma = self.spec.gamma
        pending = set(self.scheduler.pending_prefill)
        rows = [i for i, req in enumerate(self.slots)
                if req is not None and i not in pending and req.out]
        if not rows:
            return
        with tr.span("serve.prepare", track="engine", rows=len(rows),
                     live=int(self.pos[rows].sum())):
            t0 = np.zeros((self.B,), np.int32)
            tprev = np.zeros((self.B,), np.int32)
            posv = np.ones((self.B,), np.int32)
            limit = np.zeros((self.B,), np.int32)
            active = np.zeros((self.B,), bool)
            ext = {}
            for i in rows:
                req = self.slots[i]
                t0[i] = req.out[-1]
                tprev[i] = (req.out[-2] if len(req.out) >= 2
                            else req.prompt[-1])
                posv[i] = self.pos[i]
                # emit at most limit+1 tokens: stay under max_new AND under
                # the decode length bound (pos must end < max_len - 1,
                # matching the non-speculative finish condition)
                limit[i] = max(min(req.max_new - len(req.out),
                                   self.max_len - 1 - self.pos[i]) - 1, 0)
                active[i] = True
                ext[i] = (int(self.pos[i]), gamma + 1)
            args = (jnp.asarray(tprev), jnp.asarray(t0), jnp.asarray(posv))
        with tr.span("serve.dispatch", track="engine"):
            drafts, qprobs = self.spec.propose(*args, self._split_key())
        with tr.span("serve.wait", track="engine"):
            # the draft lives on its own (typically single-device) mesh;
            # its outputs are committed there — hop through the host so the
            # verify jit can place them on the target's mesh.  The verify
            # batch [t0, d_1..d_γ, pad] is assembled here too (see
            # make_verify: a device-side concatenate mis-reshards on
            # multi-device meshes)
            drafts = np.asarray(jax.device_get(drafts))
            qprobs = np.asarray(jax.device_get(qprobs))
            vtok = np.zeros((self.B, self._spec_pad()), np.int32)
            vtok[:, 0] = t0
            vtok[:, 1:gamma + 1] = drafts
            phys_map = self.kv.extend_phys_map(
                [(i, *ext.get(i, (0, 0))) for i in range(self.B)],
                self.B, self._spec_pad())
            a, emit, self.pool = self._verify(
                self.params, self.pool, jnp.asarray(vtok), drafts, qprobs,
                jnp.asarray(posv), jnp.asarray(np.where(active, gamma + 1, 0)
                                               .astype(np.int32)),
                self.kv.tables_device(), phys_map, jnp.asarray(limit),
                self._split_key())
            a = np.asarray(jax.device_get(a))
            emit = np.asarray(jax.device_get(emit))
        with tr.span("serve.emit", track="engine"):
            for i in rows:
                req = self.slots[i]
                n = int(a[i]) + 1
                req.out.extend(int(t) for t in emit[i, :n])
                self.metrics.token(req.uid, n)
                self.metrics.spec_accept(int(a[i]))
                self.pos[i] += n
                if (len(req.out) >= req.max_new
                        or self.pos[i] >= self.max_len - 1):
                    self._finish(i)

    # ------------------------------------------------------------------
    def _busy(self) -> bool:
        return (self.scheduler.has_queued()
                or bool(self.scheduler.pending_prefill)
                or any(s is not None for s in self.slots))

    def run(self, requests: List[Request], progress: Callable = None):
        # per-run metrics: each run() reports exactly its own requests (and
        # drops the previous run's tracking, so a long-lived engine doesn't
        # accumulate per-request state across runs)
        self.metrics = ServeMetrics(tracer=self.tracer)
        if self.paged:
            self.kv.lookups = self.kv.hits = self.kv.tokens_reused = 0
            self.kv.allocator.evictions = 0
        for r in requests:
            self.submit(r)
        t0 = time.time()
        start = self.steps
        while self._busy():
            self.step()
            if progress and (self.steps - start) % 16 == 0:
                progress(self.steps)
        wall = time.time() - t0
        if self.paged:
            self.metrics.prefix_stats(self.kv.lookups, self.kv.hits,
                                      self.kv.tokens_reused,
                                      self.kv.allocator.evictions)
        stats = self.metrics.summary(wall)
        stats.update(steps=self.steps - start, wall_s=wall,
                     tokens=sum(len(r.out) for r in requests))
        return stats
