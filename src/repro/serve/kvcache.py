"""Paged KV cache: fixed-size blocks, per-slot block tables, free-list
allocation, eviction on request completion.

Contract
--------
The *pool* is the single device-resident store for every length-indexed
decode cache of a paged family (dense kv, MLA latent): each leaf of the
per-kind cache tree ``(n_layers, B, L, ...)`` becomes a pool leaf
``(n_layers, n_blocks * block, ...)`` — the batch and length dims are
replaced by one flat *physical* dim of ``n_blocks`` fixed-size blocks.
Which physical block holds which ``(slot, logical position)`` pair is pure
host-side bookkeeping (``PagedKVCache``: a free list plus one block table
per engine slot); the device functions below are shape-stable pure pytree
ops, safe to close over inside one jitted engine step:

  * ``gather_view``      pool + tables -> the per-slot contiguous cache view
                         ``(n_layers, B, L_view, ...)`` that
                         ``transformer.forward(mode="decode")`` consumes
                         unchanged (the decode ring modulus is the view
                         length, so views are always whole blocks).
  * ``scatter_decode``   write the one new entry per slot back to its block.
  * ``scatter_prefill``  write a whole chunk of prefill kv per slot at once.
  * ``clear_positions``  invalidate (pos = -1) freshly allocated blocks so a
                         reused block never leaks a previous request's keys.

Two physical blocks are reserved: block 0 is the *null* block — every
unallocated block-table entry points at it, its positions stay -1 forever,
so gathered views of unallocated regions are masked out of attention — and
block 1 is the *trash* block, the write target for masked-out lanes
(inactive slots, prompt padding); it is never referenced by any table.

Sharding: pool leaves drop the cache's batch/length sharding (the physical
dim is replicated over the data and sequence axes) and keep the trailing
head sharding, so the gather/scatter ops are plain GSPMD gathers — no new
shard_map regions (the attention islands inside ``forward`` reshard the
views to their own specs).
"""
from __future__ import annotations

import dataclasses
import math
from collections import OrderedDict
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..config import ModelConfig
from ..core.params import Param, init_params, is_param, tree_map_params
from ..core.topology import Layout

RESERVED = 2                      # block 0 = null (reads), block 1 = trash (writes)


# ---------------------------------------------------------------------------
# Host-side allocation
# ---------------------------------------------------------------------------
class BlockAllocator:
    """Ref-counted free-list allocator over ``n_blocks`` fixed-size blocks
    with an LRU of cached (refcount-0 but content-preserving) blocks.

    Blocks 0 and 1 are reserved (null / trash) and never handed out.  Every
    non-reserved block is in exactly one of three states:

      * *free*    — content-less, on the plain free list;
      * *live*    — refcount >= 1 (one count per owner: a slot's table, a
        prefix-sharing acquirer, a COW-source hold);
      * *cached*  — refcount dropped to 0 via ``release(cache=True)``: the
        content (an indexed prefix block) stays resident and matchable
        until ``alloc`` needs the space, evicting in LRU order (and firing
        ``on_evict`` so the prefix index forgets the block first).

    Invariants (enforced by ``check``): the three sets partition the
    non-reserved blocks; a block is never handed out while its refcount is
    > 0; only live blocks may be released; releasing below zero raises.
    """

    def __init__(self, n_blocks: int):
        if n_blocks <= RESERVED:
            raise ValueError(f"need more than {RESERVED} blocks, got {n_blocks}")
        self.n_blocks = n_blocks
        self._free: List[int] = list(range(RESERVED, n_blocks))
        self._ref: Dict[int, int] = {}
        self._lru: "OrderedDict[int, None]" = OrderedDict()
        self.on_evict: Optional[Callable[[int], None]] = None
        self.evictions = 0

    @property
    def n_free(self) -> int:
        """Allocatable blocks: truly free plus evictable cached ones."""
        return len(self._free) + len(self._lru)

    def alloc(self, n: int) -> Optional[List[int]]:
        """n blocks at refcount 1, or None (and no state change) when fewer
        than n are allocatable.  Plain free blocks are preferred; cached
        blocks are evicted oldest-first, each eviction notifying
        ``on_evict`` before the block is handed to its new owner."""
        if n > self.n_free:
            return None
        blocks, self._free = self._free[:n], self._free[n:]
        while len(blocks) < n:
            b, _ = self._lru.popitem(last=False)         # oldest first
            self.evictions += 1
            if self.on_evict is not None:
                self.on_evict(b)
            blocks.append(b)
        for b in blocks:
            self._ref[b] = 1
        return blocks

    def acquire(self, block: int):
        """Take a reference on a live or cached block (a prefix hit revives
        a cached block back to refcount 1).  Free/foreign blocks raise."""
        if block in self._ref:
            self._ref[block] += 1
        elif block in self._lru:
            del self._lru[block]
            self._ref[block] = 1
        else:
            raise ValueError(f"acquire of free / foreign block {block}")

    def release(self, block: int, cache: bool = False):
        """Drop one reference.  At refcount 0 the block returns to the free
        list, or — ``cache=True`` — parks on the LRU with its content
        matchable until evicted."""
        if block not in self._ref:
            raise ValueError(f"double free / foreign block {block}")
        self._ref[block] -= 1
        if self._ref[block] == 0:
            del self._ref[block]
            if cache:
                self._lru[block] = None                  # MRU end
            else:
                self._free.append(block)

    def refcount(self, block: int) -> int:
        return self._ref.get(block, 0)

    def free(self, blocks: Sequence[int]):
        """Back-compat bulk release without caching."""
        for b in blocks:
            self.release(b, cache=False)

    def check(self):
        """Invariant: free / live / cached partition the non-reserved
        blocks, and every live refcount is >= 1."""
        free, live, cached = set(self._free), set(self._ref), set(self._lru)
        assert len(self._free) == len(free)
        assert not (free & live) and not (free & cached) and not (live & cached)
        assert len(free) + len(live) + len(cached) == self.n_blocks - RESERVED
        assert all(c >= 1 for c in self._ref.values())


# ---------------------------------------------------------------------------
# Prefix index: content-addressed lookup of cached full blocks
# ---------------------------------------------------------------------------
class PrefixIndex:
    """Maps full-block content to resident physical blocks.

    A full block holding prompt tokens ``t[j*B:(j+1)*B]`` is keyed by the
    chain key ``(parent_block_id, tuple(tokens))`` — the rolling hash over
    (model, token-ids, position) of the design: the parent id pins the
    entire prefix before this block (recursively, back to the root
    sentinel -1), the token tuple pins this block's content, and Python's
    tuple hashing provides the rolling hash with exact-match semantics (no
    collision risk; the model never enters the key because one index serves
    exactly one engine/pool).

    ``deregister`` is recursive over the child tree: when a block is
    evicted and its id recycled, any indexed descendant's chain key would
    dangle on the stale parent id and could falsely match a future chain —
    so the whole subtree is forgotten with it.
    """

    def __init__(self):
        self._by_key: Dict[tuple, int] = {}
        self._children: Dict[int, List[int]] = {}
        self._tokens: Dict[int, tuple] = {}
        self._parent: Dict[int, int] = {}

    def __len__(self) -> int:
        return len(self._tokens)

    def register(self, parent: int, tokens: tuple, block: int) -> int:
        """Index ``block`` as holding ``tokens`` directly after ``parent``
        (-1 = chain root).  Returns the indexed block: the existing one on
        a duplicate-content race (the caller's block then stays private)."""
        key = (parent, tokens)
        if key in self._by_key:
            return self._by_key[key]
        self._by_key[key] = block
        self._tokens[block] = tokens
        self._parent[block] = parent
        self._children.setdefault(parent, []).append(block)
        return block

    def deregister(self, block: int):
        """Forget a block and (recursively) every indexed descendant."""
        for c in list(self._children.get(block, ())):
            self.deregister(c)
        self._children.pop(block, None)
        if block in self._tokens:
            parent = self._parent.pop(block)
            self._by_key.pop((parent, self._tokens.pop(block)), None)
            sibs = self._children.get(parent)
            if sibs is not None:
                sibs.remove(block)
                if not sibs:
                    del self._children[parent]

    def match(self, tokens: Sequence[int], block: int):
        """Longest indexed chain for a prompt: returns ``(chain, partial)``
        — ``chain`` the matched full blocks in order, ``partial`` the
        ``(block, lcp)`` best partial continuation (an indexed child whose
        first ``lcp >= 1`` tokens extend the match) or None."""
        chain: List[int] = []
        parent = -1
        i = 0
        while i + block <= len(tokens):
            nxt = self._by_key.get((parent, tuple(tokens[i:i + block])))
            if nxt is None:
                break
            chain.append(nxt)
            parent = nxt
            i += block
        best = None
        rest = tokens[i:]
        if rest:
            for c in self._children.get(parent, ()):
                ct = self._tokens[c]
                lcp = 0
                for a, b in zip(rest, ct):
                    if a != b:
                        break
                    lcp += 1
                if lcp and (best is None or lcp > best[1]):
                    best = (c, lcp)
        return chain, best


# ---------------------------------------------------------------------------
# Device-side pure pytree ops (safe to close over under jit)
# ---------------------------------------------------------------------------
def gather_view(pool, tables, block: int):
    """pool leaves (n, n_blocks*block, ...) + tables (B, nb) ->
    view leaves (n, B, nb*block, ...): the contiguous per-slot cache that
    the decode forward consumes."""
    flat = (tables[:, :, None] * block
            + jnp.arange(block, dtype=tables.dtype)).reshape(tables.shape[0], -1)
    return jax.tree.map(lambda leaf: leaf[:, flat], pool)


def scatter_decode(pool, new_view, slot, phys):
    """Write each slot's new entry (at view index ``slot``) back to its
    physical position ``phys`` (both (B,) int32; masked lanes point phys at
    the trash block)."""
    rows = jnp.arange(slot.shape[0])

    def s(pl, vw):
        entry = vw[:, rows, slot]                       # (n, B, ...)
        return pl.at[:, phys].set(entry.astype(pl.dtype))

    return jax.tree.map(s, pool, new_view)


def scatter_step(pool, updates, phys):
    """Write one fused decode step's new entries back in a single batched
    scatter: update leaves (n, B, ...) — the per-layer (k, v, pos) stacks
    the no-view fused decode collects — land at physical positions ``phys``
    (B,) int32 (masked lanes point phys at the trash block).  One scatter
    per leaf for ALL layers, mirroring ``scatter_decode``, instead of a
    per-layer pool update inside the forward."""
    def s(pl, up):
        return pl.at[:, phys].set(up.astype(pl.dtype))

    return jax.tree.map(s, pool, updates)


def scatter_prefill(pool, updates, phys_map):
    """Write whole prefill chunks: updates leaves (n, B, S, ...) land at
    flat physical indices ``phys_map`` (B, S) (padding lanes -> trash)."""
    flat = phys_map.reshape(-1)

    def s(pl, up):
        vals = up.reshape(up.shape[0], -1, *up.shape[3:])
        return pl.at[:, flat].set(vals.astype(pl.dtype))

    return jax.tree.map(s, pool, updates)


def copy_block(pool, src_rows, dst_rows, keep):
    """Copy-on-write: duplicate one block's worth of entries per slot from
    ``src_rows`` to ``dst_rows`` (both (B, block) flat physical indices;
    non-diverging rows point both at the trash block).  ``keep`` (B, block)
    bool masks how much of the source block is actually shared: integer
    (position) leaves outside ``keep`` land as -1, so the copied block is
    valid exactly up to the divergence point; float garbage past it is
    masked out of attention by those positions."""
    src = src_rows.reshape(-1)
    dst = dst_rows.reshape(-1)
    k = keep.reshape(-1)

    def c(leaf):
        vals = leaf[:, src]
        if jnp.issubdtype(leaf.dtype, jnp.integer):
            vals = jnp.where(k[None, :], vals, -1)
        return leaf.at[:, dst].set(vals)

    return jax.tree.map(c, pool)


def scatter_prefill_state(cache, updates, idx):
    """Write prefill kv into a *contiguous* (B, L, ...) cache (the draft
    model's store in serve/speculate.py): update leaves (n, B, S, ...) land
    at per-row ring indices ``idx`` (B, S); padding lanes carry idx = L and
    drop off the end."""
    rows = jnp.arange(idx.shape[0])[:, None]

    def s(leaf, up):
        return leaf.at[:, rows, idx].set(up.astype(leaf.dtype), mode="drop")

    return jax.tree.map(s, cache, updates)


def clear_positions(pool, idx):
    """Invalidate integer (position) leaves at flat indices ``idx`` so
    recycled blocks never leak a previous request's entries."""
    flat = idx.reshape(-1)

    def c(leaf):
        if not jnp.issubdtype(leaf.dtype, jnp.integer):
            return leaf
        return leaf.at[:, flat].set(-1)

    return jax.tree.map(c, pool)


def cache_with_dtype(tree, dtype):
    """Promote the floating leaves of an abstract cache tree to at least
    ``dtype`` (so an f32-parameter engine gets an f32 kv cache and the
    chunked-prefill hand-off stays bit-faithful to token-by-token decode);
    leaves already wider — e.g. the f32 recurrent states — are kept."""
    def one(p: Param):
        if jnp.issubdtype(jnp.dtype(p.dtype), jnp.floating):
            return dataclasses.replace(
                p, dtype=jnp.promote_types(p.dtype, dtype))
        return p
    return tree_map_params(one, tree)


# ---------------------------------------------------------------------------
# The manager
# ---------------------------------------------------------------------------
class PagedKVCache:
    """Host-side paged-cache bookkeeping for one engine.

    Block math: the family's abstract cache has length ``L_abs``
    (= min(max_len, window) for sliding-window archs).  Each slot's view is
    ``nb = ceil(L_abs / block)`` whole blocks, so the view length (and the
    decode ring modulus) is ``view_len = nb * block``.  A request needing
    ``t`` cache entries occupies ``ceil(min(t, view_len) / block)`` blocks,
    allocated at admission and freed when the request completes (eviction on
    completion).  The pool holds ``n_blocks`` physical blocks (default:
    2 reserved + full residency for every slot).
    """

    def __init__(self, cfg: ModelConfig, layout: Layout, batch_size: int,
                 max_len: int, block: int = 16,
                 n_blocks: Optional[int] = None, dtype=None,
                 prefix_cache: bool = False):
        from ..models import registry, transformer
        stack = registry.get_stack(cfg.family)
        dirs = transformer.entry_dirs()
        abstract = registry.stack_cache(stack, cfg, layout, dirs, 1, max_len)
        if not abstract:
            raise ValueError(f"{cfg.arch}: no length-indexed cache to page")
        lens = {leaf.shape[2] for leaf in
                jax.tree.leaves(abstract, is_leaf=is_param)}
        if len(lens) != 1:
            raise ValueError(f"{cfg.arch}: mixed cache lengths {lens} — "
                             "paged serving needs one common view length")
        (l_abs,) = lens
        if prefix_cache and l_abs < max_len:
            raise ValueError(
                f"{cfg.arch}: prefix sharing needs a non-wrapping view "
                f"(view {l_abs} < max_len {max_len}: the sliding-window "
                "ring would decode over shared blocks)")
        self.layout = layout
        self.block = block
        self.blocks_per_slot = -(-l_abs // block)
        self.view_len = self.blocks_per_slot * block
        self.B = batch_size
        self.n_blocks = n_blocks or (RESERVED
                                     + batch_size * self.blocks_per_slot)
        self.allocator = BlockAllocator(self.n_blocks)
        self.tables = np.zeros((batch_size, self.blocks_per_slot), np.int32)
        # _owned = the slot's private blocks in table order (its table is
        # _shared + _owned + null padding); _indexed marks private blocks
        # published to the prefix index at prefill completion
        self._owned: List[List[int]] = [[] for _ in range(batch_size)]
        self._shared: List[List[int]] = [[] for _ in range(batch_size)]
        self._indexed: List[set] = [set() for _ in range(batch_size)]
        self._prompt: List[tuple] = [() for _ in range(batch_size)]
        self._hit: List[int] = [0] * batch_size
        self._cow: List[Optional[Tuple[int, int]]] = [None] * batch_size
        self.prefix = PrefixIndex() if prefix_cache else None
        if prefix_cache:
            self.allocator.on_evict = self.prefix.deregister
        self.lookups = 0
        self.hits = 0
        self.tokens_reused = 0
        self._abstract_pool = self._pool_params(abstract, dtype)

    def _pool_params(self, abstract, dtype):
        phys = self.n_blocks * self.block

        def one(p: Param) -> Param:
            entries = tuple(p.spec or ()) + (None,) * (len(p.shape)
                                                       - len(p.spec or ()))
            floating = jnp.issubdtype(jnp.dtype(p.dtype), jnp.floating)
            return Param(
                shape=(p.shape[0], phys, *p.shape[3:]),
                spec=P(None, None, *entries[3:]),
                dtype=(dtype or p.dtype) if floating else p.dtype,
                init="zeros" if floating else "neg_ones")

        return tree_map_params(one, abstract)

    def init_pool(self):
        """Materialize the zeroed pool (positions start at -1: every block,
        including the null block, is invalid until written)."""
        return init_params(self._abstract_pool, jax.random.key(0),
                           layout=self.layout)

    # ---- admission / eviction -------------------------------------------
    def blocks_needed(self, n_tokens: int) -> int:
        return -(-min(n_tokens, self.view_len) // self.block)

    def _match(self, prompt: Sequence[int]):
        """Cap the raw index match to this prompt: at least one tail token
        must stay un-hit (the extend step needs a fresh position to produce
        logits from).  Returns (full_chain_blocks, cow, hit_len) where
        ``cow`` is (source_block, n_tokens_reused) or None."""
        Bk = self.block
        chain, partial = self.prefix.match(prompt, Bk)
        usable = len(prompt) - 1
        m_full = min(len(chain), usable // Bk)
        if len(chain) > m_full:
            # the chain over-covers: reuse the next chain block partially
            cow_src, r = chain[m_full], usable - m_full * Bk
        elif partial is not None:
            cow_src, r = partial[0], min(partial[1], usable - m_full * Bk)
        else:
            cow_src, r = -1, 0
        cow = (cow_src, r) if r > 0 else None
        return chain[:m_full], cow, m_full * Bk + (r if cow else 0)

    def can_admit(self, n_tokens: int, prompt: Sequence[int] = None) -> bool:
        shared = 0
        if self.prefix is not None and prompt:
            shared = len(self._match(prompt)[0])
        return (self.allocator.n_free
                >= self.blocks_needed(n_tokens) - shared)

    def admit(self, slot: int, n_tokens: int,
              prompt: Sequence[int] = None) -> bool:
        """Reserve the slot's blocks for a request needing ``n_tokens``
        cache entries; False (no state change) when the pool is exhausted.

        With the prefix index enabled and a ``prompt`` given, the longest
        cached prefix chain enters the slot's table by reference (each
        shared block acquired *before* the private allocation so the
        allocator cannot evict it in the same breath), a partially matching
        block is scheduled for copy-on-write (``cow_info``), and only the
        remaining blocks are freshly allocated."""
        if self._owned[slot] or self._shared[slot]:
            raise ValueError(f"slot {slot} already holds blocks")
        chain: List[int] = []
        cow = None
        hit = 0
        if self.prefix is not None and prompt:
            self.lookups += 1
            chain, cow, hit = self._match(prompt)
            for b in chain:
                self.allocator.acquire(b)
            if cow is not None:
                self.allocator.acquire(cow[0])   # pin the COW source until
                                                 # cow_done (engine copied it)
        blocks = self.allocator.alloc(self.blocks_needed(n_tokens)
                                      - len(chain))
        if blocks is None:
            for b in chain:
                self.allocator.release(b, cache=True)
            if cow is not None:
                self.allocator.release(cow[0], cache=True)
            return False
        if hit:
            self.hits += 1
            self.tokens_reused += hit
        self._shared[slot] = chain
        self._owned[slot] = blocks
        self._prompt[slot] = tuple(prompt) if prompt else ()
        self._hit[slot] = hit
        self._cow[slot] = cow
        self.tables[slot, :] = 0
        self.tables[slot, :len(chain)] = chain
        self.tables[slot, len(chain):len(chain) + len(blocks)] = blocks
        return True

    def hit_len(self, slot: int) -> int:
        """Prompt tokens this slot reuses from the prefix cache (the extend
        step starts at this offset)."""
        return self._hit[slot]

    def cow_info(self, slot: int) -> Optional[Tuple[int, int]]:
        """(source_block, n_tokens) the engine must copy into the slot's
        first private block before prefilling, or None."""
        return self._cow[slot]

    def cow_done(self, slot: int):
        """Drop the COW-source pin taken at admission (the engine has
        issued the device copy)."""
        if self._cow[slot] is not None:
            self.allocator.release(self._cow[slot][0], cache=True)
            self._cow[slot] = None

    def register_prefix(self, slot: int):
        """Publish the slot's fully written prompt blocks to the prefix
        index (called once the prompt's kv is resident).  Shared blocks are
        already indexed; each private full block is chained after its table
        predecessor.  A duplicate-content race keeps the existing entry and
        leaves this slot's copy private."""
        if self.prefix is None or not self._prompt[slot]:
            return
        prompt, Bk = self._prompt[slot], self.block
        n_shared = len(self._shared[slot])
        for j in range(n_shared, len(prompt) // Bk):
            b = int(self.tables[slot, j])
            parent = int(self.tables[slot, j - 1]) if j else -1
            got = self.prefix.register(parent, prompt[j * Bk:(j + 1) * Bk], b)
            if got == b:
                self._indexed[slot].add(b)

    def release(self, slot: int):
        """Eviction on completion: drop the slot's references.  Private
        blocks that made it into the prefix index (and all shared blocks)
        stay cached on the allocator's LRU, matchable until evicted;
        anonymous private blocks return straight to the free list."""
        self.cow_done(slot)
        for b in self._shared[slot]:
            self.allocator.release(b, cache=True)
        for b in self._owned[slot]:
            self.allocator.release(b, cache=b in self._indexed[slot])
        self._owned[slot] = []
        self._shared[slot] = []
        self._indexed[slot] = set()
        self._prompt[slot] = ()
        self._hit[slot] = 0
        self.tables[slot, :] = 0

    # ---- index computation (host) ---------------------------------------
    def phys(self, slot: int, pos: int) -> int:
        """Flat physical index of logical position ``pos`` for ``slot``
        (ring over the view length, like the contiguous decode cache)."""
        v = pos % self.view_len
        return int(self.tables[slot, v // self.block]) * self.block \
            + v % self.block

    def tables_device(self):
        return jnp.asarray(self.tables)

    def trash_row(self, row: int) -> int:
        return self.block + row % self.block

    def prefill_phys_map(self, rows: Sequence[Tuple[int, int]], n_rows: int,
                         s_pad: int) -> np.ndarray:
        """(n_rows, s_pad) flat physical targets for one prefill program:
        map row ``j`` is ``rows[j] = (slot, prompt_len)``, which keeps its
        last ``view_len`` positions (sliding-window ring); rows past
        ``len(rows)`` are padding, and every unkept lane goes to trash."""
        out = np.empty((n_rows, s_pad), np.int64)
        for j in range(n_rows):
            out[j, :] = self.trash_row(j)
        for j, (slot, n) in enumerate(rows):
            for p in range(max(0, n - self.view_len), min(n, s_pad)):
                out[j, p] = self.phys(slot, p)
        return out

    def extend_phys_map(self, rows: Sequence[Tuple[int, int, int]],
                        n_rows: int, s_pad: int) -> np.ndarray:
        """(n_rows, s_pad) flat physical targets for one extend program:
        map row ``j`` is ``rows[j] = (slot, offset, tail_len)``, whose tail
        tokens land at logical positions offset..offset+tail_len-1; rows
        past ``len(rows)`` and padding lanes go to trash.

        Positions past the view (a speculative verify near ``max_len``
        would wrap the ring onto live blocks) or landing on an unallocated
        (null) table entry also fall to trash: the engine's accepted-count
        clamp guarantees such tokens are never emitted, so their kv is
        droppable."""
        out = np.empty((n_rows, s_pad), np.int64)
        for j in range(n_rows):
            out[j, :] = self.trash_row(j)
        for j, (slot, off, n) in enumerate(rows):
            for t in range(min(n, s_pad)):
                p = off + t
                if p >= self.view_len \
                        or self.tables[slot, p // self.block] == 0:
                    continue
                out[j, t] = self.phys(slot, p)
        return out

    def cow_rows(self, slots: Sequence[int]):
        """(src, dst, keep) inputs for ``copy_block`` covering the given
        slots' pending copy-on-write divergences ((B, block) each; rows
        with nothing to copy shuttle trash -> trash)."""
        Bk = self.block
        lane = np.arange(Bk, dtype=np.int64)
        src = np.empty((self.B, Bk), np.int64)
        dst = np.empty((self.B, Bk), np.int64)
        keep = np.zeros((self.B, Bk), bool)
        any_cow = False
        for i in range(self.B):
            src[i, :] = self.trash_row(i)
            dst[i, :] = self.trash_row(i)
            if i in slots and self._cow[i] is not None:
                cow_src, r = self._cow[i]
                dst_block = int(self.tables[i, len(self._shared[i])])
                src[i, :] = cow_src * Bk + lane
                dst[i, :] = dst_block * Bk + lane
                keep[i, :] = lane < r
                any_cow = True
        return (src, dst, keep) if any_cow else None

    def clear_targets(self, slots: Sequence[int]) -> np.ndarray:
        """(B, blocks_per_slot*block) flat indices whose positions must be
        invalidated: the full allocated extent of the given slots; other
        rows target the trash block."""
        width = self.blocks_per_slot * self.block
        out = np.empty((self.B, width), np.int64)
        for i in range(self.B):
            out[i, :] = self.trash_row(i)
            if i in slots:
                for j, b in enumerate(self._owned[i]):
                    out[i, j * self.block:(j + 1) * self.block] = \
                        np.arange(b * self.block, (b + 1) * self.block)
        return out
