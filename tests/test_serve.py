"""Serving subsystem tests: block-table allocator invariants, scheduler
units, chunked-prefill vs token-by-token equivalence, greedy determinism,
and the slow multi-device (cube (2,2,2)) end-to-end engine runs."""
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def layout():
    from repro.core.topology import single_device_layout
    return single_device_layout("3d")


# ---------------------------------------------------------------------------
# Block allocator / block table invariants (pure host)
# ---------------------------------------------------------------------------
def test_block_allocator_invariants():
    from repro.serve.kvcache import BlockAllocator, RESERVED
    a = BlockAllocator(10)
    assert a.n_free == 10 - RESERVED
    b1 = a.alloc(3)
    b2 = a.alloc(4)
    assert b1 is not None and b2 is not None
    assert not (set(b1) & set(b2)), "a block was handed out twice"
    assert all(b >= RESERVED for b in b1 + b2), "reserved block leaked"
    assert a.alloc(2) is None          # only 1 free: refused atomically
    assert a.n_free == 1
    a.free(b1)
    assert a.n_free == 4
    with pytest.raises(ValueError):
        a.free(b1)                     # double free
    a.check()
    b3 = a.alloc(4)
    assert b3 is not None
    a.check()


def test_paged_cache_admit_release(layout):
    from repro.config import reduced
    from repro.configs.registry import get
    from repro.serve.kvcache import PagedKVCache, RESERVED
    cfg = reduced(get("tinyllama-1.1b"))
    kv = PagedKVCache(cfg, layout, batch_size=2, max_len=64, block=16)
    assert kv.view_len == 64 and kv.blocks_per_slot == 4
    assert kv.allocator.n_free == 2 * 4
    assert kv.admit(0, 20)             # 2 blocks
    assert kv.admit(1, 64)             # full residency
    assert kv.allocator.n_free == 8 - 2 - 4
    # tables point only at owned blocks; unallocated entries at null block 0
    assert set(kv.tables[0][kv.tables[0] > 0]) == set(kv._owned[0])
    assert (kv.tables[0] == 0).sum() == 2
    # physical index math: pos p -> owned block, in-block offset p % block
    p = kv.phys(0, 17)
    assert p // kv.block == kv._owned[0][1] and p % kv.block == 1
    kv.release(0)
    kv.allocator.check()
    assert (kv.tables[0] == 0).all()
    assert kv.allocator.n_free == 8 - 4
    with pytest.raises(ValueError):
        kv.admit(1, 8)                 # occupied slot cannot double-admit


# ---------------------------------------------------------------------------
# Scheduler units (pure host)
# ---------------------------------------------------------------------------
def _req(uid, n, priority=0, max_new=4):
    from repro.serve import Request
    return Request(uid=uid, prompt=list(range(2, 2 + n)), max_new=max_new,
                   priority=priority)


def test_scheduler_admission_rejection():
    from repro.serve.scheduler import Scheduler
    s = Scheduler(batch_size=2, max_len=16)
    bad = _req(0, 16)                  # prompt == max_len: can never fit
    assert not s.submit(bad)
    assert bad.done and "max_len" in bad.error and bad.out == []
    empty = _req(1, 0)
    assert not s.submit(empty) and empty.done
    ok = _req(2, 15)
    assert s.submit(ok) and not ok.done
    assert s.queue_depth() == 1


def test_scheduler_slot_refill_and_priority():
    from repro.serve.scheduler import Scheduler
    s = Scheduler(batch_size=2, max_len=64)
    r_fifo = [_req(i, 4) for i in range(3)]
    r_prio = _req(9, 4, priority=1)
    for r in r_fifo:
        s.submit(r)
    s.submit(r_prio)
    placed = s.fill([0, 1], can_place=lambda r, slot: True)
    # priority queue drains first, then FIFO order
    assert [r.uid for _, r in placed] == [9, 0]
    assert s.pending_prefill == [0, 1]
    # capacity gate: nothing placeable -> nothing placed, queue intact
    placed = s.fill([0], can_place=lambda r, slot: False)
    assert placed == [] and s.queue_depth() == 2


def test_scheduler_prefill_grouping():
    from repro.serve.scheduler import Scheduler
    s = Scheduler(batch_size=2, max_len=512, chunk_tokens=64)
    s.pending_prefill = [0, 1, 2]
    lens = {0: 100, 1: 10, 2: 300}
    group, s_pad = s.prefill_group(lens)
    # head always runs even beyond the 64/2=32-token budget; slot 2 waits
    assert group == [0, 1] and s_pad == 128
    assert s.pending_prefill == [2]
    group, s_pad = s.prefill_group(lens)
    assert group == [2] and s_pad == 512


# ---------------------------------------------------------------------------
# Chunked prefill == token-by-token (f32: <= 1e-4), greedy determinism
# ---------------------------------------------------------------------------
def test_chunked_prefill_matches_token_by_token(layout):
    import jax
    import jax.numpy as jnp
    from repro.config import reduced
    from repro.configs.registry import get
    from repro.core.params import init_params
    from repro.models import transformer
    from repro.serve import kvcache
    cfg = reduced(get("qwen3-4b"))
    params = init_params(transformer.abstract_params(cfg, layout),
                         jax.random.key(0), dtype=jnp.float32)
    prompt = list(range(5, 5 + 18))    # >= 16 tokens
    B, L = 1, 64

    # reference: one token per decode step through the contiguous cache
    tree = kvcache.cache_with_dtype(
        transformer.abstract_cache(cfg, layout, B, L), jnp.float32)
    cache = init_params(tree, jax.random.key(0))
    dec = jax.jit(lambda p, b, c: transformer.forward(
        cfg, layout, p, b, mode="decode", cache=c))
    for t, tok in enumerate(prompt):
        batch = {"token": jnp.asarray([[tok]], jnp.int32),
                 "pos": jnp.full((B,), t, jnp.int32)}
        logits, cache = dec(params, batch, cache)
    ref = np.asarray(logits, np.float32)[0]

    # chunked prefill: whole prompt in one call, logits at the last position
    got, _ = jax.jit(lambda p, b: transformer.prefill(cfg, layout, p, b))(
        params, {"tokens": jnp.asarray([prompt], jnp.int32),
                 "length": jnp.asarray([len(prompt)], jnp.int32)})
    err = float(np.max(np.abs(np.asarray(got, np.float32)[0] - ref)))
    assert err <= 1e-4, f"prefill/token-by-token logit mismatch: {err}"

    # and the engine's full generation trajectory matches greedy decode
    # continued from the reference cache
    from repro.serve import Engine, Request
    want = [int(ref.argmax())]
    pos = len(prompt)
    for _ in range(3):
        batch = {"token": jnp.asarray([[want[-1]]], jnp.int32),
                 "pos": jnp.full((B,), pos, jnp.int32)}
        logits, cache = dec(params, batch, cache)
        want.append(int(np.asarray(logits, np.float32)[0].argmax()))
        pos += 1
    eng = Engine(cfg, layout, params, batch_size=2, max_len=L)
    r = Request(uid=0, prompt=list(prompt), max_new=4)
    eng.run([r])
    assert r.out == want, (r.out, want)


@pytest.mark.parametrize("arch", ["mixtral-8x7b", "deepseek-v3-671b"])
def test_paged_families_chunked_matches_sequential(layout, arch):
    """MoE (windowed kv ring) and MLA (compressed-latent cache) paged
    serving: the chunked-prefill hand-off must reproduce the seed-style
    token-per-step prefill trajectory exactly (f32, greedy)."""
    import jax
    import jax.numpy as jnp
    from repro.config import reduced
    from repro.configs.registry import get
    from repro.core.params import init_params
    from repro.models import registry, transformer
    from repro.serve import Engine, Request
    cfg = reduced(get(arch))
    assert registry.serve_cache_mode(cfg) == "paged"
    params = init_params(transformer.abstract_params(cfg, layout),
                         jax.random.key(0), dtype=jnp.float32)
    outs = []
    for chunked in (True, False):
        eng = Engine(cfg, layout, params, batch_size=2, max_len=64,
                     chunked_prefill=chunked)
        reqs = [Request(uid=i, prompt=list(range(4, 4 + 17 + i)), max_new=4)
                for i in range(2)]
        eng.run(reqs)
        assert all(r.done and len(r.out) == 4 for r in reqs)
        outs.append([r.out for r in reqs])
    assert outs[0] == outs[1], f"{arch}: chunked != sequential prefill"


def test_engine_greedy_bit_deterministic(layout):
    import jax
    from repro.config import reduced
    from repro.configs.registry import get
    from repro.models import transformer
    from repro.serve import Engine, Request
    cfg = reduced(get("tinyllama-1.1b"))
    params = transformer.init(cfg, layout, jax.random.key(0))
    outs = []
    for _ in range(2):
        eng = Engine(cfg, layout, params, batch_size=2, max_len=64,
                     temperature=0.0)
        reqs = [Request(uid=i, prompt=[1, 2, 3, 4, 5], max_new=6)
                for i in range(4)]
        eng.run(reqs)
        assert all(r.done and len(r.out) == 6 for r in reqs)
        outs.append([r.out for r in reqs])
    assert outs[0] == outs[1], "temperature=0 must be bit-deterministic"
    assert len({tuple(o) for o in outs[0]}) == 1, \
        "identical prompts in different slots must decode identically"


def test_engine_rejects_overlong_prompt(layout):
    import jax
    from repro.config import reduced
    from repro.configs.registry import get
    from repro.models import transformer
    from repro.serve import Engine, Request
    cfg = reduced(get("tinyllama-1.1b"))
    params = transformer.init(cfg, layout, jax.random.key(0))
    eng = Engine(cfg, layout, params, batch_size=2, max_len=32)
    bad = Request(uid=0, prompt=list(range(2, 2 + 40)), max_new=4)
    good = Request(uid=1, prompt=[3, 4, 5], max_new=4)
    stats = eng.run([bad, good])
    # the too-long prompt is rejected at admission — it never wedges a slot
    assert bad.done and bad.error and bad.out == []
    assert good.done and len(good.out) == 4
    assert stats["rejected"] == 1 and stats["completed"] == 1


def test_sampling_filters():
    import jax
    import jax.numpy as jnp
    from repro.serve import sampling
    logits = jnp.asarray([[0.0, 1.0, 2.0, 3.0, 4.0]])
    greedy = sampling.make_sampler(0.0)
    assert int(greedy(logits, jax.random.key(0))[0]) == 4
    # top-k=2 restricts support to ids {3, 4}
    s = sampling.make_sampler(1.0, top_k=2)
    draws = {int(s(logits, jax.random.key(i))[0]) for i in range(20)}
    assert draws <= {3, 4} and draws
    # tight nucleus keeps only the argmax
    s = sampling.make_sampler(1.0, top_p=0.05)
    draws = {int(s(logits, jax.random.key(i))[0]) for i in range(10)}
    assert draws == {4}


# ---------------------------------------------------------------------------
# Multi-device end-to-end: 8 host devices, cube (2,2,2), paged + state
# ---------------------------------------------------------------------------
MULTIDEV_SCRIPT = r"""
import jax
from repro.config import reduced
from repro.configs.registry import get
from repro.core.topology import make_layout
from repro.models import transformer
from repro.serve import Engine, Request

assert len(jax.devices()) == 8
for arch in ("qwen3-4b", "xlstm-350m"):
    cfg = reduced(get(arch))
    lay = make_layout(1, 1, 8, "3d", cube=(2, 2, 2))
    params = transformer.init(cfg, lay, jax.random.key(0))

    def run():
        eng = Engine(cfg, lay, params, batch_size=4, max_len=64)
        reqs = [Request(uid=i, prompt=[2 + (i + j) % 17
                                       for j in range(4 + i % 5)],
                        max_new=6, priority=1 if i == 5 else 0)
                for i in range(6)]
        stats = eng.run(list(reqs))
        assert all(r.done and len(r.out) == 6 for r in reqs), arch
        assert stats["tokens"] == 36
        return [r.out for r in reqs], eng.paged

    outs1, paged = run()
    outs2, _ = run()
    assert outs1 == outs2, f"{arch}: nondeterministic multi-device decode"
    print(arch, "paged" if paged else "state", "ok")
print("ALL-OK")
"""


@pytest.mark.slow
def test_serve_engine_multidev_cube():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(ROOT, "src"))
    proc = subprocess.run([sys.executable, "-c", MULTIDEV_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=3000)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-2000:]
    assert "ALL-OK" in proc.stdout


# ---------------------------------------------------------------------------
# Ref-counting / LRU allocator (prefix sharing substrate)
# ---------------------------------------------------------------------------
def test_block_allocator_refcount_lru():
    from repro.serve.kvcache import BlockAllocator, RESERVED
    a = BlockAllocator(8)                       # 6 usable
    evicted = []
    a.on_evict = evicted.append
    b1, b2 = a.alloc(1), a.alloc(1)
    (b1,), (b2,) = b1, b2
    a.acquire(b1)                               # second owner
    assert a.refcount(b1) == 2
    a.release(b1)
    assert a.refcount(b1) == 1                  # still live: not allocatable
    got = a.alloc(4)
    assert got is not None and b1 not in got and b2 not in got
    assert a.alloc(1) is None                   # all 6 live
    a.release(b1, cache=True)                   # park on the LRU
    assert a.refcount(b1) == 0 and a.n_free == 1
    a.acquire(b1)                               # prefix hit revives it
    assert a.refcount(b1) == 1 and a.n_free == 0
    a.release(b1, cache=True)
    a.release(b2, cache=True)                   # LRU order: b1 older than b2
    (victim,) = a.alloc(1)
    assert victim == b1 and evicted == [b1]     # oldest evicted, hook fired
    assert a.evictions == 1
    with pytest.raises(ValueError):
        a.acquire(victim + 100)                 # foreign block
    a.check()


def test_block_allocator_random_walk():
    """Seeded random acquire/release/alloc walk against a pure-python
    refcount model: never double-hands a block, never leaks."""
    from repro.serve.kvcache import BlockAllocator, RESERVED
    rng = np.random.default_rng(7)
    a = BlockAllocator(12)
    ref = {}                                    # model: block -> refcount
    cached = []
    for _ in range(400):
        op = rng.integers(0, 4)
        if op == 0:                             # alloc
            n = int(rng.integers(1, 4))
            got = a.alloc(n)
            if got is None:
                # allocatable = everything not live (cached blocks evictable)
                assert 12 - 2 - len(ref) < n
            else:
                for b in got:
                    assert b not in ref, "live block handed out twice"
                    if b in cached:
                        cached.remove(b)
                    ref[b] = 1
        elif op == 1 and ref:                   # release a live ref
            b = int(rng.choice(sorted(ref)))
            cache = bool(rng.integers(0, 2))
            a.release(b, cache=cache)
            ref[b] -= 1
            if ref[b] == 0:
                del ref[b]
                if cache:
                    cached.append(b)
        elif op == 2 and (ref or cached):       # acquire live or cached
            pool = sorted(ref) + cached
            b = int(rng.choice(pool))
            a.acquire(b)
            if b in cached:
                cached.remove(b)
                ref[b] = 1
            else:
                ref[b] += 1
        else:                                   # cross-check
            a.check()
            assert {b: c for b, c in ref.items()} == a._ref
            assert a.n_free == 12 - 2 - len(ref)
    for b in sorted(ref):                       # drain: no block leaks
        for _ in range(ref[b]):
            a.release(b)
    a.check()
    assert a.n_free == 12 - 2


# ---------------------------------------------------------------------------
# Prefix index (content-addressed chain lookup)
# ---------------------------------------------------------------------------
def test_prefix_index_chain_match_and_deregister():
    from repro.serve.kvcache import PrefixIndex
    ix = PrefixIndex()
    t = list(range(40))
    b0 = ix.register(-1, tuple(t[0:4]), 10)
    b1 = ix.register(b0, tuple(t[4:8]), 11)
    assert (b0, b1) == (10, 11)
    assert ix.register(-1, tuple(t[0:4]), 99) == 10   # duplicate: existing wins
    assert len(ix) == 2
    chain, partial = ix.match(t[:10], 4)
    assert chain == [10, 11] and partial is None
    # a child extends the chain partially
    ix.register(11, tuple(t[8:12]), 12)
    chain, partial = ix.match(t[:8] + [8, 9, 77, 78], 4)
    assert chain == [10, 11] and partial == (12, 2)
    # divergence inside the chain stops the walk
    chain, _ = ix.match([0, 1, 2, 3, 4, 99, 6, 7], 4)
    assert chain == [10]
    # deregister is recursive: the whole subtree under 10 is forgotten
    ix.deregister(10)
    assert len(ix) == 0
    assert ix.match(t[:10], 4) == ([], None)


def test_paged_cache_prefix_sharing_and_cow(layout):
    from repro.config import reduced
    from repro.configs.registry import get
    from repro.serve.kvcache import PagedKVCache
    cfg = reduced(get("tinyllama-1.1b"))
    kv = PagedKVCache(cfg, layout, batch_size=2, max_len=64, block=16,
                      prefix_cache=True)
    prompt = [3 + j % 13 for j in range(50)]
    assert kv.admit(0, 64, prompt)
    assert kv.hit_len(0) == 0 and kv.cow_info(0) is None
    kv.register_prefix(0)                       # 50 tokens -> 3 full blocks
    assert len(kv.prefix) == 3
    kv.release(0)                               # indexed blocks park on LRU
    kv.allocator.check()
    # identical prompt: hits 48 of 50 (one tail token must stay fresh)
    assert kv.admit(1, 64, prompt)
    assert kv.hit_len(1) == 48 and len(kv._shared[1]) == 3
    assert kv.cow_info(1) is None
    shared = list(kv._shared[1])
    assert all(kv.allocator.refcount(b) == 1 for b in shared)
    # divergence inside block 3: chain match 2 blocks + partial COW of 8
    p2 = prompt[:40] + [201, 202, 203, 204]
    assert kv.admit(0, 64, p2)
    assert len(kv._shared[0]) == 2
    src, n = kv.cow_info(0)
    assert n == 8 and src == shared[2]          # 40 - 2*16 = 8 reused tokens
    assert kv.hit_len(0) == 40
    assert kv.allocator.refcount(src) == 2      # slot 1's table + COW pin
    rows = kv.cow_rows([0])
    assert rows is not None
    s, d, keep = rows
    assert keep[0].sum() == 8 and not keep[1].any()
    kv.cow_done(0)
    assert kv.allocator.refcount(src) == 1 and kv.cow_info(0) is None
    assert kv.lookups == 3 and kv.hits == 2 and kv.tokens_reused == 88
    kv.release(0)
    kv.release(1)
    # exhaustive reallocation evicts every cached block and empties the index
    assert kv.admit(0, 64) and kv.admit(1, 64)
    assert len(kv.prefix) == 0 and kv.allocator.n_free == 0
    assert kv.allocator.evictions >= 3
    kv.allocator.check()


# ---------------------------------------------------------------------------
# Extend (mid-sequence chunk append) vs full prefill equivalence
# ---------------------------------------------------------------------------
def test_extend_matches_prefill(layout):
    import jax
    import jax.numpy as jnp
    from repro.config import reduced
    from repro.configs.registry import get
    from repro.core.params import init_params
    from repro.models import registry, transformer
    cfg = reduced(get("tinyllama-1.1b"))
    params = init_params(transformer.abstract_params(cfg, layout),
                         jax.random.key(0), dtype=jnp.float32)
    B, L, S = 2, 16, 8
    rng = np.random.default_rng(3)
    toks = rng.integers(2, cfg.vocab, (B, L + S)).astype(np.int32)
    _, kv = transformer.prefill(
        cfg, layout, params,
        {"tokens": jnp.asarray(toks[:, :L]),
         "length": jnp.full((B,), L, jnp.int32)})
    pos2d = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (B, L))
    view = registry.pack_prefill_cache(cfg, kv, pos2d)
    # ragged extend: row 0 appends S fresh tokens, row 1 only 5
    lens = jnp.asarray([S, 5], jnp.int32)
    logits, _, _ = transformer.extend(
        cfg, layout, params,
        {"tokens": jnp.asarray(toks[:, L:]),
         "offset": jnp.full((B,), L, jnp.int32), "length": lens}, view)
    last = jnp.take_along_axis(logits, (lens - 1)[:, None, None], axis=1)[:, 0]
    ref, _ = transformer.prefill(
        cfg, layout, params,
        {"tokens": jnp.asarray(toks), "length": L + lens})
    diff = float(jnp.max(jnp.abs(last.astype(jnp.float32)
                                 - ref.astype(jnp.float32))))
    assert diff < 1e-4, f"extend diverged from full prefill: {diff:.2e}"


# ---------------------------------------------------------------------------
# Engine spans: the order of a step's phases, their args, one device_get
# ---------------------------------------------------------------------------
PHASES = ["serve.admit", "serve.prepare", "serve.dispatch", "serve.wait",
          "serve.emit"]


def test_engine_step_spans_in_order_with_one_device_get(layout,
                                                        monkeypatch):
    import jax
    from repro.config import reduced
    from repro.configs.registry import get
    from repro.models import transformer
    from repro.obs import Tracer
    from repro.serve import Engine, Request
    from repro.serve.scheduler import pad_bucket
    cfg = reduced(get("tinyllama-1.1b"))
    params = transformer.init(cfg, layout, jax.random.key(0))
    tr = Tracer(annotate=False)
    eng = Engine(cfg, layout, params, batch_size=2, max_len=64, tracer=tr)
    prompts = [[1, 2, 3, 4, 5], list(range(3, 14))]
    for i, p in enumerate(prompts):
        eng.submit(Request(uid=i, prompt=p, max_new=4))
    gets = []
    device_get = jax.device_get
    monkeypatch.setattr(jax, "device_get",
                        lambda x: gets.append(1) or device_get(x))
    steps = []
    for kind in ("prefill", "decode"):
        n_gets, n_events = len(gets), len(tr.events)
        eng.step()
        assert len(gets) - n_gets == 1, kind        # no sync added
        evs = [e for e in tr.events[n_events:]
               if e["ev"] == "span" and e["track"] == "engine"]
        (step,) = [e for e in evs if e["name"] == "serve.step"]
        inner = sorted((e for e in evs if e is not step),
                       key=lambda e: e["ts"])
        assert [e["name"] for e in inner] == PHASES, kind
        assert all(step["ts"] <= e["ts"] and e["ts"] + e["dur"]
                   <= step["ts"] + step["dur"] for e in inner)
        assert {"queue", "slots", "compile_n", "compile_s"} <= \
            set(step["args"])
        steps.append({e["name"]: e.get("args", {}) for e in inner})
    lens = [len(p) for p in prompts]
    # prefill: one program per row (batch-shard degree 1 on one device),
    # each padded to its own bucket
    assert steps[0]["serve.prepare"] == {
        "rows": 2, "tokens": sum(lens), "runs": 2,
        "padded": sum(pad_bucket(n) for n in lens)}
    # decode: the paged-decode kernel walks each row up to its last live
    # block (positions 5 and 11 both lie in the first block of 16) of the
    # 2 rows x 4 blocks its tables span
    assert steps[1]["serve.prepare"] == {"rows": 2, "live": sum(lens),
                                         "walked": 2, "blocks": 2 * 4}


# ---------------------------------------------------------------------------
# Engine fast paths: prefix cache and speculative decoding vs the baseline
# ---------------------------------------------------------------------------
def test_engine_prefix_and_speculative_match_baseline(layout):
    import jax
    import jax.numpy as jnp
    from repro.config import reduced
    from repro.configs.registry import get
    from repro.core.params import init_params
    from repro.models import transformer
    from repro.serve import Engine, Request
    from repro.serve.speculate import DraftSpec
    cfg = reduced(get("qwen3-4b"))
    params = init_params(transformer.abstract_params(cfg, layout),
                         jax.random.key(0), dtype=jnp.float32)
    shared = list(range(7, 7 + 32))             # two full blocks @ block=16
    prompts = [shared + [100 + i, 101 + i] for i in range(3)]
    prompts.append(shared[:20] + [55, 56])      # partial-block COW divergence

    def run(eng):
        reqs = [Request(uid=i, prompt=list(p), max_new=5)
                for i, p in enumerate(prompts)]
        stats = eng.run(reqs)
        assert all(r.done and not r.error for r in reqs), \
            [r.error for r in reqs]
        return [r.out for r in reqs], stats

    base, _ = run(Engine(cfg, layout, params, batch_size=2, max_len=64))

    pfx = Engine(cfg, layout, params, batch_size=2, max_len=64,
                 prefix_cache=True)
    out, st = run(pfx)
    assert out == base, "prefix-cache engine diverged from baseline"
    assert st["prefix_hits"] >= 2 and st["prefix_tokens_reused"] > 0
    out2, st2 = run(pfx)                        # warm index: every prompt hits
    assert out2 == base
    assert st2["prefix_hits"] == len(prompts)
    pfx.kv.allocator.check()

    spec = Engine(cfg, layout, params, batch_size=2, max_len=64,
                  draft=DraftSpec(cfg, layout, params, gamma=3))
    out3, st3 = run(spec)
    assert out3 == base, "speculative engine diverged at temperature 0"
    assert st3["spec_steps"] > 0 and st3["accepted_mean"] >= 1.0
