"""Prefill programs over the admitted prompts only: the engine runs a
prefill group as programs of D rows (the layout's batch-shard degree), each
padded to its own rows' bucket.  Against the B-row program over every slot
at the group's bucket, that must give the same first tokens and the same
pool contents; on the (2, 2, 2) cube D = 2; and after one request per
bucket no burst compiles anything new."""
import dataclasses
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


def _config(arch):
    """The reduced config, with a dropless expert capacity on MoE stacks:
    capacity-based dispatch sizes each expert's buffer from the program's
    token count, so padding rows would change which tokens overflow; with
    capacity = tokens nothing overflows, and only the program's shape
    differs between the two sides."""
    from repro.config import reduced
    from repro.configs.registry import get
    cfg = reduced(get(arch))
    if cfg.moe is not None:
        m = cfg.moe
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            m, capacity_factor=m.n_experts / m.top_k))
    return cfg


def _b_row_program(eng):
    """The B-row program over the engine's next prefill group: every slot
    a row, all padded to the group's bucket, through ``transformer.prefill``
    (or ``transformer.extend`` over the gathered view with the prefix
    cache), scattered into the pool.  Returns ({slot: first token}, pool)."""
    import jax.numpy as jnp
    from repro.models import registry, transformer
    from repro.serve import kvcache
    cfg, kv, B = eng.cfg, eng.kv, eng.B
    eng._admit()
    off = {s: kv.hit_len(s) if eng.prefix else 0
           for s in eng.scheduler.pending_prefill}
    lens = {s: len(eng.slots[s].prompt) - off[s] for s in off}
    group, s_pad = eng.scheduler.prefill_group(lens)
    tokens = np.zeros((B, s_pad), np.int32)
    length = np.zeros((B,), np.int32)
    offset = np.zeros((B,), np.int32)
    phys = np.empty((B, s_pad), np.int64)
    for i in range(B):
        phys[i] = kv.trash_row(i)
    for s in group:
        p = eng.slots[s].prompt
        tokens[s, :lens[s]] = p[off[s]:]
        length[s], offset[s] = lens[s], off[s]
        for t in range(lens[s]):
            if off[s] + t >= len(p) - kv.view_len:     # the ring keeps these
                phys[s, t] = kv.phys(s, off[s] + t)
    if eng.prefix:
        view = kvcache.gather_view(eng.pool, kv.tables_device(), kv.block)
        logits, out, pos = transformer.extend(
            cfg, eng.layout, eng.params,
            {"tokens": jnp.asarray(tokens), "offset": jnp.asarray(offset),
             "length": jnp.asarray(length)}, view)
        last = logits[np.arange(B), np.maximum(length - 1, 0)]
    else:
        last, out = transformer.prefill(
            cfg, eng.layout, eng.params,
            {"tokens": jnp.asarray(tokens), "length": jnp.asarray(length)})
        p = np.arange(s_pad)[None, :]
        pos = jnp.asarray(np.where(p < length[:, None], p, -1))
    updates = registry.pack_prefill_cache(cfg, out, pos)
    pool = kvcache.scatter_prefill(eng.pool, updates, jnp.asarray(phys))
    last = np.asarray(last, np.float32)
    return {s: int(last[s].argmax()) for s in group}, pool


def _live(eng, slots):
    """Flat physical indices the slots' prompts hold in the pool."""
    kv = eng.kv
    return np.asarray(sorted(
        kv.phys(s, p) for s in slots
        for p in range(max(0, len(eng.slots[s].prompt) - kv.view_len),
                       len(eng.slots[s].prompt))))


# (arch, prefix cache, max_len, shared prefix, prompt lengths of the group):
# three buckets each; the sliding-window ring (reduced window 64) gets a
# prompt longer than its view; the extend cases share two full blocks
CASES = [("qwen3-4b", False, 128, 0, (5, 20, 40)),
         ("deepseek-v3-671b", False, 128, 0, (5, 20, 40)),
         ("mixtral-8x7b", False, 128, 0, (5, 20, 70)),
         ("qwen3-4b", True, 128, 32, (5, 20, 40)),
         ("mixtral-8x7b", True, 64, 32, (3, 10, 20))]


@pytest.mark.parametrize("arch,prefix,max_len,shared,tails", CASES,
                         ids=[f"{c[0]}-{'extend' if c[1] else 'prefill'}"
                              for c in CASES])
def test_row_programs_match_the_b_row_program(layout, arch, prefix, max_len,
                                              shared, tails):
    import jax
    import jax.numpy as jnp
    from repro.core.params import init_params
    from repro.models import transformer
    from repro.serve import Engine, Request
    cfg = _config(arch)
    params = init_params(transformer.abstract_params(cfg, layout),
                         jax.random.key(0), dtype=jnp.float32)
    rng = np.random.default_rng(7)
    head = [int(t) for t in rng.integers(2, cfg.vocab, shared)]
    prompts = [head + [int(t) for t in rng.integers(2, cfg.vocab, n)]
               for n in tails]
    engines = []
    for _ in range(2):
        eng = Engine(cfg, layout, params, batch_size=4, max_len=max_len,
                     prefix_cache=prefix)
        if prefix:      # seed the prefix index with the shared blocks
            eng.run([Request(uid=9, prompt=head + [3], max_new=2)])
        for i, p in enumerate(prompts):
            eng.submit(Request(uid=i, prompt=list(p), max_new=4))
        engines.append(eng)
    new, old = engines
    new.step()                                  # admit + one prefill step
    group = [s for s, r in enumerate(new.slots) if r is not None]
    assert len(group) == len(prompts)
    if prefix:
        assert all(new.kv.hit_len(s) == shared for s in group)
    assert not new.scheduler.pending_prefill    # one group, three programs
    want, pool = _b_row_program(old)
    assert {s: new.slots[s].out[0] for s in group} == want
    idx = _live(new, group)
    assert np.array_equal(new.kv.tables, old.kv.tables)
    for a, b in zip(jax.tree.leaves(new.pool), jax.tree.leaves(pool)):
        np.testing.assert_allclose(np.asarray(a[:, idx], np.float32),
                                   np.asarray(b[:, idx], np.float32),
                                   rtol=1e-5, atol=1e-5)


CUBE_SCRIPT = r"""
import jax
from repro.config import reduced
from repro.configs.registry import get
from repro.core.topology import make_layout, single_device_layout
from repro.models import transformer
from repro.obs import Tracer
from repro.serve import Engine, Request

assert len(jax.devices()) == 8
cfg = reduced(get("qwen3-4b"))
lay1 = single_device_layout("3d")
cube = make_layout(1, 1, 8, "3d", cube=(2, 2, 2))
params = jax.device_get(transformer.init(cfg, lay1, jax.random.key(0)))
prompts = [[2 + (7 * i + 3 * j) % 61 for j in range(n)]
           for i, n in enumerate((5, 20, 40))]


def serve(lay):
    tr = Tracer(annotate=False)
    eng = Engine(cfg, lay, params, batch_size=4, max_len=64, tracer=tr)
    reqs = [Request(uid=i, prompt=p, max_new=4)
            for i, p in enumerate(prompts)]
    eng.run(reqs)
    assert all(r.done and len(r.out) == 4 for r in reqs)
    prep = [e["args"] for e in tr.events if e.get("name") == "serve.prepare"
            and "padded" in e.get("args", {})]
    return eng._prefill_rows, prep, [r.out for r in reqs]


d1, prep1, out1 = serve(lay1)
d2, prep2, out2 = serve(cube)
assert (d1, d2) == (1, 2), (d1, d2)
assert [a["runs"] for a in prep1] == [3], prep1
# rows sorted by length: (5, 20) pad to 32, (40,) and a padding row to 64
assert [(a["runs"], a["padded"]) for a in prep2] == [(2, 2 * 32 + 2 * 64)], \
    prep2
assert out1 == out2, (out1, out2)
print("ALL-OK")
"""


def test_cube_runs_a_group_two_rows_a_program():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(ROOT, "src"))
    proc = subprocess.run([sys.executable, "-c", CUBE_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    assert "ALL-OK" in proc.stdout


def test_no_compile_after_one_request_per_bucket(layout):
    import jax
    from repro.config import reduced
    from repro.configs.registry import get
    from repro.models import transformer
    from repro.obs.trace import COMPILES
    from repro.serve import Engine, Request
    cfg = reduced(get("qwen3-4b"))
    params = transformer.init(cfg, layout, jax.random.key(0))
    eng = Engine(cfg, layout, params, batch_size=4, max_len=128)
    for b in (8, 16, 32, 64):                  # one request per bucket
        req = Request(uid=-b, prompt=[(7 * j) % cfg.vocab for j in range(b)],
                      max_new=3)
        eng.run([req])
        assert req.done and not req.error
    n = COMPILES.n
    burst = [Request(uid=i, max_new=3,
                     prompt=[(3 * j + i) % cfg.vocab for j in range(k)])
             for i, k in enumerate((5, 60, 12, 33, 9, 20, 47))]
    eng.run(burst)
    assert all(r.done and len(r.out) == 3 for r in burst)
    assert COMPILES.n == n


def test_prefill_pad_share_reads_the_dispatched_positions(layout,
                                                          monkeypatch):
    """The benchmark's ``prefill_pad_share.serve`` reader on the engine's
    ``serve.prepare`` spans: padding is counted against the positions the
    row programs ran (each row at its own bucket, D = 1 here), which are
    fewer than the B-row program's B x the group's bucket."""
    import jax
    from repro.config import reduced
    from repro.configs.registry import get
    from repro.models import transformer
    from repro.obs import Tracer
    from repro.serve import Engine, Request
    from repro.serve.scheduler import pad_bucket
    monkeypatch.syspath_prepend(ROOT)
    from bench import run as harness
    from bench.metrics import _program
    cfg = reduced(get("qwen3-4b"))
    params = transformer.init(cfg, layout, jax.random.key(0))
    tr = Tracer(annotate=False)
    eng = Engine(cfg, layout, params, batch_size=4, max_len=64,
                 prefill_chunk=64, tracer=tr)
    groups, group_of = [], eng.scheduler.prefill_group

    def recorded(lens):
        group, s_pad = group_of(lens)
        groups.append((sum(lens[s] for s in group),
                       sum(pad_bucket(lens[s]) for s in group),
                       eng.B * s_pad))
        return group, s_pad

    eng.scheduler.prefill_group = recorded
    reqs = [Request(uid=i, max_new=3,
                    prompt=[(3 * j + i) % cfg.vocab for j in range(n)])
            for i, n in enumerate((5, 9, 12, 20, 7, 15, 24))]
    eng.run(reqs)
    assert all(r.done and len(r.out) == 3 for r in reqs)
    assert len(groups) >= 2
    sp = [_program.Span(e["name"], 0.0, 0.0, e["args"]) for e in tr.events
          if e.get("name") == "serve.prepare"]
    tokens, padded, b_rows = (sum(g[i] for g in groups) for i in range(3))
    assert padded < b_rows
    got = harness.load_metric("prefill_pad_share.serve").read(
        {"program": sp})
    assert got == pytest.approx(100.0 * (1.0 - tokens / padded))
