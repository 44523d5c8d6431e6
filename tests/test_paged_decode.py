"""Fused paged flash-decode tests: the kernel (interpret mode) and the jnp
fallback against a dense gather oracle across block sizes / ragged lengths /
GQA groups / windows / null+recycled entries, the softmax-residual shard
combine, the engine-level fused-vs-gather_view equivalence (including the
windowed ring wrap), the batched scatter_step write-back, the kernel
install hooks, and the multi-device async-overlap training equivalence."""
import math
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

F32 = None  # populated lazily (conftest sets sys.path before jax import)


def _oracle(q, k_pool, v_pool, pos_pool, tables, cur, *, block, window,
            scale=None):
    """Dense reference: gather the view through the tables, mask by logical
    position, plain f32 softmax."""
    import jax.numpy as jnp
    B, nq, dk = q.shape
    nkv = k_pool.shape[1]
    g = nq // nkv
    if scale is None:
        scale = 1.0 / math.sqrt(dk)
    flat = (tables[:, :, None] * block
            + jnp.arange(block, dtype=tables.dtype)).reshape(B, -1)
    k = k_pool[flat].astype(jnp.float32)
    v = v_pool[flat].astype(jnp.float32)
    kp = pos_pool[flat]
    valid = (kp >= 0) & (kp <= cur[:, None])
    if window:
        valid &= (cur[:, None] - kp) < window
    qf = q.reshape(B, nkv, g, dk).astype(jnp.float32) * scale
    s = jnp.einsum("bhgd,blhd->bhgl", qf, k)
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    p = jnp.where(valid[:, None, None, :], p, 0.0)
    p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    return jnp.einsum("bhgl,blhd->bhgd", p, v).reshape(B, nq, -1)


def _make_case(key, *, B, nq, nkv, dk, dv, block, nb, n_blocks, ragged=True):
    """Build a pool + tables with per-slot distinct physical blocks, a null
    block 0, unwritten (-1) tails, and a recycled block holding positions
    beyond every slot's cur (must be masked)."""
    import jax
    import jax.numpy as jnp
    ks = jax.random.split(key, 4)
    phys = n_blocks * block
    k_pool = jax.random.normal(ks[0], (phys, nkv, dk), jnp.float32)
    v_pool = jax.random.normal(ks[1], (phys, nkv, dv), jnp.float32)
    pos_pool = np.full((phys,), -1, np.int32)
    tables = np.zeros((B, nb), np.int32)          # pad slots -> null block 0
    cur = np.zeros((B,), np.int32)
    nxt = 2                                       # 0 = null, 1 = recycled
    for b in range(B):
        L = (b * 7 + 5) % (nb * block) + 1 if ragged else nb * block - 1
        cur[b] = L - 1
        for j in range((L + block - 1) // block):
            tables[b, j] = nxt
            for e in range(block):
                p = j * block + e
                if p < L:
                    pos_pool[nxt * block + e] = p
            nxt += 1
            assert nxt <= n_blocks
    # recycled block: stale positions larger than any cur — masked by kp<=cur
    pos_pool[block:2 * block] = int(cur.max()) + 100
    return (k_pool, v_pool, jnp.asarray(pos_pool), jnp.asarray(tables),
            jnp.asarray(cur))


@pytest.mark.parametrize("shape", [
    # (B, nq, nkv, dk, dv, block, nb, n_blocks)
    (3, 8, 2, 32, 32, 8, 5, 16),
    (2, 4, 1, 16, 48, 4, 7, 16),      # MQA, dv != dk (MLA-shaped)
    (2, 8, 8, 16, 16, 16, 3, 8),      # MHA
])
@pytest.mark.parametrize("window", [0, 10])
@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_paged_kernel_matches_oracle(shape, window, impl):
    import jax
    import jax.numpy as jnp
    from repro.kernels.paged_decode import paged_flash_decode
    B, nq, nkv, dk, dv, block, nb, n_blocks = shape
    k_pool, v_pool, pos_pool, tables, cur = _make_case(
        jax.random.key(0), B=B, nq=nq, nkv=nkv, dk=dk, dv=dv, block=block,
        nb=nb, n_blocks=n_blocks)
    q = jax.random.normal(jax.random.key(9), (B, nq, dk), jnp.float32)
    got = paged_flash_decode(q, k_pool, v_pool, pos_pool, tables, cur,
                             block=block, window=window, impl=impl,
                             interpret=True)
    want = _oracle(q, k_pool, v_pool, pos_pool, tables, cur, block=block,
                   window=window)
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want)))
    assert err < 1e-5, err


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
@pytest.mark.parametrize("nshards", [2, 4])
def test_paged_kernel_residual_combine(impl, nshards):
    """Sharding the table columns and psum-combining (m, l, acc) residuals
    must reproduce the unsharded softmax — including null-block padding and
    shards whose every entry is masked."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.paged_decode import paged_flash_decode
    B, nq, nkv, dk, dv, block, nb, n_blocks = 3, 8, 2, 32, 32, 8, 5, 16
    k_pool, v_pool, pos_pool, tables, cur = _make_case(
        jax.random.key(1), B=B, nq=nq, nkv=nkv, dk=dk, dv=dv, block=block,
        nb=nb, n_blocks=n_blocks)
    q = jax.random.normal(jax.random.key(2), (B, nq, dk), jnp.float32)
    full = paged_flash_decode(q, k_pool, v_pool, pos_pool, tables, cur,
                              block=block, impl=impl, interpret=True)
    pad = (-tables.shape[1]) % nshards
    tbl = jnp.pad(tables, ((0, 0), (0, pad)))     # null block 0 = masked
    nb_loc = tbl.shape[1] // nshards
    parts = [paged_flash_decode(q, k_pool, v_pool, pos_pool,
                                tbl[:, s * nb_loc:(s + 1) * nb_loc], cur,
                                block=block, impl=impl, interpret=True,
                                return_residuals=True)
             for s in range(nshards)]
    m = jnp.max(jnp.stack([p[1] for p in parts]), axis=0)
    o = sum(p[0] * jnp.exp(p[1] - m)[..., None] for p in parts)
    l = sum(p[2] * jnp.exp(p[1] - m) for p in parts)
    got = o / jnp.maximum(l, 1e-30)[..., None]
    err = float(jnp.max(jnp.abs(got - full.astype(jnp.float32))))
    assert err < 1e-5, err


def test_live_blocks_bounds_each_row():
    """live_blocks: cdiv(cur + 1, block) columns, clipped to the table (a
    wrapped ring walks it all), 0 for a row the step does not decode, and
    a shard's part of the bound for a column slice; numpy and jax agree."""
    import jax.numpy as jnp
    from repro.kernels.paged_decode import live_blocks
    cur = np.array([0, 3, 4, 7, 19, 40, 9], np.int32)
    active = np.array([1, 1, 1, 1, 1, 1, 0], bool)
    want = [1, 1, 2, 2, 5, 5, 0]
    assert live_blocks(cur, active, block=4, nb=5).tolist() == want
    assert live_blocks(jnp.asarray(cur), jnp.asarray(active), block=4,
                       nb=5).tolist() == want
    # host counting with every listed row decoded
    assert live_blocks(cur[:6], True, block=4, nb=5).tolist() == want[:6]
    # two shards of 3 columns (the table padded to 6): the parts add up
    parts = [live_blocks(cur, active, block=4, nb=3, start=3 * s)
             for s in range(2)]
    assert parts[0].tolist() == [1, 1, 2, 2, 3, 3, 0]
    assert parts[1].tolist() == [0, 0, 0, 0, 2, 3, 0]
    assert (parts[0] + parts[1]).tolist() == [1, 1, 2, 2, 5, 6, 0]


def _bounded_case(*, cur, active, block, nb, nkv, g, dk, dv, ring=False,
                  poison=False, seed=0):
    """Rows that write their positions the way the pool does: position p
    of row b in table column (p mod view_len) // block, each column its
    own physical block, columns never written on the null block 0.  A
    dense row holds 0..cur; a ring row the view's last view_len
    positions.  An inactive row keeps a stale table full of live blocks.
    ``poison`` fills the K/V of every block a row's bound leaves out (the
    null block and the inactive row's blocks) with NaN, positions -1."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.paged_decode import live_blocks
    B, L = len(cur), nb * block
    cur = np.asarray(cur, np.int32)
    active = np.asarray(active, bool)
    n_blocks = 2 + B * nb
    phys = n_blocks * block
    ks = jax.random.split(jax.random.key(seed), 3)
    k_pool = np.array(jax.random.normal(ks[0], (phys, nkv, dk)), np.float32)
    v_pool = np.array(jax.random.normal(ks[1], (phys, nkv, dv)), np.float32)
    pos_pool = np.full((phys,), -1, np.int32)
    tables = np.zeros((B, nb), np.int32)
    nxt = 2
    for b in range(B):
        first = max(0, int(cur[b]) - L + 1) if ring else 0
        for p in range(first, int(cur[b]) + 1):
            col, off = (p % L) // block, p % block
            if tables[b, col] == 0:
                tables[b, col], nxt = nxt, nxt + 1
            pos_pool[tables[b, col] * block + off] = p
    live = live_blocks(cur, active, block=block, nb=nb)
    if poison:
        dead = {0} | {int(tables[b, j]) for b in range(B)
                      for j in range(live[b], nb)}
        for blk_id in dead:
            rows = slice(blk_id * block, (blk_id + 1) * block)
            k_pool[rows] = np.nan
            v_pool[rows] = np.nan
            pos_pool[rows] = -1
    q = jax.random.normal(ks[2], (B, nkv * g, dk), jnp.float32)
    return (q, jnp.asarray(k_pool), jnp.asarray(v_pool),
            jnp.asarray(pos_pool), jnp.asarray(tables), jnp.asarray(cur),
            jnp.asarray(active), jnp.asarray(live))


# (cur per row, active per row, block, nb, window, ring, table shards)
BOUNDED = {
    # live 0 (not decoded, stale table), 1, exactly on a block boundary
    # (cur + 1 = 2 blocks), a partly filled block, the full table
    "ragged": ([9, 0, 7, 10, 19], [0, 1, 1, 1, 1], 4, 5, 0, False, 1),
    # a windowed ring past its wrap (walks all of it) beside one before
    "ring": ([33, 6, 47], [1, 1, 1], 4, 5, 12, True, 1),
    # the ragged rows' table cut in 2 and 3 column slices (padded with the
    # null block), each walked up to its own part of the bound
    "shard2": ([9, 0, 7, 10, 19], [0, 1, 1, 1, 1], 4, 5, 0, False, 2),
    "shard3": ([9, 0, 7, 10, 19], [0, 1, 1, 1, 1], 4, 5, 0, False, 3),
}


def _bounded_walk(args, *, impl, block, nb, window, nshards):
    """The bounded kernel over ``nshards`` column slices, combined by the
    softmax residuals: (acc, m, l) as one unsharded call gives them."""
    import jax.numpy as jnp
    from repro.kernels.paged_decode import live_blocks, paged_flash_decode
    q, k_pool, v_pool, pos_pool, tables, cur, active, _ = args
    tbl = jnp.pad(tables, ((0, 0), (0, (-nb) % nshards)))
    nb_loc = tbl.shape[1] // nshards
    parts = [paged_flash_decode(
        q, k_pool, v_pool, pos_pool, tbl[:, s * nb_loc:(s + 1) * nb_loc],
        cur, block=block, window=window, impl=impl, interpret=True,
        return_residuals=True,
        live=live_blocks(cur, active, block=block, nb=nb_loc,
                         start=s * nb_loc))
        for s in range(nshards)]
    if nshards == 1:
        return parts[0]
    m = jnp.max(jnp.stack([p[1] for p in parts]), axis=0)
    acc = sum(p[0] * jnp.exp(p[1] - m)[..., None] for p in parts)
    l = sum(p[2] * jnp.exp(p[1] - m) for p in parts)
    return acc, m, l


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
@pytest.mark.parametrize("case", list(BOUNDED))
def test_bounded_walk_matches_oracle(case, impl):
    """Each row walks only its live table blocks: decoded rows equal the
    dense oracle and the unbounded walk to float32 round-off; a row the
    step does not decode returns acc = 0, m = NEG_INF, l = 0 exactly, as a
    walk over masked blocks alone does; shards combine to the whole."""
    import jax.numpy as jnp
    from repro.kernels.paged_decode import NEG_INF, paged_flash_decode
    cur, active, block, nb, window, ring, nshards = BOUNDED[case]
    args = _bounded_case(cur=cur, active=active, block=block, nb=nb, nkv=2,
                         g=4, dk=32, dv=16, ring=ring)
    q, k_pool, v_pool, pos_pool, tables, cur_a, active_a, live = args
    acc, m, l = _bounded_walk(args, impl=impl, block=block, nb=nb,
                              window=window, nshards=nshards)
    on = np.asarray(active, bool)
    got = acc / jnp.maximum(l, 1e-30)[..., None]
    want = _oracle(q, k_pool, v_pool, pos_pool, tables, cur_a, block=block,
                   window=window)
    err = float(jnp.max(jnp.abs(got - want)[on]))
    assert err < 1e-5, err
    # the unbounded walk: every column, with the idle row's table nulled
    null_idle = jnp.where(active_a[:, None], tables, 0)
    full = paged_flash_decode(q, k_pool, v_pool, pos_pool, null_idle, cur_a,
                              block=block, window=window, impl=impl,
                              interpret=True, return_residuals=True)
    for a, b in zip((acc, m, l), full):
        assert float(jnp.max(jnp.abs(a - b)[on])) <= 1e-5 * float(
            jnp.max(jnp.abs(b[on])))
    off = ~on
    assert off.any() == (case != "ring")
    if off.any():
        assert float(jnp.max(jnp.abs(acc[off]))) == 0.0
        assert float(jnp.max(l[off])) == 0.0
        assert bool(jnp.all(m[off] == NEG_INF))
        for a, b in zip((acc, m, l), full):
            assert bool(jnp.all(a[off] == b[off]))


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_walk_stops_at_the_bound(impl):
    """K/V of every block past a row's bound hold NaN: the output stays
    finite and equals the clean oracle, so those blocks are never read
    into the softmax (an unbounded walk gives NaN through 0 x NaN in
    p @ v)."""
    import jax.numpy as jnp
    from repro.kernels.paged_decode import paged_flash_decode
    cur, active, block, nb, window, _, _ = BOUNDED["ragged"]
    kw = dict(cur=cur, active=[1] * len(cur), block=block, nb=nb, nkv=2,
              g=4, dk=32, dv=16)
    clean = _bounded_case(**kw)
    bad = _bounded_case(**kw, poison=True)
    q, k_pool, v_pool, pos_pool, tables, cur_a, _, live = bad
    got = paged_flash_decode(q, k_pool, v_pool, pos_pool, tables, cur_a,
                             block=block, live=live, impl=impl,
                             interpret=True)
    assert bool(jnp.all(jnp.isfinite(got)))
    want = _oracle(*clean[:6], block=block, window=window)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5
    unbounded = paged_flash_decode(q, k_pool, v_pool, pos_pool, tables,
                                   cur_a, block=block, impl=impl,
                                   interpret=True)
    assert not bool(jnp.all(jnp.isfinite(unbounded)))


def test_scatter_step_batched_writeback():
    """scatter_step lands every layer's new (k, v, pos) entry at its
    physical row in one scatter, trash lanes included."""
    import jax.numpy as jnp
    from repro.serve import kvcache
    n, phys, nkv, d, B = 2, 32, 2, 4, 3
    pool = {"dense": {"k": jnp.zeros((n, phys, nkv, d)),
                      "pos": jnp.full((n, phys), -1, jnp.int32)}}
    upd = {"dense": {"k": jnp.arange(n * B * nkv * d, dtype=jnp.float32)
                     .reshape(n, B, nkv, d),
                     "pos": jnp.asarray([[5, 6, 7]] * n, jnp.int32)}}
    tgt = jnp.asarray([10, 4, 29], jnp.int32)
    out = kvcache.scatter_step(pool, upd, tgt)
    for li in range(n):
        for b, t in enumerate([10, 4, 29]):
            assert jnp.array_equal(out["dense"]["k"][li, t],
                                   upd["dense"]["k"][li, b])
            assert int(out["dense"]["pos"][li, t]) == int(
                upd["dense"]["pos"][li, b])
    # untouched rows stay untouched
    assert float(jnp.abs(out["dense"]["k"][:, 0]).max()) == 0.0
    assert int(out["dense"]["pos"][0, 0]) == -1


def test_enable_kernels_routes_paged_decode():
    """enable_kernels forces the serving default through the Pallas kernel
    (interpret mode on CPU) with identical numerics."""
    import jax
    from repro.kernels import ops
    from repro.kernels.paged_decode import paged_flash_decode
    k_pool, v_pool, pos_pool, tables, cur = _make_case(
        jax.random.key(3), B=2, nq=4, nkv=2, dk=16, dv=16, block=4, nb=4,
        n_blocks=8)
    import jax.numpy as jnp
    q = jax.random.normal(jax.random.key(4), (2, 4, 16), jnp.float32)
    base = paged_flash_decode(q, k_pool, v_pool, pos_pool, tables, cur,
                              block=4)                     # auto -> jnp on CPU
    ops.enable_kernels(interpret=True)
    try:
        got = paged_flash_decode(q, k_pool, v_pool, pos_pool, tables, cur,
                                 block=4)                  # forced -> pallas
    finally:
        ops.disable_kernels()
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                 - base.astype(jnp.float32)))) < 1e-5


@pytest.mark.parametrize("island", ["1d", "2d"])
def test_enable_kernels_routes_1d_2d_islands(island):
    """The Pallas local matmul also backs the 1-D (Megatron) and 2-D
    (SUMMA) islands, not just the 3-D ones."""
    import jax
    import jax.numpy as jnp
    from repro.core import ops1d, ops2d
    from repro.core.topology import single_device_layout
    from repro.kernels import ops
    lay = single_device_layout(island)
    x = jax.random.normal(jax.random.key(1), (2, 8, 64), jnp.float32)
    w = jax.random.normal(jax.random.key(2), (64, 32), jnp.float32)
    if island == "1d":
        fn = lambda a, b: ops1d.linear1d_col(lay, a, b)    # noqa: E731
    else:
        fn = lambda a, b: ops2d.matmul2d(lay, a, b)        # noqa: E731
    base = jax.jit(fn)(x, w)
    ops.enable_kernels(interpret=True)
    try:
        got = jax.jit(fn)(x, w)
    finally:
        ops.disable_kernels()
    assert jnp.allclose(base, got, atol=1e-4)


@pytest.mark.parametrize("arch", ["qwen3-4b", "mixtral-8x7b",
                                  "deepseek-v3-671b"])
def test_engine_fused_matches_gather_view(arch):
    """End-to-end: the fused no-view decode (read-only pool + residual
    current-token fold + batched scatter_step) generates the same greedy
    tokens as the gather_view path — dense GQA, windowed MoE, and MLA."""
    import jax
    import jax.numpy as jnp
    from repro.config import reduced
    from repro.configs.registry import get
    from repro.core.params import init_params
    from repro.core.topology import single_device_layout
    from repro.models import transformer
    from repro.serve import Engine, Request
    layout = single_device_layout("3d")
    cfg = reduced(get(arch))
    params = init_params(transformer.abstract_params(cfg, layout),
                         jax.random.key(0), dtype=jnp.float32)
    outs = {}
    for fused in (False, True):
        eng = Engine(cfg, layout, params, batch_size=2, max_len=64,
                     fused_decode=fused)
        reqs = [Request(uid=i, prompt=[3 + (i + j) % 13 for j in range(12)],
                        max_new=6) for i in range(2)]
        eng.run(reqs)
        outs[fused] = [tuple(r.out) for r in reqs]
    assert outs[False] == outs[True], (outs[False], outs[True])


def test_engine_fused_window_ring_wrap():
    """Generation past the sliding window wraps the decode ring: the fused
    read-only-pool path must mask the stale (age >= ring length) entry it
    has not yet overwritten exactly like write-before-attend did."""
    import jax
    import jax.numpy as jnp
    from repro.config import reduced
    from repro.configs.registry import get
    from repro.core.params import init_params
    from repro.core.topology import single_device_layout
    from repro.models import transformer
    from repro.serve import Engine, Request
    layout = single_device_layout("3d")
    cfg = reduced(get("mixtral-8x7b"))
    W = cfg.window
    params = init_params(transformer.abstract_params(cfg, layout),
                         jax.random.key(0), dtype=jnp.float32)
    outs = {}
    for fused in (False, True):
        eng = Engine(cfg, layout, params, batch_size=2, max_len=W * 2,
                     fused_decode=fused)
        reqs = [Request(uid=0, prompt=[3 + j % 13 for j in range(6)],
                        max_new=W + 12)]       # well past the wrap at W
        eng.run(reqs)
        outs[fused] = tuple(reqs[0].out)
    assert len(outs[False]) == W + 12
    assert outs[False] == outs[True]


OVERLAP_BATTERY = r"""
import dataclasses
import jax, jax.numpy as jnp
from repro.config import ShapeConfig, reduced
from repro.configs.registry import get
from repro.core.topology import make_layout
from repro.data.pipeline import TokenStream
from repro.models import transformer

assert len(jax.devices()) == 8, jax.devices()
failures = []
cfg = dataclasses.replace(reduced(get("paper-transformer"), d_model=256),
                          n_layers=2, remat=False)
shape = ShapeConfig("t", 128, 8, "train")

def loss_and_grads(lay):
    params = transformer.init(cfg, lay, jax.random.key(0))
    params = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    batch = next(iter(TokenStream(cfg, lay, shape)))
    def fwd(p, b):
        loss, _ = transformer.forward(cfg, lay, p, b, mode="train")
        return loss
    loss, grads = jax.jit(jax.value_and_grad(fwd))(params, batch)
    return float(loss), jax.device_get(grads)

# overlap on/off equivalence on the (1,2,4) cube, and composed with dp and pp
cases = {
    "cube124": dict(cube=(1, 2, 4)),
    "dp2": dict(n_dp=2, n_model=4, cube=(1, 2, 2)),
    "pp2": dict(n_model=4, cube=(1, 2, 2), n_pp=2, microbatches=2),
}
for name, kw in cases.items():
    base_l, base_g = loss_and_grads(make_layout(**kw))
    ov_l, ov_g = loss_and_grads(make_layout(overlap=True, overlap_chunks=4,
                                            **kw))
    if abs(base_l - ov_l) > 1e-4:
        failures.append(f"{name} loss: {base_l} vs {ov_l}")
    md = max(float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                   - b.astype(jnp.float32))))
             for a, b in zip(jax.tree.leaves(base_g), jax.tree.leaves(ov_g)))
    if md > 1e-4:
        failures.append(f"{name} grads: {md}")

# overlap composed with ZeRO-1 sharded optimizer state: a 3-step real
# training trajectory must track the unfused islands
from repro.config import OptimConfig
from repro.core.params import init_params
from repro.optim.optimizers import opt_state_abstract
from repro.train.step import make_train_step

opt_cfg = OptimConfig(lr=1e-3, warmup=1, total_steps=3)
losses = {}
for overlap in (False, True):
    lay = make_layout(n_dp=2, n_model=4, cube=(1, 2, 2), zero_stage=1,
                      overlap=overlap, overlap_chunks=4)
    params = transformer.init(cfg, lay, jax.random.key(0))
    opt_state = init_params(opt_state_abstract(
        transformer.abstract_params(cfg, lay), lay, opt_cfg),
        jax.random.key(1))
    step_fn = jax.jit(make_train_step(cfg, lay, opt_cfg))
    stream = iter(TokenStream(cfg, lay, shape))
    traj = []
    for _ in range(3):
        params, opt_state, met = step_fn(params, opt_state, next(stream))
        traj.append(float(met["loss"]))
    losses[overlap] = traj
md = max(abs(a - b) for a, b in zip(losses[False], losses[True]))
if md > 5e-3:   # bf16 params: trajectories drift at rounding level only
    failures.append(f"zero1 trajectory: {losses[False]} vs {losses[True]}")

if failures:
    print("FAILURES:", failures)
    raise SystemExit(1)
print("ALL-OK")
"""


@pytest.mark.slow
def test_overlap_equivalence_multidev():
    """Async-overlap chunked 3-D collectives: loss + full grad tree match
    the unfused islands <= 1e-4 on 8 host devices, alone and composed with
    dp, pp, and a ZeRO-1 two-step training trajectory."""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(ROOT, "src"))
    proc = subprocess.run([sys.executable, "-c", OVERLAP_BATTERY], env=env,
                          capture_output=True, text=True, timeout=3000)
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-4000:]
    assert "ALL-OK" in proc.stdout


FUSED_CUBE_SCRIPT = r"""
import jax, jax.numpy as jnp
from repro.config import reduced
from repro.configs.registry import get
from repro.core.topology import make_layout
from repro.models import transformer
from repro.serve import Engine, Request

assert len(jax.devices()) == 8, jax.devices()
for arch in ("qwen3-4b", "deepseek-v3-671b"):
    cfg = reduced(get(arch))
    lay = make_layout(cube=(1, 2, 4))
    params = transformer.init(cfg, lay, jax.random.key(0))
    params = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    outs = {}
    for fused in (False, True):
        eng = Engine(cfg, lay, params, batch_size=4, max_len=64,
                     fused_decode=fused)
        reqs = [Request(uid=i, prompt=[3 + (i + j) % 13 for j in range(10)],
                        max_new=5) for i in range(4)]
        eng.run(reqs)
        outs[fused] = [tuple(r.out) for r in reqs]
    assert outs[False] == outs[True], (arch, outs)
    print(arch, "ok")
print("ALL-OK")
"""


@pytest.mark.slow
def test_engine_fused_multidev_cube():
    """Fused decode on the (1,2,4) cube: table-column sharding over the
    gather axes + psum residual combine + head sharding must match the
    gather_view path token-for-token (dense GQA and MLA)."""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(ROOT, "src"))
    proc = subprocess.run([sys.executable, "-c", FUSED_CUBE_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=3000)
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-4000:]
    assert "ALL-OK" in proc.stdout
