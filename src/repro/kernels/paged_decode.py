"""Fused paged flash-decode: attention for one new token per slot, read
directly out of the paged KV pool through the block table.

The baseline decode path materializes a contiguous per-slot cache view
(``serve/kvcache.py:gather_view``) — a full copy of every layer's pool —
before the attention islands ever run.  This kernel removes that copy: the
grid walks each slot's block table (scalar-prefetched so the index maps can
dereference it), streams the table's physical KV blocks straight from the
pool, and accumulates an online softmax over blocks.  Null-block lanes
(table entry 0, positions forever -1) and recycled blocks are masked by the
pool's position leaf, exactly like the gathered path.

Shapes (one layer, per device):

    q        (B, nq, dk)        new-token queries, nq = nkv * group
    k_pool   (phys, nkv, dk)    phys = n_blocks * block
    v_pool   (phys, nkv, dv)    dv may differ from dk (MLA latents)
    pos_pool (phys,) int32      logical position per entry, -1 = invalid
    tables   (B, nb) int32      physical block id per view block
    cur      (B,) int32         current decode position per slot
    live     (B,) int32         table columns each slot walks (optional)
    -> out   (B, nq, dv)

Masking contract: entry ``e`` of slot ``b`` attends iff
``0 <= pos_pool[e] <= cur[b]`` (and ``cur[b] - pos_pool[e] < window`` when
sliding-window).  The fused decode paths keep the current token OUT of the
pool during the step (the pool is read-only in the forward) and fold its
(k, v) into the online softmax afterwards via ``return_residuals``; the
engine then writes all layers' new entries in one batched scatter.

Live-length bound: the pool places position ``p`` of a slot in table
column ``(p mod view_len) // block``, so only the first
``cdiv(cur + 1, block)`` columns can hold a position the slot attends
(the whole table once a sliding-window ring has wrapped).
``live_blocks`` gives that count per row (0 for a row the step does not
decode, and the part that falls in a column slice for a caller that
shards the table).  The kernel walks ``live[b]`` columns of row ``b``:
past them the K/V/position index maps repeat the row's last live block,
so the pipeline issues no copy, and the compute is skipped.  A row with
``live = 0`` returns ``acc = 0, m = NEG_INF, l = 0``, as a walk over
nothing but masked blocks does.

MLA fits the same kernel with nkv=1: K = concat(c_kv, k_rope) features,
V = c_kv, q = concat(absorbed q_latent, q_rope) — see ``models/mla.py``.

``impl`` selects the backend: "pallas" (the fused kernel; interpret mode on
CPU) or "jnp" (a pool-indexing jnp fallback that still skips gather_view's
all-layer copy).  ``None`` resolves to pallas on TPU and jnp on CPU
(interpret-mode Pallas is python-slow; the jnp path is the CPU serving
default, the kernel is covered by interpret-mode tests).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# (impl, interpret) forced by kernels/ops.py:enable_kernels; None = auto
_FORCED: Optional[tuple] = None


def set_default_impl(impl: Optional[str], interpret: Optional[bool] = None):
    """Force the backend picked when callers pass impl=None (enable_kernels
    routes serving through the Pallas kernel even on CPU); None resets."""
    global _FORCED
    _FORCED = None if impl is None else (impl, interpret)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------
def live_blocks(cur, active, *, block: int, nb: int, start=0):
    """Per row, how many of the table columns ``[start, start + nb)`` hold
    a position ``0 <= p <= cur``: ``clip(cdiv(cur + 1, block) - start, 0,
    nb)``, and 0 where ``active`` is false.  Works on numpy and jax arrays
    alike (the engine counts with it on the host, the models bound the
    kernel with it in the jitted step)."""
    return ((cur + block) // block - start).clip(0, nb) * active


def _decode_kernel(tbl_ref, cur_ref, live_ref, q_ref, k_ref, v_ref, kp_ref,
                   o_ref, mo_ref, lo_ref, m_ref, l_ref, acc_ref, *, nkv: int,
                   dk: int, dv: int, window: int, scale: float,
                   residuals: bool):
    # one grid step = one slot x one table block, every kv head at once:
    # k_ref (block, nkv*dk) / v_ref (block, nkv*dv) hold the heads side by
    # side in the lane dim, q_ref and the accumulators are (nkv, g, .)
    b, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j < live_ref[b])
    def _walk():
        cur = cur_ref[b]
        kp = kp_ref[0, :]                               # (block,)
        valid = (kp >= 0) & (kp <= cur)
        if window:
            valid &= (cur - kp) < window
        for h in range(nkv):
            q = q_ref[h].astype(jnp.float32) * scale    # (g, dk)
            k = k_ref[:, h * dk:(h + 1) * dk].astype(jnp.float32)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))
            s = jnp.where(valid[None, :], s, NEG_INF)   # (g, block)
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            # explicit re-mask: a fully-invalid block (the null block)
            # would give exp(NEG_INF - NEG_INF) = 1 on the first step
            p = jnp.where(valid[None, :], jnp.exp(s - m_new), 0.0)
            v = v_ref[:, h * dv:(h + 1) * dv].astype(jnp.float32)
            l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[h] = acc_ref[h] * alpha + p @ v
            m_ref[h] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        mo_ref[...] = m_ref[...]
        lo_ref[...] = l_ref[...]
        if residuals:
            # unnormalized accumulator: the caller combines table shards
            # via softmax residuals (m, l) and divides once at the end
            o_ref[...] = acc_ref[...].astype(o_ref.dtype)
        else:
            o_ref[...] = (acc_ref[...]
                          / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _pool_block(b, j, tbl, cp, lv):
    # block-table indirection in the index map: grid step (b, j) pulls
    # physical block tbl[b, j] out of the pool.  Past the row's live
    # columns the index repeats the last live one, so no copy is issued.
    return (tbl[b, jnp.maximum(jnp.minimum(j, lv[b] - 1), 0)], 0, 0)


def _row(b, j, tbl, cp, lv):
    return (b, 0, 0, 0)


def _pallas_impl(q, k_pool, v_pool, pos_pool, tables, cur, live, *, block,
                 window, scale, interpret, residuals=False):
    B, nq, dk = q.shape
    phys, nkv, _ = k_pool.shape
    dv = v_pool.shape[-1]
    g = nq // nkv
    nb = tables.shape[1]
    n_blocks = phys // block
    qr = q.reshape(B, nkv, g, dk)
    # row-major views of the pool: a physical block is `block` rows of
    # every head's features side by side, so a BlockSpec's last two dims
    # are (block, whole row) and no head axis is squeezed out of them.
    # On the TPU's tiled layouts XLA materializes each view as a copy.
    kr = k_pool.reshape(n_blocks, block, nkv * dk)
    vr = v_pool.reshape(n_blocks, block, nkv * dv)
    pr = pos_pool.reshape(n_blocks, 1, block)

    kernel = functools.partial(_decode_kernel, nkv=nkv, dk=dk, dv=dv,
                               window=window, scale=scale,
                               residuals=residuals)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, nb),
        in_specs=[
            pl.BlockSpec((None, nkv, g, dk), _row),
            pl.BlockSpec((None, block, nkv * dk), _pool_block),
            pl.BlockSpec((None, block, nkv * dv), _pool_block),
            pl.BlockSpec((None, 1, block), _pool_block),
        ],
        out_specs=[
            pl.BlockSpec((None, nkv, g, dv), _row),
            pl.BlockSpec((None, nkv, g, 1), _row),
            pl.BlockSpec((None, nkv, g, 1), _row),
        ],
        scratch_shapes=[
            pltpu.VMEM((nkv, g, 1), jnp.float32),
            pltpu.VMEM((nkv, g, 1), jnp.float32),
            pltpu.VMEM((nkv, g, dv), jnp.float32),
        ],
    )
    out, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, nkv, g, dv),
                                 jnp.float32 if residuals else q.dtype),
            jax.ShapeDtypeStruct((B, nkv, g, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, nkv, g, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="paged_decode",
    )(tables.astype(jnp.int32), cur.astype(jnp.int32),
      live.astype(jnp.int32), qr, kr, vr, pr)
    if residuals:
        return (out.reshape(B, nq, dv), m.reshape(B, nq), l.reshape(B, nq))
    return out.reshape(B, nq, dv)


# ---------------------------------------------------------------------------
# jnp fallback (CPU serving default): indexes the pool through the tables
# per layer — no Pallas, but still no all-layer gather_view copy.
# ---------------------------------------------------------------------------
def _jnp_impl(q, k_pool, v_pool, pos_pool, tables, cur, live, *, block,
              window, scale, residuals=False):
    B, nq, dk = q.shape
    nkv = k_pool.shape[1]
    g = nq // nkv
    dv = v_pool.shape[-1]
    flat = (tables[:, :, None] * block
            + jnp.arange(block, dtype=tables.dtype)).reshape(B, -1)
    k = k_pool[flat]                                    # (B, L, nkv, dk)
    v = v_pool[flat]                                    # (B, L, nkv, dv)
    kp = pos_pool[flat]                                 # (B, L)
    # the same walk as the kernel: columns past live[b] never enter
    walked = jnp.arange(flat.shape[1]) // block < live[:, None]
    v = jnp.where(walked[:, :, None, None], v, 0)
    valid = walked & (kp >= 0) & (kp <= cur[:, None])
    if window:
        valid &= (cur[:, None] - kp) < window
    qf = q.reshape(B, nkv, g, dk).astype(jnp.float32) * scale
    s = jnp.einsum("bhgd,blhd->bhgl", qf, k.astype(jnp.float32))
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(valid[:, None, None, :], jnp.exp(s - m), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    if residuals:
        acc = jnp.einsum("bhgl,blhd->bhgd", p, v.astype(jnp.float32))
        return (acc.reshape(B, nq, dv), m.reshape(B, nq), l.reshape(B, nq))
    out = jnp.einsum("bhgl,blhd->bhgd", p / jnp.maximum(l, 1e-30),
                     v.astype(jnp.float32))
    return out.reshape(B, nq, -1).astype(q.dtype)


def paged_flash_decode(q, k_pool, v_pool, pos_pool, tables, cur, *,
                       block: int, live=None, window: int = 0,
                       scale: Optional[float] = None,
                       impl: Optional[str] = None,
                       interpret: Optional[bool] = None,
                       return_residuals: bool = False):
    """One decode step of paged attention; see the module docstring.

    ``live`` (B,) int32 bounds the walk: row ``b`` visits table columns
    ``0 .. live[b] - 1`` (``live_blocks``); ``None`` walks every column.

    ``return_residuals=True`` returns ``(acc, m, l)`` — the unnormalized
    f32 accumulator plus the online-softmax max and sum — so a caller that
    shards the block table across devices can psum-combine the partials
    (``o = psum(acc * exp(m - pmax(m))) / psum(l * exp(m - pmax(m)))``).
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if live is None:
        live = jnp.full(cur.shape, tables.shape[1], jnp.int32)
    if impl is None:
        if _FORCED is not None:
            impl, forced_interp = _FORCED
            if interpret is None:
                interpret = forced_interp
        else:
            impl = "pallas" if _on_tpu() else "jnp"
    if impl == "jnp":
        return _jnp_impl(q, k_pool, v_pool, pos_pool, tables, cur, live,
                         block=block, window=window, scale=scale,
                         residuals=return_residuals)
    if impl != "pallas":
        raise ValueError(f"unknown paged decode impl {impl!r}")
    if interpret is None:
        interpret = not _on_tpu()
    return _pallas_impl(q, k_pool, v_pool, pos_pool, tables, cur, live,
                        block=block, window=window, scale=scale,
                        interpret=interpret, residuals=return_residuals)
