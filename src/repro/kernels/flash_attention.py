"""Pallas TPU flash attention (causal / windowed), online softmax.

Grid (batch*kv_heads, q_blocks, kv_blocks) with the kv dimension innermost so
the (m, l, acc) state lives in VMEM scratch across the contraction.  GQA is
handled by folding the q-per-kv group into the q block rows.

TARGET: TPU; validated with interpret=True against ref.attention_ref.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  n_kv: int, bq: int, bk: int, causal: bool, window: int,
                  scale: float, q_offset: int, sq: int):
    kv_i = pl.program_id(2)

    @pl.when(kv_i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...].astype(jnp.float32) * scale          # (bq, d)
    k = k_ref[...].astype(jnp.float32)                  # (bk, d)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (bq, bk)

    # rows are group-major over (g, sq): global position = row % sq
    row = pl.program_id(1) * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    q_pos = q_offset + row % sq
    k_pos = kv_i * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask = mask & (q_pos >= k_pos)
    if window:
        mask = mask & (q_pos - k_pos < window)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    m_ref[...] = m_new
    acc_ref[...] = acc_ref[...] * corr + jnp.dot(
        p, v_ref[...].astype(jnp.float32), preferred_element_type=jnp.float32)

    @pl.when(kv_i == n_kv - 1)
    def _done():
        o_ref[...] = (acc_ref[...] /
                      jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk",
                                             "q_offset", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    bq: int = 128, bk: int = 128, q_offset: int = 0,
                    interpret: bool = False):
    """q: (B, Sq, Hq, D); k/v: (B, Sk, Hkv, D) -> (B, Sq, Hq, D).

    ``q_offset`` is the global position of q row 0 (sequence-parallel shards).
    """
    b, sq, hq, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    scale = 1.0 / math.sqrt(d)
    bq = min(bq, sq)
    while sq % bq:
        bq -= 1
    bk = min(bk, sk)
    while sk % bk:
        bk -= 1

    # fold GQA group into q rows: (b*hkv, sq*g, d) where rows are g-major
    qf = q.reshape(b, sq, hkv, g, d).transpose(0, 2, 3, 1, 4) \
          .reshape(b * hkv, g * sq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * hkv, sk, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * hkv, sk, v.shape[-1])

    grid_rows = g * sq
    n_kv = sk // bk
    out = pl.pallas_call(
        functools.partial(_flash_kernel, n_kv=n_kv, bq=bq, bk=bk,
                          causal=causal, window=window, scale=scale,
                          q_offset=q_offset, sq=sq),
        grid=(b * hkv, grid_rows // bq, n_kv),
        in_specs=[
            pl.BlockSpec((None, bq, d), lambda h, i, s: (h, i, 0)),
            pl.BlockSpec((None, bk, d), lambda h, i, s: (h, s, 0)),
            pl.BlockSpec((None, bk, vf.shape[-1]), lambda h, i, s: (h, s, 0)),
        ],
        out_specs=pl.BlockSpec((None, bq, vf.shape[-1]),
                               lambda h, i, s: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hkv, grid_rows, vf.shape[-1]),
                                       q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, vf.shape[-1]), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, hkv, g, sq, v.shape[-1]).transpose(0, 3, 1, 2, 4) \
              .reshape(b, sq, hq, v.shape[-1])
