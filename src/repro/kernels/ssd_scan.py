"""Pallas TPU kernel for the Mamba2 SSD chunked scan.

Grid (batch*heads, chunks) with the chunk dimension innermost/sequential: the
running state (dh, N) lives in a VMEM scratch accumulator across chunks.
Per chunk: intra-chunk decay-masked quadratic term + contribution of the
carried state, then the state update — the TPU-native replacement for the
GPU kernel in the Mamba2 paper (DESIGN.md hardware adaptation).

Validated with interpret=True against ref.ssd_ref.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _ssd_kernel(x_ref, la_ref, b_ref, c_ref, o_ref, h_ref, *, n_chunks: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[...].astype(jnp.float32)       # (Q, dh)  pre-scaled by dt
    la = la_ref[...].astype(jnp.float32)     # (Q, 1)   log-decay
    Bm = b_ref[...].astype(jnp.float32)      # (Q, N)
    Cm = c_ref[...].astype(jnp.float32)      # (Q, N)

    cum = jnp.cumsum(la, axis=0)             # (Q, 1)
    tot = cum[-1]                            # (1,)

    # intra-chunk: scores_ij = (C_i . B_j) exp(cum_i - cum_j), i >= j
    cb = jnp.dot(Cm, Bm.T, preferred_element_type=jnp.float32)   # (Q, Q)
    dec = jnp.exp(cum - cum.T)
    Q = x.shape[0]
    causal = (jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
              >= jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1))
    scores = jnp.where(causal, cb * dec, 0.0)
    y = jnp.dot(scores, x, preferred_element_type=jnp.float32)   # (Q, dh)

    # inter-chunk: contribution of the carried state
    h = h_ref[...]                                               # (N, dh)
    y = y + jnp.exp(cum) * jnp.dot(Cm, h, preferred_element_type=jnp.float32)

    # state update: h' = exp(tot) h + sum_j exp(tot - cum_j) B_j^T xbar_j
    w = jnp.exp(tot - cum)                                       # (Q, 1)
    h_ref[...] = jnp.exp(tot) * h + jnp.dot(
        (w * Bm).T, x, preferred_element_type=jnp.float32)

    o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(xbar, la, Bh, Ch, *, chunk: int = 256, interpret: bool = False):
    """xbar: (BH, T, dh) dt-scaled inputs; la: (BH, T) log-decays;
    Bh/Ch: (BH, T, N) per-head (group-broadcast) B/C.  Returns (BH, T, dh).

    The D skip term and head/group plumbing live in ops.py.
    """
    bh, T, dh = xbar.shape
    N = Bh.shape[-1]
    chunk = min(chunk, T)
    assert T % chunk == 0
    n_chunks = T // chunk

    return pl.pallas_call(
        functools.partial(_ssd_kernel, n_chunks=n_chunks),
        grid=(bh, n_chunks),
        in_specs=[
            pl.BlockSpec((None, chunk, dh), lambda h, c: (h, c, 0)),
            pl.BlockSpec((None, chunk, 1), lambda h, c: (h, c, 0)),
            pl.BlockSpec((None, chunk, N), lambda h, c: (h, c, 0)),
            pl.BlockSpec((None, chunk, N), lambda h, c: (h, c, 0)),
        ],
        out_specs=pl.BlockSpec((None, chunk, dh), lambda h, c: (h, c, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, T, dh), xbar.dtype),
        scratch_shapes=[pltpu.VMEM((N, dh), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(xbar, la[..., None], Bh, Ch)
