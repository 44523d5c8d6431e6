"""Pallas TPU tiled matmul with fused bias + activation.

This is the local-shard GEMM of Algorithm 1 (the compute the paper's 3-D
scheme distributes).  MXU-aligned 128x128 tiles, f32 accumulator in VMEM,
K-innermost grid so the accumulator lives across the contraction steps.

TARGET: TPU (pl.pallas_call + BlockSpec VMEM tiling); validated on CPU with
interpret=True against ref.matmul_ref.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


ACTS = {
    "none": lambda x: x,
    "gelu": lambda x: jax.nn.gelu(x, approximate=True),
    "silu": jax.nn.silu,
    "relu": jax.nn.relu,
}


def _matmul_kernel(x_ref, w_ref, b_ref, o_ref, acc_ref, *, n_k: int, act: str,
                   has_bias: bool):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _done():
        acc = acc_ref[...]
        if has_bias:
            acc = acc + b_ref[...].astype(jnp.float32)
        o_ref[...] = ACTS[act](acc).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "act",
                                             "interpret"))
def matmul(x, w, bias: Optional[jax.Array] = None, *, bm: int = 128,
           bn: int = 128, bk: int = 128, act: str = "none",
           interpret: bool = False):
    """(M, K) @ (K, N) [+ bias (N,)] with fused activation."""
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k, bm, bn, bk)
    n_k = k // bk
    has_bias = bias is not None
    if not has_bias:
        bias = jnp.zeros((n,), x.dtype)

    return pl.pallas_call(
        functools.partial(_matmul_kernel, n_k=n_k, act=act, has_bias=has_bias),
        grid=(m // bm, n // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, s: (i, s)),
            pl.BlockSpec((bk, bn), lambda i, j, s: (s, j)),
            pl.BlockSpec((bn,), lambda i, j, s: (j,)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, w, bias)
