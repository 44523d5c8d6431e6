"""Pallas TPU fused RMSNorm (forward): one VMEM pass computes the f32
moment and applies the scale — the 3-D layer's matrix-vector op (paper
Algorithm 7 family) as a fused kernel.

Validated with interpret=True against ref.rmsnorm_ref.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _rmsnorm_kernel(x_ref, g_ref, o_ref, *, eps: float, zero_centered: bool):
    x = x_ref[...].astype(jnp.float32)                  # (bm, H)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    g = g_ref[...].astype(jnp.float32)
    if zero_centered:
        g = g + 1.0
    o_ref[...] = (x * jax.lax.rsqrt(var + eps) * g).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("eps", "zero_centered", "bm",
                                             "interpret"))
def rmsnorm(x, gamma, *, eps: float = 1e-6, zero_centered: bool = False,
            bm: int = 256, interpret: bool = False):
    """x: (..., H); gamma: (H,)."""
    lead = x.shape[:-1]
    H = x.shape[-1]
    m = 1
    for s in lead:
        m *= s
    x2 = x.reshape(m, H)
    bm = min(bm, m)
    while m % bm:
        bm -= 1
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps, zero_centered=zero_centered),
        grid=(m // bm,),
        in_specs=[pl.BlockSpec((bm, H), lambda i: (i, 0)),
                  pl.BlockSpec((H,), lambda i: (0,))],
        out_specs=pl.BlockSpec((bm, H), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, H), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x2, gamma)
    return out.reshape(*lead, H)
