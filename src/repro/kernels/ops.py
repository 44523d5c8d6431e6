"""Jit'd wrappers over the Pallas kernels + integration hooks.

``enable_kernels(interpret=...)`` installs the Pallas local matmul into the
3-D ops (ops3d.set_local_matmul) so every Algorithm-1 island computes its
local shard product on the MXU kernel.  ``interpret=None`` is decided when a
kernel is called: compiled on a TPU backend, interpret mode on any other.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import ref
from . import paged_decode as paged_decode_mod
from .flash_attention import flash_attention
from .matmul import matmul
from .paged_decode import live_blocks, paged_flash_decode
from .rmsnorm import rmsnorm
from .ssd_scan import ssd_scan


def _interpret(interpret):
    """None -> interpret mode unless the default backend is a TPU (asked at
    call time, so importing this module never initialises a backend)."""
    return jax.default_backend() != "tpu" if interpret is None else interpret


def pallas_matmul(x, w, *, act="none", interpret=None):
    """(…, S, K) @ (K, N): flattens the leading dims for the 2-D kernel and
    pads block sizes down for small shapes."""
    lead = x.shape[:-1]
    m = 1
    for s in lead:
        m *= s
    x2 = x.reshape(m, x.shape[-1])
    k, n = w.shape
    # MXU-aligned tiles when possible; fall back to full dims for small shapes
    bm = 128 if m % 128 == 0 else m
    bn = 128 if n % 128 == 0 else n
    bk = 128 if k % 128 == 0 else k
    out = matmul(x2, w, bm=bm, bn=bn, bk=bk, act=act,
                 interpret=_interpret(interpret))
    return out.reshape(*lead, n)


def pallas_flash(q, k, v, *, causal=True, window=0, q_offset=0, interpret=None):
    return flash_attention(q, k, v, causal=causal, window=window,
                           q_offset=q_offset, interpret=_interpret(interpret))


def pallas_ssd(xbar, la, Bh, Ch, *, chunk=256, interpret=None):
    return ssd_scan(xbar, la, Bh, Ch, chunk=chunk,
                    interpret=_interpret(interpret))


def pallas_rmsnorm(x, gamma, *, eps=1e-6, zero_centered=False, interpret=None):
    return rmsnorm(x, gamma, eps=eps, zero_centered=zero_centered,
                   interpret=_interpret(interpret))


def pallas_paged_decode(q, k_pool, v_pool, pos_pool, tables, cur, *,
                        block, window=0, scale=None, interpret=None):
    """Fused paged flash-decode through the block table (serving hot path):
    every row is decoded, each walks its table up to its last live block."""
    live = live_blocks(cur, True, block=block, nb=tables.shape[1])
    return paged_flash_decode(q, k_pool, v_pool, pos_pool, tables, cur,
                              block=block, live=live, window=window,
                              scale=scale, impl="pallas",
                              interpret=_interpret(interpret))


def enable_kernels(interpret=None):
    """Install the Pallas matmul as the local GEMM of every tensor-parallel
    island (3-D, 2-D SUMMA, 1-D Megatron) and route the serving engine's
    paged decode through the Pallas kernel."""
    from ..core import ops1d, ops2d, ops3d
    interp = _interpret(interpret)

    def local_mm(a, b):
        return pallas_matmul(a, b, interpret=interp)

    ops3d.set_local_matmul(local_mm)
    ops1d.set_local_matmul(local_mm)
    ops2d.set_local_matmul(local_mm)
    paged_decode_mod.set_default_impl("pallas", interpret=interp)


def disable_kernels():
    from ..core import ops1d, ops2d, ops3d
    ops3d.set_local_matmul(None)
    ops1d.set_local_matmul(None)
    ops2d.set_local_matmul(None)
    paged_decode_mod.set_default_impl(None)
