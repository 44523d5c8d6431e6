"""The paper's 3-D parallel linear operations (Algorithms 1-6).

Every op is a ``jax.shard_map`` island embedded in the surrounding jitted
program: inputs/outputs are global arrays whose shardings follow the
load-balanced placement of §3.1.1, and the island body is the paper's
pseudo-code verbatim — all-gather the activation along ``in_ax``, all-gather
the weight along ``x``, local matmul, reduce-scatter along ``out_ax``.

The backward pass is a ``custom_vjp`` implementing Algorithm 2 explicitly
(re-gathering the *balanced* blocks instead of saving gathered copies), which
is what gives the paper's O(1/P) activation-memory claim.

Layouts (global-array PartitionSpecs):

    x  : (B, S, H)   P(batch, in_ax, out_ax)     # tokens split (x ⊗ in_ax), hidden split out_ax
    w  : (H, F)      P(out_ax, (in_ax, x))
    y  : (B, S, F)   P(batch, out_ax, in_ax)     # directions exchanged (paper §3.2)
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from .topology import Layout

# ---------------------------------------------------------------------------
# local matmul hook — replaced by the Pallas kernel when enabled (kernels/ops.py)
# ---------------------------------------------------------------------------
_LOCAL_MATMUL = None


def set_local_matmul(fn):
    """Install a custom local matmul (e.g. the Pallas MXU kernel)."""
    global _LOCAL_MATMUL
    _LOCAL_MATMUL = fn


def _mm(a, b):
    """Local shard matmul, f32 accumulation (MXU-style)."""
    if _LOCAL_MATMUL is not None:
        return _LOCAL_MATMUL(a, b)
    out = jnp.einsum("...sh,hf->...sf", a, b, preferred_element_type=jnp.float32)
    return out.astype(a.dtype)


def _seq_spec(layout: Layout, ax: str):
    seq = tuple(a for a in (*layout.seq_axes, ax) if a is not None and layout.size(a) > 1)
    return seq or None


def _x_spec(layout: Layout, in_ax: str, out_ax: str) -> P:
    return P(layout.batch_spec(), _seq_spec(layout, in_ax), out_ax)


def _y_spec(layout: Layout, in_ax: str, out_ax: str) -> P:
    return P(layout.batch_spec(), _seq_spec(layout, out_ax), in_ax)


def _w_spec(in_ax: str, out_ax: str) -> P:
    return P(out_ax, (in_ax, "x"))


def _shmap(layout, body, in_specs, out_specs):
    return shard_map(body, mesh=layout.mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _grad_sync_axes(layout: Layout) -> Tuple[str, ...]:
    """Axes the weight gradient must be summed over beyond the cube 'x'
    reduce-scatter: all data-parallel batch axes and any context-parallel
    sequence axes (the contraction runs over tokens)."""
    axes = [a for a in (*layout.batch_axes, *layout.seq_axes)
            if a not in ("x", "y", "z") and layout.size(a) > 1]
    return tuple(dict.fromkeys(axes))


# ---------------------------------------------------------------------------
# Async-TP chunking (Layout.overlap): each island matmul is decomposed into
# K chunks along the *local contraction* dimension, so chunk t's all_gather /
# psum_scatter is independent of chunk t-1's partial matmul and the compiler
# (async collectives on TPU) can run them concurrently.  Chunking the
# contraction dim — never the gathered sequence dim — keeps the device-major
# concatenation order of every all_gather identical to the unfused path, so
# the result matches up to f32 summation reordering (psum_scatter is linear:
# scattering each chunk and summing scattered partials in f32 equals
# scattering the full f32 sum).
# ---------------------------------------------------------------------------
def _overlap_k(layout: Layout, n: int) -> int:
    """Effective chunk count: the largest divisor of the local contraction
    size ``n`` that is <= layout.overlap_chunks; 1 disables chunking."""
    if not layout.overlap:
        return 1
    k = max(1, min(layout.overlap_chunks, n))
    while n % k:
        k -= 1
    return k


def _fwd_chunked(layout, in_ax, out_ax, shard_f, x, w, k):
    """Chunked Algorithm 1 body: per-chunk AG(x-slice)/AG(w-rows) + partial
    matmul + per-chunk reduce-scatter, accumulated in f32."""
    ck = x.shape[-1] // k
    acc = None
    for t in range(k):
        xk = lax.slice_in_dim(x, t * ck, (t + 1) * ck, axis=-1)
        wk = lax.slice_in_dim(w, t * ck, (t + 1) * ck, axis=0)
        xg = lax.all_gather(xk, in_ax, axis=1, tiled=True)
        wg = lax.all_gather(wk, "x", axis=1, tiled=True) if shard_f else wk
        c = _mm(xg, wg).astype(jnp.float32)
        p = lax.psum_scatter(c, out_ax, scatter_dimension=1, tiled=True)
        acc = p if acc is None else acc + p
    return acc.astype(x.dtype)


def _dx_chunked(layout, in_ax, out_ax, dcg, w, k):
    """Chunked dx = dc @ w^T over the contraction dim f.  ``dcg`` is the
    (shared, unchunked) gather of dc along out_ax; w's column chunks are
    gathered along 'x' per chunk.  The gathered w columns are x-device-major
    blocks of the local width, so dcg's matching features are selected by a
    (sx, f_loc) reshape before slicing.  Requires shard_f."""
    f_loc = w.shape[1]
    ck = f_loc // k
    sx = layout.size("x")
    b, s, _ = dcg.shape
    dcr = dcg.reshape(b, s, sx, f_loc)
    acc = None
    for t in range(k):
        wk = lax.slice_in_dim(w, t * ck, (t + 1) * ck, axis=1)
        wg = lax.all_gather(wk, "x", axis=1, tiled=True)       # (h/so, sx*ck)
        dck = lax.slice_in_dim(dcr, t * ck, (t + 1) * ck, axis=3)
        dck = dck.reshape(b, s, sx * ck)
        dxp = jnp.einsum("bsf,hf->bsh", dck, wg,
                         preferred_element_type=jnp.float32)
        p = lax.psum_scatter(dxp, in_ax, scatter_dimension=1, tiled=True)
        acc = p if acc is None else acc + p
    return acc


def _dw_chunked(layout, in_ax, out_ax, shard_f, x, dcg, k):
    """Chunked dw = x^T @ dc over the output-row dim h: per-chunk AG of x's
    feature slice + per-chunk reduce-scatter of the dw row block.  Row
    chunks are disjoint, so they concatenate (no accumulation) and each
    matches the unfused value exactly."""
    ck = x.shape[-1] // k
    rows = []
    for t in range(k):
        xk = lax.slice_in_dim(x, t * ck, (t + 1) * ck, axis=-1)
        xg = lax.all_gather(xk, in_ax, axis=1, tiled=True)     # (b, S', ck)
        dwp = jnp.einsum("bsh,bsf->hf", xg, dcg,
                         preferred_element_type=jnp.float32).astype(x.dtype)
        if shard_f:
            rows.append(lax.psum_scatter(dwp, "x", scatter_dimension=1,
                                         tiled=True))
        else:
            rows.append(lax.psum(dwp, "x") if layout.size("x") > 1 else dwp)
    return jnp.concatenate(rows, axis=0) if k > 1 else rows[0]


# ---------------------------------------------------------------------------
# Algorithm 1 (forward  C = AB) + Algorithm 2 (backward) — training path
#
# ``shard_f`` selects whether the weight's output dim uses the full balanced
# placement (cols split over (in_ax, x)) or stays unsharded — the latter is
# used for small projections (e.g. MQA/GQA kv heads narrower than the y axis).
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _matmul3d(layout: Layout, in_ax: str, out_ax: str, shard_f: bool, x, w):
    return _matmul3d_fwd_island(layout, in_ax, out_ax, shard_f)(x, w)


def matmul3d(layout: Layout, in_ax: str, out_ax: str, x, w, shard_f: bool = True):
    """3-D parallel ``y = x @ w`` for (B, S, H) x (H, F).

    Forward = Algorithm 1: all-gather x along in_ax, all-gather w along 'x',
    local matmul, reduce-scatter along out_ax.  Output directions swapped.
    """
    return _matmul3d(layout, in_ax, out_ax, shard_f, x, w)


def w_spec3d(in_ax: str, out_ax: str, shard_f: bool = True) -> P:
    return _w_spec(in_ax, out_ax) if shard_f else P(out_ax, None)


def y_spec3d(layout: Layout, in_ax: str, out_ax: str, shard_f: bool = True) -> P:
    return (_y_spec(layout, in_ax, out_ax) if shard_f
            else P(layout.batch_spec(), _seq_spec(layout, out_ax), None))


def _matmul3d_fwd_island(layout, in_ax, out_ax, shard_f=True):
    def body(x, w):
        k = _overlap_k(layout, x.shape[-1])
        if k > 1:
            return _fwd_chunked(layout, in_ax, out_ax, shard_f, x, w, k)
        xg = lax.all_gather(x, in_ax, axis=1, tiled=True)      # (b, S', h/so)
        wg = lax.all_gather(w, "x", axis=1, tiled=True) if shard_f else w
        c = _mm(xg, wg)                                        # partial over out_ax
        return lax.psum_scatter(c, out_ax, scatter_dimension=1, tiled=True)

    return _shmap(layout, body,
                  (_x_spec(layout, in_ax, out_ax), w_spec3d(in_ax, out_ax, shard_f)),
                  y_spec3d(layout, in_ax, out_ax, shard_f))


def _matmul3d_dx_island(layout, in_ax, out_ax, shard_f=True):
    # Algorithm 2, line 1:  dx = dc @ w^T  in directions (out_ax, x, in_ax)
    def body(dc, w):
        dcg = lax.all_gather(dc, out_ax, axis=1, tiled=True)   # (b, S', f/si)
        if shard_f:
            k = _overlap_k(layout, w.shape[1])
            if k > 1:
                return _dx_chunked(layout, in_ax, out_ax, dcg, w,
                                   k).astype(dc.dtype)
        wg = lax.all_gather(w, "x", axis=1, tiled=True) if shard_f else w
        dxp = jnp.einsum("bsf,hf->bsh", dcg, wg,
                         preferred_element_type=jnp.float32).astype(dc.dtype)
        if shard_f:
            # contraction dim f is split over in_ax -> reduce-scatter sums it
            return lax.psum_scatter(dxp, in_ax, scatter_dimension=1, tiled=True)
        # f unsplit: dxp is already the full value (identical across in_ax);
        # just take this device's seq slice — zero communication.
        si = layout.size(in_ax)
        s_loc = dxp.shape[1] // si
        idx = lax.axis_index(in_ax)
        return lax.dynamic_slice_in_dim(dxp, idx * s_loc, s_loc, axis=1)

    return _shmap(layout, body,
                  (y_spec3d(layout, in_ax, out_ax, shard_f),
                   w_spec3d(in_ax, out_ax, shard_f)),
                  _x_spec(layout, in_ax, out_ax))


def _matmul3d_dw_island(layout, in_ax, out_ax, shard_f=True):
    # Algorithm 2, line 2:  dw = x^T @ dc  in directions (in_ax, out_ax, x)
    sync = _grad_sync_axes(layout)

    def body(x, dc):
        dcg = lax.all_gather(dc, out_ax, axis=1, tiled=True)   # (b, S', f/si)
        k = _overlap_k(layout, x.shape[-1])
        if k > 1:
            dw = _dw_chunked(layout, in_ax, out_ax, shard_f, x, dcg, k)
            if sync:
                dw = lax.psum(dw, sync)
            return dw.astype(x.dtype)
        xg = lax.all_gather(x, in_ax, axis=1, tiled=True)      # (b, S', h/so)
        dwp = jnp.einsum("bsh,bsf->hf", xg, dcg,
                         preferred_element_type=jnp.float32)   # partial over batch+x
        # bf16 gradient reduction (standard practice): halves the dw
        # reduce-scatter / all-reduce bytes (EXPERIMENTS.md §Perf P1.i3)
        dwp = dwp.astype(x.dtype)
        if shard_f:
            dw = lax.psum_scatter(dwp, "x", scatter_dimension=1, tiled=True)
        else:
            dw = lax.psum(dwp, "x") if layout.size("x") > 1 else dwp
        if sync:
            dw = lax.psum(dw, sync)                            # data-parallel reduce
        return dw.astype(x.dtype)

    return _shmap(layout, body,
                  (_x_spec(layout, in_ax, out_ax),
                   y_spec3d(layout, in_ax, out_ax, shard_f)),
                  w_spec3d(in_ax, out_ax, shard_f))


def _matmul3d_vjp_fwd(layout, in_ax, out_ax, shard_f, x, w):
    # Residuals are the *balanced* blocks (O(1/P) memory) — gathered copies
    # are re-formed in the backward islands, exactly like the paper's Alg. 2.
    return _matmul3d(layout, in_ax, out_ax, shard_f, x, w), (x, w)


def _matmul3d_bwd_island(layout, in_ax, out_ax, shard_f=True):
    """Fused Algorithm-2 backward: dx and dw share one gather of dc along
    out_ax (the paper's pseudo-code gathers it twice) — §Perf iteration."""
    sync = _grad_sync_axes(layout)

    def body(x, dc, w):
        dcg = lax.all_gather(dc, out_ax, axis=1, tiled=True)   # shared gather
        k = _overlap_k(layout, x.shape[-1])
        if k > 1:
            if shard_f:
                kf = _overlap_k(layout, w.shape[1])
                dx = (_dx_chunked(layout, in_ax, out_ax, dcg, w, kf)
                      .astype(dc.dtype) if kf > 1 else None)
            else:
                dx = None
            if dx is None:
                wg = (lax.all_gather(w, "x", axis=1, tiled=True)
                      if shard_f else w)
                dxp = jnp.einsum("bsf,hf->bsh", dcg, wg,
                                 preferred_element_type=jnp.float32
                                 ).astype(dc.dtype)
                if shard_f:
                    dx = lax.psum_scatter(dxp, in_ax, scatter_dimension=1,
                                          tiled=True)
                else:
                    si = layout.size(in_ax)
                    s_loc = dxp.shape[1] // si
                    idx = lax.axis_index(in_ax)
                    dx = lax.dynamic_slice_in_dim(dxp, idx * s_loc, s_loc,
                                                  axis=1)
            dw = _dw_chunked(layout, in_ax, out_ax, shard_f, x, dcg, k)
            if sync:
                dw = lax.psum(dw, sync)
            return dx, dw.astype(x.dtype)
        wg = lax.all_gather(w, "x", axis=1, tiled=True) if shard_f else w
        dxp = jnp.einsum("bsf,hf->bsh", dcg, wg,
                         preferred_element_type=jnp.float32).astype(dc.dtype)
        if shard_f:
            dx = lax.psum_scatter(dxp, in_ax, scatter_dimension=1, tiled=True)
        else:
            si = layout.size(in_ax)
            s_loc = dxp.shape[1] // si
            idx = lax.axis_index(in_ax)
            dx = lax.dynamic_slice_in_dim(dxp, idx * s_loc, s_loc, axis=1)
        xg = lax.all_gather(x, in_ax, axis=1, tiled=True)
        dwp = jnp.einsum("bsh,bsf->hf", xg, dcg,
                         preferred_element_type=jnp.float32).astype(x.dtype)
        if shard_f:
            dw = lax.psum_scatter(dwp, "x", scatter_dimension=1, tiled=True)
        else:
            dw = lax.psum(dwp, "x") if layout.size("x") > 1 else dwp
        if sync:
            dw = lax.psum(dw, sync)
        return dx, dw.astype(x.dtype)

    return _shmap(layout, body,
                  (_x_spec(layout, in_ax, out_ax),
                   y_spec3d(layout, in_ax, out_ax, shard_f),
                   w_spec3d(in_ax, out_ax, shard_f)),
                  (_x_spec(layout, in_ax, out_ax),
                   w_spec3d(in_ax, out_ax, shard_f)))


def _matmul3d_vjp_bwd(layout, in_ax, out_ax, shard_f, res, dc):
    x, w = res
    return _matmul3d_bwd_island(layout, in_ax, out_ax, shard_f)(x, dc, w)


_matmul3d.defvjp(_matmul3d_vjp_fwd, _matmul3d_vjp_bwd)


# ---------------------------------------------------------------------------
# Decode path: single-token matvec against the 3-D weight placement.
# s == 1 cannot be sequence-sharded, so the gather/scatter on the token dim
# degenerates to: all-gather w along 'x', local matmul, all-reduce along
# out_ax.  Activation hidden splits still alternate in_ax <-> out_ax.
# ---------------------------------------------------------------------------
def matmul3d_decode(layout: Layout, in_ax: str, out_ax: str, x, w,
                    shard_f: bool = True):
    """x: (B, 1, H) P(batch, None, out_ax) -> (B, 1, F) P(batch, None, in_ax)."""
    gather_x = shard_f and not layout.inference_opt

    def body(x, w):
        wg = lax.all_gather(w, "x", axis=1, tiled=True) if gather_x else w
        c = _mm(x, wg)
        return lax.psum(c, out_ax)

    wspec = (P(out_ax, in_ax) if (shard_f and layout.inference_opt)
             else w_spec3d(in_ax, out_ax, shard_f))
    return _shmap(layout, body,
                  (P(layout.batch_spec(), None, out_ax), wspec),
                  P(layout.batch_spec(), None, in_ax if shard_f else None))(x, w)


# ---------------------------------------------------------------------------
# Vocab-parallel embedding (3-D placement: table rows over in_ax, cols over
# out_ax, replicated over x/batch axes).  Lookup gathers the int ids along
# in_ax (cheap), takes from the local vocab slice with masking, and the
# reduce-scatter along in_ax simultaneously sums the vocab partials and
# restores the balanced sequence split.
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def embedding3d(layout: Layout, in_ax: str, out_ax: str, ids, table):
    return _embed_fwd_island(layout, in_ax, out_ax)(ids, table)


def embed_table_spec(in_ax: str, out_ax: str) -> P:
    return P(in_ax, out_ax)


def _embed_fwd_island(layout, in_ax, out_ax):
    def body(ids, table):
        v_loc = table.shape[0]
        idsg = lax.all_gather(ids, in_ax, axis=1, tiled=True)    # (b, S')
        start = lax.axis_index(in_ax) * v_loc
        local = idsg - start
        ok = (local >= 0) & (local < v_loc)
        emb = jnp.take(table, jnp.clip(local, 0, v_loc - 1), axis=0)
        emb = jnp.where(ok[..., None], emb, 0).astype(table.dtype)
        return lax.psum_scatter(emb, in_ax, scatter_dimension=1, tiled=True)

    return _shmap(layout, body,
                  (P(layout.batch_spec(), _seq_spec(layout, in_ax)),
                   embed_table_spec(in_ax, out_ax)),
                  _x_spec(layout, in_ax, out_ax))


def _embed_vjp_fwd(layout, in_ax, out_ax, ids, table):
    # the table residual is only used for its shape/dtype (zero-cost alias)
    return embedding3d(layout, in_ax, out_ax, ids, table), (ids, table)


def _embed_vjp_bwd(layout, in_ax, out_ax, res, dc):
    ids, table = res
    tshape, tdtype = table.shape, table.dtype
    sync = tuple(a for a in (*_grad_sync_axes(layout), "x") if layout.size(a) > 1)
    v_local = tshape[0] // layout.size(in_ax)

    def body(ids, dc):
        v_loc = v_local
        idsg = lax.all_gather(ids, in_ax, axis=1, tiled=True)    # (b, S')
        dcg = lax.all_gather(dc, in_ax, axis=1, tiled=True)      # (b, S', h/so)
        start = lax.axis_index(in_ax) * v_loc
        local = idsg - start
        ok = (local >= 0) & (local < v_loc)
        upd = jnp.where(ok[..., None], dcg, 0).astype(jnp.float32)
        flat_ids = jnp.clip(local, 0, v_loc - 1).reshape(-1)
        dtab = jnp.zeros((v_loc, dcg.shape[-1]), jnp.float32)
        dtab = dtab.at[flat_ids].add(upd.reshape(-1, dcg.shape[-1]))
        if sync:
            dtab = lax.psum(dtab, sync)
        return dtab.astype(tdtype)

    dtable = _shmap(layout, body,
                    (P(layout.batch_spec(), _seq_spec(layout, in_ax)),
                     _x_spec(layout, in_ax, out_ax)),
                    embed_table_spec(in_ax, out_ax))(ids, dc)
    return None, dtable


embedding3d.defvjp(_embed_vjp_fwd, _embed_vjp_bwd)


# ---------------------------------------------------------------------------
# No-swap linear: contraction over the hidden split (psum over out_ax), the
# sequence split untouched, output features replicated.  Used for small
# low-rank projections (MLA down-projections) where a direction exchange
# would leave the enclosing block with an odd number of swaps.
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def matmul3d_noswap(layout: Layout, in_ax: str, out_ax: str, x, w):
    """x: (B,S,H) P(batch, in_ax, out_ax) @ w: (H,F) P(out_ax, None)
    -> (B,S,F) P(batch, in_ax, None)."""
    def body(x, w):
        c = _mm(x, w)
        return lax.psum(c, out_ax)
    return _shmap(layout, body,
                  (_x_spec(layout, in_ax, out_ax), P(out_ax, None)),
                  P(layout.batch_spec(), _seq_spec(layout, in_ax), None))(x, w)


def _noswap_vjp_fwd(layout, in_ax, out_ax, x, w):
    return matmul3d_noswap(layout, in_ax, out_ax, x, w), (x, w)


def _noswap_vjp_bwd(layout, in_ax, out_ax, res, dc):
    x, w = res
    sync = _grad_sync_axes(layout)

    def dx_body(dc, w):
        # w rows split over out_ax; contraction over full F — local, no comm
        return jnp.einsum("bsf,hf->bsh", dc, w,
                          preferred_element_type=jnp.float32).astype(dc.dtype)

    def dw_body(x, dc):
        dwp = jnp.einsum("bsh,bsf->hf", x, dc, preferred_element_type=jnp.float32)
        red = tuple(a for a in ("x", in_ax, *sync) if layout.size(a) > 1)
        if red:
            dwp = lax.psum(dwp, red)
        return dwp.astype(x.dtype)

    dspec = P(layout.batch_spec(), _seq_spec(layout, in_ax), None)
    dx = _shmap(layout, dx_body, (dspec, P(out_ax, None)),
                _x_spec(layout, in_ax, out_ax))(dc, w)
    dw = _shmap(layout, dw_body, (_x_spec(layout, in_ax, out_ax), dspec),
                P(out_ax, None))(x, dc)
    return dx, dw


matmul3d_noswap.defvjp(_noswap_vjp_fwd, _noswap_vjp_bwd)


# ---------------------------------------------------------------------------
# Replicated-contraction linear (the up-projection dual of matmul3d_noswap):
# the contraction dim is replicated, so the local matmul is exact and the
# "reduce-scatter" degenerates to a zero-communication sequence slice.
# Used for MLA up-projections out of a low-rank latent.
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def matmul3d_repc(layout: Layout, in_ax: str, out_ax: str, x, w):
    """x: (B,S,R) P(batch, in_ax, None) @ w: (R,F) P(None, (in_ax, x))
    -> (B,S,F) P(batch, out_ax, in_ax)."""
    so = layout.size(out_ax)

    def body(x, w):
        xg = lax.all_gather(x, in_ax, axis=1, tiled=True)     # (b, S', R)
        wg = lax.all_gather(w, "x", axis=1, tiled=True)       # (R, f/si)
        c = _mm(xg, wg)                                       # exact (R replicated)
        s_loc = c.shape[1] // so
        idx = lax.axis_index(out_ax)
        return lax.dynamic_slice_in_dim(c, idx * s_loc, s_loc, axis=1)

    return _shmap(layout, body,
                  (P(layout.batch_spec(), _seq_spec(layout, in_ax), None),
                   P(None, (in_ax, "x"))),
                  _y_spec(layout, in_ax, out_ax))(x, w)


def matmul3d_repc_decode(layout: Layout, in_ax: str, out_ax: str, x, w):
    """Decode variant: x (B,1,R) replicated -> (B,1,F) split over in_ax."""
    gather_x = not layout.inference_opt

    def body(x, w):
        wg = lax.all_gather(w, "x", axis=1, tiled=True) if gather_x else w
        return _mm(x, wg)
    wspec = P(None, in_ax) if layout.inference_opt else P(None, (in_ax, "x"))
    return _shmap(layout, body,
                  (P(layout.batch_spec(), None, None), wspec),
                  P(layout.batch_spec(), None, in_ax))(x, w)


def _repc_vjp_fwd(layout, in_ax, out_ax, x, w):
    return matmul3d_repc(layout, in_ax, out_ax, x, w), (x, w)


def _repc_vjp_bwd(layout, in_ax, out_ax, res, dc):
    x, w = res
    sync = _grad_sync_axes(layout)

    def dx_body(dc, w):
        dcg = lax.all_gather(dc, out_ax, axis=1, tiled=True)   # (b, S', f/si)
        wg = lax.all_gather(w, "x", axis=1, tiled=True)        # (R, f/si)
        dxp = jnp.einsum("bsf,hf->bsh", dcg, wg,
                         preferred_element_type=jnp.float32).astype(dc.dtype)
        return lax.psum_scatter(dxp, in_ax, scatter_dimension=1, tiled=True)

    def dw_body(x, dc):
        xg = lax.all_gather(x, in_ax, axis=1, tiled=True)      # (b, S', R)
        dcg = lax.all_gather(dc, out_ax, axis=1, tiled=True)   # (b, S', f/si)
        dwp = jnp.einsum("bsh,bsf->hf", xg, dcg, preferred_element_type=jnp.float32)
        dw = lax.psum_scatter(dwp, "x", scatter_dimension=1, tiled=True)
        if sync:
            dw = lax.psum(dw, sync)
        return dw.astype(x.dtype)

    xspec = P(layout.batch_spec(), _seq_spec(layout, in_ax), None)
    wspec = P(None, (in_ax, "x"))
    dx = _shmap(layout, dx_body, (_y_spec(layout, in_ax, out_ax), wspec), xspec)(dc, w)
    dw = _shmap(layout, dw_body, (xspec, _y_spec(layout, in_ax, out_ax)), wspec)(x, dc)
    return dx, dw


matmul3d_repc.defvjp(_repc_vjp_fwd, _repc_vjp_bwd)


def swap_dirs(in_ax: str, out_ax: str) -> Tuple[str, str]:
    return out_ax, in_ax
