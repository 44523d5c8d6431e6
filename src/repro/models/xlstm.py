"""xLSTM blocks (mLSTM matrix-memory + sLSTM scalar-memory), parallelized
like the Mamba2 block: 3-D matmuls for all projections, heads sharded over
the projection's feature split, time recurrence on the gathered sequence.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from ..config import ModelConfig
from ..core.linear3d import norm_param, plinear, rmsnorm, weight_param
from ..core.params import Param
from ..core.topology import Dirs, Layout

F32 = jnp.float32


# ---------------------------------------------------------------------------
# Pure recurrences (f32) — also serve as kernel oracles
# ---------------------------------------------------------------------------
def mlstm_scan_seq(q, k, v, ig, fg, state=None):
    """Sequential reference. q/k/v: (b, T, nh, dh); ig/fg: (b, T, nh)."""
    b, T, nh, dh = q.shape
    scale = 1.0 / math.sqrt(dh)
    if state is None:
        C0 = jnp.zeros((b, nh, dh, dh), F32)
        n0 = jnp.zeros((b, nh, dh), F32)
        m0 = jnp.full((b, nh), -1e30, F32)
    else:
        C0, n0, m0 = state

    def step(carry, xs):
        C, n, m = carry
        qt, kt, vt, it, ft = xs
        m_new = jnp.maximum(ft + m, it)
        i_ = jnp.exp(it - m_new)
        f_ = jnp.exp(ft + m - m_new)
        kt = kt * scale
        C = f_[..., None, None] * C + i_[..., None, None] * \
            jnp.einsum("bhd,bhe->bhde", vt, kt)
        n = f_[..., None] * n + i_[..., None] * kt
        num = jnp.einsum("bhde,bhe->bhd", C, qt)
        den = jnp.maximum(jnp.abs(jnp.einsum("bhd,bhd->bh", n, qt)),
                          jnp.exp(-m_new))
        h = num / den[..., None]
        return (C, n, m_new), h

    xs = (q.astype(F32).swapaxes(0, 1), k.astype(F32).swapaxes(0, 1),
          v.astype(F32).swapaxes(0, 1), ig.astype(F32).swapaxes(0, 1),
          fg.astype(F32).swapaxes(0, 1))
    (C, n, m), hs = lax.scan(step, (C0, n0, m0), xs)
    return hs.swapaxes(0, 1), (C, n, m)


def mlstm_scan(q, k, v, ig, fg, state=None, chunk: int = 256):
    """Chunk-parallel stabilized mLSTM (matches mlstm_scan_seq).

    Within a chunk the stabilizer is m_t = b_t + max(cummax_j(i_j - b_j),
    m_carry - b_0...), where b is the cumulative log-forget; the carried
    state (C', n') is stored normalized by exp(m_carry).  Sequential scan
    runs over chunks only, checkpointed — O(T/Q) backward residuals.
    """
    b, T, nh, dh = q.shape
    scale = 1.0 / math.sqrt(dh)
    Q = min(chunk, T)
    while T % Q:
        Q -= 1
    nc = T // Q
    if state is None:
        C0 = jnp.zeros((b, nh, dh, dh), F32)
        n0 = jnp.zeros((b, nh, dh), F32)
        m0 = jnp.full((b, nh), -1e30, F32)
    else:
        C0, n0, m0 = state

    def chop(a):
        return a.reshape(b, nc, Q, *a.shape[2:]).swapaxes(0, 1)

    qc, kc, vc = chop(q), chop(k), chop(v)
    ic, fc = chop(ig), chop(fg)
    causal = jnp.tril(jnp.ones((Q, Q), bool))

    def step(carry, xs):
        C, n, m_c = carry                         # C/n normalized by exp(m_c)
        qq, kk, vv, ii, ff = xs                   # (b, Q, nh, ...)
        qq, vv = qq.astype(F32), vv.astype(F32)
        kk = kk.astype(F32) * scale
        ii, ff = ii.astype(F32), ff.astype(F32)
        bcum = jnp.cumsum(ff, axis=1)             # (b, Q, nh) cumulative log-f
        # stabilizer: m_t = max(b_t + m_c, b_t + cummax_j<=t (i_j - b_j))
        g = jax.lax.cummax(ii - bcum, axis=1)     # (b, Q, nh)
        m_t = bcum + jnp.maximum(g, m_c[:, None])
        # intra-chunk: w_tj = exp(b_t - b_j + i_j - m_t) for j <= t
        lw = (bcum[:, :, None] - bcum[:, None] + ii[:, None]) \
            - m_t[:, :, None]                     # (b, t, j, nh)
        lw = jnp.where(causal[None, :, :, None], lw, -1e30)  # mask pre-exp
        w = jnp.exp(lw)
        qk = jnp.einsum("bthd,bjhd->bhtj", qq, kk)            # (b, nh, t, j)
        num = jnp.einsum("bhtj,btjh,bjhn->bthn", qk, w, vv)
        den = jnp.einsum("bhtj,btjh->bth", qk, w)
        # carried-state contribution: exp(b_t + m_c - m_t) q_t . C'
        dec = jnp.exp(bcum + m_c[:, None] - m_t)  # (b, Q, nh)
        num = num + dec[..., None] * jnp.einsum("bthd,bhdn->bthn", qq, C)
        den = den + dec * jnp.einsum("bthd,bhd->bth", qq, n)
        h = num / jnp.maximum(jnp.abs(den), jnp.exp(-m_t))[..., None]
        # chunk-end state (normalized by m_T)
        m_T = m_t[:, -1]
        ws = jnp.exp((bcum[:, -1:] - bcum) + ii - m_T[:, None])  # (b, Q, nh)
        C = jnp.exp(bcum[:, -1] + m_c - m_T)[..., None, None] * C + \
            jnp.einsum("bjh,bjhd,bjhn->bhdn", ws, kk, vv)
        n = jnp.exp(bcum[:, -1] + m_c - m_T)[..., None] * n + \
            jnp.einsum("bjh,bjhd->bhd", ws, kk)
        return (C, n, m_T), h

    step = jax.checkpoint(step)
    (C, n, m), hs = lax.scan(step, (C0, n0, m0), (qc, kc, vc, ic, fc))
    return hs.swapaxes(0, 1).reshape(b, T, nh, dh), (C, n, m)


def mlstm_step(state, qt, kt, vt, it, ft):
    """Single decode step; qt/kt/vt: (b, nh, dh); it/ft: (b, nh)."""
    C, n, m = state
    dh = qt.shape[-1]
    scale = 1.0 / math.sqrt(dh)
    m_new = jnp.maximum(ft + m, it)
    i_ = jnp.exp(it - m_new)
    f_ = jnp.exp(ft + m - m_new)
    kt = kt * scale
    C = f_[..., None, None] * C + i_[..., None, None] * \
        jnp.einsum("bhd,bhe->bhde", vt, kt)
    n = f_[..., None] * n + i_[..., None] * kt
    num = jnp.einsum("bhde,bhe->bhd", C, qt)
    den = jnp.maximum(jnp.abs(jnp.einsum("bhd,bhd->bh", n, qt)),
                      jnp.exp(-m_new))
    return num / den[..., None], (C, n, m_new)


def slstm_scan(zg, ig, fg, og, R, state=None):
    """Gates pre-activation from the input path: (b, T, nh, dh) each.
    R: (4, nh, dh, dh) recurrent block-diagonal weights (z, i, f, o)."""
    b, T, nh, dh = zg.shape
    if state is None:
        c0 = jnp.zeros((b, nh, dh), F32)
        n0 = jnp.ones((b, nh, dh), F32)
        h0 = jnp.zeros((b, nh, dh), F32)
        m0 = jnp.zeros((b, nh, dh), F32)
    else:
        c0, n0, h0, m0 = state
    Rf = R.astype(F32)

    def step(carry, xs):
        c, n, h, m = carry
        zt, it, ft, ot = xs
        rec = jnp.einsum("ghde,bhe->gbhd", Rf.reshape(4, nh, dh, dh), h)
        zt, it, ft, ot = (zt + rec[0], it + rec[1], ft + rec[2], ot + rec[3])
        m_new = jnp.maximum(ft + m, it)
        i_ = jnp.exp(it - m_new)
        f_ = jnp.exp(ft + m - m_new)
        c = f_ * c + i_ * jnp.tanh(zt)
        n = f_ * n + i_
        h = jax.nn.sigmoid(ot) * c / jnp.maximum(n, 1e-6)
        return (c, n, h, m_new), h

    xs = tuple(a.astype(F32).swapaxes(0, 1) for a in (zg, ig, fg, og))
    (c, n, h, m), hs = lax.scan(jax.checkpoint(step), (c0, n0, h0, m0), xs)
    return hs.swapaxes(0, 1), (c, n, h, m)


def slstm_step(state, zt, it, ft, ot, R):
    c, n, h, m = state
    nh, dh = zt.shape[-2], zt.shape[-1]
    rec = jnp.einsum("ghde,bhe->gbhd", R.astype(F32).reshape(4, nh, dh, dh), h)
    zt, it, ft, ot = (zt.astype(F32) + rec[0], it.astype(F32) + rec[1],
                      ft.astype(F32) + rec[2], ot.astype(F32) + rec[3])
    m_new = jnp.maximum(ft + m, it)
    i_ = jnp.exp(it - m_new)
    f_ = jnp.exp(ft + m - m_new)
    c = f_ * c + i_ * jnp.tanh(zt)
    n = f_ * n + i_
    h = jax.nn.sigmoid(ot) * c / jnp.maximum(n, 1e-6)
    return h, (c, n, h, m_new)


# ---------------------------------------------------------------------------
# Parallel blocks
# ---------------------------------------------------------------------------
def _feat_ax(layout: Layout, dirs: Dirs):
    return dirs.in_ax if layout.strategy == "3d" else "z"


def _dims(cfg: ModelConfig):
    d_in = 2 * cfg.d_model          # projection factor 2 (xLSTM paper)
    nh = cfg.n_heads
    return d_in, nh, d_in // nh


def mlstm_params(layout: Layout, cfg: ModelConfig, dirs: Dirs):
    d = cfg.d_model
    d_in, nh, dh = _dims(cfg)
    return {
        "ln": norm_param(layout, dirs, d),
        "w_q": weight_param(layout, dirs, d, d_in, kind="first"),
        "w_k": weight_param(layout, dirs, d, d_in, kind="first"),
        "w_v": weight_param(layout, dirs, d, d_in, kind="first"),
        "w_z": weight_param(layout, dirs, d, d_in, kind="first"),
        "w_if": weight_param(layout, dirs, d, 2 * nh, kind="first", shard_f=False),
        "out_ln": Param((d_in,), P(_feat_ax(layout, dirs)), init="ones"),
        "w_out": weight_param(layout, dirs.swap(), d_in, d, kind="second"),
    }


def mlstm_apply(layout: Layout, cfg: ModelConfig, dirs: Dirs, x, p, positions,
                *, decode=False, cache=None):
    d_in, nh, dh = _dims(cfg)
    B_ = x.shape[0]
    h = rmsnorm(x, p["ln"])
    q, d2 = plinear(layout, dirs, h, p["w_q"], kind="first", decode=decode)
    k, _ = plinear(layout, dirs, h, p["w_k"], kind="first", decode=decode)
    v, _ = plinear(layout, dirs, h, p["w_v"], kind="first", decode=decode)
    zg, _ = plinear(layout, dirs, h, p["w_z"], kind="first", decode=decode)
    gif, _ = plinear(layout, dirs, h, p["w_if"], kind="first", shard_f=False,
                     decode=decode)

    feat_ax = _feat_ax(layout, dirs)
    n_feat = layout.size(feat_ax)
    nh_loc = nh // n_feat if nh % n_feat == 0 else nh

    if decode:
        qh = q.reshape(B_, nh, dh).astype(F32)
        kh = k.reshape(B_, nh, dh).astype(F32)
        vh = v.reshape(B_, nh, dh).astype(F32)
        ig, fg = gif[:, 0, :nh].astype(F32), gif[:, 0, nh:].astype(F32)
        fg = jax.nn.log_sigmoid(fg)
        y, new_state = mlstm_step(tuple(cache[k_] for k_ in ("C", "n", "m")),
                                  qh, kh, vh, ig, fg)
        y = y.reshape(B_, 1, d_in).astype(x.dtype)
        new_cache = {"C": new_state[0], "n": new_state[1], "m": new_state[2]}
    else:
        seq_ax = d2.in_ax if layout.strategy == "3d" else (
            "y" if layout.strategy == "2d" else None)
        gax = tuple(a for a in (*layout.seq_axes, seq_ax)
                    if a is not None and layout.size(a) > 1)
        nsh = math.prod(layout.size(a) for a in gax) if gax else 1
        xspec = P(layout.batch_spec(), gax or None,
                  feat_ax if n_feat > 1 else None)
        rspec = P(layout.batch_spec(), gax or None, None)

        def body(q, k, v, gif):
            if gax:
                q, k, v, gif = (lax.all_gather(a, gax, axis=1, tiled=True)
                                for a in (q, k, v, gif))
            hi = lax.axis_index(feat_ax) if n_feat > 1 else 0
            T = q.shape[1]
            qh = q.reshape(q.shape[0], T, nh_loc, dh)
            kh = k.reshape(q.shape[0], T, nh_loc, dh)
            vh = v.reshape(q.shape[0], T, nh_loc, dh)
            ig = lax.dynamic_slice_in_dim(gif[..., :nh], hi * nh_loc, nh_loc, 2)
            fg = jax.nn.log_sigmoid(
                lax.dynamic_slice_in_dim(gif[..., nh:], hi * nh_loc, nh_loc, 2)
                .astype(F32))
            y, _ = mlstm_scan(qh, kh, vh, ig, fg)
            y = y.reshape(q.shape[0], T, -1).astype(q.dtype)
            if gax:
                off = 0
                for a in gax:
                    off = off * layout.size(a) + lax.axis_index(a)
                y = lax.dynamic_slice_in_dim(y, off * (T // nsh), T // nsh, 1)
            return y

        y = shard_map(body, mesh=layout.mesh,
                          in_specs=(xspec, xspec, xspec, rspec),
                          out_specs=xspec, check_vma=False)(q, k, v, gif)
        new_cache = None

    y = rmsnorm(y * jax.nn.silu(zg.astype(F32)).astype(y.dtype), p["out_ln"])
    out, _ = plinear(layout, d2, y, p["w_out"], kind="second", decode=decode)
    return x + out, new_cache


def slstm_params(layout: Layout, cfg: ModelConfig, dirs: Dirs):
    d = cfg.d_model
    nh = cfg.n_heads
    dh = d // nh
    return {
        "ln": norm_param(layout, dirs, d),
        "w_gates": weight_param(layout, dirs, d, 4 * d, kind="first",
                                shard_f=False),
        "R": Param((4, nh, dh, dh), P(None, None, None, None), scale=0.3,
                   init="fan_in", fan_axis=-1),
        "w_out": weight_param(layout, dirs.swap(), d, d, kind="second"),
    }


def slstm_apply(layout: Layout, cfg: ModelConfig, dirs: Dirs, x, p, positions,
                *, decode=False, cache=None):
    d = cfg.d_model
    nh = cfg.n_heads
    dh = d // nh
    B_ = x.shape[0]
    h = rmsnorm(x, p["ln"])
    g, d2 = plinear(layout, dirs, h, p["w_gates"], kind="first", shard_f=False,
                    decode=decode)

    if decode:
        gt = g[:, 0].reshape(B_, 4, nh, dh)
        y, new_state = slstm_step(
            tuple(cache[k_] for k_ in ("c", "n", "h", "m")),
            gt[:, 0], gt[:, 1], gt[:, 2], gt[:, 3], p["R"])
        y = y.reshape(B_, 1, d).astype(x.dtype)
        new_cache = dict(zip(("c", "n", "h", "m"), new_state))
        # re-pack: slstm_step returns (c, n, h, m)
        new_cache = {"c": new_state[0], "n": new_state[1],
                     "h": new_state[2], "m": new_state[3]}
    else:
        seq_ax = d2.in_ax if layout.strategy == "3d" else (
            "y" if layout.strategy == "2d" else None)
        gax = tuple(a for a in (*layout.seq_axes, seq_ax)
                    if a is not None and layout.size(a) > 1)
        nsh = math.prod(layout.size(a) for a in gax) if gax else 1
        rspec = P(layout.batch_spec(), gax or None, None)

        def body(g, R):
            if gax:
                g = lax.all_gather(g, gax, axis=1, tiled=True)
            T = g.shape[1]
            gt = g.reshape(g.shape[0], T, 4, nh, dh)
            y, _ = slstm_scan(gt[:, :, 0], gt[:, :, 1], gt[:, :, 2],
                              gt[:, :, 3], R)
            y = y.reshape(g.shape[0], T, d).astype(g.dtype)
            if gax:
                off = 0
                for a in gax:
                    off = off * layout.size(a) + lax.axis_index(a)
                y = lax.dynamic_slice_in_dim(y, off * (T // nsh), T // nsh, 1)
            return y

        y = shard_map(body, mesh=layout.mesh,
                          in_specs=(rspec, P(None, None, None, None)),
                          out_specs=rspec, check_vma=False)(g, p["R"])
        new_cache = None

    out, _ = plinear(layout, d2, y, p["w_out"], kind="second", decode=decode)
    return x + out, new_cache


def mlstm_cache_init(layout: Layout, cfg: ModelConfig, dirs: Dirs, batch: int):
    d_in, nh, dh = _dims(cfg)
    feat_ax = _feat_ax(layout, dirs)
    hspec = feat_ax if nh % layout.size(feat_ax) == 0 and layout.size(feat_ax) > 1 else None
    bs = layout.batch_spec()
    return {
        "C": Param((batch, nh, dh, dh), P(bs, hspec, None, None),
                   dtype=jnp.float32, init="zeros"),
        "n": Param((batch, nh, dh), P(bs, hspec, None), dtype=jnp.float32,
                   init="zeros"),
        "m": Param((batch, nh), P(bs, hspec), dtype=jnp.float32, init="zeros"),
    }


def slstm_cache_init(layout: Layout, cfg: ModelConfig, dirs: Dirs, batch: int):
    nh = cfg.n_heads
    dh = cfg.d_model // nh
    bs = layout.batch_spec()
    z = lambda init: Param((batch, nh, dh), P(bs, None, None),
                           dtype=jnp.float32, init=init)
    return {"c": z("zeros"), "n": z("ones"), "h": z("zeros"), "m": z("zeros")}
