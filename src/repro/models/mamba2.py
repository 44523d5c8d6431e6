"""Mamba2 (SSD) block, 3-D parallel projections + head-sharded chunked scan.

The in/out projections use the paper's 3-D matmul (they are ordinary linear
ops); the SSD scan itself is a time recurrence — not a GEMM chain — so it is
sharded over *heads* (the in_ax split of the projection output) and runs on
the sequence gathered along the out_ax split (DESIGN.md §4).  The gathered
scan is recomputed redundantly across the out_ax group; replacing that with a
chunk-passing ppermute pipeline is a recorded §Perf candidate.
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from ..config import ModelConfig
from ..core.linear3d import norm_param, plinear, rmsnorm, weight_param, wsc
from ..core.params import Param
from ..core.topology import Dirs, Layout

F32 = jnp.float32
HEAD_DIM = 64


# ---------------------------------------------------------------------------
# Pure SSD reference (also the Pallas kernel oracle): chunked scan, f32.
# ---------------------------------------------------------------------------
def ssd_chunked(x, dt, A_log, B, C, D, chunk: int, init_state=None):
    """x: (b, T, nh, dh); dt: (b, T, nh); A_log: (nh,); B/C: (b, T, G, N);
    D: (nh,).  Returns (y: (b, T, nh, dh), final_state: (b, nh, dh, N)).

    Sequential lax.scan over chunks (state carried, per-chunk intra term),
    checkpointed so the backward pass stores only chunk-boundary states —
    the same structure as the Pallas ssd_scan kernel."""
    b, T, nh, dh = x.shape
    G, N = B.shape[2], B.shape[3]
    rep = nh // G
    Q = min(chunk, T)
    while T % Q:
        Q -= 1
    nc = T // Q

    # chunk inputs stay in the input dtype; per-chunk f32 casts happen
    # inside the checkpointed step (bounds the f32 working set to one chunk)
    xc = x.reshape(b, nc, Q, nh, dh).swapaxes(0, 1)       # (nc, b, Q, nh, dh)
    dtc = dt.reshape(b, nc, Q, nh).swapaxes(0, 1)
    Bc = B.reshape(b, nc, Q, G, N).swapaxes(0, 1)
    Cc = C.reshape(b, nc, Q, G, N).swapaxes(0, 1)
    a = -jnp.exp(A_log.astype(F32))                       # (nh,) < 0

    causal = jnp.tril(jnp.ones((Q, Q), bool))

    def step(h, inp):
        xr, dtq, Bq, Cq = inp                             # per-chunk slices
        dtf = jax.nn.softplus(dtq.astype(F32))            # (b, Q, nh)
        laq = dtf * a
        xq = xr.astype(F32) * dtf[..., None]              # (b, Q, nh, dh)
        Bq, Cq = Bq.astype(F32), Cq.astype(F32)
        cum = jnp.cumsum(laq, axis=1)                     # (b, Q, nh)
        tot = cum[:, -1]                                  # (b, nh)
        Bh = jnp.repeat(Bq, rep, axis=2) if rep > 1 else Bq   # (b, Q, nh, N)
        Ch = jnp.repeat(Cq, rep, axis=2) if rep > 1 else Cq
        cb = jnp.einsum("bihn,bjhn->bhij", Ch, Bh)        # (b, nh, Q, Q)
        cumT = cum.transpose(0, 2, 1)                     # (b, nh, Q)
        # mask the exponent BEFORE exp: the j > i entries are positive and
        # overflow to inf, which poisons the backward pass (inf * 0 = nan)
        ldec = jnp.where(causal, cumT[..., :, None] - cumT[..., None, :], -1e30)
        scores = jnp.where(causal, cb, 0.0) * jnp.exp(ldec)
        y = jnp.einsum("bhij,bjhd->bihd", scores, xq)
        # carried-state contribution
        y = y + jnp.einsum("bihn,bhdn->bihd", Ch * jnp.exp(cum)[..., None], h)
        # state update
        w = jnp.exp(tot[:, None] - cum)                   # (b, Q, nh)
        h = h * jnp.exp(tot)[..., None, None] \
            + jnp.einsum("bjh,bjhd,bjhn->bhdn", w, xq, Bh)
        return h, y

    step = jax.checkpoint(step)
    h0 = jnp.zeros((b, nh, dh, N), F32) if init_state is None \
        else init_state.astype(F32)
    hT, ys = lax.scan(step, h0, (xc, dtc, Bc, Cc))
    y = ys.swapaxes(0, 1).reshape(b, T, nh, dh)
    y = y + x.astype(F32) * D.astype(F32)[None, None, :, None]
    return y, hT


def ssd_step(state, x_t, dt_t, A_log, B_t, C_t, D):
    """Single decode step. state: (b, nh, dh, N); x_t: (b, nh, dh);
    dt_t: (b, nh); B_t/C_t: (b, G, N)."""
    b, nh, dh, N = state.shape
    G = B_t.shape[1]
    rep = nh // G
    a = -jnp.exp(A_log.astype(F32))
    dtf = jax.nn.softplus(dt_t.astype(F32))               # (b, nh)
    decay = jnp.exp(dtf * a)                              # (b, nh)
    Bh = jnp.repeat(B_t.astype(F32), rep, axis=1) if rep > 1 else B_t.astype(F32)
    Ch = jnp.repeat(C_t.astype(F32), rep, axis=1) if rep > 1 else C_t.astype(F32)
    xbar = x_t.astype(F32) * dtf[..., None]               # (b, nh, dh)
    new = state.astype(F32) * decay[..., None, None] + \
        jnp.einsum("bhd,bhn->bhdn", xbar, Bh)
    y = jnp.einsum("bhdn,bhn->bhd", new, Ch) + x_t.astype(F32) * D.astype(F32)[None, :, None]
    return y, new


def causal_conv(x, w, b):
    """Depthwise causal conv. x: (b, T, C); w: (K, C); b: (C,)."""
    K = w.shape[0]
    xp = jnp.pad(x.astype(F32), ((0, 0), (K - 1, 0), (0, 0)))
    y = sum(xp[:, i:i + x.shape[1]] * w[i].astype(F32) for i in range(K))
    return jax.nn.silu(y + b.astype(F32))


# ---------------------------------------------------------------------------
# Parallel Mamba2 block
# ---------------------------------------------------------------------------
class MambaCache(NamedTuple):
    state: jax.Array      # (B, nh, dh, N)
    conv: jax.Array       # (B, K-1, d_inner) — x-channel conv tail
    conv_bc: jax.Array    # (B, K-1, 2*G*N)


def _dims(cfg: ModelConfig):
    s = cfg.ssm
    d_in = s.expand * cfg.d_model
    nh = d_in // HEAD_DIM
    return d_in, nh, s.n_groups, s.d_state


def mamba_params(layout: Layout, cfg: ModelConfig, dirs: Dirs):
    d = cfg.d_model
    d_in, nh, G, N = _dims(cfg)
    K = cfg.ssm.d_conv
    return {
        "ln": norm_param(layout, dirs, d),
        "w_x": weight_param(layout, dirs, d, d_in, kind="first"),
        "w_z": weight_param(layout, dirs, d, d_in, kind="first"),
        "w_bc": weight_param(layout, dirs, d, 2 * G * N, kind="first", shard_f=False),
        "w_dt": weight_param(layout, dirs, d, nh, kind="first", shard_f=False),
        "dt_bias": Param((nh,), P(None), init="zeros", dtype=jnp.float32),
        "A_log": Param((nh,), P(None), init="zeros", dtype=jnp.float32),
        "D": Param((nh,), P(None), init="ones", dtype=jnp.float32),
        "conv_x": Param((K, d_in), _conv_spec(layout, dirs)),
        "conv_x_b": Param((d_in,), _conv_spec1(layout, dirs), init="zeros"),
        "conv_bc": Param((K, 2 * G * N), P(None, None)),
        "conv_bc_b": Param((2 * G * N,), P(None), init="zeros"),
        "gate_ln": Param((d_in,), _conv_spec1(layout, dirs), init="ones"),
        "w_out": weight_param(layout, dirs.swap(), d_in, d, kind="second"),
    }


def _feat_ax(layout: Layout, dirs: Dirs):
    """Axis sharding a projection's output features."""
    if layout.strategy == "3d":
        return dirs.in_ax
    return "z"


def _conv_spec(layout: Layout, dirs: Dirs) -> P:
    return P(None, _feat_ax(layout, dirs))


def _conv_spec1(layout: Layout, dirs: Dirs) -> P:
    return P(_feat_ax(layout, dirs))


def mamba_apply(layout: Layout, cfg: ModelConfig, dirs: Dirs, x, p,
                positions, *, decode=False, cache: MambaCache = None):
    """Pre-norm Mamba2 block with residual. Returns (y, new_cache)."""
    d_in, nh, G, N = _dims(cfg)
    K = cfg.ssm.d_conv
    B_, S_ = x.shape[0], x.shape[1]
    h = rmsnorm(x, p["ln"])
    xc, d2 = plinear(layout, dirs, h, p["w_x"], kind="first", decode=decode)
    zg, _ = plinear(layout, dirs, h, p["w_z"], kind="first", decode=decode)
    bc, _ = plinear(layout, dirs, h, p["w_bc"], kind="first", shard_f=False,
                    decode=decode)
    dt, _ = plinear(layout, dirs, h, p["w_dt"], kind="first", shard_f=False,
                    decode=decode)

    feat_ax = _feat_ax(layout, dirs)
    n_feat = layout.size(feat_ax)
    nh_loc = nh // n_feat

    if decode:
        # --- GSPMD decode: single-step state update, heads sharded ---
        conv_in = jnp.concatenate([cache["conv"], xc.astype(F32)], axis=1)  # (B,K,d_in)
        x_t = jax.nn.silu(jnp.sum(conv_in * p["conv_x"].astype(F32)[None], axis=1)
                          + p["conv_x_b"].astype(F32))
        conv_bc_in = jnp.concatenate([cache["conv_bc"], bc.astype(F32)], axis=1)
        bc_t = jax.nn.silu(jnp.sum(conv_bc_in * p["conv_bc"].astype(F32)[None], axis=1)
                           + p["conv_bc_b"].astype(F32))
        B_t = bc_t[:, :G * N].reshape(B_, G, N)
        C_t = bc_t[:, G * N:].reshape(B_, G, N)
        dt_t = dt[:, 0].astype(F32) + p["dt_bias"].astype(F32)
        xh = x_t.reshape(B_, nh, HEAD_DIM)
        y, new_state = ssd_step(cache["state"], xh, dt_t, p["A_log"], B_t, C_t, p["D"])
        y = y.reshape(B_, 1, d_in).astype(x.dtype)
        new_cache = {"state": new_state, "conv": conv_in[:, 1:],
                     "conv_bc": conv_bc_in[:, 1:]}
    else:
        # --- scan island: gather seq along the out_ax split, slice heads ---
        seq_ax = d2.in_ax if layout.strategy == "3d" else (
            "y" if layout.strategy == "2d" else None)
        gax = tuple(a for a in (*layout.seq_axes, seq_ax)
                    if a is not None and layout.size(a) > 1)
        nsh = math.prod(layout.size(a) for a in gax) if gax else 1

        xspec = P(layout.batch_spec(), gax or None, feat_ax if n_feat > 1 else None)
        rspec = P(layout.batch_spec(), gax or None, None)

        def body(xc, bc, dt, cw, cwb, dtb, A_log, D):
            if gax:
                xc = lax.all_gather(xc, gax, axis=1, tiled=True)
                bc = lax.all_gather(bc, gax, axis=1, tiled=True)
                dt = lax.all_gather(dt, gax, axis=1, tiled=True)
            hi = lax.axis_index(feat_ax) if n_feat > 1 else 0
            dt_l = lax.dynamic_slice_in_dim(dt.astype(F32), hi * nh_loc, nh_loc, 2) \
                + lax.dynamic_slice_in_dim(dtb.astype(F32), hi * nh_loc, nh_loc, 0)
            A_l = lax.dynamic_slice_in_dim(A_log, hi * nh_loc, nh_loc, 0)
            D_l = lax.dynamic_slice_in_dim(D, hi * nh_loc, nh_loc, 0)
            xf = causal_conv(xc, cw, cwb)                     # (b, T, d_in_loc)
            bcf = jax.nn.silu(bc.astype(F32))                 # conv'd at GSPMD level
            Bt = bcf[..., :G * N].reshape(*bcf.shape[:2], G, N)
            Ct = bcf[..., G * N:].reshape(*bcf.shape[:2], G, N)
            T = xf.shape[1]
            xh = xf.reshape(xf.shape[0], T, nh_loc, HEAD_DIM)
            y, _ = ssd_chunked(xh, dt_l, A_l, Bt, Ct, D_l, cfg.ssm.chunk)
            y = y.reshape(xf.shape[0], T, -1).astype(xc.dtype)
            if gax:
                # every member of the gather group computed the full output —
                # take the local sequence slice (zero communication)
                off = 0
                for a in gax:
                    off = off * layout.size(a) + lax.axis_index(a)
                y = lax.dynamic_slice_in_dim(y, off * (T // nsh), T // nsh, 1)
            return y

        # conv over B/C at GSPMD level first (replicated feature dim)
        bc = _gspmd_causal_conv(bc, p["conv_bc"], p["conv_bc_b"], pre_act=False)
        y = shard_map(body, mesh=layout.mesh,
                          in_specs=(xspec, rspec, rspec,
                                    _conv_spec(layout, dirs), _conv_spec1(layout, dirs),
                                    P(None), P(None), P(None)),
                          out_specs=xspec, check_vma=False)(
            xc, bc, dt, p["conv_x"], p["conv_x_b"], p["dt_bias"],
            p["A_log"], p["D"])
        new_cache = None

    y = rmsnorm(y * jax.nn.silu(zg.astype(F32)).astype(y.dtype), p["gate_ln"])
    out, _ = plinear(layout, d2, y, p["w_out"], kind="second", decode=decode)
    return x + out, new_cache


def _gspmd_causal_conv(x, w, b, pre_act=True):
    """Causal depthwise conv at the GSPMD level (seq may be sharded; XLA
    inserts the halo exchange)."""
    K = w.shape[0]
    xp = jnp.pad(x.astype(F32), ((0, 0), (K - 1, 0), (0, 0)))
    y = sum(xp[:, i:i + x.shape[1]] * w[i].astype(F32) for i in range(K))
    return (y + b.astype(F32)).astype(x.dtype)


def mamba_cache_init(layout: Layout, cfg: ModelConfig, dirs: Dirs, batch: int):
    d_in, nh, G, N = _dims(cfg)
    K = cfg.ssm.d_conv
    feat_ax = _feat_ax(layout, dirs)
    return {
        "state": Param((batch, nh, HEAD_DIM, N),
                       P(layout.batch_spec(), feat_ax, None, None),
                       dtype=jnp.float32, init="zeros"),
        "conv": Param((batch, K - 1, d_in),
                      P(layout.batch_spec(), None, feat_ax),
                      dtype=jnp.float32, init="zeros"),
        "conv_bc": Param((batch, K - 1, 2 * G * N),
                         P(layout.batch_spec(), None, None),
                         dtype=jnp.float32, init="zeros"),
    }
