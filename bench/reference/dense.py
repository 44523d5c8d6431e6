"""Plain float32 reference of a dense decoder: embedding, pre-norm blocks of
attention (multi-head or grouped-query, optional RMSNorm of q and k per
head, rotate-half RoPE, causal softmax) and MLP (SwiGLU or tanh-GELU), a
final norm and an output head.

It imports nothing of the program.  What the model holds (qk-norm, a gated
MLP and its activation, RMSNorm or LayerNorm) and every size come from the
configuration file's keys (``features``), never from the weights it is
given.  It reads the benchmark's weights by their names in the parameter
tree, and ``leaves_off`` lists where that tree departs from the leaves and
shapes the configuration states: ``embed`` (V, d), ``head`` (d, V),
``ln_f``, and under ``stack/dense`` the layer-stacked ``ln1``, ``ln2``,
``attn/{wq,wk,wv,wo[,q_norm,k_norm]}`` and ``mlp/{w_up[,w_gate],w_down}``.

``prec`` selects how every matrix product is computed: ``"f32"`` in float32
at the highest precision (the reference), ``"fp8"`` with both operands
rounded to float8 e4m3 under a per-tensor scale (the control: the
precision below the bfloat16 that the configurations state).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
E4M3_MAX = 448.0


def _q8(x):
    """Round to float8 e4m3 under a per-tensor scale; straight-through."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / E4M3_MAX
    y = (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s
    return x + jax.lax.stop_gradient(y - x)


def mm(spec, a, b, prec):
    a, b = a.astype(F32), b.astype(F32)
    if prec == "fp8":
        a, b = _q8(a), _q8(b)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def features(c: dict) -> dict:
    """What the configuration states the model has.  Qwen3 applies an
    RMSNorm to each head of q and k (its ``model_type``); other files say
    so in a ``qk_norm`` key."""
    act = c.get("hidden_act", "silu")
    if act not in ("silu", "gelu_tanh"):
        raise ValueError(f"no reference for hidden_act {act!r}")
    return {"qk_norm": bool(c.get("qk_norm", c.get("model_type") == "qwen3")),
            "gated": c.get("mlp", "gated") == "gated", "act": act,
            "layernorm": c.get("norm", "rmsnorm") == "layernorm"}


def expected_leaves(c: dict) -> dict:
    """Path -> shape of every weight the configuration states."""
    f = features(c)
    L, d, V = c["num_hidden_layers"], c["hidden_size"], c["vocab_size"]
    nh, nkv, dh = (c["num_attention_heads"], c["num_key_value_heads"],
                   c["head_dim"])
    ff = c["intermediate_size"]
    norm = ("g", "b") if f["layernorm"] else ("g",)
    out = {"embed": (V, d), "head": (d, V)}
    out.update({f"ln_f/{n}": (d,) for n in norm})
    blk = {f"{ln}/{n}": (d,) for ln in ("ln1", "ln2") for n in norm}
    blk.update({"attn/wq": (d, nh * dh), "attn/wk": (d, nkv * dh),
                "attn/wv": (d, nkv * dh), "attn/wo": (nh * dh, d),
                "mlp/w_up": (d, ff), "mlp/w_down": (ff, d)})
    if f["qk_norm"]:
        blk.update({"attn/q_norm": (dh,), "attn/k_norm": (dh,)})
    if f["gated"]:
        blk["mlp/w_gate"] = (d, ff)
    out.update({f"stack/dense/{k}": (L,) + v for k, v in blk.items()})
    return out


def leaves_off(c: dict, params) -> list:
    """The leaves of ``params`` that the configuration does not state, or
    states with another shape, and those it states that are missing."""
    got = {"/".join(str(getattr(k, "key", k)) for k in path): tuple(x.shape)
           for path, x in jax.tree_util.tree_flatten_with_path(params)[0]}
    want = expected_leaves(c)
    return sorted(k for k in set(got) | set(want) if got.get(k) != want.get(k))


def _norm(x, p, c):
    if features(c)["layernorm"]:
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
        y = (x - mu) / jnp.sqrt(var + c["layer_norm_eps"])
        return y * p["g"].astype(F32) + p["b"].astype(F32)
    return _rms(x, p["g"], c["rms_norm_eps"])


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * \
        g.astype(F32)


def _rope(x, pos, theta):
    """x (B, S, n, d): rotate-half RoPE at positions pos (S,)."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = pos.astype(F32)[:, None] * inv[None, :]          # (S, d/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _act(c):
    if features(c)["act"] == "gelu_tanh":
        return lambda h: jax.nn.gelu(h, approximate=True)
    return jax.nn.silu


def block(c, x, p, prec):
    """One pre-norm block on x (B, S, d)."""
    B, S, _ = x.shape
    nh, nkv, dh = (c["num_attention_heads"], c["num_key_value_heads"],
                   c["head_dim"])
    f = features(c)
    a = p["attn"]
    h = _norm(x, p["ln1"], c)
    q = mm("bsd,df->bsf", h, a["wq"], prec).reshape(B, S, nh, dh)
    k = mm("bsd,df->bsf", h, a["wk"], prec).reshape(B, S, nkv, dh)
    v = mm("bsd,df->bsf", h, a["wv"], prec).reshape(B, S, nkv, dh)
    if f["qk_norm"]:
        eps = c["rms_norm_eps"]
        q, k = _rms(q, a["q_norm"], eps), _rms(k, a["k_norm"], eps)
    pos = jnp.arange(S)
    q, k = _rope(q, pos, c["rope_theta"]), _rope(k, pos, c["rope_theta"])
    g = nh // nkv
    q = q.reshape(B, S, nkv, g, dh) / math.sqrt(dh)
    s = mm("bqhgd,bkhd->bhgqk", q, k, prec)
    causal = pos[:, None] >= pos[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    o = mm("bhgqk,bkhd->bqhgd", w, v, prec).reshape(B, S, nh * dh)
    x = x + mm("bsf,fd->bsd", o, a["wo"], prec)
    h = _norm(x, p["ln2"], c)
    m = p["mlp"]
    up = mm("bsd,df->bsf", h, m["w_up"], prec)
    if f["gated"]:
        hid = _act(c)(mm("bsd,df->bsf", h, m["w_gate"], prec)) * up
    else:
        hid = _act(c)(up)
    return x + mm("bsf,fd->bsd", hid, m["w_down"], prec)


def hidden(c, params, tokens, prec="f32"):
    """Final-norm hidden states (B, S, d) of token ids (B, S)."""
    x = params["embed"].astype(F32)[tokens]

    def body(x, p):
        return block(c, x, p, prec), None

    x, _ = jax.lax.scan(body, x, params["stack"]["dense"])
    return _norm(x, params["ln_f"], c)


def logits(c, params, tokens, prec="f32"):
    """(B, S, V) float32 logits."""
    return mm("bsd,dv->bsv", hidden(c, params, tokens, prec),
              params["head"], prec)
