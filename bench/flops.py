"""Operations and bytes that the metrics divide by, from the configuration
file's published keys alone (never from the program's own counts).

* ``matmul_params``: parameters that take part in a matrix product per
  token: every block's projections and MLP, and the output head; the
  embedding is a lookup and does not count.
* ``decode_flops``: decoding one token for each of the context lengths
  given: 2 x matmul_params + 4 x layers x heads x head_dim x length per
  token.
* ``paged_attention``: the paged-decode kernel's operations and bytes for
  the same tokens, over their live context: q.k and p.v products, and K/V
  of every live token in bfloat16 plus each token's query in bfloat16 and
  its float32 output.
"""
from __future__ import annotations

from typing import Iterable, Tuple


def _dims(c: dict):
    return (c["num_hidden_layers"], c["hidden_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"], c["intermediate_size"],
            c["vocab_size"])


def matmul_params(c: dict) -> int:
    L, d, nh, nkv, dh, f, V = _dims(c)
    attn = d * nh * dh + 2 * d * nkv * dh + nh * dh * d
    mats = 2 if c.get("mlp", "gated") == "plain" else 3
    return L * (attn + mats * d * f) + d * V


def decode_flops(c: dict, live: Iterable[int]) -> float:
    L, _, nh, _, dh, _, _ = _dims(c)
    live = list(live)
    return (2.0 * matmul_params(c) * len(live)
            + 4.0 * L * nh * dh * sum(live))


def paged_attention(c: dict, live: Iterable[int]) -> Tuple[float, float]:
    """(operations, bytes) of the paged attention of decoding one token at
    each of the context lengths ``live``, all layers."""
    L, _, nh, nkv, dh, _, _ = _dims(c)
    live = list(live)
    ops = 4.0 * L * nh * dh * sum(live)
    kv = 2.0 * 2 * nkv * dh * sum(live)           # K and V, bf16
    qo = len(live) * nh * dh * (2 + 4)            # q bf16 in, f32 out
    return ops, L * (kv + qo)


def least_time(ops: float, nbytes: float, peak: dict) -> Tuple[float, str]:
    """The roofline's least time and which bound sets it."""
    t_c, t_m = ops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "bytes")
