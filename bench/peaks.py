"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

TPU v5e (Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of
HBM at 819 GB/s).  The described v5e that the compiler
rehearsal uses reports the same kind.  A kind that is not in the table is an
error, not a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}
PEAKS["TPU v5e"] = PEAKS["TPU v5 lite"]


def peak_for(kind: str) -> dict:
    try:
        return PEAKS[kind]
    except KeyError:
        raise ValueError(f"no published peak for device kind {kind!r}; "
                         f"known: {sorted(PEAKS)}") from None
