"""Per-step train telemetry: step time, tokens/s, MFU, memory watermarks,
loss/grad-norm series, and a non-finite sentinel.

``TrainTelemetry`` sits in the train loop as one call per step::

    tel = TrainTelemetry(cfg, layout, global_batch=B, seq_len=S)
    for step in range(steps):
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        rec = tel.record(step, metrics)          # blocks on metrics["loss"]

``record`` is an explicit sync point (it blocks on the loss so the step
time covers device work, not dispatch) — per-step telemetry is therefore
*not* free.

What it accounts:

  * step time with a warm-up split — the first ``warmup_steps`` steps
    (compile + first dispatch) are reported separately so the steady-state
    mean is not polluted by compilation.
  * tokens/s and model-FLOPs-utilization: the numerator comes from the
    registry's per-family ``step_flops`` hook
    (``registry.train_flops_per_token``), the denominator from
    ``peak_flops_per_device * n_devices``.  The peak is the caller's, or
    the ``PEAK_FLOPS`` entry for the layout's device kind; a device kind
    missing from the table with no explicit peak is an error.
  * per-device memory watermarks: ``device.memory_stats()`` where the
    backend provides it (TPU/GPU), else a ``live_buffers`` fallback that
    sums the per-device shard bytes of every live ``jax.Array`` — the CPU
    backend returns ``None`` from ``memory_stats``.
  * loss / grad-norm series (anything numeric in the step metrics dict is
    host-fetched once, after the loss sync — no extra device round trips).
  * a non-finite sentinel: the first non-finite loss flips
    ``tel.nonfinite`` and ``tel.blame(params)`` names the first offending
    param pytree path (``first_nonfinite_path``) — the tool the internvl2
    cube=(1,2,2) NaN regression reports through.
"""
from __future__ import annotations

import json
import time
from typing import Dict, List, Optional

# Published bf16 peak FLOP/s per chip, keyed by ``device.device_kind``.
#   "TPU v5 lite": TPU v5e, 197 TFLOP/s (Google Cloud documentation, "TPU v5e")
PEAK_FLOPS = {"TPU v5 lite": 197e12}


def peak_flops(device_kind: str) -> float:
    """The table's peak for ``device_kind``; a kind it lacks is an error
    (pass an explicit peak instead), never a default."""
    if device_kind not in PEAK_FLOPS:
        raise ValueError(f"no published peak FLOP/s for device kind "
                         f"{device_kind!r}; pass the peak explicitly "
                         f"(--peak-flops)")
    return PEAK_FLOPS[device_kind]


# ---------------------------------------------------------------------------
# Non-finite sentinel
# ---------------------------------------------------------------------------
def first_nonfinite_path(tree) -> Optional[str]:
    """Pytree path of the first leaf containing a non-finite value (NaN or
    inf), or None when every float leaf is finite.  Host-side diagnostic —
    it fetches leaves, so call it only after something already went wrong."""
    import jax
    import jax.numpy as jnp
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    for path, leaf in leaves:
        if not hasattr(leaf, "dtype") or \
                not jnp.issubdtype(leaf.dtype, jnp.floating):
            continue
        if not bool(jnp.all(jnp.isfinite(leaf))):
            return jax.tree_util.keystr(path)
    return None


def nonfinite_report(**trees) -> str:
    """One-line blame report over named pytrees: the first non-finite leaf
    path per tree, e.g. ``nonfinite_report(params=p, grads=g)`` ->
    ``"params: all finite; grads: ['layers']['0']['wq']"``."""
    parts = []
    for name, tree in trees.items():
        path = first_nonfinite_path(tree)
        parts.append(f"{name}: {path}" if path else f"{name}: all finite")
    return "; ".join(parts)


# ---------------------------------------------------------------------------
# Memory watermarks
# ---------------------------------------------------------------------------
def device_memory() -> Dict:
    """Per-device bytes in use: ``memory_stats()`` when the backend reports
    it, else the live-buffers fallback (sum of addressable shard bytes of
    every live jax.Array — what the CPU backend supports)."""
    import jax
    stats = {}
    for d in jax.local_devices():
        ms = None
        try:
            ms = d.memory_stats()
        except Exception:
            ms = None
        if ms:
            stats[str(d.id)] = {
                "bytes_in_use": int(ms.get("bytes_in_use", 0)),
                "peak_bytes_in_use": int(ms.get("peak_bytes_in_use",
                                                ms.get("bytes_in_use", 0))),
            }
    if stats:
        return {"source": "memory_stats", "per_device": stats}
    per: Dict[str, int] = {}
    for a in jax.live_arrays():
        try:
            shards = a.addressable_shards
        except Exception:
            continue
        for sh in shards:
            key = str(sh.device.id)
            per[key] = per.get(key, 0) + int(sh.data.nbytes)
    return {"source": "live_buffers",
            "per_device": {k: {"bytes_in_use": v, "peak_bytes_in_use": v}
                           for k, v in sorted(per.items())}}


# ---------------------------------------------------------------------------
# Per-step telemetry
# ---------------------------------------------------------------------------
class TrainTelemetry:
    def __init__(self, cfg, layout, *, global_batch: int, seq_len: int,
                 warmup_steps: int = 1,
                 peak_flops_per_device: Optional[float] = None,
                 mem_every: int = 1, clock=time.perf_counter, tracer=None):
        from ..models import registry
        from .trace import NULL
        self.cfg, self.layout = cfg, layout
        self.global_batch, self.seq_len = global_batch, seq_len
        self.warmup_steps = max(warmup_steps, 1)
        self.flops_per_step = (registry.train_flops_per_token(cfg, seq_len)
                               * global_batch * seq_len)
        self.n_devices = layout.n_devices
        if peak_flops_per_device is None:
            peak_flops_per_device = peak_flops(
                layout.mesh.devices.flat[0].device_kind)
        self.peak = float(peak_flops_per_device)
        self.mem_every = max(mem_every, 1)
        self._clock = clock
        self._last: Optional[float] = None
        self.tracer = tracer if tracer is not None else NULL
        self.records: List[dict] = []
        self.mem_source = ""
        self.mem_peak: Dict[str, int] = {}    # device id -> watermark bytes
        self.nonfinite: Optional[dict] = None

    def record(self, step: int, metrics: dict) -> dict:
        """Close out one step: sync on the loss, stamp the step time, fetch
        the scalar metrics, poll memory, run the finite check."""
        import jax
        import math
        jax.block_until_ready(metrics["loss"])
        now = self._clock()
        t_step = (now - self._last) if self._last is not None else 0.0
        self._last = now
        rec = {"step": int(step), "t_step": t_step,
               "warmup": len(self.records) < self.warmup_steps}
        for k, v in metrics.items():
            if hasattr(v, "ndim") and v.ndim == 0:
                rec[k] = float(v)
        if t_step > 0:
            rec["tokens_per_s"] = self.global_batch * self.seq_len / t_step
            rec["mfu"] = (self.flops_per_step / t_step
                          / (self.peak * self.n_devices))
        if (len(self.records) % self.mem_every) == 0:
            mem = device_memory()
            self.mem_source = mem["source"]
            for did, m in mem["per_device"].items():
                peak = m["peak_bytes_in_use"]
                if peak > self.mem_peak.get(did, 0):
                    self.mem_peak[did] = peak
        loss = rec.get("loss")
        if self.nonfinite is None and loss is not None \
                and not math.isfinite(loss):
            self.nonfinite = {"step": int(step), "loss": loss}
        self.records.append(rec)
        tr = self.tracer
        if tr.enabled:
            for k in ("loss", "gnorm"):
                if k in rec:
                    tr.counter(k, rec[k], track="telemetry")
            if t_step > 0:
                tr.counter("t_step_s", t_step, track="telemetry")
        return rec

    def blame(self, params) -> str:
        """Sentinel report for the current params (call on non-finite loss);
        names the first offending param path or declares the params clean."""
        return nonfinite_report(params=params)

    # -- reduction -----------------------------------------------------------
    def summary(self) -> dict:
        warm = [r["t_step"] for r in self.records
                if r["warmup"] and r["t_step"] > 0]
        steady = [r["t_step"] for r in self.records
                  if not r["warmup"] and r["t_step"] > 0]
        losses = [r["loss"] for r in self.records if "loss" in r]
        gnorms = [r["gnorm"] for r in self.records if "gnorm" in r]
        mean = lambda xs: sum(xs) / len(xs) if xs else 0.0  # noqa: E731
        t_steady = mean(steady)
        toks = (self.global_batch * self.seq_len / t_steady
                if t_steady > 0 else 0.0)
        return {
            "steps": len(self.records),
            "warmup_steps": self.warmup_steps,
            "t_step_warmup_s": mean(warm),
            "t_step_s": t_steady,
            "tokens_per_s": toks,
            "flops_per_step": self.flops_per_step,
            "peak_flops_per_device": self.peak,
            "n_devices": self.n_devices,
            "mfu": (self.flops_per_step / t_steady
                    / (self.peak * self.n_devices) if t_steady > 0 else 0.0),
            "mem_source": self.mem_source,
            "mem_peak_bytes_per_device": dict(self.mem_peak),
            "mem_peak_bytes_max": max(self.mem_peak.values(), default=0),
            "loss_first": losses[0] if losses else None,
            "loss_last": losses[-1] if losses else None,
            "gnorm_max": max(gnorms, default=0.0),
            "nonfinite": self.nonfinite,
            "series": {"loss": losses, "gnorm": gnorms,
                       "t_step": [r["t_step"] for r in self.records]},
        }

    def write(self, path: str):
        with open(path, "w") as f:
            json.dump(self.summary(), f, indent=2, sort_keys=True)

    def format_summary(self) -> str:
        s = self.summary()
        mem = s["mem_peak_bytes_max"] / 2**20
        lines = [
            f"telemetry: {s['steps']} steps "
            f"(warmup {s['warmup_steps']}: {s['t_step_warmup_s']:.3f}s, "
            f"steady {s['t_step_s']:.3f}s/step)",
            f"  {s['tokens_per_s']:.0f} tok/s   "
            f"MFU {s['mfu']*100:.2f}% of {s['n_devices']}x"
            f"{s['peak_flops_per_device']:.0e} FLOP/s",
            f"  mem watermark {mem:.1f} MiB/device [{s['mem_source']}]",
        ]
        if s["nonfinite"] is not None:
            lines.append(f"  NON-FINITE loss at step "
                         f"{s['nonfinite']['step']}")
        return "\n".join(lines)
