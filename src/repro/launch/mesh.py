"""Production mesh definitions.

``make_production_mesh`` is the prescribed topology (verbatim).  The
framework view (``make_framework_layout``) factors the 16-wide model axis
into the paper's (x, y, z) cube by reshaping the *same row-major device
order*, so the physical topology is identical — "data" = dp, "model" = x*y*z.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType

from ..core.topology import Layout, factor_model_axis, make_layout


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_framework_layout(*, multi_pod: bool = False, strategy: str = "3d",
                          cube: Optional[Tuple[int, int, int]] = None,
                          batch_axes=("pod", "dp", "x"), seq_axes=(),
                          n_dp: int = 16, n_model: int = 16,
                          n_pp: int = 1, microbatches: int = 1,
                          zero_stage: int = 1) -> Layout:
    """6-axis layout over the production devices (same device order as the
    prescribed mesh: row-major over (pod, data, model)).  With n_pp > 1 the
    pipeline axis is carved out of the data axis (n_dp must divide by it)."""
    prod = make_production_mesh(multi_pod=multi_pod)
    devices = prod.devices.reshape(-1)
    if n_pp > 1:
        if n_dp % n_pp:
            raise ValueError(f"n_dp={n_dp} not divisible by pp={n_pp}")
        n_dp //= n_pp
    return make_layout(n_pod=2 if multi_pod else 1, n_dp=n_dp,
                       n_model=n_model, strategy=strategy, cube=cube,
                       batch_axes=batch_axes, seq_axes=seq_axes,
                       devices=devices, n_pp=n_pp, microbatches=microbatches,
                       zero_stage=zero_stage)


def shape_layout_args(shape_name: str, multi_pod: bool):
    """Per-input-shape batch/sequence axis policy (DESIGN.md §3)."""
    if shape_name == "train_4k":        # B=256
        return dict(batch_axes=("pod", "dp", "x"), seq_axes=())
    if shape_name == "prefill_32k":     # B=32 < pod*dp*x on multipod
        if multi_pod:
            return dict(batch_axes=("dp", "x"), seq_axes=("pod",))
        return dict(batch_axes=("dp", "x"), seq_axes=())
    if shape_name == "decode_32k":      # B=128
        return dict(batch_axes=("pod", "dp", "x"), seq_axes=())
    if shape_name == "long_500k":       # B=1: context-parallel KV over dp
        return dict(batch_axes=(), seq_axes=("pod", "dp") if multi_pod
                    else ("dp",))
    raise ValueError(shape_name)
