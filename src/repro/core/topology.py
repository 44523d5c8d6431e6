"""Mesh topology & parallel layout for 3-D tensor model parallelism.

The paper's processing cube has three directions (x, y, z).  We generalize the
p**3 cube to a rectangular grid (px, py, pz) so that a pod's 16-chip model axis
factors as (2, 2, 4); the cube (p, p, p) is the special case used in the
paper-fidelity tests.

Framework mesh axes (always all six, sizes may be 1):

    ("pod", "dp", "pp", "x", "y", "z")

``pod``/``dp`` carry data parallelism (and FSDP param sharding); ``pp`` is
the pipeline-stage axis (size = number of pipeline stages, 1 = no
pipelining); (x, y, z) is the model cube.  Activations cycle between two
layouts, following the paper's direction-exchange rule (section 3.2):

    X  : (B, S, H)  sharded  (BATCH, in_ax, out_ax)
    Y  : (B, S, F)  sharded  (BATCH, out_ax, in_ax)     after a 3-D linear

with in_ax/out_ax alternating between 'y' and 'z' after every linear layer,
while weights stay attached to 'x':

    W  : (H, F)     sharded  (out_ax, (in_ax, 'x'))

Sharding contract of this module: a ``Layout`` only *names* placements — it
never moves data.  Every spec it hands out (``act_spec``, ``weight_spec``,
``batch_spec``) refers to the 6-axis mesh above; arrays entering a function
with one of these specs leave it with the same spec unless the function's
own docstring says otherwise.  Optimizer state is NOT covered by these
specs: its placement additionally extends the parameter spec with the data
axes per ``Layout.zero_stage`` (see ``optim/optimizers.py`` for that
contract).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

AXES = ("pod", "dp", "pp", "x", "y", "z")
AUTO_AXES = (AxisType.Auto,) * len(AXES)


def bubble_fraction(n_stages: int, microbatches: int) -> float:
    """Idle fraction (pp-1)/m of the synchronous 1F1B/GPipe schedule —
    the single source for every bubble report (Layout, ParallelPlan,
    pipeline schedule, analytic cost model)."""
    if n_stages <= 1:
        return 0.0
    return (n_stages - 1) / max(microbatches, 1)


def pipeline_efficiency(n_stages: int, microbatches: int) -> float:
    """m / (m + pp - 1): useful-tick fraction of the schedule."""
    m = max(microbatches, 1)
    return m / (m + n_stages - 1)


def stage_assignment(n_items: int, n_stages: int) -> Tuple[Tuple[int, int], ...]:
    """Contiguous [start, end) ranges assigning ``n_items`` layer slots to
    ``n_stages`` pipeline stages.  Non-divisible counts are legal: the first
    ``n_items % n_stages`` stages take one extra slot (the LM head lives on
    the last stage, so the remainder goes early to balance compute)."""
    if n_items < n_stages:
        raise ValueError(
            f"cannot split {n_items} blocks over pp={n_stages} stages: "
            "every stage needs at least one block")
    base, rem = divmod(n_items, n_stages)
    bounds = []
    start = 0
    for s in range(n_stages):
        end = start + base + (1 if s < rem else 0)
        bounds.append((start, end))
        start = end
    return tuple(bounds)


@dataclasses.dataclass(frozen=True)
class Layout:
    """Parallel layout: mesh + the paper's direction bookkeeping.

    strategy: "3d" (the paper), "2d" (Optimus/SUMMA baseline), "1d"
    (Megatron baseline).  All strategies use the same 5-axis mesh; the
    baselines simply use degenerate cube factors.
    """
    mesh: Mesh
    strategy: str = "3d"
    # beyond-paper ablation: keep the 3-D placement but lower the linears as
    # plain einsums + sharding constraints, letting XLA choose the collective
    # schedule instead of the paper's explicit AG/AG/RS (EXPERIMENTS.md §Perf)
    gspmd_linears: bool = False
    # inference weight layout (§Perf hillclimb): replicate weight columns
    # over 'x' so the decode matvec needs no per-token weight all-gather
    # (trades param memory x|x| for zero weight movement per step)
    inference_opt: bool = False
    # mesh axis names that shard the batch dimension of activations
    batch_axes: Tuple[str, ...] = ("pod", "dp", "x")
    # extra axes (beyond in_ax) sharding the sequence dim, e.g. ("pod",) for
    # context-parallel prefill when the batch is too small for all DP axes.
    seq_axes: Tuple[str, ...] = ()
    # gradient-accumulation microbatches per optimizer step (schedule
    # bookkeeping; with pp > 1 this is the pipeline's m, bubble = (pp-1)/m)
    microbatches: int = 1
    # ZeRO stage for optimizer-state partitioning over the data axes
    # (pod, dp): 0 = fully replicated opt state, 1 = Adam m/v (and the f32
    # master update) sharded 1/dp per replica, 2 = additionally keep the
    # gradient-accumulation buffer reduce-scattered over dp.  Inert when the
    # data degree is 1 (see effective_zero_stage).  Default 1 preserves the
    # historical behaviour of sharding moments whenever dp > 1.
    zero_stage: int = 1
    # async-TP: decompose each 3-D island matmul into ``overlap_chunks``
    # contraction-dim chunks so every chunk's all_gather / psum_scatter can
    # run concurrently with the neighbouring chunk's partial matmul
    # (nanotron's tp_linear_async_communication; Narayanan et al. 2021
    # scatter-gather).  Numerics match the unfused path up to f32 summation
    # reordering.  Only the 3-D islands read these fields.
    overlap: bool = False
    overlap_chunks: int = 4

    # ---- sizes ----
    @property
    def sizes(self):
        return dict(self.mesh.shape)

    def size(self, ax) -> int:
        if ax is None:
            return 1
        if isinstance(ax, (tuple, list)):
            return math.prod(self.size(a) for a in ax)
        return self.sizes[ax]

    @property
    def cube(self) -> Tuple[int, int, int]:
        s = self.sizes
        return (s["x"], s["y"], s["z"])

    @property
    def n_model(self) -> int:
        return math.prod(self.cube)

    @property
    def n_data(self) -> int:
        return self.size(("pod", "dp"))

    @property
    def n_stages(self) -> int:
        """Pipeline-parallel degree (size of the 'pp' axis; 1 = no pipeline)."""
        return self.size("pp") if "pp" in self.sizes else 1

    @property
    def n_devices(self) -> int:
        return math.prod(self.sizes.values())

    # ---- pipeline bookkeeping ----
    def stage_layers(self, n_layers: int) -> int:
        """Layers per contiguous pipeline stage (must divide evenly)."""
        if n_layers % self.n_stages:
            raise ValueError(
                f"n_layers={n_layers} not divisible by pp={self.n_stages}")
        return n_layers // self.n_stages

    def stage_bounds(self, n_layers: int) -> Tuple[Tuple[int, int], ...]:
        """[(start, end)) layer ranges per stage, contiguous in depth.
        Non-divisible depths give the first ``n_layers % pp`` stages one
        extra layer (see ``stage_assignment``)."""
        return stage_assignment(n_layers, self.n_stages)

    def bubble_fraction(self) -> float:
        """1F1B / GPipe pipeline bubble (pp-1)/m as a fraction of ideal time."""
        return bubble_fraction(self.n_stages, self.microbatches)

    def effective_zero_stage(self) -> int:
        """ZeRO stage actually in force: the configured stage, degraded to 0
        when there is nothing to partition (data degree pod*dp == 1)."""
        return self.zero_stage if self.n_data > 1 else 0

    # ---- specs ----
    def batch_spec(self):
        return tuple(self.batch_axes) or None

    def act_spec(self, in_ax: str, out_ax: str) -> P:
        """(B, S, H) activation spec: batch, seq over in_ax (+seq_axes), hidden over out_ax."""
        seq = tuple(a for a in (*self.seq_axes, in_ax) if a is not None and self.size(a) > 1)
        return P(self.batch_spec(), seq or None, out_ax)

    def weight_spec(self, in_ax: str, out_ax: str) -> P:
        """(H, F) weight spec per the balanced 3-D placement: rows over out_ax,
        cols over (in_ax, x)."""
        return P(out_ax, (in_ax, "x"))

    def sharding(self, spec: P) -> NamedSharding:
        return NamedSharding(self.mesh, spec)


@dataclasses.dataclass
class Dirs:
    """Mutable direction state threaded through the layer stack (paper §3.2)."""
    in_ax: str = "y"
    out_ax: str = "z"

    def swap(self) -> "Dirs":
        return Dirs(self.out_ax, self.in_ax)

    def as_tuple(self):
        return (self.in_ax, self.out_ax)


def factor_model_axis(n_model: int, strategy: str) -> Tuple[int, int, int]:
    """Factor the model-parallel degree into the (x, y, z) cube.

    3d: as close to a cube as possible (16 -> (2,2,4); 8 -> (2,2,2); 64 -> (4,4,4)).
    2d: (1, q, q) SUMMA grid.
    1d: (1, 1, n) Megatron.
    """
    if strategy == "1d":
        return (1, 1, n_model)
    if strategy == "2d":
        q = int(round(math.sqrt(n_model)))
        if q * q != n_model:
            raise ValueError(f"2d strategy needs a square model degree, got {n_model}")
        return (1, q, q)
    if strategy != "3d":
        raise ValueError(f"unknown strategy {strategy}")
    # 3d: greedy near-cube factorisation, px <= py <= pz
    best = None
    for px in range(1, n_model + 1):
        if n_model % px:
            continue
        rem = n_model // px
        for py in range(px, rem + 1):
            if rem % py:
                continue
            pz = rem // py
            if pz < py:
                continue
            spread = pz - px
            if best is None or spread < best[0]:
                best = (spread, (px, py, pz))
    return best[1]


def make_mesh(n_pod: int = 1, n_dp: int = 1, n_model: int = 1,
              strategy: str = "3d",
              cube: Optional[Tuple[int, int, int]] = None,
              devices=None, n_pp: int = 1) -> Mesh:
    """Build the 6-axis framework mesh.  Device order is row-major over
    (pod, data, pipeline, model) — with pp=1 this is identical to the
    prescribed production mesh's device array reshaped, so the physical
    topology is the same; pp>1 carves stages out of that same order."""
    px, py, pz = cube or factor_model_axis(n_model, strategy)
    shape = (n_pod, n_dp, n_pp, px, py, pz)
    if devices is not None:
        import numpy as np
        devs = np.asarray(devices).reshape(shape)
        return Mesh(devs, AXES, axis_types=AUTO_AXES)
    return jax.make_mesh(shape, AXES, axis_types=AUTO_AXES)


def make_layout(n_pod=1, n_dp=1, n_model=1, strategy="3d", cube=None,
                batch_axes=("pod", "dp", "x"), seq_axes=(), devices=None,
                gspmd_linears=False, n_pp=1, microbatches=1,
                zero_stage=1, overlap=False, overlap_chunks=4) -> Layout:
    mesh = make_mesh(n_pod, n_dp, n_model, strategy, cube, devices, n_pp)
    return Layout(mesh=mesh, strategy=strategy, gspmd_linears=gspmd_linears,
                  batch_axes=tuple(batch_axes), seq_axes=tuple(seq_axes),
                  microbatches=microbatches, zero_stage=zero_stage,
                  overlap=overlap, overlap_chunks=overlap_chunks)


def single_device_layout(strategy: str = "3d") -> Layout:
    """Degenerate layout for CPU smoke tests: every axis has size 1."""
    return make_layout(1, 1, 1, strategy)
