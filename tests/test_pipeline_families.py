"""Architecture-agnostic pipeline parallelism (BlockStack registry).

Fast in-process coverage: stage assignment (including non-divisible depth),
pipeline-info/selector tables, plan-time family validation, and the
no-family-branching acceptance check on transformer.forward.  Slow battery:
pp=2/m=4 vs pp=1 training-trajectory equivalence for the moe and ssm
families on 8 host devices (same contract as tests/test_pipeline.py's dense
battery).
"""
import os
import subprocess
import sys

import pytest

from repro.config import Family, reduced
from repro.configs.registry import get
from repro.core.plan import ParallelPlan, pipeline_mode_error
from repro.core.topology import stage_assignment
from repro.models import registry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# Stage assignment (non-divisible depth included)
# ---------------------------------------------------------------------------
def test_stage_assignment_divisible():
    assert stage_assignment(8, 2) == ((0, 4), (4, 8))
    assert stage_assignment(6, 3) == ((0, 2), (2, 4), (4, 6))
    assert stage_assignment(4, 1) == ((0, 4),)


def test_stage_assignment_non_divisible():
    # remainder goes to the EARLIER stages (head lives on the last stage)
    assert stage_assignment(5, 2) == ((0, 3), (3, 5))
    assert stage_assignment(7, 3) == ((0, 3), (3, 5), (5, 7))
    assert stage_assignment(3, 2) == ((0, 2), (2, 3))


def test_stage_assignment_too_shallow():
    with pytest.raises(ValueError, match="at least one block"):
        stage_assignment(1, 2)


def test_pipeline_info_non_divisible_pads_with_noop():
    cfg = reduced(get("tinyllama-1.1b"))
    import dataclasses
    cfg = dataclasses.replace(cfg, n_layers=3)
    stack = registry.get_stack(cfg.family)
    info = registry.pipeline_info(stack, cfg, 2)
    assert info.bounds == ((0, 2), (2, 3))
    assert not info.homogeneous          # unequal stage sizes -> union slots
    assert info.slots == 2
    assert info.selectors == ((0, 0), (0, registry.NOOP))


def test_pipeline_info_interleaved_plan():
    cfg = reduced(get("xlstm-350m"))     # plan: (mlstm, slstm)
    stack = registry.get_stack(cfg.family)
    assert stack.layer_plan(cfg) == ("mlstm", "slstm")
    info = registry.pipeline_info(stack, cfg, 2)
    assert info.kind_order == ("mlstm", "slstm")
    assert not info.homogeneous
    assert info.selectors == ((0,), (1,))


def test_pipeline_info_homogeneous_matches_dense_layout():
    cfg = reduced(get("mixtral-8x7b"))   # plan: (moe, moe)
    stack = registry.get_stack(cfg.family)
    info = registry.pipeline_info(stack, cfg, 2)
    assert info.homogeneous
    assert info.slots == 1


def test_every_family_registers_a_stack():
    for fam in Family:
        stack = registry.get_stack(fam)
        assert stack.family == fam
        assert stack.kinds


# ---------------------------------------------------------------------------
# Plan-time validation (family- and mode-aware)
# ---------------------------------------------------------------------------
def test_plan_rejects_serve_mode_under_pp():
    plan = ParallelPlan(n_model=4, cube=(1, 2, 2), n_stages=2, microbatches=4)
    with pytest.raises(ValueError, match="training-only schedule"):
        plan.validate(mode="decode")
    assert pipeline_mode_error(2, "prefill") is not None
    assert pipeline_mode_error(2, "train") is None
    assert pipeline_mode_error(1, "decode") is None


def test_plan_rejects_mtp_under_pp():
    cfg = reduced(get("deepseek-v3-671b"))
    assert cfg.mtp
    plan = ParallelPlan(n_stages=2, microbatches=4)
    with pytest.raises(ValueError, match="mtp"):
        plan.validate(n_layers=cfg.n_layers, model=cfg)


def test_plan_accepts_every_family_under_pp():
    for arch in ("tinyllama-1.1b", "mixtral-8x7b", "xlstm-350m",
                 "zamba2-1.2b", "internvl2-2b", "whisper-medium"):
        cfg = reduced(get(arch))
        plan = ParallelPlan(n_stages=2, microbatches=4)
        assert plan.validate(n_layers=cfg.n_layers, global_batch=8,
                             model=cfg) is plan


def test_plan_warns_on_non_divisible_depth():
    plan = ParallelPlan(n_stages=2, microbatches=4)
    with pytest.warns(UserWarning, match="non-uniform"):
        plan.validate(n_layers=3)


# ---------------------------------------------------------------------------
# Acceptance: transformer.forward contains no per-family branching
# ---------------------------------------------------------------------------
def test_forward_is_family_free():
    import inspect
    from repro.models import transformer
    src = inspect.getsource(transformer)
    assert "Family." not in src, (
        "transformer.py must dispatch through models/registry.py, not "
        "branch on Family")


# ---------------------------------------------------------------------------
# Training equivalence on 8 host devices: moe + ssm families, pp=2/m=4 vs
# pp=1/m=4, one canonical init re-cut by registry.repartition_stack; the
# ssm (xlstm) case exercises the selector-switched union stages
# ---------------------------------------------------------------------------
BATTERY = r"""
import jax, jax.numpy as jnp
from repro.config import OptimConfig, reduced
from repro.configs.registry import get
from repro.core.params import init_params, shardings
from repro.core.plan import ParallelPlan
from repro.models import registry, transformer
from repro.optim.optimizers import opt_state_abstract
from repro.train.step import make_train_step

assert len(jax.devices()) == 8, jax.devices()
STEPS, B, S = 10, 8, 32
opt_cfg = OptimConfig(lr=1e-3, warmup=2, total_steps=STEPS)

failures = []
for arch in ("mixtral-8x7b", "xlstm-350m"):
    cfg = reduced(get(arch))
    plans = {
        "pp1_mb4": ParallelPlan(n_model=4, cube=(1, 2, 2), microbatches=4),
        "pp2_mb4": ParallelPlan(n_model=4, cube=(1, 2, 2), n_stages=2,
                                microbatches=4),
    }
    lay_ref = plans["pp1_mb4"].build()
    params0 = transformer.init(cfg, lay_ref, jax.random.key(0))
    traj = {}
    for name, plan in plans.items():
        plan.validate(n_layers=cfg.n_layers, global_batch=B, model=cfg)
        lay = plan.build()
        params = dict(params0)
        if plan.n_stages > 1:
            params["stack"] = registry.repartition_stack(
                cfg, params0["stack"], lay_ref, lay)
        # init placed the canonical params on lay_ref's devices; move the
        # (re-cut) tree onto this plan's mesh
        params = jax.device_put(params, shardings(
            transformer.abstract_params(cfg, lay), lay))
        opt_state = init_params(opt_state_abstract(
            transformer.abstract_params(cfg, lay), lay, opt_cfg),
            jax.random.key(1))
        step_fn = jax.jit(make_train_step(cfg, lay, opt_cfg))
        losses = []
        for s in range(STEPS):
            toks = jax.random.randint(jax.random.key(100 + s), (B, S), 0,
                                      cfg.vocab)
            labs = jax.random.randint(jax.random.key(200 + s), (B, S), 0,
                                      cfg.vocab)
            # uneven padding: covers the valid-token re-weighting across
            # microbatches (and the masked warm-up ticks in the pipeline)
            labs = labs.at[:2, S // 2:].set(-1)
            params, opt_state, met = step_fn(params, opt_state,
                                             {"tokens": toks, "labels": labs})
            losses.append(float(met["loss"]))
        traj[name] = losses
        print(arch, name, " ".join(f"{l:.4f}" for l in losses), flush=True)
    diffs = [abs(a - b) for a, b in zip(traj["pp1_mb4"], traj["pp2_mb4"])]
    if max(diffs) > 1e-2:
        failures.append(f"{arch} max traj diff {max(diffs):.4f}")
if failures:
    print("FAILURES:", failures)
    raise SystemExit(1)
print("PP-FAMILIES-OK")
"""


@pytest.mark.slow
def test_pipeline_family_training_equivalence():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(ROOT, "src"))
    proc = subprocess.run([sys.executable, "-c", BATTERY], env=env,
                          capture_output=True, text=True, timeout=3000)
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-4000:]
    assert "PP-FAMILIES-OK" in proc.stdout


# ---------------------------------------------------------------------------
# Non-divisible depth end-to-end: dense 3 layers over pp=2 (noop-padded
# switched stages) still matches the pp=1 trajectory
# ---------------------------------------------------------------------------
NONUNIFORM_BATTERY = r"""
import dataclasses
import jax, jax.numpy as jnp
from repro.config import OptimConfig, reduced
from repro.configs.registry import get
from repro.core.params import init_params, shardings
from repro.core.plan import ParallelPlan
from repro.models import registry, transformer
from repro.optim.optimizers import opt_state_abstract
from repro.train.step import make_train_step

STEPS, B, S = 6, 8, 32
cfg = dataclasses.replace(reduced(get("tinyllama-1.1b")), n_layers=3)
opt_cfg = OptimConfig(lr=1e-3, warmup=2, total_steps=STEPS)
plans = {
    "pp1_mb4": ParallelPlan(n_model=4, cube=(1, 2, 2), microbatches=4),
    "pp2_mb4": ParallelPlan(n_model=4, cube=(1, 2, 2), n_stages=2,
                            microbatches=4),
}
lay_ref = plans["pp1_mb4"].build()
params0 = transformer.init(cfg, lay_ref, jax.random.key(0))
traj = {}
for name, plan in plans.items():
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        plan.validate(n_layers=cfg.n_layers, global_batch=B, model=cfg)
    lay = plan.build()
    params = dict(params0)
    if plan.n_stages > 1:
        params["stack"] = registry.repartition_stack(cfg, params0["stack"],
                                                     lay_ref, lay)
    params = jax.device_put(params, shardings(
        transformer.abstract_params(cfg, lay), lay))
    opt_state = init_params(opt_state_abstract(
        transformer.abstract_params(cfg, lay), lay, opt_cfg),
        jax.random.key(1))
    step_fn = jax.jit(make_train_step(cfg, lay, opt_cfg))
    losses = []
    for s in range(STEPS):
        toks = jax.random.randint(jax.random.key(10 + s), (B, S), 0, cfg.vocab)
        labs = jax.random.randint(jax.random.key(20 + s), (B, S), 0, cfg.vocab)
        params, opt_state, met = step_fn(params, opt_state,
                                         {"tokens": toks, "labels": labs})
        losses.append(float(met["loss"]))
    traj[name] = losses
    print(name, " ".join(f"{l:.4f}" for l in losses), flush=True)
diffs = [abs(a - b) for a, b in zip(traj["pp1_mb4"], traj["pp2_mb4"])]
assert max(diffs) <= 1e-2, diffs
print("PP-NONUNIFORM-OK")
"""


@pytest.mark.slow
def test_pipeline_non_divisible_depth_equivalence():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(ROOT, "src"))
    proc = subprocess.run([sys.executable, "-c", NONUNIFORM_BATTERY], env=env,
                          capture_output=True, text=True, timeout=3000)
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-4000:]
    assert "PP-NONUNIFORM-OK" in proc.stdout
