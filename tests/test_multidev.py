"""Multi-device semantics (8 host devices) via subprocess — the main process
stays single-device per the harness contract.

One subprocess runs a battery: the 3-D matmul fwd/bwd vs the dense oracle,
and every architecture's train loss equivalence across 3-D / 2-D / 1-D /
data-parallel layouts against the single-device reference.
"""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

BATTERY = r"""
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.config import reduced, Family
from repro.configs.registry import get, ARCH_IDS
from repro.core.topology import single_device_layout, make_layout
from repro.core import ops3d
from repro.models import transformer

assert len(jax.devices()) == 8, jax.devices()
failures = []

# ---- Algorithm 1/2 vs dense oracle on the 2x2x2 cube (paper-exact) ----
lay = make_layout(1, 1, 8, "3d")
assert lay.cube == (2, 2, 2)
B, S, H, F = 4, 8, 16, 24
ks = jax.random.split(jax.random.key(0), 3)
x = jax.random.normal(ks[0], (B, S, H))
w = jax.random.normal(ks[1], (H, F))
dc = jax.random.normal(ks[2], (B, S, F))
xs = jax.device_put(x, lay.sharding(ops3d._x_spec(lay, "y", "z")))
ws = jax.device_put(w, lay.sharding(ops3d._w_spec("y", "z")))
y = jax.jit(lambda a, b: ops3d.matmul3d(lay, "y", "z", a, b))(xs, ws)
if float(jnp.abs(y - x @ w).max()) > 1e-4:
    failures.append("matmul3d fwd")
gx, gw = jax.jit(jax.grad(
    lambda a, b: jnp.sum(ops3d.matmul3d(lay, "y", "z", a, b) * dc),
    (0, 1)))(xs, ws)
if float(jnp.abs(gx - dc @ w.T).max()) > 1e-4:
    failures.append("matmul3d dx")
if float(jnp.abs(gw - x.reshape(-1, H).T @ dc.reshape(-1, F)).max()) > 1e-3:
    failures.append("matmul3d dw")

# noswap + repc ops
wn = jax.random.normal(ks[1], (H, 12))
wns = jax.device_put(wn, lay.sharding(P("z", None)))
yn = jax.jit(lambda a, b: ops3d.matmul3d_noswap(lay, "y", "z", a, b))(xs, wns)
if float(jnp.abs(yn - x @ wn).max()) > 1e-4:
    failures.append("matmul3d_noswap")
xr = jax.random.normal(ks[0], (B, S, 12))
xrs = jax.device_put(xr, lay.sharding(P(("pod", "dp", "x"), "y", None)))
wr = jax.random.normal(ks[1], (12, F))
wrs = jax.device_put(wr, lay.sharding(P(None, ("y", "x"))))
yr = jax.jit(lambda a, b: ops3d.matmul3d_repc(lay, "y", "z", a, b))(xrs, wrs)
if float(jnp.abs(yr - xr @ wr).max()) > 1e-4:
    failures.append("matmul3d_repc")

# ---- per-arch layout equivalence ----
lay1 = single_device_layout("3d")
layouts = {
    "3d(2,2,2)": make_layout(1, 1, 8, "3d"),
    "3d(dp2)": make_layout(1, 2, 4, "3d", cube=(2, 2, 1)),
    "2d(q2)": make_layout(1, 2, 4, "2d"),
    "1d(4)": make_layout(1, 2, 4, "1d"),
}
B2, S2 = 4, 64
for arch in ARCH_IDS:
    cfg = reduced(get(arch))
    # host copy of the single-device init: each layout's jit places it
    params = jax.device_get(transformer.init(cfg, lay1, jax.random.key(0)))
    toks = jax.random.randint(jax.random.key(3), (B2, S2), 0, cfg.vocab)
    labs = jax.random.randint(jax.random.key(4), (B2, S2), 0, cfg.vocab)
    batch = {"tokens": toks, "labels": labs}
    if cfg.family == Family.VLM:
        nv = cfg.n_vision_tokens
        batch = {"tokens": toks[:, :S2 - nv], "labels": labs[:, :S2 - nv],
                 "patch_embeds": jax.random.normal(
                     jax.random.key(5), (B2, nv, cfg.d_model), jnp.bfloat16)}
    elif cfg.family == Family.AUDIO:
        batch["frames"] = jax.random.normal(
            jax.random.key(5), (B2, cfg.encoder.n_frames, cfg.d_model),
            jnp.bfloat16)
    ref, _ = jax.jit(lambda p, b: transformer.forward(
        cfg, lay1, p, b, mode="train"))(params, batch)
    # hybrid/ssm recurrences accumulate bf16 rounding differently across
    # layouts (chunked scan boundaries move with the sharding), so they get
    # a slightly looser budget than pure-attention stacks
    tol = 5e-2 if cfg.family in (Family.HYBRID, Family.SSM) else 3e-2
    for name, lay_n in layouts.items():
        loss, _ = jax.jit(lambda p, b: transformer.forward(
            cfg, lay_n, p, b, mode="train"))(params, batch)
        if abs(float(loss) - float(ref)) > tol:
            failures.append(f"{arch}@{name}: {float(loss)} vs {float(ref)}")

if failures:
    print("FAILURES:", failures)
    raise SystemExit(1)
print("ALL-OK")
"""


@pytest.mark.slow
def test_multidev_battery():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(ROOT, "src"))
    proc = subprocess.run([sys.executable, "-c", BATTERY], env=env,
                          capture_output=True, text=True, timeout=3000)
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-4000:]
    assert "ALL-OK" in proc.stdout


# Regression fence: the VLM label construction once concatenated a
# replicated zeros block with seq-sharded labels; on a cube with a seq-
# sharding degree (e.g. (1,2,2)) the partitioner mis-resharded the concat
# (values summed across replicas), driving take_along_axis out of range and
# the loss to NaN.  _vlm_labels now builds the label row with jnp.pad; this
# pins that behaviour on the exact failing layout.
VLM_CUBE_SCRIPT = r"""
import jax, jax.numpy as jnp
from repro.config import reduced
from repro.configs.registry import get
from repro.core.topology import make_layout, single_device_layout
from repro.models import transformer

assert len(jax.devices()) == 4, jax.devices()
cfg = reduced(get("internvl2-2b"))
lay = make_layout(1, 1, 4, "3d", cube=(1, 2, 2))
assert lay.cube == (1, 2, 2)
params = transformer.init(cfg, lay, jax.random.key(0))
B, S = 4, 64
nv = cfg.n_vision_tokens
batch = {
    "tokens": jax.random.randint(jax.random.key(3), (B, S - nv), 0, cfg.vocab),
    "labels": jax.random.randint(jax.random.key(4), (B, S - nv), 0, cfg.vocab),
    "patch_embeds": jax.random.normal(jax.random.key(5), (B, nv, cfg.d_model),
                                      jnp.bfloat16),
}
loss, _ = jax.jit(lambda p, b: transformer.forward(
    cfg, lay, p, b, mode="train"))(params, batch)
if not jnp.isfinite(loss):
    # the obs sentinel names the first offending pytree path, turning a
    # bare "loss is nan" into an actionable blame report
    from repro.obs.telemetry import nonfinite_report
    raise AssertionError(
        f"VLM loss not finite on cube (1,2,2): {loss}; "
        + nonfinite_report(params=params, batch=batch))
ref, _ = jax.jit(lambda p, b: transformer.forward(
    cfg, single_device_layout("3d"), p, b, mode="train"))(
        jax.device_get(params), batch)
assert abs(float(loss) - float(ref)) < 3e-2, (float(loss), float(ref))
print("ALL-OK")
"""


@pytest.mark.slow
def test_vlm_train_cube_1_2_2_regression():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(ROOT, "src"))
    proc = subprocess.run([sys.executable, "-c", VLM_CUBE_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    assert "ALL-OK" in proc.stdout
