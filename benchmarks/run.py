"""Benchmark harness — one function per paper table/figure + roofline.

``python -m benchmarks.run [table1|table2|comm|kernels|minirun|ppsweep|zerosweep|servesweep|overlapsweep|roofline|all]``

Prints ``name,us_per_call,derived`` CSV rows per the harness contract:
derived entries carry the model-based quantity (step time / comm bytes /
roofline term); measured entries carry wall-clock microseconds.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from benchmarks.analytic import TPU_V5E, V100, step_time  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


_ROWS: list = []        # every CSV row, so --out covers print-only scenarios


def _row(name, us, derived):
    print(f"{name},{us},{derived}")
    _ROWS.append({"name": name, "us_per_call": us, "derived": derived})


def _host_env():
    """Environment of a scenario subprocess: every scenario that times or
    compiles runs on the CPU platform (split into host devices by its own
    script), and this parent never imports JAX, so no process here holds an
    accelerator another one needs."""
    return dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
                JAX_PLATFORMS="cpu")


# ---------------------------------------------------------------------------
# Table 1: weak scaling (paper batch/hidden ladder, seq 512, 4 layers)
# ---------------------------------------------------------------------------
PAPER_WEAK = {
    "1d": [(8, 60, 2048), (16, 60, 4096), (36, 40, 6120), (64, 30, 8192)],
    "2d": [(16, 192, 4096), (36, 288, 6120), (64, 384, 8192)],
    "3d": [(8, 192, 2048), (64, 384, 8192)],
}
PAPER_AVG_STEP = {   # published average step time (s)
    ("1d", 64): 1.560, ("2d", 64): 1.052, ("3d", 64): 0.672,
    ("1d", 8): 0.341, ("3d", 8): 0.580,
}


def _calibration():
    """Single-cell calibration: the alpha-beta model captures relative costs;
    one constant (fit on the paper's 3-D 64-GPU strong-scaling cell) absorbs
    the framework overhead the paper's absolute numbers include."""
    model = step_time("3d", V100, 64, 24, 512, 3072)["t_total"] / 24
    return PAPER_STRONG_PUB[("3d", 64)] / model


def table1():
    c = _calibration()
    for strat, rows in PAPER_WEAK.items():
        for p, batch, hidden in rows:
            r = step_time(strat, V100, p, batch, 512, hidden)
            avg = c * r["t_total"] / batch
            name = f"table1_weak|{strat}|gpus={p}|batch={batch}|hidden={hidden}"
            _row(name, f"{c*r['t_total']*1e6:.0f}", f"avg_step_s={avg:.3f}")
            pub = PAPER_AVG_STEP.get((strat, p))
            if pub:
                _row(name + "|published", "", f"avg_step_s={pub:.3f}")
    # the paper's weak-scaling claim: 3-D has the slowest-rising step time
    rises = {}
    for strat, rows in PAPER_WEAK.items():
        ts = [step_time(strat, V100, p, b, 512, h)["t_total"] / b
              for p, b, h in rows]
        rises[strat] = ts[-1] / ts[0]
    _row("table1_weak|rise_smallest_to_largest", "",
         " ".join(f"{k}={v:.2f}x" for k, v in rises.items())
         + " | claim: 3d rises slowest -> "
         + str(rises["3d"] <= min(rises.values()) + 1e-9))


# ---------------------------------------------------------------------------
# Table 2: strong scaling (fixed problem, hidden 3072, seq 512)
# ---------------------------------------------------------------------------
PAPER_STRONG = {
    "1d": [(8, 12), (16, 12), (36, 12), (64, 12)],
    "2d": [(16, 24), (36, 24), (64, 24)],
    "3d": [(8, 24), (64, 24)],
}
PAPER_STRONG_PUB = {("1d", 64): 0.550, ("2d", 64): 0.497, ("3d", 64): 0.359,
                    ("3d", 8): 0.515, ("1d", 8): 0.597}


def table2():
    c = _calibration()
    for strat, rows in PAPER_STRONG.items():
        for p, batch in rows:
            r = step_time(strat, V100, p, batch, 512, 3072)
            avg = c * r["t_total"] / batch
            name = f"table2_strong|{strat}|gpus={p}|batch={batch}"
            _row(name, f"{c*r['t_total']*1e6:.0f}", f"avg_step_s={avg:.3f}")
            pub = PAPER_STRONG_PUB.get((strat, p))
            if pub:
                _row(name + "|published", "", f"avg_step_s={pub:.3f}")
    t1 = step_time("1d", V100, 64, 12, 512, 3072)["t_total"] / 12
    t2 = step_time("2d", V100, 64, 24, 512, 3072)["t_total"] / 24
    t3 = step_time("3d", V100, 64, 24, 512, 3072)["t_total"] / 24
    _row("table2_speedup|3d_vs_1d", "", f"{t1 / t3:.2f}x (paper: 2.32x)")
    _row("table2_speedup|3d_vs_2d", "", f"{t2 / t3:.2f}x (paper: 1.57x)")
    _row("table2_ordering|3d<2d<1d", "", str(t3 < t2 < t1)
         + " (paper: True)")


# ---------------------------------------------------------------------------
# Measured per-device comm volume from compiled HLO (64 host devices)
# ---------------------------------------------------------------------------
COMM_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=64"
import sys, json, dataclasses
sys.path.insert(0, %(src)r)
import jax
from repro.config import SHAPES, ShapeConfig
from repro.configs.registry import get
from repro.core.topology import make_layout
from repro.core.params import abstract_arrays
from repro.models import transformer
from repro.launch.dryrun import collective_stats

cfg = dataclasses.replace(get("paper-transformer"), n_layers=2)
out = {}
for strat in ("1d", "2d", "3d"):
    lay = make_layout(1, 1, 64, strat)
    ap = abstract_arrays(transformer.abstract_params(cfg, lay), lay)
    shape = ShapeConfig("bench", 512, 64, "train")
    specs = transformer.input_specs(cfg, lay, shape)
    def fwd(p, b):
        loss, _ = transformer.forward(cfg, lay, p, b, mode="train")
        return loss
    compiled = jax.jit(jax.grad(fwd)).lower(ap, *specs).compile()
    st = collective_stats(compiled.as_text())
    out[strat] = st["bytes_per_device"]
print("RESULT " + json.dumps(out))
"""


def comm_volume():
    env = _host_env()
    proc = subprocess.run(
        [sys.executable, "-c", COMM_SCRIPT % {"src": os.path.join(ROOT, "src")}],
        env=env, capture_output=True, text=True, timeout=3000)
    for line in proc.stdout.splitlines():
        if line.startswith("RESULT "):
            res = json.loads(line[len("RESULT "):])
            for strat, b in res.items():
                _row(f"comm_volume|{strat}|64dev|fwd+bwd", "",
                     f"bytes_per_device={b:.3e}")
            b1, b2, b3 = res.get("1d"), res.get("2d"), res.get("3d")
            if b1 and b3:
                _row("comm_volume|ratio_1d_over_3d", "", f"{b1/b3:.2f}x")
            if b2 and b3:
                _row("comm_volume|ratio_2d_over_3d", "", f"{b2/b3:.2f}x")
            return res
    print(proc.stdout[-2000:], file=sys.stderr)
    print(proc.stderr[-2000:], file=sys.stderr)
    _row("comm_volume", "", "FAILED")


# ---------------------------------------------------------------------------
# Kernel microbenchmarks (interpret mode on the CPU: correctness-grade timing)
# ---------------------------------------------------------------------------
KERNELS_SCRIPT = r"""
import sys, time, json
sys.path.insert(0, %(src)r)
import jax
import jax.numpy as jnp
from repro.kernels import ops

def bench(fn, *args, n=5):
    r = fn(*args)
    jax.block_until_ready(r)
    t0 = time.perf_counter()
    for _ in range(n):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / n * 1e6

out = {}
x = jax.random.normal(jax.random.key(0), (256, 256), jnp.float32)
w = jax.random.normal(jax.random.key(1), (256, 256), jnp.float32)
out["kernel_matmul_pallas_interpret|256x256x256"] = bench(
    lambda a, b: ops.pallas_matmul(a, b), x, w)
out["kernel_matmul_xla|256x256x256"] = bench(
    jax.jit(lambda a, b: jnp.dot(a, b)), x, w)
q = jax.random.normal(jax.random.key(0), (1, 256, 4, 64), jnp.float32)
k = jax.random.normal(jax.random.key(1), (1, 256, 4, 64), jnp.float32)
out["kernel_flash_pallas_interpret|s256h4d64"] = bench(
    lambda a, b: ops.pallas_flash(a, b, b), q, k)
print("RESULT " + json.dumps(out))
"""


def kernels():
    proc = subprocess.run(
        [sys.executable, "-c", KERNELS_SCRIPT % {"src": os.path.join(ROOT, "src")}],
        env=_host_env(), capture_output=True, text=True, timeout=3000)
    for line in proc.stdout.splitlines():
        if line.startswith("RESULT "):
            for name, us in json.loads(line[len("RESULT "):]).items():
                _row(name, f"{us:.0f}", "")
            return
    print(proc.stderr[-1500:], file=sys.stderr)
    _row("kernels", "", "FAILED")


# ---------------------------------------------------------------------------
# Real wall-clock minirun on 8 host devices: 1D vs 2D vs 3D
# ---------------------------------------------------------------------------
MINIRUN_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, time, json, dataclasses
sys.path.insert(0, %(src)r)
import jax
from repro.config import ShapeConfig, reduced
from repro.configs.registry import get
from repro.core.topology import make_layout
from repro.data.pipeline import TokenStream
from repro.models import transformer

cfg = dataclasses.replace(reduced(get("paper-transformer"), d_model=512),
                          n_layers=2, remat=False)
out = {}
for strat, lay_args in (("1d", (1, 2, 4)), ("2d", (1, 2, 4)), ("3d", (1, 1, 8))):
    lay = make_layout(*lay_args, strat)
    params = transformer.init(cfg, lay, jax.random.key(0))
    shape = ShapeConfig("m", 256, 8, "train")
    batch = next(iter(TokenStream(cfg, lay, shape)))
    def fwd(p, b):
        loss, _ = transformer.forward(cfg, lay, p, b, mode="train")
        return loss
    g = jax.jit(jax.grad(fwd))
    jax.block_until_ready(g(params, batch))
    t0 = time.perf_counter()
    for _ in range(3):
        jax.block_until_ready(g(params, batch))
    out[strat] = (time.perf_counter() - t0) / 3
print("RESULT " + json.dumps(out))
"""


def minirun():
    env = _host_env()
    proc = subprocess.run(
        [sys.executable, "-c", MINIRUN_SCRIPT % {"src": os.path.join(ROOT, "src")}],
        env=env, capture_output=True, text=True, timeout=3000)
    for line in proc.stdout.splitlines():
        if line.startswith("RESULT "):
            res = json.loads(line[len("RESULT "):])
            for strat, t in res.items():
                _row(f"minirun_fwdbwd|{strat}|8hostdev", f"{t*1e6:.0f}", "")
            return res
    print(proc.stderr[-1500:], file=sys.stderr)
    _row("minirun", "", "FAILED")


# ---------------------------------------------------------------------------
# Pipeline sweep: 3-D-only vs 3-D+PP on 8 host devices (real wall-clock),
# across families — every BlockStack pipelines, not just the dense decoder
# ---------------------------------------------------------------------------
PPSWEEP_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, time, json, dataclasses
sys.path.insert(0, %(src)r)
import jax
from repro.config import ShapeConfig, reduced
from repro.configs.registry import get
from repro.core.plan import ParallelPlan
from repro.data.pipeline import TokenStream
from repro.models import transformer
from repro.train.step import make_train_step
from repro.config import OptimConfig

ARCHS = {            # one representative per pipelined family class
    "dense": ("tinyllama-1.1b", dict(n_layers=4, d_model=256)),
    "moe":   ("mixtral-8x7b",   dict(n_layers=2)),
    "ssm":   ("xlstm-350m",     dict(n_layers=2)),   # mLSTM/sLSTM interleave
}
opt_cfg = OptimConfig(lr=1e-3, warmup=2, total_steps=10)
out = {}
for fam, (arch, tweaks) in ARCHS.items():
    cfg = dataclasses.replace(reduced(get(arch)), remat=False, **tweaks)
    # same 8 devices, same global batch: 3-D-only vs 3-D+PP compositions
    plans = {
        "3d8":        ParallelPlan(n_model=8),
        "3d4_pp2m4":  ParallelPlan(n_model=4, cube=(1, 2, 2), n_stages=2,
                                   microbatches=4),
    }
    if fam == "dense":
        plans["3d4_pp2m8"] = ParallelPlan(n_model=4, cube=(1, 2, 2),
                                          n_stages=2, microbatches=8)
    for name, plan in plans.items():
        plan.validate(n_layers=cfg.n_layers, global_batch=16, model=cfg)
        lay = plan.build()
        params = transformer.init(cfg, lay, jax.random.key(0))
        from repro.optim.optimizers import opt_state_abstract
        from repro.core.params import init_params
        opt_state = init_params(opt_state_abstract(
            transformer.abstract_params(cfg, lay), lay, opt_cfg),
            jax.random.key(1))
        shape = ShapeConfig("b", 128, 16, "train")
        batch = next(iter(TokenStream(cfg, lay, shape)))
        step = jax.jit(make_train_step(cfg, lay, opt_cfg))
        p2, o2, m = step(params, opt_state, batch)
        jax.block_until_ready(m["loss"])
        t0 = time.perf_counter()
        for _ in range(3):
            p2, o2, m = step(p2, o2, batch)
            jax.block_until_ready(m["loss"])
        out[fam + "|" + name] = {"t_step": (time.perf_counter() - t0) / 3,
                                 "bubble": plan.bubble_fraction(),
                                 "loss": float(m["loss"])}
print("RESULT " + json.dumps(out))
"""


def ppsweep():
    env = _host_env()
    proc = subprocess.run(
        [sys.executable, "-c", PPSWEEP_SCRIPT % {"src": os.path.join(ROOT, "src")}],
        env=env, capture_output=True, text=True, timeout=3000)
    for line in proc.stdout.splitlines():
        if line.startswith("RESULT "):
            res = json.loads(line[len("RESULT "):])
            for name, r in res.items():
                _row(f"ppsweep_train_step|{name}|8hostdev",
                     f"{r['t_step']*1e6:.0f}",
                     f"bubble={r['bubble']:.3f} loss={r['loss']:.4f}")
            return res
    print(proc.stderr[-2000:], file=sys.stderr)
    _row("ppsweep", "", "FAILED")


# ---------------------------------------------------------------------------
# ZeRO sweep: per-device optimizer bytes + step time vs zero stage, dp=4
# ---------------------------------------------------------------------------
ZEROSWEEP_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, time, json, math, dataclasses
sys.path.insert(0, %(src)r)
import jax
from repro.config import OptimConfig, ShapeConfig, reduced
from repro.configs.registry import get
from repro.core.params import init_params
from repro.core.plan import ParallelPlan
from repro.data.pipeline import TokenStream
from repro.models import transformer
from repro.optim.optimizers import opt_state_abstract
from repro.train.step import make_train_step

cfg = dataclasses.replace(reduced(get("tinyllama-1.1b"), d_model=256),
                          n_layers=4, remat=False)
opt_cfg = OptimConfig(lr=1e-3, warmup=2, total_steps=10)

def device0_bytes(tree):
    # bytes of the shard device 0 actually stores (after the jitted step
    # has placed the state per its constraints)
    total = 0
    for leaf in jax.tree.leaves(tree):
        sh = leaf.sharding.shard_shape(leaf.shape)
        total += math.prod(sh) * leaf.dtype.itemsize
    return total

out = {}
for zero in (0, 1, 2):
    plan = ParallelPlan(n_dp=4, n_model=2, cube=(1, 1, 2), microbatches=2,
                        zero_stage=zero)
    plan.validate(n_layers=cfg.n_layers, global_batch=16)
    lay = plan.build()
    params = transformer.init(cfg, lay, jax.random.key(0))
    opt_state = init_params(opt_state_abstract(
        transformer.abstract_params(cfg, lay), lay, opt_cfg),
        jax.random.key(1))
    shape = ShapeConfig("z", 128, 16, "train")
    batch = next(iter(TokenStream(cfg, lay, shape)))
    step = jax.jit(make_train_step(cfg, lay, opt_cfg))
    p2, o2, m = step(params, opt_state, batch)
    jax.block_until_ready(m["loss"])
    t0 = time.perf_counter()
    for _ in range(3):
        p2, o2, m = step(p2, o2, batch)
        jax.block_until_ready(m["loss"])
    out[f"zero{zero}"] = {"t_step": (time.perf_counter() - t0) / 3,
                          "opt_bytes_dev0": device0_bytes((o2.m, o2.v)),
                          "loss": float(m["loss"])}
print("RESULT " + json.dumps(out))
"""


def zerosweep():
    env = _host_env()
    proc = subprocess.run(
        [sys.executable, "-c", ZEROSWEEP_SCRIPT % {"src": os.path.join(ROOT, "src")}],
        env=env, capture_output=True, text=True, timeout=3000)
    for line in proc.stdout.splitlines():
        if line.startswith("RESULT "):
            res = json.loads(line[len("RESULT "):])
            base = res.get("zero0", {}).get("opt_bytes_dev0")
            for name, r in res.items():
                saved = f" saved={base/r['opt_bytes_dev0']:.2f}x" if base else ""
                _row(f"zerosweep_train_step|{name}|dp4|8hostdev",
                     f"{r['t_step']*1e6:.0f}",
                     f"opt_bytes_dev0={r['opt_bytes_dev0']}"
                     f"{saved} loss={r['loss']:.4f}")
            return res
    print(proc.stderr[-2000:], file=sys.stderr)
    _row("zerosweep", "", "FAILED")


# ---------------------------------------------------------------------------
# Serve sweep: continuous-batching engine on 8 host devices — 1d/2d/3d
# strategies x batch sizes, chunked prefill vs seed-style token-per-step
# ---------------------------------------------------------------------------
SERVESWEEP_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, json
sys.path.insert(0, %(src)r)
import jax, numpy as np
import jax.numpy as jnp
from repro.config import reduced
from repro.configs.registry import get
from repro.core.params import init_params
from repro.core.plan import ParallelPlan
from repro.core.topology import single_device_layout
from repro.models import transformer
from repro.serve import Engine, Request, kvcache
from repro.serve.speculate import DraftSpec

cfg = reduced(get("qwen3-4b"))
PROMPT_LEN, MAX_NEW, N_REQ = 24, 8, 8

def reqs():
    return [Request(uid=i, prompt=[2 + (i + j) %% 17 for j in range(PROMPT_LEN)],
                    max_new=MAX_NEW) for i in range(N_REQ)]

out = {}
# 1d/2d cap at model=4: the reduced config's 4 kv heads bound the 1-D
# head sharding, and 2-D needs a square grid; spare devices go to dp
cases = [("3d", 8, 4, True), ("2d", 4, 4, True), ("1d", 4, 4, True),
         ("3d", 8, 8, True), ("3d", 8, 4, False)]
for strat, n_model, bs, chunked in cases:
    n_dp = 8 // n_model
    plan = ParallelPlan(n_dp=n_dp, n_model=n_model, strategy=strat)
    plan.validate(n_layers=cfg.n_layers, model=cfg, mode="serve")
    lay = plan.build()
    params = transformer.init(cfg, lay, jax.random.key(0))
    eng = Engine(cfg, lay, params, batch_size=bs, max_len=64,
                 chunked_prefill=chunked)
    eng.run(reqs())                       # warm-up: compile every bucket
    stats = eng.run(reqs())
    tag = "%%s|model%%d|bs%%d|%%s" %% (
        strat, n_model, bs, "chunked" if chunked else "seqprefill")
    out[tag] = {"tok_per_s": stats["tok_per_s"],
                "ttft_p50_s": stats["ttft_p50_s"],
                "tpot_p50_s": stats["tpot_p50_s"],
                "steps": stats["steps"]}

# ---- shared-prefix lane: warm prefix-cache TTFT vs cold prefill ----------
# f32 params: the logit-equivalence criterion needs headroom below 1e-4
plan = ParallelPlan(n_dp=1, n_model=8, strategy="3d")
plan.validate(n_layers=cfg.n_layers, model=cfg, mode="serve")
lay = plan.build()
p32 = jax.tree.map(lambda x: x.astype(jnp.float32),
                   transformer.init(cfg, lay, jax.random.key(0)))
SHARED, TAIL = 64, 8

def preqs(seed):
    # one batch-sized wave: every measured TTFT is pure (extend- or full-)
    # prefill — a deeper queue would fold first-wave DECODE time into the
    # later requests' TTFT identically on both engines, diluting the ratio
    common = [3 + j %% 13 for j in range(SHARED)]
    return [Request(uid=i,
                    prompt=common + [30 + (seed + 3 * i + j) %% 17
                                     for j in range(TAIL)],
                    max_new=MAX_NEW) for i in range(4)]

cold = Engine(cfg, lay, p32, batch_size=4, max_len=192)
cold.run(preqs(0))                        # warm-up: compile
cs = cold.run(preqs(1))
warm = Engine(cfg, lay, p32, batch_size=4, max_len=192, prefix_cache=True)
warm.run(preqs(0))                        # seeds the index + compiles prefill
warm.run(preqs(7))                        # prefix-hits: compiles the extend
ws = warm.run(preqs(1))                   # measured: every prompt prefix-hits
rc, rw = preqs(2), preqs(2)
cold.run(rc)
warm.run(rw)
prefix_match = [r.out for r in rc] == [r.out for r in rw]
out["prefix|cold"] = {"ttft_p50_s": cs["ttft_p50_s"],
                      "tok_per_s": cs["tok_per_s"]}
out["prefix|warm"] = {"ttft_p50_s": ws["ttft_p50_s"],
                      "tok_per_s": ws["tok_per_s"],
                      "hit_rate": ws["prefix_hit_rate"],
                      "tokens_reused": ws["prefix_tokens_reused"],
                      "evictions": ws["evictions"]}

# decode-logits equivalence on a prefix-hit admit (same fresh prompt through
# both engines; the warm one enters via shared blocks + an 8-token extend)
def first_decode_logits(eng, req):
    eng.submit(req)
    eng.step()                            # admit + (extend- or full-)prefill
    i = next(k for k, r in enumerate(eng.slots) if r is req)
    tok = np.zeros((eng.B, 1), np.int32)
    tok[i, 0] = req.out[-1]
    view = kvcache.gather_view(eng.pool, eng.kv.tables_device(), eng.kv.block)
    lg, _ = transformer.forward(cfg, lay, p32,
                                {"token": jnp.asarray(tok),
                                 "pos": jnp.asarray(eng.pos)},
                                mode="decode", cache=view)
    lg = np.asarray(lg.astype(jnp.float32))[i]
    while any(s is not None for s in eng.slots):   # drain before reuse
        eng.step()
    return lg

probe = preqs(3)[0]
lc = first_decode_logits(cold, Request(uid=90, prompt=list(probe.prompt),
                                       max_new=4))
lw = first_decode_logits(warm, Request(uid=91, prompt=list(probe.prompt),
                                       max_new=4))
prefix_logits_maxdiff = float(np.max(np.abs(lc - lw)))

# ---- speculative lane: self-draft TPOT + exactness, cross-arch draft -----
SPEC_PROMPT, SPEC_NEW, GAMMA = 16, 24, 3

def sreqs():
    return [Request(uid=i, prompt=[2 + (i + j) %% 17 for j in range(SPEC_PROMPT)],
                    max_new=SPEC_NEW) for i in range(4)]

base = Engine(cfg, lay, p32, batch_size=4, max_len=96)
base.run(sreqs())
rb = sreqs()
bs_stats = base.run(rb)
dlay = single_device_layout("3d")
d32 = jax.tree.map(lambda x: x.astype(jnp.float32),
                   transformer.init(cfg, dlay, jax.random.key(0)))
spec = Engine(cfg, lay, p32, batch_size=4, max_len=96,
              draft=DraftSpec(cfg, dlay, d32, gamma=GAMMA))
spec.run(sreqs())
rs = sreqs()
sp_stats = spec.run(rs)
spec_match = [r.out for r in rb] == [r.out for r in rs]
out["spec|baseline"] = {"tpot_p50_s": bs_stats["tpot_p50_s"],
                        "tok_per_s": bs_stats["tok_per_s"]}
out["spec|selfdraft"] = {"tpot_p50_s": sp_stats["tpot_p50_s"],
                        "tok_per_s": sp_stats["tok_per_s"],
                        "accepted_mean": sp_stats["accepted_mean"],
                        "verifies": sp_stats["spec_steps"]}

dcfg = reduced(get("tinyllama-1.1b"))
x32 = init_params(transformer.abstract_params(dcfg, dlay), jax.random.key(1),
                  dtype=jnp.float32)
xeng = Engine(cfg, lay, p32, batch_size=4, max_len=96,
              draft=DraftSpec(dcfg, dlay, x32, gamma=GAMMA))
rx = sreqs()
xs_stats = xeng.run(rx)
x_match = [r.out for r in rb] == [r.out for r in rx]
out["spec|crossdraft_tinyllama"] = {"tpot_p50_s": xs_stats["tpot_p50_s"],
                                    "accepted_mean": xs_stats["accepted_mean"],
                                    "verifies": xs_stats["spec_steps"]}

out["criteria"] = {
    "prefix_ttft_speedup": cs["ttft_p50_s"] / max(ws["ttft_p50_s"], 1e-12),
    "prefix_ttft_ge_3x": cs["ttft_p50_s"] >= 3 * ws["ttft_p50_s"],
    "prefix_hit_rate": ws["prefix_hit_rate"],
    "prefix_greedy_match": prefix_match,
    "prefix_logits_maxdiff": prefix_logits_maxdiff,
    "prefix_logits_1e-4": prefix_logits_maxdiff <= 1e-4,
    "spec_tpot_speedup": bs_stats["tpot_p50_s"]
                         / max(sp_stats["tpot_p50_s"], 1e-12),
    "spec_tpot_ge_1p5x": bs_stats["tpot_p50_s"]
                         >= 1.5 * sp_stats["tpot_p50_s"],
    "spec_greedy_bit_identical": spec_match,
    "crossdraft_greedy_bit_identical": x_match,
    "crossdraft_accepted_mean": xs_stats["accepted_mean"],
}
out["plan"] = {"strategy": "3d", "n_model": 8, "host_devices": 8,
               "shared_prefix": SHARED, "tail": TAIL, "gamma": GAMMA,
               "dtype": "float32 (equivalence lanes)"}
print("RESULT " + json.dumps(out))
"""


def servesweep():
    env = _host_env()
    proc = subprocess.run(
        [sys.executable, "-c",
         SERVESWEEP_SCRIPT % {"src": os.path.join(ROOT, "src")}],
        env=env, capture_output=True, text=True, timeout=3000)
    for line in proc.stdout.splitlines():
        if line.startswith("RESULT "):
            res = json.loads(line[len("RESULT "):])
            for name, r in res.items():
                if name in ("criteria", "plan"):
                    continue
                _row(f"servesweep|{name}|8hostdev", "",
                     " ".join(f"{k}={v:.4g}" if isinstance(v, float)
                              else f"{k}={v}" for k, v in r.items()))
            base = res.get("3d|model8|bs4|seqprefill", {}).get("tok_per_s")
            new = res.get("3d|model8|bs4|chunked", {}).get("tok_per_s")
            if base and new:
                _row("servesweep|chunked_vs_seed_speedup", "",
                     f"{new/base:.2f}x (criterion: >= 2x on prompts >= 16)")
            crit = res.get("criteria", {})
            if crit:
                _row("servesweep|prefix_ttft_speedup", "",
                     f"{crit['prefix_ttft_speedup']:.2f}x warm vs cold "
                     "(criterion: >= 3x on 64-token shared prefix)")
                _row("servesweep|spec_tpot_speedup", "",
                     f"{crit['spec_tpot_speedup']:.2f}x self-draft vs "
                     "baseline (criterion: >= 1.5x at temp=0)")
                _row("servesweep|equivalence", "",
                     f"prefix_greedy_match={crit['prefix_greedy_match']} "
                     f"prefix_logits_maxdiff="
                     f"{crit['prefix_logits_maxdiff']:.2e} "
                     f"spec_bit_identical={crit['spec_greedy_bit_identical']} "
                     f"crossdraft_bit_identical="
                     f"{crit['crossdraft_greedy_bit_identical']}")
            return res
    print(proc.stderr[-2000:], file=sys.stderr)
    _row("servesweep", "", "FAILED")


# ---------------------------------------------------------------------------
# Overlap sweep: async-TP chunked 3-D collectives (train step time) + fused
# paged flash-decode vs gather_view materialization (TPOT), 8 host devices.
# Both halves carry a <= 1e-4 equivalence check against the unfused path.
# ---------------------------------------------------------------------------
OVERLAPSWEEP_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, time, json, dataclasses
sys.path.insert(0, %(src)r)
import jax, numpy as np
import jax.numpy as jnp
from repro.config import ShapeConfig, reduced
from repro.configs.registry import get
from repro.core.topology import make_layout
from repro.data.pipeline import TokenStream
from repro.models import blocks as B
from repro.models import transformer
from repro.serve import Engine, Request, kvcache

out = {"train": {}, "decode": {}, "equivalence": {}}

# ---- training: overlapped vs unfused 3-D island collectives --------------
cfg = dataclasses.replace(reduced(get("paper-transformer"), d_model=512),
                          n_layers=2, remat=False)
lay_off = make_layout(cube=(1, 2, 4))
lay_on = dataclasses.replace(lay_off, overlap=True, overlap_chunks=4)

def grad_fn(lay):
    def fwd(p, b):
        loss, _ = transformer.forward(cfg, lay, p, b, mode="train")
        return loss
    return jax.jit(jax.value_and_grad(fwd))

shape = ShapeConfig("o", 256, 8, "train")
for name, lay in (("overlap_off", lay_off), ("overlap_on", lay_on)):
    params = transformer.init(cfg, lay, jax.random.key(0))
    batch = next(iter(TokenStream(cfg, lay, shape)))
    g = grad_fn(lay)
    jax.block_until_ready(g(params, batch))
    t0 = time.perf_counter()
    for _ in range(3):
        jax.block_until_ready(g(params, batch))
    out["train"][name] = {"t_step": (time.perf_counter() - t0) / 3}

# equivalence in f32 (bf16 rounding would mask the comparison — params
# default to bf16 regardless of cfg, so cast the whole tree): loss + the
# full grad tree must agree <= 1e-4 between overlap on and off
diffs = []
res = {}
for name, lay in (("off", lay_off), ("on", lay_on)):
    params = transformer.init(cfg, lay, jax.random.key(0))
    params = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    batch = next(iter(TokenStream(cfg, lay, shape)))
    loss, grads = grad_fn(lay)(params, batch)
    res[name] = (float(loss), jax.device_get(grads))
dl = abs(res["on"][0] - res["off"][0])
for a, b in zip(jax.tree.leaves(res["on"][1]), jax.tree.leaves(res["off"][1])):
    diffs.append(float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                       - b.astype(jnp.float32)))))
out["equivalence"]["train_loss_diff"] = dl
out["equivalence"]["train_grad_maxdiff"] = max(diffs)

# ---- decode: fused paged flash-decode vs gather_view ---------------------
scfg = reduced(get("qwen3-4b"))
slay = make_layout(cube=(1, 2, 4))
PROMPT_LEN, MAX_NEW, N_REQ, BS = 24, 16, 8, 8

def reqs():
    return [Request(uid=i, prompt=[2 + (i + j) %% 17 for j in range(PROMPT_LEN)],
                    max_new=MAX_NEW) for i in range(N_REQ)]

sparams = transformer.init(scfg, slay, jax.random.key(0))
outs = {}
for name, fused in (("fused_off", False), ("fused_on", True)):
    eng = Engine(scfg, slay, sparams, batch_size=BS, max_len=64,
                 fused_decode=fused)
    eng.run(reqs())                        # warm-up: compile every bucket
    rs = reqs()
    stats = eng.run(rs)
    outs[name] = [tuple(r.out) for r in rs]
    out["decode"][name] = {"tpot_p50_s": stats["tpot_p50_s"],
                           "tok_per_s": stats["tok_per_s"],
                           "steps": stats["steps"]}
out["equivalence"]["decode_greedy_match"] = outs["fused_off"] == outs["fused_on"]

# decode-logits equivalence in f32: same pool state, one decode step through
# the fused page path vs the materialized gather_view path (params cast to
# f32 — cfg.dtype does not reach the Param defaults)
p32 = jax.tree.map(lambda x: x.astype(jnp.float32), sparams)
eng = Engine(scfg, slay, p32, batch_size=BS, max_len=64, fused_decode=True)
for r in reqs():
    eng.submit(r)
for _ in range(3):                         # prefill + a couple decode ticks
    eng.step()
tok = np.zeros((BS, 1), np.int32)
active = np.zeros((BS,), bool)
for i, r in enumerate(eng.slots):
    if r is not None and r.out:
        tok[i, 0] = r.out[-1]
        active[i] = True
tables = eng.kv.tables_device()
blk = eng.kv.block
batch_d = {"token": jnp.asarray(tok), "pos": jnp.asarray(eng.pos)}
page = B.PageInfo(tables=tables, active=jnp.asarray(active), block=blk)
lf, _ = transformer.forward(scfg, slay, p32, batch_d, mode="decode",
                            cache=eng.pool, page=page)
view = kvcache.gather_view(eng.pool, tables, blk)
lu, _ = transformer.forward(scfg, slay, p32, batch_d, mode="decode",
                            cache=view)
d = jnp.max(jnp.abs(lf.astype(jnp.float32) - lu.astype(jnp.float32)),
            axis=tuple(range(1, lf.ndim)))
out["equivalence"]["decode_logits_maxdiff"] = float(
    jnp.max(jnp.where(jnp.asarray(active), d, 0.0)))
print("RESULT " + json.dumps(out))
"""


def overlapsweep():
    env = _host_env()
    proc = subprocess.run(
        [sys.executable, "-c",
         OVERLAPSWEEP_SCRIPT % {"src": os.path.join(ROOT, "src")}],
        env=env, capture_output=True, text=True, timeout=3000)
    for line in proc.stdout.splitlines():
        if not line.startswith("RESULT "):
            continue
        res = json.loads(line[len("RESULT "):])
        for name, r in res["train"].items():
            _row(f"overlapsweep_train_step|{name}|3d8|8hostdev",
                 f"{r['t_step']*1e6:.0f}", "")
        for name, r in res["decode"].items():
            _row(f"overlapsweep_decode|{name}|3d8|8hostdev", "",
                 f"tpot_p50_s={r['tpot_p50_s']:.4f} "
                 f"tok_per_s={r['tok_per_s']:.1f} steps={r['steps']}")
        eq = res["equivalence"]
        t_off = res["train"]["overlap_off"]["t_step"]
        t_on = res["train"]["overlap_on"]["t_step"]
        tp_off = res["decode"]["fused_off"]["tpot_p50_s"]
        tp_on = res["decode"]["fused_on"]["tpot_p50_s"]
        crit = {
            "train_step_speedup": t_off / t_on,
            "decode_tpot_speedup": tp_off / max(tp_on, 1e-12),
            "any_measured_win": t_on < t_off or tp_on < tp_off,
            "train_grad_maxdiff": eq["train_grad_maxdiff"],
            "decode_logits_maxdiff": eq["decode_logits_maxdiff"],
            "decode_greedy_match": eq["decode_greedy_match"],
            "equivalence_1e-4": (eq["train_loss_diff"] <= 1e-4
                                 and eq["train_grad_maxdiff"] <= 1e-4
                                 and eq["decode_logits_maxdiff"] <= 1e-4),
        }
        _row("overlapsweep|train_speedup", "",
             f"{crit['train_step_speedup']:.2f}x (overlap on vs off)")
        _row("overlapsweep|decode_tpot_speedup", "",
             f"{crit['decode_tpot_speedup']:.2f}x (fused vs gather_view)")
        _row("overlapsweep|criteria", "",
             f"any_measured_win={crit['any_measured_win']} "
             f"equivalence_1e-4={crit['equivalence_1e-4']} "
             f"(grad={eq['train_grad_maxdiff']:.2e} "
             f"logits={eq['decode_logits_maxdiff']:.2e} "
             f"greedy_match={eq['decode_greedy_match']})")
        res["criteria"] = crit
        res["plan"] = {"strategy": "3d", "n_model": 8, "cube": [1, 2, 4],
                       "overlap_chunks": 4, "host_devices": 8}
        return res
    print(proc.stderr[-2000:], file=sys.stderr)
    _row("overlapsweep", "", "FAILED")
    return None


# ---------------------------------------------------------------------------
# Roofline from the dry-run results
# ---------------------------------------------------------------------------
def roofline(path=None):
    path = path or os.path.join(ROOT, "results_dryrun.jsonl")
    if not os.path.exists(path):
        _row("roofline", "", "results_dryrun.jsonl missing (run dryrun first)")
        return
    from benchmarks.roofline import analyse, fmt_row
    for r in analyse(path):
        _row(f"roofline|{r['arch']}|{r['shape']}|{r['mesh_tag']}", "",
             fmt_row(r))


def _emit(scenario, res, out_dir):
    """``--out`` contract: BENCH_<scenario>.json with the scenario name, the
    plan it ran under (when the scenario reports one), its metrics and the
    criteria pass/fail map."""
    if res is None:
        return
    doc = {"scenario": scenario,
           "plan": res.pop("plan", None),
           "criteria": res.pop("criteria", None),
           "metrics": res}
    path = os.path.join(out_dir, f"BENCH_{scenario}.json")
    with open(path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
    print(f"# wrote {path}", file=sys.stderr)


def main() -> None:
    argv = [a for a in sys.argv[1:] if a != "--out"]
    out_dir = ROOT if "--out" in sys.argv[1:] else None
    which = argv[0] if argv else "all"
    scenarios = {"table1": table1, "table2": table2, "comm": comm_volume,
                 "kernels": kernels, "minirun": minirun, "ppsweep": ppsweep,
                 "zerosweep": zerosweep, "servesweep": servesweep,
                 "overlapsweep": overlapsweep, "roofline": roofline}
    print("name,us_per_call,derived")
    for name, fn in scenarios.items():
        if which not in (name, "all"):
            continue
        mark = len(_ROWS)
        res = fn()
        if out_dir is not None:
            # uniform --out contract: scenarios without a structured result
            # (table1/table2/kernels/roofline) still emit their CSV rows
            if not isinstance(res, dict):
                res = {"rows": _ROWS[mark:]}
            _emit(name, res, out_dir)


if __name__ == "__main__":
    main()
