"""Observability layer: tracer no-op/nesting/round-trip contracts, trace
validators, telemetry on a real 2-step train run (single device), the
serve-metrics percentile edge cases, and the commcheck analytic
formulas pinned against benchmarks/analytic.py.  The multi-device commcheck
measurement itself runs as a subprocess on 4 host devices with pinned
collective counts for the (1, 2, 2) cube.
"""
import json
import math
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)                     # benchmarks/, tools/

from repro.obs import NULL, NullTracer, Tracer, make_tracer  # noqa: E402
from repro.obs.telemetry import (first_nonfinite_path,  # noqa: E402
                                 nonfinite_report)
from repro.serve.metrics import percentile  # noqa: E402
from tools.check_trace import (validate_chrome,  # noqa: E402
                               validate_events, validate_jsonl)


class FakeClock:
    """Deterministic clock: every read advances by ``tick`` seconds."""

    def __init__(self, tick=1.0):
        self.t = 0.0
        self.tick = tick

    def __call__(self):
        self.t += self.tick
        return self.t


# ---------------------------------------------------------------------------
# Disabled mode is a true no-op
# ---------------------------------------------------------------------------
def test_null_tracer_is_shared_singleton_noop():
    tr = make_tracer(False)
    assert tr is NULL and isinstance(tr, NullTracer)
    assert tr.enabled is False
    # span() hands back one shared context manager: no per-call allocation
    s1, s2 = tr.span("a"), tr.span("b", track="x", foo=1)
    assert s1 is s2
    with s1 as sp:
        sp.set(bar=2)
        assert sp.sync("value") == "value"    # passthrough, no device sync
    tr.instant("i")
    tr.counter("c", 1.0)
    tr.span_at("s", 0.0, 1.0)
    assert tr.events == ()                    # nothing recorded, ever
    assert tr.now() == 0.0 and tr.rel(123.4) == 0.0


def test_null_tracer_write_is_noop(tmp_path):
    path = tmp_path / "t.json"
    NULL.write_chrome(str(path))
    NULL.write_jsonl(str(path) + "l")
    assert not path.exists()


# ---------------------------------------------------------------------------
# Recording: nesting, exception safety, schema
# ---------------------------------------------------------------------------
def test_span_nesting_emits_inner_first():
    tr = Tracer(clock=FakeClock(), annotate=False)
    with tr.span("outer"):
        with tr.span("inner"):
            pass
    names = [e["name"] for e in tr.events]
    assert names == ["inner", "outer"]        # emitted at exit
    inner, outer = tr.events
    assert outer["ts"] <= inner["ts"]
    assert outer["ts"] + outer["dur"] >= inner["ts"] + inner["dur"]
    assert validate_events(list(tr.events)) == []


def test_span_survives_exception_and_tags_error():
    tr = Tracer(clock=FakeClock(), annotate=False)
    with pytest.raises(ValueError):
        with tr.span("boom", track="t"):
            raise ValueError("x")
    (ev,) = tr.events
    assert ev["args"]["error"] == "ValueError"
    assert ev["dur"] > 0


def test_span_set_args_and_counter_instant_schema():
    tr = Tracer(clock=FakeClock(), annotate=False)
    with tr.span("s", track="a", k=1) as sp:
        sp.set(j=2)
    tr.instant("i", track="a", note="n")
    tr.counter("c", 3, track="a")
    span, inst, ctr = tr.events
    assert span["args"] == {"k": 1, "j": 2}
    assert inst["ev"] == "instant" and inst["args"] == {"note": "n"}
    assert ctr["ev"] == "counter" and ctr["value"] == 3.0
    assert validate_events(list(tr.events)) == []


def test_span_at_retroactive():
    tr = Tracer(clock=FakeClock(), annotate=False)
    t0 = tr.now()
    t1 = tr.now()
    tr.span_at("retro", t0, t1, track="req1", tokens=5)
    (ev,) = tr.events
    assert ev["ts"] == t0 and ev["dur"] == t1 - t0
    assert ev["args"]["tokens"] == 5
    # rel() maps absolute stamps of the same clock into the timebase
    assert abs(tr.rel(tr._t0) - 0.0) < 1e-12


# ---------------------------------------------------------------------------
# Profiler-only mode and the compile counter
# ---------------------------------------------------------------------------
def _profiled_spans(log_dir, body):
    """(name, stats) of the host events of a profiler trace of ``body``."""
    import glob
    import jax
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(log_dir))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                        recursive=True)
    pd = ProfileData.from_file(path)
    return [(e.name, dict(e.stats)) for plane in pd.planes
            if plane.name.startswith("/host:") for line in plane.lines
            for e in line.events]


def test_profile_tracer_spans_land_in_the_profile_only(tmp_path):
    import jax
    import jax.numpy as jnp
    from repro.obs import PROFILE, ProfileTracer
    assert isinstance(PROFILE, ProfileTracer) and PROFILE.enabled is False
    recording = Tracer(clock=FakeClock(), annotate=True)
    x = jnp.ones(4)

    def body():
        with PROFILE.span("serve.step", track="engine", queue=3,
                          compile_s=0.25):
            (x + 1).block_until_ready()
        with recording.span("train_step", track="train", step=4):
            pass
        PROFILE.instant("i")
        PROFILE.counter("c", 1.0)
        PROFILE.span_at("s", 0.0, 1.0)

    evs = _profiled_spans(tmp_path, body)
    assert ("serve.step", {"queue": 3, "compile_s": 0.25}) in evs
    # the recording tracer puts the same name and args in the profile
    assert ("train_step", {"step": 4}) in evs
    assert not any(n in ("i", "c", "s") for n, _ in evs)
    assert PROFILE.events == () and PROFILE.now() == 0.0
    assert [e["name"] for e in recording.events] == ["train_step"]


def test_compile_counter_counts_backend_compiles():
    import jax
    import jax.numpy as jnp
    from repro.obs import COMPILES
    from repro.obs.trace import COMPILE_EVENT
    assert COMPILES.watch() is COMPILES.watch()       # one listener
    x = jnp.arange(3.0)
    f = jax.jit(lambda v: v * 7.0 - 2.0)
    n0, s0 = COMPILES.n, COMPILES.s
    f(x).block_until_ready()
    assert COMPILES.n == n0 + 1 and COMPILES.s > s0
    f(x).block_until_ready()                          # cached: no compile
    n1, s1 = COMPILES.n, COMPILES.s
    assert n1 == n0 + 1
    jax.monitoring.record_event_duration_secs(COMPILE_EVENT, 0.5)
    jax.monitoring.record_event_duration_secs("/other/event", 9.0)
    assert COMPILES.n == n1 + 1
    assert COMPILES.s == pytest.approx(s1 + 0.5)


# ---------------------------------------------------------------------------
# Export round-trip through the validators
# ---------------------------------------------------------------------------
def test_jsonl_and_chrome_roundtrip(tmp_path):
    tr = Tracer(clock=FakeClock(0.5), annotate=False)
    with tr.span("outer", track="train", step=0):
        with tr.span("inner", track="train"):
            pass
        tr.counter("loss", 2.5, track="telemetry")
    tr.instant("done", track="train")
    jsonl = str(tmp_path / "t.jsonl")
    chrome = str(tmp_path / "t.json")
    tr.write_jsonl(jsonl)
    tr.write_chrome(chrome)
    assert validate_jsonl(jsonl) == []
    assert validate_chrome(chrome) == []
    # the JSONL log round-trips the exact event dicts
    back = [json.loads(l) for l in open(jsonl)]
    assert back == list(tr.events)
    doc = json.load(open(chrome))
    evs = doc["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    tracks = {m["args"]["name"] for m in meta}
    assert tracks == {"train", "telemetry"}
    x = [e for e in evs if e["ph"] == "X"]
    assert {e["name"] for e in x} == {"outer", "inner"}
    # ts/dur are microseconds of the same spans
    o = next(e for e in x if e["name"] == "outer")
    src = next(e for e in tr.events
               if e["ev"] == "span" and e["name"] == "outer")
    assert o["ts"] == pytest.approx(src["ts"] * 1e6)
    assert o["dur"] == pytest.approx(src["dur"] * 1e6)


def test_check_trace_flags_bad_traces():
    overlap = [
        {"ev": "span", "name": "a", "track": "t", "ts": 0.0, "dur": 2.0},
        {"ev": "span", "name": "b", "track": "t", "ts": 1.0, "dur": 2.0},
    ]
    assert any("improper nesting" in p for p in validate_events(overlap))
    backwards = [
        {"ev": "instant", "name": "a", "track": "t", "ts": 2.0},
        {"ev": "instant", "name": "b", "track": "t", "ts": 1.0},
    ]
    assert any("non-monotonic" in p for p in validate_events(backwards))
    malformed = [{"ev": "span", "name": "a", "track": "t", "ts": 0.0}]
    assert validate_events(malformed)         # span without dur
    assert validate_events([{"ev": "nope"}])
    assert validate_chrome({"traceEvents": [{"name": "x"}]})  # no ph


def test_check_trace_cli(tmp_path):
    tr = Tracer(clock=FakeClock(), annotate=False)
    with tr.span("s"):
        pass
    good = str(tmp_path / "good.json")
    tr.write_chrome(good)
    bad = str(tmp_path / "bad.json")
    with open(bad, "w") as f:
        f.write("not json")
    from tools.check_trace import main
    assert main([good]) == 0
    assert main([good, bad]) == 1


# ---------------------------------------------------------------------------
# Non-finite sentinel
# ---------------------------------------------------------------------------
def test_first_nonfinite_path_names_offender():
    import jax.numpy as jnp
    tree = {"a": {"w": jnp.ones(3)},
            "b": {"v": jnp.array([1.0, float("nan")])}}
    path = first_nonfinite_path(tree)
    assert path is not None and "b" in path and "v" in path
    assert first_nonfinite_path({"a": jnp.ones(2)}) is None
    # integer leaves are skipped, not fetched
    assert first_nonfinite_path({"i": jnp.arange(3)}) is None
    rep = nonfinite_report(params={"x": jnp.ones(1)}, grads=tree)
    assert "params: all finite" in rep and "grads:" in rep


# ---------------------------------------------------------------------------
# Telemetry on a real (single-device) 2-step train run
# ---------------------------------------------------------------------------
def test_telemetry_two_step_train():
    import jax
    from repro.config import OptimConfig, ShapeConfig, reduced
    from repro.configs.registry import get
    from repro.core.params import init_params
    from repro.core.plan import ParallelPlan
    from repro.data.pipeline import TokenStream
    from repro.models import transformer
    from repro.obs.telemetry import TrainTelemetry
    from repro.optim.optimizers import opt_state_abstract
    from repro.train.step import make_train_step

    cfg = reduced(get("tinyllama-1.1b"), d_model=128)
    opt_cfg = OptimConfig(lr=1e-3, warmup=2, total_steps=10)
    plan = ParallelPlan(n_dp=1, n_model=1)
    plan.validate(n_layers=cfg.n_layers, global_batch=2)
    lay = plan.build()
    params = transformer.init(cfg, lay, jax.random.key(0))
    opt_state = init_params(opt_state_abstract(
        transformer.abstract_params(cfg, lay), lay, opt_cfg),
        jax.random.key(1))
    shape = ShapeConfig("tel", 32, 2, "train")
    batch = next(iter(TokenStream(cfg, lay, shape)))
    step = jax.jit(make_train_step(cfg, lay, opt_cfg))

    tracer = Tracer(annotate=False)
    tel = TrainTelemetry(cfg, lay, global_batch=2, seq_len=32,
                         warmup_steps=1, peak_flops_per_device=197e12,
                         tracer=tracer)
    for i in range(2):
        params, opt_state, metrics = step(params, opt_state, batch)
        rec = tel.record(i, metrics)
    assert rec["tokens_per_s"] > 0 and rec["mfu"] > 0

    s = tel.summary()
    assert s["steps"] == 2 and s["warmup_steps"] == 1
    assert s["t_step_warmup_s"] == 0.0        # first record has no prior stamp
    assert s["t_step_s"] > 0
    assert s["tokens_per_s"] > 0
    assert s["flops_per_step"] > 0
    assert 0 < s["mfu"] < 1
    assert s["mem_source"] in ("memory_stats", "live_buffers")
    assert s["mem_peak_bytes_max"] > 0
    assert s["n_devices"] == 1
    assert s["nonfinite"] is None
    assert math.isfinite(s["loss_last"])
    assert len(s["series"]["loss"]) == 2
    # the tracer got the loss/t_step counters on the telemetry track
    kinds = {(e["ev"], e["name"]) for e in tracer.events}
    assert ("counter", "loss") in kinds
    assert ("counter", "t_step_s") in kinds

    # sentinel: a non-finite loss flips tel.nonfinite exactly once
    import jax.numpy as jnp
    tel.record(2, {"loss": jnp.float32(float("nan"))})
    assert tel.nonfinite is not None and tel.nonfinite["step"] == 2
    blame = tel.blame({"w": jnp.array([float("inf")])})
    assert "params:" in blame and "all finite" not in blame


def test_telemetry_peak_from_device_kind():
    """The MFU peak comes from the published table by device kind; a kind
    the table lacks (the CPU) is an error unless a peak is passed."""
    from repro.configs.registry import get
    from repro.config import reduced
    from repro.core.topology import single_device_layout
    from repro.obs.telemetry import TrainTelemetry, peak_flops

    assert peak_flops("TPU v5 lite") == 197e12
    with pytest.raises(ValueError, match="device kind"):
        peak_flops("cpu")
    cfg = reduced(get("tinyllama-1.1b"))
    with pytest.raises(ValueError, match="--peak-flops"):
        TrainTelemetry(cfg, single_device_layout(), global_batch=2,
                       seq_len=16)
    tel = TrainTelemetry(cfg, single_device_layout(), global_batch=2,
                         seq_len=16, peak_flops_per_device=1e12)
    assert tel.peak == 1e12


def test_telemetry_write(tmp_path):
    from repro.configs.registry import get
    from repro.config import reduced
    from repro.core.plan import ParallelPlan

    cfg = reduced(get("tinyllama-1.1b"))
    plan = ParallelPlan(n_dp=1, n_model=1)
    plan.validate(n_layers=cfg.n_layers, global_batch=2)
    from repro.obs.telemetry import TrainTelemetry
    tel = TrainTelemetry(cfg, plan.build(), global_batch=2, seq_len=16,
                         peak_flops_per_device=197e12)
    path = tmp_path / "tel.json"
    tel.write(str(path))
    doc = json.loads(path.read_text())
    assert doc["steps"] == 0 and "mfu" in doc


# ---------------------------------------------------------------------------
# Serve metrics: percentile totality
# ---------------------------------------------------------------------------
def test_percentile_edge_cases():
    assert percentile([], 50) == 0.0
    assert percentile([float("nan"), float("inf")], 50) == 0.0
    assert percentile([3.0], 0) == 3.0
    assert percentile([3.0], 50) == 3.0
    assert percentile([3.0], 100) == 3.0
    assert percentile([1.0, 2.0, 3.0], -5) == 1.0     # q clamped
    assert percentile([1.0, 2.0, 3.0], 205) == 3.0
    assert percentile([1.0, float("nan"), 3.0], 100) == 3.0
    assert percentile([5.0] * 7, 95) == 5.0


def test_serve_metrics_emit_shared_schema():
    clk = FakeClock()
    tr = Tracer(clock=clk, annotate=False)
    from repro.serve.metrics import ServeMetrics
    m = ServeMetrics(clock=clk, tracer=tr)
    m.submit(7)
    m.admit(7)
    m.token(7)
    m.token(7)
    m.finish(7)
    m.observe_step(3, "decode")
    evs = list(tr.events)
    assert validate_events(evs) == []
    req = [(e["ev"], e["name"]) for e in evs if e["track"] == "req7"]
    assert ("instant", "submit") in req
    assert ("span", "queue") in req
    assert ("span", "prefill") in req
    assert ("span", "decode") in req
    assert ("instant", "finish") in req
    eng = [e for e in evs if e["track"] == "engine"]
    assert eng and eng[0]["name"] == "queue_depth" and eng[0]["value"] == 3.0
    s = m.summary(wall_s=10.0)
    assert s["queue_wait_p50_s"] > 0
    # with the NULL tracer the same hooks emit nothing
    m2 = ServeMetrics(clock=clk)
    m2.submit(1)
    m2.admit(1)
    m2.finish(1)
    assert m2.tracer is NULL


# ---------------------------------------------------------------------------
# Commcheck: analytic side pinned to benchmarks/analytic.py
# ---------------------------------------------------------------------------
def test_commcheck_analytic_matches_benchmarks():
    from benchmarks import analytic as bench
    from repro.obs import commcheck as cc
    shapes = [(6144, 3072, 3072), (6144, 3072, 9216), (6144, 12288, 3072),
              (1024, 512, 2048)]
    for (M, N, K) in shapes:
        assert cc.comm_1d(M, N, K, 8) == pytest.approx(
            bench.comm_1d(M, N, K, 8))
        assert cc.comm_2d(M, N, K, 4) == pytest.approx(
            bench.comm_2d(M, N, K, 4))
        assert cc.comm_3d(M, N, K, 8) == pytest.approx(
            bench.comm_3d(M, N, K, 8))


def test_commcheck_config_matmuls():
    from repro.configs.registry import get
    from repro.obs import commcheck as cc
    cfg = get("paper-transformer")
    mm = cc.config_matmuls(cfg, batch=2, seq=8)
    assert len(mm) == 4
    assert all(m[0] == 16 for m in mm)        # M = batch * seq everywhere
    ana = cc.analytic_bytes(cfg, "3d", 8, 2, 8)
    assert ana > 0


# ---------------------------------------------------------------------------
# Commcheck measurement: pinned collective counts on the (1, 2, 2) cube
# ---------------------------------------------------------------------------
COMMCHECK_SCRIPT = r"""
import json
from repro.obs.commcheck import analytic_bytes, measure_plan
from repro.configs.registry import get
from repro.config import reduced
import dataclasses

cfg = dataclasses.replace(reduced(get("paper-transformer")), n_layers=2)
lay, meas, detail = measure_plan(cfg, "3d", 4, batch=2, seq=32)
assert lay.cube == (1, 2, 2), lay.cube
out = {"counts": meas["counts"], "bytes": meas["bytes_per_device"],
       "analytic": analytic_bytes(cfg, "3d", 4, 2, 32),
       "kinds": sorted(k for k, v in meas["by_kind"].items() if v)}
print("RESULT " + json.dumps(out))
"""


@pytest.mark.slow
def test_commcheck_measured_counts_cube_1_2_2():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(ROOT, "src"))
    proc = subprocess.run([sys.executable, "-c", COMMCHECK_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    line = next(l for l in proc.stdout.splitlines()
                if l.startswith("RESULT "))
    res = json.loads(line[len("RESULT "):])
    # grad(forward) on the 3-D (1,2,2) plan must communicate: both gather
    # kinds present and a strictly positive per-device byte count
    assert res["bytes"] > 0
    assert res["analytic"] > 0
    counts = res["counts"]
    assert sum(counts.values()) > 0, counts
    assert counts.get("all-gather", 0) > 0, counts
    # reduce phases appear as all-reduce and/or reduce-scatter
    assert counts.get("all-reduce", 0) + counts.get("reduce-scatter", 0) > 0
