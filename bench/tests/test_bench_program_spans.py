"""The serving engine's own spans as the per-layer readers see them: a CPU
profiler trace of the tiny cell's engine inside a ``bench.window``
annotation goes through ``bench/metrics/_program.py`` and the four readers
of the engine's spans; the device's share is checked on intervals counted
by hand, since a CPU trace holds no device plane."""
import pytest

from bench import run as harness
from bench import spec, trace, weights
from bench.drivers import serve_open_loop as drv
from bench.metrics import _program
from bench.tests.tiny import tiny_cell

READERS = ("engine_host_ms.serve", "device_idle_host.serve",
           "prefill_pad_share.serve", "compile_ms.serve")
# prompt lengths per traced run: "warm" stays inside the buckets the
# warm-up ran (8, 16, 32); "cold" needs bucket 64, which it never ran
PROMPTS = {"warm": [5, 9, 12, 20, 7, 15, 24], "cold": [40]}


def read(name, ctx):
    return harness.load_metric(name).read(ctx)


def _traced(eng, prompts, out_dir, vocab):
    import jax
    from repro.serve.engine import Request
    reqs = [Request(uid=100 + i, max_new=3,
                    prompt=[(3 * j + i) % vocab for j in range(n)])
            for i, n in enumerate(prompts)]
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(out_dir), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(trace.WINDOW):
            for r in reqs:
                eng.submit(r)
            while not all(r.done for r in reqs):
                eng.step()
    finally:
        jax.profiler.stop_trace()
    assert all(r.out and not r.error for r in reqs)
    return trace.find_xplane(str(out_dir))


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """Per traced run: the readers' context and the engine's own prefill
    groups as (tokens, padded positions)."""
    from jax.profiler import ProfileData
    from repro.models import transformer
    from repro.serve.engine import Engine
    cell = tiny_cell("qwen3-4b.serve.chat")
    mix, conf = cell.traffic, cell.config
    cfg = spec.model_config(conf, cell.config_name)
    layout = drv.make_layout(1)
    params = weights.make(transformer.abstract_params(cfg, layout), layout,
                          5, conf["config"]["initializer_range"])
    eng = Engine(cfg, layout, params, batch_size=mix["slots"],
                 max_len=mix["max_len"], block_size=mix["block"],
                 prefill_chunk=mix["prefill_chunk"],
                 temperature=mix["temperature"], seed=5)
    drv.warm_up(eng, mix, cfg.vocab)
    groups = []
    group_of = eng.scheduler.prefill_group

    def recorded(lens):
        group, s_pad = group_of(lens)
        groups.append((sum(lens[s] for s in group), eng.B * s_pad))
        return group, s_pad

    eng.scheduler.prefill_group = recorded
    out = {}
    for name, prompts in PROMPTS.items():
        groups.clear()
        pd = ProfileData.from_file(_traced(
            eng, prompts, tmp_path_factory.mktemp(name), cfg.vocab))
        ctx = {"trace": trace.reduce_profile(pd),
               "program": _program.from_profile(pd)}
        out[name] = ctx, list(groups)
    return out


def test_spans_lie_in_the_window_with_their_args(runs):
    ctx, _ = runs["warm"]
    sp = ctx["program"]
    names = {s.name for s in sp}
    assert {"serve.step", "serve.admit", "serve.prepare", "serve.dispatch",
            "serve.wait", "serve.emit"} <= names
    w = ctx["trace"].window_s
    assert all(0.0 <= s.start <= s.end <= w for s in sp)
    steps = _program.named(sp, "serve.step")
    assert {"queue", "slots", "compile_n", "compile_s"} <= set(steps[0].args)


def test_prefill_pad_share_matches_the_engines_groups(runs):
    ctx, groups = runs["warm"]
    assert len(groups) >= 2
    tokens = sum(t for t, _ in groups)
    padded = sum(p for _, p in groups)
    assert read("prefill_pad_share.serve", ctx) == pytest.approx(
        100.0 * (1.0 - tokens / padded))


def test_compile_ms_zero_after_warm_up_above_zero_for_a_new_bucket(runs):
    assert read("compile_ms.serve", runs["warm"][0]) == 0.0
    assert read("compile_ms.serve", runs["cold"][0]) > 0.0


def test_engine_host_ms_is_step_time_less_its_wait(runs):
    ctx, _ = runs["warm"]
    steps = _program.named(ctx["program"], "serve.step")
    got = read("engine_host_ms.serve", ctx)
    assert 0.0 < got < 1e3 * max(s.dur for s in steps)
    step = _program.Span("serve.step", 0.0, 0.010, {})
    hand = [step, _program.Span("serve.wait", 0.004, 0.009, {}),
            _program.Span("serve.step", 0.010, 0.020, {})]
    # 10 ms less a 5 ms wait, and 10 ms with none
    assert read("engine_host_ms.serve", {"program": hand}) == \
        pytest.approx(7.5)


def test_device_idle_under_host_code_by_hand():
    S = _program.Span
    dev = trace.Device(ops=[("a", 10.0, 30.0), ("b", 25.0, 40.0),
                            ("c", 60.0, 70.0)], modules=[])
    red = trace.Reduced(window_s=100.0, devices={"/device:TPU:0": dev},
                        host=[])
    sp = [S("serve.step", 0.0, 95.0, {}),          # the step: not counted
          S("serve.admit", 0.0, 12.0, {}),         # idle 0-10
          S("serve.prepare", 35.0, 50.0, {}),
          S("serve.dispatch", 45.0, 55.0, {}),     # with prepare: idle 40-55
          S("serve.wait", 55.0, 62.0, {}),         # waiting: not counted
          S("serve.emit", 66.0, 90.0, {})]         # idle 70-90
    ctx = {"trace": red, "program": sp}
    assert read("device_idle_host.serve", ctx) == pytest.approx(45.0)
    assert read("device_idle_host.serve", ctx) <= \
        read("device_idle.serve", ctx)             # busy 40 of 100


@pytest.mark.parametrize("name", READERS)
def test_silent_without_the_programs_spans(runs, name):
    # a program that records no serve.* spans (the parent of this reader)
    ctx, _ = runs["warm"]
    assert read(name, dict(ctx, program=[])) is None
