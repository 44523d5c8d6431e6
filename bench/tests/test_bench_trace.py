"""The trace reduction on a small recorded trace: busy union, idle gaps
named by host spans, program and kernel time."""
import pytest

from bench import trace

KERNEL_OP = ('%closed_call.3 = f32[16,8]{1,0} custom-call(s32[16,64]{1,0} '
             '%copy.1), custom_call_target="tpu_custom_call"')
# one TPU and the host, times in microseconds from 0 (the proto's ps)
OPS = [  # (name, start, duration) on /device:TPU:0 "XLA Ops"
    ("fusion.1", 10, 20),
    (KERNEL_OP, 25, 15),                   # overlaps fusion.1 by 5
    ("copy.7", 50, 10),                    # 4 of its 10 under fusion.2
    ("fusion.2", 56, 9),
    ("fusion.3", 90, 10),
]
MODULES = [("jit_decode_step(4)", 8, 35), ("jit_prefill_step(9)", 48, 20),
           ("jit_decode_step(4)", 88, 14)]
HOST = [("bench.window", 5, 100), ("bench.engine_step", 5, 100),
        ("bench.wait_arrival", 70, 15)]


def q(text):
    return text.replace('"', '\\"')


def _plane(pid, name, lines):
    meta, out, k = {}, [], 0
    for lid, (lname, evs) in enumerate(lines, 1):
        body = []
        for n, s, d in evs:
            if n not in meta:
                k += 1
                meta[n] = k
            body.append(f"events {{ metadata_id: {meta[n]} "
                        f"offset_ps: {s * 10**6} duration_ps: {d * 10**6} }}")
        out.append(f'lines {{ id: {lid} name: "{lname}" timestamp_ns: 0 '
                   + " ".join(body) + " }")
    md = " ".join(f'event_metadata {{ key: {i} value {{ id: {i} name: '
                  f'"{q(n)}" }} }}' for n, i in meta.items())
    return f'planes {{ id: {pid} name: "{name}" ' + " ".join(out) + " " + md + " }"


@pytest.fixture(scope="module")
def red():
    from jax.profiler import ProfileData
    txt = (_plane(1, "/device:TPU:0", [("XLA Ops", OPS),
                                       ("XLA Modules", MODULES)])
           + _plane(2, "/host:CPU", [("python", HOST)]))
    return trace.reduce_profile(ProfileData.from_text_proto(txt))


US = 1e-6


def test_window_and_clipping(red):
    assert red.window_s == pytest.approx(100 * US)
    dev = red.devices["/device:TPU:0"]
    assert dev.ops[0][1] == pytest.approx(5 * US)        # 10 - window start
    assert [h[0] for h in red.host] == ["bench.engine_step",
                                        "bench.wait_arrival"]


def test_busy_is_the_union_of_ops(red):
    dev = red.devices["/device:TPU:0"]
    # [10,40] + [50,65] + [90,100] = 30 + 15 + 10
    assert trace.busy_s(dev) == pytest.approx(55 * US)


def test_kernel_time_by_name_inside_its_program(red):
    import importlib.util
    import os
    path = os.path.join(trace.os.path.dirname(trace.__file__), "metrics",
                        "paged_decode_roofline.py")
    sp = importlib.util.spec_from_file_location("pdr", path)
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    dev = red.devices["/device:TPU:0"]
    assert mod.kernel_s(dev) == pytest.approx(15 * US)


def test_idle_gaps_named_by_host_span(red):
    gaps = trace.idle_by_host(red)
    assert [g[0] for g in gaps] == ["bench.wait_arrival", "bench.engine_step"]
    assert [g[1] for g in gaps] == pytest.approx([25 * US, 10 * US])


def test_top_ops(red):
    assert trace.top_ops(red, 2)[0] == ("fusion.1", pytest.approx(20 * US))
    assert trace.top_ops(red, 2)[1] == (
        "custom-call:closed_call.3:tpu_custom_call", pytest.approx(15 * US))


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError):
        trace.clip([("bench.engine_step", 0.0, 1.0)], {})
