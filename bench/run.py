"""The on-chip benchmark: one run of one cell.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``; its configuration, traffic and
limits are files under ``bench/`` found by name (``bench/spec.py``).  The
traffic file names the driver (``bench/drivers/<driver>.py``), which makes
the weights from the seed, warms up, measures for ``--seconds`` and checks
what the timed path produced against the float32 reference.

With ``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` the window runs under ``jax.profiler`` and the result carries
the per-layer metrics, each read by ``bench/metrics/<metric>.py`` from the
reduced trace and the run's counts.

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` and, when tracing, ``breakdown``; its
last key, ``checked``, gives each number compared with its limit.  The same
numbers are the last lines of stderr.  Without a TPU, or with fewer chips
than the cell asks for, it exits nonzero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import spec  # noqa: E402

CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, "bench_out", "trace")


def fail(msg: str):
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    sys.exit(2)


def device_check(chips: int) -> dict:
    """The device as JAX reports it; exits nonzero unless it is a TPU with
    at least ``chips`` chips.  Never falls back to the CPU."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        fail(f"JAX found no accelerator: {e}")
    if devs[0].platform != "tpu":
        fail(f"needs a TPU, JAX found platform {devs[0].platform!r}")
    if len(devs) < chips:
        fail(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


class Harness:
    """What a driver gets from the harness: the set-up clock, spans, the
    traced window and the memory reading."""

    def __init__(self, trace: bool, chips: int):
        self.trace, self.chips = trace, chips
        self.trace_path = None

    def setup_done(self) -> float:
        return time.perf_counter() - T_START

    def span(self, name: str):
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    @contextlib.contextmanager
    def window(self):
        if not self.trace:
            yield
            return
        import jax
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                yield
        finally:
            jax.profiler.stop_trace()
        from bench import trace
        self.trace_path = trace.find_xplane(TRACE_DIR)

    def memory_peak(self) -> int:
        import jax
        stats = [d.memory_stats() or {} for d in jax.devices()[:self.chips]]
        return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


def load_metric(name: str, bench_dir: str = HERE):
    """The reader ``bench/metrics/<name>.py``, found by the metric's name."""
    path = os.path.join(bench_dir, "metrics", name + ".py")
    sp = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    return mod


def per_layer(cell: spec.Cell, ctx: dict, bench_dir: str = HERE) -> dict:
    """Each per-layer metric of the cell that its reader finds."""
    out = {}
    for m in cell.per_layer:
        v = load_metric(m["name"], bench_dir).read(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def traced_context(cell: spec.Cell, res: dict, harness: Harness) -> dict:
    """What the per-layer readers get: the reduced trace, the run's counts,
    the cell and the chip's peaks."""
    from bench import trace
    from bench.peaks import peak_for
    red = trace.reduce(harness.trace_path)
    return {"trace": red, "counts": res["counts"], "cell": cell,
            "peak": peak_for(res["device"]["kind"])}


def checked_lines(check: dict) -> list:
    return [f"check {name}: {v['value']!r} limit {v['limit']!r}"
            for name, v in check["numbers"].items()]


def run(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    device = device_check(cell.chips)
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE_DIR)
    harness = Harness(bool(args.trace), cell.chips)
    driver = importlib.import_module(f"bench.drivers.{cell.traffic['driver']}")
    res = driver.run(cell, args.seed, args.seconds, harness)
    res["device"] = dict(device, memory_peak_bytes=res["memory"])
    out = {"correct": bool(res["check"]["correct"]),
           "attempted": int(res["attempted"]), "failed": int(res["failed"])}
    if args.trace:
        t_reduce = time.perf_counter()
        ctx = traced_context(cell, res, harness)
        print(f"trace reduced in {time.perf_counter() - t_reduce:.1f} s",
              file=sys.stderr)
        red = ctx["trace"]
        from bench import trace
        busy = [trace.busy_s(d) for d in red.devices.values()]
        res["device"]["busy_s"] = sum(busy) / max(len(busy), 1)
        res["device"]["window_s"] = red.window_s
        out["metrics"] = per_layer(cell, ctx)
        out["breakdown"] = {"device_ops": [list(x) for x in trace.top_ops(red)],
                            "idle_gaps": [list(x)
                                          for x in trace.idle_by_host(red)]}
    else:
        names = {m["name"]: m["unit"] for m in cell.end_to_end}
        out["metrics"] = {k: {"value": v, "unit": names[k]}
                          for k, v in res["metrics"].items() if k in names}
    out["device"] = res["device"]
    out["checked"] = {k: {"value": v["value"], "limit": v["limit"]}
                      for k, v in res["check"]["numbers"].items()}
    summary = {k: (len(v) if isinstance(v, list) else v)
               for k, v in res["counts"].items()}
    print(f"counts: {summary}", file=sys.stderr)
    print(f"metrics: {res['metrics']}", file=sys.stderr)
    print(f"reference check took {res['check'].get('seconds', 0):.1f} s",
          file=sys.stderr)
    if res["check"].get("leaves_off"):
        print(f"weights off the configuration: {res['check']['leaves_off']}",
              file=sys.stderr)
    for line in checked_lines(res["check"]):
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return out


if __name__ == "__main__":
    run()
