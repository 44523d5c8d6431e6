"""A configuration, a traffic mix and a per-layer metric are added as files
and entries alone: the harness finds each by its name in BENCHMARK.json,
and no file that was there changes."""
import hashlib
import json
import os
import shutil
import subprocess
import sys

from bench import spec


def _digests(root):
    out = {}
    for d, _, files in os.walk(os.path.join(root, "bench")):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_new_cell_found_by_name(tmp_path):
    root = str(tmp_path)
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(spec.ROOT, "bench"),
                    os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _digests(root)

    b = os.path.join(root, "bench")
    with open(os.path.join(b, "configs", "toy-model.json"), "w") as f:
        json.dump({"source": "https://example.org/toy",
                   "config": {"hidden_size": 8}}, f)
    with open(os.path.join(b, "traffic", "toy.mix.json"), "w") as f:
        json.dump({"driver": "serve_open_loop", "rate_per_s": 1.0}, f)
    with open(os.path.join(b, "limits", "toy-model.toy.mix.json"), "w") as f:
        json.dump({"served_logit_gap": {"limit": 0.5}}, f)
    with open(os.path.join(b, "metrics", "toy_share.py"), "w") as f:
        f.write("def read(ctx):\n    return 2.0 * ctx['counts']['x']\n")
    with open(os.path.join(b, "metrics", "toy_silent.py"), "w") as f:
        f.write("def read(ctx):\n    return None\n")
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "toy-model", "source": "x",
                             "file": "bench/configs/toy-model.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "toy-model.toy.mix",
                               "config": "toy-model", "traffic": "toy.mix",
                               "chips": 1, "why": "x"})
    for name in ("toy_share", "toy_silent"):
        bench["per_layer"].append({"name": name, "unit": "%",
                                   "better": "higher", "source": "device_trace",
                                   "layer": "toy", "moves": "setup_s",
                                   "workloads": ["toy-model.toy.mix"]})
    with open(path, "w") as f:
        json.dump(bench, f)

    after = _digests(root)
    assert all(after[k] == v for k, v in before.items())

    cell = spec.load_cell("toy-model.toy.mix", root)
    assert cell.config["config"] == {"hidden_size": 8}
    assert cell.traffic["rate_per_s"] == 1.0
    assert cell.limits["served_logit_gap"]["limit"] == 0.5
    assert [m["name"] for m in cell.per_layer] == ["toy_share", "toy_silent"]
    assert [m["name"] for m in cell.end_to_end] == ["setup_s"]

    from bench import run as harness
    got = harness.per_layer(cell, {"counts": {"x": 3}}, bench_dir=b)
    # a reader that finds nothing leaves its metric out of the line
    assert got == {"toy_share": {"value": 6.0, "unit": "%"}}


def test_no_tpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(spec.ROOT, "bench", "run.py"),
         "--workload", "qwen3-4b.serve.chat", "--seed", "3", "--seconds",
         "1", "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=120, cwd=spec.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr
