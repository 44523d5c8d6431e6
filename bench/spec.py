"""What a cell is: its entry in BENCHMARK.json, the configuration file, the
traffic file and the file of limits, found by name.

    bench/configs/<config>.json   the model as it is run (published keys)
    bench/traffic/<traffic>.json  the driver it uses and its parameters
    bench/limits/<workload>.json  the limits that decide `correct`

Nothing here imports JAX or the program, so the files can be read and
checked before any device is touched.
"""
from __future__ import annotations

import dataclasses
import json
import os

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict            # the config file: source, config, reduced, ...
    traffic_name: str
    traffic: dict           # the traffic file: driver + parameters
    limits: dict            # the limits file
    end_to_end: tuple       # BENCHMARK.json metric entries this cell reports
    per_layer: tuple

    @property
    def model(self) -> dict:
        return self.config["config"]


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    confs = {c["name"]: c for c in bench["configs"]}
    conf = confs[w["config"]]
    bench_dir = os.path.join(root, "bench")
    return Cell(
        name=name, chips=int(w["chips"]),
        config_name=w["config"],
        config=load_json(os.path.join(root, conf["file"])),
        traffic_name=w["traffic"],
        traffic=load_json(os.path.join(bench_dir, "traffic",
                                       w["traffic"] + ".json")),
        limits=load_json(os.path.join(bench_dir, "limits", name + ".json")),
        end_to_end=tuple(m for m in bench["end_to_end"] if _reports(m, name)),
        per_layer=tuple(m for m in bench["per_layer"] if _reports(m, name)))


def model_config(conf: dict, name: str):
    """The program's ModelConfig for a dense decoder configuration file."""
    from repro.config import Family, ModelConfig
    c = conf["config"]
    act = c.get("hidden_act", "silu")
    if c.get("mlp", "gated") == "plain":
        if act != "gelu_tanh":
            raise ValueError(f"{name}: a plain MLP runs tanh-GELU only")
        act = "gelu_mlp"
    elif act != "silu":
        raise ValueError(f"{name}: a gated MLP runs SiLU only, not {act}")
    norm = c.get("norm", "rmsnorm")
    return ModelConfig(
        arch=name, family=Family.DENSE,
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"], n_kv=c["num_key_value_heads"],
        d_head=c["head_dim"], d_ff=c["intermediate_size"],
        vocab=c["vocab_size"], act=act,
        qk_norm=c.get("qk_norm", c.get("model_type") == "qwen3"),
        norm=norm, rope_base=float(c["rope_theta"]),
        tie_embeddings=bool(c.get("tie_word_embeddings", False)),
        dtype=c.get("torch_dtype", "bfloat16"), source=conf["source"])
