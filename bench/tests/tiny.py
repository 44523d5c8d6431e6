"""Cells of the benchmark cut to a size that a CPU test can run: the same
files, with the widths, depth, slots and lengths made small; and the paper's
transformer block (LayerNorm, tanh-GELU MLP, multi-head attention) at such
a size, for the reference's tests."""
import copy
import dataclasses
import time

from bench import spec

SMALL = dict(num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
             head_dim=16, intermediate_size=128, vocab_size=256)

# the paper's evaluation transformer (arXiv 2105.14450, Tables 1-2) with
# its widths cut to SMALL
PAPER = dict(SMALL, num_key_value_heads=4, hidden_act="gelu_tanh",
             mlp="plain", norm="layernorm", layer_norm_eps=1e-5,
             qk_norm=False, rope_theta=10000, initializer_range=0.2,
             torch_dtype="bfloat16")


def tiny_cell(name: str, init: float = 0.2, **traffic) -> spec.Cell:
    """The cell ``name`` of BENCHMARK.json cut small.  Weights of std
    ``init`` keep the logits' spread near the full-size model's (about 1 at
    0.02 x sqrt(2560))."""
    base = spec.load_cell(name)
    conf = copy.deepcopy(base.config)
    c = conf["config"]
    gqa = c["num_key_value_heads"] != c["num_attention_heads"]
    c.update(SMALL, num_key_value_heads=2 if gqa else 4,
             initializer_range=init)
    t = copy.deepcopy(base.traffic)
    t.update(slots=4, max_len=64, prefill_chunk=64, rate_per_s=6.0,
             ramp_s=0.3,
             prompt={"median": 12, "sigma": 0.6, "min": 4, "max": 24},
             output={"median": 6, "sigma": 0.5, "min": 2, "max": 12})
    t.update(traffic)
    return dataclasses.replace(base, config=conf, traffic=t)


class CpuHarness:
    """The harness without the look for a chip and without a trace."""

    def __init__(self):
        self.t_start = time.perf_counter()

    def setup_done(self):
        return time.perf_counter() - self.t_start

    def span(self, name):
        import contextlib
        return contextlib.nullcontext()

    def window(self):
        import contextlib
        return contextlib.nullcontext()

    def memory_peak(self):
        return 0
