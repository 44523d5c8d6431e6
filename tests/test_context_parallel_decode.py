"""Context-parallel decode (the long_500k path): the KV cache sharded over
('dp', z) with psum-combined softmax must produce identical logits to the
single-device decode — verified in an 8-device subprocess."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = r"""
import jax, jax.numpy as jnp
import dataclasses
from repro.config import reduced
from repro.configs.registry import get
from repro.core.topology import single_device_layout, make_layout
from repro.core.params import init_params
from repro.models import transformer

assert len(jax.devices()) == 8
failures = []
for arch in ("mixtral-8x7b", "zamba2-1.2b", "xlstm-350m"):
    cfg = reduced(get(arch))
    lay1 = single_device_layout("3d")
    # long_500k-style layout: batch unsharded, cache over ('dp', z)
    layc = make_layout(1, 2, 4, "3d", cube=(1, 1, 4),
                       batch_axes=(), seq_axes=("dp",))
    # host copy of the single-device init: each layout's jit places it
    params = jax.device_get(transformer.init(cfg, lay1, jax.random.key(0)))
    T, B, L = 6, 1, 64
    toks = jax.random.randint(jax.random.key(7), (B, T), 0, cfg.vocab)

    def roll(lay):
        cache = init_params(transformer.abstract_cache(cfg, lay, B, L),
                            jax.random.key(1))
        dec = jax.jit(lambda p, b, c: transformer.forward(
            cfg, lay, p, b, mode="decode", cache=c))
        outs = []
        for t in range(T):
            batch = {"token": toks[:, t:t+1],
                     "pos": jnp.full((B,), t, jnp.int32)}
            logits, cache = dec(params, batch, cache)
            import numpy as np
            outs.append(np.asarray(jax.device_get(logits), np.float32))
        import numpy as np
        return np.stack(outs)

    ref = roll(lay1)
    got = roll(layc)
    import numpy as np
    err = float(np.max(np.abs(ref - got)))
    argmax_ok = bool((ref.argmax(-1) == got.argmax(-1)).all())
    # bf16 logits: absolute tolerance ~1e-1; greedy decisions must agree
    if err > 1.5e-1 or not argmax_ok:
        failures.append(f"{arch}: err={err} argmax_ok={argmax_ok}")
    print(arch, "err", err, "argmax_ok", argmax_ok)

if failures:
    print("FAILURES:", failures)
    raise SystemExit(1)
print("ALL-OK")
"""


@pytest.mark.slow
def test_context_parallel_decode():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(ROOT, "src"))
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=3000)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-2000:]
    assert "ALL-OK" in proc.stdout
