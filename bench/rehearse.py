"""Compile each cell's programs at their real size for a described TPU v5e,
without the chip, and print what each device would hold.

    JAX_PLATFORMS=cpu python bench/rehearse.py qwen3-4b.serve.chat

For a serve cell it compiles the decode program and the prefill program of
every padding bucket the traffic can reach.  Each line gives ``memory_analysis()`` per device: arguments, outputs,
temporaries, and the sum against the chip's memory.  Nothing runs, so this
says nothing about times or results.  It is a script and not a test: a
whole-step compile at these sizes takes up to a minute.
"""
from __future__ import annotations

import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), os.path.join(os.path.dirname(HERE),
                                                    "src")]


def _memory(compiled) -> dict:
    m = compiled.memory_analysis()
    out = {k: getattr(m, k + "_size_in_bytes", 0)
           for k in ("argument", "output", "temp", "alias", "generated_code")}
    out["total"] = (out["argument"] + out["output"] + out["temp"]
                    - out["alias"] + out["generated_code"])
    return out


def _show(label: str, compiled, hbm: float):
    m = _memory(compiled)
    gb = {k: round(v / 1e9, 3) for k, v in m.items()}
    print(f"{label}: {gb} GB per device; {100 * m['total'] / hbm:.1f}% of "
          f"{hbm / 1e9:.1f} GB; pallas={'tpu_custom_call' in compiled.as_text()}",
          flush=True)
    return m


def engine_shell(cfg, layout, mix: dict):
    """The engine's compiled programs without any device array: for the
    compile rehearsal on a described chip."""
    import jax.numpy as jnp
    from repro.serve import kvcache, sampling
    from repro.serve.engine import Engine
    eng = Engine.__new__(Engine)
    eng.cfg, eng.layout = cfg, layout
    eng.B, eng.max_len = mix["slots"], mix["max_len"]
    eng.fused = True
    eng.sampler = sampling.make_sampler(mix["temperature"], 0, 0.0)
    eng.kv = kvcache.PagedKVCache(cfg, layout, eng.B, eng.max_len,
                                  block=mix["block"], dtype=jnp.bfloat16)
    eng._build_paged()
    return eng


def rehearse_serve(cell, devices, hbm):
    import jax
    import jax.numpy as jnp
    from repro.core.params import abstract_arrays
    from repro.kernels import paged_decode
    from repro.models import transformer
    from bench.drivers import serve_open_loop as drv
    from bench import spec

    paged_decode.set_default_impl("pallas", interpret=False)
    cfg = spec.model_config(cell.config, cell.config_name)
    t = cell.traffic
    layout = drv.make_layout(1, devices)
    eng = engine_shell(cfg, layout, t)
    params = abstract_arrays(transformer.abstract_params(cfg, layout), layout)
    pool = abstract_arrays(eng.kv._abstract_pool, layout)
    rep = jax.sharding.NamedSharding(layout.mesh, jax.sharding.PartitionSpec())

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=rep)

    B, nb = eng.B, eng.kv.blocks_per_slot
    key = jax.eval_shape(lambda: jax.random.key(0))
    key = jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=rep)
    c = eng._decode.lower(params, pool, sds((B, 1), jnp.int32),
                          sds((B,), jnp.int32), sds((B, nb), jnp.int32),
                          sds((B,), jnp.bool_), key).compile()
    _show("decode", c, hbm)
    for s_pad in drv.prefill_buckets(t):
        c = eng._prefill.lower(params, pool, sds((B, s_pad), jnp.int32),
                               sds((B,), jnp.int32),
                               sds((B, s_pad), jnp.int64), key).compile()
        _show(f"prefill s_pad={s_pad}", c, hbm)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("workload", help="a cell of BENCHMARK.json")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from bench import spec
    from bench.peaks import peak_for
    jax.config.update("jax_enable_compilation_cache", False)
    cell = spec.load_cell(args.workload)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    devices = topo.devices[:cell.chips]
    hbm = peak_for(devices[0].device_kind)["hbm_bytes"]
    if cell.traffic["driver"] != "serve_open_loop":
        raise SystemExit(f"no rehearsal for driver {cell.traffic['driver']!r}")
    rehearse_serve(cell, devices, hbm)


if __name__ == "__main__":
    main()
