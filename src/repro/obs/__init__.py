"""Unified observability layer shared by train, serve, and benchmarks.

  * ``trace``     — span tracer (Chrome-trace / JSONL export, jax.profiler
                    annotations, strict no-op when disabled, a
                    profiler-only mode) and the compile counter
  * ``telemetry`` — per-step train telemetry (step time, tokens/s, MFU,
                    memory watermarks, non-finite sentinel)
  * ``commcheck`` — measured-vs-analytic collective-bytes report per plan

docs/observability.md is the user-facing guide.
"""
from .trace import (COMPILES, NULL, PROFILE, NullTracer,  # noqa: F401
                    ProfileTracer, Tracer, make_tracer)
