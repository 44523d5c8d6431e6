"""Low-overhead span tracer shared by train, serve, and benchmarks.

One event schema everywhere (the JSONL log is the source of truth; the
Chrome-trace JSON is a view of the same events):

  * span     — {"ev": "span", "name", "track", "ts", "dur", "args"?}
  * instant  — {"ev": "instant", "name", "track", "ts", "args"?}
  * counter  — {"ev": "counter", "name", "track", "ts", "value"}

Timestamps are seconds relative to tracer construction (``perf_counter``
based); a ``track`` is a horizontal lane in the viewer — the train loop
uses ``"train"``, the serve engine ``"engine"`` plus one ``"req<uid>"``
lane per request, so a serve trace reads as a swimlane diagram of the
request lifecycle.

Design constraints (the reason this is not a logging wrapper):

  * strict no-op when disabled: ``NULL`` is a :class:`NullTracer` whose
    ``span()`` returns a shared singleton context manager — no allocation,
    no clock read, no branch in the caller.  Pass a tracer everywhere and
    default it to ``NULL``; never ``if tracer is not None`` in hot paths.
  * no implicit device syncs: jax dispatch is async, so a span around a
    jitted call measures *dispatch* unless the caller opts in.  Either call
    ``span.sync(value)`` before exit (blocks on that value and attributes
    the wait to the span) or time at natural sync points (``device_get``,
    printing a loss).
  * spans nest by construction (enter/exit discipline) and survive
    exceptions: a span whose body raises is still emitted, tagged with
    ``error=<ExceptionType>``.
  * ``annotate=True`` (default) additionally wraps each span in
    ``jax.profiler.TraceAnnotation``, with the span's entry args, so the
    same names and args land inside XLA profiles when one is being
    captured.
  * profiler-only mode: ``PROFILE`` is a :class:`ProfileTracer` whose
    ``span()`` is that ``TraceAnnotation`` and nothing else — no clock
    read, no event kept in Python.  The profiler records the span only
    while a trace is captured, on the host plane of the same session as
    the device ops, so it shares the device trace's clock.  A server that
    never stops runs with it (the serve engine's default).
  * ``COMPILES`` counts backend compiles and persistent-cache loads (one
    ``jax.monitoring`` listener), process-wide, for spans to carry.

Export: ``write_jsonl(path)`` and ``write_chrome(path)``; the Chrome file
loads in ``chrome://tracing`` / Perfetto (``ph:"X"`` complete events, one
tid per track, thread-name metadata).  ``tools/check_trace.py`` validates
both formats.
"""
from __future__ import annotations

import json
import threading
import time
from typing import Callable, Dict, List, Optional


class _NullSpan:
    """Shared do-nothing context manager returned by the disabled tracer."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **args):
        pass

    def sync(self, value):
        return value


_NULL_SPAN = _NullSpan()


class NullTracer:
    """Disabled tracer: every method is a no-op, ``span`` hands back one
    shared singleton.  The hot-path cost of passing this around is a method
    call returning a constant — nothing is recorded, timed, or allocated."""
    enabled = False
    events: tuple = ()

    def span(self, name, track="main", annotate=None, **args):
        return _NULL_SPAN

    def instant(self, name, track="main", **args):
        pass

    def counter(self, name, value, track="main"):
        pass

    def span_at(self, name, t0, t1, track="main", **args):
        pass

    def now(self) -> float:
        return 0.0

    def rel(self, t_abs: float) -> float:
        return 0.0

    def write_jsonl(self, path):
        pass

    def write_chrome(self, path):
        pass


NULL = NullTracer()


class ProfileTracer(NullTracer):
    """Profiler-only tracer: ``span`` is ``jax.profiler.TraceAnnotation``
    with the span's args and does nothing else; every other method is the
    disabled tracer's no-op.  With no trace being captured a span costs
    the annotation's construction and two calls."""

    _annotation = None

    def span(self, name, track="main", annotate=None, **args):
        if self._annotation is None:      # jax is imported on first use
            from jax.profiler import TraceAnnotation
            self._annotation = TraceAnnotation
        return self._annotation(name, **args)


PROFILE = ProfileTracer()


class _Span:
    __slots__ = ("_tr", "name", "track", "args", "t0", "_ann")

    def __init__(self, tracer: "Tracer", name: str, track: str,
                 annotate: bool, args: dict):
        self._tr = tracer
        self.name = name
        self.track = track
        self.args = args
        self.t0 = 0.0
        self._ann = tracer._annotation(name, args) if annotate else None

    def __enter__(self):
        if self._ann is not None:
            self._ann.__enter__()
        self.t0 = self._tr.now()
        return self

    def set(self, **args):
        """Attach extra args to the span (merged at exit)."""
        self.args.update(args)
        return self

    def sync(self, value):
        """Opt-in sync point: block until ``value`` is ready so the span
        covers device time, not just dispatch.  Returns ``value``."""
        import jax
        jax.block_until_ready(value)
        return value

    def __exit__(self, exc_type, exc, tb):
        t1 = self._tr.now()
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        if exc_type is not None:
            self.args["error"] = exc_type.__name__
        ev = {"ev": "span", "name": self.name, "track": self.track,
              "ts": self.t0, "dur": t1 - self.t0}
        if self.args:
            ev["args"] = self.args
        self._tr._emit(ev)
        return False


class Tracer:
    """Recording tracer.  Thread-safe appends; host-side only (events live
    in a python list until exported)."""

    enabled = True

    def __init__(self, clock: Callable[[], float] = time.perf_counter,
                 annotate: bool = True):
        self._clock = clock
        self._t0 = clock()
        self.events: List[dict] = []
        self._lock = threading.Lock()
        self.annotate = annotate
        self._ann_cls = None
        if annotate:
            try:
                from jax.profiler import TraceAnnotation
                self._ann_cls = TraceAnnotation
            except Exception:        # jax-free host use stays valid
                self._ann_cls = None

    # -- clock ---------------------------------------------------------------
    def now(self) -> float:
        """Seconds since tracer construction (the event timebase)."""
        return self._clock() - self._t0

    def rel(self, t_abs: float) -> float:
        """Convert an absolute stamp of the *same* clock into the event
        timebase (for retroactive ``span_at`` from timestamps recorded
        outside the tracer, e.g. serve/metrics.py request stamps)."""
        return t_abs - self._t0

    def _annotation(self, name, args):
        if self._ann_cls is None:
            return None
        return self._ann_cls(name, **args)

    def _emit(self, ev: dict):
        with self._lock:
            self.events.append(ev)

    # -- recording API -------------------------------------------------------
    def span(self, name: str, track: str = "main",
             annotate: Optional[bool] = None, **args) -> _Span:
        """Context manager timing its body.  ``with tracer.span("step"):``"""
        ann = self.annotate if annotate is None else annotate
        return _Span(self, name, track, ann, args)

    def instant(self, name: str, track: str = "main", **args):
        ev = {"ev": "instant", "name": name, "track": track, "ts": self.now()}
        if args:
            ev["args"] = args
        self._emit(ev)

    def counter(self, name: str, value: float, track: str = "main"):
        self._emit({"ev": "counter", "name": name, "track": track,
                    "ts": self.now(), "value": float(value)})

    def span_at(self, name: str, t0: float, t1: float, track: str = "main",
                **args):
        """Retroactive span from recorded timestamps (tracer timebase, i.e.
        values of ``now()``).  The serve engine uses this to emit
        queue/prefill/decode phases at finish time from per-request stamps
        instead of holding a context manager open across engine steps."""
        ev = {"ev": "span", "name": name, "track": track,
              "ts": float(t0), "dur": max(float(t1) - float(t0), 0.0)}
        if args:
            ev["args"] = args
        self._emit(ev)

    # -- export --------------------------------------------------------------
    def write_jsonl(self, path: str):
        with open(path, "w") as f:
            for ev in self.events:
                f.write(json.dumps(ev) + "\n")

    def chrome_trace(self) -> dict:
        """Events as a Chrome-trace/Perfetto document (ts/dur in us)."""
        tids: Dict[str, int] = {}
        out = []
        for ev in self.events:
            track = ev["track"]
            if track not in tids:
                tid = tids[track] = len(tids)
                out.append({"ph": "M", "name": "thread_name", "pid": 0,
                            "tid": tid, "args": {"name": track}})
            tid = tids[track]
            base = {"name": ev["name"], "pid": 0, "tid": tid,
                    "ts": ev["ts"] * 1e6}
            if ev["ev"] == "span":
                base.update(ph="X", dur=ev["dur"] * 1e6)
                if "args" in ev:
                    base["args"] = ev["args"]
            elif ev["ev"] == "instant":
                base.update(ph="i", s="t")
                if "args" in ev:
                    base["args"] = ev["args"]
            else:                    # counter
                base.update(ph="C", args={ev["name"]: ev["value"]})
            out.append(base)
        return {"traceEvents": out, "displayTimeUnit": "ms"}

    def write_chrome(self, path: str):
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)


def make_tracer(enabled: bool, **kw):
    """``Tracer(**kw)`` when enabled, the shared ``NULL`` otherwise."""
    return Tracer(**kw) if enabled else NULL


COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    """Count and summed seconds of the process's backend compiles.

    JAX records ``COMPILE_EVENT`` around ``compile_or_get_cached``, so a
    persistent-cache load counts as well as a compile.  ``watch()``
    registers the one listener (idempotent); ``n`` and ``s`` only grow.
    A server that compiles under traffic shows it here."""

    def __init__(self):
        self.n = 0
        self.s = 0.0
        self._lock = threading.Lock()
        self._watching = False

    def _on_duration(self, event: str, secs: float, **_):
        if event == COMPILE_EVENT:
            with self._lock:
                self.n += 1
                self.s += secs

    def watch(self) -> "CompileCounter":
        with self._lock:
            if not self._watching:
                import jax.monitoring
                jax.monitoring.register_event_duration_secs_listener(
                    self._on_duration)
                self._watching = True
        return self


COMPILES = CompileCounter()
