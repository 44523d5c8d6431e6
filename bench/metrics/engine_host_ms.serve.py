"""engine_host_ms.serve (ms): mean over the engine's steps in the window
(``serve.step`` spans) of the step's time less its ``serve.wait``: the
host time a step spent not waiting on the device."""
from bench.metrics import _program


def read(ctx):
    sp = _program.spans(ctx)
    steps = _program.named(sp, "serve.step")
    if not steps:
        return None
    waits = _program.named(sp, "serve.wait")
    host = [s.dur - sum(w.dur for w in _program.inside(s, waits))
            for s in steps]
    return 1e3 * sum(host) / len(host)
