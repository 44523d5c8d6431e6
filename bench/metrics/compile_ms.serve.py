"""compile_ms.serve (ms): compile time inside the window.  The engine's
``serve.step`` carries the process's compile seconds at its entry
(``compile_s``); the reading is that of the last step less that of the
first, plus the backend compiles (``backend_compile_and_load``) that
started inside the last step.  After the warm-up it reads 0; above 0 it
names a recompile under traffic."""
from bench.metrics import _program


def read(ctx):
    sp = _program.spans(ctx)
    steps = [s for s in _program.named(sp, "serve.step")
             if "compile_s" in s.args]
    if not steps:
        return None
    first, last = steps[0], steps[-1]
    late = sum(c.dur for c in _program.named(sp, _program.COMPILE)
               if last.start <= c.start <= last.end)
    return 1e3 * (last.args["compile_s"] - first.args["compile_s"] + late)
