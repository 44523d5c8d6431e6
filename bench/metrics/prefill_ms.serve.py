"""prefill_ms.serve (ms): mean device time of one run of the engine's
prefill program (``jit_prefill_step``) in the traced window."""
from bench.metrics._common import first_device, runs


def read(ctx):
    r = runs(first_device(ctx), "prefill_step")
    if not r:
        return None
    return 1e3 * sum(e - s for s, e in r) / len(r)
