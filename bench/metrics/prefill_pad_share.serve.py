"""prefill_pad_share.serve (%): 1 - tokens / padded positions, summed over
the prefill runs in the window (the ``serve.prepare`` spans that carry
``padded``, the group's rows times its padding bucket): the share of
prefill positions that are padding."""
from bench.metrics import _program


def read(ctx):
    runs = [s.args for s in _program.named(_program.spans(ctx),
                                           "serve.prepare")
            if "padded" in s.args]
    padded = sum(a["padded"] for a in runs)
    if not padded:
        return None
    return 100.0 * (1.0 - sum(a["tokens"] for a in runs) / padded)
