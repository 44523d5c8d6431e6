"""decode_walk_share.serve (%): walked / blocks, summed over the decode
runs in the window (the ``serve.prepare`` spans that carry ``walked``,
the table blocks the paged-decode kernel visits with each row stopped at
its last live block, and ``blocks``, the B x table blocks it spans): the
share of the table the kernel walks."""
from bench.metrics import _program


def read(ctx):
    runs = [s.args for s in _program.named(_program.spans(ctx),
                                           "serve.prepare")
            if "walked" in s.args and "blocks" in s.args]
    blocks = sum(a["blocks"] for a in runs)
    if not blocks:
        return None
    return 100.0 * sum(a["walked"] for a in runs) / blocks
