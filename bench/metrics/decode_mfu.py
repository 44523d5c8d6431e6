"""decode_mfu (%): the benchmark's operations of the tokens that decode
produced in the window (``flops.decode_flops`` over each token's context
length, counted from the run's own token records) / (device time of the
decode program's runs in the trace x peak bf16 FLOP/s)."""
from bench import flops
from bench.metrics._common import first_device, runs, total


def read(ctx):
    t = total(runs(first_device(ctx), "decode_step"))
    lengths = ctx["counts"]["decode_lengths"]
    if t <= 0 or not lengths:
        return None
    work = flops.decode_flops(ctx["cell"].model, lengths)
    return 100.0 * work / (t * ctx["peak"]["bf16_flops"])
