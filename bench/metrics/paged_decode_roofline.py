"""paged_decode_roofline (%): the kernel's least time / its device time.

Least time = max(operations / peak FLOP/s, bytes / HBM bytes/s) over the
live tokens that each token decode produced in the window attends to
(``flops.paged_attention`` over the context lengths the run records); at
these lengths the bytes bound it.  Device
time = the summed durations of the Pallas kernels' ops (custom calls to
``tpu_custom_call``) inside the decode program's runs: the paged-decode
kernel is the only Pallas kernel that program holds.
"""
from bench import flops, trace
from bench.metrics._common import first_device, runs

KERNEL = "tpu_custom_call"


def kernel_s(dev) -> float:
    dec = trace.union(runs(dev, "decode_step"))
    ops = [(s, e) for n, s, e in dev.ops if KERNEL in n]
    return sum(trace.overlap(iv, dec) for iv in ops)


def read(ctx):
    t = kernel_s(first_device(ctx))
    lengths = ctx["counts"]["decode_lengths"]
    if t <= 0 or not lengths:
        return None
    ops, nbytes = flops.paged_attention(ctx["cell"].model, lengths)
    least, _ = flops.least_time(ops, nbytes, ctx["peak"])
    return 100.0 * least / t
