"""host_gap_ms.serve (ms): mean device-idle gap between consecutive runs of
the engine's programs in the window, leaving out gaps in which the open
loop waited for the next arrival (the engine had nothing to do).  The
``breakdown`` names the host span under each of the longest gaps."""
from bench import trace
from bench.metrics._common import first_device


def read(ctx):
    dev = first_device(ctx)
    u = trace.union(dev.modules)
    waits = [(s, e) for n, s, e in ctx["trace"].host
             if n == "bench.wait_arrival"]
    gaps = [b[0] - a[1] for a, b in zip(u, u[1:])
            if not any(s <= 0.5 * (a[1] + b[0]) <= e for s, e in waits)]
    if not gaps:
        return None
    return 1e3 * sum(gaps) / len(gaps)
