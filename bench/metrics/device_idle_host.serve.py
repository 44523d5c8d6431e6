"""device_idle_host.serve (%): share of the window in which the device ran
no op while the engine's host code ran (``serve.admit``,
``serve.prepare``, ``serve.dispatch``, ``serve.emit``): the part of
``device_idle.serve`` that the engine's host code caused.  Mean over the
devices."""
from bench import trace
from bench.metrics import _program

HOST = ("serve.admit", "serve.prepare", "serve.dispatch", "serve.emit")


def idle_under(host, ops) -> float:
    """Length of the union of the ``host`` intervals that no op covers."""
    busy = trace.union(ops)
    idle, j = 0.0, 0
    for s, e in trace.union(host):
        while j < len(busy) and busy[j][1] <= s:
            j += 1
        covered, k = 0.0, j
        while k < len(busy) and busy[k][0] < e:
            covered += min(e, busy[k][1]) - max(s, busy[k][0])
            k += 1
        idle += (e - s) - covered
    return idle


def read(ctx):
    red = ctx["trace"]
    host = [(s.start, s.end) for s in _program.spans(ctx) if s.name in HOST]
    if not host or not red.devices or red.window_s <= 0:
        return None
    idle = [idle_under(host, d.ops) for d in red.devices.values()]
    return 100.0 * sum(idle) / len(idle) / red.window_s
