"""device idle share (%): 1 - union of the device's op intervals / traced
window, mean over the devices."""
from bench import trace


def read(ctx):
    red = ctx["trace"]
    if not red.devices or red.window_s <= 0:
        return None
    busy = [trace.busy_s(d) for d in red.devices.values()]
    return 100.0 * (1.0 - sum(busy) / len(busy) / red.window_s)
