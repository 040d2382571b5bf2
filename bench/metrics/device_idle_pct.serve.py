"""Share of the traced window in which no operation ran on the device
(profiler trace; busy time is the union of the device's operation
intervals inside the window span)."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
