"""Share of the traced window in which no operation ran on the device:
1 - busy (the union of the operations' intervals) / window."""


def read(ctx):
    t = ctx["trace"]
    if t is None or not t.window_s:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
