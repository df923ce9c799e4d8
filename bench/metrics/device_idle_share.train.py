"""Share of the traced training window in which no op ran on the chip,
%: 1 - busy / window, busy the union of the device's op intervals."""


def read(ctx):
    t = ctx["trace"]
    if t is None or not any(t.ops) or ctx["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s() / ctx["window_s"])
