"""Share of the traced window in which no op ran on the device, in %:
1 - (union of device op intervals) / window, averaged over the chips."""


def read(ctx):
    t = ctx["trace"]
    if t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
