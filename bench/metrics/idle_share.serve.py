"""Device idle share of the traced window: 1 - the union of device-op
intervals over the window."""


def read(ctx):
    t = ctx["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
