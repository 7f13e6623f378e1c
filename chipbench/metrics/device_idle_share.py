"""Device: the share of the traced window in which no operation ran on the
chip, in percent, from the profiler's trace."""


def read(ctx):
    t = ctx["trace"]
    if not t:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
