"""1 - busy / traced window on the first device, from the device trace."""


def read(ctx):
    t = ctx["trace"]
    return 100.0 * (1.0 - t["busy_s_first"] / t["window_s"]) if t else None
