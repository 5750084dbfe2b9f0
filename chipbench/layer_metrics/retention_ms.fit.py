"""First-device time a step inside the power-retention forward and
backward ops (the union of the device events the configuration's
``trace_patterns`` name, summed by ``chipbench/kernel_time.py`` and
handed over by the runner) / steps in the traced window.  The forward
that recomputation repeats is in the time."""


def read(ctx):
    t = ctx["trace"]
    batches = t and t["span_counts"].get("fit_step")
    kernel = (ctx["facts"].get("kernel_s") or {}).get("retention")
    if not batches or not kernel:
        return None
    return kernel["seconds"] / batches * 1e3
