"""Device busy time per batch: union of device-op intervals of the first
device in the traced window / batches in it (the window is cut at batch-end
callbacks, where the device is drained, so it holds whole batches)."""


def read(ctx):
    t = ctx["trace"]
    batches = t and t["span_counts"].get("fit_step")
    return t["busy_s_first"] / batches * 1e3 if batches else None
