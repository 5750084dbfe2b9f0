"""First-device time inside collective ops (all-reduce, reduce-scatter,
all-gather, collective-permute, all-to-all, by op name) per batch.  Sum of
op durations: how much of it is hidden behind compute is not told apart."""


def read(ctx):
    t = ctx["trace"]
    batches = t and t["span_counts"].get("fit_step")
    if not batches or t["devices"] < 2:
        return None
    return t["collective_s_first"] / batches * 1e3
