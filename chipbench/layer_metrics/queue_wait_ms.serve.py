"""Mean time a request waited in the batcher's queue over the window, from
``SlotMetrics.queue_wait_us`` (count x mean, after minus before), in ms."""


def read(ctx):
    slot = ctx["facts"].get("slot")
    if not slot or not slot["queue_wait_n"]:
        return None
    return slot["queue_wait_sum_us"] / slot["queue_wait_n"] / 1e3
