"""Rows / (rows + padded rows) over the window, from the slot's exact
counters: how full the batcher's buckets ran."""


def read(ctx):
    slot = ctx["facts"].get("slot")
    if not slot or not slot["rows"]:
        return None
    return 100.0 * slot["rows"] / (slot["rows"] + slot["padded_rows"])
