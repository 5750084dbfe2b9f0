"""Median host time inside ``Module._fit_step`` per batch of the window
(the runner's span around the bound method), in ms."""
import statistics


def read(ctx):
    spans = ctx["facts"].get("fit_step_s")
    return statistics.median(spans) * 1e3 if spans else None
