"""Median per batch of ``fit_update_metric`` minus the ``metric_wait``
spans inside it (the program's own spans, ``module/base_module.py`` and
``metric.py``): the device-to-host copy and the host's arithmetic, during
which the device has nothing queued — the wait before them is overlap
with the running step — in ms."""
from chipbench import program_spans


def per_batch(spans):
    update = program_spans.first(spans, "fit_update_metric")
    if update is None:
        return None
    return update[1] - sum(dur for _, dur in spans.get("metric_wait", ()))


def read(ctx):
    return program_spans.median_ms(per_batch)
