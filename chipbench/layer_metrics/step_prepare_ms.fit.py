"""Median host time per batch from the start of ``module_train_step`` to
the start of ``module_step_enqueue`` (the program's own spans,
``mxnet_tpu/module/cached_step.py``): feed, placement, optimizer
bookkeeping and the per-step key programs — everything the host does
before the step program can start — in ms."""
from chipbench import program_spans


def per_batch(spans):
    step = program_spans.first(spans, "module_train_step")
    enqueue = program_spans.first(spans, "module_step_enqueue")
    if step is None or enqueue is None:
        return None
    return enqueue[0] - step[0]


def read(ctx):
    return program_spans.median_ms(per_batch)
