"""Median duration per batch of ``module_step_enqueue`` (the program's own
span around the fused step's jitted call: flatten, transfer of the
hyper-parameters, launch), in ms."""
from chipbench import program_spans


def per_batch(spans):
    enqueue = program_spans.first(spans, "module_step_enqueue")
    return None if enqueue is None else enqueue[1]


def read(ctx):
    return program_spans.median_ms(per_batch)
