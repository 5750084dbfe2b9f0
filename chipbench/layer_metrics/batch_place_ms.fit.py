"""Median duration per batch of ``module_step_place_batch`` (the program's
own span around the placement of data and label on the executor's devices;
on the fused SPMD group the ``device_put`` over the mesh), in ms."""
from chipbench import program_spans


def per_batch(spans):
    place = program_spans.first(spans, "module_step_place_batch")
    return None if place is None else place[1]


def read(ctx):
    return program_spans.median_ms(per_batch)
