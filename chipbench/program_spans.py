"""The program's own host spans, for the per-layer readers that time the
Module trainer from inside (``source: program_span``).

``mxnet_tpu.telemetry.span`` keeps every span it records in a ring (name,
start and duration in microseconds on one host clock, parent, and the id of
the batch it belongs to) and records whenever a JAX profiler session is
open.  A reader runs in the program's process after the window, so with
telemetry at its default the ring holds exactly the batches of the traced
window.  A program that has no such spans (the parent of the PR that added
them) leaves the ring empty: every reader then returns None.
"""
import collections
import statistics

ROOT = "fit_batch"


def ring():
    """The complete ("X") events of the program's span ring."""
    from mxnet_tpu import telemetry
    return [e for e in telemetry.chrome_trace_payload()["traceEvents"]
            if e.get("ph") == "X"]


def batches(events):
    """``[{span name: [(start_us, dur_us), ...]}, ...]``, one entry per
    batch id whose root span is in *events* (a span is recorded when it
    ends, so a batch cut by either end of the ring has no root and is
    dropped), in the order the batches ended."""
    by_id = {}
    for e in events:
        batch_id = (e.get("args") or {}).get("trace_id")
        if batch_id is not None:
            by_id.setdefault(batch_id, collections.defaultdict(list))[
                e["name"]].append((e["ts"], e["dur"]))
    return [spans for spans in by_id.values() if ROOT in spans]


def median_ms(per_batch):
    """Median over the ring's complete batches of ``per_batch(spans)`` (in
    microseconds; None where the batch lacks a span it needs, which drops
    the batch), in ms; None when no batch is left."""
    values = [per_batch(spans) for spans in batches(ring())]
    values = [v for v in values if v is not None]
    return statistics.median(values) / 1e3 if values else None


def first(spans, name):
    """(start_us, dur_us) of the batch's first span *name*, or None."""
    return min(spans[name]) if name in spans else None
