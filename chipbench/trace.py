"""Profiler trace -> rows -> device busy/idle, idle gaps by host span, top
device ops, collective time.  The reduction is the benchmark's own, so
every PR computes these numbers the same way.

A row is ``{"plane", "line", "name", "start_ns", "dur_ns"}``.  Device
planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one event per
executed HLO op.  Host spans are ``jax.profiler.TraceAnnotation``s whose
names start with ``chipbench.``; they land on the same clock.  The traced
window is the span ``chipbench.trace_window``.
"""
import bisect
import collections
import glob
import os
import re

SPAN_PREFIX = "chipbench."
WINDOW = SPAN_PREFIX + "trace_window"
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(
    r"^(all-reduce|reduce-scatter|all-gather|collective-permute|all-to-all)")


def op_name(event_name):
    """The profiler names a device op by its whole HLO line
    (``%fusion.12 = bf16[...] fusion(...)``); keep the instruction name."""
    return event_name.lstrip("%").split(" ", 1)[0]


def read_xplane(trace_dir):
    """Rows of the newest ``.xplane.pb`` under *trace_dir* that the
    reduction needs: device op events and the benchmark's host spans."""
    from jax.profiler import ProfileData
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        return []
    rows = []
    for plane in ProfileData.from_file(found[-1]).planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            for ev in line.events:
                if device or ev.name.startswith(SPAN_PREFIX):
                    rows.append({"plane": plane.name, "line": line.name,
                                 "name": op_name(ev.name) if device
                                 else ev.name,
                                 "start_ns": float(ev.start_ns),
                                 "dur_ns": float(ev.duration_ns)})
    return rows


def union(intervals):
    """Sorted, merged copy of [(start, end), ...]."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def owner_timeline(spans, lo, hi):
    """Cut [lo, hi] at every span edge; name each piece after the covering
    span that started last (the innermost of nested spans), or
    ``outside_any_span``.  Returns (cuts, owners), len(owners) ==
    len(cuts) - 1."""
    flat = sorted(((s, e, name) for name, ivs in spans.items()
                   for s, e in ivs), key=lambda t: (t[0], -t[1]))
    cuts = sorted({lo, hi} | {t for s, e, _ in flat for t in (s, e)})
    owners, active, nxt = [], [], 0
    for a, b in zip(cuts, cuts[1:]):
        while nxt < len(flat) and flat[nxt][0] <= a:
            active.append(flat[nxt])
            nxt += 1
        active = [sp for sp in active if sp[1] >= b]
        owners.append(active[-1][2] if active else "outside_any_span")
    return cuts, owners


def reduce_rows(rows):
    """Everything the per-layer readers and the result line take from a
    trace, or None when the trace holds no window or no device op."""
    window = [r for r in rows if r["name"] == WINDOW]
    if not window:
        return None
    lo = window[0]["start_ns"]
    hi = lo + window[0]["dur_ns"]
    spans = collections.defaultdict(list)       # host span name -> intervals
    devices = collections.defaultdict(list)     # device plane -> op rows
    for r in rows:
        iv = (r["start_ns"], r["start_ns"] + r["dur_ns"])
        if r["plane"].startswith("/device:"):
            if r["line"] == OPS_LINE:
                devices[r["plane"]].append((iv, r["name"]))
        elif r["name"] != WINDOW and r["name"].startswith(SPAN_PREFIX):
            spans[r["name"][len(SPAN_PREFIX):]].extend(clip([iv], lo, hi))
    if not devices:
        return None
    busy = {}
    for plane, ops in devices.items():
        merged = union(clip([iv for iv, _ in ops], lo, hi))
        busy[plane] = (merged, sum(e - s for s, e in merged))
    first = sorted(devices)[0]
    merged0, busy0 = busy[first]

    # idle gaps on the first device, each nanosecond of a gap booked to the
    # innermost-last host span covering it (later-starting spans nest
    # inside earlier ones), the rest to outside_any_span
    edges = [lo] + [t for iv in merged0 for t in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    cuts, owners = owner_timeline(spans, lo, hi)
    gap_by = collections.Counter()
    for gs, ge in gaps:
        i = bisect.bisect_right(cuts, gs) - 1
        while i < len(owners) and cuts[i] < ge:
            gap_by[owners[i]] += min(ge, cuts[i + 1]) - max(gs, cuts[i])
            i += 1

    op_time = collections.Counter()
    collective = 0.0
    for (s, e), name in devices[first]:
        d = sum(b - a for a, b in clip([(s, e)], lo, hi))
        op_time[name] += d
        if COLLECTIVE.match(name):
            collective += d
    ns = 1e-9
    return {
        "window_s": (hi - lo) * ns,
        "busy_s_first": busy0 * ns,
        "busy_s_mean": sum(b for _, b in busy.values()) / len(busy) * ns,
        "devices": len(busy),
        "collective_s_first": collective * ns,
        "span_counts": {k: len(v) for k, v in spans.items()},
        "device_ops": [[n, t * ns] for n, t in op_time.most_common(10)],
        "idle_gaps": [[n, t * ns] for n, t in gap_by.most_common(10)],
    }
