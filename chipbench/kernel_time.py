"""Device time inside named kernels, from the profiler's ``.xplane.pb``.

``trace.read_xplane`` keeps an event's HLO instruction name only, and
``trace.reduce_rows`` the ten longest ops; a kernel's time needs every
device event that belongs to it.  An event belongs to a kernel when the
kernel's pattern (a regular expression from the configuration's
``trace_patterns``) matches the event's name — a Pallas call is named
after its kernel (``%power_retention_fwd.1 = ... custom-call(...)``) — or
the JAX scope path of its op (``jax.named_scope``), which the profiler
keeps as the statistic ``tf_op`` of the event's metadata
(``jit(step)/.../power_retention_bwd/while/body/.../dot_general``).
``jax.profiler.ProfileData`` does not hand out an event's metadata, so
that one table is read from the file's protobuf wire format directly
(``tsl/profiler/protobuf/xplane.proto``; the field numbers are below).
The time is the union of the matching intervals of the first device
inside the traced window, so an op and the loop it sits in are not
counted twice; a loop's own event carries no scope, its body's ops do.
"""
import glob
import os
import re

from chipbench import trace as trace_mod

# xplane.proto: XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4,
# .stat_metadata = 5 (maps: key = 1, value = 2); XEventMetadata.name = 2,
# .stats = 5; XStatMetadata.name = 2; XStat.metadata_id = 1,
# .str_value = 5, .ref_value = 7 (a string interned as a stat's name)
SCOPE_STAT = "tf_op"


def varint(buf, pos):
    value, shift = 0, 0
    while True:
        byte = buf[pos]
        pos += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return value, pos


def wire_fields(buf):
    """(field number, value) pairs of one protobuf message: an int for a
    varint, the bytes for a length-delimited field; fixed-width fields
    are skipped."""
    pos, end = 0, len(buf)
    while pos < end:
        key, pos = varint(buf, pos)
        field, kind = key >> 3, key & 7
        if kind == 0:
            value, pos = varint(buf, pos)
            yield field, value
        elif kind == 2:
            size, pos = varint(buf, pos)
            yield field, buf[pos:pos + size]
            pos += size
        else:
            pos += {1: 8, 5: 4}[kind]


def first(buf, number, default=None):
    return next((v for f, v in wire_fields(buf) if f == number), default)


def scope_paths(path):
    """{plane name: {event name: JAX scope path}} from an ``.xplane.pb``."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for field, plane in wire_fields(space):
        if field != 1:
            continue
        name = bytes(first(plane, 2, b"")).decode()
        stat_names, events = {}, []
        for number, entry in wire_fields(plane):
            if number == 5:
                stat_names[first(entry, 1, 0)] = bytes(
                    first(first(entry, 2, b""), 2, b"")).decode()
            elif number == 4:
                events.append(first(entry, 2, b""))
        table = {}
        for meta in events:
            for number, stat in wire_fields(meta):
                if number != 5 or \
                        stat_names.get(first(stat, 1, 0)) != SCOPE_STAT:
                    continue
                text = first(stat, 5)
                scope = bytes(text).decode() if text is not None \
                    else stat_names.get(first(stat, 7, 0), "")
                table[bytes(first(meta, 2, b"")).decode()] = scope
        out[name] = table
    return out


def device_events(trace_dir):
    """(window or None, events): the traced window (lo_ns, hi_ns) and, for
    the first device plane, (text, start_ns, end_ns) per op event, text
    being the event's name and its op's scope path joined by a space."""
    from jax.profiler import ProfileData
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        return None, []
    planes = list(ProfileData.from_file(found[-1]).planes)
    window = None
    for plane in planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == trace_mod.WINDOW:
                    window = (float(ev.start_ns),
                              float(ev.start_ns + ev.duration_ns))
    # the first device plane that has an op line (a chip's trace also
    # holds planes such as "/device:CUSTOM:Megascale Trace" without one)
    devices = sorted(p.name for p in planes
                     if p.name.startswith("/device:") and
                     any(ln.name == trace_mod.OPS_LINE for ln in p.lines))
    if not devices:
        return window, []
    scopes = scope_paths(found[-1]).get(devices[0], {})
    events = []
    for plane in planes:
        if plane.name != devices[0]:
            continue
        for line in plane.lines:
            if line.name != trace_mod.OPS_LINE:
                continue
            for ev in line.events:
                events.append((ev.name + " " + scopes.get(ev.name, ""),
                               float(ev.start_ns),
                               float(ev.start_ns + ev.duration_ns)))
    return window, events


def seconds_by_pattern(trace_dir, patterns):
    """{kernel: {"seconds", "events", "longest_ops"}} for each pattern that
    matched something; an empty dict where the trace has no window or no
    device (a CPU rehearsal), so that a reader finds nothing to read."""
    window, events = device_events(trace_dir)
    if window is None or not events:
        return {}
    lo, hi = window
    out = {}
    for kernel, pattern in patterns.items():
        rx = re.compile(pattern)
        hits = [(text, s, e) for text, s, e in events if rx.search(text)]
        merged = trace_mod.union(trace_mod.clip(
            [(s, e) for _, s, e in hits], lo, hi))
        if merged:
            by_op = {}
            for text, s, e in hits:
                op = trace_mod.op_name(text)
                by_op[op] = by_op.get(op, 0.0) + (e - s) / 1e9
            longest = sorted(by_op.items(), key=lambda kv: -kv[1])[:8]
            out[kernel] = {"seconds": sum(e - s for s, e in merged) / 1e9,
                           "events": len(hits), "longest_ops": longest}
    return out
