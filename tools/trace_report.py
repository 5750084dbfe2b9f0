#!/usr/bin/env python
"""trace_report: summarise a mxnet_tpu Chrome trace + telemetry snapshot.

Reads the ``traceEvents`` JSON produced by ``profiler.dump_profile()`` /
``telemetry.dump_chrome_trace()`` (and optionally the JSON snapshot from
``telemetry.dump_snapshot()``) and prints the tables that answer "where
did the step go" — and "what could the hardware have done":

  * step-time percentiles  — spans of category ``step`` (``trainer_step``,
    ``module_train_step``)
  * top-N ops by SELF time — per-track (tid) stack sweep over the nested
    'X' events; self time excludes enclosed children, so a fat parent
    span doesn't hide the child that actually burned the time
  * kvstore bucket traffic — ``kvstore_bucket_reduce`` spans' payload
    bytes (how much gradient actually moved per reduce program)
  * retrace report         — watched-jit compile events (``compile:*``
    trace events, enriched by the snapshot's per-callable accounting)
  * MFU / roofline         — the snapshot's XLA cost accounting: step
    FLOPs, MFU and HBM-bandwidth utilization against the device peaks,
    plus per-program arithmetic intensity vs. the machine balance point
    (is each program compute- or memory-bound?)
  * step timeline          — the MXNET_DEVICE_TIME decomposition from
    the snapshot's ``device`` section: data-wait / host-gap / device-
    compute / collective-comm per sampled step plus ``overlap_ratio``
    (the fraction of collective time hidden under compute — ROADMAP
    item 2's win condition) and the per-program device-time table.
    ``--gate-overlap RATIO`` turns the win condition into a CI gate:
    nonzero exit when the mean ``overlap_ratio`` falls below RATIO
    (exit 3) or when no timeline exists to measure it (exit 4)

``--fleet DIR`` switches to fleet mode: every ``trace_<role>_<rank>.json``
artifact in DIR (written by ``dist_ps.dump_trace_artifacts`` /
``MXNET_TRACE_DUMP_DIR``) is merged into ONE clock-aligned Chrome trace —
each rank's events shifted onto the scheduler's clock by the heartbeat-
estimated offset in its ``rank_meta``, re-pid'd per rank, and the
``ps_send``/``ps_recv`` RPC pairs joined with Chrome flow arrows on their
shared span id.  A missing or corrupt rank artifact degrades to a warning
and a partial merge, never a traceback.

``--health TIMESERIES.json`` switches to model-health mode: reads a
``telemetry.timeseries.export_json()`` artifact (the MXNET_MODEL_STATS
record) and renders per-parameter drift tables — weight-norm first→last,
grad-norm last/max, update/weight-ratio mean/max, grad-absmax peak —
plus a loss-curve summary and the step-gauge means.  The comparing twin
(same series vs a reference envelope, with exit codes) is
``tools/health_gate.py``.

Degrades gracefully: an empty or missing ``traceEvents`` array, or a
snapshot from an older build lacking the newer keys, prints "(no ...)"
placeholders instead of a traceback — this tool runs in CI pipelines on
whatever artifacts a dead job left behind.  ``--json`` emits the same
report machine-readable for CI consumption.

Stdlib-only on purpose: the report must run anywhere the trace file can
be copied, with no jax / framework import.

Usage:
    python tools/trace_report.py trace.json [--snapshot snap.json]
                                 [--top 10] [--json]
    python tools/trace_report.py --fleet DIR [--out merged.json] [--json]
    python tools/trace_report.py --health timeseries.json [--json]
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from collections import defaultdict


def load_events(path):
    """The 'X' trace events of *path*, or [] for anything unreadable —
    a truncated dump from a crashed job must not crash the reporter."""
    try:
        with open(path) as f:
            payload = json.load(f)
    except (OSError, ValueError) as exc:
        print("trace_report: unreadable trace %s (%s)" % (path, exc),
              file=sys.stderr)
        return []
    # both legal Chrome formats: {"traceEvents": [...]} and a bare array
    events = payload.get("traceEvents", []) if isinstance(payload, dict) \
        else payload
    if not isinstance(events, list):
        return []
    return [e for e in events
            if isinstance(e, dict) and e.get("ph") == "X"
            and isinstance(e.get("ts"), (int, float))
            and isinstance(e.get("dur"), (int, float))]


def load_snapshot(path):
    try:
        with open(path) as f:
            snap = json.load(f)
    except (OSError, ValueError) as exc:
        print("trace_report: unreadable snapshot %s (%s)" % (path, exc),
              file=sys.stderr)
        return None
    return snap if isinstance(snap, dict) else None


def percentile(sorted_vals, q):
    """Nearest-rank percentile of a pre-sorted list."""
    if not sorted_vals:
        return 0.0
    rank = max(1, int(round(q / 100.0 * len(sorted_vals))))
    return sorted_vals[min(rank, len(sorted_vals)) - 1]


def step_stats(events):
    durs = sorted(e["dur"] for e in events
                  if e.get("cat") == "step")
    if not durs:
        return None
    return {"count": len(durs),
            "p50_ms": percentile(durs, 50) / 1e3,
            "p90_ms": percentile(durs, 90) / 1e3,
            "p99_ms": percentile(durs, 99) / 1e3,
            "max_ms": durs[-1] / 1e3,
            "total_ms": sum(durs) / 1e3}


def self_times(events):
    """Aggregate per-name total/self wall time via a per-tid stack sweep.

    Chrome 'X' events nest by time containment within one tid: sweep each
    track in (ts, -dur) order keeping an open-span stack; every event's
    duration is subtracted from its innermost enclosing parent.
    """
    agg = defaultdict(lambda: [0, 0.0, 0.0])      # name -> [calls, total, self]
    by_tid = defaultdict(list)
    for e in events:
        if e.get("cat") == "compile":
            continue                              # accounted separately
        by_tid[e.get("tid", 0)].append(e)
    for track in by_tid.values():
        track.sort(key=lambda e: (e["ts"], -e["dur"]))
        stack = []                                # [(end_ts, name)]
        for e in track:
            ts, dur, name = e["ts"], e["dur"], e.get("name", "?")
            while stack and stack[-1][0] <= ts:
                stack.pop()
            rec = agg[name]
            rec[0] += 1
            rec[1] += dur
            rec[2] += dur
            if stack:
                agg[stack[-1][1]][2] -= dur       # parent loses child's time
            stack.append((ts + dur, name))
    return {name: {"calls": c, "total_ms": t / 1e3, "self_ms": s / 1e3}
            for name, (c, t, s) in agg.items()}


def bucket_stats(events):
    buckets = [e for e in events
               if e.get("name") == "kvstore_bucket_reduce"]
    if not buckets:
        return None
    sizes = [e.get("args", {}).get("bytes", 0) or 0 for e in buckets]
    return {"reduces": len(buckets),
            "total_bytes": sum(sizes),
            "avg_bytes": sum(sizes) / len(buckets),
            "max_bytes": max(sizes),
            "total_ms": sum(e["dur"] for e in buckets) / 1e3}


def retrace_stats(events, snapshot):
    """Merge compile trace events with the snapshot's retrace accounting."""
    out = {}
    for e in events:
        if e.get("cat") != "compile":
            continue
        name = e.get("name", "?").split(":", 1)[-1]
        rec = out.setdefault(name, {"count": 0, "total_ms": 0.0,
                                    "storm": False})
        rec["count"] += 1
        rec["total_ms"] += e["dur"] / 1e3
    retraces = (snapshot or {}).get("retraces")
    if isinstance(retraces, dict):
        for name, rec in retraces.items():
            if not isinstance(rec, dict):
                continue
            out[name] = {"count": rec.get("count", 0),
                         "total_ms": rec.get("total_ms", 0.0),
                         "storm": rec.get("storm", False)}
    return out


def mfu_stats(snapshot):
    """The cost-accounting view: step gauges + per-program roofline.

    Tolerates snapshots from builds predating cost accounting (missing
    ``costs``/gauge keys → None)."""
    if not isinstance(snapshot, dict):
        return None
    gauges = snapshot.get("gauges") or {}
    costs = snapshot.get("costs") or {}
    programs = costs.get("programs") or {}
    peaks = costs.get("peaks") or None
    out = {"step_model_flops": gauges.get("step_model_flops"),
           "step_mfu": gauges.get("step_mfu"),
           "step_hbm_bw_util": gauges.get("step_hbm_bw_util"),
           "peaks": peaks, "programs": []}
    balance = None
    if peaks and peaks.get("hbm_bw"):
        balance = peaks.get("flops", 0) / peaks["hbm_bw"]
        out["machine_balance_flops_per_byte"] = balance
    for name, rec in sorted(programs.items()):
        if not isinstance(rec, dict):
            continue
        flops = rec.get("flops", 0) or 0
        nbytes = rec.get("bytes_accessed", 0) or 0
        row = {"program": name, "flops": flops,
               "bytes_accessed": nbytes,
               "flops_per_byte": flops / nbytes if nbytes else None}
        if balance and row["flops_per_byte"] is not None:
            row["bound"] = ("compute" if row["flops_per_byte"] >= balance
                            else "memory")
        out["programs"].append(row)
    if out["step_model_flops"] is None and not out["programs"]:
        return None
    return out


def zero_stats(snapshot):
    """The ZeRO-1 sharded-update view: the ``zero_*`` gauges the fused
    Trainer sets under MXNET_ZERO (absent/None on replicated runs or
    snapshots from older builds)."""
    if not isinstance(snapshot, dict):
        return None
    gauges = snapshot.get("gauges") or {}
    per_dev = gauges.get("zero_optimizer_bytes_per_device")
    if not per_dev:        # absent, or zeroed when ZeRO deactivated
        return None
    replicated = gauges.get("zero_optimizer_bytes_replicated") or 0
    out = {"shards": gauges.get("zero_shards"),
           "optimizer_bytes_per_device": per_dev,
           "optimizer_bytes_replicated": replicated,
           "bytes_ratio": (per_dev / replicated) if replicated else None}
    return out


def timeline_stats(snapshot):
    """The MXNET_DEVICE_TIME step-timeline view from the snapshot's
    ``device`` section (None on snapshots from runs without it)."""
    if not isinstance(snapshot, dict):
        return None
    device = snapshot.get("device")
    if not isinstance(device, dict):
        return None
    last = device.get("last_step")
    if not last and not device.get("programs"):
        return None
    timelines = [t for t in (device.get("timelines") or [])
                 if isinstance(t, dict)]
    mean = None
    if timelines:
        keys = ("wall_us", "data_wait_us", "host_us", "device_us",
                "collective_us", "overlap_ratio", "overlap_hidden_us",
                "overlap_exposed_us")
        mean = {k: sum(t.get(k) or 0 for t in timelines) / len(timelines)
                for k in keys}
        mean["samples"] = len(timelines)
    return {"sample_period": device.get("sample_period"),
            "last_step": last,
            "mean": mean,
            "free_wall_ewma_us": device.get("free_wall_ewma_us"),
            "programs": device.get("programs") or {}}


# --------------------------------------------------------------------------
# fleet mode: merge per-rank artifacts into one clock-aligned trace
# --------------------------------------------------------------------------

def load_fleet_artifacts(directory):
    """(ranks, problems): per-rank dicts from every readable
    ``trace_*.json`` in *directory*, sorted scheduler→servers→workers.
    Unreadable artifacts land in *problems* instead of raising."""
    ranks, problems = [], []
    paths = sorted(glob.glob(os.path.join(directory, "trace_*.json")))
    for path in paths:
        try:
            with open(path) as fh:
                payload = json.load(fh)
        except (OSError, ValueError) as exc:
            problems.append("%s: unreadable (%s)"
                            % (os.path.basename(path), exc))
            continue
        if not isinstance(payload, dict):
            problems.append("%s: not a trace object"
                            % os.path.basename(path))
            continue
        meta = payload.get("rank_meta") or {}
        events = [e for e in payload.get("traceEvents", [])
                  if isinstance(e, dict)]
        ranks.append({"path": path,
                      "label": "%s-%s" % (meta.get("role", "?"),
                                          meta.get("rank", "?")),
                      "meta": meta,
                      "offset_us": float(meta.get("clock_offset_us")
                                         or 0.0),
                      "events": events})
    order = {"scheduler": 0, "server": 1, "worker": 2}
    ranks.sort(key=lambda r: (order.get(r["meta"].get("role"), 3),
                              r["meta"].get("rank", 0) or 0))
    return ranks, problems


def merge_fleet(ranks):
    """One Chrome trace: every rank's 'X' events shifted onto the
    scheduler clock (``ts + clock_offset_us``), pid = rank index with a
    process_name metadata row, plus flow events ('s'/'f', bound to the
    enclosing ps_send/ps_recv events) joining each traced RPC's
    send/recv pair across ranks on their shared span id."""
    merged = []
    sends = {}              # span_id -> (pid, tid, ts, name, trace_id)
    recvs = []              # (parent_span, pid, tid, ts, name)
    for pid, rank in enumerate(ranks):
        merged.append({"ph": "M", "name": "process_name", "pid": pid,
                       "tid": 0, "args": {"name": rank["label"]}})
        offset = rank["offset_us"]
        for e in rank["events"]:
            if e.get("ph") == "M":
                ev = dict(e, pid=pid)
                merged.append(ev)
                continue
            if not isinstance(e.get("ts"), (int, float)):
                continue
            ev = dict(e, pid=pid, ts=e["ts"] + offset)
            merged.append(ev)
            if e.get("cat") != "rpc":
                continue
            args = e.get("args") or {}
            name = e.get("name", "")
            if name.startswith("ps_send:") and args.get("span_id"):
                sends[args["span_id"]] = (pid, ev.get("tid", 0),
                                          ev["ts"], name,
                                          args.get("trace_id"))
            elif name.startswith("ps_recv:") and args.get("parent_span"):
                recvs.append((args["parent_span"], pid,
                              ev.get("tid", 0), ev["ts"], name))
    flows = 0
    for parent_span, rpid, rtid, rts, rname in recvs:
        src = sends.get(parent_span)
        if src is None:
            continue                    # sender artifact missing: skip
        spid, stid, sts, sname, trace_id = src
        op = sname.split(":", 1)[-1]
        flow = {"cat": "rpc", "name": "rpc:%s" % op, "id": parent_span,
                "args": {"trace_id": trace_id}}
        merged.append(dict(flow, ph="s", pid=spid, tid=stid, ts=sts))
        merged.append(dict(flow, ph="f", bp="e", pid=rpid, tid=rtid,
                           ts=max(rts, sts)))
        flows += 1
    return merged, flows


def fleet_report(directory, out_path=None):
    """Build + write the merged fleet trace; returns the summary dict."""
    ranks, problems = load_fleet_artifacts(directory)
    summary = {"directory": directory, "ranks": [], "problems": problems,
               "merged": None, "flows": 0}
    if not ranks:
        problems.append("no trace_*.json artifacts in %s" % directory)
        return summary
    merged, flows = merge_fleet(ranks)
    for pid, rank in enumerate(ranks):
        xs = [e["ts"] for e in rank["events"]
              if e.get("ph") == "X"
              and isinstance(e.get("ts"), (int, float))]
        summary["ranks"].append({
            "pid": pid, "label": rank["label"],
            "clock_offset_us": rank["offset_us"],
            "clock_rtt_us": rank["meta"].get("clock_rtt_us"),
            "steps": rank["meta"].get("steps"),
            "events": len(xs),
            "first_ts_us": round(min(xs) + rank["offset_us"], 1)
            if xs else None,
            "last_ts_us": round(max(xs) + rank["offset_us"], 1)
            if xs else None})
    summary["flows"] = flows
    if out_path is None:
        out_path = os.path.join(directory, "fleet_merged.json")
    try:
        with open(out_path, "w") as fh:
            json.dump({"traceEvents": merged, "displayTimeUnit": "ms"},
                      fh)
        summary["merged"] = out_path
    except OSError as exc:
        problems.append("cannot write %s (%s)" % (out_path, exc))
    return summary


def render_fleet(summary):
    lines = ["== fleet trace merge =="]
    for problem in summary["problems"]:
        lines.append("WARNING: %s" % problem)
    if summary["ranks"]:
        lines.append("%-16s %8s %14s %12s %7s" %
                     ("rank", "events", "clock_off_us", "rtt_us",
                      "steps"))
        for r in summary["ranks"]:
            lines.append("%-16s %8d %14.1f %12s %7s"
                         % (r["label"], r["events"],
                            r["clock_offset_us"],
                            "-" if r["clock_rtt_us"] is None
                            else "%.1f" % r["clock_rtt_us"],
                            "-" if r["steps"] is None else r["steps"]))
        lines.append("flow arrows (rpc send->recv pairs): %d"
                     % summary["flows"])
    if summary["merged"]:
        lines.append("merged trace: %s  (load in Perfetto / "
                     "chrome://tracing)" % summary["merged"])
    return "\n".join(lines)


def _fmt_bytes(n):
    for unit in ("B", "KiB", "MiB", "GiB"):
        if n < 1024 or unit == "GiB":
            return "%.1f%s" % (n, unit)
        n /= 1024.0
    return "%.1fGiB" % n


def _fmt_big(n):
    """1.23e9-style short form for FLOP counts."""
    if n is None:
        return "-"
    for thresh, suffix in ((1e12, "T"), (1e9, "G"), (1e6, "M"),
                           (1e3, "k")):
        if abs(n) >= thresh:
            return "%.2f%s" % (n / thresh, suffix)
    return "%.0f" % n


def build_report(events, snapshot, top):
    """All sections as one JSON-shaped dict (the --json payload)."""
    rows = sorted(self_times(events).items(),
                  key=lambda kv: kv[1]["self_ms"], reverse=True)[:top]
    report = {"steps": step_stats(events),
              "self_times": [dict(r, name=name) for name, r in rows],
              "buckets": bucket_stats(events),
              "retraces": retrace_stats(events, snapshot),
              "mfu": mfu_stats(snapshot),
              "zero": zero_stats(snapshot),
              "timeline": timeline_stats(snapshot),
              "data_pipeline": None}
    gauges = (snapshot or {}).get("gauges") or {}
    wait = gauges.get("io_batch_wait_us")
    st = report["steps"]
    if wait is not None and st and st["count"]:
        mean_step = st["total_ms"] / st["count"]
        report["data_pipeline"] = {
            "last_batch_wait_ms": wait / 1e3,
            "mean_step_ms": mean_step,
            "verdict": "DATA-STARVED" if wait / 1e3 > mean_step else "ok"}
    return report


def render(report, top):
    lines = []

    lines.append("== step time ==")
    st = report["steps"]
    if st:
        lines.append("steps %d  p50 %.3fms  p90 %.3fms  p99 %.3fms  "
                     "max %.3fms  total %.3fms"
                     % (st["count"], st["p50_ms"], st["p90_ms"],
                        st["p99_ms"], st["max_ms"], st["total_ms"]))
    else:
        lines.append("(no step spans in trace)")

    lines.append("")
    lines.append("== top %d ops by self time ==" % top)
    if report["self_times"]:
        lines.append("%-32s %8s %12s %12s" % ("name", "calls",
                                              "total_ms", "self_ms"))
        for r in report["self_times"]:
            lines.append("%-32s %8d %12.3f %12.3f"
                         % (r["name"][:32], r["calls"], r["total_ms"],
                            r["self_ms"]))
    else:
        lines.append("(no span events in trace)")

    lines.append("")
    lines.append("== kvstore bucket traffic ==")
    bs = report["buckets"]
    if bs:
        lines.append("reduces %d  bytes %s  avg %s  max %s  wall %.3fms"
                     % (bs["reduces"], _fmt_bytes(bs["total_bytes"]),
                        _fmt_bytes(bs["avg_bytes"]),
                        _fmt_bytes(bs["max_bytes"]), bs["total_ms"]))
    else:
        lines.append("(no kvstore bucket spans in trace)")

    lines.append("")
    lines.append("== retrace report ==")
    rt = report["retraces"]
    if rt:
        lines.append("%-32s %9s %12s %6s" % ("callable", "compiles",
                                             "compile_ms", "storm"))
        for name, r in sorted(rt.items(), key=lambda kv: -kv[1]["count"]):
            lines.append("%-32s %9d %12.3f %6s"
                         % (name[:32], r["count"], r["total_ms"],
                            "YES" if r["storm"] else "-"))
    else:
        lines.append("(no compile events recorded)")

    lines.append("")
    lines.append("== mfu / roofline ==")
    mfu = report["mfu"]
    if mfu:
        parts = ["step flops %s" % _fmt_big(mfu["step_model_flops"])]
        if mfu["step_mfu"] is not None:
            parts.append("MFU %.2f%%" % (mfu["step_mfu"] * 100))
        if mfu["step_hbm_bw_util"] is not None:
            parts.append("HBM BW %.2f%%"
                         % (mfu["step_hbm_bw_util"] * 100))
        peaks = mfu.get("peaks")
        if peaks:
            parts.append("peak %sFLOP/s (%s x%d)"
                         % (_fmt_big(peaks.get("flops")),
                            peaks.get("device_kind", "?"),
                            peaks.get("n_devices", 1)))
        lines.append("  ".join(parts))
        if mfu["programs"]:
            lines.append("%-32s %10s %10s %8s %8s"
                         % ("program", "flops", "bytes", "FLOP/B",
                            "bound"))
            for r in mfu["programs"]:
                lines.append("%-32s %10s %10s %8s %8s"
                             % (r["program"][:32], _fmt_big(r["flops"]),
                                _fmt_bytes(r["bytes_accessed"]),
                                "-" if r["flops_per_byte"] is None
                                else "%.1f" % r["flops_per_byte"],
                                r.get("bound", "-")))
    else:
        lines.append("(no cost accounting in snapshot — run with "
                     "MXNET_TELEMETRY=1 on a build with telemetry.costs)")

    tl = report.get("timeline")
    if tl:
        lines.append("")
        lines.append("== step timeline (MXNET_DEVICE_TIME, 1/%s steps "
                     "sampled) ==" % (tl.get("sample_period") or "?"))
        lines.append("%-12s %14s %14s" % ("segment", "last_step_us",
                                          "mean_us"))
        last = tl.get("last_step") or {}
        mean = tl.get("mean") or {}
        for key, label in (("data_wait_us", "data-wait"),
                           ("host_us", "host"),
                           ("device_us", "device"),
                           ("collective_us", "collective"),
                           ("overlap_hidden_us", "comm hidden"),
                           ("overlap_exposed_us", "comm exposed"),
                           ("wall_us", "step wall")):
            lines.append("%-12s %14s %14s"
                         % (label,
                            "-" if last.get(key) is None
                            else "%.1f" % last[key],
                            "-" if mean.get(key) is None
                            else "%.1f" % mean[key]))
        over_last = last.get("overlap_ratio")
        over_mean = mean.get("overlap_ratio")
        lines.append("%-12s %14s %14s"
                     % ("overlap",
                        "-" if over_last is None
                        else "%.2f" % over_last,
                        "-" if over_mean is None
                        else "%.2f" % over_mean))
        if tl.get("free_wall_ewma_us") is not None:
            lines.append("free-running wall EWMA %.1fus (the overlap "
                         "baseline)" % tl["free_wall_ewma_us"])
        programs = tl.get("programs") or {}
        if programs:
            lines.append("%-32s %8s %10s %10s %5s"
                         % ("program (device time)", "samples",
                            "mean_us", "max_us", "coll"))
            ordered = sorted(programs.items(),
                             key=lambda kv: -(kv[1].get("total_us") or 0))
            for name, rec in ordered:
                lines.append("%-32s %8d %10.1f %10.1f %5s"
                             % (name[:32], rec.get("samples", 0),
                                rec.get("mean_us", 0.0),
                                rec.get("max_us", 0.0),
                                "yes" if rec.get("collective") else "-"))

    z = report.get("zero")
    if z:
        lines.append("")
        lines.append("== zero-1 sharded update ==")
        parts = ["shards %s" % int(z["shards"] or 0),
                 "optimizer state/device %s"
                 % _fmt_bytes(z["optimizer_bytes_per_device"]),
                 "replicated %s"
                 % _fmt_bytes(z["optimizer_bytes_replicated"])]
        if z["bytes_ratio"] is not None:
            parts.append("ratio %.3f" % z["bytes_ratio"])
        lines.append("  ".join(parts))

    dp = report["data_pipeline"]
    if dp:
        lines.append("")
        lines.append("== data pipeline ==")
        lines.append("last batch wait %.3fms vs mean step %.3fms -> %s"
                     % (dp["last_batch_wait_ms"], dp["mean_step_ms"],
                        dp["verdict"]))

    return "\n".join(lines)


# --------------------------------------------------------------------------
# model-health mode (--health): the timeseries export, rendered
# --------------------------------------------------------------------------

def _series_stats(points):
    """min/max/mean/first/last over one [[step, value], ...] series."""
    vals = [float(v) for _, v in points]
    finite = [v for v in vals if v == v and abs(v) != float("inf")]
    return {"n": len(vals),
            "first": vals[0] if vals else None,
            "last": vals[-1] if vals else None,
            "min": min(finite) if finite else None,
            "max": max(finite) if finite else None,
            "mean": sum(finite) / len(finite) if finite else None,
            "nonfinite": len(vals) - len(finite)}


def health_report(export):
    """JSON-shaped model-health summary of one timeseries export: the
    per-parameter drift table, the loss curve, and the step gauges."""
    series = export.get("series", {})
    params = {}
    for name, points in series.items():
        if not name.startswith("model/") or name == "model/loss":
            continue
        try:
            _, pname, stat = name.split("/", 2)
        except ValueError:
            continue
        params.setdefault(pname, {})[stat] = _series_stats(points)
    drift = {}
    for pname, stats in sorted(params.items()):
        wsq = stats.get("weight_norm_sq", {})
        gsq = stats.get("grad_norm_sq", {})
        ratio = stats.get("update_ratio", {})
        absmax = stats.get("grad_absmax", {})
        sqrt = lambda v: None if v is None else max(0.0, v) ** 0.5
        drift[pname] = {
            "weight_norm_first": sqrt(wsq.get("first")),
            "weight_norm_last": sqrt(wsq.get("last")),
            "grad_norm_last": sqrt(gsq.get("last")),
            "grad_norm_max": sqrt(gsq.get("max")),
            "update_ratio_mean": ratio.get("mean"),
            "update_ratio_max": ratio.get("max"),
            "grad_absmax_max": absmax.get("max"),
            "nonfinite_points": sum(s.get("nonfinite", 0)
                                    for s in stats.values()),
            "points": max((s.get("n", 0) for s in stats.values()),
                          default=0),
        }
    gauges = {name: _series_stats(points)
              for name, points in sorted(series.items())
              if not name.startswith("model/")}
    loss = _series_stats(series["model/loss"]) \
        if "model/loss" in series else None
    return {"steps_seen": export.get("steps_seen", 0),
            "cap": export.get("cap"),
            "loss": loss, "params": drift, "gauges": gauges}


def render_health(report):
    lines = ["== model health (MXNET_MODEL_STATS timeseries) =="]
    loss = report.get("loss")
    if loss and loss.get("n"):
        lines.append(
            "loss: %d points  first %.6g  last %.6g  min %.6g  "
            "nonfinite %d"
            % (loss["n"], loss["first"], loss["last"],
               loss["min"] if loss["min"] is not None else float("nan"),
               loss["nonfinite"]))
    else:
        lines.append("loss: (no model/loss series — train under a "
                     "guardian or record it explicitly)")
    params = report.get("params", {})
    if params:
        lines.append("")
        lines.append("%-28s %10s %10s %10s %10s %10s" %
                     ("param", "|w| first", "|w| last", "|g| last",
                      "upd/w mean", "|g|max max"))
        fmt = lambda v: "-" if v is None else "%.4g" % v
        for pname, row in params.items():
            lines.append("%-28s %10s %10s %10s %10s %10s" %
                         (pname[:28], fmt(row["weight_norm_first"]),
                          fmt(row["weight_norm_last"]),
                          fmt(row["grad_norm_last"]),
                          fmt(row["update_ratio_mean"]),
                          fmt(row["grad_absmax_max"])))
            if row["nonfinite_points"]:
                lines.append("%-28s   ^ %d nonfinite stat points "
                             "(overflow/NaN steps)" %
                             ("", row["nonfinite_points"]))
    else:
        lines.append("(no model/* series — run with MXNET_MODEL_STATS=1)")
    gauges = report.get("gauges", {})
    if gauges:
        lines.append("")
        lines.append("step gauges (per step-span exit):")
        for name, st in gauges.items():
            if st.get("mean") is None:
                continue
            lines.append("  %-24s mean %.4g  last %.4g  (%d points)"
                         % (name, st["mean"], st["last"], st["n"]))
    return "\n".join(lines)


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="Summarise an mxnet_tpu Chrome trace "
                    "(+ optional telemetry snapshot).")
    ap.add_argument("trace", nargs="?", default=None,
                    help="Chrome trace JSON from dump_profile()")
    ap.add_argument("--snapshot", default=None,
                    help="JSON from telemetry.dump_snapshot()")
    ap.add_argument("--top", type=int, default=10,
                    help="rows in the self-time table (default 10)")
    ap.add_argument("--fleet", default=None, metavar="DIR",
                    help="merge the per-rank trace_*.json artifacts in "
                         "DIR (MXNET_TRACE_DUMP_DIR) into one "
                         "clock-aligned trace")
    ap.add_argument("--out", default=None,
                    help="--fleet: merged trace path (default "
                         "DIR/fleet_merged.json)")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="machine-readable report on stdout (CI)")
    ap.add_argument("--gate-overlap", type=float, default=None,
                    metavar="RATIO",
                    help="exit nonzero unless the step timeline's mean "
                         "overlap_ratio (collective time hidden under "
                         "backward) reaches RATIO — the ROADMAP item-2 "
                         "win condition as a CI gate")
    ap.add_argument("--health", default=None, metavar="TIMESERIES",
                    help="model-health mode: render the per-param drift "
                         "table and loss summary of a "
                         "telemetry.timeseries export_json() file")
    ap.add_argument("--memory", default=None, metavar="MEMJSON",
                    help="memory-budget mode: render the per-program "
                         "bytes-vs-budget table of a graftcheck "
                         "--memory-json report")
    ap.add_argument("--gate-memory", action="store_true",
                    help="with --memory: exit 3 when any program is "
                         "over budget or unbudgeted, 4 when the report "
                         "cannot measure (topology mismatch / empty) — "
                         "the JX204 verdict as a CI gate")
    ap.add_argument("--ops", default=None, metavar="OPSJSON",
                    help="hot-op mode: render the ranked per-op "
                         "roofline table and kernel candidates of a "
                         "``python -m mxnet_tpu.telemetry.opprof "
                         "--json`` artifact")
    args = ap.parse_args(argv)

    # gates declare their evidence up front: a gate whose input section
    # is missing is a usage error (2), never a silent skip
    if args.gate_memory and args.memory is None:
        ap.error("--gate-memory requires --memory MEMJSON")
    if args.gate_overlap is not None and args.trace is None \
            and args.fleet is None:
        ap.error("--gate-overlap requires a trace file")

    if args.fleet is not None:
        summary = fleet_report(args.fleet, out_path=args.out)
        if args.as_json:
            print(json.dumps(summary, indent=1, sort_keys=True))
        else:
            print(render_fleet(summary))
        return 0 if summary["ranks"] else 2

    def _load(path, label):
        try:
            with open(path) as fh:
                return json.load(fh)
        except (OSError, ValueError) as exc:
            print("%s: cannot read %s: %s" % (label, path, exc),
                  file=sys.stderr)
            raise

    # every requested section renders; every requested gate runs; the
    # exit code is the worst gate verdict — combining --gate-overlap and
    # --gate-memory must never silently drop one
    sections = []               # (key, payload, render thunk)
    mem_payload = trace_payload = None
    try:
        if args.memory is not None:
            mem_payload = _load(args.memory, "memory")
            sections.append(("memory", mem_payload,
                             lambda r=mem_payload: render_memory(r)))
        if args.ops is not None:
            ops_payload = _load(args.ops, "ops")
            sections.append(("ops", ops_payload,
                             lambda r=ops_payload:
                             render_ops(r, args.top)))
        if args.health is not None:
            export = _load(args.health, "health")
            hreport = health_report(export)
            sections.append(("health", hreport,
                             lambda r=hreport: render_health(r)))
    except (OSError, ValueError):
        return 2
    if args.trace is not None:
        events = load_events(args.trace)
        snapshot = load_snapshot(args.snapshot) if args.snapshot \
            else None
        trace_payload = build_report(events, snapshot, args.top)
        empty = not events and not snapshot
        sections.append(("trace", trace_payload,
                         lambda r=trace_payload, e=empty:
                         "no events" if e else render(r, args.top)))

    if not sections:
        ap.error("a trace file is required (or use --fleet DIR / "
                 "--memory / --ops / --health)")

    if args.as_json:
        if len(sections) == 1:
            print(json.dumps(sections[0][1], indent=1, sort_keys=True))
        else:
            print(json.dumps({k: p for k, p, _r in sections},
                             indent=1, sort_keys=True))
    else:
        print("\n\n".join(r() for _k, _p, r in sections))

    rcs = []
    if args.gate_overlap is not None and trace_payload is not None:
        rcs.append(gate_overlap(trace_payload, args.gate_overlap))
    if args.gate_memory:
        rcs.append(gate_memory(mem_payload))
    return max(rcs) if rcs else 0


def _fmt_bytes(n):
    if n is None:
        return "-"
    if n >= 1 << 20:
        return "%.1fMiB" % (n / float(1 << 20))
    if n >= 1 << 10:
        return "%.1fKiB" % (n / float(1 << 10))
    return "%dB" % n


def render_memory(report):
    """The per-program bytes-vs-budget table of a graftcheck
    --memory-json report (JX204's evidence, human-shaped)."""
    lines = ["memory budgets: %d program(s), %d device(s), tolerance "
             "+%d%%" % (len(report.get("programs", ())),
                        report.get("n_devices") or 0,
                        int((report.get("tolerance") or 0) * 100))]
    if not report.get("baseline_present"):
        lines.append("  (no MEM_BASELINE.json — every program reads as "
                     "unbudgeted)")
    elif not report.get("topology_match"):
        lines.append("  (baseline captured at %s device(s), running %s — "
                     "comparison skipped)"
                     % (report.get("baseline_n_devices"),
                        report.get("n_devices")))
    lines.append("  %-40s %9s %9s %9s %9s %9s  %s"
                 % ("program", "args", "outputs", "temps", "total",
                    "budget", "verdict"))
    for p in sorted(report.get("programs", ()),
                    key=lambda e: -e.get("total_bytes", 0)):
        if p.get("over_budget"):
            verdict = "OVER"
        elif p.get("unbudgeted"):
            verdict = "unbudgeted"
        elif p.get("budget_total_bytes") is None:
            verdict = "skipped"
        else:
            verdict = "ok"
        lines.append("  %-40s %9s %9s %9s %9s %9s  %s"
                     % (p.get("name", "?"),
                        _fmt_bytes(p.get("argument_bytes")),
                        _fmt_bytes(p.get("output_bytes")),
                        _fmt_bytes(p.get("temp_bytes")),
                        _fmt_bytes(p.get("total_bytes")),
                        _fmt_bytes(p.get("budget_total_bytes")),
                        verdict))
    stale = report.get("stale_budgets") or []
    if stale:
        lines.append("  stale budget(s) (program gone): %s"
                     % ", ".join(stale))
    return "\n".join(lines)


def gate_memory(report):
    """The --gate-memory exit policy (mirrors --gate-overlap and
    health_gate): 0 when every program is within budget; 3 when any is
    over budget or unbudgeted; 4 when the report cannot measure —
    topology mismatch, no baseline comparison possible, or no programs
    at all (a gate that cannot measure must fail loudly)."""
    programs = report.get("programs") or []
    if not programs or not report.get("topology_match"):
        why = "no programs in the report" if not programs else \
            ("baseline n_devices=%s vs live n_devices=%s"
             % (report.get("baseline_n_devices"),
                report.get("n_devices")))
        print("gate-memory: UNMEASURABLE — %s" % why, file=sys.stderr)
        return 4
    over = [p["name"] for p in programs if p.get("over_budget")]
    unbudgeted = [p["name"] for p in programs if p.get("unbudgeted")]
    if over or unbudgeted:
        parts = []
        if over:
            parts.append("over budget: %s" % ", ".join(sorted(over)))
        if unbudgeted:
            parts.append("unbudgeted: %s" % ", ".join(sorted(unbudgeted)))
        print("gate-memory: FAIL — %s" % "; ".join(parts),
              file=sys.stderr)
        return 3
    print("gate-memory: ok — %d program(s) within budget (+%d%% "
          "tolerance)" % (len(programs),
                          int((report.get("tolerance") or 0) * 100)))
    return 0


def _fmt_rate(v, unit):
    if v is None or v <= 0:
        return "-"
    for scale, prefix in ((1e12, "T"), (1e9, "G"), (1e6, "M")):
        if v >= scale:
            return "%.1f%s%s" % (v / scale, prefix, unit)
    return "%.0f%s" % (v, unit)


def render_ops(report, top=10):
    """The ranked hot-op table and kernel-candidate list of an opprof
    ``--json`` artifact: every owned program's fusions, rooflined."""
    lines = ["hot ops: %d program(s), roofline floor %.1f us, machine "
             "balance %.2f FLOP/B (peaks: %s, HBM %s, ICI %s)"
             % (len(report.get("programs", {})),
                report.get("total_est_us") or 0,
                report.get("machine_balance") or 0,
                _fmt_rate((report.get("peaks") or {}).get("flops"),
                          "FLOP/s"),
                _fmt_rate((report.get("peaks") or {}).get("hbm_bw"),
                          "B/s"),
                _fmt_rate((report.get("peaks") or {}).get("ici_bw"),
                          "B/s"))]
    rows = []
    for name, p in (report.get("programs") or {}).items():
        for u in p.get("units", ()):
            rows.append((u.get("est_us") or 0.0, name, u))
    rows.sort(key=lambda r: -r[0])
    lines.append("  %-26s %-28s %-11s %8s %-7s %10s %7s %9s"
                 % ("program", "unit", "class", "FLOP/B", "bound",
                    "ceiling", "share", "est us"))
    for us, name, u in rows[:top]:
        lines.append("  %-26s %-28s %-11s %8.2f %-7s %10s %6.1f%% %9.3f"
                     % (name[:26], u.get("unit", "?")[:28],
                        u.get("op_class", "?"),
                        u.get("intensity") or 0.0,
                        u.get("bound", "?"),
                        _fmt_rate(u.get("ceiling"),
                                  "F/s" if u.get("ceiling_kind") ==
                                  "flops_per_s" else "B/s"),
                        100 * (u.get("share") or 0.0), us))
    cands = report.get("candidates") or []
    if cands:
        lines.append("")
        lines.append("kernel candidates (ROADMAP item-2 handoff):")
        for i, c in enumerate(cands, 1):
            lines.append(
                "  %d. [%s] %s :: %s  %s/%s  ceiling %s  "
                "share %.2f%%  score %.4f"
                % (i, c.get("kind", "?"), c.get("program", "?"),
                   c.get("unit", "?"), c.get("op_class", "?"),
                   c.get("bound", "?"),
                   _fmt_rate(c.get("ceiling"),
                             "F/s" if c.get("ceiling_kind") ==
                             "flops_per_s" else "B/s"),
                   100 * (c.get("global_share") or 0.0),
                   c.get("score") or 0.0))
    problems = report.get("problems") or []
    for prob in problems:
        lines.append("  problem: %s" % prob)
    return "\n".join(lines)


def gate_overlap(report, threshold):
    """The --gate-overlap exit policy: 0 when the sampled step
    timeline's mean ``overlap_ratio`` reaches *threshold*; 3 when it
    falls short; 4 when no timeline exists at all (a gate that cannot
    measure must fail loudly, not vacuously pass)."""
    tl = report.get("timeline") or {}
    mean = tl.get("mean") or {}
    ratio = mean.get("overlap_ratio")
    if ratio is None:
        last = tl.get("last_step") or {}
        ratio = last.get("overlap_ratio")
    if ratio is None:
        print("gate-overlap: FAIL — no step-timeline overlap_ratio in "
              "the snapshot (run with MXNET_DEVICE_TIME)",
              file=sys.stderr)
        return 4
    verdict = "ok" if ratio >= threshold else "FAIL"
    print("gate-overlap: %s — mean overlap_ratio %.3f vs threshold %.3f"
          % (verdict, ratio, threshold),
          file=sys.stderr if verdict == "FAIL" else sys.stdout)
    return 0 if verdict == "ok" else 3


if __name__ == "__main__":
    sys.exit(main())
