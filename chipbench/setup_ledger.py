"""The program's own account of a start, for the five ``.setup`` readers
(``moves: setup_s``).

Two records, both kept by ``mxnet_tpu.telemetry`` whatever its switches say:

* the **compile ledger** (``telemetry.compile_events()``): one row a backend
  compile anywhere in the process, from JAX's own monitoring events —
  ``fun_name``, ``watch`` (the program's name for the jit, if it owns it),
  ``span`` (the program span it happened under, None for the benchmark's
  reference programs and checks), ``trace_s``, ``lower_s``, ``backend_s``
  (XLA's compile, or the retrieval when the persistent cache served it),
  ``cache`` (``hit`` / ``miss`` / ``off``) and ``ts`` in microseconds on the
  spans' clock;
* the **set-up spans** in the span ring (category ``setup``): ``module_bind``,
  ``module_init_params`` (children ``init_params_host``,
  ``init_params_place``), ``module_init_optimizer``, ``module_step_build``,
  ``module_first_step``, each root carrying ``args.module``, the trainer it
  belongs to.

**Before the window** means: rows and spans whose ``ts`` precedes the start
of the ring's earliest ``fit_batch`` root.  Per-batch spans record only while
a profiler session is open, so that root is the first batch of the traced
window, which opens after the measured one; ``correct`` already demands zero
compiles between the two, so the rows counted here are the harness's
``compiles_before_window`` (two listeners on one event).  A ring without a
``fit_batch`` (an untraced run read by hand) has no cut: everything counts.

**The trainer** is the module whose fused step ran (it has a
``module_first_step``): a second module the benchmark binds for a check has
set-up spans too, and they are the check's, not the trainer's.

A reader returns None when the program keeps no such ledger (the parent of
the PR that added it), and when the run is not on a device ``peaks.py``
knows: a toy's compile seconds on a CPU are not the cell's.
"""
import json

WINDOW_ROOT = "fit_batch"
ROOTS = ("module_bind", "module_init_params", "module_init_optimizer",
         "module_step_build", "module_first_step")
TOP = 10

_said = False


def ledger():
    """(rows, ring events) as the program holds them now."""
    from mxnet_tpu import telemetry
    events = [e for e in telemetry.chrome_trace_payload()["traceEvents"]
              if e.get("ph") == "X"]
    return telemetry.compile_events(), events


def before_window(rows, events):
    """``(rows, set-up spans)`` that precede the window, or None when
    *rows* is not a compile ledger (no row carries ``backend_s``)."""
    rows = [r for r in rows if "backend_s" in r]
    if not rows:
        return None
    cut = min((e["ts"] for e in events if e["name"] == WINDOW_ROOT),
              default=float("inf"))
    return ([r for r in rows if r["ts"] < cut],
            [e for e in events if e.get("cat") == "setup" and e["ts"] < cut])


def start(ctx):
    """What the readers read: ``before_window`` of the live program, or
    None off the chip.  The first call of a run also prints the summary
    line."""
    global _said
    if ctx["device_kind"] not in ctx["peaks"].PEAKS:
        return None
    found = before_window(*ledger())
    if found is not None and not _said:
        _said = True
        print("chipbench: setup_ledger "
              + json.dumps(summary(*found), sort_keys=True), flush=True)
    return found


def of_rows(ctx, fn):
    """A reader's whole body: *fn* over the rows before the window."""
    found = start(ctx)
    return None if found is None else fn(found[0])


def of_trainer(ctx, names=ROOTS):
    """Likewise: seconds under the trainer's root spans named *names*."""
    found = start(ctx)
    return None if found is None \
        else union_s(trainer_spans(found[1], names))


def trainer_spans(spans, names=ROOTS):
    """The root set-up spans named *names* of every module that ran a
    first fused step."""
    def module(e):
        return (e.get("args") or {}).get("module")
    trainers = {module(e) for e in spans if e["name"] == "module_first_step"}
    return [e for e in spans if e["name"] in names and module(e) in trainers]


def union_s(spans):
    """Seconds covered by at least one of *spans*."""
    total, end = 0.0, float("-inf")
    for t0, t1 in sorted((e["ts"], e["ts"] + e["dur"]) for e in spans):
        if t1 > end:
            total += t1 - max(t0, end)
            end = t1
    return total / 1e6


def compile_s(rows):
    return sum(r["backend_s"] for r in rows)


def trace_lower_s(rows):
    return sum(r["trace_s"] + r["lower_s"] for r in rows)


def cache_miss_programs(rows):
    return sum(r["cache"] != "hit" for r in rows)


def summary(rows, spans):
    """The table behind the five numbers: where a start's seconds went."""
    def top(key):
        best = sorted(rows, key=key, reverse=True)[:TOP]
        return [[r["fun_name"], r["watch"], r["span"], r["cache"],
                 round(r["backend_s"], 3),
                 round(r["trace_s"] + r["lower_s"], 3)] for r in best]

    def seconds(picked):
        return round(sum(r["trace_s"] + r["lower_s"] + r["backend_s"]
                         for r in picked), 3)
    by_cache = {kind: [r for r in rows if r["cache"] == kind]
                for kind in ("hit", "miss", "off")}
    by_span = {}
    for r in rows:
        by_span.setdefault(r["span"] or "(none)", []).append(r)
    return {
        "rows": len(rows),
        "cache": {kind: len(picked) for kind, picked in by_cache.items()},
        "backend_s": {kind: round(compile_s(picked), 3)
                      for kind, picked in by_cache.items()},
        "saved_s": round(sum(r["saved_s"] for r in rows), 3),
        "trace_s": round(sum(r["trace_s"] for r in rows), 3),
        "lower_s": round(sum(r["lower_s"] for r in rows), 3),
        "seconds_by_span": {name: [len(picked), seconds(picked)]
                            for name, picked in sorted(by_span.items())},
        "spans_s": [[e["name"], (e.get("args") or {}).get("module"),
                     round(e["dur"] / 1e6, 3)] for e in spans],
        "trainer_setup_s": round(union_s(trainer_spans(spans)), 3),
        "top_backend": top(lambda r: r["backend_s"]),
        "top_trace_lower": top(lambda r: r["trace_s"] + r["lower_s"]),
        "columns": ["fun_name", "watch", "span", "cache", "backend_s",
                    "trace_lower_s"]}
