"""Operator-level profiler with Chrome trace-event output.

Parity surface: reference ``python/mxnet/profiler.py:27-55`` +
``src/engine/profiler.{h,cc}`` (SURVEY §5.1): engine workers stamp each op
with ``OprExecStat{opr_name, start/end µs, thread_id, dev}`` and
``Profiler::DumpProfile`` emits Chrome trace-event JSON.

TPU-native redesign: there is no engine worker to instrument — eager ops
dispatch through ``ndarray.invoke`` and compiled graphs execute as one XLA
program.  So the profiler has two layers:

1. **Op events**: when running, the eager dispatch path and the Executor
   forward/backward record wall-clock spans per op / per program, dumped as
   Chrome ``traceEvents`` JSON — same file format the reference produces,
   loadable in chrome://tracing or Perfetto.
2. **Device profile**: ``start()/stop()`` also drive ``jax.profiler``
   (XPlane/TensorBoard) when a trace dir is configured, which is where
   real per-kernel TPU timing lives (XLA fuses ops, so per-op host spans
   are the honest analogue of the reference's engine stats).

The buffers themselves live in :mod:`mxnet_tpu.telemetry` — the runtime
telemetry plane (hierarchical spans, metrics registry, retrace watchdog)
shares one merged trace with this module, and ``bump()``/``counter()``
here are compatibility shims over its typed metrics registry.

Env autostart: ``MXNET_PROFILER_AUTOSTART=1`` (reference env_var.md:101).
"""
from __future__ import annotations

import os
import threading

from . import telemetry as _telemetry
from .telemetry import (bump, counter, counters, reset_counters,  # noqa: F401
                        now_us as _now_us)

__all__ = ["profiler_set_config", "set_config", "set_state", "dump_profile",
           "dump", "pause", "resume", "clear", "Marker",
           "bump", "counter", "counters", "reset_counters"]

_lock = threading.Lock()
# serializes the jax device-trace transition (flag + jax.profiler call as
# one unit) — held only on run/stop, never on the hot path
_jax_trace_lock = threading.Lock()
_state = {
    "mode": "symbolic",
    "filename": "profile.json",
    "running": False,
    "jax_trace_dir": None,
    "jax_tracing": False,
}


def profiler_set_config(mode="symbolic", filename="profile.json",
                        **kwargs):
    """Configure profiler (reference profiler.py:27).

    mode: 'symbolic' records Executor program spans only; 'all' also
    records eager op dispatches.  ``jax_trace_dir`` additionally captures
    an XLA device trace viewable in TensorBoard.
    """
    with _lock:
        _state["mode"] = mode
        _state["filename"] = filename
        _state["jax_trace_dir"] = kwargs.get("jax_trace_dir")
    _telemetry.clear_events()  # new config = new profiling session


set_config = profiler_set_config


def set_state(state="stop"):
    """'run' | 'stop' (reference profiler.py:40).

    Events accumulate across run/stop cycles (so ``pause``/``resume``
    exclude a window without losing prior spans); ``set_config`` or
    ``clear`` starts a fresh buffer.
    """
    run = state == "run"
    with _lock:
        _state["running"] = run
        tdir = _state["jax_trace_dir"]
        # mirror into telemetry under the same lock: concurrent run/stop
        # must not leave is_running() and trace_active() disagreeing
        _telemetry._set_profiler_running(run)
    # the jax_tracing flag and the jax.profiler side effect transition as
    # ONE unit under a dedicated lock: concurrent run/stop calls can
    # neither double-start the device trace nor stop it before the
    # in-flight start has actually run.  `running` is RE-READ inside the
    # lock — acting on this call's stale snapshot could start a device
    # trace after a later stop already won.
    with _jax_trace_lock:
        now_running = _state["running"]
        if now_running and tdir and not _state["jax_tracing"]:
            import jax
            jax.profiler.start_trace(tdir)
            _state["jax_tracing"] = True
        elif not now_running and _state["jax_tracing"]:
            import jax
            jax.profiler.stop_trace()
            _state["jax_tracing"] = False


def clear():
    """Drop all accumulated events."""
    _telemetry.clear_events()


def pause():
    set_state("stop")


def resume():
    set_state("run")


def is_running():
    return _state["running"]


def record_op(name, start_us, dur_us):
    """Called from the eager dispatch path (mode='all')."""
    if _state["running"] and _state["mode"] == "all":
        _telemetry.add_event(name, "operator", start_us, dur_us)


class Marker(_telemetry.span):
    """User annotation span: ``with profiler.Marker("data-load"): ...``

    Markers are telemetry spans: nested Markers record parent/depth and
    render as nested tracks, and they obey either gate (profiler running
    OR ``MXNET_TELEMETRY=1``).
    """

    def __init__(self, name, cat="user"):
        super().__init__(name, cat=cat)


def dump_profile(filename=None):
    """Write accumulated events as Chrome trace JSON
    (reference Profiler::DumpProfile, profiler.cc:127-192), including
    ``ph:"M"`` process/thread-name metadata so Perfetto labels tracks."""
    fname = filename or _state["filename"]
    return _telemetry.dump_chrome_trace(fname)


dump = dump_profile


if os.environ.get("MXNET_PROFILER_AUTOSTART", "0") == "1":
    profiler_set_config(mode=os.environ.get("MXNET_PROFILER_MODE",
                                            "symbolic"))
    set_state("run")
