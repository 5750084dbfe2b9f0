"""Runtime telemetry: hierarchical spans, metrics, retrace watchdog, exporters.

The reference engine stamps every op with ``OprExecStat`` and dumps Chrome
trace JSON (``src/engine/profiler.{h,cc}``, SURVEY §5.1).  On the TPU build
the unit of execution is a compiled XLA program, so the observability plane
is organised around four questions instead of one:

1. **Where does wall time go?**  Hierarchical spans (``span()``): a
   contextvar carries the enclosing span, so a ``trainer_step`` span
   contains its kvstore-bucket and optimizer-program children.  Spans land
   in the same Chrome ``traceEvents`` buffer the profiler always produced
   (nesting renders by time containment per tid; each event also carries
   ``args.parent``/``args.depth`` for tooling).
2. **How many programs / bytes?**  A typed metrics registry — monotonic
   :class:`Counter`, last-value :class:`Gauge`, fixed-bucket
   :class:`Histogram` — supersedes the loose ``profiler._counters`` dict.
   ``profiler.bump()/counter()`` remain as shims onto it, and the counter
   fast path stays a lock+int-add (tests hold program-count contracts to
   deltas of ``xla_program_calls``; that must never get slower or gated).
3. **What compiles, and what did a start cost?**  One compile ledger,
   always on, fed by JAX's own monitoring events: every backend compile
   in the process — the framework's, a user's own jit, an eager op's — is
   one row of :func:`compile_events` (JAX's ``fun_name``, the enclosing
   :func:`watch_jit` name and program span if any, seconds tracing,
   lowering and in the backend, and whether the persistent cache served
   it).  The listeners fire only when JAX compiles, so a steady-state
   step never reaches them.  With telemetry on, past
   ``MXNET_TELEMETRY_RETRACE_LIMIT`` compiles for one watched name the
   watchdog logs ONE structured retrace-storm warning — the signature of
   a shape-unstable input pipeline silently recompiling every step.
4. **How do I read it?**  Exporters: :func:`dump_chrome_trace` (merged
   trace + ``ph:"M"`` track-name metadata), :func:`prometheus_text`
   (text exposition), :func:`snapshot`/:func:`dump_snapshot` (JSON),
   consumed by ``tools/trace_report.py``.
5. **What was the hardware doing?**  Step-span exits close a cost window
   fed by :class:`_WatchedJit`'s XLA ``cost_analysis()`` capture: the
   gauges ``step_model_flops`` / ``step_mfu`` / ``step_hbm_bw_util``
   relate each step to the per-device peak table in
   :mod:`mxnet_tpu.telemetry.costs`.

The post-mortem / live tier lives in the sibling modules of this package:
:mod:`..flight` (always-on crash ring + dump hooks, fed from span exits
and compile events here), :mod:`..server` (the ``MXNET_TELEMETRY_HTTP``
introspection endpoints), :mod:`..costs` (MFU/roofline accounting).

Gating: ``MXNET_TELEMETRY=1`` enables spans/histograms/watchdog/memory
sampling.  Counters and the compile ledger are ALWAYS on, and so are the
spans of category ``setup`` (bind, parameter init, optimizer init, the
step's build and its first run: once a bind, never once a batch); with
telemetry off every other hook is
one cached-bool check (plus, for step/program spans, the one attribute
compare that keeps the flight recorder's progress clock ticking).  Spans
also record whenever the classic profiler is running
(``profiler.set_state('run')``), so existing profiler workflows keep
working unchanged.  A third leg needs no switch of ours: while a
JAX profiler session is open in this process, whoever opened it, spans
record — and ONLY spans (ring + ``mxnet_tpu.<name>`` annotation in the
session's own trace, on the device's clock); the watchdog, cost capture,
histograms, memory sampling and time series stay as off as they were.

This module is import-light on purpose (stdlib only; jax only touched
inside memory sampling and, lazily, by the profiler-session leg and the
compile ledger's two listeners) — every hot path in the framework
imports it.
"""
from __future__ import annotations

import contextlib
import contextvars
import json
import logging
import os
import sys
import threading
import time
from collections import deque

from . import flight as _flight

__all__ = ["enabled", "set_enabled", "configure", "trace_active",
           "span", "NO_SPAN", "now_us", "add_event", "clear_events",
           "Counter", "Gauge", "Histogram",
           "bump", "counter", "counters", "reset_counters",
           "set_gauge", "gauge", "observe", "histogram",
           "watch_jit", "compile_events", "retrace_report",
           "dump_chrome_trace", "chrome_trace_payload", "prometheus_text",
           "snapshot", "dump_snapshot", "reset", "sample_memory",
           "program_cost", "program_costs",
           "trace_context", "set_trace_context", "reset_trace_context",
           "new_trace_id", "new_span_id",
           "COUNTERS", "GAUGES", "HISTOGRAMS", "SPANS", "METRIC_NAMES"]

_LOG = logging.getLogger("mxnet_tpu.telemetry")

# --------------------------------------------------------------------------
# config / gating
# --------------------------------------------------------------------------

_TRUTHY = ("1", "true", "on", "yes")


def _env_enabled():
    return os.environ.get("MXNET_TELEMETRY", "0").strip().lower() in _TRUTHY


def _env_retrace_limit():
    try:
        return max(1, int(os.environ.get("MXNET_TELEMETRY_RETRACE_LIMIT", 5)))
    except ValueError:
        return 5


def _env_max_events():
    try:
        return max(1, int(os.environ.get("MXNET_TELEMETRY_MAX_EVENTS",
                                         200_000)))
    except ValueError:
        return 200_000


def _env_tracecheck():
    return os.environ.get("MXNET_TRACECHECK", "0").strip().lower() \
        in _TRUTHY


_ENABLED = _env_enabled()
_RETRACE_LIMIT = _env_retrace_limit()
_TRACECHECK = _env_tracecheck()
_PROF_RUNNING = False          # mirrored by profiler.set_state
# mirrored by telemetry.device (MXNET_DEVICE_TIME): the watched-jit hot
# path gates the sampled device-timing hook on this one module global
_DEVICE_TIME = False


def _set_device_time(flag):
    global _DEVICE_TIME
    _DEVICE_TIME = bool(flag)


def enabled():
    """Whether the telemetry layer (spans/histograms/watchdog) is on."""
    return _ENABLED


def set_enabled(value):
    global _ENABLED
    _ENABLED = bool(value)


def configure(enabled=None, retrace_limit=None, max_events=None):
    """Programmatic override of the MXNET_TELEMETRY* env configuration."""
    global _RETRACE_LIMIT, _events
    if enabled is not None:
        set_enabled(enabled)
    if retrace_limit is not None:
        _RETRACE_LIMIT = max(1, int(retrace_limit))
    if max_events is not None:
        cap = max(1, int(max_events))
        with _lock:
            _events = deque(list(_events)[-cap:], maxlen=cap)


def refresh_from_env():
    """Re-read MXNET_TELEMETRY / MXNET_TELEMETRY_RETRACE_LIMIT /
    MXNET_TRACECHECK / MXNET_DEVICE_TIME (and, when the cost module is
    loaded, its MXNET_PEAK_* overrides)."""
    global _ENABLED, _RETRACE_LIMIT, _TRACECHECK
    _ENABLED = _env_enabled()
    _RETRACE_LIMIT = _env_retrace_limit()
    _TRACECHECK = _env_tracecheck()
    _costs().refresh_from_env()
    dev = sys.modules.get("mxnet_tpu.telemetry.device")
    if dev is not None:
        dev.refresh_from_env()
    ts = sys.modules.get("mxnet_tpu.telemetry.timeseries")
    if ts is not None:
        ts.refresh_from_env()


def retrace_limit():
    return _RETRACE_LIMIT


def _set_profiler_running(running):
    """Called by profiler.set_state so spans honor the classic profiler."""
    global _PROF_RUNNING
    _PROF_RUNNING = bool(running)


# third leg of trace_active(): a JAX profiler session is open in this
# process (mx.profiler with jax_trace_dir, a harness's start_trace, a capture
# from a profiler server).  The answer is cached: batch-root spans ask once
# per batch (_poll_session), every other span reads _SESSION.
_SESSION = False
_session_probe = None          # TraceMe.is_enabled, bound on first poll
_annotation_cls = None         # jax.profiler.TraceAnnotation, ditto
_ANNOTATION_PREFIX = "mxnet_tpu."
# batch-root spans open on this process: lets a step span nested in a
# batch skip its own poll even when nothing records.  A plain int on
# purpose (no lock, no contextvar on the off path); a wrong value under
# concurrent fit loops costs one extra or one skipped poll, nothing else
_BATCH_OPEN = 0


def _poll_session():
    """Ask JAX whether a profiler session is open; caches and returns the
    answer.  Under 0.1 us (one C++ atomic load).  Binds lazily so this module
    never imports jax; if jax was never imported nobody can have opened a
    session, and if this jaxlib has no such call the leg is simply false."""
    global _SESSION, _session_probe, _annotation_cls
    probe = _session_probe
    if probe is None:
        if "jax" not in sys.modules:
            return False
        _bind_compile_listeners()
        try:
            from jax._src.lib import _profiler
            from jax.profiler import TraceAnnotation
            probe = _profiler.TraceMe.is_enabled
            _annotation_cls = TraceAnnotation
        except Exception:
            probe = _never
        _session_probe = probe
    _SESSION = probe()
    return _SESSION


def _never():
    return False


def trace_active():
    """True when spans should record trace events.  A cached "session
    open" is re-asked here, so a session that closed since the last batch
    root stops the recording at the next span (and no stale flag outlives
    the session)."""
    return _ENABLED or _PROF_RUNNING or (_SESSION and _poll_session())


# --------------------------------------------------------------------------
# trace-event buffer (the Chrome traceEvents the profiler always produced)
# --------------------------------------------------------------------------

_lock = threading.Lock()
# ring buffer: always-on telemetry must not grow host RSS without bound
# over a week-long run — the newest MXNET_TELEMETRY_MAX_EVENTS spans win,
# and evictions are themselves counted (trace_events_dropped)
_events = deque(maxlen=_env_max_events())
_tid_cats = {}                     # tid -> set of categories seen on it
_t0 = time.perf_counter()

# track labels per span category: chrome://tracing / Perfetto show these as
# the thread-name of each tid's track.  One thread usually hosts several
# categories (its spans nest on one track — that containment is also what
# trace_report's self-time sweep relies on), so the label is chosen at
# dump time from the highest-priority category the tid hosted.
_CAT_TRACK = {"operator": "eager-dispatch", "program": "executor",
              "step": "train-step", "batch": "train-step",
              "host": "host-phase", "kvstore": "kvstore", "io": "data-io",
              "compile": "jit-compile", "serving": "serving",
              "rpc": "dist-rpc", "setup": "set-up", "user": "user"}
_CAT_PRIORITY = ("step", "batch", "serving", "program", "kvstore", "io",
                 "operator", "rpc", "setup", "compile", "host", "user")


def now_us():
    return (time.perf_counter() - _t0) * 1e6


# the flight ring timestamps with this module's clock so its entries line
# up with the Chrome trace events
_flight.set_clock(now_us)


# os.getpid() is a system call, and a slow one under a sandboxed kernel
# (~6 us on the chip's host, most of what recording one event cost): read
# it once, and again in a forked child
_PID = os.getpid()


def _refresh_pid():
    global _PID
    _PID = os.getpid()


os.register_at_fork(after_in_child=_refresh_pid)


def add_event(name, cat, start_us, dur_us, tid=None, args=None):
    """Append one complete ('X') event to the trace buffer.

    The append happens under the buffer lock: a concurrent
    ``dump_chrome_trace`` iterates the ring, and deque iteration raises
    if it races a mutation.  Events are only recorded while tracing is
    active, so the lock never touches the telemetry-off path.
    """
    if tid is None:
        tid = threading.get_ident() % 10000
    ev = {"name": name, "cat": cat, "ph": "X", "ts": start_us,
          "dur": dur_us, "pid": _PID, "tid": tid}
    if args:
        ev["args"] = args
    with _lock:
        _tid_cats.setdefault(tid, set()).add(cat)
        dropped = len(_events) == _events.maxlen   # ring evicts the oldest
        _events.append(ev)
    if dropped:
        bump("trace_events_dropped")


def clear_events():
    with _lock:
        _events.clear()
        _tid_cats.clear()


# --------------------------------------------------------------------------
# hierarchical spans
# --------------------------------------------------------------------------

_SPAN_STACK = contextvars.ContextVar("mxnet_tpu_span_stack", default=())


def current_span():
    """Name of the innermost open span on this context (None outside)."""
    stack = _SPAN_STACK.get()
    return stack[-1] if stack else None


# --------------------------------------------------------------------------
# trace context (distributed tracing)
# --------------------------------------------------------------------------
#
# One trace id names one logical unit of work across processes: a
# training step (minted by its step span), a serving request (minted at
# submit), an RPC (minted per frame when nothing is active).  dist_ps
# propagates it on the wire; trace_report --fleet joins the per-rank
# traces back together on it.

_TRACE_CTX = contextvars.ContextVar("mxnet_tpu_trace_id", default=None)


def trace_context():
    """The active trace id on this context (None outside any trace)."""
    return _TRACE_CTX.get()


def set_trace_context(trace_id):
    """Adopt *trace_id* (e.g. one received over the wire); returns the
    reset token."""
    return _TRACE_CTX.set(trace_id)


def reset_trace_context(token):
    try:
        _TRACE_CTX.reset(token)
    except ValueError:        # token from another context: best effort
        pass


def new_trace_id():
    """16-hex-char process-unique trace id."""
    return os.urandom(8).hex()


def new_span_id():
    """8-hex-char span id (send/recv flow pairing)."""
    return os.urandom(4).hex()


# What a per-batch hot path enters in place of a child span when its
# batch root found nothing recording: ``with span(<name>) if rec else
# NO_SPAN:`` is one bool test and no allocation (a span object costs
# ~0.5 us to build and enter even when inert).  The call site keeps its
# span literal, so the static name gate still sees the name.
NO_SPAN = contextlib.nullcontext()

# the id minted by the batch-root span open on this context (None outside
# one): a step span nested in a batch carries the batch's id, not its own
_BATCH_ID = contextvars.ContextVar("mxnet_tpu_batch_id", default=None)


class span:
    """Hierarchical timed span: ``with telemetry.span("trainer_step"): ...``

    Nesting is carried by a contextvar (so it survives thread-pool hops
    that copy context), and recorded two ways: structurally via
    ``args.parent``/``args.depth``, and visually via time containment on
    the owning thread's track.  While recording, the span also enters a
    ``jax.profiler.TraceAnnotation`` named ``mxnet_tpu.<name>``: if a
    profiler session is open the same span is in its trace, on the
    device's clock.  Off path (telemetry off, profiler stopped, no
    session) is one bool check.

    ``cat="setup"`` spans record whatever the switches say: they run once
    a bind (``module_bind`` ... ``module_first_step``), a dozen times a
    process, and are how a start is read afterwards (the compile
    ledger's rows name the one they happened under).

    Batch roots — ``cat="batch"`` spans, and ``cat="step"`` spans outside
    one — ask once whether a profiler session is open; everything under
    them reads the cached answer.  A session alone records spans and
    nothing else (no step window, no histogram, no memory sample, no
    time-series row, the flight ring as with telemetry off).

    *hist*: name of a registered histogram to observe with the span's
    duration (µs).  *memory*: sample host/device memory watermarks at span
    exit (step-boundary spans only; it costs a getrusage + device query).
    *args*: extra key/values for the trace event (e.g. bucket bytes).
    """

    __slots__ = ("_name", "_cat", "_hist", "_memory", "_args",
                 "_on", "_t0", "_tok", "_parent", "_trace_tok",
                 "_batch_tok", "_full", "_window", "_ann")

    def __init__(self, name, cat="user", hist=None, memory=False, args=None):
        self._name = name
        self._cat = cat
        self._hist = hist
        self._memory = memory
        self._args = args

    def __enter__(self):
        global _BATCH_OPEN
        cat = self._cat
        # trace_active(), inlined — except that a batch root asks where
        # everything else reads (and re-asks only a cached "open")
        if cat == "batch":
            _BATCH_OPEN += 1
            session = _poll_session()
        elif cat == "step" and not _BATCH_OPEN:
            session = _poll_session()
        else:
            session = _SESSION and _poll_session()
        if not (_ENABLED or _PROF_RUNNING or session or cat == "setup"):
            self._on = False
            self._t0 = None
            if _DEVICE_TIME and cat == "step":
                # device-time attribution works with the trace buffer
                # off: the window still opens so sampled programs are
                # decomposed (the span itself records nothing)
                _open_step_window()
                self._t0 = now_us()
            return self
        self._on = True
        # the clock starts before, and the annotation opens right after,
        # the span's own bookkeeping (and at exit the other way round):
        # a span's cost falls inside the span, so a parent's self time is
        # code no child covers, not tracer overhead
        self._t0 = now_us()
        self._trace_tok = self._batch_tok = None
        if cat == "batch":
            trace_id = new_trace_id()
            self._trace_tok = _TRACE_CTX.set(trace_id)
            self._batch_tok = _BATCH_ID.set(trace_id)
        elif cat == "step":
            # one trace id per step: RPCs issued inside (kvstore push/
            # pull over dist_ps) inherit it, so --fleet can join the
            # step's spans across ranks.  Steps are trace ROOTS unless a
            # batch root is open — mint regardless of the ambient id: one
            # adopted from an earlier RPC reply (recv sets the contextvar)
            # must not glue every step of the run into one giant trace
            trace_id = _BATCH_ID.get() or new_trace_id()
            self._trace_tok = _TRACE_CTX.set(trace_id)
        else:
            trace_id = _TRACE_CTX.get()
        if _session_probe is None:
            _poll_session()            # binds the annotation class
        self._ann = None
        if _annotation_cls is not None:
            name = _ANNOTATION_PREFIX + self._name
            self._ann = _annotation_cls(name) if trace_id is None \
                else _annotation_cls(name, trace_id=trace_id)
            self._ann.__enter__()
        stack = _SPAN_STACK.get()
        self._parent = stack[-1] if stack else None
        self._tok = _SPAN_STACK.set(stack + (self._name,))
        # everything beyond the span itself needs one of our own switches
        self._full = full = _ENABLED or _PROF_RUNNING
        self._window = cat == "step" and (full or _DEVICE_TIME)
        if self._window:
            _open_step_window()
        return self

    def __exit__(self, exc_type, exc, tb):
        global _BATCH_OPEN
        cat = self._cat
        if cat == "batch" and _BATCH_OPEN:
            _BATCH_OPEN -= 1
        if not self._on:
            # telemetry off: the flight recorder's progress clock still
            # ticks for coarse spans (step/program exits are what the
            # hang watchdog and /healthz reason about) — one string
            # compare, no timing, no lock
            # gate on the OPENED window (_t0), not the live flag:
            # disabling device timing mid-span must not leak the step
            # depth the matching open incremented
            if self._t0 is not None:
                _close_step_window(now_us() - self._t0)
            if cat in ("step", "program"):
                _flight.note_span(self._name, cat)
            return False
        _SPAN_STACK.reset(self._tok)
        args = {"parent": self._parent,
                "depth": len(_SPAN_STACK.get())}
        trace_id = _TRACE_CTX.get()
        if trace_id is not None:
            args["trace_id"] = trace_id
        if self._args:
            args.update(self._args)
        dur = now_us() - self._t0
        add_event(self._name, cat, self._t0, dur, args=args)
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        if self._full:
            _flight.note_span(self._name, cat, dur)
        elif cat in ("step", "program"):
            _flight.note_span(self._name, cat)
        if self._window:
            _close_step_window(dur)
        if self._trace_tok is not None:
            reset_trace_context(self._trace_tok)
        if self._batch_tok is not None:
            _BATCH_ID.reset(self._batch_tok)
        if self._hist is not None and _ENABLED:
            observe(self._hist, dur)
        if self._memory and _ENABLED:
            sample_memory()
        return False


# --------------------------------------------------------------------------
# metrics registry
# --------------------------------------------------------------------------
#
# Declarations first: every metric name the framework itself uses MUST be
# listed here — tests/test_telemetry.py statically scans mxnet_tpu/ for
# bump()/counter()/observe()/set_gauge() string literals and asserts
# membership, so a typo'd counter name fails CI instead of silently
# splitting a time series.

COUNTERS = {
    "xla_program_calls": "XLA programs launched (perf-contract currency)",
    "kvstore_push": "kvstore push operations (per key)",
    "kvstore_pull": "kvstore pull broadcast copies (per destination)",
    "kvstore_bucket_reduce": "bucketed gradient-reduce programs",
    "kvstore_reduce_scatter": "bucketed reduce-scatter rounds (ZeRO-1 "
                              "gradient leg: reduce + per-replica "
                              "row placement)",
    "trainer_zero_step": "fused Trainer steps run with the MXNET_ZERO "
                         "sharded weight update",
    "kvstore_push_bytes": "bytes entering kvstore reduction",
    "kvstore_pull_bytes": "bytes broadcast out of the kvstore",
    "kvstore_reduce_bytes": "payload bytes moved through bucket reduces",
    "optimizer_update": "eager per-slot optimizer updates",
    "trainer_fused_step": "fused whole-model Trainer steps",
    "module_train_step": "Module CachedTrainStep executions",
    "module_step_carried": "CachedTrainStep executions that took every "
                           "param, aux and optimizer-state input by "
                           "identity from the previous step (no _place)",
    "fit_step_overlapped": "Module.fit batches whose step was enqueued "
                           "before the previous batch's metric was read "
                           "(the fit loop's one step of overlap)",
    "executor_remat_segments": "recomputation segments (force_mirroring "
                               "+ mirror_stage) wrapped in jax.checkpoint "
                               "at bind: the backward replays each but "
                               "for the values counted below",
    "executor_remat_kept": "values that ops traced inside a recomputation "
                           "segment marked to be kept for the backward "
                           "instead of replayed (ops/remat.py:keep: two an "
                           "attention op on the kernel path, four a "
                           "SparseMoE op, one a PowerRetention or a "
                           "StateSpaceScan op), summed "
                           "over traces",
    "power_retention_traced": "_contrib_PowerRetention ops traced (the "
                              "chunked state form)",
    "power_retention_chunks": "chunks a sequence over all traced "
                              "_contrib_PowerRetention ops",
    "power_retention_states_traced": "retention backward rules traced: each "
                                     "remakes the chunk-start states from "
                                     "k, v and the gate by the states-only "
                                     "pass (the forward saves none)",
    "sparse_moe_traced": "_contrib_SparseMoE ops traced (dropless top-k "
                         "routing, grouped matrix product)",
    "sparse_moe_rows": "routed rows (tokens x experts a token) over all "
                       "traced _contrib_SparseMoE ops: every one is in "
                       "a group, none is dropped",
    "sparse_moe_held_rows_budget": "rows of one chunk of a partial share's "
                                   "sorted order (ops/moe.py:_share_rows), "
                                   "summed over the SparseMoE ops traced "
                                   "that hold fewer experts than they route",
    "causal_attention_traced": "_contrib_CausalAttention ops traced without "
                               "a window",
    "window_attention_traced": "_contrib_CausalAttention ops traced with a "
                               "sliding window (banded kernels on a TPU)",
    "short_conv_traced": "_contrib_ShortConv ops traced",
    "causal_conv_traced": "_contrib_CausalConv1D ops traced",
    "causal_conv_kernel_traced": "_contrib_CausalConv1D and "
                                 "_contrib_ShortConv ops traced on the "
                                 "Pallas path (causal_conv_fwd / "
                                 "causal_conv_bwd: a TPU, channels in "
                                 "whole lane tiles, the sequence in whole "
                                 "tiles); the others run the jnp form",
    "state_space_traced": "_contrib_StateSpaceScan ops traced (the chunked "
                          "dual form)",
    "state_space_chunks": "chunks a sequence over all traced "
                          "_contrib_StateSpaceScan ops",
    "state_space_states_traced": "state-space backward rules traced: each "
                                 "remakes the chunk-start states from x, "
                                 "dt and b by the states pass (the forward "
                                 "saves none)",
    "lm_head_fused_traced": "_contrib_BlockedSoftmaxCE forward rules traced "
                            "(under differentiation: loss, dh and dW in "
                            "one scan; the undifferentiated op does not "
                            "count)",
    "batchnorm_onepass_traced": "training BatchNorm ops traced (one-pass "
                                "float32 moments, hand-derived VJP)",
    "eager_invocations": "eager op dispatches through ndarray.invoke",
    "io_batches": "data batches produced by iterators",
    "jit_compiles": "backend compiles (cache loads included) that "
                    "happened inside a watched jit's call: rows of the "
                    "compile ledger that carry a watch name",
    "compile_cache_hits": "backend compiles the persistent compilation "
                          "cache served (ledger rows with cache == hit)",
    "compile_cache_misses": "backend compiles XLA ran with the persistent "
                            "cache on (ledger rows with cache == miss): 0 "
                            "over a start is a warm start",
    "retrace_storms": "watched callables that crossed the retrace limit",
    "trace_events_dropped": "spans evicted from the bounded trace ring",
    "sanitizer_violations": "footguns caught at runtime by MXNET_SANITIZE "
                            "(tracer leaks, syncs-under-trace, engine "
                            "ordering)",
    "lockcheck_violations": "lock acquisition-order inversions witnessed "
                            "live by MXNET_LOCKCHECK (the runtime side "
                            "of the JG009 static cycle check)",
    "flight_dumps": "flight-recorder post-mortem files written (crash, "
                    "signal, hang, or manual)",
    "tracecheck_findings": "trace-tier (JX rule) findings booked by the "
                           "MXNET_TRACECHECK compile hook",
    "serving_requests": "predict requests accepted into a serving queue",
    "serving_batches": "coalesced batches dispatched by the serving "
                       "scheduler",
    "serving_overloads": "requests shed (503) by a full bounded serving "
                         "queue",
    "serving_errors": "predict requests that finished with an error",
    "serving_straight_through": "oversize requests run unpadded outside "
                                "the bucket table (the jit escape hatch)",
    "serving_padded_rows": "padding rows added to reach serving bucket "
                           "boundaries (throughput spent on waste)",
    "serving_warmup_compiles": "AOT bucket variants compiled at model "
                               "load/warmup",
    "checkpoint_saves": "checkpoints committed to disk (periodic async "
                        "or SIGTERM-final synchronous)",
    "checkpoint_restores": "successful CheckpointManager.restore() "
                           "loads",
    "checkpoint_write_retries": "transient checkpoint write failures "
                                "retried with backoff",
    "checkpoint_restore_fallbacks": "corrupt/partial checkpoints skipped "
                                    "in favor of an older complete one",
    "serving_deadline_drops": "queued predict requests dropped un-run "
                              "because their deadline passed before "
                              "dispatch",
    "serving_breaker_opens": "circuit-breaker open transitions after "
                             "consecutive serving batch failures",
    "serving_breaker_shed": "predict requests shed (503) by an open "
                            "serving circuit breaker",
    "chaos_faults": "faults injected by the MXNET_CHAOS chaos tier "
                    "(each also lands in the flight ring)",
    "ps_rpc_timeouts": "dist transport RPC recvs that hit the "
                       "MXNET_PS_RPC_TIMEOUT_S deadline",
    "ps_rpc_retries": "idempotent dist RPCs retried on a fresh "
                      "connection (backoff + jitter)",
    "ps_peer_lost": "structured PeerLost errors raised by the dist "
                    "transport (dead/silent peers, failed barriers)",
    "ps_reconnects": "dist server connections re-established after a "
                     "failure or refresh_servers recovery",
    "ps_heartbeats": "heartbeat frames sent to the dist scheduler",
    "guardian_checks": "trainer steps whose finite-health verdict the "
                       "guardian evaluated",
    "guardian_skipped_steps": "optimizer updates suppressed in-program "
                              "by a nonfinite gradient/loss verdict",
    "guardian_loss_spikes": "applied steps whose loss exceeded the EWMA "
                            "spike factor (blocks last-good pinning)",
    "guardian_rollbacks": "automatic restores to the last-good pinned "
                          "checkpoint after an exhausted skip budget",
    "guardian_scale_cuts": "dynamic loss-scale halvings on overflow",
    "guardian_scale_growths": "dynamic loss-scale doublings after a "
                              "clean growth interval",
    "metric_nonfinite_updates": "EvalMetric updates excluded from "
                                "running sums because their "
                                "contribution was NaN/Inf",
    "device_time_samples": "watched-jit calls block_until_ready-timed "
                           "by the MXNET_DEVICE_TIME sampler",
    "ps_fleet_syncs": "fleet_sync exchanges completed on the heartbeat "
                      "link (digest out, peer/fleet tables + scheduler "
                      "clock back)",
    "fleet_requests": "predict requests accepted by the serving fleet "
                      "router",
    "fleet_hedges": "hedged duplicate attempts fired after the "
                    "p99-derived hedge timeout (first reply wins)",
    "fleet_failovers": "predict attempts re-routed to another replica "
                       "after a replica failure or not-ready reply",
    "fleet_errors": "fleet predict requests that ultimately failed "
                    "(every failover/hedge exhausted or deadline hit)",
    "fleet_shed": "fleet predict requests refused with no routable "
                  "replica (all dead, not-ready, or breaker-open)",
    "fleet_replica_deaths": "replicas declared dead by the router "
                            "(heartbeat disconnect or staleness)",
    "fleet_registrations": "replica registrations accepted by the "
                           "router (including re-registrations into a "
                           "dead rank)",
    "fleet_reloads": "per-replica reload RPCs completed during rolling "
                     "rollouts",
    "replica_predicts": "predict RPCs served by this replica process",
    "overlap_bucket_dispatches": "gradient-bucket reduces dispatched as "
                                 "engine tasks under backward "
                                 "(comm/compute overlap)",
    "overlap_steps": "trainer steps that consumed an overlapped "
                     "bucket-reduce session at drain",
    "overlap_fallbacks": "armed overlap sessions discarded at drain "
                         "(changed slot set, re-written gradient, "
                         "flipped ZeRO plan) — the step fell back to "
                         "the synchronous round",
    "collective_chunk_programs": "chunk-sum programs launched by the "
                                 "chunked collective path (pipelined "
                                 "reduce, arXiv 2112.01075)",
    "collective_gather_home": "sharded arrays streamed home chunk by "
                              "chunk (the chunked all-gather leg)",
    "collective_redistribute": "arrays re-placed onto a new sharding "
                               "through the chunked redistribution "
                               "schedule",
    "model_stats_records": "model-health stats blocks fetched and "
                           "recorded (MXNET_MODEL_STATS due steps)",
    "timeseries_evictions": "points evicted from full time-series rings "
                            "(ring capacity: MXNET_TIMESERIES_STEPS)",
}

GAUGES = {
    "io_batch_wait_us": "time the training loop waited for the last batch "
                        "(data starvation when this rivals step time)",
    "host_rss_peak_bytes": "process peak resident set size",
    "device_bytes_in_use": "device allocator bytes in use, summed over "
                           "local devices (0 if the backend does not "
                           "report memory stats)",
    "device_bytes_in_use_peak": "high-water bytes in use on the most "
                                "loaded single local device",
    "engine_pending_tasks": "host-engine tasks queued or running "
                            "(sampled by the introspection sampler and "
                            "at step-span exits)",
    "step_rate_per_s": "training steps completed per second over the "
                       "sampler's last window",
    "step_model_flops": "model FLOPs executed by compiled programs "
                        "during the last step span (XLA cost_analysis)",
    "step_mfu": "model FLOP utilization of the last step against the "
                "device peak (0-1; MXNET_PEAK_FLOPS overrides)",
    "step_hbm_bw_util": "HBM bandwidth utilization of the last step "
                        "against the device peak (0-1; "
                        "MXNET_PEAK_HBM_BW overrides)",
    "serving_queue_depth": "requests waiting in serving queues, summed "
                           "over model slots",
    "serving_models_loaded": "model slots currently loaded in the "
                             "serving registry",
    "checkpoint_last_step": "training step of the last committed (or "
                            "restored) checkpoint",
    "checkpoint_write_seconds": "background-writer wall seconds for the "
                                "last committed checkpoint",
    "checkpoint_bytes": "total serialized bytes of the last committed "
                        "checkpoint (all shards + manifest'd files)",
    "ps_dead_peers": "peers the dist scheduler currently considers dead "
                     "(live on the scheduler; a worker's cached view "
                     "elsewhere)",
    "guardian_loss_scale": "current guardian loss scale (1.0 when "
                           "scaling is off)",
    "guardian_consecutive_skips": "steps skipped in a row by the "
                                  "guardian (rollback fires at "
                                  "MXNET_GUARDIAN_MAX_SKIPS)",
    "guardian_loss_ewma": "the guardian's EWMA loss baseline for spike "
                          "detection",
    "checkpoint_pinned_step": "the last-good checkpoint step pinned "
                              "against retention (guardian rollback "
                              "target)",
    "zero_shards": "replica count of the active MXNET_ZERO sharded "
                   "weight update (0/absent when replicated)",
    "zero_optimizer_bytes_per_device": "optimizer-state bytes resident "
                                       "per device under the active "
                                       "ZeRO-1 layout",
    "zero_optimizer_bytes_replicated": "optimizer-state bytes a fully "
                                       "replicated layout would hold "
                                       "per device (the ZeRO-1 "
                                       "denominator)",
    "step_data_wait_us": "data-wait segment of the last sampled step "
                         "timeline (io_batch_wait at window open)",
    "step_host_us": "host-gap segment of the last sampled step timeline "
                    "(wall minus device minus collective)",
    "step_device_us": "device-compute segment of the last sampled step "
                      "timeline (blocked compute-program time)",
    "step_collective_us": "collective-comm segment of the last sampled "
                          "step timeline (blocked kvstore-program time)",
    "overlap_ratio": "fraction of the last sampled step's collective "
                     "time hidden under compute (0-1; the ROADMAP "
                     "item-2 win condition)",
    "ps_clock_offset_us": "this rank's estimated trace-clock offset to "
                          "the dist scheduler (RTT-midpoint method)",
    "ps_clock_rtt_us": "round-trip time of the last scheduler clock "
                       "exchange (offset error is bounded by RTT/2)",
    "fleet_replicas_ready": "replicas the serving fleet router currently "
                            "routes traffic to",
    "fleet_replicas_total": "replicas registered with the serving fleet "
                            "router (any state, including dead)",
    "fleet_outstanding": "predict attempts in flight across all "
                         "replicas (the least-outstanding balancing "
                         "signal, summed)",
    "overlap_hidden_us": "collective wall time of the last drained "
                         "step that ran under backward (overlapped "
                         "bucket reduces completed before the drain)",
    "overlap_exposed_us": "collective wall time of the last drained "
                          "step paid inside the step (drain wait + "
                          "buckets that could not run off-thread)",
}

# fixed bucket edges (upper bounds; +Inf is implicit)
_US_BUCKETS = (50.0, 100.0, 250.0, 500.0, 1e3, 2.5e3, 5e3, 1e4, 2.5e4,
               5e4, 1e5, 2.5e5, 5e5, 1e6, 5e6)
_BYTE_BUCKETS = (1 << 10, 16 << 10, 256 << 10, 1 << 20, 4 << 20, 16 << 20,
                 64 << 20, 256 << 20)

_PCT_BUCKETS = (10.0, 25.0, 50.0, 75.0, 90.0, 100.0)

HISTOGRAMS = {
    "step_time_us": ("trainer/module step wall time", _US_BUCKETS),
    "eager_dispatch_us": ("eager op dispatch latency", _US_BUCKETS),
    "jit_compile_us": ("trace + lowering + backend time of a compile "
                       "ledger row", _US_BUCKETS),
    "bucket_bytes": ("kvstore bucket payload sizes", _BYTE_BUCKETS),
    "serving_latency_us": ("predict request latency, submit to result",
                           _US_BUCKETS),
    "serving_batch_occupancy": ("dispatched rows as a percent of bucket "
                                "capacity per serving batch",
                                _PCT_BUCKETS),
    "device_time_us": ("sampled per-program device execution time "
                       "(block-until-ready delta)", _US_BUCKETS),
    "serving_queue_wait_us": ("request queue wait, submit to batch "
                              "dispatch", _US_BUCKETS),
    "serving_execute_us": ("serving batch execute segment (dispatch "
                           "wall; true device time on sampled batches "
                           "under MXNET_DEVICE_TIME)", _US_BUCKETS),
    "fleet_request_us": ("fleet predict latency at the router, accept "
                         "to first winning reply (hedges and failovers "
                         "included)", _US_BUCKETS),
}

# Span names the framework itself emits (``span("...")`` literals).
# Declared for the same reason the metrics are: a typo'd span name
# silently splits trace_report's self-time series, so the static gate
# in tests/test_telemetry.py checks every literal against this table.
# (Dynamic span names — the executor's per-program labels — are booked
# through watch_jit names instead and are out of the literal gate's
# reach by construction.)
SPANS = {
    "trainer_step": "one Trainer.step (the step-timeline anchor)",
    "data_batch": "one data-iterator batch production (io tier)",
    "fit_batch": "one Module.fit iteration, prepare to the batch-end "
                 "callback's return (batch root: mints the batch's id; "
                 "nbatch is the batch whose step it enqueues)",
    "fit_update_metric": "the fit loop's metric update for one batch "
                         "(overlapped: the batch before the root's)",
    "fit_callback": "the fit loop's batch-end callbacks for one batch "
                    "(overlapped: the batch before the root's)",
    "metric_wait": "a metric blocked until a device array is ready "
                   "(the device is still busy: overlap)",
    "metric_fetch": "a metric's device-to-host copy of a ready array",
    "module_bind": "set-up: Module.bind, executors made for the input "
                   "shapes (category setup: recorded whatever the switch)",
    "module_init_params": "set-up: Module.init_params, host fill and "
                          "placement",
    "init_params_host": "set-up: the initializer (or the given dicts' "
                        "copies) over the host arrays",
    "init_params_place": "set-up: the host arrays onto the executors' "
                         "devices (exec group set_params)",
    "module_init_optimizer": "set-up: Module.init_optimizer (kvstore, "
                             "optimizer, updater)",
    "module_step_build": "set-up: the fused train step made for an "
                         "executor group (CachedTrainStep; on the SPMD "
                         "group also the parameters' placement over the "
                         "mesh)",
    "module_first_step": "set-up: the CachedTrainStep.run that finds no "
                         "compiled step — trace, lower, compile or cache "
                         "load, and the first run to its end",
    "module_train_step": "one Module cached train step (host side)",
    "module_step_feed": "module step: batch into the executor's arg_dict",
    "module_step_place_batch": "module step: data/label onto the "
                               "executor's device(s)",
    "module_step_hyper": "module step: optimizer state check, lr/wd/"
                         "update-count bookkeeping",
    "module_step_place_params": "module step: identity checks of "
                                "params, aux and optimizer state against "
                                "the previous step's outputs; _place of "
                                "any that are not",
    "module_step_rng": "module step: random's root key taken on loan "
                       "(placed if it is not the previous step's)",
    "module_step_enqueue": "module step: the fused program's call "
                           "(flatten, hyper transfer, launch)",
    "module_step_writeback": "module step: new params/aux/state and "
                             "outputs back into their handles",
    "kvstore_push_pull": "gradient reduce round inside a step",
    "kvstore_bucket_reduce": "one bucketed reduce program (also a "
                             "counter)",
    "optimizer_update": "eager per-slot optimizer update",
    "fused_optimizer_step": "the fused whole-model update program",
    "serving_run_batch": "one coalesced serving batch, dispatch to "
                         "futures resolved",
    "serving_pad": "pad + device_put segment of a serving batch",
    "serving_execute": "executable-call segment of a serving batch",
    "serving_slice": "result slice/host-transfer segment of a serving "
                     "batch",
    "fleet_route": "one fleet-routed predict request, router side "
                   "(accept to winning reply or final failure)",
}

METRIC_NAMES = frozenset(COUNTERS) | frozenset(GAUGES) \
    | frozenset(HISTOGRAMS) | frozenset(SPANS)


class Counter:
    """Monotonic counter view (the value lives in the registry dict so the
    bump fast path stays a plain int add under the registry lock)."""

    __slots__ = ("name", "help")

    def __init__(self, name, help=""):
        self.name, self.help = name, help

    def inc(self, n=1):
        bump(self.name, n)

    @property
    def value(self):
        return counter(self.name)


class Gauge:
    """Last-value gauge."""

    __slots__ = ("name", "help")

    def __init__(self, name, help=""):
        self.name, self.help = name, help

    def set(self, value):
        set_gauge(self.name, value)

    @property
    def value(self):
        return gauge(self.name)


class Histogram:
    """Fixed-bucket histogram: cumulative-style buckets + sum + count."""

    __slots__ = ("name", "help", "buckets", "counts", "total", "count")

    def __init__(self, name, help="", buckets=_US_BUCKETS):
        self.name, self.help = name, help
        self.buckets = tuple(sorted(buckets))
        self.counts = [0] * (len(self.buckets) + 1)   # last = +Inf
        self.total = 0.0
        self.count = 0

    def observe(self, value):
        with _mlock:
            self._observe(value)

    def _observe(self, value):
        i = 0
        for i, edge in enumerate(self.buckets):       # noqa: B007
            if value <= edge:
                break
        else:
            i = len(self.buckets)
        self.counts[i] += 1
        self.total += value
        self.count += 1

    def percentile(self, q):
        """Approximate percentile from bucket boundaries (upper edge of
        the bucket containing the q-quantile observation)."""
        if self.count == 0:
            return 0.0
        rank = q / 100.0 * self.count
        seen = 0
        for i, c in enumerate(self.counts):
            seen += c
            if seen >= rank and c:
                return self.buckets[i] if i < len(self.buckets) \
                    else float("inf")
        return float("inf")

    def to_dict(self):
        return {"buckets": list(self.buckets), "counts": list(self.counts),
                "sum": self.total, "count": self.count}


_mlock = threading.Lock()
_counters = {}                 # name -> int
_gauges = {}                   # name -> float
_hists = {}                    # name -> Histogram


def bump(name, n=1):
    """Increment a named monotonic counter.

    ALWAYS on (no gating on ``enabled()``): counters are how tests and
    benches prove call-count claims — e.g. the fused Trainer step's
    "one XLA program per step" contract gates on the
    ``xla_program_calls`` delta across a step.
    """
    with _mlock:
        _counters[name] = _counters.get(name, 0) + n


def counter(name):
    """Current value of one counter (0 if never bumped)."""
    return _counters.get(name, 0)


def counters():
    """Snapshot of all counters."""
    with _mlock:
        return dict(_counters)


def reset_counters():
    with _mlock:
        _counters.clear()


def set_gauge(name, value):
    _gauges[name] = float(value)


def gauge(name, default=0.0):
    return _gauges.get(name, default)


def histogram(name):
    """The named Histogram, creating it from the declaration table (or
    with default µs buckets for ad-hoc names)."""
    h = _hists.get(name)
    if h is None:
        with _mlock:
            h = _hists.get(name)
            if h is None:
                help_, buckets = HISTOGRAMS.get(name, ("", _US_BUCKETS))
                h = _hists[name] = Histogram(name, help_, buckets)
    return h


def observe(name, value):
    histogram(name).observe(value)


# --------------------------------------------------------------------------
# compile ledger + retrace watchdog
# --------------------------------------------------------------------------
#
# JAX tells a start in events (jax 0.9.0: jax/_src/dispatch.py, compiler.py,
# compilation_cache.py), on the thread that compiles and in this order:
#   duration  /jax/core/compile/jaxpr_trace_duration          fun_name=f
#             (inner jits first, then the function that holds them; the
#             lowering rules that follow trace too, inside the next event)
#   duration  /jax/core/compile/jaxpr_to_mlir_module_duration fun_name=jit(f)
#   event     /jax/compilation_cache/compile_requests_use_cache
#   on a hit: event .../cache_hits, duration .../compile_time_saved_sec,
#             duration .../cache_retrieval_time_sec
#   duration  /jax/core/compile/backend_compile_duration      fun_name=jit(f)
#             (XLA's compile, or on a hit the retrieval)
# The backend event closes one row; what came before it on the thread is
# that row's.  Nothing here is reached by a call that compiles nothing.

_EV_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_EV_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_EV_BACKEND = "/jax/core/compile/backend_compile_duration"
_EV_CACHE_USED = "/jax/compilation_cache/compile_requests_use_cache"
_EV_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_EV_CACHE_SAVED = "/jax/compilation_cache/compile_time_saved_sec"

_MAX_COMPILE_ROWS = 16384      # newest rows win; the per-name sums keep all

_compile_lock = threading.Lock()
_compiles = {}                 # name -> sums (see _new_sums)
_compile_log = deque(maxlen=_MAX_COMPILE_ROWS)
_storm_warned = set()
_pending = threading.local()   # this thread's open row + rows it closed
_LISTENING = False


def _bind_compile_listeners():
    """Register the ledger's two listeners with ``jax.monitoring``, once.
    Called at import, and again from the lazy binders, so this module still
    imports (and a process that never loads jax still runs) without it."""
    global _LISTENING
    if _LISTENING or "jax" not in sys.modules:
        return
    try:
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(_on_duration)
        monitoring.register_event_listener(_on_event)
        _LISTENING = True
    except Exception:          # no monitoring: the ledger stays empty
        pass


def _on_event(event, **kwargs):
    if event == _EV_CACHE_USED:
        _pending.cache = "miss"        # until a hit says otherwise
    elif event == _EV_CACHE_HIT:
        _pending.cache = "hit"


def _on_duration(event, duration, **kwargs):
    if event == _EV_BACKEND:
        _close_row(kwargs.get("fun_name"), duration)
    elif event == _EV_TRACE:
        # inner jits report before the function that holds them, and
        # lowering rules trace too: keep them by name for the lowering
        # event to pick its own
        _pending.__dict__.setdefault("traces", {})[
            kwargs.get("fun_name")] = duration
    elif event == _EV_LOWER:
        pend = _pending.__dict__
        name = kwargs.get("fun_name") or ""
        traces = pend.pop("traces", None) or {}
        # "jit(f)" was traced as "f"
        pend.setdefault("lowered", {})[name] = (
            traces.get(name[name.find("(") + 1:-1], 0.0), duration)
    elif event == _EV_CACHE_SAVED:
        _pending.saved_s = duration


def _watch_on_stack():
    """Name of the innermost watched jit whose call is on this thread's
    stack.  Walked at a compile only, so that a watched call that compiles
    nothing pays nothing for the answer."""
    code = _WatchedJit.__call__.__code__
    frame = sys._getframe(2)
    while frame is not None:
        if frame.f_code is code:
            return frame.f_locals["self"]._name
        frame = frame.f_back
    return None


def _new_sums():
    return {"count": 0, "trace_s": 0.0, "lower_s": 0.0, "backend_s": 0.0,
            "saved_s": 0.0, "hit": 0, "miss": 0, "off": 0}


def _total_ms(rec):
    return (rec["trace_s"] + rec["lower_s"] + rec["backend_s"]) * 1e3


def _close_row(fun_name, backend_s):
    """One backend compile (or cache load) ended on this thread: book the
    row, the sums per name, the counters and — while tracing — the
    ``compile:<name>`` ring event."""
    pend = _pending.__dict__
    trace_s, lower_s = pend.get("lowered", {}).pop(fun_name, (0.0, 0.0))
    row = {"fun_name": fun_name, "watch": _watch_on_stack(),
           "span": current_span(),
           "trace_s": trace_s, "lower_s": lower_s,
           "backend_s": backend_s,
           "cache": pend.pop("cache", "off"),
           "saved_s": pend.pop("saved_s", 0.0),
           "ts": now_us() - backend_s * 1e6}
    pend["rows"] = pend.get("rows", 0) + 1
    name = row["watch"] or fun_name or "?"
    total_s = row["trace_s"] + row["lower_s"] + backend_s
    with _compile_lock:
        rec = _compiles.get(name)
        if rec is None:
            rec = _compiles[name] = _new_sums()
        rec["count"] += 1
        for key in ("trace_s", "lower_s", "backend_s", "saved_s"):
            rec[key] += row[key]
        rec[row["cache"]] += 1
        count = rec["count"]
        _compile_log.append(row)
        # the watchdog's part: a storm is a WATCHED name past the limit
        storm = _ENABLED and row["watch"] is not None \
            and count > _RETRACE_LIMIT and name not in _storm_warned
        if storm:
            _storm_warned.add(name)
            storm_ms = _total_ms(rec)
    if row["watch"] is not None:
        bump("jit_compiles")
    if row["cache"] == "hit":
        bump("compile_cache_hits")
    elif row["cache"] == "miss":
        bump("compile_cache_misses")
    if _ENABLED:
        observe("jit_compile_us", total_s * 1e6)
        _flight.record("compile", name, wall_us=round(total_s * 1e6, 1),
                       cache=row["cache"], compiles=count)
    if trace_active():
        add_event("compile:%s" % name, "compile", row["ts"],
                  backend_s * 1e6,
                  args={"fun_name": fun_name, "span": row["span"],
                        "cache": row["cache"], "compiles": count,
                        "trace_s": row["trace_s"],
                        "lower_s": row["lower_s"]})
        if _annotation_cls is not None:
            # the compile is over by the time JAX says so: what an open
            # profiler session gets is a marker at its end that carries
            # the seconds
            with _annotation_cls(_ANNOTATION_PREFIX + "compile:%s" % name,
                                 backend_s=backend_s, cache=row["cache"]):
                pass
    if storm:
        bump("retrace_storms")
        _LOG.warning(
            "retrace-storm %s",
            json.dumps({"callable": name, "compiles": count,
                        "limit": _RETRACE_LIMIT,
                        "total_compile_ms": round(storm_ms, 3),
                        "hint": "inputs keep changing shape/dtype/structure;"
                                " pad or bucket them so the compiled program"
                                " is reused"}, sort_keys=True))


class _WatchedJit:
    """Wrap a jitted callable and give it a name in the compile ledger: a
    backend compile that happens while its call is on the stack carries
    ``watch=<name>``, telemetry on or off (the ledger's listener looks for
    this frame; the call itself does nothing for it).

    With telemetry on the wrapper also acts on a call that compiled — the
    ledger closed a row on this thread meanwhile — by capturing the
    program's cost and running the trace checks.  Attribute access
    (``_cache_size``, ``lower`` ...) proxies to the wrapped callable so
    cache-size contract tests keep working against the wrapper.
    """

    __slots__ = ("_fn", "_name")

    def __init__(self, fn, name):
        self._fn = fn
        self._name = name

    def __call__(self, *args, **kwargs):
        # MXNET_TRACECHECK and MXNET_DEVICE_TIME ride the same wrapper
        # even with telemetry off (findings/samples are counter-booked,
        # and counters are always on)
        if not (_ENABLED or _TRACECHECK or _DEVICE_TIME):
            return self._fn(*args, **kwargs)
        pend = _pending.__dict__
        before = pend.get("rows", 0)
        t0 = now_us()
        out = self._fn(*args, **kwargs)
        compiled = pend.get("rows", 0) != before
        if _DEVICE_TIME and not compiled:
            # sampled device timing: block on the outputs so the wall
            # delta ≈ dispatch + device execution.  Fresh-compile calls
            # are excluded (trace+compile wall would pollute the
            # device-time series), and no extra XLA program ever runs —
            # block_until_ready only waits.
            _device().maybe_time(self._name, t0, out)
        if compiled:
            # the captured flops/bytes are only ever read by step spans,
            # which need telemetry on: the MXNET_TRACECHECK-only path
            # skips the capture
            if _ENABLED:
                _capture_cost(self._fn, self._name, args, kwargs)
            if _TRACECHECK:
                _run_tracecheck(self._name, self._fn, args, kwargs)
        # cost window: a step span is open on this process — attribute
        # this program execution's FLOPs/bytes to it (dict .get + two
        # float adds; the window is None outside step spans)
        win = _STEP_WINDOW
        if win is not None:
            cost = _PROGRAM_COSTS.get(self._name)
            if cost is not None:
                win[0] += cost[0]
                win[1] += cost[1]
        return out

    def __getattr__(self, item):
        return getattr(object.__getattribute__(self, "_fn"), item)


def watch_jit(fn, name):
    """Register *fn* (a ``jax.jit`` product) with the retrace watchdog."""
    _bind_compile_listeners()
    return _WatchedJit(fn, name)


def _run_tracecheck(name, fn, args, kwargs):
    """MXNET_TRACECHECK compile hook: hand the freshly compiled program
    to the lint trace tier (JX rules + the JX105 retrace explainer).
    Lazy import — the lint package must never load on the normal path —
    and exception-proof: analysis must never break a training step."""
    try:
        from ..lint import tracecheck as _tc
        _tc.on_compile(name, fn, args, kwargs)
    except Exception:
        pass


# --------------------------------------------------------------------------
# XLA cost accounting (per-program capture + per-step window)
# --------------------------------------------------------------------------
#
# _PROGRAM_COSTS holds the last-compiled (flops, bytes_accessed) per
# watched-jit name, written on compile events and read on every watched
# call while a step window is open.  The heavy lifting (ShapeDtypeStruct
# re-lower, cost_analysis parsing, peak tables) lives in ..costs, loaded
# lazily so the import-light contract of this module holds.

_PROGRAM_COSTS = {}            # name -> (flops, bytes_accessed)
_STEP_WINDOW = None            # [flops, bytes] while a step span is open
_STEP_DEPTH = 0
_costs_mod = None


def _costs():
    global _costs_mod
    if _costs_mod is None:
        from . import costs as _costs_mod_  # noqa: PLC0415
        _costs_mod = _costs_mod_
    return _costs_mod


_device_mod = None


def _device():
    global _device_mod
    if _device_mod is None:
        from . import device as _device_mod_  # noqa: PLC0415
        _device_mod = _device_mod_
    return _device_mod


def _capture_cost(fn, name, args, kwargs):
    """Ask XLA what the freshly compiled program costs; never raises."""
    try:
        cost = _costs().capture(fn, args, kwargs)
    except Exception:      # cost accounting must never break a step
        cost = None
    if cost is not None:
        _PROGRAM_COSTS[name] = cost
    return cost


def program_cost(name):
    """(flops, bytes_accessed) of *name*'s last-compiled program, or
    None before its first compile (or when capture failed)."""
    return _PROGRAM_COSTS.get(name)


def program_costs():
    """Snapshot of every captured program cost (JSON-shaped)."""
    return {name: {"flops": c[0], "bytes_accessed": c[1]}
            for name, c in sorted(_PROGRAM_COSTS.items())}


def _open_step_window():
    global _STEP_WINDOW, _STEP_DEPTH
    _STEP_DEPTH += 1
    if _STEP_DEPTH == 1:
        _STEP_WINDOW = [0.0, 0.0]
        if _DEVICE_TIME:
            _device().open_step_window()


def _close_step_window(dur_us):
    """Step-span exit: convert the window's FLOPs/bytes into the MFU and
    bandwidth-utilization gauges, and sample the engine backlog."""
    global _STEP_WINDOW, _STEP_DEPTH
    _STEP_DEPTH = max(0, _STEP_DEPTH - 1)
    if _STEP_DEPTH:
        return
    win, _STEP_WINDOW = _STEP_WINDOW, None
    if win is not None and win[0] > 0:
        try:
            _costs().finalize_step(win[0], win[1], dur_us)
        except Exception:
            pass
    if _DEVICE_TIME:
        _device().close_step_window(dur_us)
    _sample_engine_pending()
    # step time-series hook: the store keys every step-span exit's
    # gauges by step (sys.modules, not an import — core stays the
    # package's dependency root)
    ts = sys.modules.get("mxnet_tpu.telemetry.timeseries")
    if ts is not None:
        try:
            ts.note_step_exit(dur_us)
        except Exception:
            pass


def _sample_engine_pending():
    """engine_pending_tasks gauge — without importing (or creating!) the
    engine: only an already-live singleton is observed."""
    eng = sys.modules.get("mxnet_tpu.engine")
    if eng is None:
        return
    singleton = getattr(eng, "_SINGLETON", None)
    if singleton is None:
        return
    try:
        set_gauge("engine_pending_tasks", singleton.num_pending())
    except Exception:
        pass


def compile_events():
    """The compile ledger, oldest row first: one dict a backend compile —
    ``fun_name`` (JAX's, ``jit(f)``), ``watch`` (the enclosing watched
    jit's name or None), ``span`` (the innermost open program span or
    None: a compile outside every span is not the trainer's), ``trace_s``,
    ``lower_s``, ``backend_s`` (XLA's compile, or the retrieval on a
    hit), ``cache`` (``hit`` / ``miss`` / ``off``), ``saved_s`` (JAX's
    estimate of compile time a hit saved) and ``ts``, the backend phase's
    start on :func:`now_us`, the spans' clock."""
    with _compile_lock:
        return [dict(e) for e in _compile_log]


def _acquire(lock, timeout):
    """Lock acquire with optional timeout — the crash/signal dump path
    must never deadlock on a lock the interrupted main thread holds."""
    if timeout is None:
        lock.acquire()
        return True
    return lock.acquire(timeout=timeout)


def retrace_report(lock_timeout=None):
    """Per-name compile accounting for exporters / trace_report: the
    ledger's sums by watch name, or by JAX's ``fun_name`` for a compile
    outside every watched jit.

    *lock_timeout*: crash-dump callers pass a bound; on timeout the
    report is built from an unlocked best-effort copy (the holder is the
    very thread a signal interrupted — it will never release)."""
    locked = _acquire(_compile_lock, lock_timeout)
    try:
        items = [(name, dict(rec)) for name, rec in _compiles.items()]
        warned = set(_storm_warned)
    except RuntimeError:          # unlocked copy raced a resize
        return {}
    finally:
        if locked:
            _compile_lock.release()
    report = {}
    for name, rec in items:
        rec["total_ms"] = _total_ms(rec)
        rec["storm"] = name in warned
        report[name] = rec
    return report


def _compile_totals(report):
    """The ledger summed over names: what a start paid, and to whom."""
    total = _new_sums()
    for rec in report.values():
        for key in total:
            total[key] += rec[key]
    return total


# --------------------------------------------------------------------------
# memory watermarks
# --------------------------------------------------------------------------

def _device_memory(devices):
    """(total bytes_in_use, max single-device bytes_in_use) over
    *devices*; (None, None) when no device reports memory stats."""
    total, worst, reported = 0, 0, False
    for dev in devices:
        try:
            stats = dev.memory_stats()
        except Exception:
            stats = None
        if not stats:
            continue
        used = int(stats.get("bytes_in_use", 0))
        total += used
        worst = max(worst, used)
        reported = True
    return (total, worst) if reported else (None, None)


def sample_memory():
    """Record host/device memory watermarks into the gauges (called at
    step-span boundaries and by the introspection sampler; safe on
    backends without memory_stats).

    Device usage is summed over ALL local devices — a multi-chip run
    reading one device would under-report HBM by 1/N — and the most
    loaded single device feeds a monotonic high-water gauge (the OOM
    question is always about the worst chip, not the average).
    """
    try:
        import resource
        rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        # linux reports KiB, macOS bytes; normalise to bytes
        set_gauge("host_rss_peak_bytes",
                  rss * 1024 if os.uname().sysname == "Linux" else rss)
    except Exception:
        pass
    try:
        import jax
        total, worst = _device_memory(jax.local_devices())
        if total is not None:
            set_gauge("device_bytes_in_use", total)
            set_gauge("device_bytes_in_use_peak",
                      max(worst, gauge("device_bytes_in_use_peak")))
    except Exception:
        pass


# --------------------------------------------------------------------------
# exporters
# --------------------------------------------------------------------------

def _metadata_events():
    """ph:'M' process/thread-name events so Perfetto / chrome://tracing
    label the tracks instead of showing bare numeric tids.  A track's name
    is its highest-priority hosted category (a train thread that also
    dispatches eager ops reads 'train-step', an io producer 'data-io').
    Caller holds ``_lock``."""
    pid = os.getpid()
    meta = [{"ph": "M", "name": "process_name", "pid": pid, "tid": 0,
             "args": {"name": "mxnet_tpu"}}]
    for tid, cats in sorted(_tid_cats.items()):
        label = next((_CAT_TRACK[c] for c in _CAT_PRIORITY if c in cats),
                     "thread-%d" % tid)
        meta.append({"ph": "M", "name": "thread_name", "pid": pid,
                     "tid": tid, "args": {"name": label}})
    return meta


def chrome_trace_payload():
    """The merged trace (spans + op events + compile events) with
    track-name metadata, as the Chrome trace JSON object."""
    with _lock:
        return {"traceEvents": _metadata_events() + list(_events),
                "displayTimeUnit": "ms"}


def dump_chrome_trace(filename):
    """Write :func:`chrome_trace_payload` to *filename*."""
    payload = chrome_trace_payload()
    with open(filename, "w") as f:
        json.dump(payload, f)
    return filename


def _escape_help(text):
    """Prometheus exposition-format HELP escaping: a raw newline in a
    HELP line terminates it mid-text and the next fragment becomes an
    unparseable sample line — the whole scrape fails."""
    return str(text).replace("\\", "\\\\").replace("\n", "\\n")


def _escape_label(value):
    """Label-value escaping per the exposition format (backslash first,
    then quote and newline)."""
    return str(value).replace("\\", "\\\\").replace('"', '\\"') \
        .replace("\n", "\\n")


def prometheus_text():
    """Prometheus text exposition of every live metric."""
    lines = []
    with _mlock:
        counter_items = sorted(_counters.items())
        gauge_items = sorted(_gauges.items())
        # copy each histogram's fields under the lock: a concurrent
        # observe() must not yield buckets disagreeing with _count/_sum
        hists = [(h.name, h.help, h.buckets, list(h.counts),
                  h.total, h.count) for h in _hists.values()]
    for name, val in counter_items:
        lines.append("# HELP %s %s"
                     % (name, _escape_help(COUNTERS.get(name, name))))
        lines.append("# TYPE %s counter" % name)
        lines.append("%s %d" % (name, val))
    for name, val in gauge_items:
        lines.append("# HELP %s %s"
                     % (name, _escape_help(GAUGES.get(name, name))))
        lines.append("# TYPE %s gauge" % name)
        lines.append("%s %.17g" % (name, val))
    for name, help_, buckets, counts, total, count in hists:
        lines.append("# HELP %s %s" % (name, _escape_help(help_ or name)))
        lines.append("# TYPE %s histogram" % name)
        cum = 0
        for edge, c in zip(buckets, counts):
            cum += c
            lines.append('%s_bucket{le="%s"} %d'
                         % (name, _escape_label("%.17g" % edge), cum))
        cum += counts[-1]
        lines.append('%s_bucket{le="+Inf"} %d' % (name, cum))
        lines.append("%s_sum %.17g" % (name, total))
        lines.append("%s_count %d" % (name, count))
    return "\n".join(lines) + "\n"


def snapshot(lock_timeout=None):
    """JSON-serialisable snapshot of the whole telemetry state.

    *lock_timeout*: bounds every lock acquire — the flight recorder's
    signal handler snapshots from the main thread, which may itself be
    mid-``bump()`` holding ``_mlock``; a plain blocking acquire there
    would turn SIGTERM into a hang.  On timeout the copies are taken
    unlocked (worst case: one torn histogram in a post-mortem)."""
    locked = _acquire(_mlock, lock_timeout)
    try:
        counters_ = dict(_counters)
        gauges_ = dict(_gauges)
        hists_ = {n: h.to_dict() for n, h in _hists.items()}
    except RuntimeError:          # unlocked copy raced a resize
        counters_, gauges_, hists_ = {}, {}, {}
    finally:
        if locked:
            _mlock.release()
    costs_ = {"programs": program_costs(),
              "peaks": _costs().peaks_if_resolved()}
    retraces = retrace_report(lock_timeout)
    snap = {"enabled": _ENABLED,
            "retrace_limit": _RETRACE_LIMIT,
            "counters": counters_,
            "gauges": gauges_,
            "histograms": hists_,
            "retraces": retraces,
            "compiles": _compile_totals(retraces),
            "costs": costs_}
    if _DEVICE_TIME:
        try:
            snap["device"] = _device().device_report()
        except Exception:     # a post-mortem snapshot must never fail
            pass
    return snap


def dump_snapshot(filename):
    with open(filename, "w") as f:
        json.dump(snapshot(), f, indent=1, sort_keys=True)
    return filename


def reset():
    """Clear events, metrics, and watchdog state (tests / new session)."""
    global _STEP_WINDOW, _STEP_DEPTH, _SESSION, _BATCH_OPEN
    clear_events()
    reset_counters()
    with _mlock:
        _gauges.clear()
        _hists.clear()
    with _compile_lock:
        _compiles.clear()
        _compile_log.clear()
        _storm_warned.clear()
    _PROGRAM_COSTS.clear()
    _STEP_WINDOW = None
    _STEP_DEPTH = 0
    _SESSION = False
    _BATCH_OPEN = 0
    dev = sys.modules.get("mxnet_tpu.telemetry.device")
    if dev is not None:
        dev.reset()
    ts = sys.modules.get("mxnet_tpu.telemetry.timeseries")
    if ts is not None:
        ts.reset()
    _flight.reset()


_bind_compile_listeners()
