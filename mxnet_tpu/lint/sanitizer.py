"""Runtime sanitizer: turn the worst TPU footguns into loud errors.

The static pass (``rules.py``) catches what it can see; this module catches
the same hazards at runtime, where there are no false positives:

* **Tracer-leak / host-sync-under-trace** (runtime JG001):
  ``NDArray.asnumpy`` — the single funnel every host materialization goes
  through (``__array__``, ``asscalar``, ``item``, ``__bool__``, ``__int__``,
  ``__float__``) — calls :func:`check_host_sync`.  Under an active JAX
  trace it raises (or warns) with the offending user frame: either the
  value IS a tracer (jax would die anyway, with a far worse message) or it
  is concrete and would silently bake into the compiled program as a
  constant — the nastier bug, because it "works" until the constant goes
  stale.

* **Engine ordering** (a lightweight happens-before checker):
  :func:`guard_task` wraps tasks pushed onto the host dependency engine and
  validates the declared read/write contract as they execute — no two
  writers of one var concurrently, writes land in push order, and no
  reader overlaps a writer.  This is how the reference's threaded engine
  bugs (mis-declared ``const_vars``/``mutable_vars``) surface as errors
  instead of corrupted checkpoints.

Gating: ``MXNET_SANITIZE=1`` raises, ``MXNET_SANITIZE=warn`` warns once per
site, unset/0 is a single module-bool check on the hot path.  Import-light:
jax is only touched once a check actually runs.
"""
from __future__ import annotations

import contextlib
import functools
import logging
import os
import threading
import traceback

__all__ = ["SanitizerError", "enabled", "mode", "configure",
           "refresh_from_env", "check_host_sync", "allow_host_sync",
           "guard_task", "engine_checker_enabled"]

_LOG = logging.getLogger("mxnet_tpu.sanitizer")


class SanitizerError(RuntimeError):
    """A TPU footgun caught at runtime with MXNET_SANITIZE=1."""


def _env_mode():
    raw = os.environ.get("MXNET_SANITIZE", "0").strip().lower()
    if raw in ("1", "true", "on", "yes", "raise"):
        return "raise"
    if raw == "warn":
        return "warn"
    return "off"


_MODE = _env_mode()


def enabled():
    return _MODE != "off"


def mode():
    return _MODE


def configure(mode=None):
    """Programmatic override: 'off' | 'warn' | 'raise' (tests, notebooks)."""
    global _MODE
    if mode is not None:
        if mode not in ("off", "warn", "raise"):
            raise ValueError("sanitizer mode must be off/warn/raise, got %r"
                             % (mode,))
        _MODE = mode
        with _warn_lock:
            _warned_sites.clear()     # re-arm once-per-site warnings


def refresh_from_env():
    global _MODE
    _MODE = _env_mode()
    with _warn_lock:
        _warned_sites.clear()


_warn_lock = threading.Lock()
_warned_sites = set()


def _violation(message, site=None):
    try:
        from .. import telemetry as _tel
        _tel.bump("sanitizer_violations")
        # the flight ring keeps the last violations for post-mortems:
        # in warn mode the log line scrolls away, the ring does not
        _tel.flight.record("sanitizer", message[:300],
                           site=str(site) if site is not None else None)
    except Exception:
        pass
    if _MODE == "raise":
        raise SanitizerError(message)
    if site is not None:
        # warn mode logs once per site: a sync inside a training-step
        # trace would otherwise flood the log once per step
        with _warn_lock:
            if site in _warned_sites:
                return
            _warned_sites.add(site)
    _LOG.warning("MXNET_SANITIZE: %s", message)


def _user_frame():
    """The first stack frame outside mxnet_tpu — where the footgun lives."""
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for frame in reversed(traceback.extract_stack()):
        if not frame.filename.startswith(pkg):
            return "%s:%d in %s: %s" % (frame.filename, frame.lineno,
                                        frame.name, frame.line or "")
    return "<inside mxnet_tpu>"


# ---------------------------------------------------------------------------
# tracer-leak / host-sync-under-trace
# ---------------------------------------------------------------------------

_sync_tls = threading.local()


@contextlib.contextmanager
def allow_host_sync():
    """Suppress the *sync-under-trace* check on this thread.

    For framework code whose host materialization is deliberate and
    observation-only — ``monitor.Monitor._render`` formatting its stat
    values while a user's trace happens to be open on the same thread.
    The value is concrete and never flows back into traced math, so the
    "baked constant" hazard the check guards against cannot occur; a
    genuine TRACER leak still raises (a tracer escaping into a print is
    a real bug regardless of who formats it)."""
    depth = getattr(_sync_tls, "depth", 0)
    _sync_tls.depth = depth + 1
    try:
        yield
    finally:
        _sync_tls.depth = depth


@functools.lru_cache(maxsize=None)
def _trace_state_probe():
    """jax's "no trace is open on this thread" predicate, resolved on the
    first check.  It is jax-internal: this is the one place that knows
    where it lives."""
    from jax._src import core
    return core.trace_state_clean


def check_host_sync(data, what="asnumpy"):
    """Validate one host materialization.  Called from NDArray.asnumpy;
    off mode returns after a single module-bool check."""
    if _MODE == "off":
        return
    import jax
    try:
        tracing = not _trace_state_probe()()
    except (ImportError, AttributeError) as exc:
        # a sanitizer that cannot see says so: returning here would report
        # every sync under a trace as clean
        _violation(
            "sanitizer is blind: jax's trace-state probe cannot be resolved "
            "on jax %s (%s: %s), so host syncs under a trace go unchecked. "
            "Repair mxnet_tpu/lint/sanitizer.py:_trace_state_probe."
            % (jax.__version__, type(exc).__name__, exc), site=("blind",))
        return
    is_tracer = isinstance(data, jax.core.Tracer)
    if tracing and not is_tracer and getattr(_sync_tls, "depth", 0):
        return              # an allow_host_sync() scope: deliberate read
    if is_tracer:
        site = _user_frame()
        _violation(
            "tracer leak: NDArray.%s() on a value that is being traced by "
            "jax.jit/grad — the array escaped the traced function into "
            "host code.  Thread it through the function's return value "
            "instead.  Site: %s" % (what, site), site=("leak", site))
    elif tracing:
        site = _user_frame()
        _violation(
            "host sync under trace: NDArray.%s() called while a jax trace "
            "is active; the concrete value will be baked into the "
            "compiled program as a constant and silently go stale on "
            "later calls.  Site: %s" % (what, site), site=("sync", site))


# ---------------------------------------------------------------------------
# engine happens-before checker
# ---------------------------------------------------------------------------

def engine_checker_enabled():
    return _MODE != "off"


def push_scope(engine):
    """Lock held across ticket issuance AND the native enqueue, so the
    sanitizer's write tickets cannot interleave differently from the
    engine's own push order under concurrent pushers.  A no-op context
    when the checker is off."""
    if _MODE == "off":
        return contextlib.nullcontext()
    return _hb_state(engine).push_lock


class _VarState:
    __slots__ = ("readers", "writer", "pushed", "landed", "cancelled",
                 "forget")

    def __init__(self):
        self.readers = 0       # concurrent readers executing now
        self.writer = False    # a writer executing now
        self.pushed = 0        # write tickets issued (push order)
        self.landed = 0        # writes completed
        self.cancelled = set()  # tickets whose task will never execute
        self.forget = False    # delete_variable'd: drop once drained

    @property
    def drained(self):
        return (self.landed == self.pushed and not self.writer
                and self.readers == 0)

    def advance(self):
        """Skip landed past tickets abandoned before execution (a push
        that raised after taking its ticket)."""
        while self.landed in self.cancelled:
            self.cancelled.discard(self.landed)
            self.landed += 1


class _HBState:
    """Per-engine happens-before ledger (attached lazily to the engine)."""

    def __init__(self):
        self.lock = threading.Lock()
        self.push_lock = threading.RLock()
        self.vars = {}

    def var(self, v):
        st = self.vars.get(v)
        if st is None:
            st = self.vars[v] = _VarState()
        return st


def _hb_state(engine):
    st = getattr(engine, "_graftlint_hb", None)
    if st is None:
        st = engine._graftlint_hb = _HBState()
    return st


def forget_var(engine, var):
    """Drop a deleted engine variable's ledger entry (bounds the ledger
    over long runs with variable churn).

    Deletion mirrors the engine's own semantics: it only takes effect
    once every pending task on the var has drained — an eager pop while
    a queued write still holds a ticket would recreate the state at
    landed=0 and misreport that write as out of push order.
    """
    hb = getattr(engine, "_graftlint_hb", None)
    if hb is not None:
        with hb.lock:
            st = hb.vars.get(int(var))
            if st is None:
                return
            if st.drained:
                hb.vars.pop(int(var), None)
            else:
                st.forget = True     # reaped by the last draining task


def guard_task(engine, fn, const_vars, mutable_vars):
    """Wrap an engine task so the declared dependency contract is asserted
    while it runs.

    Invariants checked at execution time (the engine's scheduling is the
    thing under test, so violations mean mis-declared deps or a scheduler
    bug):

    * writes to one var execute in push order (each task takes a ticket
      per mutable var at push time and must be the next to land);
    * no two writers of one var run concurrently;
    * no reader of a var runs while a writer of it runs.
    """
    hb = _hb_state(engine)
    # mirror the engine's DeduplicateVarHandle: repeated handles are one
    # dependency, and a var both read and written counts as written
    mv = tuple(dict.fromkeys(int(v) for v in mutable_vars))
    cv = tuple(v for v in dict.fromkeys(int(v) for v in const_vars)
               if v not in set(mv))
    tickets = {}
    with hb.lock:
        for v in mv:
            st = hb.var(v)
            tickets[v] = st.pushed
            st.pushed += 1

    def guarded():
        problems = []
        with hb.lock:
            for v in mv:
                st = hb.var(v)
                st.advance()
                if st.writer:
                    problems.append("two writers of engine var %d running "
                                    "concurrently" % v)
                if st.readers:
                    problems.append("writer of engine var %d overlaps %d "
                                    "reader(s)" % (v, st.readers))
                if st.landed != tickets[v]:
                    problems.append(
                        "write %d to engine var %d executing out of push "
                        "order (expected write %d next)"
                        % (tickets[v], v, st.landed))
                st.writer = True
            for v in cv:
                st = hb.var(v)
                if st.writer and v not in mv:
                    problems.append("reader of engine var %d overlaps a "
                                    "writer" % v)
                st.readers += 1
        try:
            if problems:
                # site key excludes ticket numbers: one mis-declared task
                # re-pushed every step must warn once, not flood the log
                _violation("engine ordering: " + "; ".join(problems),
                           site=("engine",) + tuple(sorted(set(mv)
                                                           | set(cv))))
            return fn()
        finally:
            with hb.lock:
                for v in mv:
                    st = hb.var(v)
                    st.writer = False
                    st.landed += 1
                    st.advance()
                for v in cv:
                    hb.var(v).readers -= 1
                for v in set(mv) | set(cv):
                    st = hb.vars.get(v)
                    if st is not None and st.forget and st.drained:
                        hb.vars.pop(v, None)

    def cancel():
        """Roll back the tickets of a push that will never execute (the
        native enqueue raised) so later writes don't read as reordered."""
        with hb.lock:
            for v, t in tickets.items():
                st = hb.var(v)
                st.cancelled.add(t)
                st.advance()
                if st.forget and st.drained:
                    hb.vars.pop(v, None)

    guarded.cancel = cancel
    return guarded
