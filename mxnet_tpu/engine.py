"""Engine: dependency-scheduled host tasks + observable async semantics
over XLA/PJRT dispatch.

Reference analogue: the threaded dependency engine
(``include/mxnet/engine.h:95-280``, ``src/engine/threaded_engine.cc``) whose
observable contract is: ops issue asynchronously; ``WaitForVar`` blocks
until pending writes land; ``WaitForAll`` drains everything; writes to one
buffer serialize in push order, reads run in parallel (SURVEY §3.3).

TPU-native split of responsibilities:

* **Device side** — XLA/PJRT *is* the engine: jax dispatch is already
  async, jax arrays are immutable (write-serialization by construction —
  each mutation rebinds to a new buffer) and ``block_until_ready`` is
  WaitForVar.  ``wait_for_var``/``wait_for_all``/``push`` below keep that
  facade, including the NaiveEngine-style sync-dispatch debug mode
  (reference ``MXNET_ENGINE_TYPE=NaiveEngine``).

* **Host side** — the reference also routes IO, checkpoint, and kvstore
  transport through the engine.  ``ThreadedEngine`` below is a real native
  scheduler (C++ worker pool + per-variable dependency queues,
  ``native/engine.cc`` via ctypes) with the same protocol: tasks declare
  ``const_vars`` (reads) and ``mutable_vars`` (writes); the engine
  guarantees serialized writes and parallel reads per variable.

Env vars (docs/env_var.md): ``MXNET_ENGINE_TYPE=NaiveEngine`` forces
synchronous execution everywhere (usable backtraces);
``MXNET_CPU_WORKER_NTHREADS`` sizes the native worker pool.
"""
from __future__ import annotations

import atexit
import ctypes
import itertools
import logging
import os
import threading
import weakref

import jax
import numpy as np

from . import chaos as _chaos
from .lint import lockwitness as _lockwitness
from .lint import sanitizer as _san
from .telemetry import flight as _flight

__all__ = ["wait_for_var", "wait_for_all", "push", "is_sync_dispatch",
           "set_sync_dispatch", "ThreadedEngine", "engine"]

_SYNC = os.environ.get("MXNET_ENGINE_TYPE", "") == "NaiveEngine"


def is_sync_dispatch():
    return _SYNC


def set_sync_dispatch(flag):
    """Debug mode: force synchronous execution after every op (the
    NaiveEngine idea — crashes surface with a usable backtrace)."""
    global _SYNC
    _SYNC = bool(flag)
    eng = _SINGLETON
    if eng is not None:
        eng.set_sync(flag)


# ---------------------------------------------------------------------------
# Device-side facade (XLA/PJRT is the scheduler)
# ---------------------------------------------------------------------------

def wait_for_var(arr):
    """Block until all pending computation producing ``arr`` is done.

    Accepts a jax/NDArray value (PJRT future) or an ``int`` variable
    handle from :meth:`ThreadedEngine.new_variable`.
    """
    if isinstance(arr, (int, np.integer)) and not isinstance(arr, bool):
        engine().wait_for_var(int(arr))
        return
    jax.block_until_ready(arr)


def wait_for_all():
    """Engine::WaitForAll — drain every outstanding computation."""
    eng = _SINGLETON
    if eng is not None:
        eng.wait_for_all()
    # PJRT has no global barrier; an empty device sync per device
    # suffices for the device side.  A device that fails its sync raises.
    for dev in jax.devices():
        jax.device_put(0, dev).block_until_ready()


def push(fn, *args, **kwargs):
    """Run a function 'on the engine' (async by construction under jax)."""
    out = fn(*args, **kwargs)
    if _SYNC:
        jax.block_until_ready(out)
    return out


# ---------------------------------------------------------------------------
# Host-side native engine
# ---------------------------------------------------------------------------

# One immortal ctypes trampoline shared by every task: the C side receives
# (trampoline, key) and the key resolves to the Python callable at run
# time.  This avoids per-task CFUNCTYPE closures entirely — nothing to
# keep alive per task, nothing to free while a C stack frame might still
# reference it.
_TASKS_LOCK = _lockwitness.make_lock("engine._TASKS_LOCK")
_LIVE_TASKS = {}          # key -> (engine, callable)
_KEY_SEQ = itertools.count(1)
_TRAMPOLINE = None        # created on first native engine


def _make_trampoline(fn_type):
    global _TRAMPOLINE
    if _TRAMPOLINE is None:
        def _run(arg):
            key = int(arg or 0)
            with _TASKS_LOCK:
                entry = _LIVE_TASKS.pop(key, None)
            if entry is None:     # pragma: no cover - defensive
                return
            eng, fn = entry
            eng._run_inline(fn)
        _TRAMPOLINE = fn_type(_run)
    return _TRAMPOLINE


class _EngineCore:
    """Owner of one native engine handle.  Holds no reference back to the
    Python-facing ``ThreadedEngine``, so it can serve as the
    ``weakref.finalize`` callback target: ``close()`` and the finalizer
    both funnel into the idempotent shutdown paths below, and every
    native call claims the handle through :meth:`enter`/:meth:`exit`
    so shutdown can wait out (or exclude) concurrent callers.
    """

    def __init__(self, nat, h):
        self.nat = nat
        self.h = h
        self.lock = _lockwitness.make_lock("_EngineCore.lock")
        self.idle = _lockwitness.make_condition(self.lock,
                                                "_EngineCore.idle")
        self.inflight = 0

    def enter(self):
        """Claim the handle for one native call; None once shut down."""
        with self.lock:
            if self.h is None:
                return None
            self.inflight += 1
            return self.h

    def exit(self):
        with self.lock:
            self.inflight -= 1
            if self.inflight == 0:
                self.idle.notify_all()

    def shutdown_sync(self):
        """Drain and free, waiting out concurrent native calls.  Must not
        run on one of the engine's own worker threads."""
        with self.lock:
            if self.h is None:
                return
            h, self.h = self.h, None     # new calls now see 'closed'
            while self.inflight:
                self.idle.wait()
        self.nat.MXEngineWaitForAll(h)
        self.nat.MXEngineFree(h)

    def shutdown_async(self):
        """Free via a detached native deleter — for GC on a non-main
        thread, possibly one of this engine's own workers mid-task,
        where a synchronous drain would self-deadlock.  No inflight wait
        is needed: GC implies the engine was unreachable, so no API call
        can be concurrently holding the handle."""
        with self.lock:
            if self.h is None:
                return
            h, self.h = self.h, None
        self.nat.MXEngineFreeAsync(h)


def _finalize_core(core):
    """weakref.finalize callback (GC of a dropped engine, or weakref's
    atexit hook for engines still alive at interpreter exit)."""
    import sys
    if sys.is_finalizing():     # pragma: no cover - teardown path
        # Too late to run trampolines; let the OS reclaim at exit.
        return
    if threading.current_thread() is threading.main_thread():
        # The main thread can never be an engine worker: safe to drain.
        # This covers the weakref-atexit path, where a detached deleter
        # would race process teardown.
        core.shutdown_sync()
    else:
        core.shutdown_async()


class ThreadedEngine:
    """Host-task scheduler with the reference engine's dependency protocol.

    Backed by ``native/engine.cc`` (C++ worker pool, per-variable FIFO
    dependency queues).  When the native library is unavailable the same
    API degrades to synchronous inline execution — the observable
    contract (completion order per variable) is preserved, only the
    parallelism is lost.
    """

    def __init__(self, num_workers=None, sync=None):
        from ._native import engine as nat
        if num_workers is None:
            num_workers = int(os.environ.get(
                "MXNET_CPU_WORKER_NTHREADS",
                str(min(8, os.cpu_count() or 1))))
        if sync is None:
            sync = _SYNC
        self._nat = nat.lib()
        self._errors = []
        self._pyvar_seq = itertools.count(1)
        if self._nat is not None:
            h = self._nat.MXEngineCreate(int(num_workers), 1 if sync else 0)
            self._core = _EngineCore(self._nat, h)
            self._trampoline = _make_trampoline(nat.TASK_FN)
            # GC safety net: a dropped instance still drains and frees
            # its C++ engine (and worker threads) instead of leaking
            # them — and before interpreter teardown, so no trampoline
            # fires into a finalizing Python.
            self._finalizer = weakref.finalize(self, _finalize_core,
                                               self._core)
        else:
            self._core = None

    # -- variables ---------------------------------------------------------

    def new_variable(self):
        """A scheduling variable (an ``int`` handle)."""
        h = self._enter_native()
        if h is None:
            return next(self._pyvar_seq)
        try:
            return int(self._nat.MXEngineNewVariable(h))
        finally:
            self._exit_native()

    def delete_variable(self, var):
        """GC the variable once every pending task touching it completes."""
        _san.forget_var(self, var)
        h = self._enter_native()
        if h is not None:
            try:
                self._nat.MXEngineDeleteVariable(h, int(var))
            finally:
                self._exit_native()

    # -- tasks -------------------------------------------------------------

    def push(self, fn, const_vars=(), mutable_vars=(), priority=0,
             tag=None):
        """Schedule ``fn()`` after its dependencies resolve.

        ``const_vars`` are read-dependencies (may run concurrently with
        other readers); ``mutable_vars`` are write-dependencies
        (serialized in push order per variable).  Exceptions raised by
        ``fn`` are captured and re-raised at the next wait point.
        *tag* names the task in the flight ring (callers pushing
        lambdas — e.g. the serving batcher — would otherwise all read
        as ``<lambda>`` in a post-mortem).

        Under ``MXNET_SANITIZE`` every task is wrapped in a happens-before
        checker that asserts the declared contract as it executes (writes
        land in push order, writers exclusive, readers never overlap a
        writer) — mis-declared deps surface as errors at the next wait
        point instead of corrupted data.  The checker's write tickets and
        the native enqueue happen under one push scope so concurrent
        pushers cannot interleave ticket order against engine order.
        """
        if _flight.enabled():     # opted-out path stays one bool check
            _flight.record("engine_push",
                           tag or getattr(fn, "__qualname__", None)
                           or getattr(fn, "__name__", repr(type(fn))),
                           reads=len(const_vars), writes=len(mutable_vars))
        if _chaos.active():       # decided HERE (deterministic push
            act = _chaos.decide("engine.task")   # order), applied in-task
            if act is not None:
                fn = _chaos.chaos_task(fn, act)
        with _san.push_scope(self):
            if _san.engine_checker_enabled():
                fn = _san.guard_task(self, fn, const_vars, mutable_vars)
            self._push_raw(fn, const_vars, mutable_vars, priority)

    def _push_raw(self, fn, const_vars, mutable_vars, priority):
        if self._core is None:
            self._run_inline(fn)
            return

        key = next(_KEY_SEQ)
        with _TASKS_LOCK:
            _LIVE_TASKS[key] = (self, fn)
        h = self._enter_native()
        if h is None:                        # closed concurrently
            with _TASKS_LOCK:
                _LIVE_TASKS.pop(key, None)
            # Degrade like the no-native fallback: the task still runs.
            self._run_inline(fn)
            return
        try:
            cv = (ctypes.c_int64 * max(1, len(const_vars)))(*const_vars)
            mv = (ctypes.c_int64 * max(1, len(mutable_vars)))(*mutable_vars)
            self._nat.MXEnginePushAsync(
                h, self._trampoline, ctypes.c_void_p(key),
                cv, len(const_vars), mv, len(mutable_vars), int(priority))
        except BaseException:
            # never handed to the engine: the registry entry would leak,
            # and the happens-before ticket must be rolled back or every
            # later write to these vars reads as out-of-order
            with _TASKS_LOCK:
                _LIVE_TASKS.pop(key, None)
            getattr(fn, "cancel", lambda: None)()
            raise
        finally:
            self._exit_native()

    def _run_inline(self, fn):
        """Run a task on the calling thread, capturing its exception for
        the next wait point (shared by the trampoline and fallbacks)."""
        try:
            fn()
        except BaseException as e:      # noqa: BLE001
            with _TASKS_LOCK:
                self._errors.append(e)

    # -- synchronization ---------------------------------------------------

    def _enter_native(self):
        """Claim the handle for one native call; None when unavailable."""
        return None if self._core is None else self._core.enter()

    def _exit_native(self):
        self._core.exit()

    def _raise_pending(self):
        with _TASKS_LOCK:
            if not self._errors:
                return
            err, rest = self._errors[0], self._errors[1:]
            self._errors.clear()
        # surface the FIRST failure; chain the rest via __context__ so no
        # async task error is silently discarded when several fail between
        # wait points (e.g. two async checkpoint writes)
        node = err
        for extra in rest:
            logging.getLogger(__name__).error(
                "additional async engine task failure: %r", extra)
            node.__context__ = extra
            node = extra
        raise err

    def wait_for_var(self, var):
        """Block until every write pushed on ``var`` so far has landed."""
        h = self._enter_native()
        if h is not None:
            try:
                self._nat.MXEngineWaitForVar(h, int(var))
            finally:
                self._exit_native()
        self._raise_pending()

    def wait_for_all(self):
        h = self._enter_native()
        if h is not None:
            try:
                self._nat.MXEngineWaitForAll(h)
            finally:
                self._exit_native()
        self._raise_pending()

    def num_pending(self):
        h = self._enter_native()
        if h is None:
            return 0
        try:
            return int(self._nat.MXEnginePendingTasks(h))
        finally:
            self._exit_native()

    def set_sync(self, flag):
        h = self._enter_native()
        if h is not None:
            try:
                self._nat.MXEngineSetSync(h, 1 if flag else 0)
            finally:
                self._exit_native()

    def close(self):
        """Drain and free the native engine (waits out concurrent calls).
        Idempotent; safe against a finalizer that already fired."""
        if self._core is not None:
            self._core.shutdown_sync()

    @property
    def native(self):
        """True when backed by the C++ scheduler (not the sync fallback)."""
        return self._core is not None


_SINGLETON = None
_SINGLETON_LOCK = _lockwitness.make_lock("engine._SINGLETON_LOCK")


def engine():
    """The process-wide host-task engine (created on first use)."""
    global _SINGLETON
    if _SINGLETON is None:
        with _SINGLETON_LOCK:
            if _SINGLETON is None:
                _SINGLETON = ThreadedEngine()
    return _SINGLETON


@atexit.register
def _shutdown():  # pragma: no cover - interpreter teardown
    global _SINGLETON
    if _SINGLETON is not None:
        try:
            _SINGLETON.close()
        except Exception:
            pass
        # Raising at atexit is useless, but swallowing task failures
        # (e.g. a final async checkpoint hitting a full disk) silently
        # is worse: surface them in the log.
        with _TASKS_LOCK:
            errors, _SINGLETON._errors = list(_SINGLETON._errors), []
        for err in errors:
            import logging
            logging.getLogger("mxnet_tpu").error(
                "host-engine task failed before exit: %r", err)
        _SINGLETON = None
