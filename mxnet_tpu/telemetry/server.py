"""Live introspection: an in-process HTTP server + background sampler.

``MXNET_TELEMETRY_HTTP=<port>`` starts a stdlib ``http.server`` daemon
thread bound to localhost (port 0 = ephemeral, read it back from
``server.port``) so a live job can be asked what it is doing without
touching the training loop:

    /metrics    Prometheus text exposition (scrape target)
    /healthz    liveness verdict: steps progressing? retrace storm?
                sanitizer violations?  200 when healthy, 503 when not
    /snapshot   full telemetry snapshot (counters/gauges/histograms/
                retraces/costs) as JSON
    /trace      the Chrome traceEvents buffer (load in Perfetto)
    /flight     the flight-recorder payload (ring + stacks + snapshot)
    /stacks     every thread's Python stack, plain text
    /checkpoints  the active CheckpointManager: committed checkpoints,
                last step, preemption state (an inactive stub before a
                manager is constructed)

A background sampler (default 500 ms, ``MXNET_TELEMETRY_SAMPLE_MS``)
keeps the passive gauges honest between steps: host-engine backlog
(``engine_pending_tasks``), device memory watermarks, and the
``step_rate_per_s`` moving rate.  The sampler only *observes* — it looks
the engine and jax up in ``sys.modules`` and never imports, so a process
that never touched the engine never pays for one.

Localhost-only on purpose: these endpoints expose argv and stack traces.
Front with a real proxy if you need the metrics off-host.
"""
from __future__ import annotations

import json
import logging
import os
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from . import core, flight

__all__ = ["IntrospectionServer", "start_server", "stop_server",
           "get_server", "health", "start_from_env",
           "start_sampler", "stop_sampler", "sample_once"]

_LOG = logging.getLogger("mxnet_tpu.telemetry")


def _env_port():
    raw = os.environ.get("MXNET_TELEMETRY_HTTP", "").strip()
    if not raw:
        return None
    try:
        port = int(raw)
    except ValueError:
        return None
    return port if 0 <= port <= 65535 else None


def _env_sampler_ms():
    try:
        return max(50.0, float(os.environ.get("MXNET_TELEMETRY_SAMPLE_MS",
                                              500)))
    except ValueError:
        return 500.0


def _env_stall_secs():
    try:
        return max(1.0, float(os.environ.get("MXNET_HEALTH_STALL_SECS",
                                             120)))
    except ValueError:
        return 120.0


_STALL_SECS = _env_stall_secs()


# --------------------------------------------------------------------------
# health verdict
# --------------------------------------------------------------------------

def _guardian_health():
    """Guardian contribution to the 503 criteria — observe-only
    (``sys.modules`` lookup; a process without an installed guardian
    contributes nothing).  Unhealthy when the consecutive-skip budget is
    exhausted (rollback imminent or, with no manager, the job is
    spinning on poisoned batches) or a rollback is in progress (the last
    step's verdict forced a restore and no applied step has landed
    since)."""
    gmod = sys.modules.get("mxnet_tpu.guardian")
    if gmod is None:
        return None
    try:
        guard = gmod.current()
    except Exception:
        return None
    if guard is None:
        return None
    try:
        desc = guard.describe()
    except Exception:
        return None
    skips = int(desc.get("consecutive_skips") or 0)
    budget = int(desc.get("max_skips") or 0)
    exhausted = budget > 0 and skips >= budget
    rolling_back = desc.get("last_action") == "rollback"
    return {"ok": not (exhausted or rolling_back),
            "consecutive_skips": skips,
            "max_skips": budget,
            "skip_budget_exhausted": exhausted,
            "rollback_in_progress": rolling_back,
            "last_action": desc.get("last_action"),
            "rollbacks": core.counter("guardian_rollbacks")}


def health():
    """(ok, detail-dict).  Healthy means: if training has started, a step
    landed within MXNET_HEALTH_STALL_SECS; no retrace storm; no sanitizer
    violations; and no installed guardian reporting an exhausted skip
    budget or an in-progress rollback.  A process that never steps (pure
    inference, a notebook) is healthy by the step criterion."""
    age = flight.last_step_age()
    stalled = age is not None and age > _STALL_SECS
    storms = core.counter("retrace_storms")
    violations = core.counter("sanitizer_violations")
    guardian = _guardian_health()
    ok = not stalled and storms == 0 and violations == 0 \
        and (guardian is None or guardian["ok"])
    return ok, {
        "ok": ok,
        "steps": {"count": flight.step_count(),
                  "last_step_age_s": None if age is None
                  else round(age, 3),
                  "stalled": stalled,
                  "stall_limit_s": _STALL_SECS},
        "retrace_storms": storms,
        "sanitizer_violations": violations,
        "guardian": guardian,
        "engine_pending_tasks": core.gauge("engine_pending_tasks"),
        "flight_dumps": core.counter("flight_dumps"),
    }


# --------------------------------------------------------------------------
# HTTP server
# --------------------------------------------------------------------------

_INDEX = ("mxnet_tpu introspection\n"
          "endpoints: /metrics /healthz /readyz /snapshot /trace "
          "/flight /stacks /checkpoints /peers /fleet /guardian "
          "/timeseries /profile\n"
          "serving:   /v1/models  /v1/models/<name>[/predict|/load|"
          "/unload|/reload]\n")


def _serving_reply(method, path, body, allow_import=False):
    """Delegate a /v1 path to the serving tier.  GETs and predicts only
    observe (``sys.modules`` lookup — a process that never imported
    serving answers 404 and initializes nothing); *allow_import* is set
    for the explicit management POSTs, where the operator is asking this
    process to BECOME a server."""
    serving = sys.modules.get("mxnet_tpu.serving")
    if serving is None and allow_import:
        import importlib
        serving = importlib.import_module("mxnet_tpu.serving")
    if serving is None:
        return (404, "application/json",
                json.dumps({"error": "serving tier not initialized "
                            "(import mxnet_tpu.serving and load a model, "
                            "or POST /v1/models/<name>/load)"}))
    return serving.handle_http(method, path, body)


class _Handler(BaseHTTPRequestHandler):
    server_version = "mxnet-tpu-introspect/1"

    def log_message(self, *args):            # quiet: we ARE the telemetry
        pass

    def _reply(self, code, content_type, body, headers=()):
        if isinstance(body, str):
            body = body.encode("utf-8")
        self.send_response(code)
        self.send_header("Content-Type", content_type)
        for key, value in headers:
            self.send_header(key, value)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _reply_json(self, obj, code=200):
        self._reply(code, "application/json",
                    json.dumps(obj, default=repr))

    def do_GET(self):                        # noqa: N802 (stdlib API)
        path = self.path.split("?", 1)[0].rstrip("/") or "/"
        try:
            if path == "/":
                self._reply(200, "text/plain; charset=utf-8", _INDEX)
            elif path == "/metrics":
                self._reply(200,
                            "text/plain; version=0.0.4; charset=utf-8",
                            core.prometheus_text())
            elif path == "/healthz":
                ok, detail = health()
                self._reply_json(detail, 200 if ok else 503)
            elif path == "/readyz":
                # READINESS, split from /healthz LIVENESS: "safe to
                # route new traffic here" vs "process is not wedged".
                # A replica compiling/warming/draining is alive (200 on
                # /healthz) but not ready (503 here) — the router and
                # any external LB key off this one.  Observe-only
                # sys.modules delegation like /v1: a process without a
                # serving tier is trivially ready.
                serving = sys.modules.get("mxnet_tpu.serving")
                if serving is None:
                    self._reply_json({"ok": True, "serving": False}, 200)
                else:
                    ok, detail = serving.readiness()
                    self._reply_json(dict(detail, ok=ok, serving=True),
                                     200 if ok else 503)
            elif path == "/snapshot":
                self._reply_json(core.snapshot())
            elif path == "/trace":
                self._reply_json(core.chrome_trace_payload())
            elif path == "/flight":
                self._reply_json(flight.payload("http"))
            elif path == "/checkpoints":
                # observe-only sys.modules lookup, like /v1 — never
                # initializes anything.  `import mxnet_tpu` pulls the
                # checkpoint package in, so in practice this answers the
                # inactive stub until a CheckpointManager exists; the
                # 404 arm only covers a standalone-telemetry embedding.
                ckpt = sys.modules.get("mxnet_tpu.checkpoint")
                if ckpt is None:
                    self._reply_json(
                        {"error": "checkpoint subsystem not initialized "
                                  "(construct a CheckpointManager)"}, 404)
                else:
                    self._reply_json(ckpt.http_view())
            elif path == "/guardian":
                # observe-only sys.modules lookup, like /checkpoints:
                # `import mxnet_tpu` pulls gluon (hence guardian) in, so
                # in practice this answers the inactive stub until a
                # TrainingGuardian is installed; the 404 arm only covers
                # a standalone-telemetry embedding.
                guard = sys.modules.get("mxnet_tpu.guardian")
                if guard is None:
                    self._reply_json(
                        {"error": "guardian subsystem not initialized "
                                  "(construct a TrainingGuardian)"}, 404)
                else:
                    self._reply_json(guard.http_view())
            elif path == "/fleet":
                # observe-only sys.modules lookup, like /peers: the
                # dist part reports the scheduler's live digest table
                # (or a worker's cached snapshot); the serving part
                # reports the in-process FleetRouter's replica table —
                # never network IO from this handler.
                out = {}
                dist = sys.modules.get("mxnet_tpu.dist_ps")
                if dist is not None:
                    out = dist.fleet_view()
                fleet_mod = sys.modules.get("mxnet_tpu.serving.fleet")
                router = fleet_mod.current_router() \
                    if fleet_mod is not None else None
                if router is not None:
                    out["serving_fleet"] = router.http_view()
                if not out:
                    self._reply_json(
                        {"error": "no fleet in this process (neither "
                                  "mxnet_tpu.dist_ps nor a serving "
                                  "FleetRouter is initialized)"}, 404)
                else:
                    self._reply_json(out)
            elif path == "/peers":
                # observe-only sys.modules lookup, like /checkpoints: a
                # process that never touched the dist transport answers
                # 404 and initializes nothing.  peer_view() itself does
                # no network IO — it reports the heartbeat thread's
                # cached scheduler snapshot (or the live table when this
                # process IS the scheduler).
                dist = sys.modules.get("mxnet_tpu.dist_ps")
                if dist is None:
                    self._reply_json(
                        {"error": "dist transport not initialized "
                                  "(no mxnet_tpu.dist_ps in this "
                                  "process)"}, 404)
                else:
                    self._reply_json(dist.peer_view())
            elif path == "/timeseries":
                # observe-only sys.modules lookup, like /checkpoints:
                # the summary reports per-ring bounds and last values,
                # never the full rings (timeseries.export_json is the
                # bulk path); ?full=1 serves the whole export for a
                # quick scrape of a short run
                ts = sys.modules.get("mxnet_tpu.telemetry.timeseries")
                if ts is None:
                    self._reply_json(
                        {"error": "timeseries store not initialized "
                                  "(import mxnet_tpu.telemetry)"}, 404)
                elif "full=1" in (self.path.split("?", 1) + [""])[1]:
                    self._reply_json(ts.export())
                else:
                    self._reply_json(ts.summary())
            elif path == "/profile":
                # observe-only: the runtime per-program device-time
                # table via sys.modules — a process that never imported
                # device reports None, triggers nothing
                dev = sys.modules.get("mxnet_tpu.telemetry.device")
                self._reply_json({
                    "device": dev.device_report()
                    if dev is not None else None})
            elif path == "/stacks":
                stacks = flight.thread_stacks()
                text = "\n".join("--- %s ---\n%s" % (k, "".join(v))
                                 for k, v in sorted(stacks.items()))
                self._reply(200, "text/plain; charset=utf-8", text)
            elif path.startswith("/v1/"):
                self._reply(*_serving_reply("GET", path, None))
            else:
                self._reply(404, "text/plain; charset=utf-8",
                            "unknown endpoint\n" + _INDEX)
        except BrokenPipeError:              # client went away mid-reply
            pass
        except Exception as exc:             # introspection never kills
            try:
                self._reply(500, "text/plain; charset=utf-8",
                            "introspection error: %r" % (exc,))
            except Exception:
                pass

    def do_POST(self):                       # noqa: N802 (stdlib API)
        path = self.path.split("?", 1)[0].rstrip("/") or "/"
        try:
            try:
                length = int(self.headers.get("Content-Length") or 0)
            except ValueError:
                length = 0
            body = self.rfile.read(length) if length > 0 else b""
            if path.startswith("/v1/"):
                # management actions (load/unload/reload) may initialize
                # the serving tier; predict stays observe-only
                allow_import = path.rsplit("/", 1)[-1] == "load"
                code, ctype, payload = _serving_reply("POST", path, body,
                                                      allow_import)
                # shed load politely: retry soon
                headers = (("Retry-After", "1"),) if code == 503 else ()
                self._reply(code, ctype, payload, headers=headers)
            else:
                self._reply(404, "text/plain; charset=utf-8",
                            "unknown endpoint\n" + _INDEX)
        except BrokenPipeError:
            pass
        except Exception as exc:
            try:
                self._reply(500, "text/plain; charset=utf-8",
                            "introspection error: %r" % (exc,))
            except Exception:
                pass


class IntrospectionServer:
    """One ThreadingHTTPServer on localhost + its serve thread."""

    def __init__(self, port):
        self._httpd = ThreadingHTTPServer(("127.0.0.1", port), _Handler)
        self._httpd.daemon_threads = True
        self._thread = threading.Thread(
            target=self._httpd.serve_forever,
            name="mxnet-introspect-http", daemon=True)

    @property
    def port(self):
        return self._httpd.server_address[1]

    def start(self):
        self._thread.start()
        return self

    def stop(self):
        self._httpd.shutdown()
        self._httpd.server_close()


_server = None
_server_lock = threading.Lock()


def start_server(port=None, sample_ms=None):
    """Start (or return the running) introspection server; also starts
    the background sampler.  *port* 0 binds an ephemeral port."""
    global _server
    with _server_lock:
        if _server is None:
            if port is None:
                port = _env_port()
            if port is None:
                raise ValueError(
                    "no port: pass one or set MXNET_TELEMETRY_HTTP")
            _server = IntrospectionServer(port).start()
            _LOG.info("introspection server on http://127.0.0.1:%d "
                      "(/metrics /healthz /snapshot /trace /flight "
                      "/stacks)", _server.port)
        server = _server
    start_sampler(sample_ms)
    return server


def get_server():
    return _server


def stop_server():
    global _server
    with _server_lock:
        server, _server = _server, None
    if server is not None:
        server.stop()
    stop_sampler()


def start_from_env():
    """Import-time hook: start iff MXNET_TELEMETRY_HTTP is set."""
    if _env_port() is None:
        return None
    try:
        return start_server()
    except OSError as exc:       # port taken: log, never break import
        _LOG.warning("introspection server failed to bind: %s", exc)
        return None


# --------------------------------------------------------------------------
# background sampler
# --------------------------------------------------------------------------

_sampler = None
_sampler_lock = threading.Lock()


def sample_once(rate_state=None):
    """One sampler tick: engine backlog, device memory, step rate.
    *rate_state* is the (prev_steps, prev_monotonic) carried between
    ticks; returns the updated tuple."""
    core._sample_engine_pending()
    if "jax" in sys.modules:     # observe-only: never initialize jax
        core.sample_memory()
    serving = sys.modules.get("mxnet_tpu.serving")
    if serving is not None:      # observe-only: refresh queue-depth gauges
        try:
            serving.refresh_gauges()
        except Exception:
            pass
    dist = sys.modules.get("mxnet_tpu.dist_ps")
    if dist is not None:         # observe-only: ps_dead_peers gauge
        try:
            dist.refresh_gauges()
        except Exception:
            pass
    now = time.monotonic()
    steps = flight.step_count()
    if rate_state is not None:
        prev_steps, prev_t = rate_state
        dt = now - prev_t
        if dt > 0:
            core.set_gauge("step_rate_per_s",
                           max(0, steps - prev_steps) / dt)
    return (steps, now)


def start_sampler(sample_ms=None):
    """Start the daemon sampler thread (idempotent)."""
    global _sampler
    with _sampler_lock:
        if _sampler is not None:
            return _sampler[0]
        if sample_ms is None:
            sample_ms = _env_sampler_ms()
        interval = max(0.05, sample_ms / 1e3)
        stop = threading.Event()

        def _loop():
            state = (flight.step_count(), time.monotonic())
            while not stop.wait(interval):
                try:
                    state = sample_once(state)
                except Exception:    # a dying backend must not kill us
                    pass

        thread = threading.Thread(target=_loop,
                                  name="mxnet-telemetry-sampler",
                                  daemon=True)
        thread.start()
        _sampler = (thread, stop)
        return thread


def stop_sampler():
    global _sampler
    with _sampler_lock:
        sampler, _sampler = _sampler, None
    if sampler is not None:
        sampler[1].set()
