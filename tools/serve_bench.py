"""serve_bench: closed- and open-loop load generator for mxnet_tpu.serving.

Prints ONE JSON line with the numbers a serving tier is judged by:
p50/p99 request latency, sustained QPS, and mean batch occupancy —
the ROADMAP item-1 acceptance artifact, tier-1-safe on CPU with a tiny
MLP (no checkpoint needed: the bench builds and saves its own).

Two phases, both against the same loaded model slot:

* **closed loop** (``--clients N --requests R``): N threads each issue R
  sequential predicts with random batch sizes — latency under
  think-time-free saturation, the scheduler's coalescing at its busiest.
* **open loop** (``--qps Q --duration S``): Poisson arrivals at target
  rate Q, submitted async — latency at a fixed offered load, the number
  a capacity plan actually needs (closed-loop QPS self-throttles; open
  loop shows queueing delay growing before the 503 cliff).

The closed-loop client honors shed signals the way a well-behaved
real client does: a 503 (bounded queue full / breaker open) is retried
after its Retry-After hint and **counted** (``shed_retried``) instead of
inflating the error rate — backpressure is the serving contract, not a
failure.

**Fleet mode** (``--fleet N``): starts an in-process
:class:`~mxnet_tpu.serving.fleet.FleetRouter`, spawns N replica
subprocesses warmed from the bench checkpoint, and drives the closed
loop through the router — reporting aggregate QPS plus the per-replica
request distribution.  ``--rolling-reload`` additionally performs a
zero-downtime rollout of every replica *while the load runs* and gates
on zero failed requests (the ISSUE-13 acceptance artifact; run with
``--fleet 1`` and ``--fleet 4`` to see the near-linear scaling).

The retrace contract is asserted here the same way tests assert it: the
``jit_compiles`` + ``serving_warmup_compiles`` counters must not move
after warmup — every request lands on an AOT-compiled bucket executable
(``retraces_after_warmup`` in the output JSON; nonzero means the bucket
table leaks).

Usage::

    JAX_PLATFORMS=cpu python tools/serve_bench.py
    python tools/serve_bench.py --clients 8 --requests 50 --qps 200 \
        --duration 5 --http     # drive through the live /v1 HTTP surface
    python tools/serve_bench.py --fleet 4 --rolling-reload
"""
from __future__ import annotations

import argparse
import json
import os
import re
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

FEATURES = 16
CLASSES = 8
MODEL = "bench_mlp"


def build_checkpoint(tmpdir, seed=0):
    """A tiny MLP checkpoint in reference save_checkpoint format."""
    import mxnet_tpu as mx
    from mxnet_tpu.model import save_checkpoint
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=32, name="sb_fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=CLASSES, name="sb_fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    shapes = {"data": (1, FEATURES)}
    arg_shapes, _, _ = net.infer_shape(**shapes)
    host = np.random.RandomState(seed)
    args = {name: mx.nd.array((host.randn(*shape) * 0.1)
                              .astype(np.float32))
            for name, shape in zip(net.list_arguments(), arg_shapes)
            if name not in shapes and not name.endswith("_label")}
    prefix = os.path.join(tmpdir, "serve_bench_mlp")
    save_checkpoint(prefix, 0, net, args, {})
    return prefix


def _percentiles(latencies_us):
    if not latencies_us:
        return {"p50_ms": None, "p99_ms": None, "mean_ms": None}
    arr = np.sort(np.asarray(latencies_us, np.float64)) / 1e3
    return {"p50_ms": round(float(np.percentile(arr, 50)), 3),
            "p99_ms": round(float(np.percentile(arr, 99)), 3),
            "mean_ms": round(float(arr.mean()), 3)}


class _Shed(Exception):
    """A 503 with its Retry-After hint: backpressure, not failure."""

    def __init__(self, retry_after_s):
        super().__init__("shed; retry in %.3fs" % retry_after_s)
        self.retry_after_s = retry_after_s


_RETRY_IN_RE = re.compile(r"retry in ([0-9.]+)\s*s")


class _Driver:
    """Issue predicts in-process, through the live HTTP server, or
    through an in-process fleet router."""

    def __init__(self, use_http, port=None, router=None):
        self.use_http = use_http
        self.port = port
        self.router = router

    def _predict_once(self, x):
        from mxnet_tpu.serving.batcher import Overloaded
        if self.router is not None:
            try:
                return self.router.predict(MODEL, {"data": x},
                                           timeout_s=60.0)
            except Overloaded as exc:
                raise _Shed(self._hint(exc)) from exc
        if not self.use_http:
            import mxnet_tpu.serving as serving
            try:
                return serving.predict(MODEL, {"data": x}, timeout=60.0)
            except Overloaded as exc:
                raise _Shed(self._hint(exc)) from exc
        import urllib.error
        import urllib.request
        body = json.dumps({"inputs": {"data": x.tolist()}}).encode()
        req = urllib.request.Request(
            "http://127.0.0.1:%d/v1/models/%s/predict" % (self.port, MODEL),
            data=body, method="POST",
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=60) as resp:
                json.loads(resp.read())
        except urllib.error.HTTPError as exc:
            if exc.code == 503:
                try:
                    after = float(exc.headers.get("Retry-After", 0.05))
                except (TypeError, ValueError):
                    after = 0.05
                raise _Shed(min(max(after, 0.01), 1.0)) from exc
            raise

    @staticmethod
    def _hint(exc):
        """Retry-After from an Overloaded message ('retry in Xs' — the
        breaker includes it; a plain full queue gets a short default)."""
        m = _RETRY_IN_RE.search(str(exc))
        if m:
            try:
                return min(max(float(m.group(1)), 0.01), 1.0)
            except ValueError:
                pass
        return 0.05

    def predict(self, x, deadline_s=60.0):
        """One predict with shed-retry: a 503 sleeps out its Retry-After
        and tries again (bounded by *deadline_s*).  Returns the number
        of sheds absorbed; raises only on real failure."""
        sheds = 0
        t_end = time.perf_counter() + deadline_s
        while True:
            try:
                self._predict_once(x)
                return sheds
            except _Shed as shed:
                if time.perf_counter() + shed.retry_after_s >= t_end:
                    raise
                sheds += 1
                time.sleep(shed.retry_after_s)


def closed_loop(driver, clients, requests, max_rows, seed):
    """N clients, zero think time; returns (latencies_us, wall_s,
    errors, shed_retried).  Latency includes any shed-retry backoff —
    that IS the latency a politely-retrying client observes."""
    latencies = [[] for _ in range(clients)]
    errors = [0] * clients
    sheds = [0] * clients
    barrier = threading.Barrier(clients + 1)

    def client(idx):
        rng = np.random.RandomState(seed + idx)
        xs = [rng.randn(int(rng.randint(1, max_rows + 1)), FEATURES)
              .astype(np.float32) for _ in range(requests)]
        barrier.wait()
        for x in xs:
            t0 = time.perf_counter()
            try:
                sheds[idx] += driver.predict(x)
            except Exception:
                errors[idx] += 1
                continue
            latencies[idx].append((time.perf_counter() - t0) * 1e6)

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(clients)]
    for t in threads:
        t.start()
    barrier.wait()
    t0 = time.perf_counter()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    flat = [v for chunk in latencies for v in chunk]
    return flat, wall, sum(errors), sum(sheds)


def open_loop(qps, duration, max_rows, seed):
    """Poisson arrivals at target *qps* for *duration* seconds, submitted
    async in-process; measures queueing + service latency at a fixed
    offered load.  Returns (latencies_us, wall_s, errors, offered)."""
    import mxnet_tpu.serving as serving
    rng = np.random.RandomState(seed)
    pending, latencies = [], []
    errors = offered = 0
    t_end = time.perf_counter() + duration
    next_at = time.perf_counter()
    while time.perf_counter() < t_end:
        now = time.perf_counter()
        if now < next_at:
            time.sleep(min(next_at - now, 0.005))
            continue
        next_at += rng.exponential(1.0 / qps)
        x = rng.randn(int(rng.randint(1, max_rows + 1)),
                      FEATURES).astype(np.float32)
        offered += 1
        try:
            pending.append(serving.submit(MODEL, {"data": x}))
        except Exception:      # Overloaded: shed — that IS the contract
            errors += 1
    t0_drain = time.perf_counter()
    for req in pending:
        try:
            req.wait(60.0)
            latencies.append(req.latency_us)
        except Exception:
            errors += 1
    wall = duration + (time.perf_counter() - t0_drain)
    return latencies, wall, errors, offered


def spawn_replica(router_addr, prefix, max_batch, rank_hint=None,
                  buckets=None):
    """One replica subprocess warmed from *prefix* (the checkpoint
    tier), registered with the router at *router_addr*."""
    cmd = [sys.executable, "-m", "mxnet_tpu.serving.replica",
           "--router", "%s:%d" % tuple(router_addr),
           "--name", MODEL, "--prefix", prefix, "--epoch", "0",
           "--input-shapes", json.dumps({"data": [1, FEATURES]}),
           "--max-batch", str(max_batch)]
    if rank_hint is not None:
        cmd += ["--rank-hint", str(rank_hint)]
    if buckets:
        cmd += ["--buckets", ",".join(str(b) for b in buckets)]
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu",
                "PYTHONPATH": REPO + os.pathsep
                + env.get("PYTHONPATH", "")})
    return subprocess.Popen(cmd, env=env, cwd=REPO)


def fleet_main(args):
    """--fleet N: router + N replica subprocesses, closed loop through
    the balancer, per-replica distribution, optional rolling reload
    under load (zero-failed-requests gate)."""
    from mxnet_tpu.serving.fleet import FleetRouter

    with tempfile.TemporaryDirectory(prefix="serve-bench-fleet-") as tmp:
        prefix = build_checkpoint(tmp, args.seed)
        router = FleetRouter(port=0).start()
        procs = [spawn_replica(router.addr, prefix, args.max_batch)
                 for _ in range(args.fleet)]
        try:
            if not router.wait_ready(args.fleet, timeout=180.0):
                print(json.dumps({
                    "metric": "serve_bench", "fleet": args.fleet,
                    "error": "only %d/%d replicas became ready"
                             % (router.ready_count(), args.fleet),
                    "view": router.http_view()}, default=repr))
                return 1
            driver = _Driver(False, router=router)
            driver.predict(np.zeros((1, FEATURES), np.float32))

            reload_report = None
            reload_thread = None
            reload_errors = []
            if args.rolling_reload:
                new_prefix = build_checkpoint(tmp, args.seed + 1)

                def _roll():
                    try:
                        reload_errors.append(
                            ("results",
                             router.rolling_reload(MODEL,
                                                   prefix=new_prefix,
                                                   epoch=0)))
                    except Exception as exc:  # gate below reports it
                        reload_errors.append(("error", repr(exc)))

                reload_thread = threading.Thread(target=_roll,
                                                 daemon=True)
                reload_thread.start()

            lat, wall, errors, sheds = closed_loop(
                driver, args.clients, args.requests, args.max_rows,
                args.seed)
            if reload_thread is not None:
                reload_thread.join(300.0)
                results = dict(reload_errors).get("results") or {}
                reload_report = {
                    "ok": bool(results)
                    and all(v == "ok" for v in results.values()),
                    "replicas": {str(r): v for r, v in results.items()},
                    "error": dict(reload_errors).get("error"),
                }
            view = router.http_view()
            distribution = {rank: rep["served"]
                            for rank, rep in view["replicas"].items()}
            report = {
                "metric": "serve_bench",
                "model": MODEL,
                "transport": "fleet",
                "fleet": {
                    "replicas": args.fleet,
                    "distribution": distribution,
                    "replicas_ready": view["replicas_ready"],
                    "hedge_timeout_ms": view["hedge_timeout_ms"],
                    "counters": view["counters"],
                    "rolling_reload": reload_report,
                },
                "closed_loop": dict(
                    _percentiles(lat),
                    clients=args.clients,
                    requests=len(lat),
                    errors=errors,
                    shed_retried=sheds,
                    qps=round(len(lat) / wall, 1) if wall > 0 else None),
            }
            print(json.dumps(report, default=repr))
            balanced = sum(1 for n in distribution.values() if n > 0) \
                == args.fleet
            ok = (errors == 0 and balanced
                  and (reload_report is None or reload_report["ok"]))
            return 0 if ok else 1
        finally:
            router.shutdown_replicas()
            router.stop()
            for p in procs:
                try:
                    p.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    p.kill()


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--clients", type=int, default=4)
    parser.add_argument("--requests", type=int, default=25,
                        help="closed-loop requests per client")
    parser.add_argument("--qps", type=float, default=100.0,
                        help="open-loop offered load")
    parser.add_argument("--duration", type=float, default=2.0,
                        help="open-loop seconds")
    parser.add_argument("--max-rows", type=int, default=4,
                        help="max rows per request (random 1..N)")
    parser.add_argument("--max-batch", type=int, default=16,
                        help="serving bucket ceiling")
    parser.add_argument("--timeout-ms", type=float, default=2.0,
                        help="batch coalescing deadline")
    parser.add_argument("--http", action="store_true",
                        help="drive the closed loop through the live "
                             "/v1 HTTP surface")
    parser.add_argument("--fleet", type=int, default=0, metavar="N",
                        help="fleet mode: route the closed loop through "
                             "an in-process FleetRouter over N replica "
                             "subprocesses; reports per-replica request "
                             "distribution")
    parser.add_argument("--rolling-reload", action="store_true",
                        help="fleet mode: roll every replica onto fresh "
                             "weights WHILE the load runs and gate on "
                             "zero failed requests")
    parser.add_argument("--max-queue-ms", type=float, default=None,
                        help="fail (exit 1) when queue-wait p99 exceeds "
                             "this budget — the SLO gate on the "
                             "request-span decomposition")
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)

    # telemetry ON is load-bearing, not decoration: with it off the
    # retrace watchdog skips compile detection entirely and the
    # zero-retrace gate below would pass vacuously
    os.environ.setdefault("MXNET_TELEMETRY", "1")
    import mxnet_tpu.serving as serving
    from mxnet_tpu import telemetry
    telemetry.set_enabled(True)

    if args.fleet > 0:
        return fleet_main(args)

    with tempfile.TemporaryDirectory(prefix="serve-bench-") as tmpdir:
        prefix = build_checkpoint(tmpdir, args.seed)
        t0 = time.perf_counter()
        slot = serving.load(MODEL, prefix=prefix, epoch=0,
                            input_shapes={"data": (1, FEATURES)},
                            max_batch=args.max_batch,
                            timeout_ms=args.timeout_ms)
        load_s = time.perf_counter() - t0

        port = None
        if args.http:
            from mxnet_tpu.telemetry import server as tserver
            port = tserver.start_server(port=0).port
        driver = _Driver(args.http, port)

        # settle everything lazy (engine threads, first executions) so
        # the retrace assertion below only sees request-path behavior
        driver.predict(np.zeros((1, FEATURES), np.float32))
        driver.predict(np.zeros((args.max_rows, FEATURES), np.float32))
        compiles_after_warmup = (telemetry.counter("jit_compiles")
                                 + telemetry.counter(
                                     "serving_warmup_compiles"))

        closed_lat, closed_wall, closed_err, closed_shed = closed_loop(
            driver, args.clients, args.requests, args.max_rows, args.seed)
        open_lat, open_wall, open_err, offered = open_loop(
            args.qps, args.duration, args.max_rows, args.seed + 1000)

        retraces = (telemetry.counter("jit_compiles")
                    + telemetry.counter("serving_warmup_compiles")
                    - compiles_after_warmup)
        stats = slot.stats()

        def _span_ms(key):
            """p50/p99/mean (ms) of one request-span segment from the
            slot's decomposition histograms."""
            seg = stats.get(key) or {}
            return {"p50_ms": round((seg.get("p50") or 0.0) / 1e3, 3),
                    "p99_ms": round((seg.get("p99") or 0.0) / 1e3, 3),
                    "mean_ms": round((seg.get("mean") or 0.0) / 1e3, 3),
                    "count": seg.get("count", 0)}

        spans = {"queue_wait": _span_ms("queue_wait_us"),
                 "execute": _span_ms("execute_us")}
        queue_p99_ms = spans["queue_wait"]["p99_ms"]
        queue_over_budget = (args.max_queue_ms is not None
                             and queue_p99_ms > args.max_queue_ms)
        report = {
            "metric": "serve_bench",
            "model": MODEL,
            "buckets": list(slot.program.buckets),
            "load_compile_s": round(load_s, 3),
            "transport": "http" if args.http else "inproc",
            "closed_loop": dict(
                _percentiles(closed_lat),
                clients=args.clients,
                requests=len(closed_lat),
                errors=closed_err,
                shed_retried=closed_shed,
                qps=round(len(closed_lat) / closed_wall, 1)
                if closed_wall > 0 else None),
            "open_loop": dict(
                _percentiles(open_lat),
                offered_qps=args.qps,
                offered=offered,
                completed=len(open_lat),
                shed_or_failed=open_err,
                qps=round(len(open_lat) / open_wall, 1)
                if open_wall > 0 else None),
            "mean_batch_occupancy": round(
                stats["batch_occupancy_mean"], 4)
            if stats["batch_occupancy_mean"] is not None else None,
            "padded_rows": stats["padded_rows"],
            "batches": stats["batches"],
            "rows": stats["rows"],
            "mfu_since_load": stats["mfu_since_load"],
            "retraces_after_warmup": retraces,
            # the request-span decomposition: where a p99 actually went
            # (a fat queue_wait means capacity/coalescing, a fat execute
            # means the model itself)
            "spans": spans,
            "max_queue_ms": args.max_queue_ms,
            "queue_wait_over_budget": queue_over_budget,
        }
        # where the served executables live, not what jax defaults to
        dev = slot.program._dev
        report["device"] = {"platform": dev.platform,
                            "kind": dev.device_kind}
        serving.unload(MODEL)
        print(json.dumps(report))
        ok = retraces == 0 and not closed_err and not queue_over_budget
        return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
