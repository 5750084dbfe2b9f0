"""Serving fleet (ISSUE 13 tentpole): multi-replica router with
health-gated failover, hedged retries, and zero-downtime rollout.

Acceptance contract pinned here:

* an accepted request completes — hedged or failed over — through a
  replica death, within its deadline (``test_failover...``, and the
  kill -9 subprocess variant via ``tools/fleet_smoke.py``);
* a dead replica is shed within 2x the heartbeat interval and a
  restarted replica re-registers into its dead rank, warms from the
  checkpoint tier, and takes traffic again;
* a rolling reload of every replica completes with zero failed
  requests and actually swaps the weights;
* the half-open circuit breaker admits EXACTLY one probe under real
  thread contention (the PR-8 review fix, stress-locked);
* the ``fleet.route`` / ``replica.predict`` chaos sites parse, inject,
  and replay deterministically.
"""
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
import mxnet_tpu.serving as serving
from mxnet_tpu import chaos, telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.model import save_checkpoint
from mxnet_tpu.serving import fleet as fleet_mod
from mxnet_tpu.serving.batcher import Overloaded
from mxnet_tpu.serving.fleet import FleetRouter
from mxnet_tpu.serving.replica import ReplicaServer
from mxnet_tpu.serving.slots import CircuitBreaker

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FEATURES = 6
CLASSES = 3
BUCKETS = (1, 4)          # small ladder: 2 compiles per replica


def _save_mlp(prefix, seed=0):
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=8, name="fl_fc1")
    net = mx.sym.Activation(net, act_type="tanh")
    net = mx.sym.FullyConnected(net, num_hidden=CLASSES, name="fl_fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    shapes = {"data": (1, FEATURES)}
    arg_shapes, _, _ = net.infer_shape(**shapes)
    host = np.random.RandomState(seed)
    args = {name: mx.nd.array((host.randn(*shape) * 0.3)
                              .astype(np.float32))
            for name, shape in zip(net.list_arguments(), arg_shapes)
            if name not in shapes and not name.endswith("_label")}
    save_checkpoint(prefix, 0, net, args, {})
    return prefix


@pytest.fixture(scope="module")
def checkpoint(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("fleet")
    return _save_mlp(str(tmp / "mlp"))


@pytest.fixture
def fast_fleet_env(monkeypatch):
    """Tight heartbeats so dead-detection tests run in milliseconds."""
    monkeypatch.setenv("MXNET_FLEET_HEARTBEAT_S", "0.15")
    fleet_mod.refresh_from_env()
    yield
    fleet_mod.refresh_from_env()


def _spawn_replica(router, checkpoint, rank_hint=None):
    rep = ReplicaServer(router=router.addr, port=0,
                        rank_hint=rank_hint).start()
    rep.load("mlp", prefix=checkpoint, epoch=0,
             input_shapes={"data": (1, FEATURES)}, buckets=BUCKETS)
    rep.advertise_ready()
    return rep


@pytest.fixture
def fleet(checkpoint, fast_fleet_env):
    """Router + two in-process replicas, torn down hard."""
    router = FleetRouter(port=0).start()
    replicas = [_spawn_replica(router, checkpoint) for _ in range(2)]
    assert router.wait_ready(2, timeout=30.0), router.http_view()
    yield router, replicas
    chaos.configure(None)
    router.stop()
    for rep in replicas:
        try:
            rep.stop(drain=False)
        except Exception:
            pass


def _x(n, seed=0):
    return np.random.RandomState(seed).randn(n, FEATURES) \
        .astype(np.float32)


# ---------------------------------------------------------------------------
# satellite: breaker half-open stress (the PR-8 review fix, under real
# concurrency)
# ---------------------------------------------------------------------------

def test_breaker_half_open_admits_exactly_one_probe_under_threads():
    """8 threads hammer a half-open breaker through a barrier: exactly
    one leased probe admits; everyone else sheds until record()."""
    breaker = CircuitBreaker(threshold=1, cooldown_s=0.05)
    breaker.record(ok=False)               # open
    assert breaker.state() == "open"
    time.sleep(0.08)                       # cooldown elapsed: half-open
    assert breaker.state() == "half-open"
    n = 8
    barrier = threading.Barrier(n)
    admitted = []
    lock = threading.Lock()

    def prober():
        barrier.wait()
        ok = breaker.allow()
        with lock:
            admitted.append(ok)

    threads = [threading.Thread(target=prober) for _ in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(10.0)
    assert sum(admitted) == 1, admitted
    # the probe resolves: success closes, the next allow is free again
    breaker.record(ok=True)
    assert breaker.state() == "closed"
    assert breaker.allow()


# ---------------------------------------------------------------------------
# satellite: chaos grammar — the new fleet sites
# ---------------------------------------------------------------------------

def test_chaos_spec_round_trip_fleet_sites():
    spec = "seed=3;fleet.route:exc@2;replica.predict:delay@1-2=3ms"
    seed, rules = chaos.parse_spec(spec)
    assert seed == 3
    assert [r.describe() for r in rules] == [
        "fleet.route:exc@2", "replica.predict:delay@1-2=0.003s"]
    # prefix matching: a bare "fleet" clause covers fleet.route
    _, rules = chaos.parse_spec("fleet:exc@1")
    assert rules[0].matches("fleet.route")
    # unknown sites still refused loudly
    with pytest.raises(chaos.ChaosSpecError):
        chaos.parse_spec("fleet.rouet:exc@1")


def test_chaos_fleet_route_site_fires_and_replays(fleet):
    """Seeded router-side chaos injects deterministically and the fault
    log replays bitwise from the same spec + seed."""
    router, _ = fleet
    spec = "seed=11;fleet.route:exc@2"
    chaos.configure(spec)
    logs = []
    for _ in range(2):
        errors = 0
        for i in range(4):
            try:
                router.predict("mlp", {"data": _x(1, seed=i)},
                               timeout_s=10.0)
            except chaos.ChaosError:
                errors += 1
        assert errors == 1       # exactly the @2 occurrence
        logs.append(chaos.fault_log())
        chaos.reset()
    assert logs[0] == logs[1] == [
        ("fleet.route", "fleet.route", "exc", 2)]
    chaos.configure(None)


# ---------------------------------------------------------------------------
# tentpole: routing, failover, hedging
# ---------------------------------------------------------------------------

def test_least_outstanding_routing_spreads_idle_traffic(fleet):
    """Sequential (never-concurrent) requests round-robin via the
    least-served tie-break — the per-replica distribution both
    serve_bench --fleet and /fleet report."""
    router, _ = fleet
    for i in range(8):
        router.predict("mlp", {"data": _x(2, seed=i)}, timeout_s=10.0)
    view = router.http_view()
    served = {rank: rep["served"]
              for rank, rep in view["replicas"].items()}
    assert sum(served.values()) == 8
    assert all(n == 4 for n in served.values()), served
    assert view["models"] == ["mlp"]


def test_predict_results_match_local_and_unknown_model_404s(fleet,
                                                           checkpoint):
    router, replicas = fleet
    x = _x(3, seed=7)
    outs, meta = router.predict_detail("mlp", {"data": x},
                                       timeout_s=10.0)
    # bitwise vs the replica's own slot (same AOT program, same weights)
    local = replicas[0].registry.get("mlp").predict({"data": x})
    np.testing.assert_array_equal(np.asarray(outs[0]),
                                  np.asarray(local[0]))
    assert meta["output_names"] == ["softmax_output"]
    with pytest.raises(MXNetError, match="is not loaded"):
        router.predict("nope", {"data": x}, timeout_s=5.0)


def test_failover_completes_accepted_request_through_replica_death(
        fleet, checkpoint):
    """(1) A replica-side fault on the first attempt fails over to the
    other replica and the accepted request completes (deterministic via
    the replica.predict chaos seam).  (2) An actually-killed replica is
    shed within 2x the heartbeat interval and the fleet keeps serving
    on the survivor."""
    router, replicas = fleet
    before = telemetry.counter("fleet_failovers")
    chaos.configure("seed=1;replica.predict:exc@1")
    outs, meta = router.predict_detail("mlp", {"data": _x(2)},
                                       timeout_s=10.0)
    chaos.configure(None)
    assert np.asarray(outs[0]).shape == (2, CLASSES)
    assert meta["attempts"] == 2
    assert telemetry.counter("fleet_failovers") == before + 1
    # now kill one replica for real (hard stop: listener + conns die)
    replicas[0].stop(drain=False)
    for i in range(4):
        outs = router.predict("mlp", {"data": _x(2, seed=i)},
                              timeout_s=10.0)
        assert np.asarray(outs[0]).shape == (2, CLASSES)
    # the dead replica is shed within 2x the heartbeat interval
    deadline = time.monotonic() + 2.0 * fleet_mod.heartbeat_s() + 0.5
    while time.monotonic() < deadline:
        if router.http_view()["replicas"]["0"]["state"] == "dead":
            break
        time.sleep(0.01)
    assert router.http_view()["replicas"]["0"]["state"] == "dead"
    assert router.ready_count() == 1


def test_hedge_fires_after_timeout_and_first_reply_wins(fleet,
                                                        monkeypatch):
    """A deterministically-slow replica RPC (chaos delay on the first
    replica.predict) triggers one hedged duplicate after the pinned
    hedge timeout; the fast replica's reply wins well before the slow
    one lands."""
    router, _ = fleet
    monkeypatch.setenv("MXNET_FLEET_HEDGE_MS", "50")
    fleet_mod.refresh_from_env()
    chaos.configure("seed=5;replica.predict:delay@1=600ms")
    before = telemetry.counter("fleet_hedges")
    t0 = time.perf_counter()
    outs, meta = router.predict_detail("mlp", {"data": _x(1)},
                                       timeout_s=10.0)
    wall = time.perf_counter() - t0
    assert np.asarray(outs[0]).shape == (1, CLASSES)
    assert telemetry.counter("fleet_hedges") == before + 1
    assert meta["hedged_win"] and meta["attempts"] == 2
    assert wall < 0.55, "hedge did not cut the slow replica's tail " \
        "(%.3fs)" % wall
    chaos.configure(None)
    fleet_mod.refresh_from_env()


def test_dead_rank_takeover_and_warm_rejoin(fleet, checkpoint):
    """A replacement replica re-registers into the dead rank, warms its
    slots from the checkpoint tier, and takes traffic."""
    router, replicas = fleet
    replicas[0].stop(drain=False)
    deadline = time.monotonic() + 3.0
    while time.monotonic() < deadline \
            and router.http_view()["replicas"]["0"]["state"] != "dead":
        time.sleep(0.01)
    fresh = _spawn_replica(router, checkpoint, rank_hint=0)
    replicas.append(fresh)                  # fixture teardown owns it
    assert fresh.rank == 0
    assert router.wait_ready(2, timeout=15.0)
    for i in range(4):
        router.predict("mlp", {"data": _x(1, seed=i)}, timeout_s=10.0)
    assert router.http_view()["replicas"]["0"]["served"] > 0


# ---------------------------------------------------------------------------
# tentpole: zero-downtime rolling reload
# ---------------------------------------------------------------------------

def test_rolling_reload_zero_failed_requests_and_new_weights(
        fleet, tmp_path):
    """Roll both replicas onto fresh weights while background load
    runs: zero failed requests, and the fleet actually serves the new
    weights afterwards."""
    router, _ = fleet
    new_prefix = _save_mlp(str(tmp_path / "mlp2"), seed=99)
    x = _x(2, seed=3)
    before = np.asarray(router.predict("mlp", {"data": x},
                                       timeout_s=10.0)[0])
    stop = threading.Event()
    errors = []
    completed = [0]

    def load_loop():
        i = 0
        while not stop.is_set():
            i += 1
            try:
                router.predict("mlp", {"data": _x(1, seed=i)},
                               timeout_s=10.0)
                completed[0] += 1
            except Exception as exc:
                errors.append(repr(exc))

    thread = threading.Thread(target=load_loop, daemon=True)
    thread.start()
    results = router.rolling_reload("mlp", prefix=new_prefix, epoch=0)
    stop.set()
    thread.join(30.0)
    assert results == {0: "ok", 1: "ok"}
    assert not errors, errors[:3]
    assert completed[0] > 0
    after = np.asarray(router.predict("mlp", {"data": x},
                                      timeout_s=10.0)[0])
    assert not np.array_equal(before, after), \
        "reload did not swap the weights"
    assert router.ready_count() == 2


# ---------------------------------------------------------------------------
# satellite: /readyz (readiness) split from /healthz (liveness)
# ---------------------------------------------------------------------------

@pytest.fixture
def live_server():
    from mxnet_tpu.telemetry import server
    # /healthz reads the process's counters and step clock: what earlier
    # files of this worker left there (a sanitizer violation, a step
    # older than the stall limit) is not this server's health
    telemetry.reset()
    srv = server.start_server(port=0, sample_ms=100)
    yield srv
    server.stop_server()


def _http_get(srv, path):
    import urllib.error
    import urllib.request
    try:
        with urllib.request.urlopen(
                "http://127.0.0.1:%d%s" % (srv.port, path),
                timeout=30) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as exc:
        return exc.code, json.loads(exc.read())


def test_readyz_liveness_split_and_slot_compile_state(live_server,
                                                      checkpoint):
    serving.reset_registry()
    try:
        registry = serving.get_registry()
        registry.load("mlp", prefix=checkpoint, epoch=0,
                      input_shapes={"data": (1, FEATURES)},
                      buckets=BUCKETS)
        code, detail = _http_get(live_server, "/readyz")
        assert code == 200 and detail["ok"] and detail["serving"]
        assert detail["slots"]["slots"] == {"mlp": "ready"}
        # a compiling/reloading slot flips readiness, NOT liveness
        registry.get("mlp").status = "reloading"
        code, detail = _http_get(live_server, "/readyz")
        assert code == 503 and not detail["ok"]
        assert detail["slots"]["not_ready"] == ["mlp"]
        code, health = _http_get(live_server, "/healthz")
        assert code == 200 and health["ok"], \
            "liveness must not inherit readiness"
        registry.get("mlp").status = "ready"
        code, detail = _http_get(live_server, "/readyz")
        assert code == 200
    finally:
        serving.reset_registry()


def test_readyz_tracks_replica_state_and_fleet_view(live_server,
                                                   fleet):
    router, replicas = fleet
    code, detail = _http_get(live_server, "/readyz")
    assert code == 200
    assert detail["fleet"]["replicas_ready"] == 2
    # the process's replica view: warming = not ready
    replicas[-1].state = "warming"
    code, detail = _http_get(live_server, "/readyz")
    assert code == 503 and detail["replica"]["state"] == "warming"
    replicas[-1].state = "ready"
    # /fleet carries the serving fleet table
    code, view = _http_get(live_server, "/fleet")
    assert code == 200
    assert view["serving_fleet"]["replicas_total"] == 2


def test_router_http_surface_predict_and_rolling_reload(live_server,
                                                        fleet,
                                                        checkpoint):
    """The /v1 surface fronts the fleet when a router is live: predict
    routes through the balancer (response names the replica), reload is
    the rolling rollout, load is refused."""
    import urllib.request
    router, _ = fleet

    def post(path, obj):
        import urllib.error
        req = urllib.request.Request(
            "http://127.0.0.1:%d%s" % (live_server.port, path),
            data=json.dumps(obj).encode(), method="POST")
        try:
            with urllib.request.urlopen(req, timeout=60) as resp:
                return resp.status, json.loads(resp.read())
        except urllib.error.HTTPError as exc:
            return exc.code, json.loads(exc.read())

    code, reply = post("/v1/models/mlp/predict",
                       {"inputs": {"data": _x(2).tolist()}})
    assert code == 200, reply
    assert reply["replica"] in (0, 1)
    assert len(reply["outputs"]["softmax_output"]) == 2
    code, reply = post("/v1/models/mlp/reload",
                       {"prefix": checkpoint, "epoch": 0})
    assert code == 200 and reply["ok"], reply
    assert set(reply["replicas"]) == {"0", "1"}
    code, reply = post("/v1/models/other/load", {"prefix": "x"})
    assert code == 400 and "per-replica" in reply["error"]
    code, body = _http_get(live_server, "/v1/models")
    assert code == 200 and body["fleet"]["replicas_ready"] == 2


# ---------------------------------------------------------------------------
# acceptance: the kill -9 subprocess smoke (fast tier-1 variant of
# tools/fleet_smoke.py)
# ---------------------------------------------------------------------------

def test_fleet_smoke_tier1():
    """Router + 3 replica subprocesses; kill -9 one mid-load: shed
    within 2x heartbeat, zero lost accepted requests, bounded p99, and
    the restarted replica re-registers into its dead rank and serves.
    The full-fat surface lives in tools/fleet_smoke.py; this is the
    CI-gated fast variant."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "fleet_smoke.py"),
         "--replicas", "3", "--clients", "3", "--requests", "10",
         "--json"],
        capture_output=True, text=True, timeout=420, env=env, cwd=REPO)
    assert out.returncode == 0, \
        "fleet_smoke failed:\n%s\n%s" % (out.stdout, out.stderr[-3000:])
    line = [l for l in out.stdout.splitlines() if l.startswith("{")][-1]
    report = json.loads(line)
    assert report["ok"], report["problems"]
    assert report["phase_a"]["errors"] == 0
    assert report["dead_detect_s"] <= 2.0 * 0.5 + 0.5
    assert report["phase_b"]["revived_rank_state"] == "ready"
    assert report["phase_b"]["revived_rank_served"] > 0


@pytest.mark.slow
def test_serve_bench_fleet_mode_scales_and_balances(tmp_path):
    """serve_bench --fleet 2 --rolling-reload: per-replica distribution
    reported, zero errors, rolling reload ok (the --fleet 1 vs 4 QPS
    scaling comparison is the operator-run acceptance)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "serve_bench.py"),
         "--fleet", "2", "--clients", "3", "--requests", "12",
         "--rolling-reload"],
        capture_output=True, text=True, timeout=600, env=env,
        cwd=str(tmp_path))
    assert out.returncode == 0, out.stderr[-3000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("{")][-1]
    report = json.loads(line)
    assert report["closed_loop"]["errors"] == 0
    assert report["fleet"]["rolling_reload"]["ok"]
    assert sum(int(n) for n
               in report["fleet"]["distribution"].values()) > 0
