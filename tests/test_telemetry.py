"""Runtime telemetry layer (ISSUE 2): hierarchical spans, metrics
registry, retrace watchdog, exporters, and the trace_report tool.

Acceptance contract: a 3-step train loop under MXNET_TELEMETRY=1 produces
a trace where ``trainer_step`` spans contain nested kvstore/optimizer
child spans; ``trace_report.py`` prints step-time percentiles + top ops +
the retrace table from it; an intentional shape-changing input triggers
exactly ONE retrace-storm warning; and with telemetry off the
``xla_program_calls`` accounting (tests/test_fused_trainer.py) is
untouched — the watchdog/span off path is a cached-bool check.
"""
import json
import logging
import os
import re
import subprocess
import sys
import threading

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd, telemetry
from mxnet_tpu.gluon import nn

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def tel(monkeypatch):
    """Telemetry enabled via the env gate, state isolated per test."""
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    telemetry.refresh_from_env()
    telemetry.reset()
    yield telemetry
    telemetry.reset()
    monkeypatch.delenv("MXNET_TELEMETRY", raising=False)
    telemetry.refresh_from_env()


def _train_loop(steps=3, width=8):
    np.random.seed(0)
    mx.random.seed(0)
    net = nn.Sequential()
    net.add(nn.Dense(width, activation="relu"))
    net.add(nn.Dense(3))
    net.initialize(init=mx.initializer.Xavier())
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1, "momentum": 0.9},
                            kvstore="device")
    loss_fn = gluon.loss.L2Loss()
    for _ in range(steps):
        x = mx.nd.array(np.random.randn(8, 6).astype(np.float32))
        y = mx.nd.array(np.random.randn(8, 3).astype(np.float32))
        with autograd.record():
            loss = loss_fn(net(x), y)
        loss.backward()
        trainer.step(8)
    return trainer


def _contained(child, parent):
    return (parent["ts"] <= child["ts"]
            and child["ts"] + child["dur"] <= parent["ts"] + parent["dur"])


# ---- acceptance: 3-step loop -> nested spans -> trace_report -------------

def test_train_loop_nested_spans(tel, tmp_path):
    _train_loop(steps=3)
    trace = json.load(open(tel.dump_chrome_trace(
        str(tmp_path / "trace.json"))))
    ev = trace["traceEvents"]

    steps = [e for e in ev if e["name"] == "trainer_step"]
    assert len(steps) == 3
    assert all(e["cat"] == "step" for e in steps)

    kids = [e for e in ev
            if e.get("args", {}).get("parent") == "trainer_step"]
    kid_names = {e["name"] for e in kids}
    assert "kvstore_push_pull" in kid_names
    assert "fused_optimizer_step" in kid_names
    # structural parentage is backed by temporal containment on the track
    for child in kids:
        assert any(_contained(child, s) for s in steps), child

    # ph:"M" metadata labels the tracks (satellite: Perfetto track names)
    meta = [e for e in ev if e.get("ph") == "M"]
    assert any(e["name"] == "process_name" for e in meta)
    assert any(e["name"] == "thread_name" for e in meta)

    # step-time histogram observed once per step
    assert tel.histogram("step_time_us").count == 3
    # memory watermarks sampled at the step boundary
    assert tel.gauge("host_rss_peak_bytes") > 0


def test_trace_report_renders_all_sections(tel, tmp_path, capsys):
    _train_loop(steps=3)
    trace = tel.dump_chrome_trace(str(tmp_path / "trace.json"))
    snap = tel.dump_snapshot(str(tmp_path / "snap.json"))

    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import trace_report
    finally:
        sys.path.pop(0)
    assert trace_report.main([trace, "--snapshot", snap, "--top", "5"]) == 0
    out = capsys.readouterr().out
    assert "== step time ==" in out and "p50" in out
    assert "== top 5 ops by self time ==" in out
    assert "trainer_step" in out
    assert "== retrace report ==" in out
    assert "fused_trainer_step" in out       # the step program compiled once


def test_trace_report_smoke_cli(tel, tmp_path):
    """Satellite: the CLI runs against a freshly dumped trace (separate
    interpreter, no framework import)."""
    _train_loop(steps=2)
    trace = tel.dump_chrome_trace(str(tmp_path / "trace.json"))
    snap = tel.dump_snapshot(str(tmp_path / "snap.json"))
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "trace_report.py"),
         trace, "--snapshot", snap],
        capture_output=True, timeout=60)
    assert proc.returncode == 0, proc.stderr.decode()
    assert b"step time" in proc.stdout
    assert b"retrace report" in proc.stdout


# ---- retrace watchdog ----------------------------------------------------

def test_shape_change_triggers_one_retrace_storm(tel, caplog):
    """Shape-unstable input recompiles the per-slot optimizer program every
    call; crossing the limit must log exactly ONE structured warning."""
    tel.configure(retrace_limit=3)
    opt = mx.optimizer.create("sgd", learning_rate=0.1)
    with caplog.at_level(logging.WARNING, logger="mxnet_tpu.telemetry"):
        for n in range(1, 7):                     # 6 shapes -> 6 compiles
            w = nd.array(np.zeros(n, np.float32))
            g = nd.array(np.ones(n, np.float32))
            opt.update(0, w, g, opt.create_state(0, w))
    storms = [r for r in caplog.records if "retrace-storm" in r.getMessage()]
    assert len(storms) == 1, [r.getMessage() for r in storms]
    payload = json.loads(storms[0].getMessage().split(" ", 1)[1])
    assert payload["callable"] == "optimizer_update_step"
    assert payload["compiles"] == 4               # fired when limit crossed
    report = tel.retrace_report()["optimizer_update_step"]
    assert report["count"] == 6
    assert report["storm"] is True
    assert report["total_ms"] > 0
    assert tel.counter("jit_compiles") >= 6
    assert tel.counter("retrace_storms") == 1


def test_stable_shapes_do_not_storm(tel, caplog):
    tel.configure(retrace_limit=3)
    opt = mx.optimizer.create("sgd", learning_rate=0.1)
    with caplog.at_level(logging.WARNING, logger="mxnet_tpu.telemetry"):
        for _ in range(8):                        # same shape: one compile
            w = nd.array(np.zeros(4, np.float32))
            g = nd.array(np.ones(4, np.float32))
            opt.update(0, w, g, opt.create_state(0, w))
    assert not [r for r in caplog.records
                if "retrace-storm" in r.getMessage()]
    assert tel.retrace_report()["optimizer_update_step"]["count"] == 1


def test_watch_jit_off_path_is_passthrough(monkeypatch):
    """Telemetry off: the wrapper neither times, captures nor checks, and
    cache introspection still proxies to the jitted callable.  The
    compile itself is in the ledger all the same, under the watch's name:
    JAX's event put it there, not the wrapper."""
    import jax
    from mxnet_tpu.telemetry import core

    def acted(*args, **kwargs):
        raise AssertionError("the wrapper acted on a call, telemetry off")

    telemetry.reset()
    telemetry.set_enabled(False)
    monkeypatch.setattr(core, "_capture_cost", acted)
    monkeypatch.setattr(core, "_run_tracecheck", acted)
    fn = telemetry.watch_jit(jax.jit(lambda x: x + 1), "passthrough_test")
    np.testing.assert_allclose(np.asarray(fn(np.ones(3))), 2 * np.ones(3))
    assert fn._cache_size() == 1                  # proxied attribute
    assert telemetry.retrace_report()["passthrough_test"]["count"] == 1
    assert telemetry.counter("jit_compiles") == 1
    assert telemetry.program_costs() == {}
    assert telemetry.histogram("jit_compile_us").count == 0


# ---- metrics registry ----------------------------------------------------

def test_typed_metrics_and_exposition(tel):
    tel.bump("xla_program_calls", 3)
    tel.set_gauge("io_batch_wait_us", 123.5)
    for v in (10, 60, 60, 5000):
        tel.observe("eager_dispatch_us", v)

    h = tel.histogram("eager_dispatch_us")
    assert h.count == 4 and h.total == 5130
    assert h.percentile(50) >= 60

    text = tel.prometheus_text()
    assert "# TYPE xla_program_calls counter" in text
    assert "xla_program_calls 3" in text
    assert "# TYPE io_batch_wait_us gauge" in text
    assert "# TYPE eager_dispatch_us histogram" in text
    assert 'eager_dispatch_us_bucket{le="+Inf"} 4' in text
    assert "eager_dispatch_us_count 4" in text

    snap = tel.snapshot()
    assert snap["counters"]["xla_program_calls"] == 3
    assert snap["gauges"]["io_batch_wait_us"] == 123.5
    assert snap["histograms"]["eager_dispatch_us"]["count"] == 4
    json.dumps(snap)                              # fully serialisable

    c = tel.Counter("xla_program_calls")
    c.inc(2)
    assert c.value == 5
    g = tel.Gauge("io_batch_wait_us")
    g.set(7)
    assert g.value == 7.0


def test_eager_dispatch_histogram(tel):
    a = nd.array(np.random.randn(4, 4).astype(np.float32))
    before = tel.counter("eager_invocations")
    nd.dot(a, a).wait_to_read()
    assert tel.counter("eager_invocations") > before
    assert tel.histogram("eager_dispatch_us").count > 0


def test_io_batch_wait_gauge(tel):
    from mxnet_tpu import io
    data = np.random.randn(32, 4).astype(np.float32)
    it = io.NDArrayIter(data, np.zeros(32, np.float32), batch_size=8)
    n = sum(1 for _ in it)
    assert n == 4
    assert tel.counter("io_batches") == 4
    assert tel.gauge("io_batch_wait_us") > 0


def test_prefetch_counts_consumer_batches_only(tel):
    """Producer-thread fetches are excluded: a healthy prefetched pipeline
    must not double-count batches or book the producer's full fetch time
    as consumer wait (which would fake a DATA-STARVED verdict)."""
    from mxnet_tpu import io
    data = np.random.randn(32, 4).astype(np.float32)
    inner = io.NDArrayIter(data, np.zeros(32, np.float32), batch_size=8)
    pf = io.PrefetchingIter(inner)
    n = sum(1 for _ in pf)
    assert n == 4
    assert tel.counter("io_batches") == 4


def test_nested_iterators_count_each_batch_once(tel):
    """Same-thread composition (ResizeIter over NDArrayIter) must book
    one io_batches per logical batch, not one per nesting level."""
    from mxnet_tpu import io
    data = np.random.randn(32, 4).astype(np.float32)
    inner = io.NDArrayIter(data, np.zeros(32, np.float32), batch_size=8)
    rit = io.ResizeIter(inner, 6)        # rewinds the inner on exhaustion
    n = sum(1 for _ in rit)
    assert n == 6
    assert tel.counter("io_batches") == 6


def test_kvstore_bucket_bytes_accounting(tel, tmp_path):
    rng = np.random.RandomState(0)
    kv = mx.kv.create("device")
    keys = list(range(6))
    for k in keys:
        kv.init(k, nd.zeros((8, 8)))
    vals = [[nd.array(rng.randn(8, 8).astype(np.float32))
             for _ in range(2)] for _ in keys]
    kv.push_pull_all(keys, vals)

    per_key = 8 * 8 * 4
    assert tel.counter("kvstore_reduce_bytes") == per_key * len(keys)
    assert tel.histogram("bucket_bytes").count == 1    # one flat bucket

    trace = json.load(open(tel.dump_chrome_trace(
        str(tmp_path / "kv.json"))))
    buckets = [e for e in trace["traceEvents"]
               if e["name"] == "kvstore_bucket_reduce"
               and e.get("ph") == "X"]
    assert len(buckets) == 1
    assert buckets[0]["args"]["bytes"] == per_key * len(keys)
    assert buckets[0]["args"]["copies"] == 2


def test_event_ring_buffer_is_bounded(tel, tmp_path):
    """Always-on telemetry must not grow host RSS without bound: the
    trace buffer is a ring — newest spans win, evictions are counted."""
    tel.configure(max_events=16)
    try:
        for i in range(40):
            tel.add_event("ev%d" % i, "user", float(i), 1.0)
        assert tel.counter("trace_events_dropped") == 40 - 16
        snap_names = [e["name"] for e in
                      json.load(open(tel.dump_chrome_trace(
                          str(tmp_path / "ring.json"))))["traceEvents"]
                      if e["ph"] == "X"]
        assert len(snap_names) == 16
        assert snap_names[-1] == "ev39" and "ev0" not in snap_names
    finally:
        tel.configure(max_events=200_000)


def test_off_path_records_nothing():
    """MXNET_TELEMETRY unset: spans are inert, histograms empty — but the
    always-on counters (the perf-contract currency) still count."""
    telemetry.reset()
    telemetry.set_enabled(False)
    assert not telemetry.trace_active()
    with telemetry.span("should_not_record", cat="step",
                        hist="step_time_us"):
        pass
    assert telemetry.histogram("step_time_us").count == 0
    before = telemetry.counter("xla_program_calls")
    telemetry.bump("xla_program_calls")
    assert telemetry.counter("xla_program_calls") == before + 1
    snap = telemetry.snapshot()
    assert snap["enabled"] is False


# ---- satellite: every metric name used in mxnet_tpu/ is declared ---------

_METRIC_USE = re.compile(
    r'(?:\bbump|\bcounter|\bobserve|\bset_gauge|\bgauge|\bhistogram)'
    r'\(\s*["\']([A-Za-z0-9_]+)["\']'
    r'|hist=["\']([A-Za-z0-9_]+)["\']'
    r'|\bspan\(\s*["\']([A-Za-z0-9_]+)["\']'
    r'|\badd_event\(\s*["\']([A-Za-z0-9_]+)["\']')


def test_all_metric_names_declared():
    """Static check: a typo'd counter OR span name silently splits a
    time series / trace_report table — every literal used inside
    mxnet_tpu/ (bump/observe/set_gauge/histogram, ``span("...")``,
    ``add_event("...")``) must be declared in telemetry.METRIC_NAMES
    (which folds in core.SPANS; tools/tests may use ad-hoc names).
    Dynamic names — e.g. the executor's per-program span labels and the
    ``ps_send:<op>`` rpc events — go through watch_jit names or carry a
    declared prefix and are outside the literal scan by construction."""
    used = {}
    pkg = os.path.join(REPO, "mxnet_tpu")
    for dirpath, _, files in os.walk(pkg):
        for fname in files:
            if not fname.endswith(".py"):
                continue
            path = os.path.join(dirpath, fname)
            with open(path) as f:
                src = f.read()
            for m in _METRIC_USE.finditer(src):
                name = next(g for g in m.groups() if g)
                used.setdefault(name, []).append(
                    os.path.relpath(path, REPO))
    assert used, "scan found no metric uses — regex rotted?"
    undeclared = {n: ps for n, ps in used.items()
                  if n not in telemetry.METRIC_NAMES}
    assert not undeclared, (
        "span/metric names used but not declared in telemetry.core: %r"
        % undeclared)
    # the new-code gate is live: the serving/device names are declared
    for name in ("serving_run_batch", "device_time_us", "overlap_ratio"):
        assert name in telemetry.METRIC_NAMES


# ---- counters contract stays intact with telemetry ON --------------------

def test_fused_step_program_calls_unchanged_under_telemetry(tel):
    """Turning telemetry on must observe, not perturb: the fused step
    still issues <= 4 XLA programs (the PR-1 contract)."""
    from mxnet_tpu import profiler
    np.random.seed(1)
    loss_fn = gluon.loss.L2Loss()
    net = nn.Sequential()
    net.add(nn.Dense(8, activation="relu"))
    net.add(nn.Dense(3))
    net.initialize(init=mx.initializer.Xavier())
    tr2 = gluon.Trainer(net.collect_params(), "sgd",
                        {"learning_rate": 0.1}, kvstore="device")
    for _ in range(2):
        xx = mx.nd.array(np.random.randn(8, 6).astype(np.float32))
        yy = mx.nd.array(np.random.randn(8, 3).astype(np.float32))
        with autograd.record():
            ll = loss_fn(net(xx), yy)
        ll.backward()
        before = profiler.counter("xla_program_calls")
        tr2.step(8)
        delta = profiler.counter("xla_program_calls") - before
    assert delta <= 4, "telemetry perturbed the program-call contract"
