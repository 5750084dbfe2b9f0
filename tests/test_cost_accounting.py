"""XLA cost accounting (ISSUE 4 tentpole 3): per-program
cost_analysis() capture, the step MFU/bandwidth gauges, the peak table,
and the trace_report MFU/roofline surfaces.

Acceptance contract: a watched jitted step yields nonzero
``step_model_flops`` and an MFU in (0, 1] on CPU with an env-pinned
peak; ``tools/trace_report.py --json`` smoke via subprocess.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd, sym, telemetry
from mxnet_tpu.gluon import nn
from mxnet_tpu.telemetry import costs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def tel(monkeypatch):
    """Telemetry on, peaks pinned via env so MFU is deterministic-ish:
    1e18 FLOP/s is far above anything the CPU does, so MFU lands in
    (0, 1] regardless of machine speed."""
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    monkeypatch.setenv("MXNET_PEAK_FLOPS", "1e18")
    monkeypatch.setenv("MXNET_PEAK_HBM_BW", "1e18")
    telemetry.refresh_from_env()                # also refreshes costs
    telemetry.reset()
    yield telemetry
    telemetry.reset()
    for var in ("MXNET_TELEMETRY", "MXNET_PEAK_FLOPS",
                "MXNET_PEAK_HBM_BW"):
        monkeypatch.delenv(var, raising=False)
    telemetry.refresh_from_env()


def test_watched_step_yields_flops_and_mfu(tel):
    """The acceptance case, minimal form: one watched jitted program
    inside a step span."""
    f = telemetry.watch_jit(jax.jit(lambda x: x @ x), "cost_test_step")
    x = jnp.ones((32, 32), jnp.float32)
    with telemetry.span("cost_step", cat="step"):
        f(x).block_until_ready()

    cost = telemetry.program_cost("cost_test_step")
    assert cost is not None
    flops, nbytes = cost
    # a 32x32 matmul is 2*n^3 = 65536 model FLOPs
    assert flops >= 2 * 32 ** 3
    assert nbytes > 0

    gauges = telemetry.snapshot()["gauges"]
    assert gauges["step_model_flops"] == flops
    assert 0 < gauges["step_mfu"] <= 1.0
    assert 0 < gauges["step_hbm_bw_util"] <= 1.0


def test_cached_cost_accumulates_without_recompiles(tel):
    """Steps after the first recompile nothing; the window still fills
    from the per-name cost cache, and two programs sum."""
    f = telemetry.watch_jit(jax.jit(lambda x: x @ x), "cost_prog_a")
    g = telemetry.watch_jit(jax.jit(lambda x: x + x), "cost_prog_b")
    x = jnp.ones((16, 16), jnp.float32)
    for _ in range(3):
        with telemetry.span("cost_step", cat="step"):
            f(x).block_until_ready()
            g(x).block_until_ready()
    per_step = (telemetry.program_cost("cost_prog_a")[0]
                + telemetry.program_cost("cost_prog_b")[0])
    assert telemetry.gauge("step_model_flops") == per_step
    assert telemetry.counter("jit_compiles") == 2   # one compile each


def test_trainer_step_mfu_end_to_end(tel):
    """The real step: fused Trainer under telemetry reports MFU."""
    np.random.seed(0)
    net = nn.Sequential()
    net.add(nn.Dense(16, activation="relu"))
    net.add(nn.Dense(4))
    net.initialize(init=mx.initializer.Xavier())
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1}, kvstore="device")
    loss_fn = gluon.loss.L2Loss()
    for _ in range(2):
        x = mx.nd.array(np.random.randn(8, 6).astype(np.float32))
        y = mx.nd.array(np.random.randn(8, 4).astype(np.float32))
        with autograd.record():
            loss = loss_fn(net(x), y)
        loss.backward()
        trainer.step(8)

    snap = telemetry.snapshot()
    assert snap["gauges"]["step_model_flops"] > 0
    assert 0 < snap["gauges"]["step_mfu"] <= 1.0
    programs = snap["costs"]["programs"]
    assert "fused_trainer_step" in programs
    assert programs["fused_trainer_step"]["flops"] > 0
    peaks = snap["costs"]["peaks"]
    assert peaks["flops"] == 1e18 and peaks["source"]["flops"] == "env"


def test_donated_programs_still_capture_cost(tel):
    """The re-lower uses ShapeDtypeStruct specs, so a program that
    donated (and deleted) its inputs still gets cost-accounted."""
    f = telemetry.watch_jit(
        jax.jit(lambda x: x * 2.0, donate_argnums=(0,)),
        "cost_donated")
    x = jnp.ones((64,), jnp.float32)
    with telemetry.span("cost_step", cat="step"):
        f(x).block_until_ready()
    assert telemetry.program_cost("cost_donated") is not None


def test_peak_table_fallback_and_env_override(monkeypatch):
    monkeypatch.delenv("MXNET_PEAK_FLOPS", raising=False)
    monkeypatch.delenv("MXNET_PEAK_HBM_BW", raising=False)
    costs.refresh_from_env()
    pk = costs.peaks()
    n = len(jax.local_devices())
    assert pk["device_kind"] == "cpu" and pk["n_devices"] == n
    assert pk["flops"] == costs.PEAK_TABLE["cpu"][0] * n
    assert pk["source"]["flops"] == "table"

    monkeypatch.setenv("MXNET_PEAK_FLOPS", "2.5e14")
    costs.refresh_from_env()
    pk = costs.peaks()
    assert pk["flops"] == 2.5e14                 # aggregate, verbatim
    assert pk["source"]["flops"] == "env"
    costs.refresh_from_env()


def test_unknown_device_kind_is_an_error(monkeypatch):
    """A device_kind with no sourced row never borrows the CPU row."""
    class _Dev:
        device_kind = "TPU v99"

    for var in ("MXNET_PEAK_FLOPS", "MXNET_PEAK_HBM_BW",
                "MXNET_PEAK_ICI_BW"):
        monkeypatch.delenv(var, raising=False)
    monkeypatch.setattr(jax, "local_devices", lambda: [_Dev()])
    costs.refresh_from_env()
    try:
        with pytest.raises(mx.MXNetError, match="TPU v99"):
            costs.peaks()
        # all three pinned from outside: the operator supplied the peaks
        monkeypatch.setenv("MXNET_PEAK_FLOPS", "1e15")
        monkeypatch.setenv("MXNET_PEAK_HBM_BW", "2e12")
        monkeypatch.setenv("MXNET_PEAK_ICI_BW", "3e11")
        costs.refresh_from_env()
        assert costs.peaks()["flops"] == 1e15
    finally:
        monkeypatch.undo()
        costs.refresh_from_env()


def test_executor_cost_analysis_aot(tel):
    """Per-executor AOT cost: nothing executed, PRNG stream untouched."""
    data = sym.var("data")
    net = sym.FullyConnected(data, num_hidden=8, name="fc")
    ex = net.simple_bind(mx.cpu(), data=(4, 16))
    report = ex.cost_analysis()
    assert report["eval"]["flops"] >= 2 * 4 * 16 * 8   # the matmul
    assert report["fwd_bwd"]["flops"] > report["eval"]["flops"]
    assert report["eval"]["bytes_accessed"] > 0


def test_capture_env_kill_switch(tel, monkeypatch):
    monkeypatch.setenv("MXNET_COST_ANALYSIS", "0")
    costs.refresh_from_env()
    try:
        f = telemetry.watch_jit(jax.jit(lambda x: x @ x),
                                "cost_gated_off")
        with telemetry.span("cost_step", cat="step"):
            f(jnp.ones((8, 8), jnp.float32)).block_until_ready()
        assert telemetry.program_cost("cost_gated_off") is None
        assert telemetry.gauge("step_model_flops") == 0.0
    finally:
        monkeypatch.delenv("MXNET_COST_ANALYSIS", raising=False)
        costs.refresh_from_env()


# ---- trace_report surfaces -----------------------------------------------

def _dump_artifacts(tmp_path):
    trace = telemetry.dump_chrome_trace(str(tmp_path / "trace.json"))
    snap = telemetry.dump_snapshot(str(tmp_path / "snap.json"))
    return trace, snap


def test_trace_report_json_smoke_subprocess(tel, tmp_path):
    """Acceptance: --json machine-readable output from a live dump."""
    f = telemetry.watch_jit(jax.jit(lambda x: x @ x), "cost_test_step")
    for _ in range(2):
        with telemetry.span("cost_step", cat="step"):
            f(jnp.ones((32, 32), jnp.float32)).block_until_ready()
    trace, snap = _dump_artifacts(tmp_path)

    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "trace_report.py"),
         trace, "--snapshot", snap, "--json"],
        capture_output=True, timeout=60)
    assert proc.returncode == 0, proc.stderr.decode()
    report = json.loads(proc.stdout)
    assert report["steps"]["count"] == 2
    assert report["mfu"]["step_model_flops"] > 0
    assert 0 < report["mfu"]["step_mfu"] <= 1
    rows = {r["program"]: r for r in report["mfu"]["programs"]}
    assert rows["cost_test_step"]["flops"] > 0
    assert rows["cost_test_step"]["bound"] in ("compute", "memory")


def test_trace_report_degrades_on_empty_and_legacy_inputs(tmp_path):
    """Satellite: no traceback on empty traces or pre-cost snapshots."""
    empty = tmp_path / "empty.json"
    empty.write_text("{}")
    legacy_snap = tmp_path / "legacy.json"
    legacy_snap.write_text(json.dumps(
        {"counters": {}, "gauges": {}}))     # no retraces/costs keys

    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "trace_report.py"),
         str(empty)], capture_output=True, timeout=60)
    assert out.returncode == 0, out.stderr.decode()
    assert b"no events" in out.stdout

    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "trace_report.py"),
         str(empty), "--snapshot", str(legacy_snap), "--json"],
        capture_output=True, timeout=60)
    assert out.returncode == 0, out.stderr.decode()
    report = json.loads(out.stdout)
    assert report["steps"] is None and report["mfu"] is None


# ---- prometheus escaping (satellite) -------------------------------------

def test_prometheus_help_and_label_escaping(tel, monkeypatch):
    monkeypatch.setitem(telemetry.COUNTERS, "esc_test_total",
                        'line1\nline2 with \\backslash and "quotes"')
    telemetry.bump("esc_test_total")
    text = telemetry.prometheus_text()
    help_lines = [ln for ln in text.splitlines()
                  if ln.startswith("# HELP esc_test_total")]
    assert len(help_lines) == 1                  # newline did not split it
    assert "line1\\nline2" in help_lines[0]
    assert "\\\\backslash" in help_lines[0]
    # escape helpers honor the exposition format for label values too
    from mxnet_tpu.telemetry import core
    assert core._escape_label('a"b\\c\nd') == 'a\\"b\\\\c\\nd'
