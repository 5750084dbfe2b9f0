"""The sequence convolution's two readers (``causal_conv_ms.fit``,
``causal_conv_roofline_pct.fit``), shared by ``lfm2_8b_a1b`` (the gated
short convolution, scope ``short_conv``) and ``granite_4_0_h_micro`` (the
biased one, scope ``causal_conv``): the work function against PERF.md's
bytes at the cells' shapes, None where there is nothing to read, each
configuration's pattern taking the convolution's events and no other, and
a traced toy run of each configuration through its runner with a device
plane put beside the CPU's trace, so that the two rows reach the result
line.  No number here is a device metric."""
import importlib
import importlib.util
import json
import os
import shutil
import time

import pytest

from chipbench import flops_conv, harness, kernel_time, peaks
from chipbench import run as bench_run
from chipbench import trace as trace_mod

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
NAMES = ("causal_conv_ms.fit", "causal_conv_roofline_pct.fit")
HBM = 819e9                                   # TPU v5 lite, peaks.py
CONV_OPS = {"causal_conv_fwd.1", "fusion.4", "causal_conv_bwd.2"}


def load(*parts):
    with open(os.path.join(REPO, *parts)) as f:
        return json.load(f)


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"), os.path.join(
            REPO, "chipbench", "layer_metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# configuration: its test module, the op's scope, conv layers held,
# PERF.md's bytes a layer at the bandwidth (forward, backward, ms), and
# the cell's other kernel readers that the events below feed
CASES = {
    "lfm2_8b_a1b": ("test_chipbench_lfm2", "short_conv", 5, 0.33, 0.57,
                    ("moe_ms.fit", "attention_ms.fit")),
    "granite_4_0_h_micro": ("test_chipbench_granite", "causal_conv", 9,
                            0.35, 0.52,
                            ("state_space_ms.fit", "attention_ms.fit"))}


def work(cfg, tokens):
    """The function the configuration names under ``causal_conv_work``."""
    module, _, fn = cfg["causal_conv_work"].partition(":")
    assert module == "chipbench.flops_conv"
    return getattr(flops_conv, fn)(cfg, tokens)


@pytest.mark.parametrize("config", sorted(CASES))
def test_work_at_the_cells_shapes(config):
    _, _, layers, forward_ms, backward_ms = CASES[config][:5]
    cfg = load("chipbench", "configs", config + ".json")
    need, nbytes = work(cfg, 16384)
    per_layer_ms = 1e3 * nbytes / HBM / layers
    assert per_layer_ms == pytest.approx(forward_ms + backward_ms, abs=0.01)
    assert nbytes / HBM > 20 * need / 197e12        # bound by HBM
    # by hand, bf16: forward the input read and the output written,
    # backward the input and dy read and dx written
    c_in, c_out = (3 * 2048, 2048) if config == "lfm2_8b_a1b" else \
        (4352, 4352)
    assert 1e3 * (c_in + c_out) * 16384 * 2 / HBM == pytest.approx(
        forward_ms, abs=0.005)
    assert 1e3 * (2 * c_in + c_out) * 16384 * 2 / HBM == pytest.approx(
        backward_ms, abs=0.005)


@pytest.mark.parametrize("config", sorted(CASES))
def test_readers(config):
    cfg = load("chipbench", "configs", config + ".json")
    ms, share = (reader(n) for n in NAMES)
    ctx = {"cfg": cfg, "trace": None, "facts": {"batch_per_chip": 16384}}
    assert ms.read(ctx) is None and share.read(ctx) is None
    # a program without the named ops: a trace, nothing to read
    ctx["trace"] = {"span_counts": {"fit_step": 3}}
    assert ms.read(ctx) is None and share.read(ctx) is None
    ctx.update(peaks=peaks, device_kind="TPU v5 lite",
               facts={"batch_per_chip": 16384,
                      "kernel_s": {"causal_conv": {"seconds": 0.06}}})
    assert ms.read(ctx) == pytest.approx(20.0)
    _, nbytes = work(cfg, 16384)
    assert share.read(ctx) == pytest.approx(100 * 3 * nbytes / HBM / 0.06)
    assert 0 < share.read(ctx) < 100
    # a configuration that names no work function: nothing to read
    ctx["cfg"] = {k: v for k, v in cfg.items() if not k.endswith("_work")}
    assert share.read(ctx) is None


def chip_like_events(scope, steps):
    """(text, start, end) as ``kernel_time.device_events`` hands them on
    the chip — the HLO line, a space, the op's scope path — a step's worth
    per (lo, hi) in *steps*: the convolution's two kernels and an XLA op
    under its scope (a transform can wrap it, as the chip shows
    ``jvp(lm_head_loss)``), one event of every other pattern the two
    configurations name, and two consumers whose HLO lines name a
    kernel's output among their operands (the out projection's product
    after the convolution, an input projection's gradient after the
    scan): they belong to no kernel."""
    out = []
    for lo, hi in steps:
        d = (hi - lo) / 10
        out += [
            ("%causal_conv_fwd.1 = bf16[] custom-call() jit(step)/layer1/"
             + scope + "/pallas_call:", lo, lo + d),
            ("%fusion.4 = bf16[] fusion() jit(step)/layer1/jvp(" + scope
             + ")/mul:", lo + d, lo + 2 * d),
            ("%causal_conv_bwd.2 = bf16[] custom-call() jit(step)/"
             "transpose(jvp(layer1))/" + scope + "/pallas_call:",
             lo + 2 * d, lo + 3 * d),
            ("%flash_attention_fwd.1 = bf16[] custom-call() jit(step)/"
             "causal_attention_fwd/pallas_call:", lo + 3 * d, lo + 4 * d),
            ("%gmm.1 = bf16[] custom-call() jit(step)/moe_experts_fwd/gmm:",
             lo + 4 * d, lo + 5 * d),
            ("%state_space_fwd.1 = bf16[] custom-call() jit(step)/"
             "state_space_fwd/pallas_call:", lo + 5 * d, lo + 6 * d),
            ("%fusion.9 = f32[] fusion() jit(step)/jvp(lm_head_loss)/"
             "while/dot:",
             lo + 6 * d, lo + 9 * d),
            ("%fusion.12 = bf16[] fusion(bf16[] %causal_conv_fwd.1) "
             "jit(step)/layer1/out_proj/dot_general:", lo + 9 * d,
             lo + 9.5 * d),
            ("%fusion.13 = bf16[] fusion(bf16[] %state_space_bwd.2) "
             "jit(step)/transpose(jvp(layer1))/in_proj/dot_general:",
             lo + 9.5 * d, hi)]
    return out


@pytest.mark.parametrize("config", sorted(CASES))
def test_each_pattern_takes_its_own_events(config, tmp_path, monkeypatch):
    """A pattern is matched on its own: the convolution's takes its three
    events a step and nothing of attention, the experts, the scan, the head
    or a consumer of its output, and theirs take nothing of it."""
    cfg = load("chipbench", "configs", config + ".json")
    scope = CASES[config][1]
    events = chip_like_events(scope, [(0., 1000.), (1000., 2000.)])
    monkeypatch.setattr(kernel_time, "device_events",
                        lambda d: ((0., 2000.), events))
    got = kernel_time.seconds_by_pattern(str(tmp_path),
                                         cfg["trace_patterns"])
    conv = got["causal_conv"]
    assert conv["events"] == 6
    assert conv["seconds"] == pytest.approx(2 * 300e-9)
    assert {op for op, _ in conv["longest_ops"]} == CONV_OPS
    for kernel, seen in got.items():          # one event a step; the head 3
        if kernel != "causal_conv":
            assert seen["events"] == 2, kernel
            assert seen["seconds"] == pytest.approx(
                2 * (300e-9 if kernel == "head" else 100e-9)), kernel


def test_anchored_form_on_a_recorded_chip_trace(tmp_path):
    """The anchored form reads a real v5e trace as the loose one does
    where no consumer of the kernel's output is in the window: the 59
    events of the retention sample (``test_chipbench_brumby.py``), the
    Pallas call by its own instruction name, the rest by the scope
    inside the path."""
    where = tmp_path / "plugins" / "profile" / "run"
    where.mkdir(parents=True)
    shutil.copy(os.path.join(REPO, "chipbench", "testdata",
                             "xplane_retention_sample.pb"),
                where / "host.xplane.pb")
    got = kernel_time.seconds_by_pattern(str(tmp_path), {
        "loose": "power_retention_(fwd|bwd)",
        "anchored": r"^%power_retention_(fwd|bwd)\b"
                    r"|[/(]power_retention_(fwd|bwd)[/:)][^ ]*$",
        "name_only": r"^%power_retention_(fwd|bwd)\b"})
    assert got["anchored"] == got["loose"]
    assert got["anchored"]["events"] == 59
    assert got["name_only"]["events"] == 1        # the forward's call


@pytest.mark.parametrize("config", sorted(CASES))
def test_traced_rehearsal_reads_the_two_rows(config, tmp_path, capsys,
                                             monkeypatch):
    """The configuration's toy cell, traced, through ``module_fit_probed``
    with its real readers and patterns.  The CPU's trace has no device
    plane, so one is put beside it: the chip's events of a step inside
    each ``chipbench.fit_step`` span of the window.  The two rows must
    come out of the result line as the events say, and the cell's other
    kernel readers must read their own events only."""
    module, scope = CASES[config][:2]
    root = importlib.import_module(module).toy_root(tmp_path)
    made = {}

    def rows_with_a_device(trace_dir):
        rows = real_rows(trace_dir)
        window = [r for r in rows if r["name"] == trace_mod.WINDOW][0]
        lo, hi = window["start_ns"], window["start_ns"] + window["dur_ns"]
        steps = [(r["start_ns"], r["start_ns"] + r["dur_ns"]) for r in rows
                 if r["name"] == trace_mod.SPAN_PREFIX + "fit_step"]
        # the reduction counts a step the window cuts; events go into
        # the steps it holds whole
        made["steps"] = len(trace_mod.clip(steps, lo, hi))
        made["window"], made["events"] = (lo, hi), chip_like_events(
            scope, [(a, b) for a, b in steps if lo <= a and b <= hi])
        return rows + [{"plane": "/device:TPU:0", "line": trace_mod.OPS_LINE,
                        "name": trace_mod.op_name(text), "start_ns": s,
                        "dur_ns": e - s} for text, s, e in made["events"]]

    real_rows = trace_mod.read_xplane
    monkeypatch.setattr(trace_mod, "read_xplane", rows_with_a_device)
    monkeypatch.setattr(kernel_time, "device_events",
                        lambda d: (made["window"], made["events"]))
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    env = harness.Env(root, "toy.cell", seed=2 ** 31 + 9, seconds=0.5,
                      traced=1, t_process=time.perf_counter())
    result = bench_run.execute(env)
    out = capsys.readouterr().out
    assert result["correct"], out
    assert made["events"], out
    conv_s = sum(e - s for text, s, e in made["events"]
                 if trace_mod.op_name(text) in CONV_OPS) / 1e9
    got = result["metrics"]
    assert got["causal_conv_ms.fit"]["value"] == pytest.approx(
        1e3 * conv_s / made["steps"])
    with open(os.path.join(root, "chipbench", "configs", "toy.json")) as f:
        cfg = json.load(f)
    tokens = importlib.import_module(module).TOY_TRAFFIC["batch_per_chip"]
    flops, nbytes = work(cfg, tokens)
    floor_s = max(flops / 197e12, nbytes / HBM)
    assert got["causal_conv_roofline_pct.fit"]["value"] == pytest.approx(
        100 * floor_s * made["steps"] / conv_s)
    # the cell's other kernels read their own event a step, a third of
    # the convolution's three: no pattern took another's events
    for name in CASES[config][5]:
        assert got[name]["value"] == pytest.approx(
            got["causal_conv_ms.fit"]["value"] / 3), name
