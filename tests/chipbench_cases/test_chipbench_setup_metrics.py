"""The five ``.setup`` readers (``moves: setup_s``, ISSUE 35) on a
hand-written compile ledger and span ring: the sums, the cut at the first
``fit_batch``, the trainer told from a check's module, None without a
ledger and None off the chip — and the form of their ``BENCHMARK.json``
entries.  No number here is a measurement."""
import importlib.util
import json
import os
import time

import pytest

from chipbench import harness, peaks, setup_ledger
from chipbench import run as bench_run

import test_chipbench_rehearsal as rehearsal

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
with open(os.path.join(REPO, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
READERS = ["compile_s.setup", "cache_miss_programs.setup",
           "trace_lower_s.setup", "trainer_setup_s.setup",
           "init_params_s.setup"]
CELLS = ["resnet50.fit", "mobilenet_v1.fit", "resnet50.fit_dp4",
         "brumby14b.fit", "trinity_mini.fit", "lfm2_8b_a1b.fit",
         "granite_4_0_h_micro.fit"]
S = 1e6                                   # the ring's clock is microseconds


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"),
        os.path.join(REPO, "chipbench", "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def row(fun_name, ts, trace_s, lower_s, backend_s, cache, watch=None,
        span=None):
    return {"fun_name": fun_name, "watch": watch, "span": span,
            "trace_s": trace_s, "lower_s": lower_s, "backend_s": backend_s,
            "cache": cache, "saved_s": 9.0 if cache == "hit" else 0.0,
            "ts": ts * S}


def span(name, ts, dur, module=None, parent=None, cat="setup", batch=None):
    args = {"parent": parent, "depth": 0 if parent is None else 1}
    if module is not None:
        args["module"] = module
    if batch is not None:
        args["trace_id"] = batch
    return {"name": name, "cat": cat, "ph": "X", "ts": ts * S,
            "dur": dur * S, "pid": 1, "tid": 1, "args": args}


def hand_written():
    """A start of 100 s: the trainer (module 7) binds, fills and places,
    a check's module (8) binds and fills too, the reference compiles
    outside every span, the first step compiles the step program; the
    traced window's first batch starts at 100 s, and what follows it (a
    late compile, a re-bind) is not the start's."""
    rows = [
        row("jit(convert_element_type)", 1.0, 0.01, 0.02, 0.5, "hit",
            span="module_bind"),
        row("jit(reference)", 30.0, 2.0, 3.0, 40.0, "miss"),
        row("jit(_fwd_bwd)", 75.0, 0.5, 0.5, 4.0, "off",
            watch="executor_fwd_bwd"),
        row("jit(step)", 82.0, 1.0, 2.0, 8.0, "hit",
            watch="module_cached_step", span="module_step_enqueue"),
        row("jit(late)", 120.0, 5.0, 5.0, 5.0, "miss")]
    events = [
        span("module_bind", 0.5, 4.0, module=7),
        span("init_params_host", 5.0, 20.0, parent="module_init_params"),
        span("init_params_place", 25.0, 2.0, parent="module_init_params"),
        span("module_init_params", 5.0, 22.5, module=7),
        span("module_bind", 70.0, 1.0, module=8),
        span("module_init_params", 71.0, 3.0, module=8),
        span("module_init_optimizer", 78.0, 0.25, module=7),
        span("module_step_build", 79.0, 0.75, module=7),
        # a first step that another root span of the trainer overlaps by
        # a second: the union counts the second once
        span("module_first_step", 80.0, 12.0, module=7),
        span("module_init_optimizer", 91.0, 2.0, module=7),
        span("fit_batch", 110.0, 1.0, cat="batch", batch="b"),
        span("module_train_step", 100.2, 0.5, cat="step", batch="a",
             parent="fit_batch"),
        span("fit_batch", 100.0, 1.0, cat="batch", batch="a"),
        span("module_bind", 130.0, 50.0, module=7)]
    return rows, events


# seconds by hand: backend 0.5 + 40 + 4 + 8; trace + lower 0.03 + 5 + 1 + 3;
# misses: every row but the two hits; the trainer's roots 4 + 22.5 + 0.25
# + 0.75 + union(80-92, 91-93) = 13
WANT = {"compile_s.setup": 52.5, "cache_miss_programs.setup": 2,
        "trace_lower_s.setup": 9.03, "trainer_setup_s.setup": 40.5,
        "init_params_s.setup": 22.5}


@pytest.fixture
def program(monkeypatch):
    """Put a ledger and a ring in the place of the live program's."""
    def put(rows, events):
        monkeypatch.setattr(setup_ledger, "ledger", lambda: (rows, events))
        monkeypatch.setattr(setup_ledger, "_said", False)
    return put


def ctx(device_kind="TPU v5 lite"):
    return {"device_kind": device_kind, "peaks": peaks}


@pytest.mark.parametrize("name", READERS)
def test_reader_on_a_hand_written_start(name, program, capsys):
    program(*hand_written())
    assert reader(name).read(ctx()) == pytest.approx(WANT[name])
    # the first reader of a run prints the table behind the numbers, once
    assert reader(name).read(ctx()) == pytest.approx(WANT[name])
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("chipbench: setup_ledger ")]
    assert len(lines) == 1
    said = json.loads(lines[0].split(" ", 2)[2])
    assert said["rows"] == 4
    assert said["cache"] == {"hit": 2, "miss": 1, "off": 1}
    assert said["backend_s"] == {"hit": 8.5, "miss": 40.0, "off": 4.0}
    assert said["saved_s"] == 18.0
    assert said["seconds_by_span"] == {
        "(none)": [2, 50.0], "module_bind": [1, 0.53],
        "module_step_enqueue": [1, 11.0]}
    assert said["trainer_setup_s"] == 40.5
    assert said["top_backend"][0][:4] == ["jit(reference)", None, None,
                                          "miss"]
    assert said["top_trace_lower"][1][:2] == ["jit(step)",
                                              "module_cached_step"]


def test_without_a_window_everything_is_the_starts(program):
    rows, events = hand_written()
    program(rows, [e for e in events if e["name"] != "fit_batch"])
    assert reader("compile_s.setup").read(ctx()) == pytest.approx(57.5)
    assert reader("cache_miss_programs.setup").read(ctx()) == 3
    assert reader("trainer_setup_s.setup").read(ctx()) \
        == pytest.approx(90.5)


@pytest.mark.parametrize("rows", [
    [],                                                 # telemetry's default
    [{"name": "module_cached_step", "wall_us": 4e6,     # MXNET_TELEMETRY=1
      "cache_size": 1, "ts": 5.0}]],
    ids=["empty", "the_parents_rows"])
def test_none_where_the_program_keeps_no_ledger(rows, program, capsys):
    """The parent of the PR that added the ledger: an empty log, or with
    telemetry on rows of its own form.  Its ring holds no set-up span."""
    program(rows, [e for e in hand_written()[1] if e["cat"] != "setup"])
    assert [reader(name).read(ctx()) for name in READERS] == [None] * 5
    assert "setup_ledger" not in capsys.readouterr().out


def test_none_off_the_chip(program, capsys):
    """A rehearsal on the CPU has compile seconds and spans of its own;
    they are a toy's, and no reader reports them."""
    program(*hand_written())
    assert [reader(name).read(ctx("cpu")) for name in READERS] == [None] * 5
    assert "setup_ledger" not in capsys.readouterr().out


def test_readers_read_the_live_program():
    """Against the real telemetry module: a compile under a set-up span
    is in what ``ledger()`` returns, in the shape the readers take."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import telemetry
    telemetry.reset()
    with telemetry.span("module_first_step", cat="setup",
                        args={"module": 1}):
        jax.jit(lambda x: x * 19 + 2)(jnp.ones(3)).block_until_ready()
    rows, spans = setup_ledger.before_window(*setup_ledger.ledger())
    telemetry.reset()
    assert [r["span"] for r in rows if r["fun_name"] == "jit(<lambda>)"] \
        == ["module_first_step"]
    assert [e["name"] for e in setup_ledger.trainer_spans(spans)] \
        == ["module_first_step"]
    assert setup_ledger.compile_s(rows) > 0


@pytest.mark.parametrize("chips", [1, 4])
def test_traced_rehearsal_holds_the_cross_checks(tmp_path, capsys,
                                                 monkeypatch, chips):
    """The ``module_fit`` runner at toy size with the five readers listed
    and the CPU passed off as a known device, so that they read: the
    ledger's rows before the window are the harness's own count of
    compiles, and the seconds nest as they must.  The values are a CPU's
    and a toy's; none is asserted on."""
    from mxnet_tpu import telemetry
    root = rehearsal.toy_root(tmp_path, "fit", chips)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["per_layer"] += [{"name": n, "unit": "x"} for n in READERS]
    monkeypatch.setitem(peaks.PEAKS, "cpu", {})
    monkeypatch.setattr(setup_ledger, "_said", False)
    telemetry.reset()
    env = harness.Env(root, "toy.cell", seed=2 ** 31 + 7, seconds=0.5,
                      traced=1, t_process=time.perf_counter(), bench=bench)
    result = bench_run.execute(env)
    telemetry.reset()
    out = capsys.readouterr().out
    assert result["correct"], out

    def said(key):
        return [json.loads(ln.split(" ", 2)[2]) for ln in out.splitlines()
                if ln.startswith("chipbench: %s " % key)][-1]

    got = {n: result["metrics"][n]["value"] for n in READERS}
    setup, table = said("setup"), said("setup_ledger")
    phases = dict(setup["phases"])
    assert table["rows"] == setup["compiles_before_window"] > 0
    assert setup["compiles_in_window"] == 0
    assert got["cache_miss_programs.setup"] <= table["rows"]
    assert got["compile_s.setup"] + got["trace_lower_s.setup"] \
        <= setup["setup_s"]
    assert 0 < got["init_params_s.setup"] < got["trainer_setup_s.setup"] \
        <= phases["build_bind_init"] + phases["first_step"] \
        + phases["warmup"]
    # the check's module bound and filled too: its spans are in the ring
    # and not in the trainer's seconds
    modules = {m for name, m, _ in table["spans_s"] if name == "module_bind"}
    assert len(modules) == 2
    firsts = [m for name, m, _ in table["spans_s"]
              if name == "module_first_step"]
    assert len(firsts) == 1 and firsts[0] in modules


def setup_entries_keep_their_form(bench):
    """The five entries, found by what they move and not by position:
    a later PR appends to ``per_layer`` after them and appends its cells
    to their ``workloads``."""
    mine = [m for m in bench["per_layer"] if m["moves"] == "setup_s"]
    assert [m["name"] for m in mine] == READERS        # in order
    for m in mine:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["better"] == "lower" and set(m["workloads"]) >= set(CELLS)
        assert os.path.exists(os.path.join(
            REPO, "chipbench", "layer_metrics", m["name"] + ".py"))
    assert [(m["unit"], m["source"], m["layer"]) for m in mine] == [
        ("s", "program_counter", "XLA compile and persistent cache"),
        ("programs", "program_counter", "XLA compile and persistent cache"),
        ("s", "program_counter", "Module trainer"),
        ("s", "program_span", "Module trainer"),
        ("s", "program_span", "Module trainer")]
    # every cell reports setup_s, and every other per-layer entry moves
    # another end-to-end metric (train_items_s_per_chip, or what a later
    # cell's runner reports)
    others = {m["name"] for m in bench["end_to_end"]} - {"setup_s"}
    assert all(m["moves"] in others
               for m in bench["per_layer"] if m not in mine)


def test_benchmark_json_gained_five_setup_metrics():
    setup_entries_keep_their_form(BENCH)
