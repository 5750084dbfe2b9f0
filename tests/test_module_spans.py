"""The Module trainer's host phases as spans on the device's clock (ISSUE
25): ``telemetry.span`` records into its ring AND into an open JAX profiler
session's trace, a session alone turns spans on and nothing else, and the
fit loop, the fused step and the metric carry one span per host phase.

No duration is asserted anywhere: a CPU time says nothing about the chip.
"""
import collections
import glob
import os
import re

import jax
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import profiler, telemetry
from mxnet_tpu.telemetry import core, flight, timeseries

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STEP_CHILDREN = ["module_step_feed", "module_step_place_batch",
                 "module_step_hyper", "module_step_place_params",
                 "module_step_rng", "module_step_enqueue",
                 "module_step_writeback"]
FIT_SPANS = ["fit_batch", "data_batch", "module_train_step",
             "fit_update_metric", "metric_wait", "metric_fetch",
             "fit_callback"] + STEP_CHILDREN
PARENT = dict({c: "module_train_step" for c in STEP_CHILDREN},
              module_train_step="fit_batch", fit_update_metric="fit_batch",
              fit_callback="fit_batch", metric_wait="fit_update_metric",
              metric_fetch="fit_update_metric")
BATCHES = 4
# what a start leaves whatever the switches say (ISSUE 35;
# tests/test_setup_trace.py holds their nesting): the set-up spans, and
# while something records the ring's copy of each compile ledger row
START_CATS = ("setup", "compile")
SETUP_SPANS = ["module_bind", "module_init_params", "init_params_host",
               "init_params_place", "module_init_optimizer",
               "module_step_build", "module_first_step"]


def of_the_start(annotation):
    name = annotation[len("mxnet_tpu."):]
    return name in SETUP_SPANS or name.startswith("compile:")


@pytest.fixture(autouse=True)
def clean_telemetry():
    """Telemetry at its default (off) and empty, before and after: no
    process-global state leaves this file."""
    telemetry.set_enabled(False)
    telemetry.reset()
    yield
    assert not core._PROF_RUNNING
    telemetry.set_enabled(False)
    telemetry.reset()
    assert not telemetry.trace_active()


def toy_fit(ctx=None, batches=BATCHES, batch=8):
    rs = np.random.RandomState(0)
    x = rs.randn(batches * batch, 10).astype(np.float32)
    y = rs.randint(0, 4, batches * batch).astype(np.float32)
    net = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(net, num_hidden=16, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    mod = mx.mod.Module(net, context=ctx or mx.cpu(0))
    mod.fit(mx.io.NDArrayIter(x, y, batch_size=batch), num_epoch=1,
            optimizer="sgd", eval_metric="acc",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
            batch_end_callback=lambda param: None)
    return mod


class Session:
    """A JAX profiler session opened by somebody else: ``start_trace`` to
    a temp dir, nothing of ours switched on."""

    def __init__(self, tmp_path):
        self.dir = str(tmp_path / "trace")

    def __enter__(self):
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=options)
        return self

    def __exit__(self, *exc):
        jax.profiler.stop_trace()

    def host_events(self, start=False):
        """[(line name, event name, start_ns, end_ns, stats)] of every
        per-batch ``mxnet_tpu.*`` annotation in the session's
        ``.xplane.pb``; with *start* the start's own instead."""
        from jax.profiler import ProfileData
        found = glob.glob(os.path.join(self.dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        assert len(found) == 1, found
        out = []
        for plane in ProfileData.from_file(found[0]).planes:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("mxnet_tpu.") \
                            and of_the_start(ev.name) == start:
                        out.append((line.name, ev.name[len("mxnet_tpu."):],
                                    ev.start_ns, ev.start_ns + ev.duration_ns,
                                    dict(ev.stats)))
        return out


def ring(start=False):
    """The ring's per-batch spans; with *start* the start's own events
    (categories ``setup`` and ``compile``) instead."""
    return [e for e in telemetry.chrome_trace_payload()["traceEvents"]
            if e["ph"] == "X" and (e["cat"] in START_CATS) == start]


def inside(child, parents):
    """*child* = (start, end) lies within one of *parents*."""
    return any(ps <= child[0] and child[1] <= pe for ps, pe in parents)


# ---- (a) a session alone: ring and .xplane.pb both hold every span -------

def test_fit_under_a_profiler_session_records_every_span(tmp_path):
    assert not telemetry.enabled() and not profiler.is_running()
    with Session(tmp_path) as session:
        toy_fit()
    events = ring()
    names = collections.Counter(e["name"] for e in events)
    assert set(names) == set(FIT_SPANS)
    # the fused Module's fit is one step ahead of its metric: a root per
    # step and one more for the drain of the last batch's metric
    assert names["module_train_step"] == BATCHES
    assert names["fit_batch"] == BATCHES + 1
    assert names["fit_update_metric"] == names["fit_callback"] == BATCHES
    assert names["metric_wait"] == names["metric_fetch"] == 2 * BATCHES

    # one id per root, shared by everything under it; data_batch runs
    # between two roots and carries neither's id
    roots = sorted((e for e in events if e["name"] == "fit_batch"),
                   key=lambda e: e["ts"])
    ids = [e["args"]["trace_id"] for e in roots]
    assert len(set(ids)) == BATCHES + 1
    # nbatch names the batch whose step the root enqueued; the drain names
    # the batch it settles
    assert [e["args"]["nbatch"] for e in roots] \
        == list(range(BATCHES)) + [BATCHES - 1]
    assert [e["args"].get("drain", False) for e in roots] \
        == [False] * BATCHES + [True]
    for e in events:
        if e["name"] == "data_batch":
            assert "trace_id" not in e["args"] and e["args"]["depth"] == 0
        else:
            assert e["args"]["trace_id"] in ids, e
    by_batch = collections.defaultdict(lambda: collections.defaultdict(list))
    for e in events:
        if e["name"] != "data_batch":
            by_batch[e["args"]["trace_id"]][e["name"]].append(
                (e["ts"], e["ts"] + e["dur"]))
    for i, batch_id in enumerate(ids):
        spans = by_batch[batch_id]
        # at most one step, one metric update and one callback a root, in
        # that order: the first root has a step only, the drain no step
        assert len(spans["module_train_step"]) == (i < BATCHES)
        assert len(spans["fit_update_metric"]) == (i > 0)
        assert len(spans["fit_callback"]) == (i > 0)
        if 0 < i < BATCHES:
            assert spans["module_train_step"][0][1] \
                <= spans["fit_update_metric"][0][0]
        if i > 0:
            assert spans["fit_update_metric"][0][1] \
                <= spans["fit_callback"][0][0]
        # the seven children, by name, in every module_train_step, and
        # each child inside its parent in time
        if i < BATCHES:
            assert set(STEP_CHILDREN) <= set(spans)
        for child, parent in PARENT.items():
            for iv in spans[child]:
                assert inside(iv, spans[parent]), (child, parent)
    for e in events:
        first = e["name"] == "module_train_step" \
            and e["args"]["trace_id"] == ids[0]
        # the fit's first step runs under the set-up span that times it
        assert e["args"]["parent"] == ("module_first_step" if first
                                       else PARENT.get(e["name"])), e
    start = {e["name"]: e["args"]["parent"] for e in ring(start=True)
             if e["cat"] == "setup"}
    assert start["module_first_step"] == "fit_batch"

    # the same spans in the session's own trace, prefixed, nested in time
    # on one host line, each carrying its batch's id
    host = session.host_events()
    assert collections.Counter(name for _, name, _, _, _ in host) == names
    assert len({line for line, _, _, _, _ in host}) == 1
    by_name = collections.defaultdict(list)
    for _, name, start, end, stats in host:
        by_name[name].append((start, end))
        if name != "data_batch":
            assert stats["trace_id"] in ids
    for child, parent in PARENT.items():
        for iv in by_name[child]:
            assert inside(iv, by_name[parent]), (child, parent)


# ---- (b) no session, telemetry off: nothing recorded, nothing built ------

def test_fit_without_a_session_records_and_constructs_nothing(monkeypatch):
    core._poll_session()                    # bind the lazy jax hooks first
    built = []

    class Counting(core._annotation_cls):
        def __init__(self, *args, **kwargs):
            built.append(args)
            super().__init__(*args, **kwargs)

    monkeypatch.setattr(core, "_annotation_cls", Counting)
    toy_fit()
    # only the start's own spans were recorded and annotated: one of each,
    # once a bind, and nothing for any of the batches
    assert ring() == []
    assert sorted(e["name"] for e in ring(start=True)) == sorted(SETUP_SPANS)
    assert sorted(args[0] for args in built) \
        == sorted("mxnet_tpu." + name for name in SETUP_SPANS)
    del built[:]
    assert not core._SESSION and not telemetry.trace_active()
    # the counting stub does count when something records
    telemetry.set_enabled(True)
    with telemetry.span("fit_callback", cat="host"):
        pass
    assert built == [("mxnet_tpu.fit_callback",)]


# ---- (c) a session turns on spans and nothing else -----------------------

def test_a_session_alone_turns_on_nothing_but_spans(tmp_path, monkeypatch):
    def no_cost(*args, **kwargs):
        raise AssertionError("_capture_cost ran under a session alone")

    monkeypatch.setattr(core, "_capture_cost", no_cost)
    steps_before = flight.step_count()
    with Session(tmp_path):
        toy_fit()               # a fresh Module: its step program compiles
    assert len(ring()) > 0
    # the compile ledger is on whatever the switches say: the step
    # program's row is there, under its watch and its set-up span
    assert [(r["watch"], r["span"]) for r in telemetry.compile_events()
            if r["watch"]] == [("module_cached_step", "module_step_enqueue")]
    assert telemetry.counter("jit_compiles") == 1
    assert telemetry.histogram("jit_compile_us").count == 0
    assert timeseries.names() == []
    assert telemetry.histogram("step_time_us").count == 0
    assert telemetry.gauge("host_rss_peak_bytes", None) is None
    assert telemetry.program_costs() == {}
    # the flight ring got what it gets with telemetry off: the progress
    # ticks of step/program spans, without durations
    spans = [e for e in flight.events() if e["kind"] == "span"]
    assert {e["name"] for e in spans} == {"module_train_step",
                                         "module_step_enqueue"}
    assert all("dur_us" not in e for e in spans)
    assert flight.step_count() - steps_before == BATCHES


# ---- (d) the fused SPMD group places its batch under the same span -------

def test_fused_four_device_group_emits_place_batch(tmp_path):
    from mxnet_tpu.module.fused_group import FusedExecutorGroup
    with Session(tmp_path) as session:
        mod = toy_fit(ctx=[mx.cpu(i) for i in range(4)])
    assert isinstance(mod._exec_group, FusedExecutorGroup)
    assert mod._cached_step is not None
    names = collections.Counter(e["name"] for e in ring())
    assert names["module_step_place_batch"] == BATCHES
    assert names["module_step_enqueue"] == BATCHES
    assert sum(name == "module_step_place_batch"
               for _, name, _, _, _ in session.host_events()) == BATCHES


# ---- (e) recording splits asnumpy; the metric's value does not move ------

@pytest.mark.parametrize("metric", ["acc", "ce", "mse", "top_k_accuracy"])
def test_metric_is_identical_with_and_without_recording(metric):
    rs = np.random.RandomState(3)
    probs = rs.dirichlet(np.ones(5), size=16).astype(np.float32)
    labels = rs.randint(0, 5, 16).astype(np.float32)
    if metric == "mse":
        probs, labels = probs[:, :1], labels.reshape(-1, 1)
    kwargs = {"top_k": 2} if metric == "top_k_accuracy" else {}
    got = []
    for recording in (False, True):
        telemetry.set_enabled(recording)
        m = mx.metric.create(metric, **kwargs)
        m.update([mx.nd.array(labels)], [mx.nd.array(probs)])
        got.append(m.get())
    telemetry.set_enabled(False)
    assert got[0] == got[1]
    names = collections.Counter(e["name"] for e in ring())
    assert names["metric_wait"] == names["metric_fetch"] == 2


# ---- the switch itself ---------------------------------------------------

def test_trace_active_follows_the_session_and_forgets_it(tmp_path):
    assert not telemetry.trace_active()
    with Session(tmp_path):
        # nobody has asked yet: children read a cached answer ...
        assert not core._SESSION and not telemetry.trace_active()
        with telemetry.span("fit_callback", cat="host"):
            pass
        assert ring() == []
        # ... that a batch root refreshes
        with telemetry.span("fit_batch", cat="batch"):
            assert core._SESSION and telemetry.trace_active()
    # the session closed after the last root asked: the cached answer is
    # stale until the next question, which corrects it
    assert core._SESSION
    assert not telemetry.trace_active() and not core._SESSION
    with telemetry.span("fit_callback", cat="host"):
        pass
    assert [e["name"] for e in ring()] == ["fit_batch"]


def test_step_span_is_its_own_root_outside_a_batch(tmp_path):
    with Session(tmp_path):
        with telemetry.span("module_train_step", cat="step"):
            lone = telemetry.trace_context()
        with telemetry.span("fit_batch", cat="batch"):
            batch = telemetry.trace_context()
            with telemetry.span("module_train_step", cat="step"):
                nested = telemetry.trace_context()
        with telemetry.span("module_train_step", cat="step"):
            after = telemetry.trace_context()
    assert lone and batch and nested == batch
    assert len({lone, batch, after}) == 3
    assert telemetry.trace_context() is None
    assert core._BATCH_OPEN == 0


def test_mx_profiler_with_a_trace_dir_shows_the_spans(tmp_path):
    """The documented recipe: set_config(jax_trace_dir=...) + run."""
    session = Session(tmp_path)
    profiler.set_config(mode="symbolic", jax_trace_dir=session.dir)
    profiler.set_state("run")
    try:
        toy_fit(batches=2)
    finally:
        profiler.set_state("stop")
        profiler.set_config()
    names = {name for _, name, _, _, _ in session.host_events()}
    assert set(STEP_CHILDREN) | {"fit_batch", "metric_wait"} <= names


# ---- what went -----------------------------------------------------------

def test_retired_names_have_no_reader_left():
    assert "module_step_program" not in telemetry.SPANS
    assert "module_step_enqueue" in telemetry.SPANS
    assert not hasattr(profiler, "record_program")
    retired = re.compile(r"module_step_program|profiler\.record_program")
    hits = []
    for top in ("mxnet_tpu", "tools", "chipbench", "docs", "examples"):
        for dirpath, _, files in os.walk(os.path.join(REPO, top)):
            for fname in files:
                if fname.endswith((".py", ".md")):
                    path = os.path.join(dirpath, fname)
                    with open(path, errors="replace") as f:
                        if retired.search(f.read()):
                            hits.append(os.path.relpath(path, REPO))
    assert hits == []
    with open(os.path.join(REPO, "mxnet_tpu", "io.py")) as f:
        assert "add_event" not in f.read()
