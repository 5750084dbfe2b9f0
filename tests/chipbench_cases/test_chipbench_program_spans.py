"""The four readers of the program's own spans (``source: program_span``):
each on a hand-written event list — the median over complete batches, a
batch without its root dropped, None when the ring holds none — and in a
traced rehearsal of the ``module_fit`` runner on one and four virtual
chips.  Host spans need no device plane, so on the CPU the readers return
values; none of them is a device number and none is asserted on."""
import importlib.util
import json
import os
import time

import pytest

from chipbench import harness, program_spans
from chipbench import run as bench_run

import test_chipbench_rehearsal as rehearsal

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
READERS = ["step_prepare_ms.fit", "step_enqueue_ms.fit",
           "batch_place_ms.fit", "metric_host_ms.fit"]


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"),
        os.path.join(REPO, "chipbench", "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def ev(name, ts, dur, batch=None, parent=None):
    args = {"parent": parent, "depth": 0}
    if batch is not None:
        args["trace_id"] = batch
    return {"name": name, "cat": "host", "ph": "X", "ts": float(ts),
            "dur": float(dur), "pid": 1, "tid": 1, "args": args}


def batch_events(batch, t0, prepare, enqueue, place, update, waits,
                 root=True):
    """One batch as the program records it (children before parents, in
    the order they end), in microseconds from *t0*."""
    step = "module_train_step"
    events = [
        ev("module_step_feed", t0 + 5, 10, batch, step),
        ev("module_step_place_batch", t0 + 15, place, batch, step),
        ev("module_step_enqueue", t0 + prepare, enqueue, batch, step),
        ev("module_step_writeback", t0 + prepare + enqueue, 50, batch, step),
        ev(step, t0, prepare + enqueue + 60, batch, "fit_batch")]
    t = t0 + prepare + enqueue + 100
    for i, wait in enumerate(waits):
        events.append(ev("metric_wait", t + 10 + 500 * i, wait, batch,
                         "fit_update_metric"))
    events.append(ev("fit_update_metric", t, update, batch, "fit_batch"))
    if root:
        events.append(ev("fit_batch", t0 - 10, 10 ** 5, batch))
    return events


def hand_written():
    events = [{"ph": "M", "name": "process_name", "pid": 1, "tid": 0,
               "args": {"name": "mxnet_tpu"}},
              ev("data_batch", 0, 40)]                  # no batch id
    # three complete batches, then one the ring cut before its root ended
    events += batch_events("a", 1000, 4000, 2000, 100, 3000, [900, 100])
    events += batch_events("b", 200000, 6000, 1000, 300, 2500, [500])
    events += batch_events("c", 400000, 5000, 3000, 200, 9000, [])
    events += batch_events("d", 600000, 90000, 90000, 90000, 90000, [1],
                           root=False)
    return [e for e in events if e["ph"] == "X"]


WANT = {"step_prepare_ms.fit": 5.0,         # median(4000, 6000, 5000) us
        "step_enqueue_ms.fit": 2.0,         # median(2000, 1000, 3000)
        "batch_place_ms.fit": 0.2,          # median(100, 300, 200)
        "metric_host_ms.fit": 2.0}          # median(3000-1000, 2500-500, 9000)


@pytest.mark.parametrize("name", READERS)
def test_reader_on_hand_written_events(name, monkeypatch):
    events = hand_written()
    monkeypatch.setattr(program_spans, "ring", lambda: events)
    assert reader(name).read({}) == pytest.approx(WANT[name])
    # an even count: the mean of the two middle batches, still without "d"
    monkeypatch.setattr(
        program_spans, "ring",
        lambda: [e for e in events if e["args"].get("trace_id") != "c"])
    two = {"step_prepare_ms.fit": 5.0, "step_enqueue_ms.fit": 1.5,
           "batch_place_ms.fit": 0.2, "metric_host_ms.fit": 2.0}
    assert reader(name).read({}) == pytest.approx(two[name])


@pytest.mark.parametrize("name", READERS)
def test_reader_returns_none_when_nothing_to_read(name, monkeypatch):
    # the parent's ring (telemetry off, no span of ours): empty
    monkeypatch.setattr(program_spans, "ring", lambda: [])
    assert reader(name).read({}) is None
    # batches without a root, and roots without the spans the reader needs
    cut = [e for e in hand_written() if e["name"] != "fit_batch"]
    monkeypatch.setattr(program_spans, "ring", lambda: cut)
    assert reader(name).read({}) is None
    bare = [ev("fit_batch", 0, 10, "a"), ev("fit_callback", 5, 1, "a")]
    monkeypatch.setattr(program_spans, "ring", lambda: bare)
    assert reader(name).read({}) is None


def test_ring_is_the_programs_own_and_empty_at_its_defaults():
    from mxnet_tpu import telemetry
    telemetry.reset()
    assert program_spans.ring() == []
    telemetry.set_enabled(True)
    try:
        with telemetry.span("fit_batch", cat="batch"):
            with telemetry.span("fit_callback", cat="host"):
                pass
        got = program_spans.batches(program_spans.ring())
    finally:
        telemetry.set_enabled(False)
        telemetry.reset()
    assert len(got) == 1 and set(got[0]) == {"fit_batch", "fit_callback"}


@pytest.mark.parametrize("chips", [1, 4])
def test_module_fit_rehearsal_prints_the_span_metrics(tmp_path, capsys,
                                                      chips):
    from mxnet_tpu import telemetry
    telemetry.reset()
    root = rehearsal.toy_root(tmp_path, "fit", chips)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        real = {m["name"]: m for m in json.load(f)["per_layer"]}
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    # the four entries as BENCHMARK.json lists them, on the toy cell
    assert set(READERS) <= set(real)
    bench["per_layer"] += [dict(real[name], workloads=["toy.cell"])
                           for name in READERS]
    with open(path, "w") as f:
        json.dump(bench, f)
    env = harness.Env(root, "toy.cell", seed=2 ** 31 + 7, seconds=0.5,
                      traced=1, t_process=time.perf_counter())
    try:
        assert not telemetry.enabled()          # the program's default
        result = bench_run.execute(env)
        batches = program_spans.batches(program_spans.ring())
    finally:
        telemetry.reset()
    assert result["correct"], capsys.readouterr().out
    got = result["metrics"]
    assert set(READERS) | {"dispatch_ms.fit"} == set(got)
    for name in READERS:
        assert got[name]["unit"] == "ms" and got[name]["value"] >= 0
    # only the session turned the spans on, so the ring holds the traced
    # window's batches and nothing of the warm-up before it
    assert 0 < len(batches) < result["attempted"] + 2
    assert not telemetry.trace_active()
