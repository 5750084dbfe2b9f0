"""Step time-series store (ISSUE 17 tentpole 2): bounded rings, export/
merge round-trips, the step-span exit hook, and the live ``/timeseries``
endpoint."""
import json
import urllib.request

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, telemetry
from mxnet_tpu.telemetry import core as tcore
from mxnet_tpu.telemetry import server
from mxnet_tpu.telemetry import timeseries as ts


@pytest.fixture(autouse=True)
def clean_store():
    ts.reset()
    ts.configure(ts._DEFAULT_CAP)
    yield
    ts.reset()
    ts.configure(ts._DEFAULT_CAP)


def test_ring_wraparound_bounded_and_counted():
    ts.configure(steps=8)
    before = telemetry.counter("timeseries_evictions")
    for step in range(20):
        ts.record("step_time_us", step, 100.0 + step)
    pts = ts.series("step_time_us")
    assert len(pts) == 8, "ring must stay at MXNET_TIMESERIES_STEPS"
    # oldest points dropped first: the survivors are the last 8 steps
    assert [s for s, _ in pts] == list(range(12, 20))
    assert telemetry.counter("timeseries_evictions") - before == 12


def test_configure_shrink_rebounds_in_place():
    for step in range(10):
        ts.record("m", step, float(step))
    ts.configure(steps=4)
    assert [s for s, _ in ts.series("m")] == [6, 7, 8, 9]
    assert ts.cap() == 4


def test_refresh_from_env_parses_cap(monkeypatch):
    monkeypatch.setenv("MXNET_TIMESERIES_STEPS", "16")
    ts.refresh_from_env()
    assert ts.cap() == 16
    monkeypatch.setenv("MXNET_TIMESERIES_STEPS", "garbage")
    ts.refresh_from_env()
    assert ts.cap() == ts._DEFAULT_CAP
    monkeypatch.setenv("MXNET_TIMESERIES_STEPS", "0")
    ts.refresh_from_env()
    assert ts.cap() == ts._DEFAULT_CAP


def test_export_json_round_trip(tmp_path):
    ts.record("a", 0, 1.5)
    ts.record("a", 1, 2.5)
    ts.record("b", 0, -3.0)
    path = str(tmp_path / "run.json")
    ts.export_json(path)
    loaded = ts.load_export(path)
    assert loaded["version"] == 1
    assert loaded["series"]["a"] == [[0, 1.5], [1, 2.5]]
    assert loaded["series"]["b"] == [[0, -3.0]]
    with pytest.raises(ValueError):
        bad = str(tmp_path / "bad.json")
        with open(bad, "w") as fh:
            json.dump({"not": "an export"}, fh)
        ts.load_export(bad)


def test_merge_concatenates_and_sorts_by_step():
    exp1 = {"steps_seen": 3,
            "series": {"a": [[0, 1.0], [2, 3.0]], "only1": [[0, 9.0]]}}
    exp2 = {"steps_seen": 5,
            "series": {"a": [[1, 2.0], [3, 4.0]], "only2": [[1, 8.0]]}}
    merged = ts.merge([exp1, exp2])
    assert merged["steps_seen"] == 5
    assert merged["series"]["a"] == [[0, 1.0], [1, 2.0], [2, 3.0],
                                     [3, 4.0]]
    assert merged["series"]["only1"] == [[0, 9.0]]
    assert merged["series"]["only2"] == [[1, 8.0]]


def test_note_step_exit_books_time_and_live_gauges(monkeypatch):
    # "this run" is this test: the gauge table is the process's, and other
    # files of the same worker leave theirs set (overlap_ratio)
    monkeypatch.setattr(tcore, "_gauges", {})
    telemetry.set_gauge("io_batch_wait_us", 17.0)
    ts.note_step_exit(1234.0)
    ts.note_step_exit(5678.0)
    assert ts.series("step_time_us") == [(0, 1234.0), (1, 5678.0)]
    assert ts.series("io_batch_wait_us") == [(0, 17.0), (1, 17.0)]
    # gauges never set this run record nothing (no phantom zeros)
    assert ts.series("overlap_ratio") == []
    assert ts.export()["steps_seen"] == 2


def test_step_span_exit_feeds_timeseries(monkeypatch):
    """The integration seam: closing a real telemetry step span lands a
    step_time_us point — core._close_step_window calls the hook."""
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    telemetry.refresh_from_env()
    telemetry.reset()
    try:
        with telemetry.span("train_step", cat="step"):
            nd.array(np.ones((2, 2), np.float32)).sum().asnumpy()
        assert len(ts.series("step_time_us")) == 1
    finally:
        telemetry.reset()
        monkeypatch.delenv("MXNET_TELEMETRY", raising=False)
        telemetry.refresh_from_env()


def test_record_model_stats_series_names():
    from mxnet_tpu import model_stats
    stats = [[1.0, 4.0, 0.1, 2.0], [9.0, 16.0, 0.2, 3.0]]
    ts.record_model_stats(5, ["w", "b"], stats, loss=0.5)
    assert ts.series("model/w/grad_norm_sq") == [(5, 1.0)]
    assert ts.series("model/b/weight_norm_sq") == [(5, 16.0)]
    assert ts.series("model/w/update_ratio") == [(5, 0.1)]
    assert ts.series("model/b/grad_absmax") == [(5, 3.0)]
    assert ts.series("model/loss") == [(5, 0.5)]
    assert len(ts.names()) == 2 * len(model_stats.STAT_NAMES) + 1


def test_timeseries_endpoint_live(monkeypatch):
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    telemetry.refresh_from_env()
    telemetry.reset()
    srv = server.start_server(port=0, sample_ms=100)
    try:
        ts.record("step_time_us", 0, 111.0)
        ts.record("model/loss", 0, 0.25)

        def get(path):
            url = "http://127.0.0.1:%d%s" % (srv.port, path)
            with urllib.request.urlopen(url, timeout=10) as resp:
                return resp.status, json.loads(resp.read().decode())

        status, body = get("/timeseries")
        assert status == 200
        assert body["n_series"] == 2
        assert body["series"]["model/loss"]["last_value"] == 0.25
        assert "points" in body["series"]["step_time_us"]

        status, full = get("/timeseries?full=1")
        assert status == 200
        assert full["series"]["model/loss"] == [[0, 0.25]]

        # the endpoint is observe-only: scraping must not create series
        assert ts.names() == ["model/loss", "step_time_us"]
    finally:
        server.stop_server()
        telemetry.reset()
        monkeypatch.delenv("MXNET_TELEMETRY", raising=False)
        telemetry.refresh_from_env()
