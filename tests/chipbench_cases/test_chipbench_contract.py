"""BENCHMARK.json against the contract's limits, the files it names, the
yardstick's own arithmetic (trace reduction, FLOP counts, peaks, the rule)
and the device gate of run.py."""
import json
import os
import re
import subprocess
import sys

import pytest

from chipbench import correct, flops, peaks, trace, traffic

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def load(*parts):
    with open(os.path.join(REPO, *parts)) as f:
        return json.load(f)


BENCH = load("BENCHMARK.json")


def test_benchmark_json_keeps_the_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["chipbench", "tests/chipbench_cases"]
    assert 1 <= BENCH["run_seconds"] <= 51
    # a full check with all 24 cells must fit the driver's limit
    assert (2 + 14 * 24) * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names = [e["name"] for k in ("configs", "workloads")
             for e in BENCH[k]] + [m["name"] for m in metrics]
    for name in names + [w[k] for w in BENCH["workloads"]
                         for k in ("config", "traffic")]:
        assert NAME.match(name), name
    for group in (BENCH["configs"], BENCH["workloads"], metrics):
        got = [e["name"] for e in group]
        assert len(got) == len(set(got)), got
    cells = {w["name"] for w in BENCH["workloads"]}
    assert len({(w["config"], w["traffic"]) for w in BENCH["workloads"]}) \
        == len(cells)
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= \
        max(1, len(cells) // 4)
    assert {c["name"] for c in BENCH["configs"]} == \
        {w["config"] for w in BENCH["workloads"]}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells and \
            m.get("workloads", cells), m
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        moved = e2e[m["moves"]]
        assert set(m.get("workloads", cells)) <= \
            set(moved.get("workloads", cells)), m
        if m["name"].startswith(("device_idle_pct", "step_device_ms",
                                 "busy_mfu_pct", "collective_ms")):
            assert m["source"] == "device_trace"
    for cell in cells:         # setup_s, one more end-to-end, one per-layer
        assert any(cell in m.get("workloads", cells) and m["name"] !=
                   "setup_s" for m in BENCH["end_to_end"]), cell
        assert any(cell in m.get("workloads", cells)
                   for m in BENCH["per_layer"]), cell


def test_every_named_file_exists_and_agrees():
    for c in BENCH["configs"]:
        cfg = load(c["file"])
        assert c["file"].startswith("chipbench/configs/")
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        assert cfg["source"].split(",")[0] in c["source"]
        assert os.path.exists(os.path.join(
            REPO, "chipbench", "reference", cfg["reference"] + ".py"))
        assert flops.forward_macs(cfg) > 0
    for w in BENCH["workloads"]:
        tr = load("chipbench", "traffic", w["traffic"] + ".json")
        assert os.path.exists(os.path.join(
            REPO, "chipbench", "runners", tr["runner"] + ".py"))
        assert len(w["why"]) <= 200 and "\n" not in w["why"]
    for m in BENCH["per_layer"]:
        path = os.path.join(REPO, "chipbench", "layer_metrics",
                            m["name"] + ".py")
        assert os.path.exists(path), path


def test_trace_reduction_on_a_recorded_chip_sample():
    rows = load("chipbench", "testdata",
                "trace_rows_step_boundary.json")["rows"]
    assert os.path.getsize(os.path.join(
        REPO, "chipbench", "testdata",
        "trace_rows_step_boundary.json")) < 100 * 1024
    got = trace.reduce_rows(rows)
    # known numbers: the same rows rastered at 1 ns by an independent
    # brute-force pass when the sample was cut (PR 24)
    assert got["window_s"] == pytest.approx(5e-3, abs=1e-12)
    assert got["busy_s_first"] == pytest.approx(4786667e-9, abs=1e-12)
    assert got["devices"] == 1 and got["collective_s_first"] == 0.0
    gaps = dict(got["idle_gaps"])
    assert gaps["wait_previous_step"] == pytest.approx(152652e-9, abs=1e-12)
    assert gaps["fit_step"] == pytest.approx(60681e-9, abs=1e-12)
    assert sum(gaps.values()) == pytest.approx(5e-3 - 4786667e-9, abs=1e-12)
    assert got["device_ops"][0][0] == "convert_reduce_fusion.4"
    assert got["span_counts"] == {"wait_previous_step": 1, "fit_step": 1}
    # no window, or no device op: nothing to read, not a zero
    assert trace.reduce_rows([r for r in rows
                              if r["name"] != trace.WINDOW]) is None
    assert trace.reduce_rows([r for r in rows
                              if not r["plane"].startswith("/device")]) \
        is None


def test_trace_reduction_nesting_devices_and_collectives():
    def row(plane, name, start, dur, line="XLA Ops"):
        return {"plane": plane, "line": line, "name": name,
                "start_ns": float(start), "dur_ns": float(dur)}
    host = "/host:CPU"
    rows = [row(host, trace.WINDOW, 0, 1000, "main"),
            row(host, "chipbench.outer", 100, 600, "main"),
            row(host, "chipbench.inner", 200, 100, "main"),
            row("/device:TPU:0", "fusion.1", 0, 150),
            row("/device:TPU:0", "all-reduce.3", 400, 200),
            row("/device:TPU:0", "fusion.1", 500, 300),     # overlaps
            row("/device:TPU:0", "fusion.9", 900, 500),     # runs past
            row("/device:TPU:1", "fusion.1", 0, 500),
            row("/device:TPU:0", "step", 0, 1000, "XLA Modules")]
    got = trace.reduce_rows(rows)
    assert got["busy_s_first"] == pytest.approx(650e-9)    # 150+400+100
    assert got["busy_s_mean"] == pytest.approx(575e-9)
    assert got["devices"] == 2
    assert got["collective_s_first"] == pytest.approx(200e-9)
    assert dict(got["idle_gaps"]) == pytest.approx(
        {"outer": 150e-9, "inner": 100e-9, "outside_any_span": 100e-9})


def test_device_ops_are_named_by_instruction():
    hlo = "%multiply_reduce_fusion.2 = (bf16[256]{0:T(256)}) fusion(bf16[2] %x)"
    assert trace.op_name(hlo) == "multiply_reduce_fusion.2"
    assert trace.op_name("all-reduce-start.3") == "all-reduce-start.3"
    assert trace.COLLECTIVE.match(trace.op_name("%all-reduce.7 = f32[] ..."))


def test_flops_match_the_papers():
    mobilenet = flops.forward_macs(load("chipbench", "configs",
                                        "mobilenet_v1.json"))
    assert round(mobilenet / 1e6) == 569        # Howard et al., Table 8
    resnet = flops.forward_macs(load("chipbench", "configs",
                                     "resnet50_v1.json"))
    assert abs(resnet / 3.8e9 - 1) < 0.05       # He et al., Table 1
    assert flops.train_flops_per_item({"flops": "chipbench.flops:"
                                       "mobilenet_v1", "multiplier": 1.0,
                                       "image": 224, "classes": 1000}) \
        == mobilenet * 6


def test_peaks_unknown_device_raises():
    assert peaks.peak("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks.peak("cpu")


def test_the_rule():
    assert correct.F <= 4
    want = [1.0, 2.0, 3.0]
    near, far = [1.0, 2.0, 3.05], [1.0, 2.0, 3.5]
    assert correct.judge("q", near, want, near)["verdict"] == "pass"
    assert correct.judge("q", far, want, near)["verdict"] == "fail"
    assert correct.judge("q", far, want, [3, -2, 1])["verdict"] == "ill"
    assert correct.judge("q", [float("nan")] * 3, want, near)["verdict"] \
        == "fail"
    rows = [correct.judge("head", near, want, near),
            correct.judge("chaotic", far, want, [3, -2, 1])]
    assert correct.summarise(rows, ["head"])[0]
    assert not correct.summarise(rows, ["chaotic"])[0]   # must be judged
    rows.append(correct.judge("known", far, want, near))
    assert not correct.summarise(rows, ["head"])[0]
    assert correct.summarise(rows, ["head"], skip=["known"])[0]


def test_every_seed_gets_the_same_sizes_in_another_order():
    mix = load("chipbench", "traffic", "serve_closed.json")["rows_mix"]
    a = traffic.rows_schedule(1, 0, mix, 20)
    b = traffic.rows_schedule(2 ** 31 + 9, 3, mix, 20)
    assert sorted(a) == sorted(b) and a != b
    assert sum(a) / len(a) == pytest.approx(2.5)


def test_run_py_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(REPO, "chipbench", "run.py"),
         "--workload", BENCH["workloads"][0]["name"], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300, cwd=REPO)
    assert done.returncode != 0
    assert "Nothing was run" in done.stderr
    assert "chipbench:" not in done.stdout and "{" not in done.stdout
