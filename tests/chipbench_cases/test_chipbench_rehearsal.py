"""Both runners end to end on the CPU at toy size: the same code path the
chip runs (seeded init, the checks by the rule, Module.fit with iterator,
metric and callback / serving.load and the closed loop, window, result
object), minus the device gate and the device trace.  No number printed
here is a device metric."""
import json
import os
import shutil
import time

import pytest

from chipbench import harness
from chipbench import run as bench_run

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
TOY = {"fit": dict(batch_per_chip=8, check_batch=8, warmup_batches=2,
                   pool_batches=2, trace_after_s=0.0, trace_s=0.2),
       "serve_closed": dict(clients=3, pool_rows=16, stats_batch=8,
                            warmup_s=0.2, trace_after_s=0.0, trace_s=0.2)}


# every reader of its kind is called; on the CPU those fed by the device
# trace find nothing to read
METRICS = {
    "fit": (["train_items_s_per_chip"],
            ["dispatch_ms.fit", "step_device_ms.fit", "busy_mfu_pct.fit",
             "collective_ms.fit", "device_idle_pct.fit"]),
    "serve_closed": (["serve_items_s", "serve_p95_ms"],
                     ["queue_wait_ms.serve", "batch_fill_pct.serve",
                      "device_idle_pct.serve"])}


def load(*parts):
    with open(os.path.join(REPO, *parts)) as f:
        return json.load(f)


def toy_root(tmp_path, traffic, chips=1):
    """A checkout-shaped directory: toy data files, the real readers."""
    root = tmp_path / "root"
    (root / "chipbench" / "traffic").mkdir(parents=True)
    (root / "chipbench" / "configs").mkdir()
    shutil.copytree(os.path.join(REPO, "chipbench", "layer_metrics"),
                    root / "chipbench" / "layer_metrics")
    cfg = load("chipbench", "configs", "mobilenet_v1.json")
    # float32 keeps the toy well-conditioned (at 32x32 and 8 images a
    # BatchNorm sees a handful of values, and bf16 drowns in that): the
    # reference's own dtype run is then the float32 run, dev_plain is 0 and
    # the rule holds the system to the floor alone
    cfg.update(zoo_model="mobilenet0.25", multiplier=0.25, image=32,
               classes=10, dtype="float32")
    tr = dict(load("chipbench", "traffic", traffic + ".json"), **TOY[traffic])
    (root / "chipbench" / "configs" / "toy.json").write_text(json.dumps(cfg))
    (root / "chipbench" / "traffic" / (traffic + ".json")).write_text(
        json.dumps(tr))
    e2e, layers = METRICS[traffic]
    bench = {
        "configs": [{"name": "toy", "file": "chipbench/configs/toy.json"}],
        "workloads": [{"name": "toy.cell", "config": "toy",
                       "traffic": traffic, "chips": chips}],
        "end_to_end": [{"name": n, "unit": "x"} for n in e2e + ["setup_s"]],
        "per_layer": [{"name": n, "unit": "x"} for n in layers]}
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root)


def rehearse(tmp_path, traffic, chips=1):
    root = toy_root(tmp_path, traffic, chips)
    results = {}
    for traced in (0, 1):
        env = harness.Env(root, "toy.cell", seed=2 ** 31 + 5, seconds=0.5,
                          traced=traced, t_process=time.perf_counter())
        results[traced] = bench_run.execute(env)
    return results[0], results[1]


def common(plain, traced, out):
    assert plain["correct"] and traced["correct"], out
    assert plain["failed"] == 0 and plain["attempted"] > 0
    assert plain["device"]["platform"] == "cpu"
    # the CPU has no device plane: no device number is invented
    assert "busy_s" not in traced["device"] and "breakdown" not in traced
    for line in ("chipbench: deviations", "chipbench: check",
                 "chipbench: window", "chipbench: setup"):
        assert line in out


@pytest.mark.parametrize("chips", [1, 4])
def test_module_fit_runner_rehearsal(tmp_path, capsys, chips):
    # four "chips" are four of conftest's virtual CPU devices: the fused
    # SPMD group, the sharded reference and the per-chip rate
    plain, traced = rehearse(tmp_path, "fit", chips)
    common(plain, traced, capsys.readouterr().out)
    assert plain["device"]["count"] == chips
    assert set(plain["metrics"]) == {"train_items_s_per_chip", "setup_s"}
    assert plain["metrics"]["train_items_s_per_chip"]["value"] > 0
    assert set(traced["metrics"]) == {"dispatch_ms.fit"}


def test_serve_closed_runner_rehearsal(tmp_path, capsys):
    plain, traced = rehearse(tmp_path, "serve_closed")
    common(plain, traced, capsys.readouterr().out)
    assert set(plain["metrics"]) == {"serve_items_s", "serve_p95_ms",
                                     "setup_s"}
    assert plain["metrics"]["serve_p95_ms"]["value"] > 0
    assert set(traced["metrics"]) == {"queue_wait_ms.serve",
                                      "batch_fill_pct.serve"}
