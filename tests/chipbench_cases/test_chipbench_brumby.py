"""The ``brumby_14b_base`` configuration's benchmark pieces on the CPU at
toy size: the plain reference against the system (loss, retention output,
every gradient), the vocabulary slice against the uncut reference, the
FLOP and byte functions against hand counts, the kernel-time reduction on
synthetic events, and a rehearsal of the ``module_fit_lm`` runner.  No
number here is a device metric."""
import json
import os
import shutil
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from chipbench import flops, flops_lm, harness, kernel_time, traffic_lm
from chipbench import run as bench_run
from chipbench import trace as trace_mod
from chipbench.reference import brumby_14b_base as ref
from mxnet_tpu.io import DataBatch, DataDesc
from mxnet_tpu.models.brumby import BRUMBY_TINY, brumby_symbol

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))


def load(*parts):
    with open(os.path.join(REPO, *parts)) as f:
        return json.load(f)


CFG = load("chipbench", "configs", "brumby_14b_base.json")


def toy_module(cfg, batch, seq, seed=3, probe_layer=0):
    mod = mx.mod.Module(brumby_symbol(cfg, probe_layer=probe_layer),
                        context=mx.cpu())
    mod.bind(data_shapes=[DataDesc("data", (batch, seq), dtype=np.float32)],
             label_shapes=[DataDesc("softmax_label", (batch, seq),
                                    dtype=np.float32)])
    mx.random.seed(seed)
    mod.init_params(initializer=mx.initializer.Xavier(magnitude=6))
    return mod


def tokens(cfg, batch, seq, seed=0):
    x, y = traffic_lm.token_pool(seed, 1, batch, seq, cfg["vocab_size"])
    return np.asarray(x[0]), np.asarray(y[0])


def test_reference_equals_the_module_in_float32():
    cfg = dict(BRUMBY_TINY)
    x, y = tokens(cfg, 2, 21)            # 21 is no multiple of the chunk
    mod = toy_module(cfg, 2, 21)
    mod.forward_backward(DataBatch([mx.nd.array(x)], [mx.nd.array(y)]))
    sys_loss, sys_probe = (o.asnumpy() for o in mod.get_outputs())
    params = {k: jnp.asarray(v.asnumpy())
              for k, v in mod.get_params()[0].items()}
    with jax.default_matmul_precision("highest"):
        (loss, probe), grads = jax.value_and_grad(
            lambda p: ref.loss(cfg, p, jnp.asarray(x), jnp.asarray(y),
                               "float32", 0), has_aux=True)(params)
    assert sys_loss[0] == pytest.approx(float(loss), rel=1e-5)
    np.testing.assert_allclose(sys_probe, np.asarray(probe), rtol=1e-4,
                               atol=1e-5)
    group = mod._exec_group
    assert set(group.param_names) == set(grads)
    for name, per_dev in zip(group.param_names, group.grad_arrays):
        got, want = per_dev[0].asnumpy(), np.asarray(grads[name])
        assert np.linalg.norm(got - want) <= 1e-4 * np.linalg.norm(want), \
            name


def test_reference_asserts_it_consumed_every_tensor():
    cfg = dict(BRUMBY_TINY)
    mod = toy_module(cfg, 1, 8)
    params = {k: jnp.asarray(v.asnumpy())
              for k, v in mod.get_params()[0].items()}
    params["layer0_extra_weight"] = jnp.zeros((1,))
    x, y = tokens(cfg, 1, 8)
    with pytest.raises(AssertionError, match="never asked for"):
        ref.loss(cfg, params, jnp.asarray(x), jnp.asarray(y), "float32")


def test_vocabulary_slice_ties_to_the_uncut_model():
    """With ids from the slice, the slice's logits are the uncut
    reference's logits at those rows (8 slices of a 48-row vocabulary)."""
    full = dict(BRUMBY_TINY, vocab_size=48)
    mod = toy_module(full, 1, 12)
    whole = {k: jnp.asarray(v.asnumpy())
             for k, v in mod.get_params()[0].items()}
    cut = dict(full, vocab_size=6)
    for part in (0, 5):
        rows = slice(part * 6, part * 6 + 6)
        held = dict(whole, embed_weight=whole["embed_weight"][rows],
                    lm_head_weight=whole["lm_head_weight"][rows])
        local, _ = tokens(cut, 1, 12, seed=part)
        with jax.default_matmul_precision("highest"):
            got, _ = ref.logits(cut, held, jnp.asarray(local), "float32")
            want, _ = ref.logits(full, whole, jnp.asarray(local) + rows.start,
                                 "float32")
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(want)[..., rows], rtol=1e-5,
                                   atol=1e-6)


def test_flop_and_byte_functions_against_hand_counts():
    # the issue's arithmetic, by hand: projections and MLP of four layers
    # and the head over 18,992 rows
    layer = 5120 * 5120 * 2 + 2 * 5120 * 1024 + 5120 * 8 + 3 * 5120 * 17408
    assert flops_lm.matmul_macs_per_token(CFG) == 4 * layer + 5120 * 18992
    assert round(flops_lm.matmul_macs_per_token(CFG) / 1e5) == 14186
    assert flops_lm.retention_state_size(CFG) == 8256 == \
        CFG["retention"]["state_size"]
    ret = 40 * 8256 * 128 + 8 * 8256 * 128 + 40 * 512 * 256 + 48 * 8256 * 2
    assert flops_lm.retention_macs_per_token(CFG) == ret
    assert 56e6 < ret < 57e6            # "~56 M", 14% of the forward
    assert flops.forward_macs(CFG) == 4 * layer + 5120 * 18992 + 4 * ret
    assert 0.13 < 4 * ret / flops.forward_macs(CFG) < 0.15
    step = flops.train_flops_per_item(CFG) * 16384
    assert 1.6e14 < step < 1.65e14
    # whatever implements it: nothing but shapes enters the counts
    work, nbytes = flops_lm.retention_forward_work(CFG, 16384)
    assert work == 2 * ret * 16384 * 4
    assert nbytes == 16384 * 4 * ((2 * 40 + 2 * 8) * 128 * 2 + 8 * 4)
    assert flops_lm.retention_train_work(CFG, 16384) == (3 * work,
                                                         3 * nbytes)
    # parameters: the file's count is the graph's
    toy = toy_module(dict(BRUMBY_TINY), 1, 8)
    assert sum(v.size for v in toy.get_params()[0].values()) == \
        2 * (32 * 64 * 2 + 2 * 32 * 32 + 32 * 2 + 2 + 3 * 32 * 64 + 2 * 32
             + 2 * 16) + 2 * 50 * 32 + 32
    big = 4 * (layer + 8 + 2 * 5120 + 2 * 128) + 2 * 18992 * 5120 + 5120
    assert CFG["parameters"] == big


def test_configuration_keeps_the_published_widths():
    catalog = {"attention_bias": False, "head_dim": 128, "hidden_act": "silu",
               "hidden_size": 5120, "intermediate_size": 17408,
               "max_position_embeddings": 32768, "max_window_layers": 40,
               "model_type": "brumby", "num_attention_heads": 40,
               "num_hidden_layers": 40, "num_key_value_heads": 8,
               "rms_norm_eps": 1e-06, "rope_scaling": None,
               "rope_theta": 1000000, "sliding_window": None,
               "tie_word_embeddings": False, "use_sliding_window": False,
               "vocab_size": 151936}
    differs = sorted(k for k, v in catalog.items() if CFG[k] != v)
    assert differs == sorted(CFG["reduced"]) == ["num_hidden_layers",
                                                 "vocab_size"]
    assert CFG["published"] == {k: catalog[k] for k in CFG["reduced"]}
    assert CFG["vocab_size"] * 8 == catalog["vocab_size"]
    for key in ("degree", "gate_projection", "qk_norm", "rope", "eps",
                "chunk", "optimizer", "initial_gate_range"):
        assert key in CFG["assumed"], key


def test_kernel_time_is_the_union_of_matching_events(tmp_path, monkeypatch):
    events = [("%custom-call.3 power_retention_fwd/pallas_call", 100., 300.),
              ("%while.2 jit(step)/power_retention_bwd/while", 400., 900.),
              ("%fusion.7 jit(step)/power_retention_bwd/while/body/dot",
               500., 600.),                      # inside the loop's event
              ("%fusion.9 jit(step)/lm_head_loss/dot", 600., 2000.),
              ("%fusion.1 jit(step)/power_retention_fwd/mul", 950., 1200.)]
    monkeypatch.setattr(kernel_time, "device_events",
                        lambda d: ((0., 1000.), events))
    got = kernel_time.seconds_by_pattern(
        str(tmp_path), CFG["trace_patterns"])["retention"]
    assert got["seconds"] == pytest.approx((200 + 500 + 50) / 1e9)
    assert got["events"] == 4
    assert got["longest_ops"][0] == ("while.2", pytest.approx(500e-9))
    # nothing matches, or no window: nothing to read, and no exception
    assert kernel_time.seconds_by_pattern(str(tmp_path), {"x": "nope"}) == {}
    monkeypatch.setattr(kernel_time, "device_events", lambda d: (None, []))
    assert kernel_time.seconds_by_pattern(str(tmp_path),
                                          CFG["trace_patterns"]) == {}
    monkeypatch.undo()
    assert kernel_time.seconds_by_pattern(str(tmp_path), {"x": "y"}) == {}


def test_kernel_time_on_a_recorded_chip_sample(tmp_path):
    """70 device events of a real v5e trace of the retention op's forward
    (the Pallas call, under recomputation) and the start of its backward,
    cut with an independent protobuf library when the sample was taken
    (PR 27), which also gave the known numbers."""
    sample = os.path.join(REPO, "chipbench", "testdata",
                          "xplane_retention_sample.pb")
    assert os.path.getsize(sample) < 100 * 1024
    where = tmp_path / "plugins" / "profile" / "run"
    where.mkdir(parents=True)
    shutil.copy(sample, where / "host.xplane.pb")
    tables = kernel_time.scope_paths(sample)
    # as on the chip, a device plane without an op line sorts first
    assert sorted(tables)[0] == "/device:CUSTOM:Megascale Trace"
    scopes = tables["/device:TPU:0"]
    assert len(scopes) == 61
    kernel = [k for k in scopes if k.startswith("%power_retention_fwd.1 =")]
    assert len(kernel) == 1 and scopes[kernel[0]].endswith(
        "rematted_computation/power_retention_fwd/power_retention_fwd/"
        "pallas_call:")
    window, events = kernel_time.device_events(str(tmp_path))
    assert len(events) == 70
    assert window[1] - window[0] == pytest.approx(1480657.164, abs=2)
    got = kernel_time.seconds_by_pattern(
        str(tmp_path), dict(CFG["trace_patterns"], nothing="no_such_op"))
    assert set(got) == {"retention"}
    assert got["retention"]["seconds"] == pytest.approx(1459886056e-12,
                                                        rel=1e-4)
    assert got["retention"]["events"] == 59
    assert got["retention"]["longest_ops"][0][0] == "power_retention_fwd.1"


def test_retention_readers_find_nothing_without_a_trace():
    import importlib.util
    for name in ("retention_ms.fit", "retention_roofline_pct.fit"):
        spec = importlib.util.spec_from_file_location(
            "reader_" + name.replace(".", "_"), os.path.join(
                REPO, "chipbench", "layer_metrics", name + ".py"))
        reader = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(reader)
        ctx = {"cfg": CFG, "trace": None, "facts": {"batch_per_chip": 16384}}
        assert reader.read(ctx) is None
        # a program without the named ops (the parent): a trace, no kernel
        ctx["trace"] = {"span_counts": {"fit_step": 3}}
        assert reader.read(ctx) is None
        from chipbench import peaks
        ctx.update(peaks=peaks, device_kind="TPU v5 lite",
                   facts={"batch_per_chip": 16384,
                          "kernel_s": {"retention": {"seconds": 1.5}}})
        value = reader.read(ctx)
        if name == "retention_ms.fit":
            assert value == pytest.approx(500.0)
        else:           # 3 steps x 2.23e13 FLOPs / 197e12 / 1.5 s
            assert value == pytest.approx(
                100 * 3 * 3 * 2 * flops_lm.retention_macs_per_token(CFG)
                * 16384 * 4 / 197e12 / 1.5)
            assert value < 100


TOY_TRAFFIC = dict(seq_len=24, sequences_per_step=2, batch_per_chip=48,
                   pool_batches=2, warmup_batches=2, trace_after_s=0.0,
                   trace_s=0.2)


def toy_root(tmp_path):
    root = tmp_path / "root"
    (root / "chipbench" / "traffic").mkdir(parents=True)
    (root / "chipbench" / "configs").mkdir()
    shutil.copytree(os.path.join(REPO, "chipbench", "layer_metrics"),
                    root / "chipbench" / "layer_metrics")
    cfg = dict(CFG, **{k: v for k, v in BRUMBY_TINY.items()})
    cfg["check"] = dict(CFG["check"], gradient_subset=[
        ["lm_head_weight", "final_norm_gamma", "layer1_*"],
        ["layer0_gate_*", "layer0_q_weight", "layer0_k_weight",
         "layer0_v_weight"]],
        must_pass=["loss", "retention_output", "grad:lm_head_weight",
                   "grad:layer1_gate_weight"])
    tr = dict(load("chipbench", "traffic", "fit_lm.json"), **TOY_TRAFFIC)
    (root / "chipbench" / "configs" / "toy.json").write_text(json.dumps(cfg))
    (root / "chipbench" / "traffic" / "fit_lm.json").write_text(
        json.dumps(tr))
    layers = [m["name"] for m in load("BENCHMARK.json")["per_layer"]
              if "brumby14b.fit" in m.get("workloads", [])]
    bench = {
        "configs": [{"name": "toy", "file": "chipbench/configs/toy.json"}],
        "workloads": [{"name": "toy.cell", "config": "toy",
                       "traffic": "fit_lm", "chips": 1}],
        "end_to_end": [{"name": n, "unit": "x"}
                       for n in ("train_items_s_per_chip", "setup_s")],
        "per_layer": [{"name": n, "unit": "x"} for n in layers]}
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root), layers


def test_module_fit_lm_runner_rehearsal(tmp_path, capsys):
    root, layers = toy_root(tmp_path)
    assert {"retention_ms.fit", "retention_roofline_pct.fit",
            "dispatch_ms.fit", "busy_mfu_pct.fit"} <= set(layers)
    results = {}
    for traced in (0, 1):
        env = harness.Env(root, "toy.cell", seed=2 ** 31 + 5, seconds=0.5,
                          traced=traced, t_process=time.perf_counter())
        results[traced] = bench_run.execute(env)
    plain, traced = results[0], results[1]
    out = capsys.readouterr().out
    assert plain["correct"] and traced["correct"], out
    assert plain["failed"] == 0 and plain["attempted"] > 0
    assert set(plain["metrics"]) == {"train_items_s_per_chip", "setup_s"}
    # the CPU has no device plane: no device number is invented
    host_side = {"dispatch_ms.fit", "step_prepare_ms.fit",
                 "step_enqueue_ms.fit", "metric_host_ms.fit"}
    assert {"dispatch_ms.fit"} <= set(traced["metrics"]) <= host_side
    assert "busy_s" not in traced["device"] and "breakdown" not in traced
    for line in ("chipbench: deviations", "chipbench: check",
                 "chipbench: window", "chipbench: setup"):
        assert line in out
    window = [json.loads(ln.split(" ", 2)[2]) for ln in out.splitlines()
              if ln.startswith("chipbench: window")][-1]
    assert all(window["held"].values())
    assert window["batch"] == 48
    counters = window["counters"]
    assert counters["executor_remat_segments"] >= 2
    assert counters["power_retention_traced"] > 0


def test_trace_module_still_names_ops_by_instruction():
    # the helper leans on trace.py's union/clip/op_name: keep their meaning
    assert trace_mod.union([(0, 2), (1, 3), (5, 6)]) == [[0, 3], [5, 6]]
    assert trace_mod.clip([(0, 10)], 2, 4) == [(2, 4)]
