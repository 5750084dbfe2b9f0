"""The ``lfm2_8b_a1b`` configuration's benchmark pieces on the CPU at toy
size: the plain reference against the system (loss, probes, choices,
every gradient), the held-expert share against the uncut reference, the
FLOP and byte functions against hand counts, the configuration file
against the catalog's published keys, the four readers, and a rehearsal
of the ``module_fit_probed`` runner.  No number here is a device
metric."""
import importlib.util
import json
import os
import shutil
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from chipbench import flops, flops_lfm2, harness, peaks, traffic_lm
from chipbench import run as bench_run
from chipbench.reference import lfm2_8b_a1b as ref
from mxnet_tpu.io import DataBatch, DataDesc
from mxnet_tpu.models.lfm2 import LFM2_MOE_TINY, lfm2_moe_symbol

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
PROBES = ("layer0_op", "layer1_op", "layer0_ffn", "layer3_ffn",
          "layer1_choice", "layer3_choice")


def load(*parts):
    with open(os.path.join(REPO, *parts)) as f:
        return json.load(f)


CFG = load("chipbench", "configs", "lfm2_8b_a1b.json")
BENCH = load("BENCHMARK.json")


def toy_module(cfg, batch, seq, seed=3, probes=PROBES):
    mod = mx.mod.Module(lfm2_moe_symbol(cfg, probes=probes),
                        context=mx.cpu())
    mod.bind(data_shapes=[DataDesc("data", (batch, seq), dtype=np.float32)],
             label_shapes=[DataDesc("softmax_label", (batch, seq),
                                    dtype=np.float32)])
    mx.random.seed(seed)
    mod.init_params(initializer=mx.initializer.Xavier(magnitude=6))
    return mod


def tensors(mod):
    return {k: jnp.asarray(v.asnumpy())
            for d in mod.get_params() for k, v in d.items()}


def tokens(cfg, batch, seq, seed=0):
    x, y = traffic_lm.token_pool(seed, 1, batch, seq, cfg["vocab_size"])
    return np.asarray(x[0]), np.asarray(y[0])


def test_reference_equals_the_module_in_float32():
    cfg = dict(LFM2_MOE_TINY)
    x, y = tokens(cfg, 2, 21)
    mod = toy_module(cfg, 2, 21)
    mod.forward_backward(DataBatch([mx.nd.array(x)], [mx.nd.array(y)]))
    outs = [o.asnumpy() for o in mod.get_outputs()]
    params = tensors(mod)
    trained = {k: params[k] for k in mod._exec_group.param_names}
    with jax.default_matmul_precision("highest"):
        (loss, seen), grads = jax.value_and_grad(
            lambda p: ref.loss(cfg, dict(params, **p), jnp.asarray(x),
                               jnp.asarray(y), "float32", PROBES),
            has_aux=True)(trained)
    assert outs[0][0] == pytest.approx(float(loss), rel=1e-5)
    for name, got in zip(PROBES, outs[1:]):
        np.testing.assert_allclose(got, np.asarray(seen[name], np.float32),
                                   rtol=1e-4, atol=2e-5, err_msg=name)
    group = mod._exec_group
    assert set(group.param_names) == set(grads)
    for name, per_dev in zip(group.param_names, group.grad_arrays):
        got, want = per_dev[0].asnumpy(), np.asarray(grads[name])
        assert np.linalg.norm(got - want) <= 1e-4 * np.linalg.norm(want), \
            name


def test_reference_asserts_it_consumed_every_tensor():
    cfg = dict(LFM2_MOE_TINY)
    mod = toy_module(cfg, 1, 8, probes=())
    params = tensors(mod)
    x, y = tokens(cfg, 1, 8)
    assert ref.loss(cfg, params, jnp.asarray(x), jnp.asarray(y),
                    "float32")[1] is None
    params["layer0_extra_weight"] = jnp.zeros((1,))
    with pytest.raises(AssertionError, match="never asked for"):
        ref.loss(cfg, params, jnp.asarray(x), jnp.asarray(y), "float32")


def test_held_expert_shares_tie_to_the_uncut_reference():
    """Four shares of 2 of the 8 experts, each computed densely by the
    reference from its own slice of the stacks, add up to the uncut
    layer; the router and its bias are whole in every share."""
    cfg = dict(LFM2_MOE_TINY)
    rng = np.random.RandomState(5)
    h = jnp.asarray(rng.randn(19, 32), jnp.float32)
    w = {"router_weight": jnp.asarray(rng.randn(8, 32), jnp.float32),
         "expert_bias": jnp.asarray(rng.uniform(-.3, .3, 8), jnp.float32),
         "experts_w1_weight": jnp.asarray(rng.randn(8, 32, 24) * .2,
                                          jnp.float32),
         "experts_w3_weight": jnp.asarray(rng.randn(8, 32, 24) * .2,
                                          jnp.float32),
         "experts_w2_weight": jnp.asarray(rng.randn(8, 24, 32) * .2,
                                          jnp.float32)}
    whole, idx = ref.experts(cfg, w, h, jnp.float32)
    total = 0
    for first in range(0, 8, 2):
        part = dict(w, **{k: w[k][first:first + 2] for k in w
                          if k.startswith("experts_")})
        share, same = ref.experts(cfg, part, h, jnp.float32, first=first)
        assert np.array_equal(np.asarray(same), np.asarray(idx))
        total = total + share
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=1e-5, atol=1e-6)


def test_flop_and_byte_functions_against_hand_counts():
    conv = 2048 * 6144 + 2048 * 3 + 2048 * 2048
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512
    scores = 32 * 64 * 8192              # S/2 keys, q.k and p.v, 32 heads
    dense = 3 * 2048 * 7168
    routed = 2048 * 32 + 4 * 3 * 2048 * 1792
    assert flops_lfm2.conv_macs_per_token(CFG) == conv == 16783360
    assert flops_lfm2.attention_projection_macs_per_token(CFG) == attn
    assert flops_lfm2.attention_core_macs_per_token(CFG) == scores
    assert flops_lfm2.expert_macs_per_token(CFG) == routed
    total = 2 * (conv + dense) + (attn + scores + routed) + \
        3 * (conv + routed) + 2048 * 65536
    assert flops.forward_macs(CFG) == total
    assert round(total / 1e6) == 510                  # the issue's count
    assert 0.49 < (4 * routed + attn + scores + 3 * conv) / total < 0.51
    assert 49e12 < flops.train_flops_per_item(CFG) * 16384 < 51e12
    # parameters: the file's count is the shapes' and the toy graph's
    stacks = 32 * 3 * 2048 * 1792
    assert flops_lfm2.parameters(CFG) == CFG["parameters"] == \
        2 * (conv + dense + 4096) + (attn + 128 + 4096 + 2048 * 32 + stacks) \
        + 3 * (conv + 4096 + 2048 * 32 + stacks) + 65536 * 2048 + 2048
    toy = dict(LFM2_MOE_TINY, seq_len=8)
    mod = toy_module(toy, 1, 8, probes=())
    assert sum(v.size for v in mod.get_params()[0].values()) == \
        flops_lfm2.parameters(toy)
    # whatever implements them: nothing but shapes enters the work
    work, nbytes = flops_lfm2.expert_train_work(CFG, 16384)
    assert work == 4 * 3 * 2 * 16384 * routed
    assert nbytes == 4 * 2 * (3 * stacks + 4 * 65536 * 2048)
    work, nbytes = flops_lfm2.attention_train_work(CFG, 16384)
    assert work == 3 * 2 * 16384 * scores
    assert nbytes == 3 * 16384 * 2 * (32 + 8) * 64 * 2


def test_configuration_keeps_the_published_widths():
    catalog = {"conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
               "intermediate_size": 7168, "max_position_embeddings": 128000,
               "model_type": "lfm2_moe", "moe_intermediate_size": 1792,
               "norm_eps": 1e-05, "norm_topk_prob": True,
               "num_attention_heads": 32, "num_dense_layers": 2,
               "num_experts": 32, "num_experts_per_tok": 4,
               "num_hidden_layers": 24, "num_key_value_heads": 8,
               "rope_theta": 1000000, "routed_scaling_factor": 1,
               "use_expert_bias": True, "vocab_size": 65536}
    differs = sorted(k for k, v in catalog.items() if CFG[k] != v)
    assert differs == CFG["reduced"] == ["num_hidden_layers"]
    assert CFG["published"] == {"num_hidden_layers": 24}
    # the published pattern is kept whole; the model reads its first six
    types = CFG["layer_types"]
    assert len(types) == 24 and types.count("full_attention") == 6
    assert types[:6] == ["conv", "conv", "full_attention", "conv", "conv",
                         "conv"]
    assert types[2:6] == types[6:10] == types[10:14]    # one whole period
    assert flops_lfm2.kinds(CFG) == ref.layer_kinds(CFG) == \
        [("conv", "dense")] * 2 + [("full_attention", "experts")] + \
        [("conv", "experts")] * 3
    for key in ("head_dim", "qk_norm", "tied_head", "topk_normalisation_eps",
                "split_order", "expert_bias", "optimizer", "dtype_policy",
                "inputs"):
        assert key in CFG["assumed"], key
    assert len(CFG["layer_equations"]) == 6
    tr = load("chipbench", "traffic", "fit_lm_8k.json")
    assert tr["seq_len"] == CFG["seq_len"] == 8192
    assert tr["sequences_per_step"] * tr["seq_len"] == tr["batch_per_chip"]
    entry = [c for c in BENCH["configs"] if c["name"] == "lfm2_8b_a1b"][0]
    assert entry["source"] == CFG["source"]


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"), os.path.join(
            REPO, "chipbench", "layer_metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("kernel,work", [("moe", "expert_train_work"),
                                         ("attention",
                                          "attention_train_work")])
def test_kernel_readers(kernel, work):
    ms, share = reader(kernel + "_ms.fit"), \
        reader(kernel + "_roofline_pct.fit")
    ctx = {"cfg": CFG, "trace": None, "facts": {"batch_per_chip": 16384}}
    assert ms.read(ctx) is None and share.read(ctx) is None
    # a program without the named ops (the parent): a trace, no kernel
    ctx["trace"] = {"span_counts": {"fit_step": 3}}
    assert ms.read(ctx) is None and share.read(ctx) is None
    ctx.update(peaks=peaks, device_kind="TPU v5 lite",
               facts={"batch_per_chip": 16384,
                      "kernel_s": {kernel: {"seconds": 1.5}}})
    assert ms.read(ctx) == pytest.approx(500.0)
    need, nbytes = getattr(flops_lfm2, work)(CFG, 16384)
    assert need / 197e12 > nbytes / 819e9           # compute-bound both
    assert share.read(ctx) == pytest.approx(100 * 3 * need / 197e12 / 1.5)
    assert 0 < share.read(ctx) < 100
    # a configuration that names no work function: nothing to read
    ctx["cfg"] = {k: v for k, v in CFG.items() if not k.endswith("_work")}
    assert share.read(ctx) is None


CELL = "lfm2_8b_a1b.fit"
SHARED_KERNELS = ("moe_ms.fit", "attention_ms.fit", "moe_roofline_pct.fit",
                  "attention_roofline_pct.fit", "causal_conv_ms.fit",
                  "causal_conv_roofline_pct.fit")


def cell_and_metrics_are_listed(bench):
    """Only what this configuration owns: later cells and metrics may be
    appended to ``BENCHMARK.json``, and later cells to the shared
    entries' ``workloads``, without an edit here."""
    cell = [w for w in bench["workloads"] if w["config"] == "lfm2_8b_a1b"]
    assert CELL in [w["name"] for w in cell]
    fit = [w for w in cell if w["name"] == CELL][0]
    assert fit["chips"] == 1 and fit["traffic"] == "fit_lm_8k"
    mine = {m["name"]: m for m in bench["per_layer"]
            if CELL in m.get("workloads", [])}
    assert {"dispatch_ms.fit", "step_device_ms.fit", "busy_mfu_pct.fit",
            "device_idle_pct.fit", "step_prepare_ms.fit",
            "step_enqueue_ms.fit", "metric_host_ms.fit"} | \
        set(SHARED_KERNELS) <= set(mine)
    assert all(mine[n]["source"] == "device_trace" and
               mine[n]["moves"] == "train_items_s_per_chip"
               for n in SHARED_KERNELS)


def test_benchmark_json_gained_one_cell_and_four_metrics():
    cell_and_metrics_are_listed(BENCH)


TOY_TRAFFIC = dict(seq_len=24, sequences_per_step=2, batch_per_chip=48,
                   pool_batches=2, warmup_batches=2, trace_after_s=0.0,
                   trace_s=0.2)


def toy_root(tmp_path):
    root = tmp_path / "root"
    (root / "chipbench" / "traffic").mkdir(parents=True)
    (root / "chipbench" / "configs").mkdir()
    shutil.copytree(os.path.join(REPO, "chipbench", "layer_metrics"),
                    root / "chipbench" / "layer_metrics")
    cfg = dict(CFG, **LFM2_MOE_TINY)
    cfg["seq_len"] = TOY_TRAFFIC["seq_len"]
    cfg["check"] = dict(
        CFG["check"], probes=["layer1_op", "layer3_ffn"],
        choice_probes=["layer1_choice", "layer2_choice", "layer3_choice"],
        gradient_subset=[["embed_weight", "final_norm_gamma", "layer3_*"],
                         ["layer1_q_weight", "layer1_router_weight",
                          "layer[0-2]_conv_weight",
                          "layer0_mlp_w2_weight"]],
        must_pass=["loss", "probe:layer1_op", "probe:layer3_ffn",
                   "grad:embed_weight", "grad:layer3_experts_w2_weight",
                   "grad:layer3_router_weight"])
    tr = dict(load("chipbench", "traffic", "fit_lm_8k.json"), **TOY_TRAFFIC)
    (root / "chipbench" / "configs" / "toy.json").write_text(json.dumps(cfg))
    (root / "chipbench" / "traffic" / "fit_lm_8k.json").write_text(
        json.dumps(tr))
    layers = [m["name"] for m in BENCH["per_layer"]
              if "lfm2_8b_a1b.fit" in m.get("workloads", [])]
    bench = {
        "configs": [{"name": "toy", "file": "chipbench/configs/toy.json"}],
        "workloads": [{"name": "toy.cell", "config": "toy",
                       "traffic": "fit_lm_8k", "chips": 1}],
        "end_to_end": [{"name": n, "unit": "x"}
                       for n in ("train_items_s_per_chip", "setup_s")],
        "per_layer": [{"name": n, "unit": "x"} for n in layers]}
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root)


def test_module_fit_probed_runner_rehearsal(tmp_path, capsys):
    root = toy_root(tmp_path)
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
    lines = {key: json.loads(ln.split(" ", 2)[2]) for ln in out.splitlines()
             if ln.startswith("chipbench: ")
             for key in [ln.split(" ", 2)[1]]}
    assert {"deviations", "check", "routing", "window", "setup"} <= set(lines)
    assert lines["check"]["must_pass_not_passing"] == []
    window = lines["window"]
    assert all(window["held"].values()), window
    assert window["batch"] == 48
    # dropless: every expert layer routed tokens x k rows, none elsewhere
    assert window["routed_rows_a_step"] == 48 * 2 * 3
    assert set(lines["routing"]) == {"layer1_choice", "layer2_choice",
                                     "layer3_choice"}
    for layer in lines["routing"].values():
        assert layer["rows"] == 96 and layer["mean"] == 12.0
        assert layer["max"] >= 12 >= layer["min"]
        assert layer["agree"] == 1.0         # float32 against float32
    counters = window["counters"]
    assert counters["executor_remat_segments"] >= 4
    assert counters["fit_step_overlapped"] > 0
    assert window["traced_in_first_step"] == {
        "sparse_moe_traced": 3, "causal_attention_traced": 2,
        "short_conv_traced": 2}


def test_the_parent_would_fail_cleanly(tmp_path):
    """A program without the model (the parent commit) leaves the runner
    with SystemExit at once, not a hang or a traceback mid-run."""
    from chipbench.runners import module_fit_probed
    cfg = dict(CFG, symbol="mxnet_tpu.models.no_such_model:symbol")
    with pytest.raises(SystemExit, match="cannot run configuration"):
        module_fit_probed.probed_symbol(cfg)
