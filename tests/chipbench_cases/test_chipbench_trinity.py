"""The ``trinity_mini`` configuration's benchmark pieces on the CPU at toy
size: the plain reference against the system (loss, probes, choices,
every gradient), the held-expert shares and the shared expert against
the uncut layer, the FLOP and byte functions against hand counts, the
configuration file against the catalog's published keys, the two new
readers, and a rehearsal of the cell through ``module_fit_probed``.  No
number here is a device metric."""
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
from chipbench import flops, flops_trinity, harness, peaks, traffic_lm
from chipbench import run as bench_run
from chipbench.reference import trinity_mini as ref
from mxnet_tpu.io import DataBatch, DataDesc
from mxnet_tpu.models.trinity import AFMOE_TINY, afmoe_symbol

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
PROBES = tuple("layer%d_%s" % (i, k) for i in (0, 2, 3, 7)
               for k in ("op", "ffn")) + ("layer2_choice", "layer7_choice")


def load(*parts):
    with open(os.path.join(REPO, *parts)) as f:
        return json.load(f)


CFG = load("chipbench", "configs", "trinity_mini.json")
BENCH = load("BENCHMARK.json")


def toy_module(cfg, batch, seq, seed=3, probes=PROBES):
    mod = mx.mod.Module(afmoe_symbol(cfg, probes=probes), context=mx.cpu())
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
    cfg = dict(AFMOE_TINY)
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
                                   rtol=1e-4, atol=5e-5, err_msg=name)
    group = mod._exec_group
    assert set(group.param_names) == set(grads)
    # the gate, all four norms of a layer and the shared expert are among
    # the trained tensors the reference was differentiated by
    for name in ("layer3_gate_weight", "layer2_input_norm_gamma",
                 "layer2_post_attention_norm_gamma",
                 "layer2_pre_mlp_norm_gamma", "layer2_post_mlp_norm_gamma",
                 "layer2_shared_w2_weight", "lm_head_weight"):
        assert name in grads, name
    for name, per_dev in zip(group.param_names, group.grad_arrays):
        got, want = per_dev[0].asnumpy(), np.asarray(grads[name])
        assert np.linalg.norm(got - want) <= 1e-4 * np.linalg.norm(want), \
            name


def test_reference_asserts_it_consumed_every_tensor():
    cfg = dict(AFMOE_TINY)
    mod = toy_module(cfg, 1, 8, probes=())
    params = tensors(mod)
    x, y = tokens(cfg, 1, 8)
    assert ref.loss(cfg, params, jnp.asarray(x), jnp.asarray(y),
                    "float32")[1] is None
    params["layer0_extra_weight"] = jnp.zeros((1,))
    with pytest.raises(AssertionError, match="never asked for"):
        ref.loss(cfg, params, jnp.asarray(x), jnp.asarray(y), "float32")


def test_reference_imports_nothing_of_the_system():
    with open(os.path.join(REPO, "chipbench", "reference",
                           "trinity_mini.py")) as f:
        text = f.read()
    assert "import mxnet_tpu" not in text and "from mxnet_tpu" not in text


def test_held_shares_and_the_shared_expert_add_up_to_the_uncut_layer():
    """Four shares of 2 of the 8 routed experts, each computed by the
    reference from its own slice of the stacks, plus the shared expert
    counted once, are the uncut layer's FFN; the router and its bias are
    whole in every share."""
    cfg = dict(AFMOE_TINY, num_experts=8, first_expert=0)
    rng = np.random.RandomState(5)
    h = jnp.asarray(rng.randn(19, 32), jnp.float32)
    w = {"router_weight": jnp.asarray(rng.randn(8, 32), jnp.float32),
         "expert_bias": jnp.asarray(rng.uniform(-.3, .3, 8), jnp.float32)}
    for pre, count in (("experts_", 8), ("shared_", None)):
        for name, shape in (("w1", (32, 16)), ("w3", (32, 16)),
                            ("w2", (16, 32))):
            full = shape if count is None else (count,) + shape
            value = rng.randn(*full) * .2
            if count is None:          # a dense layer's weight is [out, in]
                value = value.T
            w[pre + name + "_weight"] = jnp.asarray(value, jnp.float32)
    whole, idx = ref.routed(cfg, w, h, jnp.float32)
    once = ref.shared(w, h, jnp.float32)
    total = once
    for first in range(0, 8, 2):
        part = dict(w, **{k: w[k][first:first + 2] for k in w
                          if k.startswith("experts_")})
        share, same = ref.routed(cfg, part, h, jnp.float32, first=first)
        assert np.array_equal(np.asarray(same), np.asarray(idx))
        assert float(jnp.abs(share).max()) > 0
        total = total + share
    np.testing.assert_allclose(np.asarray(total), np.asarray(once + whole),
                               rtol=1e-5, atol=1e-6)
    # route_scale and the epsilon reach the weights
    _, weight = ref.route(cfg, w, h)
    np.testing.assert_allclose(np.asarray(weight.sum(-1)), 2.826, rtol=1e-6)


def test_flop_and_byte_functions_against_hand_counts():
    proj = 3 * 2048 * 4096 + 2 * 2048 * 512
    assert flops_trinity.attention_projection_macs_per_token(CFG) == proj \
        == 27262976
    # the band's key count: a full layer's triangle, a sliding layer's
    # 2,048 newest keys (fewer for the first 2,047 tokens)
    full = 16384 * 16385 // 2
    band = 2048 * 2049 // 2 + (16384 - 2048) * 2048
    assert flops_trinity.visible_keys(CFG, "full_attention") == full
    assert flops_trinity.visible_keys(CFG, "sliding_attention") == band \
        == 31458304
    assert flops_trinity.visible_keys(dict(CFG, seq_len=1024),
                                      "sliding_attention") == 1024 * 1025 // 2
    assert band == sum(min(t + 1, 2048) for t in range(16384))
    full_macs = 2 * 4096 * full / 16384
    band_macs = 2 * 4096 * band / 16384
    assert flops_trinity.attention_core_macs_per_token(
        CFG, "full_attention") == full_macs
    assert 4.2 < full_macs / band_macs < 4.3        # the issue's 4.3 x
    dense = 3 * 2048 * 6144
    expert = 3 * 2048 * 1024
    routed = 2048 * 128 + 8 * expert * 32 / 128
    assert flops_trinity.routed_macs_per_token(CFG) == routed
    total = 8 * proj + 6 * band_macs + 2 * full_macs + 2 * dense + \
        6 * (routed + expert) + 2048 * 50048
    assert flops.forward_macs(CFG) == pytest.approx(total, rel=1e-12)
    assert round(total / 1e5) == 7395                 # the issue's 739.5 M
    assert 0.60 < (8 * proj + 6 * band_macs + 2 * full_macs) / total < 0.61
    assert 72e12 < flops.train_flops_per_item(CFG) * 16384 < 73e12
    # parameters: the file's count is the shapes' and the toy graph's
    assert flops_trinity.parameters(CFG) == CFG["parameters"] == \
        2 * 65020160 + 6 * 235151616 + 2 * 102498304 + 2048 == 1745948672
    toy = dict(AFMOE_TINY, seq_len=8)
    mod = toy_module(toy, 1, 8, probes=())
    assert sum(v.size for v in mod.get_params()[0].values()) == \
        flops_trinity.parameters(toy)
    # whatever implements them: nothing but shapes enters the work
    work, nbytes = flops_trinity.expert_train_work(CFG, 16384)
    assert work == 6 * 3 * 2 * 16384 * routed
    assert nbytes == 6 * 2 * (3 * 32 * expert + 4 * 32768 * 2048)
    work, nbytes = flops_trinity.attention_train_work(CFG, 16384)
    assert work == 2 * 3 * 2 * 16384 * full_macs
    assert nbytes == 2 * 3 * 16384 * 2 * (32 + 4) * 128 * 2
    work, nbytes = flops_trinity.window_attention_train_work(CFG, 16384)
    assert work == 6 * 3 * 2 * 16384 * band_macs
    assert nbytes == 6 * 3 * 16384 * 2 * (32 + 4) * 128 * 2


def test_configuration_keeps_the_published_widths():
    catalog = {"global_attn_every_n_layers": 4, "head_dim": 128,
               "hidden_size": 2048, "intermediate_size": 6144,
               "load_balance_coeff": 0.001,
               "max_position_embeddings": 131072, "model_type": "afmoe",
               "moe_intermediate_size": 1024, "mup_enabled": True,
               "n_group": 1, "num_attention_heads": 32,
               "num_dense_layers": 2, "num_expert_groups": 1,
               "num_experts": 128, "num_experts_per_tok": 8,
               "num_hidden_layers": 32, "num_key_value_heads": 4,
               "num_limited_groups": 1, "num_shared_experts": 1,
               "rms_norm_eps": 1e-05, "rope_scaling": None,
               "rope_theta": 10000, "route_norm": True,
               "route_scale": 2.826, "score_func": "sigmoid",
               "sliding_window": 2048, "tie_word_embeddings": False,
               "topk_group": 1, "use_grouped_mm": True,
               "vocab_size": 200192, "hidden_act": "silu"}
    differs = sorted(k for k, v in catalog.items() if CFG[k] != v)
    assert differs == sorted(CFG["reduced"]) == \
        ["num_experts", "num_hidden_layers", "vocab_size"]
    assert CFG["published"] == {k: catalog[k] for k in differs}
    assert (CFG["num_hidden_layers"], CFG["num_experts"],
            CFG["first_expert"], CFG["vocab_size"]) == (8, 32, 0, 50048)
    assert "four chips share each layer" in CFG["deployment"]
    # the published pattern is kept whole; the model reads two periods
    types = CFG["layer_types"]
    assert len(types) == 32 and types.count("full_attention") == 8
    assert types[:4] == ["sliding_attention"] * 3 + ["full_attention"]
    assert all(types[i:i + 4] == types[:4] for i in range(0, 32, 4))
    assert flops_trinity.kinds(CFG) == ref.layer_kinds(CFG) == \
        [(t, "dense" if i < 2 else "experts")
         for i, t in enumerate(types[:8])]
    for key in ("gate_place", "rope_by_layer_kind", "four_norms",
                "embedding_scale", "window_count", "route_norm_eps",
                "expert_bias", "balancing_rule", "optimizer",
                "dtype_policy", "inputs"):
        assert key in CFG["assumed"], key
    assert len(CFG["layer_equations"]) == 6
    tr = load("chipbench", "traffic", "fit_lm_16k.json")
    assert tr["runner"] == "module_fit_probed"
    assert tr["seq_len"] == CFG["seq_len"] == 16384
    assert tr["sequences_per_step"] * tr["seq_len"] == tr["batch_per_chip"]
    entry = [c for c in BENCH["configs"] if c["name"] == "trinity_mini"][0]
    assert entry["source"] == CFG["source"]
    assert entry["reduced"] == CFG["reduced"]


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"), os.path.join(
            REPO, "chipbench", "layer_metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_window_attention_readers():
    ms, share = reader("window_attention_ms.fit"), \
        reader("window_attention_roofline_pct.fit")
    ctx = {"cfg": CFG, "trace": None, "facts": {"batch_per_chip": 16384}}
    assert ms.read(ctx) is None and share.read(ctx) is None
    # a program without the scope (the parent): a trace, nothing to read
    ctx["trace"] = {"span_counts": {"fit_step": 3}}
    assert ms.read(ctx) is None and share.read(ctx) is None
    ctx.update(peaks=peaks, device_kind="TPU v5 lite",
               facts={"batch_per_chip": 16384,
                      "kernel_s": {"window_attention": {"seconds": 1.5}}})
    assert ms.read(ctx) == pytest.approx(500.0)
    need, nbytes = flops_trinity.window_attention_train_work(CFG, 16384)
    assert need / 197e12 > nbytes / 819e9           # compute-bound
    assert share.read(ctx) == pytest.approx(100 * 3 * need / 197e12 / 1.5)
    assert 0 < share.read(ctx) < 100
    # a configuration that names no work function: nothing to read
    ctx["cfg"] = {k: v for k, v in CFG.items() if not k.endswith("_work")}
    assert share.read(ctx) is None


def test_benchmark_json_has_the_cell_and_its_two_metrics():
    """Only what this configuration owns: later cells and metrics may be
    appended to ``BENCHMARK.json`` without an edit here."""
    cell = [w for w in BENCH["workloads"] if w["config"] == "trinity_mini"]
    assert "trinity_mini.fit" in [w["name"] for w in cell]
    fit = [w for w in cell if w["name"] == "trinity_mini.fit"][0]
    assert fit["chips"] == 1 and fit["traffic"] == "fit_lm_16k"
    config = [c for c in BENCH["configs"] if c["name"] == "trinity_mini"][0]
    for why in (fit["why"], config["why"]):     # the driver's rule of form
        assert 1 <= len(why) <= 200 and why.isprintable()
    mine = {m["name"]: m for m in BENCH["per_layer"]
            if "trinity_mini.fit" in m.get("workloads", [])}
    assert {"dispatch_ms.fit", "step_device_ms.fit", "busy_mfu_pct.fit",
            "device_idle_pct.fit", "step_prepare_ms.fit",
            "step_enqueue_ms.fit", "metric_host_ms.fit"} <= set(mine)
    for name in ("window_attention_ms.fit",
                 "window_attention_roofline_pct.fit"):
        m = mine[name]
        assert (m["source"], m["layer"], m["moves"]) == (
            "device_trace", "Attention kernel", "train_items_s_per_chip")
    rate = [m for m in BENCH["end_to_end"]
            if m["name"] == "train_items_s_per_chip"][0]
    assert "trinity_mini.fit" in rate["workloads"]


TOY_TRAFFIC = dict(seq_len=24, sequences_per_step=2, batch_per_chip=48,
                   pool_batches=2, warmup_batches=2, trace_after_s=0.0,
                   trace_s=0.2)


def toy_root(tmp_path):
    root = tmp_path / "root"
    (root / "chipbench" / "traffic").mkdir(parents=True)
    (root / "chipbench" / "configs").mkdir()
    shutil.copytree(os.path.join(REPO, "chipbench", "layer_metrics"),
                    root / "chipbench" / "layer_metrics")
    cfg = dict(CFG, **AFMOE_TINY)
    cfg["seq_len"] = TOY_TRAFFIC["seq_len"]
    cfg["check"] = dict(
        CFG["check"],
        gradient_subset=[["lm_head_weight", "final_norm_gamma", "layer7_*"],
                         ["embed_weight", "layer2_gate_weight",
                          "layer2_experts_w2_weight", "layer3_q_weight",
                          "layer[0-6]_post_mlp_norm_gamma",
                          "layer2_shared_w2_weight",
                          "layer1_mlp_w2_weight"]])
    tr = dict(load("chipbench", "traffic", "fit_lm_16k.json"), **TOY_TRAFFIC)
    (root / "chipbench" / "configs" / "toy.json").write_text(json.dumps(cfg))
    (root / "chipbench" / "traffic" / "fit_lm_16k.json").write_text(
        json.dumps(tr))
    layers = [m["name"] for m in BENCH["per_layer"]
              if "trinity_mini.fit" in m.get("workloads", [])]
    bench = {
        "configs": [{"name": "toy", "file": "chipbench/configs/toy.json"}],
        "workloads": [{"name": "toy.cell", "config": "toy",
                       "traffic": "fit_lm_16k", "chips": 1}],
        "end_to_end": [{"name": n, "unit": "x"}
                       for n in ("train_items_s_per_chip", "setup_s")],
        "per_layer": [{"name": n, "unit": "x"} for n in layers]}
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root)


def test_the_cell_rehearsed_through_module_fit_probed(tmp_path, capsys):
    from mxnet_tpu import telemetry
    root = toy_root(tmp_path)
    before = {k: telemetry.counter(k)
              for k in ("module_train_step", "fit_step_overlapped")}
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
    # dropless: every expert layer routed tokens x k rows over all eight
    # experts, whichever are held here
    assert window["routed_rows_a_step"] == 48 * 3 * 6
    assert set(lines["routing"]) == {"layer%d_choice" % i
                                     for i in range(2, 8)}
    for layer in lines["routing"].values():
        assert layer["rows"] == 144 and layer["mean"] == 18.0
        assert layer["agree"] == 1.0         # float32 against float32
    counters = window["counters"]
    assert counters["executor_remat_segments"] >= 8
    assert counters["fit_step_overlapped"] > 0
    assert counters["sparse_moe_held_rows_budget"] > 0
    # overlapped / steps = (batches - 1) / batches in each fit; the
    # runner's own first step is one more, and this process ran two cells
    steps, overlapped = (counters[k] - before[k] for k in (
        "module_train_step", "fit_step_overlapped"))
    assert steps - overlapped == 2 * 2
    assert window["traced_in_first_step"] == {
        "sparse_moe_traced": 6, "causal_attention_traced": 2,
        "window_attention_traced": 6}


def test_the_parent_would_fail_cleanly():
    """A program without the model (the parent commit) leaves the runner
    with SystemExit at once, not a hang or a traceback mid-run."""
    from chipbench.runners import module_fit_probed
    cfg = dict(CFG, symbol="mxnet_tpu.models.no_such_model:symbol")
    with pytest.raises(SystemExit, match="cannot run configuration"):
        module_fit_probed.probed_symbol(cfg)
