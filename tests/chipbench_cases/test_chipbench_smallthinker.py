"""The ``smallthinker_21ba3b`` configuration's benchmark pieces on the CPU at
toy size: the plain reference against the system (loss, probes, choices,
every gradient, the router's included), all experts held and a held
share, a reference whose router reads the wrong tensor failing the same
comparison, the shares against the uncut layer, the FLOP and byte
functions against hand counts, the configuration file against the
catalog's published keys, the new reader, and a rehearsal of the cell
through ``module_fit_probed``.  No number here is a device metric."""
import importlib
import importlib.util
import json
import os
import re
import shutil
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from chipbench import flops, flops_smallthinker, harness, peaks, traffic_lm
from chipbench import run as bench_run
from chipbench.reference import smallthinker_21ba3b as ref
from mxnet_tpu.io import DataBatch, DataDesc
from mxnet_tpu.models.smallthinker import (SMALLTHINKER_TINY,
                                           SMALLTHINKER_TINY_SHARE,
                                           smallthinker_symbol)

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
CELL = "smallthinker_21ba3b.fit"
PROBES = tuple("layer%d_%s" % (i, k) for i in (0, 1, 7)
               for k in ("op", "ffn")) + ("layer1_choice", "layer4_choice")


def load(*parts):
    with open(os.path.join(REPO, *parts)) as f:
        return json.load(f)


CFG = load("chipbench", "configs", "smallthinker_21ba3b.json")
BENCH = load("BENCHMARK.json")


def toy_module(cfg, batch, seq, seed=3, probes=PROBES):
    mod = mx.mod.Module(smallthinker_symbol(cfg, probes=probes),
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


def module_and_reference(cfg, seq=21):
    """(the module's outputs, its gradients by name, the float32
    reference's loss, probes and gradients) at toy size."""
    x, y = tokens(cfg, 2, seq)
    mod = toy_module(cfg, 2, seq)
    mod.forward_backward(DataBatch([mx.nd.array(x)], [mx.nd.array(y)]))
    outs = [o.asnumpy() for o in mod.get_outputs()]
    group = mod._exec_group
    grads = {n: g[0].asnumpy()
             for n, g in zip(group.param_names, group.grad_arrays)}
    params = tensors(mod)
    with jax.default_matmul_precision("highest"):
        (loss, seen), want = jax.value_and_grad(
            lambda p: ref.loss(cfg, dict(params, **p), jnp.asarray(x),
                               jnp.asarray(y), "float32", PROBES),
            has_aux=True)({k: params[k] for k in group.param_names})
    return outs, grads, float(loss), seen, want


@pytest.mark.parametrize("cfg", [SMALLTHINKER_TINY, SMALLTHINKER_TINY_SHARE],
                         ids=["all_held", "held_share"])
def test_reference_equals_the_module_in_float32(cfg):
    outs, grads, loss, seen, want = module_and_reference(dict(cfg))
    assert outs[0][0] == pytest.approx(loss, rel=1e-5)
    for name, got in zip(PROBES, outs[1:]):
        np.testing.assert_allclose(got, np.asarray(seen[name], np.float32),
                                   rtol=1e-4, atol=5e-5, err_msg=name)
    assert set(grads) == set(want)
    # the early router's gradient reaches the attention's input norm
    for name in ("layer1_router_weight", "layer1_input_norm_gamma",
                 "layer1_post_attention_norm_gamma", "layer1_experts_w2_weight",
                 "lm_head_weight", "embed_weight"):
        assert np.abs(grads[name]).max() > 0, name
    for name, got in grads.items():
        w = np.asarray(want[name])
        assert np.linalg.norm(got - w) <= 1e-4 * np.linalg.norm(w), name


def test_a_router_after_attention_fails_the_comparison(monkeypatch):
    """The same comparison against a reference whose router reads the
    experts' input h (the post-attention norm) instead of a: the loss, the
    experts' output and the router's gradient part ways, so the
    comparison sees where the router sits."""
    cfg = dict(SMALLTHINKER_TINY)
    experts = ref.experts
    monkeypatch.setattr(ref, "experts", lambda cfg, w, r, h, dtype, first=0:
                        experts(cfg, w, h, h, dtype, first))
    outs, grads, loss, seen, want = module_and_reference(cfg)
    assert abs(outs[0][0] - loss) > 1e-3
    assert np.abs(outs[2] - np.asarray(seen["layer0_ffn"])).max() > 1e-2
    router = np.asarray(want["layer1_router_weight"])
    assert np.linalg.norm(grads["layer1_router_weight"] - router) > \
        0.1 * np.linalg.norm(router)


def test_reference_asserts_it_consumed_every_tensor():
    cfg = dict(SMALLTHINKER_TINY)
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
                           "smallthinker_21ba3b.py")) as f:
        text = f.read()
    assert "import mxnet_tpu" not in text and "from mxnet_tpu" not in text


def test_four_shares_of_two_experts_add_up_to_the_uncut_layer():
    """Four shares of 2 of 8 experts, each computed by the reference from
    its own slice of the stacks, add up to the uncut layer; the router is
    whole in every share and chooses alike."""
    cfg = dict(SMALLTHINKER_TINY)
    rng = np.random.RandomState(5)
    r, h = (jnp.asarray(rng.randn(19, 32), jnp.float32) for _ in range(2))
    w = {"router_weight": jnp.asarray(rng.randn(8, 32), jnp.float32)}
    for name, shape in (("w1", (32, 16)), ("w3", (32, 16)), ("w2", (16, 32))):
        w["experts_%s_weight" % name] = jnp.asarray(
            rng.randn(8, *shape) * .2, jnp.float32)
    whole, idx = ref.experts(cfg, w, r, h, jnp.float32)
    total = jnp.zeros_like(whole)
    for first in range(0, 8, 2):
        part = dict(w, **{k: w[k][first:first + 2] for k in w
                          if k.startswith("experts_")})
        share, same = ref.experts(cfg, part, r, h, jnp.float32, first=first)
        assert np.array_equal(np.asarray(same), np.asarray(idx))
        assert float(jnp.abs(share).max()) > 0
        total = total + share
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=1e-5, atol=1e-6)
    # the weights are a softmax over the chosen logits: they sum to 1
    _, weight = ref.route(cfg, w, r)
    np.testing.assert_allclose(np.asarray(weight.sum(-1)), 1.0, rtol=1e-6)


def test_flop_and_byte_functions_against_hand_counts():
    proj = 2 * 2560 * 3584 + 2 * 2560 * 512
    assert flops_smallthinker.attention_projection_macs_per_token(CFG) == \
        proj == 20971520
    full = 16384 * 16385 // 2
    band = 4096 * 4097 // 2 + (16384 - 4096) * 4096
    assert flops_smallthinker.visible_keys(CFG, False) == full
    assert flops_smallthinker.visible_keys(CFG, True) == band == 58722304
    assert band == sum(min(t + 1, 4096) for t in range(16384))
    full_macs, band_macs = (2 * 3584 * n / 16384 for n in (full, band))
    expert = 3 * 2560 * 768
    routed = 2560 * 64 + 6 * expert
    assert flops_smallthinker.routed_macs_per_token(CFG) == routed
    head = 2560 * 37984
    total = 4 * proj + full_macs + 3 * band_macs + 4 * routed + head
    assert flops.forward_macs(CFG) == pytest.approx(total, rel=1e-12)
    # the shares of the forward: experts 31%, attention's cores
    # 29.6%, projections 18.3%, head 21.2%
    assert round(4 * routed / total, 3) == 0.310
    assert round((full_macs + 3 * band_macs) / total, 3) == 0.296
    assert round(4 * proj / total, 3) == 0.183
    assert round(head / total, 3) == 0.212
    step = flops.train_flops_per_item(CFG) * 16384
    assert round(step / 1e12, 2) == 45.13
    assert round(step / 197e12 * 1e3) == 229
    # parameters: the file's count is the shapes' and the toy graph's
    layer = 2 * 2560 + proj + 2560 * 64 + 64 * expert
    assert layer == 398627840
    assert flops_smallthinker.parameters(CFG) == CFG["parameters"] == \
        4 * layer + 2 * 37984 * 2560 + 2560 == 1788992000
    for toy in (SMALLTHINKER_TINY, SMALLTHINKER_TINY_SHARE):
        mod = toy_module(dict(toy), 1, 8, probes=())
        assert sum(v.size for v in mod.get_params()[0].values()) == \
            flops_smallthinker.parameters(toy)
    # whatever implements them: nothing but shapes enters the work
    work, nbytes = flops_smallthinker.expert_train_work(CFG, 16384)
    assert work == 4 * 3 * 2 * 16384 * routed
    assert nbytes == 4 * 2 * (3 * 64 * expert + 4 * 98304 * 2560)
    assert round(work / 1e12, 2) == 13.98
    assert round(work / 197e12 * 1e3, 1) == 71.0      # compute-bound:
    assert round(nbytes / 819e9 * 1e3, 1) == 20.9     # against the bytes
    work, nbytes = flops_smallthinker.attention_train_work(CFG, 16384)
    assert work == 3 * 2 * 16384 * full_macs
    assert nbytes == 3 * 16384 * 2 * (28 + 4) * 128 * 2
    assert round(work / 197e12 * 1e3, 1) == 29.3
    work, nbytes = flops_smallthinker.window_attention_train_work(CFG, 16384)
    assert work == 3 * 3 * 2 * 16384 * band_macs
    assert round(work / 197e12 * 1e3, 1) == 38.5


CATALOG_CONFIG = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 28, "num_hidden_layers": 52,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_layout": [0, 1, 1, 1] * 13, "rope_scaling": None,
    "rope_theta": 1500000, "sliding_window_layout": [0, 1, 1, 1] * 13,
    "sliding_window_size": 4096, "tie_word_embeddings": False,
    "vocab_size": 151936}


def test_configuration_keeps_the_published_widths():
    differs = sorted(k for k, v in CATALOG_CONFIG.items() if CFG[k] != v)
    assert differs == sorted(CFG["reduced"]) == \
        ["num_hidden_layers", "vocab_size"]
    assert CFG["published"] == {k: CATALOG_CONFIG[k] for k in differs}
    assert (CFG["num_hidden_layers"], CFG["vocab_size"]) == (4, 37984)
    assert 8 * CFG["vocab_size"] >= 151936          # at least an eighth
    # the runner's names for the experts agree with the published ones
    assert (CFG["num_experts"], CFG["num_experts_per_tok"]) == (
        CFG["moe_num_primary_experts"],
        CFG["moe_num_active_primary_experts"]) == (64, 6)
    assert "held_experts" not in CFG                 # every expert is held
    assert "thirteen pipeline stages" in CFG["deployment"]
    assert ref.layer_kinds(CFG) == [(False, False)] + [(True, True)] * 3
    for key in ("router_input", "reglu", "qk_norm", "window_count",
                "optimizer", "no_master_weights", "dtype_policy", "inputs",
                "vocabulary"):
        assert key in CFG["assumed"], key
    assert "un-normed residual" in CFG["assumed"]["router_input"]
    assert len(CFG["layer_equations"]) == 5
    chk = CFG["check"]
    assert chk["routed_rows_a_step"] == 16384 * 6 * 4 == 393216
    assert chk["traced_in_first_step"] == {
        "sparse_moe_traced": 4, "causal_attention_traced": 1,
        "window_attention_traced": 3, "sparse_moe_router_apart_traced": 4,
        "sparse_moe_reglu_traced": 4}
    assert set(chk["traced_in_first_step"]) == set(chk["traced_counters"])
    assert set(chk["traced_counters"]) <= set(chk["counters"])
    for name in ("grad:layer1_router_weight", "grad:layer1_input_norm_gamma",
                 "probe:layer0_op", "probe:layer1_op", "probe:layer1_ffn"):
        assert name in chk["must_pass"], name
    tr = load("chipbench", "traffic", "fit_lm_16k.json")
    assert tr["runner"] == "module_fit_probed"
    assert tr["seq_len"] == CFG["seq_len"] == 16384
    entry = [c for c in BENCH["configs"]
             if c["name"] == "smallthinker_21ba3b"][0]
    assert entry["source"] == CFG["source"]
    assert entry["reduced"] == CFG["reduced"]


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"), os.path.join(
            REPO, "chipbench", "layer_metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_the_route_reader_and_the_kernel_shares():
    route = reader("moe_route_ms.fit")
    ctx = {"cfg": CFG, "trace": None, "facts": {"batch_per_chip": 16384}}
    assert route.read(ctx) is None
    # a program without the scope (the parent): a trace, nothing to read
    ctx["trace"] = {"span_counts": {"fit_step": 4}}
    assert route.read(ctx) is None
    kernel_s = {"moe_route": {"seconds": 0.08}, "moe": {"seconds": 1.2},
                "attention": {"seconds": 0.4},
                "window_attention": {"seconds": 0.5}}
    ctx.update(peaks=peaks, device_kind="TPU v5 lite",
               facts={"batch_per_chip": 16384, "kernel_s": kernel_s})
    assert route.read(ctx) == pytest.approx(20.0)
    for kernel, work in (("moe", "expert_train_work"),
                         ("attention", "attention_train_work"),
                         ("window_attention",
                          "window_attention_train_work")):
        share = reader(kernel + "_roofline_pct.fit").read(ctx)
        need, nbytes = getattr(flops_smallthinker, work)(CFG, 16384)
        assert need / 197e12 > nbytes / 819e9       # compute-bound
        assert share == pytest.approx(
            100 * 4 * need / 197e12 / kernel_s[kernel]["seconds"])
    # the patterns name a scope inside the path and nothing the line holds
    rx = re.compile(CFG["trace_patterns"]["moe_route"])
    assert rx.search("%fusion.3 = f32[16384,64] fusion(%p) "
                     "jit(step)/remat_segment_1/moe_route/dot_general")
    assert not rx.search("%fusion.4 = f32[16384,6] fusion(%moe_route.2) "
                         "jit(step)/remat_segment_1/top_k")


def cell_and_metrics_are_listed(bench):
    """Only what this configuration owns: later cells and metrics may be
    appended to ``BENCHMARK.json``, and later cells to the shared
    entries' ``workloads``, without an edit here."""
    cell = [w for w in bench["workloads"]
            if w["config"] == "smallthinker_21ba3b"]
    assert CELL in [w["name"] for w in cell]
    fit = [w for w in cell if w["name"] == CELL][0]
    assert fit["chips"] == 1 and fit["traffic"] == "fit_lm_16k"
    config = [c for c in bench["configs"]
              if c["name"] == "smallthinker_21ba3b"][0]
    for why in (fit["why"], config["why"]):     # the benchmark's rule of form
        assert 1 <= len(why) <= 200 and why.isprintable()
    mine = {m["name"]: m for m in bench["per_layer"]
            if CELL in m.get("workloads", [])}
    assert {"dispatch_ms.fit", "step_device_ms.fit", "busy_mfu_pct.fit",
            "device_idle_pct.fit", "step_prepare_ms.fit",
            "step_enqueue_ms.fit", "metric_host_ms.fit", "compile_s.setup",
            "cache_miss_programs.setup", "trace_lower_s.setup",
            "trainer_setup_s.setup", "init_params_s.setup"} <= set(mine)
    for name, layer in (("moe_ms.fit", "Sparse-expert layer"),
                        ("moe_roofline_pct.fit", "Sparse-expert layer"),
                        ("moe_route_ms.fit", "Sparse-expert layer"),
                        ("attention_ms.fit", "Attention kernel"),
                        ("attention_roofline_pct.fit", "Attention kernel"),
                        ("window_attention_ms.fit", "Attention kernel"),
                        ("window_attention_roofline_pct.fit",
                         "Attention kernel")):
        m = mine[name]
        assert (m["source"], m["layer"], m["moves"]) == (
            "device_trace", layer, "train_items_s_per_chip")
    route = [m for m in bench["per_layer"] if m["name"] == "moe_route_ms.fit"]
    assert route[0]["workloads"][0] == CELL and route[0]["unit"] == "ms"
    rate = [m for m in bench["end_to_end"]
            if m["name"] == "train_items_s_per_chip"][0]
    assert CELL in rate["workloads"]


def test_benchmark_json_has_the_cell_and_its_metrics():
    cell_and_metrics_are_listed(BENCH)
    contract = importlib.import_module("test_chipbench_contract")
    later = contract.with_a_later_cell(BENCH)
    contract.keeps_the_contract(later)
    cell_and_metrics_are_listed(later)


TOY_TRAFFIC = dict(seq_len=24, sequences_per_step=2, batch_per_chip=48,
                   pool_batches=2, warmup_batches=2, trace_after_s=0.0,
                   trace_s=0.2)


def toy_root(tmp_path):
    root = tmp_path / "root"
    (root / "chipbench" / "traffic").mkdir(parents=True)
    (root / "chipbench" / "configs").mkdir()
    shutil.copytree(os.path.join(REPO, "chipbench", "layer_metrics"),
                    root / "chipbench" / "layer_metrics")
    cfg = dict(CFG, **SMALLTHINKER_TINY)
    cfg.update(seq_len=TOY_TRAFFIC["seq_len"], num_experts=8,
               num_experts_per_tok=3)
    cfg["check"] = dict(
        CFG["check"],
        choice_probes=["layer%d_choice" % i for i in range(8)],
        gradient_subset=[["lm_head_weight", "final_norm_gamma", "layer7_*"],
                         ["embed_weight", "layer1_*",
                          "layer[0-6]_input_norm_gamma"]])
    tr = dict(load("chipbench", "traffic", "fit_lm_16k.json"), **TOY_TRAFFIC)
    (root / "chipbench" / "configs" / "toy.json").write_text(json.dumps(cfg))
    (root / "chipbench" / "traffic" / "fit_lm_16k.json").write_text(
        json.dumps(tr))
    layers = [m["name"] for m in BENCH["per_layer"]
              if CELL in m.get("workloads", [])]
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
    root = toy_root(tmp_path)
    env = harness.Env(root, "toy.cell", seed=2 ** 31 + 7, seconds=0.5,
                      traced=1, t_process=time.perf_counter())
    result = bench_run.execute(env)
    out = capsys.readouterr().out
    assert result["correct"], out
    assert result["failed"] == 0 and result["attempted"] > 0
    # the CPU has no device plane: no device number is invented
    assert {"dispatch_ms.fit"} <= set(result["metrics"]) - {
        "train_items_s_per_chip", "setup_s"} <= {
        "dispatch_ms.fit", "step_prepare_ms.fit", "step_enqueue_ms.fit",
        "metric_host_ms.fit"}
    lines = {key: json.loads(ln.split(" ", 2)[2]) for ln in out.splitlines()
             if ln.startswith("chipbench: ")
             for key in [ln.split(" ", 2)[1]]}
    assert lines["check"]["must_pass_not_passing"] == []
    window = lines["window"]
    assert all(window["held"].values()), window
    # dropless: every one of the eight expert layers routed tokens x 3
    assert window["routed_rows_a_step"] == 48 * 3 * 8
    for layer in lines["routing"].values():
        assert layer["rows"] == 144 and layer["agree"] == 1.0
    assert window["traced_in_first_step"] == {
        "sparse_moe_traced": 8, "causal_attention_traced": 2,
        "window_attention_traced": 6, "sparse_moe_router_apart_traced": 8,
        "sparse_moe_reglu_traced": 8}
    assert window["counters"]["executor_remat_segments"] >= 8


def test_the_parent_would_fail_cleanly():
    """A program without the model (the parent commit) leaves the runner
    with SystemExit at once, not a hang or a traceback mid-run."""
    from chipbench.runners import module_fit_probed
    cfg = dict(CFG, symbol="mxnet_tpu.models.no_such_model:symbol")
    with pytest.raises(SystemExit, match="cannot run configuration"):
        module_fit_probed.probed_symbol(cfg)
