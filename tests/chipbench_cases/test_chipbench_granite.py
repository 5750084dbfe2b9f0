"""The ``granite_4_0_h_micro`` configuration's benchmark pieces on the CPU
at toy size: the plain reference against the system (loss, probes, every
gradient), the reference's dual form against the token-by-token
recurrence, the FLOP and byte functions against hand counts, the
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
from chipbench import flops, flops_granite, harness, peaks, traffic_lm
from chipbench import run as bench_run
from chipbench.reference import granite_4_0_h_micro as ref
from mxnet_tpu.io import DataBatch, DataDesc
from mxnet_tpu.models.granite import GRANITE_TINY, granite_hybrid_symbol

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
PROBES = tuple("layer%d_%s" % (i, k) for i in (0, 5, 9)
               for k in ("op", "ffn"))
CELL = "granite_4_0_h_micro.fit"


def load(*parts):
    with open(os.path.join(REPO, *parts)) as f:
        return json.load(f)


CFG = load("chipbench", "configs", "granite_4_0_h_micro.json")
BENCH = load("BENCHMARK.json")


def toy_module(cfg, batch, seq, seed=3, probes=PROBES):
    mod = mx.mod.Module(granite_hybrid_symbol(cfg, probes=probes),
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
    cfg = dict(GRANITE_TINY)
    x, y = tokens(cfg, 2, 40)
    mod = toy_module(cfg, 2, 40)
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
    # the scan's three parameters, the convolution's bias and the gated
    # norm's gain are among the trained tensors the reference was
    # differentiated by, and the embedding is the head too
    for name in ("layer2_a_log", "layer2_dt_bias", "layer2_d",
                 "layer2_conv_bias", "layer2_mixer_norm_gamma",
                 "layer5_q_weight", "layer9_mlp_input_weight",
                 "embed_weight"):
        assert name in grads, name
    assert "lm_head_weight" not in grads
    for name, per_dev in zip(group.param_names, group.grad_arrays):
        got, want = per_dev[0].asnumpy(), np.asarray(grads[name])
        assert np.linalg.norm(got - want) <= 1e-4 * np.linalg.norm(want), \
            name


def test_reference_asserts_it_consumed_every_tensor():
    cfg = dict(GRANITE_TINY)
    mod = toy_module(cfg, 1, 12, probes=())
    params = tensors(mod)
    x, y = tokens(cfg, 1, 12)
    assert ref.loss(cfg, params, jnp.asarray(x), jnp.asarray(y),
                    "float32")[1] is None
    params["layer0_extra_weight"] = jnp.zeros((1,))
    with pytest.raises(AssertionError, match="never asked for"):
        ref.loss(cfg, params, jnp.asarray(x), jnp.asarray(y), "float32")


def test_reference_imports_nothing_of_the_system():
    with open(os.path.join(REPO, "chipbench", "reference",
                           "granite_4_0_h_micro.py")) as f:
        text = f.read()
    assert "import mxnet_tpu" not in text and "from mxnet_tpu" not in text


def test_reference_dual_form_is_the_recurrence():
    """The reference's masked form against H_t = exp(a_t) H_{t-1} + dt_t
    X_t B_t^T, y_t = H_t C_t + D X_t written out token by token, for two
    groups of B and C and decays from near 0 to near 1."""
    rng = np.random.RandomState(4)
    s, heads, p, groups, n = 37, 4, 8, 2, 16
    x = rng.randn(s, heads, p)
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(2.0), (s, heads)))
    a_log = np.log(rng.uniform(1, 16, heads))
    b, c = rng.randn(s, groups, n), rng.randn(s, groups, n)
    d = rng.randn(heads)
    state = np.zeros((heads, p, n))
    want = np.zeros((s, heads, p))
    for t in range(s):
        for h in range(heads):
            g = h // (heads // groups)
            state[h] = np.exp(-np.exp(a_log[h]) * dt[t, h]) * state[h] + \
                dt[t, h] * np.outer(x[t, h], b[t, g])
            want[t, h] = state[h] @ c[t, g] + d[h] * x[t, h]
    with jax.default_matmul_precision("highest"):
        got = ref.dual_scan(*(jnp.asarray(v, jnp.float32)
                              for v in (x, dt, a_log, b, c, d)))
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-5)


def test_flop_and_byte_functions_against_hand_counts():
    proj = 2048 * (4096 + 4352 + 64) + 4096 * 2048
    assert flops_granite.mamba_projection_macs_per_token(CFG) == proj \
        == 25821184
    assert flops_granite.conv_macs_per_token(CFG) == 4352 * 4
    # inside a chunk of 256 a token meets 128.5 tokens on average, in
    # C . B (128) and in the heads' own product (4,096); the state's
    # advance and read are 128 x 64 x 64 heads each
    scan = 257 / 2 * (128 + 4096) + 2 * 128 * 64 * 64
    assert flops_granite.scan_macs_per_token(CFG) == scan == 1591360
    mlp = 3 * 2048 * 8192
    mamba = proj + 4352 * 4 + scan + mlp
    assert mamba == 77761600
    pairs = 16384 * 16385 // 2
    assert pairs == sum(t + 1 for t in range(16384))
    core = 2 * 2048 * pairs / 16384
    assert flops_granite.attention_core_macs_per_token(CFG) == core \
        == 33556480
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512 + core + mlp
    assert attn == 94373888
    head = 2048 * 100352
    total = 9 * mamba + attn + head
    assert flops.forward_macs(CFG) == pytest.approx(total, rel=1e-12)
    assert round(total / 1e5) == 9997                 # the issue's 999.7 M
    assert 98e12 < flops.train_flops_per_item(CFG) * 16384 < 98.5e12
    assert 0.20 < head / total < 0.21 and 0.013 < 9 * scan / total < 0.015
    # parameters: the file's count is the shapes' and the toy graph's
    mamba_layer = 17432576 + 4352 * 5 + 192 + 4096 + 8388608 + \
        50331648 + 4096
    assert mamba_layer == 76182976
    attn_layer = 2 * 4194304 + 2 * 1048576 + 50331648 + 4096
    assert attn_layer == 60821504
    assert flops_granite.parameters(CFG) == CFG["parameters"] == \
        9 * mamba_layer + attn_layer + 205520896 + 2048 == 951991232
    assert flops_granite.parameters(dict(CFG, num_hidden_layers=40)) == \
        36 * mamba_layer + 4 * attn_layer + 205520896 + 2048 == 3191396096
    toy = dict(GRANITE_TINY, seq_len=8)
    mod = toy_module(toy, 1, 8, probes=())
    assert sum(v.size for v in mod.get_params()[0].values()) == \
        flops_granite.parameters(toy)
    # whatever implements them: nothing but shapes enters the work, and
    # at these shapes the bytes are the floor
    work, nbytes = flops_granite.state_space_train_work(CFG, 16384)
    assert work == 9 * 3 * 2 * 16384 * scan
    operand = (4096 + 256) * 2 + 64 * 4
    assert nbytes == 9 * 16384 * (3 * operand + 3 * 8192) == 7587495936
    assert nbytes / 819e9 > work / 197e12


def test_configuration_keeps_the_published_widths():
    rows = os.path.join("/opt/skills/guides/model-configs",
                        "architectures.jsonl")
    catalog = None
    if os.path.exists(rows):
        with open(rows) as f:
            catalog = [json.loads(line) for line in f
                       if '"granite-4.0-h-micro"' in line][0]["config"]
    published = {
        "attention_bias": False, "attention_multiplier": 0.015625,
        "embedding_multiplier": 12, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 8192,
        "logits_scaling": 8, "mamba_chunk_size": 256,
        "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 64,
        "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1,
        "mamba_n_heads": 64, "mamba_proj_bias": False,
        "max_position_embeddings": 131072,
        "model_type": "granitemoehybrid",
        "normalization_function": "rmsnorm", "num_attention_heads": 32,
        "num_experts_per_tok": 0, "num_hidden_layers": 40,
        "num_key_value_heads": 8, "num_local_experts": 0,
        "position_embedding_type": "nope", "residual_multiplier": 0.22,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "shared_intermediate_size": 8192, "tie_word_embeddings": True,
        "vocab_size": 100352,
        "layer_types": (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4}
    if catalog is not None:
        assert catalog == published
    differs = sorted(k for k, v in published.items() if CFG[k] != v)
    assert differs == CFG["reduced"] == ["num_hidden_layers"]
    assert CFG["published"] == {"num_hidden_layers": 40}
    assert CFG["num_hidden_layers"] == 10
    assert "four pipeline stages of ten whole layers" in CFG["deployment"]
    assert "nothing is divided inside a layer" in CFG["deployment"]
    # the published pattern is kept whole; the model reads one period
    types = CFG["layer_types"]
    assert len(types) == 40 and types.count("attention") == 4
    assert [i for i, t in enumerate(types) if t == "attention"] == \
        [5, 15, 25, 35]
    assert flops_granite.kinds(CFG) == ref.layer_kinds(CFG) == types[:10]
    for key in ("in_proj_order", "conv_activation", "dt_softplus",
                "d_skip", "gated_norm", "attention_head_dim", "mlp_halves",
                "multipliers", "logits_division", "no_experts",
                "scan_initial_range", "scan_parameter_dtype", "optimizer",
                "dtype_policy", "no_master_weights", "inputs"):
        assert key in CFG["assumed"], key
    assert "is not used" in CFG["assumed"]["scan_initial_range"]
    assert len(CFG["layer_equations"]) == 6
    tr = load("chipbench", "traffic", "fit_lm_16k.json")
    assert tr["runner"] == "module_fit_probed"
    assert tr["seq_len"] == CFG["seq_len"] == 16384
    assert tr["sequences_per_step"] * tr["seq_len"] == tr["batch_per_chip"]
    entry = [c for c in BENCH["configs"]
             if c["name"] == "granite_4_0_h_micro"][0]
    assert entry["source"] == CFG["source"]
    assert entry["reduced"] == CFG["reduced"]
    assert set(CFG["check"]["must_pass"]) >= {
        "loss", "probe:layer0_op", "probe:layer5_op", "probe:layer9_op",
        "grad:embed_weight", "grad:layer2_in_proj_weight",
        "grad:layer2_conv_weight", "grad:layer2_a_log",
        "grad:layer2_dt_bias"}
    assert "choice_probes" not in CFG["check"]


def test_seeded_scan_parameters_carry_state_across_chunks():
    """At the seeded init a good part of the heads still holds, 256 tokens
    on, more than a thousandth of what a token wrote: the carried state is
    exercised (the family initialiser's would hold none)."""
    mx.random.seed(11)
    a_log = mx.nd.zeros((64,))
    dt_bias = mx.nd.zeros((64,))
    mx.initializer.StateSpaceInit("a_log", 1.0, 16.0)._init_weight(
        "a_log", a_log)
    mx.initializer.StateSpaceInit("dt_bias", 0.001, 0.1)._init_weight(
        "dt_bias", dt_bias)
    a, bias = a_log.asnumpy(), dt_bias.asnumpy()
    assert 0.0 <= a.min() and a.max() <= np.log(16.0)
    dt = np.log1p(np.exp(bias))             # a zero input to the softplus
    assert 0.001 <= dt.min() * 1.0001 and dt.max() <= 0.1 * 1.0001
    step = np.exp(-np.exp(a) * dt)
    assert 0.2 < step.min() and step.max() < 0.9999
    assert np.mean(step ** 256 > 1e-3) > 0.2
    family = np.exp(-np.arange(1, 65) * np.log1p(np.e))
    assert np.mean(family ** 256 > 1e-3) == 0.0


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"), os.path.join(
            REPO, "chipbench", "layer_metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_state_space_readers():
    ms, share = reader("state_space_ms.fit"), \
        reader("state_space_roofline_pct.fit")
    ctx = {"cfg": CFG, "trace": None, "facts": {"batch_per_chip": 16384}}
    assert ms.read(ctx) is None and share.read(ctx) is None
    # a program without the scope (the parent): a trace, nothing to read
    ctx["trace"] = {"span_counts": {"fit_step": 3}}
    assert ms.read(ctx) is None and share.read(ctx) is None
    ctx.update(peaks=peaks, device_kind="TPU v5 lite",
               facts={"batch_per_chip": 16384,
                      "kernel_s": {"state_space": {"seconds": 0.15}}})
    assert ms.read(ctx) == pytest.approx(50.0)
    need, nbytes = flops_granite.state_space_train_work(CFG, 16384)
    assert nbytes / 819e9 > need / 197e12           # HBM-bound
    assert share.read(ctx) == pytest.approx(100 * 3 * nbytes / 819e9 / 0.15)
    assert 0 < share.read(ctx) < 100
    # a configuration that names no work function: nothing to read
    ctx["cfg"] = {k: v for k, v in CFG.items() if not k.endswith("_work")}
    assert share.read(ctx) is None


def test_benchmark_json_has_the_cell():
    """Only what this configuration owns: later cells and metrics may be
    appended to ``BENCHMARK.json`` without an edit here.  The two
    state-space readers have no entry yet (PERF.md section 7): a PR that
    changes the program may only append to ``per_layer``, and
    ``test_chipbench_setup_metrics.py`` holds the five ``.setup`` entries
    to be its last."""
    cell = [w for w in BENCH["workloads"]
            if w["config"] == "granite_4_0_h_micro"]
    assert CELL in [w["name"] for w in cell]
    fit = [w for w in cell if w["name"] == CELL][0]
    assert fit["chips"] == 1 and fit["traffic"] == "fit_lm_16k"
    config = [c for c in BENCH["configs"]
              if c["name"] == "granite_4_0_h_micro"][0]
    for why in (fit["why"], config["why"]):     # the driver's rule of form
        assert 1 <= len(why) <= 200 and why.isprintable()
    mine = {m["name"]: m for m in BENCH["per_layer"]
            if CELL in m.get("workloads", [])}
    assert {"dispatch_ms.fit", "step_device_ms.fit", "busy_mfu_pct.fit",
            "device_idle_pct.fit", "step_prepare_ms.fit",
            "step_enqueue_ms.fit", "metric_host_ms.fit"} <= set(mine)
    for name, better in (("state_space_ms.fit", "lower"),
                         ("state_space_roofline_pct.fit", "higher")):
        assert os.path.exists(os.path.join(
            REPO, "chipbench", "layer_metrics", name + ".py"))
        for m in BENCH["per_layer"]:        # once a benchmark PR lists them
            if m["name"] == name:
                assert (m["source"], m["layer"], m["moves"], m["better"],
                        m["workloads"][0]) == (
                    "device_trace", "State-space kernel",
                    "train_items_s_per_chip", better, CELL)
    # no other cell's kernel metric and no set-up metric lists the cell
    assert not any(n.startswith(("attention_", "moe_", "retention_",
                                 "window_attention_")) or
                   n.endswith(".setup") for n in mine)
    rate = [m for m in BENCH["end_to_end"]
            if m["name"] == "train_items_s_per_chip"][0]
    assert CELL in rate["workloads"]


TOY_TRAFFIC = dict(seq_len=40, sequences_per_step=2, batch_per_chip=80,
                   pool_batches=2, warmup_batches=2, trace_after_s=0.0,
                   trace_s=0.2)


def toy_root(tmp_path):
    root = tmp_path / "root"
    (root / "chipbench" / "traffic").mkdir(parents=True)
    (root / "chipbench" / "configs").mkdir()
    shutil.copytree(os.path.join(REPO, "chipbench", "layer_metrics"),
                    root / "chipbench" / "layer_metrics")
    cfg = dict(CFG, **GRANITE_TINY)
    cfg["seq_len"] = TOY_TRAFFIC["seq_len"]
    tr = dict(load("chipbench", "traffic", "fit_lm_16k.json"), **TOY_TRAFFIC)
    (root / "chipbench" / "configs" / "toy.json").write_text(json.dumps(cfg))
    (root / "chipbench" / "traffic" / "fit_lm_16k.json").write_text(
        json.dumps(tr))
    layers = [m["name"] for m in BENCH["per_layer"]
              if CELL in m.get("workloads", [])]
    layers += [n for n in ("state_space_ms.fit",
                           "state_space_roofline_pct.fit")
               if n not in layers]          # unlisted yet; read all the same
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
              for k in ("module_train_step", "fit_step_overlapped",
                        "state_space_traced", "state_space_chunks")}
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
    assert lines["routing"] == {}               # no expert layer
    window = lines["window"]
    assert all(window["held"].values()), window
    assert window["batch"] == 80 and window["routed_rows_a_step"] == 0
    counters = window["counters"]
    assert counters["executor_remat_segments"] >= 10
    assert counters["fit_step_overlapped"] > 0
    assert counters["state_space_states_traced"] > 0
    # overlapped / steps = (batches - 1) / batches in each fit; the
    # runner's own first step is one more, and this process ran two cells
    steps, overlapped, scans, chunks = (counters[k] - before[k] for k in (
        "module_train_step", "fit_step_overlapped", "state_space_traced",
        "state_space_chunks"))
    assert steps - overlapped == 2 * 2
    assert scans > 0 and scans % 9 == 0
    assert chunks == scans * 5              # 40 tokens in chunks of 8
    assert window["traced_in_first_step"] == {
        "state_space_traced": 9, "causal_conv_traced": 9,
        "causal_attention_traced": 1}


def test_the_parent_would_fail_cleanly():
    """A program without the model (the parent commit) leaves the runner
    with SystemExit at once, not a hang or a traceback mid-run."""
    from chipbench.runners import module_fit_probed
    cfg = dict(CFG, symbol="mxnet_tpu.models.no_such_model:symbol")
    with pytest.raises(SystemExit, match="cannot run configuration"):
        module_fit_probed.probed_symbol(cfg)
