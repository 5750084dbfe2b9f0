"""What a recomputation segment keeps (``ops/remat.py``), on the CPU at toy
sizes: the flash forward's ``out`` and ``lse``, the routing's integers and
the retention op's output are not remade in the backward pass, every number
stays what the plain ``jax.checkpoint`` gives, and outside a segment an op
traces what it traced before."""
import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.executor import _build_graph_fn
from mxnet_tpu.io import DataBatch, DataDesc
from mxnet_tpu.models.brumby import BRUMBY_TINY, brumby_symbol
from mxnet_tpu.models.granite import GRANITE_TINY, granite_hybrid_symbol
from mxnet_tpu.models.lfm2 import LFM2_MOE_TINY, lfm2_moe_symbol
from mxnet_tpu.models.trinity import AFMOE_TINY, afmoe_symbol
from mxnet_tpu.ops import lm, moe, remat
from mxnet_tpu.ops import pallas_kernels as pk

TOYS = {"trinity": (afmoe_symbol, AFMOE_TINY),
        "lfm2": (lfm2_moe_symbol, LFM2_MOE_TINY),
        "brumby": (brumby_symbol, BRUMBY_TINY),
        "granite": (granite_hybrid_symbol, GRANITE_TINY)}


def walk(jaxpr, inside=()):
    """(equation, the primitives of the equations it lies under) for every
    equation of *jaxpr* and of the jaxprs under it."""
    for eqn in jaxpr.eqns:
        yield eqn, inside
        for value in eqn.params.values():
            inner = getattr(value, "jaxpr", value)
            if hasattr(inner, "eqns"):
                yield from walk(inner, inside + (eqn.primitive.name,))


def census(jaxpr):
    """Counts by primitive, with a Pallas call under its kernel's name;
    ``replayed`` counts the same inside the backward's ``jax.checkpoint``
    equations alone (the part of a segment that runs a second time)."""
    total, replayed = collections.Counter(), collections.Counter()
    for eqn, inside in walk(jaxpr):
        name = eqn.primitive.name
        if name == "pallas_call":
            name = eqn.params["name"]
        total[name] += 1
        if "remat2" in inside:
            replayed[name] += 1
    return total, replayed


@pytest.fixture
def kernel_path(monkeypatch):
    """``_contrib_CausalAttention`` on its kernel path, the kernels in
    interpret mode: what a TPU traces, runnable here."""
    plain = lm.causal_attention
    monkeypatch.setattr(
        lm, "causal_attention",
        lambda q, k, v, scale, causal, use_kernel, window=None:
        plain(q, k, v, scale, causal, True, window))
    for name, at in (("_flash_fwd_impl", 7), ("_flash_bwd_impl", 9)):
        impl = getattr(pk, name)

        def interpreted(*args, _impl=impl, _at=at):
            return _impl(*args[:_at], True, *args[_at + 1:])

        monkeypatch.setattr(pk, name, interpreted)


def graph_gradient(symbol, **shapes):
    """The function ``arguments -> gradients`` of a graph's training
    program and the shapes to trace it with (a toy LM's by default)."""
    fn = _build_graph_fn(symbol, True)[0]
    shapes = shapes or {"data": (2, 12), "softmax_label": (2, 12)}
    spec = [[jax.ShapeDtypeStruct(s, jnp.float32) for s in group]
            for group in symbol.infer_shape(**shapes)[::2]]
    return jax.grad(lambda args, aux: jnp.sum(fn(
        args, aux, jax.random.PRNGKey(0))[0][0])), spec


def traced(symbol, **shapes):
    """(census of the gradient's jaxpr, values kept at that trace)."""
    grad, spec = graph_gradient(symbol, **shapes)
    before = telemetry.counter("executor_remat_kept")
    jaxpr = jax.make_jaxpr(grad)(*spec).jaxpr
    return census(jaxpr), telemetry.counter("executor_remat_kept") - before


def toy(name):
    make, tiny = TOYS[name]
    return make(dict(tiny))


def attention_segment(window):
    """Projections and one attention op, all in one segment."""
    with mx.AttrScope(force_mirroring="True", mirror_stage="0"):
        x = mx.sym.Variable("data")
        q, k, v = (mx.sym.reshape(mx.sym.FullyConnected(
            x, num_hidden=heads * 8, flatten=False, no_bias=True, name=name),
            shape=(0, 0, heads, 8))
            for name, heads in (("q", 4), ("k", 2), ("v", 2)))
        out = mx.sym.contrib.CausalAttention(
            q, k, v, **({} if window is None else {"window": window}))
        return mx.sym.sum(mx.sym.square(out))


@pytest.mark.parametrize("window", [None, 5])
def test_a_segment_runs_the_flash_forward_once(kernel_path, window):
    (total, replayed), kept = traced(attention_segment(window),
                                     data=(2, 12, 16))
    assert kept == 2
    assert total["flash_attention_fwd"] == 1
    assert total["flash_attention_dq"] == total["flash_attention_dkv"] == 1
    assert "flash_attention_fwd" not in replayed
    # the parent's form, a checkpoint without the policy, runs it twice;
    # the projections are remade either way (the backward's equation
    # holds three products of the replay and six of its own)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(remat, "POLICY", None)
        (plain, plain_replayed), _ = traced(attention_segment(window),
                                            data=(2, 12, 16))
    assert plain["flash_attention_fwd"] == 2
    assert plain_replayed["flash_attention_fwd"] == 1
    assert replayed["dot_general"] == plain_replayed["dot_general"] == 9
    assert total["remat2"] == plain["remat2"] == 1


@pytest.mark.parametrize("name", ["lfm2", "trinity"])
def test_a_segment_sorts_and_chooses_once(name):
    """No ``sort`` and no ``top_k`` in what the backward replays, with
    every expert held (LFM2) and with a share (Trinity); the router's
    product and the expert products are still there."""
    (total, replayed), _ = traced(toy(name))
    layers = total["top_k"]
    assert layers >= 3 and total["sort"] == 2 * layers
    assert "sort" not in replayed and "top_k" not in replayed
    assert replayed["ragged_dot_general"] >= 3 * layers
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(remat, "POLICY", None)
        (total, replayed), _ = traced(toy(name))
    assert total["sort"] == 4 * layers and replayed["sort"] == 2 * layers
    assert replayed["top_k"] == layers


@pytest.mark.parametrize("name,kernel,kept", [
    ("trinity", True, 8 * 2 + 6 * 4), ("trinity", False, 6 * 4),
    ("lfm2", True, 2 * 2 + 3 * 4), ("lfm2", False, 3 * 4),
    ("brumby", False, BRUMBY_TINY["num_hidden_layers"]),
    ("granite", False, 9)])
def test_the_counter_reads_the_values_kept_a_trace(request, name, kernel,
                                                   kept):
    """Two an attention op on the kernel path, four a sparse-expert op,
    one a retention op, one a state-space scan."""
    if kernel:
        request.getfixturevalue("kernel_path")
    (total, _), count = traced(toy(name))
    assert count == kept and total.get("name", 0) == kept
    if kernel:
        assert total["flash_attention_fwd"] == (kept - 4 * total["top_k"]) / 2


def retention_passes(jaxpr):
    """(forwards replayed, states passes): scans and Pallas calls of the
    retention forward inside the backward's ``remat2`` equations, and the
    backward rules' own passes over the chunks that carry a state forward
    (the gradients' scan runs in reverse under a ``lax.map``)."""
    replayed = states = 0
    for eqn, inside in walk(jaxpr):
        if eqn.primitive.name not in ("scan", "pallas_call"):
            continue
        scope = str(eqn.source_info.name_stack)
        if scope.endswith("power_retention_fwd") and "remat2" in inside:
            replayed += 1
        if scope.endswith("power_retention_bwd") and (
                eqn.params.get("name") == "power_retention_bwd_states" or
                eqn.params.get("num_carry") == 2):
            states += 1
    return replayed, states


def test_a_brumby_replay_holds_no_retention_forward():
    """The op's output is kept, so the segment's replay stops before the
    retention forward; the backward rule makes the chunk states itself,
    one pass a layer.  A plain ``jax.checkpoint`` replays the forward as
    before, and makes the states the same way."""
    layers = BRUMBY_TINY["num_hidden_layers"]
    counters = ("executor_remat_kept", "power_retention_states_traced")

    def passes():
        grad, spec = graph_gradient(toy("brumby"))
        before = [telemetry.counter(name) for name in counters]
        jaxpr = jax.make_jaxpr(grad)(*spec).jaxpr
        return retention_passes(jaxpr), [
            telemetry.counter(name) - was
            for name, was in zip(counters, before)]

    assert passes() == ((0, layers), [layers, layers])
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(remat, "POLICY", None)
        assert passes() == ((layers, layers), [layers, layers])


@pytest.mark.parametrize("name", ["lfm2", "trinity"])
def test_a_graph_without_retention_is_the_parents_program(monkeypatch, name):
    """Nothing of the retention op is reached: with its scans and kernels
    made to raise, the gradient's jaxpr is letter for letter the same."""
    def text():
        grad, spec = graph_gradient(toy(name))
        return str(jax.make_jaxpr(grad)(*spec))

    before = telemetry.counter("power_retention_states_traced")
    ours = text()
    assert telemetry.counter("power_retention_states_traced") == before
    assert "power_retention" not in ours

    def unreachable(*args, **kwargs):
        raise AssertionError("the retention op in a graph without one")

    for fn in ("_retention_heads", "_retention_scan", "_retention_pallas",
               "_retention_states_scan", "_retention_states_pallas",
               "_retention_grads"):
        monkeypatch.setattr(pk, fn, unreachable)
    assert text() == ours


def test_an_image_graph_keeps_nothing():
    x = mx.sym.Variable("data")
    net = mx.sym.Convolution(x, num_filter=4, kernel=(3, 3), name="conv")
    net = mx.sym.BatchNorm(net, name="bn")
    net = mx.sym.SoftmaxOutput(mx.sym.flatten(net), name="softmax")
    before = {name: telemetry.counter(name) for name in
              ("executor_remat_kept", "executor_remat_segments")}
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=[("data", (2, 3, 8, 8))],
             label_shapes=[("softmax_label", (2,))])
    mod.init_params()
    mod.forward_backward(DataBatch([mx.nd.ones((2, 3, 8, 8))],
                                   [mx.nd.zeros((2,))]))
    for name, value in before.items():
        assert telemetry.counter(name) == value, name


def toy_module(name, recompute=True):
    """The toy bound and initialised, and a batch; Trinity's cut to its
    first period: three sliding layers and a full one, two of them with
    experts."""
    make, tiny = TOYS[name]
    cfg = dict(tiny, num_hidden_layers=min(tiny["num_hidden_layers"], 4))
    mod = mx.mod.Module(make(cfg, recompute=recompute), context=mx.cpu())
    desc = [DataDesc(name, (2, 12), dtype=np.float32)
            for name in ("data", "softmax_label")]
    mod.bind(data_shapes=desc[:1], label_shapes=desc[1:])
    mx.random.seed(11)
    mod.init_params(initializer=mx.initializer.Xavier(magnitude=6))
    ids = np.random.RandomState(1).randint(0, 50, (2, 2, 12))
    return mod, DataBatch(*([mx.nd.array(a.astype(np.float32))]
                            for a in ids))


def op_by_op(name):
    """Outputs and every gradient of the toy's training program run one
    primitive at a time: no fusion to round differently."""
    mod, batch = toy_module(name)
    mod.forward(batch, is_train=False)      # the batch into the arguments
    ex = mod._exec_group.execs[0]
    args = [a._data for a in ex.arg_arrays]
    aux = [a._data for a in ex.aux_arrays]
    with jax.disable_jit():
        outs, vjp = jax.vjp(lambda a: ex._train_fn(
            a, aux, jax.random.PRNGKey(0))[0], args)
        grads = vjp(tuple(jnp.ones_like(o) for o in outs))[0]
    return [np.asarray(x) for x in list(outs) + list(grads)]


def compiled(name, recompute=True):
    """The same through ``Module.forward_backward``: the executor's own
    compiled program."""
    mod, batch = toy_module(name, recompute)
    mod.forward_backward(batch)
    return [o.asnumpy() for o in mod.get_outputs()] + \
        [g[0].asnumpy() for g in mod._exec_group.grad_arrays]


def test_a_granite_replay_holds_no_scan_forward():
    """The scan's output is kept, so a mamba segment's replay stops before
    the scan; the backward rule makes the chunk states itself, one forward
    pass over the chunks a layer, and then visits them in reverse.  A
    plain ``jax.checkpoint`` replays the scan's forward as before."""
    counters = ("executor_remat_kept", "state_space_states_traced")

    def passes():
        grad, spec = graph_gradient(toy("granite"))
        before = [telemetry.counter(name) for name in counters]
        jaxpr = jax.make_jaxpr(grad)(*spec).jaxpr
        replayed = states = reverse = 0
        for eqn, inside in walk(jaxpr):
            if eqn.primitive.name != "scan":
                continue
            scope = str(eqn.source_info.name_stack)
            if scope.endswith("state_space_fwd") and "remat2" in inside:
                replayed += 1
            if scope.endswith("state_space_bwd"):
                if eqn.params["reverse"]:
                    reverse += 1
                else:
                    states += 1
        return (replayed, states, reverse), [
            telemetry.counter(name) - was
            for name, was in zip(counters, before)]

    assert passes() == ((0, 9, 9), [9, 9])
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(remat, "POLICY", None)
        assert passes() == ((9, 9, 9), [9, 9])


@pytest.mark.parametrize("name,kernel", [
    ("lfm2", True), ("trinity", True), ("lfm2", False), ("trinity", False),
    ("brumby", False), ("granite", False)])
def test_kept_values_change_no_number(request, name, kernel):
    """Bit for bit the plain ``jax.checkpoint``'s loss and gradients op by
    op; compiled, the unsegmented graph's within the rounding of another
    fusion."""
    if kernel:
        request.getfixturevalue("kernel_path")
    before = telemetry.counter("executor_remat_kept")
    ours = op_by_op(name)
    assert telemetry.counter("executor_remat_kept") > before
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(remat, "POLICY", None)
        theirs = op_by_op(name)
    assert len(ours) == len(theirs) > 30 and np.isfinite(ours[0]).all()
    assert min(np.abs(g).max() for g in ours[-6:-2]) > 0
    for a, b in zip(ours, theirs):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(compiled(name), compiled(name, recompute=False)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def moe_layer(x, router, w1, w3, w2, bias):
    return moe.sparse_moe(x, router, w1, w3, w2, bias, 8, 2, first_expert=2)


@pytest.mark.parametrize("op", ["attention", "window", "sparse_moe"])
def test_outside_a_segment_an_op_traces_what_it_did(monkeypatch, op):
    """No ``name`` equation, the counter at rest, and the jaxpr of the
    gradient the one the op gives with ``keep`` taken out."""
    rng = np.random.RandomState(0)
    if op == "sparse_moe":
        fn = moe_layer
        args = [rng.randn(*s).astype(np.float32) for s in
                ((24, 16), (8, 16), (4, 16, 12), (4, 16, 12), (4, 12, 16),
                 (8,))]
    else:
        def fn(q, k, v):
            return lm.causal_attention(q, k, v, 0.3, True, True,
                                       5 if op == "window" else None)
        args = [rng.randn(1, 12, h, 8).astype(np.float32) for h in (4, 2, 2)]

    def text():
        return str(jax.make_jaxpr(jax.grad(
            lambda *a: jnp.sum(jnp.square(fn(*a)[0])), (0, 1, 2)))(*args))

    before = telemetry.counter("executor_remat_kept")
    ours = text()
    assert telemetry.counter("executor_remat_kept") == before
    assert " name[" not in ours
    monkeypatch.setattr(remat, "keep", lambda *values: values)
    assert text() == ours
    # under a checkpoint of the caller's own, without the executor: as well
    monkeypatch.undo()
    plain = str(jax.make_jaxpr(jax.grad(jax.checkpoint(
        lambda *a: jnp.sum(jnp.square(fn(*a)[0]))), (0, 1, 2)))(*args))
    assert " name[" not in plain
    assert telemetry.counter("executor_remat_kept") == before
