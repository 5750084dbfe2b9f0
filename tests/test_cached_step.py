"""Cached train-step guarantees (VERDICT r3 #2).

The reference's contract after bind is zero per-step graph work
(``graph_executor.cc:1403`` RunOps only pushes cached engine ops). The
TPU analogue: a bound executor compiles its train-forward, backward, and
fused fwd+bwd programs ONCE and every later step is a cache hit — no
Python-level retracing, no relinearisation.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import ndarray as nd
from mxnet_tpu import symbol as sym_api


def _mlp():
    x = sym_api.Variable("data")
    w1 = sym_api.Variable("w1")
    w2 = sym_api.Variable("w2")
    h = sym_api.relu(sym_api.dot(x, w1))
    y = sym_api.dot(h, w2)
    label = sym_api.Variable("softmax_label")
    return sym_api.SoftmaxOutput(y, label, name="softmax")


def _bind(s, bs=4):
    return s.simple_bind(mx.cpu(), grad_req="write",
                         data=(bs, 6), w1=(6, 8), w2=(8, 3),
                         softmax_label=(bs,))


def test_no_retrace_across_steps():
    ex = _bind(_mlp())
    rng = np.random.RandomState(0)
    for step in range(4):
        ex.forward(is_train=True,
                   data=nd.array(rng.randn(4, 6)),
                   softmax_label=nd.array(rng.randint(0, 3, (4,))))
        ex.backward()
    # one compiled program per leg, regardless of step count
    assert ex._fwd_train_jit._cache_size() == 1
    assert ex._bwd_jit._cache_size() == 1


def test_fused_forward_backward_matches_two_call():
    rng = np.random.RandomState(1)
    data = nd.array(rng.randn(4, 6))
    label = nd.array(rng.randint(0, 3, (4,)))
    w1 = rng.randn(6, 8) * 0.1
    w2 = rng.randn(8, 3) * 0.1

    ex_a = _bind(_mlp())
    ex_b = _bind(_mlp())
    for ex in (ex_a, ex_b):
        ex.arg_dict["w1"][:] = w1
        ex.arg_dict["w2"][:] = w2

    mx.random.seed(7)
    ex_a.forward(is_train=True, data=data, softmax_label=label)
    ex_a.backward()
    mx.random.seed(7)
    ex_b.forward_backward(data=data, softmax_label=label)

    np.testing.assert_allclose(ex_a.outputs[0].asnumpy(),
                               ex_b.outputs[0].asnumpy(), rtol=1e-6)
    for n in ("w1", "w2"):
        np.testing.assert_allclose(ex_a.grad_dict[n].asnumpy(),
                                   ex_b.grad_dict[n].asnumpy(),
                                   rtol=1e-5, atol=1e-6)
    assert ex_b._fwd_bwd_ones_jit._cache_size() == 1


def test_fused_forward_backward_explicit_out_grads():
    rng = np.random.RandomState(2)
    x = sym_api.Variable("data")
    w = sym_api.Variable("w")
    y = sym_api.dot(x, w)
    ex_a = y.simple_bind(mx.cpu(), grad_req="write", data=(3, 5), w=(5, 2))
    ex_b = y.simple_bind(mx.cpu(), grad_req="write", data=(3, 5), w=(5, 2))
    data = nd.array(rng.randn(3, 5))
    wv = rng.randn(5, 2)
    og = nd.array(rng.randn(3, 2))
    for ex in (ex_a, ex_b):
        ex.arg_dict["w"][:] = wv
    ex_a.forward(is_train=True, data=data)
    ex_a.backward([og])
    ex_b.forward_backward(out_grads=[og], data=data)
    np.testing.assert_allclose(ex_a.grad_dict["w"].asnumpy(),
                               ex_b.grad_dict["w"].asnumpy(), rtol=1e-6)


def test_grad_req_add_accumulates_in_fused_path():
    rng = np.random.RandomState(3)
    x = sym_api.Variable("data")
    w = sym_api.Variable("w")
    y = sym_api.sum(sym_api.dot(x, w))
    ex = y.simple_bind(mx.cpu(), grad_req="add", data=(2, 4), w=(4, 3))
    data = nd.array(rng.randn(2, 4))
    ex.arg_dict["w"][:] = rng.randn(4, 3)
    ex.grad_dict["w"][:] = 0
    ex.forward_backward(data=data)
    once = ex.grad_dict["w"].asnumpy().copy()
    ex.forward_backward(data=data)
    np.testing.assert_allclose(ex.grad_dict["w"].asnumpy(), 2 * once,
                               rtol=1e-6)


def _small_net(dropout=False):
    net = sym_api.FullyConnected(sym_api.Variable("data"), num_hidden=8,
                                 name="fc1")
    net = sym_api.Activation(net, act_type="relu", name="relu1")
    if dropout:
        net = sym_api.Dropout(net, p=0.5, name="drop")
    net = sym_api.FullyConnected(net, num_hidden=3, name="fc2")
    return sym_api.SoftmaxOutput(net, sym_api.Variable("softmax_label"),
                                 name="softmax")


def _fit_module(it, optimizer="sgd", opt_params=(("learning_rate", 0.1),
                                                 ("momentum", 0.9)),
                num_epoch=2):
    from mxnet_tpu.module import Module
    it.reset()
    mod = Module(_small_net(), context=mx.cpu())
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(initializer=mx.initializer.Xavier(rnd_type="uniform",
                                                      magnitude=2.0))
    mod.init_optimizer(optimizer=optimizer, optimizer_params=opt_params)
    mod.fit(it, num_epoch=num_epoch)
    return mod


def _data_iter(seed=4):
    from mxnet_tpu.io import NDArrayIter
    rng = np.random.RandomState(seed)
    X = rng.randn(32, 6).astype(np.float32)
    Y = rng.randint(0, 3, (32,)).astype(np.float32)
    return NDArrayIter(X, Y, batch_size=8, label_name="softmax_label")


def test_module_fit_uses_one_donated_program():
    it = _data_iter()
    mod = _fit_module(it)
    step = mod._cached_step
    assert step is not None, "fit did not take the fused-step fast path"
    assert step._step_jit._cache_size() == 1
    ex = mod._exec_group.execs[0]
    # the split-leg programs were never needed during fit
    assert ex._fwd_train_jit._cache_size() == 0
    assert ex._fwd_bwd_ones_jit._cache_size() == 0


def test_module_fused_step_matches_slow_path():
    import os
    for optimizer, params in (
            ("sgd", (("learning_rate", 0.1), ("momentum", 0.9))),
            ("adam", (("learning_rate", 0.01),))):
        it = _data_iter()
        np.random.seed(0); mx.random.seed(0)
        fast = _fit_module(it, optimizer, params)
        os.environ["MXNET_MODULE_FUSED_STEP"] = "0"
        try:
            np.random.seed(0); mx.random.seed(0)
            slow = _fit_module(it, optimizer, params)
        finally:
            del os.environ["MXNET_MODULE_FUSED_STEP"]
        assert slow._cached_step is None or not slow._cached_step
        fa, _ = fast.get_params()
        sa, _ = slow.get_params()
        for name in fa:
            np.testing.assert_allclose(
                fa[name].asnumpy(), sa[name].asnumpy(),
                rtol=2e-5, atol=1e-6,
                err_msg="%s/%s diverged" % (optimizer, name))


def test_fused_step_optimizer_state_checkpoint_roundtrip():
    import tempfile, os as _os
    it = _data_iter()
    mod = _fit_module(it)
    assert mod._cached_step is not None
    with tempfile.TemporaryDirectory() as td:
        f = _os.path.join(td, "opt.states")
        mod.save_optimizer_states(f)
        mod2 = _fit_module(it, num_epoch=1)
        mod2.load_optimizer_states(f)
        # momentum buffers round-trip through the updater layout
        for idx, st in mod._updater.states.items():
            if st is None:
                continue
            np.testing.assert_allclose(st.asnumpy(),
                                       mod2._updater.states[idx].asnumpy())


def test_reshape_alternation_reuses_groups_and_programs():
    """Alternating input shapes (bucketing / final partial batch) must
    reuse the cached exec group AND its compiled step program instead of
    rebinding from scratch (reference shares the memory pool; here the
    costly resource is the compiled program)."""
    from mxnet_tpu.io import DataBatch, DataDesc
    from mxnet_tpu.module import Module
    rng = np.random.RandomState(7)
    mod = Module(_small_net(), context=mx.cpu())
    mod.bind(data_shapes=[DataDesc("data", (8, 6))],
             label_shapes=[DataDesc("softmax_label", (8,))])
    mod.init_params(initializer=mx.initializer.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.1),))

    def batch(bs):
        return DataBatch([nd.array(rng.randn(bs, 6))],
                         [nd.array(rng.randint(0, 3, (bs,)))])

    groups, steps = set(), set()
    for _ in range(3):
        for bs in (8, 5):          # alternate full/partial batch shapes
            mod._fit_step(batch(bs))
            groups.add(id(mod._exec_group))
            assert mod._cached_step is not None
            steps.add(id(mod._cached_step))
    assert len(groups) == 2, "groups rebuilt instead of cached"
    assert len(steps) == 2, "step programs rebuilt instead of cached"
    for step in (mod._cached_step,):
        assert step._step_jit._cache_size() == 1


def test_reshape_preserves_grad_req_add():
    """reshape must rebuild groups with the BOUND grad_req (accumulation
    was silently downgraded to 'write' for reshaped shapes)."""
    from mxnet_tpu.io import DataBatch, DataDesc
    from mxnet_tpu.module import Module
    rng = np.random.RandomState(11)
    mod = Module(_small_net(), context=mx.cpu())
    mod.bind(data_shapes=[DataDesc("data", (8, 6))],
             label_shapes=[DataDesc("softmax_label", (8,))],
             grad_req="add")
    mod.init_params(initializer=mx.initializer.Xavier())
    mod.reshape([DataDesc("data", (4, 6))],
                [DataDesc("softmax_label", (4,))])
    ex = mod._exec_group.execs[0]
    assert ex.grad_req["fc1_weight"] == "add"
    batch = DataBatch([nd.array(rng.randn(4, 6))],
                      [nd.array(rng.randint(0, 3, (4,)))])
    mod.forward_backward(batch)
    once = ex.grad_dict["fc1_weight"].asnumpy().copy()
    mod.forward_backward(batch)
    np.testing.assert_allclose(ex.grad_dict["fc1_weight"].asnumpy(),
                               2 * once, rtol=1e-5)


def test_reshape_cache_bounded(monkeypatch):
    from mxnet_tpu.io import DataDesc
    from mxnet_tpu.module import Module
    monkeypatch.setenv("MXNET_MODULE_RESHAPE_CACHE", "3")
    mod = Module(_small_net(), context=mx.cpu())
    mod.bind(data_shapes=[DataDesc("data", (8, 6))],
             label_shapes=[DataDesc("softmax_label", (8,))])
    mod.init_params(initializer=mx.initializer.Xavier())
    for bs in (7, 6, 5, 4, 3, 2):
        mod.reshape([DataDesc("data", (bs, 6))],
                    [DataDesc("softmax_label", (bs,))])
    assert len(mod._reshape_cache) <= 3


def test_bucketing_default_bucket_updates_survive_switch():
    """A fused step on the DEFAULT bucket updates device params only;
    switching buckets must sync those updates down before seeding the
    next bucket (they used to be silently reverted)."""
    from mxnet_tpu.io import DataBatch, DataDesc
    from mxnet_tpu.module import BucketingModule
    rng = np.random.RandomState(13)

    def sym_gen(key):
        # weights shared across buckets (seq-len varies, dims don't)
        emb = sym_api.Embedding(sym_api.Variable("data"), input_dim=10,
                                output_dim=6, name="emb")
        pooled = sym_api.mean(emb, axis=1)
        net = sym_api.FullyConnected(pooled, num_hidden=4, name="fc")
        net = sym_api.SoftmaxOutput(net, sym_api.Variable("softmax_label"),
                                    name="softmax")
        return net, ("data",), ("softmax_label",)

    def batch(n, key):
        return DataBatch(
            [nd.array(rng.randint(0, 10, (4, n)).astype(np.float32))],
            [nd.array(rng.randint(0, 4, (4,)))],
            bucket_key=key,
            provide_data=[DataDesc("data", (4, n))],
            provide_label=[DataDesc("softmax_label", (4,))])

    mod = BucketingModule(sym_gen, default_bucket_key=8)
    mod.bind(data_shapes=[DataDesc("data", (4, 8))],
             label_shapes=[DataDesc("softmax_label", (4,))])
    mod.init_params(initializer=mx.initializer.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.5),))

    w0 = mod._leader._exec_group.execs[0].arg_dict["emb_weight"].asnumpy()
    w0 = w0.copy()
    mod._fit_step(batch(8, 8))       # default bucket: device-only update
    w1 = mod._leader._exec_group.execs[0].arg_dict["emb_weight"].asnumpy()
    w1 = w1.copy()
    assert np.abs(w1 - w0).max() > 0, "leader step had no effect"
    mod._fit_step(batch(5, 5))       # switch must carry w1 forward
    # the non-default bucket must have STARTED from w1, and its update
    # must not regress behind w1's step
    arg, _ = mod.get_params()
    assert np.abs(arg["emb_weight"].asnumpy() - w0).max() > 0, \
        "default-bucket update was reverted by the switch"


# ---- the step threads the PRNG key and carries its own outputs ----------
#
# The fused program takes random's root key and returns the advanced one,
# and takes by identity the buffers it wrote back one step ago; in steady
# state the host launches nothing before it.

_CTXS = {"one_device": lambda: mx.cpu(),
         "four_devices": lambda: [mx.cpu(i) for i in range(4)]}


def _bound_module(ctx, net, optimizer, opt_params, bs=8):
    from mxnet_tpu.io import DataDesc
    from mxnet_tpu.module import Module
    mod = Module(net, context=ctx)
    mod.bind(data_shapes=[DataDesc("data", (bs, 6))],
             label_shapes=[DataDesc("softmax_label", (bs,))])
    mod.init_params(initializer=mx.initializer.Xavier())
    mod.init_optimizer(optimizer=optimizer, optimizer_params=opt_params)
    return mod


def _batches(n, bs=8, seed=21):
    from mxnet_tpu.io import DataBatch
    rng = np.random.RandomState(seed)
    return [DataBatch([nd.array(rng.randn(bs, 6).astype(np.float32))],
                      [nd.array(rng.randint(0, 3, (bs,))
                                .astype(np.float32))])
            for _ in range(n)]


def _carried():
    from mxnet_tpu import telemetry
    return telemetry.counter("module_step_carried")


@pytest.mark.parametrize("ctx", list(_CTXS))
@pytest.mark.parametrize("case", ["dropout_sgd", "dense_sgld"])
def test_fused_step_random_stream_is_the_host_chain(ctx, case):
    """N fused steps leave outputs, parameters and the global stream where
    the explicit host chain — ``root, sub = split(root)``; ``ukeys =
    split(sub, n + 1)``; graph key ``ukeys[0]``, parameter i's key
    ``ukeys[1 + i]`` — puts them, whether or not the model reads a key."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.optimizer import _state_raw
    dropout = case == "dropout_sgd"
    optimizer, opt_params = ("sgd", (("learning_rate", 0.1),
                                     ("momentum", 0.9))) if dropout \
        else ("sgld", (("learning_rate", 0.1),))
    mod = _bound_module(_CTXS[ctx](), _small_net(dropout), optimizer,
                        opt_params)
    # the reference: a second single-device bind of the same graph, its
    # split-path program fed the chain's keys, then update_step per param
    ref = _bound_module(mx.cpu(), _small_net(dropout), optimizer,
                        opt_params)
    arg, aux = mod.get_params()
    ref.set_params(arg, aux)
    rex = ref._exec_group.execs[0]
    opt = ref._updater.optimizer
    pnames = [n for n in ref._exec_group.param_names
              if n in rex._grad_names]
    states = {n: _state_raw(opt.create_state(i, rex.arg_dict[n]))
              for i, n in enumerate(pnames)}

    seed = 1234567
    mx.random.seed(seed)
    root = jax.random.PRNGKey(seed)
    for t, batch in enumerate(_batches(3), start=1):
        mod._fit_step(batch)
        assert mod._cached_step is not None, "not on the fused step"

        root, sub = jax.random.split(root)
        ukeys = jax.random.split(sub, len(pnames) + 1)
        rex.arg_dict["data"]._set_data(batch.data[0]._data)
        rex.arg_dict["softmax_label"]._set_data(batch.label[0]._data)
        outs, _, grads = rex._fwd_bwd_ones_jit(
            [rex.arg_dict[n]._data for n in rex.arg_names],
            [rex.aux_dict[n]._data for n in rex.aux_names], ukeys[0])
        for n, g in zip(rex._grad_names, grads):
            i = pnames.index(n)
            w = rex.arg_dict[n]._data
            hyper = {"lr": jnp.asarray(opt._get_lr(i), w.dtype),
                     "wd": jnp.asarray(opt._get_wd(i), w.dtype),
                     "t": np.int32(t), "key": ukeys[1 + i]}
            new_w, states[n] = opt.update_step(w, g.astype(w.dtype),
                                               states[n], hyper)
            rex.arg_dict[n]._set_data(new_w)

        # a wrong key moves these by O(1): the dropout mask, SGLD's noise
        np.testing.assert_allclose(mod.get_outputs()[0].asnumpy(),
                                   np.asarray(outs[0]), rtol=1e-5,
                                   atol=1e-6)
        got = mod._exec_group.execs[0].arg_dict
        for n in pnames:
            np.testing.assert_allclose(got[n].asnumpy(),
                                       np.asarray(rex.arg_dict[n]._data),
                                       rtol=1e-5, atol=1e-6, err_msg=n)
    assert np.array_equal(mx.random.get_state()["key"], np.asarray(root))
    # every other consumer draws from where the steps left the stream
    after = mx.nd.random.uniform(shape=(5,)).asnumpy()
    state = mx.random.get_state()
    state["key"] = np.asarray(root)
    mx.random.set_state(state)
    assert np.array_equal(after, mx.nd.random.uniform(shape=(5,)).asnumpy())


def _carry_scenario(ctx):
    """Steady steps around every event after which an input is no longer
    the step's own output; returns the counter's rise per step and the
    parameters at the end."""
    mx.random.seed(3)
    np.random.seed(3)
    mod = _bound_module(ctx, _small_net(), "sgd",
                        (("learning_rate", 0.1), ("momentum", 0.9)))
    batches = iter(_batches(12))
    rises = {}

    def step(what):
        before = _carried()
        mod._fit_step(next(batches))
        rises.setdefault(what, []).append(_carried() - before)

    step("first")
    step("steady")
    step("steady")
    arg, aux = mod.get_params()
    mod.set_params(arg, aux)
    step("after_set_params")
    step("steady")
    mod._updater.set_states(mod._updater.get_states())
    step("after_set_states")
    step("steady")
    handle = mod._exec_group.execs[0].arg_dict["fc1_weight"]
    handle._set_data(handle._data * 1)
    step("after_set_data")
    step("steady")
    mx.random.seed(4)       # a fresh root key is placed, nothing else
    step("after_seed")
    arg, _ = mod.get_params()
    return rises, {k: v.asnumpy() for k, v in arg.items()}


@pytest.mark.parametrize("ctx", list(_CTXS))
def test_fused_step_carries_its_outputs_and_places_what_is_not(
        ctx, monkeypatch):
    rises, fast = _carry_scenario(_CTXS[ctx]())
    assert rises.pop("steady") == [1, 1, 1, 1, 1]
    assert rises.pop("after_seed") == [1]
    assert all(v == [0] for v in rises.values()), rises
    # the slow path on one device: over four, its eager per-slot update
    # cannot take the states set_states unpickles onto the first device
    monkeypatch.setenv("MXNET_MODULE_FUSED_STEP", "0")
    slow_rises, slow = _carry_scenario(mx.cpu())
    assert not any(sum(v) for v in slow_rises.values())
    for name in fast:
        np.testing.assert_allclose(fast[name], slow[name], rtol=2e-4,
                                   atol=2e-5, err_msg=name)


def _bucket_scenario():
    from mxnet_tpu.io import DataBatch, DataDesc
    from mxnet_tpu.module import BucketingModule
    rng = np.random.RandomState(13)
    mx.random.seed(3)
    np.random.seed(3)

    def sym_gen(key):
        emb = sym_api.Embedding(sym_api.Variable("data"), input_dim=10,
                                output_dim=6, name="emb")
        net = sym_api.FullyConnected(sym_api.mean(emb, axis=1),
                                     num_hidden=4, name="fc")
        net = sym_api.SoftmaxOutput(net, sym_api.Variable("softmax_label"),
                                    name="softmax")
        return net, ("data",), ("softmax_label",)

    def batch(n):
        return DataBatch(
            [nd.array(rng.randint(0, 10, (4, n)).astype(np.float32))],
            [nd.array(rng.randint(0, 4, (4,)).astype(np.float32))],
            bucket_key=n,
            provide_data=[DataDesc("data", (4, n))],
            provide_label=[DataDesc("softmax_label", (4,))])

    mod = BucketingModule(sym_gen, default_bucket_key=8)
    mod.bind(data_shapes=[DataDesc("data", (4, 8))],
             label_shapes=[DataDesc("softmax_label", (4,))])
    mod.init_params(initializer=mx.initializer.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.5),
                                         ("momentum", 0.9)))
    rises = []
    for n in (8, 8, 5, 5, 8, 8):
        before = _carried()
        mod._fit_step(batch(n))
        rises.append(_carried() - before)
    arg, _ = mod.get_params()
    return rises, {k: v.asnumpy() for k, v in arg.items()}


def test_bucket_switch_misses_once_then_carries_again(monkeypatch):
    """Buckets share parameter handles and optimizer state: after a
    switch the handles hold the other bucket's outputs, so that step
    places, and the next one carries."""
    rises, fast = _bucket_scenario()
    assert rises == [0, 1, 0, 1, 0, 1]
    monkeypatch.setenv("MXNET_MODULE_FUSED_STEP", "0")
    slow_rises, slow = _bucket_scenario()
    assert slow_rises == [0] * 6
    for name in fast:
        np.testing.assert_allclose(fast[name], slow[name], rtol=2e-5,
                                   atol=1e-6, err_msg=name)


@pytest.mark.parametrize("ctx", list(_CTXS))
def test_steady_state_step_launches_nothing_before_its_program(
        ctx, monkeypatch):
    """With the step program's call replaced by a recorder: up to that
    call a steady-state step made no ``device_put``, no ``split``, no
    ``_place`` of a parameter and no transfer of any kind."""
    import jax
    mod = _bound_module(_CTXS[ctx](), _small_net(dropout=True), "sgld",
                        (("learning_rate", 0.1),))
    batches = _batches(4)
    mod._fit_step(batches[0])
    mod._fit_step(batches[1])
    cts = mod._cached_step
    ex = mod._exec_group.execs[0]
    # the batch where the executor wants it, as a training loop's
    # device-resident pool has it: placing it is the iterator's business
    for b in batches[2:]:
        b.data[0]._set_data(jax.device_put(
            b.data[0]._data, ex.arg_dict["data"]._data.sharding))
        b.label[0]._set_data(jax.device_put(
            b.label[0]._data, ex.arg_dict["softmax_label"]._data.sharding))

    calls = {"device_put": 0, "split": 0, "place": [], "place_rng": 0}
    seen = {}
    real = {"device_put": jax.device_put, "split": jax.random.split,
            "step": cts._step_jit, "place": ex._place,
            "place_rng": ex._place_rng}

    def counted(name):
        def fn(*a, **k):
            calls[name] += 1
            return real[name](*a, **k)
        return fn

    def place(name, arr):
        calls["place"].append(name)
        return real["place"](name, arr)

    def guard(level):
        for kind in ("host_to_device", "device_to_device",
                     "device_to_host"):
            jax.config.update("jax_transfer_guard_" + kind, level)

    def recorder(*args):
        seen.update(calls, place=list(calls["place"]), n=len(args),
                    born=[a for a in jax.live_arrays()
                          if id(a) not in alive])
        guard("allow")
        return real["step"](*args)

    monkeypatch.setattr(jax, "device_put", counted("device_put"))
    monkeypatch.setattr(jax.random, "split", counted("split"))
    monkeypatch.setattr(ex, "_place", place)
    monkeypatch.setattr(ex, "_place_rng", counted("place_rng"))
    monkeypatch.setattr(cts, "_step_jit", recorder)
    before = _carried()
    # every dispatch of a device program, eager slices included, and every
    # transfer gives birth to a device array
    alive = {id(a) for a in jax.live_arrays()}
    try:
        guard("disallow_explicit")
        mod._fit_step(batches[2])
    finally:
        guard("allow")
    assert seen["n"] == 6, "the recorder never saw the step's call"
    assert (seen["device_put"], seen["split"], seen["place_rng"]) == \
        (0, 0, 0), seen
    assert sorted(seen["place"]) == ["data", "softmax_label"], seen
    assert not seen["born"], "device arrays made before the step program"
    assert _carried() == before + 1
    # and the stream the four devices advanced serves one device
    mx.nd.random.uniform(shape=(2,), ctx=mx.cpu(0)).asnumpy()
    mod._fit_step(batches[3])
