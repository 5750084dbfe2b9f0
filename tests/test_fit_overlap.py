"""The fit loop's one step of overlap (ISSUE 30): ``_train_one_epoch``
enqueues step N+1 before it reads step N's metric wherever the module's
``_fit_step`` hands its outputs back (a fused ``Module``), and settles each
batch at once everywhere else.  Same work, same numbers, one batch later in
host order.

No duration is asserted anywhere: a CPU time says nothing about the chip.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.checkpoint import hooks
from mxnet_tpu.io import DataBatch, DataDesc, DataIter, NDArrayIter

BATCHES, BATCH = 5, 8
CTXS = {"one_device": lambda: mx.cpu(0),
        "four_devices": lambda: [mx.cpu(i) for i in range(4)]}


def mlp(loss_head=False):
    net = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(net, num_hidden=16, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
    if loss_head:
        return mx.sym.MakeLoss(mx.sym.mean(mx.sym.square(net)), name="loss")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def data(batches=BATCHES, batch=BATCH):
    rs = np.random.RandomState(5)
    x = rs.randn(batches * batch, 10).astype(np.float32)
    y = rs.randint(0, 4, batches * batch).astype(np.float32)
    return NDArrayIter(x, y, batch_size=batch)


class Recorder:
    """Host order of one ``fit``: wraps the bound ``_fit_step`` and
    ``update_metric`` on the instance, as the benchmark's runner does, is
    the batch-end callback, and listens at the step boundaries."""

    def __init__(self, mod, serial=False):
        self.mod, self.events, self.values = mod, [], []
        self.steps = self.metrics = 0
        fit_step, update_metric = mod._fit_step, mod.update_metric

        def spied_step(batch):
            self.events.append(("step", self.steps))
            self.steps += 1
            held = fit_step(batch)
            self.last_held = held
            # dropping what the step hands back is the loop's serial order
            return None if serial else held

        def spied_metric(metric, labels):
            self.events.append(("metric", self.metrics))
            self.metrics += 1
            return update_metric(metric, labels)

        mod._fit_step, mod.update_metric = spied_step, spied_metric

    def __call__(self, param):
        self.events.append(("callback", param.nbatch))
        self.values.append((param.epoch, param.nbatch,
                            param.eval_metric.get_name_value()))

    def _on_step_boundary(self, epoch=None, batch=None):
        self.events.append(("boundary", batch))


def fit(mod, recorder, metric="acc", epochs=1, it=None, **kwargs):
    mx.random.seed(11)
    mod.fit(it or data(), num_epoch=epochs, optimizer="sgd",
            eval_metric=metric, initializer=mx.init.Xavier(),
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
            batch_end_callback=recorder, **kwargs)
    arg, aux = mod.get_params()
    return {k: v.asnumpy() for k, v in {**arg, **aux}.items()}


def overlapped():
    return telemetry.counter("fit_step_overlapped")


def serial_order(batches):
    return [(kind, n) for n in range(batches)
            for kind in ("step", "metric", "callback")]


# ---- (1) the host order, and the drain -----------------------------------

@pytest.mark.parametrize("ctx", sorted(CTXS))
def test_fused_fit_enqueues_the_next_step_before_it_reads_the_metric(ctx):
    mod = mx.mod.Module(mlp(), context=CTXS[ctx]())
    rec = Recorder(mod)
    fit(mod, rec)
    assert mod._cached_step is not None
    want = [("step", 0)]
    for n in range(1, BATCHES):
        want += [("step", n), ("metric", n - 1), ("callback", n - 1)]
    want += [("metric", BATCHES - 1), ("callback", BATCHES - 1)]
    assert rec.events == want


def test_step_boundary_is_noted_with_its_own_step():
    """One boundary a step, right behind the step whose parameters the
    module now holds and with that batch's cursor, whichever batch's
    callback comes next."""
    mod = mx.mod.Module(mlp(), context=mx.cpu(0))
    rec = Recorder(mod)
    hooks.register(rec)
    try:
        fit(mod, rec)
    finally:
        hooks.unregister(rec)
    assert [e for e in rec.events if e[0] in ("step", "boundary")] \
        == [(kind, n) for n in range(BATCHES)
            for kind in ("step", "boundary")]
    for n in range(BATCHES):
        assert rec.events.index(("boundary", n)) \
            == rec.events.index(("step", n)) + 1


def test_callback_sees_the_newest_step_and_its_own_metric():
    """What changes for a user: inside callback N ``get_outputs`` is step
    N+1's, ``nbatch`` and the metric are batch N's."""
    mod = mx.mod.Module(mlp(), context=mx.cpu(0))
    rec = Recorder(mod)
    seen = []

    def callback(param):
        rec(param)
        seen.append((param.nbatch, rec.steps - 1,
                     mod.get_outputs()[0] is rec.last_held[1][0],
                     param.eval_metric.num_inst))

    fit(mod, callback)
    assert seen == [(n, min(n + 1, BATCHES - 1), True, (n + 1) * BATCH)
                    for n in range(BATCHES)]


# ---- (2) same work, same numbers -----------------------------------------

@pytest.mark.parametrize("ctx", sorted(CTXS))
@pytest.mark.parametrize("metric", ["acc", "ce", "loss"])
def test_overlapped_fit_is_bit_identical_to_serial(metric, ctx):
    runs = []
    for serial in (False, True):
        mod = mx.mod.Module(mlp(loss_head=metric == "loss"),
                            context=CTXS[ctx]())
        rec = Recorder(mod, serial=serial)
        before = overlapped()
        params = fit(mod, rec, metric=metric, epochs=2)
        runs.append((params, rec.values, overlapped() - before, rec.events))
    (got, got_values, engaged, _), (want, want_values, not_engaged, order) \
        = runs
    assert engaged == 2 * (BATCHES - 1) and not_engaged == 0
    assert [(kind, n % BATCHES) for kind, n in order] \
        == 2 * serial_order(BATCHES)
    assert got_values == want_values and len(got_values) == 2 * BATCHES
    assert set(got) == set(want)
    for name in want:
        assert np.array_equal(got[name], want[name]), name


class RaggedIter(DataIter):
    """Batches of 8, 8 and 4 rows: the last one rebinds the module, so the
    owed batch's outputs belong to another executor than the newest."""

    def __init__(self):
        super().__init__(batch_size=8)
        rs = np.random.RandomState(2)
        self.batches = [
            DataBatch([mx.nd.array(rs.randn(n, 10).astype(np.float32))],
                      [mx.nd.array(rs.randint(0, 4, n).astype(np.float32))],
                      pad=0)
            for n in (8, 8, 4)]
        self.provide_data = [DataDesc("data", (8, 10))]
        self.provide_label = [DataDesc("softmax_label", (8,))]
        self.cursor = 0

    def reset(self):
        self.cursor = 0

    def next(self):
        if self.cursor == len(self.batches):
            raise StopIteration
        self.cursor += 1
        return self.batches[self.cursor - 1]


def test_overlap_across_a_rebind_reads_each_batch_its_own_outputs():
    runs = []
    for serial in (False, True):
        mod = mx.mod.Module(mlp(), context=mx.cpu(0))
        rec = Recorder(mod, serial=serial)
        runs.append((fit(mod, rec, it=RaggedIter(), epochs=2), rec.values))
    (got, got_values), (want, want_values) = runs
    assert got_values == want_values
    assert [nbatch for _, nbatch, _ in got_values] == [0, 1, 2, 0, 1, 2]
    for name in want:
        assert np.array_equal(got[name], want[name]), name


# ---- (3) everything else keeps the serial order --------------------------

def unfused_by_kvstore(monkeypatch):
    return mx.mod.Module(mlp(), context=mx.cpu(0)), \
        {"kvstore": mx.kv.create("local")}, 0


def unfused_by_environment(monkeypatch):
    monkeypatch.setenv("MXNET_MODULE_FUSED_STEP", "0")
    return mx.mod.Module(mlp(), context=mx.cpu(0)), {}, 0


def under_a_monitor(monkeypatch):
    mon = mx.monitor.Monitor(interval=1000, pattern="nothing")
    return mx.mod.Module(mlp(), context=mx.cpu(0)), {"monitor": mon}, None


def bucketing(monkeypatch):
    mod = mx.mod.BucketingModule(
        lambda key: (mlp(), ("data",), ("softmax_label",)),
        default_bucket_key=10, context=mx.cpu(0))
    return mod, {}, BATCHES


def sequential(monkeypatch):
    head = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=16,
                                 name="s1fc")
    tail = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=4,
                              name="s2fc"), name="softmax")
    seq = mx.mod.SequentialModule()
    seq.add(mx.mod.Module(head, label_names=()), auto_wiring=True)
    seq.add(mx.mod.Module(tail), take_labels=True)
    return seq, {}, 0


@pytest.mark.parametrize("make", [
    unfused_by_kvstore, unfused_by_environment, under_a_monitor, bucketing,
    sequential], ids=lambda make: make.__name__)
def test_serial_order_is_kept_and_the_counter_stays(make, monkeypatch):
    """A monitor, a BucketingModule, a SequentialModule and the unfused
    Module paths settle each batch at once; ``fused_steps`` says how many
    fused programs ran all the same (a BucketingModule's do)."""
    mod, kwargs, fused_steps = make(monkeypatch)
    rec = Recorder(mod)
    before, steps_before = overlapped(), telemetry.counter(
        "module_train_step")
    fit(mod, rec, **kwargs)
    assert overlapped() == before
    if fused_steps is not None:
        assert telemetry.counter("module_train_step") - steps_before \
            == fused_steps
    if make is under_a_monitor:
        # the monitor's two-call path goes round _fit_step
        assert rec.events == [(kind, n) for n in range(BATCHES)
                              for kind in ("metric", "callback")]
    else:
        assert rec.events == serial_order(BATCHES)


# ---- (4) how often it engages --------------------------------------------

@pytest.mark.parametrize("ctx", sorted(CTXS))
@pytest.mark.parametrize("epochs", [1, 3])
def test_counter_share_is_all_but_one_batch_an_epoch(ctx, epochs):
    mod = mx.mod.Module(mlp(), context=CTXS[ctx]())
    before = overlapped()
    steps_before = telemetry.counter("module_train_step")
    fit(mod, None, epochs=epochs)
    steps = telemetry.counter("module_train_step") - steps_before
    assert steps == epochs * BATCHES
    assert overlapped() - before == epochs * (BATCHES - 1)


# ---- update_metric outside fit is what it was ----------------------------

def test_update_metric_outside_fit_reads_the_newest_outputs():
    mod = mx.mod.Module(mlp(), context=mx.cpu(0))
    fit(mod, None)
    batches = list(data())
    held = mod._fit_step(batches[0])
    first = held[1]
    second = mod._fit_step(batches[1])[1]
    assert first[0] is not second[0] and mod.get_outputs()[0] is second[0]

    def value(outputs, labels):
        metric = mx.metric.create("ce")
        metric.update(labels, outputs)
        return metric.get()

    metric = mx.metric.create("ce")
    mod.update_metric(metric, batches[1].label)
    assert metric.get() == value(second, batches[1].label)
    # what the loop does for an owed batch, and what it leaves behind,
    # also when the metric raises
    metric.reset()
    with mod._outputs_read_as(held):
        assert mod.get_outputs()[0] is first[0]
        mod.update_metric(metric, batches[0].label)
    assert metric.get() == value(first, batches[0].label)
    with pytest.raises(ZeroDivisionError):
        with mod._outputs_read_as(held):
            1 / 0
    assert mod.get_outputs()[0] is second[0]
