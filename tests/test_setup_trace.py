"""Set-up told from inside the program (ISSUE 35): the compile ledger that
JAX's own monitoring events feed, always on, and the ``setup`` spans on
bind, parameter init, optimizer init, the step's build and its first run.

No duration is asserted against a wall clock but where the test itself put
the seconds there: a CPU time says nothing about the chip.
"""
import collections
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.telemetry import core

ROOTS = ["module_bind", "module_init_params", "module_init_optimizer",
         "module_step_build", "module_first_step"]
CHILDREN = {"init_params_host": "module_init_params",
            "init_params_place": "module_init_params"}
ROW_KEYS = {"fun_name", "watch", "span", "trace_s", "lower_s", "backend_s",
            "cache", "saved_s", "ts"}


@pytest.fixture(autouse=True)
def clean_telemetry():
    telemetry.set_enabled(False)
    telemetry.reset()
    yield
    telemetry.set_enabled(False)
    telemetry.reset()


@pytest.fixture
def cache_dir(tmp_path):
    """A persistent compilation cache of this test's own that takes every
    program, however small or quick to compile."""
    from jax._src import compilation_cache
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    before = {n: getattr(jax.config, n) for n in names}
    jax.config.update(names[0], str(tmp_path / "cache"))
    jax.config.update(names[1], 0.0)
    jax.config.update(names[2], -1)
    compilation_cache.reset_cache()
    yield
    for n, v in before.items():
        jax.config.update(n, v)
    compilation_cache.reset_cache()


def ring(cat=None):
    return [e for e in telemetry.chrome_trace_payload()["traceEvents"]
            if e["ph"] == "X" and (cat is None or e["cat"] == cat)]


def rows_of(fun_name):
    return [r for r in telemetry.compile_events()
            if r["fun_name"] == fun_name]


def toy_fit(ctx=None, epochs=1, batches=4, batch=8):
    rs = np.random.RandomState(0)
    x = rs.randn(batches * batch, 10).astype(np.float32)
    y = rs.randint(0, 4, batches * batch).astype(np.float32)
    net = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(net, num_hidden=16, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    mod = mx.mod.Module(net, context=ctx or mx.cpu(0))
    mod.fit(mx.io.NDArrayIter(x, y, batch_size=batch), num_epoch=epochs,
            optimizer="sgd", eval_metric="acc",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9})
    return mod


# ---- the ledger ----------------------------------------------------------

def test_fresh_jit_under_a_setup_span_is_one_row_miss_then_hit(cache_dir):
    def setup_trace_fresh(x):
        return jnp.tanh(x) * 3.0 + 1.0

    x = jnp.ones((7, 5), jnp.float32)
    fn = jax.jit(setup_trace_fresh)
    with telemetry.span("module_bind", cat="setup"):
        fn(x).block_until_ready()
    (row,) = rows_of("jit(setup_trace_fresh)")
    assert set(row) == ROW_KEYS
    assert row["span"] == "module_bind" and row["watch"] is None
    assert row["cache"] == "miss" and row["saved_s"] == 0.0
    assert row["trace_s"] > 0 and row["lower_s"] > 0 and row["backend_s"] > 0
    # on the spans' clock, inside the span that held it
    (span,) = [e for e in ring("setup") if e["name"] == "module_bind"]
    assert span["ts"] <= row["ts"] \
        and row["ts"] + row["backend_s"] * 1e6 <= span["ts"] + span["dur"]
    assert telemetry.counter("compile_cache_misses") >= 1
    hits = telemetry.counter("compile_cache_hits")

    # the same program again, the in-memory caches dropped: the
    # persistent cache serves it, outside every span this time
    jax.clear_caches()
    jax.jit(setup_trace_fresh)(x).block_until_ready()
    first, second = rows_of("jit(setup_trace_fresh)")
    assert first == row
    assert second["cache"] == "hit" and second["span"] is None
    assert second["backend_s"] > 0       # the retrieval
    assert telemetry.counter("compile_cache_hits") == hits + 1
    sums = telemetry.retrace_report()["jit(setup_trace_fresh)"]
    assert (sums["count"], sums["hit"], sums["miss"]) == (2, 1, 1)


def test_a_watched_jits_row_carries_its_watch_with_telemetry_off():
    assert not telemetry.enabled()
    fn = telemetry.watch_jit(jax.jit(lambda x: x * 2 + 1),
                             "setup_trace_watched")
    fn(jnp.ones(3)).block_until_ready()
    mine = [r for r in telemetry.compile_events()
            if r["watch"] == "setup_trace_watched"]
    assert len(mine) == 1 and mine[0]["fun_name"] == "jit(<lambda>)"
    assert telemetry.counter("jit_compiles") == 1
    # a compile outside every watched call carries none, and is not one
    # of the framework's
    jax.jit(lambda x: x * 3 - 1)(jnp.ones(3)).block_until_ready()
    assert telemetry.compile_events()[-1]["watch"] is None
    assert telemetry.counter("jit_compiles") == 1
    report = telemetry.retrace_report()
    assert report["setup_trace_watched"]["count"] == 1
    assert not report["setup_trace_watched"]["storm"]


def test_trace_seconds_are_the_compiled_functions_own():
    """Inner jits and the lowering rules report traces too, before and
    after the function's own: the row takes the one with its name."""
    inner = jax.jit(lambda x: x + 1)

    def setup_trace_slow(x):
        time.sleep(0.05)                 # host Python inside the trace
        return inner(x) * jnp.cumsum(x)

    jax.jit(setup_trace_slow)(jnp.ones(8)).block_until_ready()
    (row,) = rows_of("jit(setup_trace_slow)")
    assert row["trace_s"] >= 0.05


def test_snapshot_sums_the_ledger():
    before = telemetry.snapshot()["compiles"]
    assert before["count"] == 0
    jax.jit(lambda x: x - 7)(jnp.ones(4)).block_until_ready()
    jax.jit(lambda x: x / 7)(jnp.ones(4)).block_until_ready()
    rows = telemetry.compile_events()
    after = telemetry.snapshot()["compiles"]
    assert after["count"] == len(rows) >= 2
    assert after["backend_s"] == pytest.approx(
        sum(r["backend_s"] for r in rows))
    assert after["hit"] + after["miss"] + after["off"] == len(rows)
    telemetry.reset()
    assert telemetry.compile_events() == []
    assert telemetry.snapshot()["compiles"]["count"] == 0


def test_every_row_is_a_ring_event_while_something_records():
    jax.jit(lambda x: x * 11)(jnp.ones(2)).block_until_ready()
    assert ring("compile") == []         # nothing records: no ring event
    telemetry.set_enabled(True)
    fn = telemetry.watch_jit(jax.jit(lambda x: x * 13), "setup_trace_ring")
    fn(jnp.ones(2)).block_until_ready()
    events = {e["name"]: e for e in ring("compile")}
    assert "compile:setup_trace_ring" in events
    args = events["compile:setup_trace_ring"]["args"]
    assert args["fun_name"] == "jit(<lambda>)" and args["cache"] in (
        "hit", "miss", "off")


def test_one_compile_is_one_row_with_telemetry_on():
    """The cost capture reads the executable the call built: it does not
    compile the program a second time."""
    telemetry.set_enabled(True)
    toy_fit()
    step_rows = [r for r in telemetry.compile_events()
                 if r["watch"] == "module_cached_step"]
    assert len(step_rows) == 1
    assert telemetry.retrace_report()["module_cached_step"]["count"] == 1
    assert telemetry.program_cost("module_cached_step") is not None


# ---- the set-up spans ----------------------------------------------------

def test_setup_spans_record_with_telemetry_off_and_batch_spans_do_not():
    assert not telemetry.trace_active()
    with telemetry.span("module_bind", cat="setup", args={"contexts": 1}):
        with telemetry.span("fit_callback", cat="host"):
            pass
        with telemetry.span("module_train_step", cat="step"):
            pass
        with telemetry.span("fit_batch", cat="batch"):
            pass
    (only,) = ring()
    assert only["name"] == "module_bind" and only["cat"] == "setup"
    assert only["args"] == {"parent": None, "depth": 0, "contexts": 1}
    assert not telemetry.trace_active()


@pytest.mark.parametrize("devices", [1, 4])
def test_fit_leaves_one_of_each_root_span(devices):
    ctx = mx.cpu(0) if devices == 1 else [mx.cpu(i) for i in range(devices)]
    mod = toy_fit(ctx=ctx)
    assert mod._cached_step is not None
    events = ring()
    assert {e["cat"] for e in events} == {"setup"}
    names = collections.Counter(e["name"] for e in events)
    assert names == collections.Counter(ROOTS + list(CHILDREN))
    by_name = {e["name"]: e for e in events}
    for name in ROOTS:
        args = by_name[name]["args"]
        assert args["parent"] is None and args["depth"] == 0
        assert args["module"] == id(mod)
    assert by_name["module_bind"]["args"]["contexts"] == devices
    assert by_name["module_bind"]["args"]["for_training"] is True
    for child, parent in CHILDREN.items():
        c, p = by_name[child], by_name[parent]
        assert c["args"]["parent"] == parent and c["args"]["depth"] == 1
        assert p["ts"] <= c["ts"] \
            and c["ts"] + c["dur"] <= p["ts"] + p["dur"]
    # in the order a start runs them
    order = [e["name"] for e in sorted(events, key=lambda e: e["ts"])
             if e["name"] in ROOTS]
    assert order == ROOTS
    # the step program compiled under its first step, and only there
    (row,) = [r for r in telemetry.compile_events()
              if r["watch"] == "module_cached_step"]
    assert row["span"] == "module_first_step"


def test_the_spmd_groups_step_build_places_the_parameters():
    """On the fused SPMD group the parameters go over the mesh when the
    step is built, so the first step times tracing, compiling and running
    and not their placement."""
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=4,
                                name="fc")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    mod = mx.mod.Module(net, context=[mx.cpu(i) for i in range(4)])
    mod.bind(data_shapes=[("data", (8, 6))],
             label_shapes=[("softmax_label", (8,))])
    mod.init_params()
    mod.init_optimizer()
    ex = mod._exec_group.execs[0]
    params = mod._exec_group.param_names

    def replicated():
        return [ex.arg_dict[n]._data.sharding == ex._replicated
                for n in params]

    assert not any(replicated())
    assert mod._get_cached_step() is not None
    assert all(replicated())
    (build,) = [e for e in ring("setup") if e["name"] == "module_step_build"]
    assert build["args"]["module"] == id(mod)


def test_an_epochs_end_is_no_start():
    """fit() re-sets the parameters at every epoch's end through
    set_params: the same work as init_params, and no set-up span."""
    toy_fit(epochs=3)
    names = collections.Counter(e["name"] for e in ring("setup"))
    assert names["module_init_params"] == 1
    assert names["init_params_host"] == names["init_params_place"] == 1
    assert names["module_first_step"] == 1


def test_ignored_calls_leave_no_span():
    mod = toy_fit()
    telemetry.reset()
    with pytest.warns(UserWarning, match="init_params ignored"):
        mod.init_params()
    mod.bind(data_shapes=mod.data_shapes, label_shapes=mod.label_shapes)
    mod.init_optimizer()
    assert ring() == []
    # a forced re-init is one
    mod.init_params(force_init=True)
    assert [e["name"] for e in ring()] == [
        "init_params_host", "init_params_place", "module_init_params"]


# ---- what a steady state pays --------------------------------------------

def test_steady_state_steps_call_neither_listener(monkeypatch):
    mod = toy_fit()
    batch = mx.io.DataBatch(
        [mx.nd.array(np.ones((8, 10), np.float32))],
        [mx.nd.array(np.zeros(8, np.float32))])
    for _ in range(3):                   # warm-up: every shape compiled
        mod._fit_step(batch)
        mod.get_outputs()[0].asnumpy()
    calls = []
    from jax._src import monitoring
    assert core._on_duration in monitoring.get_event_duration_listeners()
    assert core._on_event in monitoring.get_event_listeners()

    def counting(fn):
        def wrapped(*args, **kwargs):
            calls.append(args[0])
            return fn(*args, **kwargs)
        return wrapped

    # the listeners are registered by identity: swap what JAX calls
    for attr, fn in (("_event_duration_secs_listeners", core._on_duration),
                     ("_event_listeners", core._on_event)):
        monkeypatch.setattr(monitoring, attr, [
            counting(f) if f is fn else f
            for f in getattr(monitoring, attr)])
    rows = len(telemetry.compile_events())
    spans = len(ring())
    for _ in range(20):
        mod._fit_step(batch)
        mod.get_outputs()[0].asnumpy()
    assert calls == []
    assert len(telemetry.compile_events()) == rows and len(ring()) == spans
    # the counting wrappers do count when something compiles
    jax.jit(lambda x: x * 17)(jnp.ones(2)).block_until_ready()
    assert calls
