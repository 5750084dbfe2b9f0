"""graftlint: per-rule fixtures, suppressions, baseline, runtime sanitizer.

Every JG rule gets a firing (positive) and a non-firing (negative) fixture
snippet run through ``lint_source``; the sanitizer tests assert a planted
tracer leak raises under MXNET_SANITIZE=1 and is silent otherwise — the
same footgun the static JG001 fixture catches at review time (ISSUE 3
acceptance).
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.lint import (Baseline, RULES, lint_source, load_baseline,
                            repo_root)
from mxnet_tpu.lint import sanitizer

REPO = repo_root()


def codes(src, select=None):
    findings = lint_source(textwrap.dedent(src), path="fixture.py",
                          select=select)
    return [f.rule for f in findings]


# ---------------------------------------------------------------------------
# JG001 host-sync-under-trace
# ---------------------------------------------------------------------------

def test_jg001_fires_on_host_sync_in_jitted_fn():
    src = """
    import jax

    def step(x, arr):
        lr = float(arr.mean())        # host sync while tracing
        return x * lr

    step_jit = jax.jit(step)
    """
    assert "JG001" in codes(src, {"JG001"})


def test_jg001_fires_on_asnumpy_and_item():
    src = """
    import jax

    @jax.jit
    def fwd(x):
        host = x.asnumpy()
        s = x.item()
        return host, s
    """
    assert codes(src, {"JG001"}).count("JG001") == 2


def test_jg001_fires_in_nested_def():
    src = """
    import jax

    def build():
        def step(x):
            def inner(y):
                return y.asnumpy()
            return inner(x)
        return jax.jit(step)
    """
    assert "JG001" in codes(src, {"JG001"})


def test_jg001_silent_outside_trace_and_on_shapes():
    src = """
    import jax

    def step(x):
        n = int(x.shape[0])          # static under jit: fine
        return x * n

    step_jit = jax.jit(step)

    def eager(arr):
        return arr.asnumpy()          # not jitted: fine
    """
    assert codes(src, {"JG001"}) == []


# ---------------------------------------------------------------------------
# JG002 naked-jit
# ---------------------------------------------------------------------------

def test_jg002_fires_on_naked_jit_call_and_decorator():
    src = """
    import jax

    def f(x):
        return x + 1

    g = jax.jit(f)

    @jax.jit
    def h(x):
        return x * 2
    """
    assert codes(src, {"JG002"}).count("JG002") == 2


def test_jg002_silent_when_watched():
    src = """
    import jax
    from mxnet_tpu import telemetry as _tel

    def f(x):
        return x + 1

    g = _tel.watch_jit(jax.jit(f), "f_step")
    """
    assert codes(src, {"JG002"}) == []


# ---------------------------------------------------------------------------
# JG003 retrace-hazard
# ---------------------------------------------------------------------------

def test_jg003_fires_on_str_default_not_static():
    src = """
    import jax

    def step(x, mode="train", cfg={}):
        return x

    step_jit = jax.jit(step)
    """
    assert codes(src, {"JG003"}).count("JG003") == 2


def test_jg003_fires_on_kwonly_default():
    src = """
    import jax

    def step(x, *, mode="train"):
        return x

    step_jit = jax.jit(step)
    safe_jit = jax.jit(step, static_argnames=("mode",))
    """
    assert codes(src, {"JG003"}).count("JG003") == 1


def test_jg003_silent_when_declared_static():
    src = """
    import jax

    def step(x, mode="train"):
        return x

    step_jit = jax.jit(step, static_argnames=("mode",))
    other = jax.jit(lambda x: x)
    """
    assert codes(src, {"JG003"}) == []


# ---------------------------------------------------------------------------
# JG004 donation-after-use
# ---------------------------------------------------------------------------

def test_jg004_fires_on_read_after_donation():
    src = """
    import jax

    def step(p, g):
        return p - g

    step_jit = jax.jit(step, donate_argnums=(0,))

    def train(params, grads):
        out = step_jit(params, grads)
        return params.sum() + out     # params was donated!
    """
    assert "JG004" in codes(src, {"JG004"})


def test_jg004_silent_on_nested_def_rebinding_name():
    src = """
    import jax

    def step(p, g):
        return p - g

    step_jit = jax.jit(step, donate_argnums=(0,))

    def train(params, grads):
        out = step_jit(params, grads)
        def helper(params):          # fresh binding, not the donated buf
            return params * 2
        return helper(out)
    """
    assert codes(src, {"JG004"}) == []


def test_jg004_silent_on_rebind_idiom():
    src = """
    import jax

    def step(p, g):
        return p - g

    step_jit = jax.jit(step, donate_argnums=(0,))

    def train(params, grads):
        params = step_jit(params, grads)   # rebound from result: fine
        return params.sum()
    """
    assert codes(src, {"JG004"}) == []


# ---------------------------------------------------------------------------
# JG005 global-PRNG
# ---------------------------------------------------------------------------

def test_jg005_fires_on_module_state_rng():
    src = """
    import random
    import numpy as np

    def draw(shape):
        a = np.random.uniform(-1, 1, shape)
        random.shuffle(a)
        return a
    """
    assert codes(src, {"JG005"}).count("JG005") == 2


def test_jg005_silent_on_generators_and_framework_rng():
    src = """
    import numpy as np
    from mxnet_tpu import random as _random

    def draw(shape, seed):
        rng = np.random.default_rng(seed)
        st = np.random.RandomState(seed)
        host = _random.host_rng().uniform(-1, 1, shape)
        return rng.uniform(-1, 1, shape), st.rand(4), host
    """
    assert codes(src, {"JG005"}) == []


# ---------------------------------------------------------------------------
# JG006 env-read-in-hot-path
# ---------------------------------------------------------------------------

def test_jg006_fires_in_hot_function_and_loop():
    src = """
    import os

    def _limit():
        return int(os.environ.get("X_LIMIT", "8"))

    def step(xs):
        for x in xs:
            flag = os.environ.get("X_FLAG")       # in a loop
        return _limit()                           # helper on the step path
    """
    assert codes(src, {"JG006"}).count("JG006") == 2


def test_jg006_silent_for_module_level_cached_bool():
    src = """
    import os

    def _env_enabled():
        return os.environ.get("X_TELEMETRY", "0") == "1"

    _ENABLED = _env_enabled()

    def step(x):
        if _ENABLED:
            return x * 2
        return x
    """
    assert codes(src, {"JG006"}) == []


# ---------------------------------------------------------------------------
# JG007 unbounded-blocking-call (dist/engine/serving scope)
# ---------------------------------------------------------------------------

def _codes_at(src, path, select=None):
    return [f.rule for f in lint_source(textwrap.dedent(src), path=path,
                                        select=select)]


def test_jg007_fires_on_unbounded_recv_and_queue_get():
    src = """
    def pump(conn, task_queue):
        msg = conn.recv()
        item = task_queue.get()
        return msg, item
    """
    assert _codes_at(src, "mxnet_tpu/dist_ps.py",
                     {"JG007"}) == ["JG007", "JG007"]
    # same patterns inside the serving tier
    assert _codes_at(src, "mxnet_tpu/serving/batcher.py",
                     {"JG007"}) == ["JG007", "JG007"]


def test_jg007_silent_with_deadline_or_explicit_none():
    src = """
    def pump(conn, task_queue, d):
        a = conn.recv(timeout=5.0)
        b = conn.recv(timeout=None)      # documented-deliberate wait
        c = task_queue.get(timeout=1.0)
        e = task_queue.get(block=False)
        f = d.get("key")                 # dict .get, not a queue
        g = d.get("key", None)
        return a, b, c, e, f, g
    """
    assert _codes_at(src, "mxnet_tpu/dist_ps.py", {"JG007"}) == []


def test_jg007_scoped_to_dist_engine_serving():
    src = """
    def pump(conn, queue):
        return conn.recv(), queue.get()
    """
    # outside the transport/scheduling tier the rule stays quiet
    assert _codes_at(src, "mxnet_tpu/io.py", {"JG007"}) == []
    assert _codes_at(src, "tools/launch.py", {"JG007"}) == []
    assert _codes_at(src, "mxnet_tpu/engine.py",
                     {"JG007"}) == ["JG007", "JG007"]


# ---------------------------------------------------------------------------
# JG008 shard-map-outside-substrate
# ---------------------------------------------------------------------------

def test_jg008_fires_on_shard_map_import_forms():
    assert codes("""
    from jax.experimental.shard_map import shard_map
    """, {"JG008"}) == ["JG008"]
    assert codes("""
    from jax.experimental import shard_map
    """, {"JG008"}) == ["JG008"]
    assert codes("""
    import jax.experimental.shard_map as shmap
    """, {"JG008"}) == ["JG008"]


def test_jg008_fires_on_attribute_use():
    src = """
    import jax

    def split(fn, mesh, specs):
        return jax.experimental.shard_map.shard_map(
            fn, mesh=mesh, in_specs=specs, out_specs=specs)
    """
    assert codes(src, {"JG008"}) == ["JG008"]


def test_jg008_quiet_on_the_substrate_wrapper():
    # the blessed spelling: every caller goes through parallel/mesh.py
    src = """
    from mxnet_tpu.parallel import mesh as mesh_mod

    def split(fn, mesh, specs):
        return mesh_mod.shard_map(fn, mesh=mesh, in_specs=specs,
                                  out_specs=specs)
    """
    assert codes(src, {"JG008"}) == []


def test_jg008_exempt_inside_parallel_mesh():
    """parallel/mesh.py IS the substrate: the one module allowed to
    touch jax's shard_map surface."""
    src = """
    from jax.experimental.shard_map import shard_map
    """
    assert _codes_at(src, "mxnet_tpu/parallel/mesh.py", {"JG008"}) == []
    assert _codes_at(src, "mxnet_tpu/parallel/sharded.py",
                     {"JG008"}) == ["JG008"]


def test_jg008_inline_suppression():
    src = """
    from jax.experimental.shard_map import shard_map  # graftlint: disable=JG008
    """
    assert codes(src, {"JG008"}) == []


def test_jg007_repo_has_no_unannotated_blocking_calls():
    """The tentpole burn-down: every remaining unbounded wait in the
    dist/engine/serving tier is either deadline-bounded, an explicit
    ``timeout=None``, or carries a justified inline suppression —
    nothing is baselined."""
    from mxnet_tpu.lint import lint_paths
    findings = lint_paths([os.path.join(REPO, "mxnet_tpu")],
                          select={"JG007"}, rel_root=REPO)
    assert not findings, "\n".join(f.format_text() for f in findings)


# ---------------------------------------------------------------------------
# suppressions / baseline / CLI
# ---------------------------------------------------------------------------

def test_inline_suppression_same_line_and_line_above():
    src = """
    import numpy as np

    def draw(shape):
        a = np.random.uniform(0, 1, shape)  # graftlint: disable=JG005
        # graftlint: disable=JG005
        b = np.random.normal(0, 1, shape)
        c = np.random.rand(4)               # graftlint: disable=JG001
        return a, b, c
    """
    found = codes(src, {"JG005"})
    assert found == ["JG005"]          # only the un-suppressed c-line


def test_suppression_skips_interleaved_comment_and_blank_lines():
    src = """
    import numpy as np

    def draw(shape):
        # graftlint: disable=JG005
        # justification may also come AFTER the directive

        a = np.random.uniform(0, 1, shape)
        return a
    """
    assert codes(src, {"JG005"}) == []


def test_suppression_on_wrapped_statement_and_with_justification():
    src = """
    import numpy as np

    def draw(shape):
        a = np.random.uniform(
            -1, 1, shape)  # graftlint: disable=JG005
        b = np.random.rand(4)  # graftlint: disable=JG005 legacy draw
        return a, b
    """
    assert codes(src, {"JG005"}) == []


def test_suppression_disable_all():
    src = """
    import numpy as np
    a = np.random.rand(4)  # graftlint: disable=all
    """
    assert codes(src) == []


def test_baseline_round_trip(tmp_path):
    src = textwrap.dedent("""
    import numpy as np
    a = np.random.rand(4)
    b = np.random.rand(4)
    """)
    findings = lint_source(src, path="mod.py")
    assert len(findings) == 2
    bl = Baseline.from_findings(findings)
    path = tmp_path / "bl.json"
    bl.save(str(path))
    loaded = load_baseline(str(path))
    new, matched, stale = loaded.apply(findings)
    assert new == [] and len(matched) == 2 and stale == {}
    # a third identical draw exceeds the baselined count and fires
    findings3 = lint_source(src + "c = np.random.rand(4)\n", path="mod.py")
    new, matched, stale = loaded.apply(findings3)
    assert len(new) == 1 and len(matched) == 2
    # removing all draws leaves the baseline stale
    new, matched, stale = loaded.apply([])
    assert new == [] and matched == [] and sum(stale.values()) == 2


def test_every_rule_registered_with_rationale():
    assert set(RULES) == {"JG001", "JG002", "JG003", "JG004", "JG005",
                          "JG006", "JG007", "JG008", "JG009", "JG010",
                          "JG011"}
    for rule in RULES.values():
        assert rule.name and rule.rationale


def test_cli_clean_against_checked_in_baseline():
    """ISSUE 3 acceptance: the tools CLI exits 0 on mxnet_tpu/ against the
    checked-in LINT_BASELINE.json, and --check-baseline finds no rot."""
    tool = os.path.join(REPO, "tools", "graftlint.py")
    for args in (["mxnet_tpu"], ["--check-baseline"]):
        proc = subprocess.run([sys.executable, tool] + args, cwd=REPO,
                              capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stdout + proc.stderr


def test_cli_json_format(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("import numpy as np\nx = np.random.rand(3)\n")
    tool = os.path.join(REPO, "tools", "graftlint.py")
    proc = subprocess.run(
        [sys.executable, tool, str(bad), "--no-baseline", "-f", "json"],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1
    payload = json.loads(proc.stdout)
    assert payload["new"] and payload["new"][0]["rule"] == "JG005"


def test_check_baseline_detects_stale(tmp_path):
    clean = tmp_path / "clean.py"
    clean.write_text("x = 1\n")
    stale_bl = tmp_path / "bl.json"
    stale_bl.write_text(json.dumps({"version": 1, "entries": [
        {"rule": "JG005", "path": "gone.py",
         "snippet": "x = np.random.rand(3)", "count": 1}]}))
    tool = os.path.join(REPO, "tools", "graftlint.py")
    proc = subprocess.run(
        [sys.executable, tool, str(clean), "--baseline", str(stale_bl),
         "--check-baseline"],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1
    assert "stale" in proc.stdout


# ---------------------------------------------------------------------------
# runtime sanitizer
# ---------------------------------------------------------------------------

@pytest.fixture
def sanitize_raise():
    sanitizer.configure(mode="raise")
    yield
    sanitizer.configure(mode="off")


def test_sanitizer_off_is_silent():
    """The planted sync-under-trace passes silently with MXNET_SANITIZE
    unset — the hazard jax itself never reports."""
    import jax
    assert sanitizer.mode() == "off"
    const = nd.array(np.ones((2, 2)))

    def f(v):
        _ = const.asnumpy()           # concrete under trace: silently baked
        return v + 1

    jax.jit(f)(jax.numpy.ones(3))     # no error


def test_sanitizer_catches_sync_under_trace(sanitize_raise):
    import jax
    const = nd.array(np.ones((2, 2)))

    def f(v):
        _ = const.asnumpy()
        return v + 1

    with pytest.raises(sanitizer.SanitizerError, match="under trace"):
        jax.jit(f)(jax.numpy.ones(5))


def test_sanitizer_catches_tracer_leak(sanitize_raise):
    import jax
    leaked = []

    def f(v):
        leaked.append(nd.NDArray(v))
        return v * 2

    jax.jit(f)(jax.numpy.ones(3))
    with pytest.raises(sanitizer.SanitizerError, match="tracer leak"):
        leaked[0].asnumpy()


def test_sanitizer_env_gate_subprocess(tmp_path):
    """MXNET_SANITIZE=1 in the environment arms the check at import."""
    script = tmp_path / "leak.py"
    script.write_text(textwrap.dedent("""
        import jax, numpy as np
        import mxnet_tpu as mx
        from mxnet_tpu import nd
        from mxnet_tpu.lint.sanitizer import SanitizerError
        const = nd.array(np.ones((2, 2)))
        def f(v):
            _ = const.asnumpy()
            return v + 1
        try:
            jax.jit(f)(jax.numpy.ones(3))
        except SanitizerError:
            print("CAUGHT")
        else:
            print("MISSED")
    """))
    env = dict(os.environ, MXNET_SANITIZE="1", JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH",
                                                             ""))
    proc = subprocess.run([sys.executable, str(script)], env=env,
                          cwd=REPO, capture_output=True, text=True,
                          timeout=300)
    assert "CAUGHT" in proc.stdout, proc.stdout + proc.stderr


def test_sanitizer_warn_mode_logs_instead(sanitize_raise, caplog):
    import jax
    sanitizer.configure(mode="warn")
    const = nd.array(np.ones((2, 2)))

    def f(v):
        _ = const.asnumpy()
        return v + 1

    import logging
    with caplog.at_level(logging.WARNING, logger="mxnet_tpu.sanitizer"):
        jax.jit(f)(jax.numpy.ones(7))
    assert any("under trace" in r.message for r in caplog.records)


@pytest.mark.parametrize("mode", ["raise", "warn"])
def test_sanitizer_says_when_it_cannot_see(mode, sanitize_raise, monkeypatch,
                                           caplog):
    """jax moved the trace-state probe: the check is blind, and that is a
    violation of its own — not a silent pass of every sync under a trace."""
    import logging

    def moved():
        raise AttributeError("module 'jax._src.core' has no attribute "
                             "'trace_state_clean'")

    monkeypatch.setattr(sanitizer, "_trace_state_probe", moved, raising=False)
    sanitizer.configure(mode=mode)
    const = nd.array(np.ones((2, 2)))
    with caplog.at_level(logging.WARNING, logger="mxnet_tpu.sanitizer"):
        if mode == "raise":
            with pytest.raises(sanitizer.SanitizerError, match="blind"):
                const.asnumpy()
        else:
            const.asnumpy()
            const.asnumpy()
    blind = [r for r in caplog.records if "blind" in r.getMessage()]
    assert len(blind) == (1 if mode == "warn" else 0)


# ---------------------------------------------------------------------------
# engine happens-before checker
# ---------------------------------------------------------------------------

def test_engine_hb_clean_under_sanitizer(sanitize_raise):
    """A well-declared task graph runs clean under the checker."""
    eng = mx.engine.ThreadedEngine(num_workers=2)
    try:
        v1, v2 = eng.new_variable(), eng.new_variable()
        order = []
        for i in range(8):
            eng.push(lambda i=i: order.append(i), mutable_vars=(v1,))
        eng.push(lambda: order.append("r"), const_vars=(v1,),
                 mutable_vars=(v2,))
        eng.wait_for_all()
        assert order[:8] == list(range(8))     # write serialization held
    finally:
        eng.close()


def test_engine_hb_concurrent_pushers_no_false_positive(sanitize_raise):
    """Ticket issuance and the native enqueue share one push scope, so
    racing pushers can't interleave ticket order against engine order
    (which would raise on a perfectly correct program)."""
    import threading
    eng = mx.engine.ThreadedEngine(num_workers=4)
    try:
        v = eng.new_variable()
        out = []
        def pusher(tid):
            for i in range(25):
                eng.push(lambda t=tid, i=i: out.append((t, i)),
                         mutable_vars=(v,))
        threads = [threading.Thread(target=pusher, args=(t,))
                   for t in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        eng.wait_for_all()            # raises on any spurious violation
        assert len(out) == 100
    finally:
        eng.close()


def test_engine_hb_catches_out_of_order_write(sanitize_raise):
    """Violations surface at the next wait point: simulate a scheduler bug
    by running guarded tasks directly out of push order."""
    eng = mx.engine.ThreadedEngine(num_workers=1)
    try:
        v = eng.new_variable()
        t1 = sanitizer.guard_task(eng, lambda: None, (), (v,))
        t2 = sanitizer.guard_task(eng, lambda: None, (), (v,))
        with pytest.raises(sanitizer.SanitizerError,
                           match="out of push order"):
            t2()                      # write 1 landing before write 0
        del t1
    finally:
        eng.close()


def test_engine_hb_cancelled_push_does_not_poison_ordering(sanitize_raise):
    """A push that fails before reaching the engine rolls its ticket back
    (engine.push's except path calls guarded.cancel()), so later writes to
    the same var don't read as out-of-order forever."""
    eng = mx.engine.ThreadedEngine(num_workers=1)
    try:
        v = eng.new_variable()
        dead = sanitizer.guard_task(eng, lambda: None, (), (v,))
        dead.cancel()                 # the native enqueue "raised"
        ran = []
        nxt = sanitizer.guard_task(eng, lambda: ran.append(1), (), (v,))
        nxt()                         # must NOT raise out-of-push-order
        assert ran == [1]
        # delete_variable prunes the (drained) ledger entry
        eng.delete_variable(v)
        assert int(v) not in getattr(eng, "_graftlint_hb").vars
        # deletion with a pending write defers until that write drains
        w = eng.new_variable()
        t1 = sanitizer.guard_task(eng, lambda: None, (), (w,))
        t2 = sanitizer.guard_task(eng, lambda: None, (), (w,))
        t1()
        eng.delete_variable(w)        # t2 still holds ticket 1
        assert int(w) in eng._graftlint_hb.vars
        t2()                          # must not misreport push order...
        assert int(w) not in eng._graftlint_hb.vars   # ...and reaps
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# cross-module project linking (ISSUE 5 satellite)
# ---------------------------------------------------------------------------

from mxnet_tpu.lint import lint_sources  # noqa: E402


def project_codes(named, select=None):
    findings = lint_sources(
        [(path, textwrap.dedent(src)) for path, src in named], select)
    return [(f.path, f.rule) for f in findings]


def test_cross_module_jg001_through_import_edge():
    """A jitted step in one file calls a helper imported from another:
    the host sync inside the helper fires JG001 in the helper's file."""
    helper = """
    def normalize(x):
        scale = float(x.mean())       # host sync when called under trace
        return x / scale
    """
    step = """
    import jax
    from mxnet_tpu.helpers_mod import normalize

    @jax.jit
    def step(x):
        return normalize(x) * 2.0
    """
    found = project_codes([("mxnet_tpu/helpers_mod.py", helper),
                           ("mxnet_tpu/step_mod.py", step)], {"JG001"})
    assert ("mxnet_tpu/helpers_mod.py", "JG001") in found


def test_cross_module_jg001_quiet_without_traced_caller():
    """Same two files, but the caller is NOT jitted: the helper's float()
    is ordinary eager host code — no finding in either file."""
    helper = """
    def normalize(x):
        scale = float(x.mean())
        return x / scale
    """
    caller = """
    from mxnet_tpu.helpers_mod import normalize

    def evaluate(x):
        return normalize(x) * 2.0
    """
    assert project_codes([("mxnet_tpu/helpers_mod.py", helper),
                          ("mxnet_tpu/eval_mod.py", caller)],
                         {"JG001"}) == []


def test_cross_module_jg006_hot_path_through_import_edge():
    """step() in one file calls a flag helper imported from another: the
    env read inside the helper is now on the step path -> JG006 there."""
    flags = """
    import os

    def fused_enabled():
        return os.environ.get("FUSED", "1") == "1"
    """
    trainer = """
    from mxnet_tpu.flags_mod import fused_enabled

    def step(batch):
        if fused_enabled():
            return batch
        return None
    """
    found = project_codes([("mxnet_tpu/flags_mod.py", flags),
                           ("mxnet_tpu/trainer_mod.py", trainer)],
                          {"JG006"})
    assert ("mxnet_tpu/flags_mod.py", "JG006") in found


def test_cross_module_jg006_quiet_off_the_hot_path():
    flags = """
    import os

    def fused_enabled():
        return os.environ.get("FUSED", "1") == "1"
    """
    setup = """
    from mxnet_tpu.flags_mod import fused_enabled

    def build_config():
        return {"fused": fused_enabled()}
    """
    assert project_codes([("mxnet_tpu/flags_mod.py", flags),
                          ("mxnet_tpu/setup_mod.py", setup)],
                         {"JG006"}) == []


def test_cross_module_relative_import_from_package_init():
    """An __init__.py IS its package: ``from .flags_mod import f`` there
    resolves against the package itself, not its parent — the edge from a
    hot def in __init__.py must reach the helper's file."""
    flags = """
    import os

    def fused_enabled():
        return os.environ.get("FUSED", "1") == "1"
    """
    init = """
    from .flags_mod import fused_enabled

    def step(batch):
        if fused_enabled():
            return batch
        return None
    """
    found = project_codes([("mxnet_tpu/flags_mod.py", flags),
                           ("mxnet_tpu/__init__.py", init)],
                          {"JG006"})
    assert ("mxnet_tpu/flags_mod.py", "JG006") in found


def test_cross_module_linking_is_def_precise():
    """A jitted inner `def step` must not smear traced-ness onto an
    unrelated same-named eager method (the ShardedTrainer.step false
    positive): the eager step's float() stays quiet, in a linked
    multi-module project."""
    sharded = """
    import jax

    def make_step(fn):
        def step(params, batch):
            return fn(params, batch)
        return jax.jit(step)

    class Trainer:
        def step(self, batch):
            loss = self._fn(batch)
            return float(loss)        # step-boundary sync: legitimate
    """
    other = """
    from mxnet_tpu.sharded_mod import make_step

    def build(fn):
        return make_step(fn)
    """
    assert project_codes([("mxnet_tpu/sharded_mod.py", sharded),
                          ("mxnet_tpu/build_mod.py", other)],
                         {"JG001"}) == []


def test_single_file_scan_has_no_cross_module_annotations():
    """lint_source (one module) must behave exactly as before the
    project linker existed — linking requires >= 2 modules."""
    src = """
    import os

    def helper():
        return os.environ.get("FLAG")
    """
    assert codes(src, {"JG006"}) == []


# ---------------------------------------------------------------------------
# --diff mode (ISSUE 5 satellite): pre-commit-speed scans
# ---------------------------------------------------------------------------

def _git(repo, *argv):
    subprocess.run(
        ["git", "-C", str(repo)] + list(argv), check=True,
        capture_output=True,
        env=dict(os.environ, GIT_AUTHOR_NAME="t", GIT_AUTHOR_EMAIL="t@t",
                 GIT_COMMITTER_NAME="t", GIT_COMMITTER_EMAIL="t@t"))


def _run_cli(argv):
    import io
    from contextlib import redirect_stdout
    from mxnet_tpu.lint import cli
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = cli.main(argv)
    return rc, buf.getvalue()


def test_diff_mode_lints_only_changed_files(tmp_path, monkeypatch):
    """--diff <ref> scans exactly the .py files changed vs the ref: a
    committed-dirty-but-untouched file is skipped, a working-tree edit is
    caught — the contract that makes it safe as a fast pre-commit hook."""
    from mxnet_tpu.lint import cli
    pkg = tmp_path / "mxnet_tpu"
    pkg.mkdir()
    _git(tmp_path, "init", "-q")
    (pkg / "changed.py").write_text("x = 1\n")
    (pkg / "legacy.py").write_text(
        "import numpy as np\nv = np.random.rand(3)\n")       # JG005
    _git(tmp_path, "add", "-A")
    _git(tmp_path, "commit", "-qm", "seed")
    monkeypatch.setattr(cli, "repo_root", lambda: str(tmp_path))

    # nothing changed: clean exit, nothing scanned (NOT a usage error)
    rc, out = _run_cli(["--diff", "HEAD", "--no-baseline"])
    assert rc == 0 and "no changed Python files" in out

    # a working-tree edit introduces a finding -> caught; legacy.py's
    # pre-existing finding is out of the diff -> not reported
    (pkg / "changed.py").write_text(
        "import numpy as np\ny = np.random.rand(3)\n")
    rc, out = _run_cli(["--diff", "HEAD", "--no-baseline", "-f", "json"])
    assert rc == 1
    paths = {f["path"] for f in json.loads(out)["new"]}
    assert paths == {"mxnet_tpu/changed.py"}


def test_diff_mode_bad_ref_is_usage_error(tmp_path, monkeypatch):
    from mxnet_tpu.lint import cli
    (tmp_path / "mxnet_tpu").mkdir()
    _git(tmp_path, "init", "-q")
    monkeypatch.setattr(cli, "repo_root", lambda: str(tmp_path))
    rc, _out = _run_cli(["--diff", "no-such-ref", "--no-baseline"])
    assert rc == 2


def test_diff_mode_bad_path_is_usage_error(tmp_path, monkeypatch):
    """A typo'd scan root under --diff must stay exit 2 — falling through
    to 'no changed Python files' + exit 0 would silently disable lint in
    a pre-commit hook forever."""
    from mxnet_tpu.lint import cli
    (tmp_path / "mxnet_tpu").mkdir()
    _git(tmp_path, "init", "-q")
    monkeypatch.setattr(cli, "repo_root", lambda: str(tmp_path))
    rc, _out = _run_cli(["--diff", "HEAD", "--no-baseline",
                         str(tmp_path / "mxnet_tpo")])
    assert rc == 2


def test_diff_mode_catches_untracked_files(tmp_path, monkeypatch):
    """A brand-new file that was never ``git add``-ed is exactly what a
    pre-commit run must see — ``git diff`` alone would skip it."""
    from mxnet_tpu.lint import cli
    pkg = tmp_path / "mxnet_tpu"
    pkg.mkdir()
    _git(tmp_path, "init", "-q")
    (pkg / "old.py").write_text("x = 1\n")
    _git(tmp_path, "add", "-A")
    _git(tmp_path, "commit", "-qm", "seed")
    monkeypatch.setattr(cli, "repo_root", lambda: str(tmp_path))

    (pkg / "brand_new.py").write_text(
        "import numpy as np\nz = np.random.rand(3)\n")        # JG005
    rc, out = _run_cli(["--diff", "HEAD", "--no-baseline", "-f", "json"])
    assert rc == 1
    paths = {f["path"] for f in json.loads(out)["new"]}
    assert paths == {"mxnet_tpu/brand_new.py"}


def test_trace_rejects_paths_plus_diff_as_usage_error(capsys):
    """Two scopes (entry groups AND --diff) would silently intersect —
    the CLI must refuse rather than guess."""
    rc, _out = _run_cli(["--trace", "--diff", "HEAD", "guardian"])
    assert rc == 2
    assert "OR --diff" in capsys.readouterr().err


def test_groups_for_paths_maps_providers_to_entry_groups():
    from mxnet_tpu.lint import tracecheck
    assert tracecheck.groups_for_paths(["mxnet_tpu/guardian.py"]) \
        == {"guardian"}
    assert tracecheck.groups_for_paths(
        ["mxnet_tpu/models/transformer.py", "README.md"]) \
        == {"transformer"}
    assert tracecheck.groups_for_paths(["docs/LINT.md"]) == set()
    # a change to the analyzer itself dirties every verdict
    assert tracecheck.groups_for_paths(["mxnet_tpu/lint/tracecheck.py"]) \
        == {g for g, _m in tracecheck.ENTRY_POINTS}


def test_groups_for_paths_full_sweep_for_opprof():
    """A cost-model or attribution change invalidates EVERY perf
    verdict, not one entry group — opprof/costs edits map to the full
    re-sweep exactly like an analyzer edit does."""
    from mxnet_tpu.lint import tracecheck
    every = {g for g, _m in tracecheck.ENTRY_POINTS}
    assert tracecheck.groups_for_paths(
        ["mxnet_tpu/telemetry/opprof.py"]) == every
    assert tracecheck.groups_for_paths(
        ["mxnet_tpu/telemetry/costs.py", "README.md"]) == every
    # other telemetry modules stay out of the blast radius
    assert tracecheck.groups_for_paths(
        ["mxnet_tpu/telemetry/flight.py"]) == set()


def _tmp_trace_repo(tmp_path):
    """A throwaway git repo whose file layout mirrors the provider
    paths groups_for_paths keys on (content never imported — the trace
    tier loads the REAL modules; only the diff scoping is under test)."""
    pkg = tmp_path / "mxnet_tpu"
    pkg.mkdir()
    _git(tmp_path, "init", "-q")
    (pkg / "guardian.py").write_text("# provider stand-in\n")
    (tmp_path / "README.md").write_text("seed\n")
    _git(tmp_path, "add", "-A")
    _git(tmp_path, "commit", "-qm", "seed")
    return pkg


def test_trace_diff_scopes_to_changed_providers(tmp_path, monkeypatch,
                                                capsys):
    """--diff parity for the trace tier: a working-tree edit to a
    provider module re-checks exactly that entry group's programs."""
    from mxnet_tpu.lint import cli
    pkg = _tmp_trace_repo(tmp_path)
    monkeypatch.setattr(cli, "repo_root", lambda: str(tmp_path))

    (pkg / "guardian.py").write_text("# provider stand-in, edited\n")
    rc, _out = _run_cli(["--trace", "--diff", "HEAD", "--no-baseline"])
    err = capsys.readouterr().err
    assert rc == 0
    assert "entry group(s): guardian" in err
    assert "guardian_verdict" in err          # the group's program ran
    assert "transformer_train_step" not in err  # out-of-scope group didn't


def test_trace_diff_with_no_changed_providers_is_clean_noop(
        tmp_path, monkeypatch, capsys):
    """An edit that touches no provider (docs, README) exits 0 with an
    explicit 'nothing to trace' note — NOT a full sweep, NOT an error."""
    from mxnet_tpu.lint import cli
    _tmp_trace_repo(tmp_path)
    monkeypatch.setattr(cli, "repo_root", lambda: str(tmp_path))

    (tmp_path / "README.md").write_text("edited\n")
    rc, out = _run_cli(["--trace", "--diff", "HEAD", "--no-baseline"])
    capsys.readouterr()
    assert rc == 0
    assert "no changed trace providers" in out


def test_trace_diff_bad_ref_is_usage_error(tmp_path, monkeypatch,
                                           capsys):
    from mxnet_tpu.lint import cli
    _tmp_trace_repo(tmp_path)
    monkeypatch.setattr(cli, "repo_root", lambda: str(tmp_path))
    rc, _out = _run_cli(["--trace", "--diff", "no-such-ref",
                         "--no-baseline"])
    capsys.readouterr()
    assert rc == 2
