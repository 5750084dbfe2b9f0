"""tracecheck (the JX trace tier): per-rule fixtures + runtime hook.

Every AOT JX rule gets a seeded BAD program that fires and a clean twin
that stays quiet (ISSUE 5 acceptance) — the programs are traced for real
through ``tracecheck.trace_program`` (jax.jit + ShapeDtypeStruct, nothing
executed), not mocked jaxprs.  JX105 is exercised both as a unit
(``explain_retrace`` names the changed axis) and end-to-end through the
``MXNET_TRACECHECK`` compile hook off ``telemetry.watch_jit``.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import telemetry as tel
from mxnet_tpu.lint import tracecheck
from mxnet_tpu.lint.tracecheck import (TraceConfig, explain_retrace,
                                       run_rules, signature, trace_program)

# toy-sized thresholds: the fixtures below are a few KB, not the MBs the
# production defaults gate on
CFG = TraceConfig(const_bytes=256, donation_bytes=64, passthrough_bytes=64)


def rules_for(fn, args, select, config=CFG, kwargs=None):
    rec = trace_program("fixture", fn, args, kwargs)
    return [f.rule for f in run_rules(rec, select={select}, config=config)]


def spec(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


# ---------------------------------------------------------------------------
# JX101 baked-constant
# ---------------------------------------------------------------------------

def test_jx101_fires_on_closure_baked_array():
    table = jnp.asarray(np.ones((16, 16), np.float32))     # 1 KiB const

    def fwd(x):
        return x @ table

    assert "JX101" in rules_for(jax.jit(fwd), (spec((4, 16)),), "JX101")


def test_jx101_quiet_when_passed_as_argument():
    def fwd(x, table):
        return x @ table

    assert rules_for(jax.jit(fwd), (spec((4, 16)), spec((16, 16))),
                     "JX101") == []


def test_jx101_quiet_below_threshold():
    scale = jnp.asarray(np.float32(3.0))    # tiny closure scalar: fine

    def fwd(x):
        return x * scale

    assert rules_for(jax.jit(fwd), (spec((4, 16)),), "JX101") == []


# ---------------------------------------------------------------------------
# JX102 dtype-widening
# ---------------------------------------------------------------------------

@pytest.fixture
def x64():
    # f64 exists only with x64 enabled; put back what was there (the
    # package's own setting is on: a worker's later files count on it)
    was = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", was)


def test_jx102_fires_on_widening_from_f32_inputs(x64):
    def fwd(x):
        acc = x.astype(jnp.float64)          # the forgotten widening
        return (acc * 2.0).sum().astype(jnp.float32)

    assert "JX102" in rules_for(jax.jit(fwd), (spec((4, 16)),), "JX102")


def test_jx102_quiet_on_all_f32(x64):
    def fwd(x):
        return (x * 2.0).sum()

    assert rules_for(jax.jit(fwd), (spec((4, 16)),), "JX102") == []


def test_jx102_quiet_when_caller_asked_for_f64(x64):
    # wide INPUTS mean 64-bit was requested — not an accident to report
    def fwd(x):
        return (x * 2.0).sum()

    assert rules_for(jax.jit(fwd), (spec((4, 16), jnp.float64),),
                     "JX102") == []


# ---------------------------------------------------------------------------
# JX103 host-callback-in-hot-program
# ---------------------------------------------------------------------------

def test_jx103_fires_on_debug_print():
    def fwd(x):
        jax.debug.print("x sum {}", x.sum())
        return x * 2.0

    assert "JX103" in rules_for(jax.jit(fwd), (spec((4, 16)),), "JX103")


def test_jx103_fires_on_pure_callback():
    def fwd(x):
        y = jax.pure_callback(lambda a: np.asarray(a) * 2.0,
                              jax.ShapeDtypeStruct(x.shape, x.dtype), x)
        return y + 1.0

    assert "JX103" in rules_for(jax.jit(fwd), (spec((4, 16)),), "JX103")


def test_jx103_quiet_on_pure_program():
    def fwd(x):
        return x * 2.0

    assert rules_for(jax.jit(fwd), (spec((4, 16)),), "JX103") == []


# ---------------------------------------------------------------------------
# JX104 donation-waste
# ---------------------------------------------------------------------------

def test_jx104_fires_on_unaliasable_donation():
    # donated (64,) input but the only output is a scalar: freed for
    # nothing, and the caller lost the buffer
    def fwd(s):
        return s.sum()

    assert "JX104" in rules_for(jax.jit(fwd, donate_argnums=0),
                                (spec((64,)),), "JX104")


def test_jx104_fires_on_missed_donation():
    # b is donated, a is just as aliasable and large — one HBM copy wasted
    def fwd(a, b):
        return a + 1.0, b + 1.0

    assert "JX104" in rules_for(jax.jit(fwd, donate_argnums=1),
                                (spec((64,)), spec((64,))), "JX104")


def test_jx104_fires_on_passthrough_output():
    def fwd(a, b):
        return a, a + b

    assert "JX104" in rules_for(jax.jit(fwd),
                                (spec((64,)), spec((64,))), "JX104")


def test_jx104_quiet_on_full_donation():
    def fwd(a, b):
        return a + 1.0, b + 1.0

    assert rules_for(jax.jit(fwd, donate_argnums=(0, 1)),
                     (spec((64,)), spec((64,))), "JX104") == []


def test_jx104_quiet_on_donated_passthrough():
    # a donated pass-through aliases for free — nothing to report
    def fwd(a):
        return a, a.sum()

    assert rules_for(jax.jit(fwd, donate_argnums=0),
                     (spec((64,)),), "JX104") == []


# ---------------------------------------------------------------------------
# JX105 retrace-explainer
# ---------------------------------------------------------------------------

def test_jx105_names_the_changed_axis():
    old = signature((np.zeros((8, 64), np.float32),), {})
    new = signature((np.zeros((16, 64), np.float32),), {})
    msg = explain_retrace("step", [old], new)
    assert "axis 0: 8->16" in msg and "step" in msg


def test_jx105_names_dtype_and_static_changes():
    old = signature((np.zeros(4, np.float32),), {"mode": "train"})
    new_dtype = signature((np.zeros(4, np.float16),), {"mode": "train"})
    assert "float32->float16" in explain_retrace("s", [old], new_dtype)
    new_static = signature((np.zeros(4, np.float32),), {"mode": "eval"})
    assert "static value" in explain_retrace("s", [old], new_static)


def test_jx105_diffs_against_closest_variant():
    # two cached variants; the new call matches one except for ONE axis —
    # the diagnosis must name that axis, not diff the farther variant
    a = signature((np.zeros((8, 64), np.float32),), {})
    b = signature((np.zeros((8, 32), np.float16),), {})
    new = signature((np.zeros((9, 64), np.float32),), {})
    msg = explain_retrace("step", [a, b], new)
    assert "axis 0: 8->9" in msg and "float16" not in msg


def test_jx105_no_visible_change_message():
    sig = signature((np.zeros(4, np.float32),), {})
    assert "no visible" in explain_retrace("step", [sig], sig)


def test_runtime_hook_books_jx105_on_recompile(monkeypatch):
    monkeypatch.setenv("MXNET_TRACECHECK", "1")
    tel.refresh_from_env()
    tracecheck.reset_runtime()
    try:
        def fwd(x):
            return x * 2.0

        wf = tel.watch_jit(jax.jit(fwd), "tc_hook_step")
        before = tel.counter("tracecheck_findings")
        wf(jnp.ones((4, 8)))                  # first compile: no history
        wf(jnp.ones((6, 8)))                  # recompile -> JX105
        assert tel.counter("tracecheck_findings") >= before + 1
        from mxnet_tpu.telemetry import flight
        kinds = [e for e in flight._ring if e.get("kind") == "tracecheck"]
        assert any(e.get("name") == "JX105" for e in kinds)
    finally:
        monkeypatch.delenv("MXNET_TRACECHECK")
        tel.refresh_from_env()
        tracecheck.reset_runtime()


def test_runtime_hook_separates_programs_sharing_a_name(monkeypatch):
    """Two distinct jits under one watch name (a cached op's train/eval
    pair, every optimizer instance under 'optimizer_update_step') are
    separate compile caches: each one's FIRST compile must not read as a
    recompile of the other."""
    monkeypatch.setenv("MXNET_TRACECHECK", "1")
    tel.refresh_from_env()
    tracecheck.reset_runtime()
    try:
        wa = tel.watch_jit(jax.jit(lambda x: x * 2.0), "tc_shared_name")
        wb = tel.watch_jit(jax.jit(lambda x: x + 1.0), "tc_shared_name")
        before = tel.counter("tracecheck_findings")
        wa(jnp.ones((4, 8)))
        wb(jnp.ones((6, 8)))      # other program, other shape: no JX105
        assert tel.counter("tracecheck_findings") == before
    finally:
        monkeypatch.delenv("MXNET_TRACECHECK")
        tel.refresh_from_env()
        tracecheck.reset_runtime()


def test_runtime_hook_off_by_default(monkeypatch):
    monkeypatch.delenv("MXNET_TRACECHECK", raising=False)
    tel.refresh_from_env()
    tracecheck.reset_runtime()

    def fwd(x):
        return x + 1.0

    wf = tel.watch_jit(jax.jit(fwd), "tc_off_step")
    before = tel.counter("tracecheck_findings")
    wf(jnp.ones((4, 8)))
    wf(jnp.ones((6, 8)))
    assert tel.counter("tracecheck_findings") == before
    assert not tracecheck._SIG_HISTORY.get("tc_off_step")


# ---------------------------------------------------------------------------
# AOT driver plumbing
# ---------------------------------------------------------------------------

def test_scoped_entry_group_traces_only_its_programs():
    findings, names = tracecheck.check_entry_points(entries={"kvstore"})
    assert set(names) == {"kvstore_stack_sum", "kvstore_bucket_reduce"}
    assert findings == []


def test_cli_trace_rejects_unknown_group():
    from mxnet_tpu.lint import cli
    assert cli.main(["--trace", "nonesuch"]) == 2


def test_cli_trace_json_smoke():
    out = subprocess.run(
        [sys.executable, "-m", "mxnet_tpu.lint", "--trace", "kvstore",
         "-f", "json", "--no-baseline"],
        capture_output=True, text=True, timeout=240,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr
    payload = json.loads(out.stdout)
    assert payload["new"] == []
    assert "kvstore_stack_sum" in out.stderr      # coverage line


def test_provider_failure_suppresses_baseline_sweep(tmp_path, monkeypatch):
    """A full --trace run with a JX000 (a provider that didn't run) must
    NOT retire trace:// baseline entries: --write-baseline keeps the
    un-re-checked entry instead of silently dropping a group's ledger."""
    from mxnet_tpu.lint import cli
    from mxnet_tpu.lint.core import Finding
    baseline = tmp_path / "base.json"
    baseline.write_text(json.dumps({"version": 1, "entries": [
        {"rule": "JX104", "path": "trace://executor_train",
         "snippet": "donate-missed:arg[0]", "count": 1}]}))
    monkeypatch.setattr(
        tracecheck, "analyze_entry_points",
        lambda entries=None, select=None, memory=True,
        mem_baseline_path=None: (
            [Finding("JX000", "trace://executor", 0, 0, "provider failed",
                     snippet="provider:executor")], [], None))
    cli.main(["--trace", "--write-baseline", "--baseline", str(baseline)])
    kept = json.dumps(json.loads(baseline.read_text()))
    assert "trace://executor_train" in kept


def test_list_rules_shows_jx_catalogue():
    from mxnet_tpu.lint import cli
    import io
    from contextlib import redirect_stdout
    buf = io.StringIO()
    with redirect_stdout(buf):
        assert cli.main(["--list-rules"]) == 0
    text = buf.getvalue()
    for code in ("JX101", "JX102", "JX103", "JX104", "JX105"):
        assert code in text


# ---------------------------------------------------------------------------
# JX2xx SPMD fixtures: a live mesh + the substrate's shard_map
# ---------------------------------------------------------------------------

from jax.sharding import Mesh, PartitionSpec as P  # noqa: E402
from mxnet_tpu.parallel import mesh as mesh_mod  # noqa: E402
from mxnet_tpu.lint.tracecheck import (collective_sequence,  # noqa: E402
                                       run_group_rules)

# the JX203 fixtures are a few KB; the production 64 KiB floor would
# hide them
SPMD_CFG = TraceConfig(replication_bytes=256)


@pytest.fixture(scope="module")
def mesh():
    return Mesh(np.array(jax.devices()), ("x",))


def _smap(body, mesh, out_specs=P("x", None), check=None):
    return mesh_mod.shard_map(body, mesh=mesh, in_specs=P("x", None),
                              out_specs=out_specs, check=check)


def spmd_rules(fn, select, meta=None, name="fixture"):
    rec = trace_program(name, jax.jit(fn), (spec((8, 64)),), meta=meta)
    return [(f.rule, f.snippet)
            for f in run_rules(rec, select={select}, config=SPMD_CFG)]


# ---------------------------------------------------------------------------
# collective extraction: every lax collective, under whichever primitive
# name the installed jax binds it
# ---------------------------------------------------------------------------

_PAIR = [(0, 1), (1, 0)]
# name -> (body over a (4, 64) shard of two, the rendezvous expected)
_COLLECTIVE_BODIES = {
    "psum": (lambda s: jax.lax.psum(s, "x"), "psum"),
    "pmean": (lambda s: jax.lax.pmean(s, "x"), "psum"),
    "pmax": (lambda s: jax.lax.pmax(s, "x"), "pmax"),
    "pmin": (lambda s: jax.lax.pmin(s, "x"), "pmin"),
    "all_gather": (lambda s: jax.lax.all_gather(s, "x"), "all_gather"),
    "all_to_all": (lambda s: jax.lax.all_to_all(s.reshape(2, 2, 64), "x",
                                                0, 0), "all_to_all"),
    "ppermute": (lambda s: jax.lax.ppermute(s, "x", _PAIR), "ppermute"),
}


@pytest.mark.parametrize("check", [True, False],
                         ids=["check_vma", "unchecked"])
@pytest.mark.parametrize("name", sorted(_COLLECTIVE_BODIES))
def test_collectives_in_sees_each_collective(name, check):
    """One collective over ``x`` in a two-device shard_map body is one
    rendezvous over ``("x",)``, under the same name whether or not jax
    checks the body's varying axes (checked, ``lax.psum`` and ``pmean``
    bind ``psum_invariant``)."""
    body, rendezvous = _COLLECTIVE_BODIES[name]
    two = Mesh(np.array(jax.devices()[:2]), ("x",))
    prog = mesh_mod.shard_map(body, mesh=two, in_specs=P("x", None),
                              out_specs=P("x"), check=check)
    rec = trace_program("fixture", jax.jit(prog), (spec((8, 64)),))
    assert tracecheck._collectives_in(rec.jaxpr) == ((rendezvous, ("x",)),)


# ---------------------------------------------------------------------------
# JX201 collective-divergence
# ---------------------------------------------------------------------------

def test_jx201_fires_on_collective_under_one_cond_arm(mesh):
    """The canonical SPMD deadlock: ranks whose data makes the predicate
    disagree take different arms — one enters the psum rendezvous, its
    peers never do."""
    def prog(v):
        def body(s):
            pred = jnp.sum(s) > 0.0
            # both arms must have one type: the psum's result, the same
            # on every rank, is cast back to varying over "x" like ``t``
            return jax.lax.cond(
                pred,
                lambda t: mesh_mod.pvary(jax.lax.psum(t, "x"), ("x",)),
                lambda t: t, s)
        return _smap(body, mesh)(v)

    assert spmd_rules(prog, "JX201") == [("JX201", "cond-divergence")]


def test_jx201_quiet_on_where_skip_twin(mesh):
    """The fix the rule message prescribes: run the collective
    unconditionally, branch the VALUES with jnp.where."""
    def prog(v):
        def body(s):
            pred = jnp.sum(s) > 0.0
            return jnp.where(pred, jax.lax.psum(s, "x"), s)
        return _smap(body, mesh)(v)

    assert spmd_rules(prog, "JX201") == []


def test_jx201_quiet_when_arms_rendezvous_identically(mesh):
    """Both arms psum over the same axis: every rank meets the
    rendezvous whichever arm it takes — safe, must stay quiet."""
    def prog(v):
        def body(s):
            pred = jnp.sum(s) > 0.0
            return jax.lax.cond(pred,
                                lambda t: jax.lax.psum(t, "x"),
                                lambda t: jax.lax.psum(t * 2.0, "x"), s)
        return _smap(body, mesh)(v)

    assert spmd_rules(prog, "JX201") == []


def test_jx201_fires_on_collective_inside_while(mesh):
    """A while trip count is data-dependent by construction: ranks can
    run the rendezvous a different number of times."""
    def prog(v):
        def body(s):
            def w_body(c):
                i, t = c
                return i + 1, jax.lax.psum(t, "x") * 0.5

            def w_cond(c):
                i, t = c
                return (i < 4) & (jnp.sum(t) > 1.0)

            _i, out = jax.lax.while_loop(w_cond, w_body, (0, s))
            return out
        return _smap(body, mesh, check=False)(v)

    assert spmd_rules(prog, "JX201") == [("JX201", "while-collective")]


# ---------------------------------------------------------------------------
# JX202 collective-order
# ---------------------------------------------------------------------------

def test_jx202_fires_on_undeclared_axis(mesh):
    def prog(v):
        def body(s):
            return jax.lax.psum(s, "x")
        return _smap(body, mesh)(v)

    assert spmd_rules(prog, "JX202", meta={"mesh_axes": ("data",)}) \
        == [("JX202", "undeclared-axis:x")]


def test_jx202_quiet_on_declared_axis(mesh):
    def prog(v):
        def body(s):
            return jax.lax.psum(s, "x")
        return _smap(body, mesh)(v)

    assert spmd_rules(prog, "JX202", meta={"mesh_axes": ("x",)}) == []


def test_jx202_quiet_without_declared_axes(mesh):
    """No mesh_axes metadata means the provider opted out of the
    declared-axis contract — not an implicit declare-nothing."""
    def prog(v):
        def body(s):
            return jax.lax.psum(s, "x")
        return _smap(body, mesh)(v)

    assert spmd_rules(prog, "JX202", meta=None) == []


def _lane_pair(mesh, flip):
    perm = [(i, (i + 1) % mesh.devices.size)
            for i in range(mesh.devices.size)]

    def psum_then_permute(v):
        def body(s):
            return jax.lax.ppermute(jax.lax.psum(s, "x"), "x", perm)
        return _smap(body, mesh)(v)

    def permute_then_psum(v):
        def body(s):
            return jax.lax.psum(jax.lax.ppermute(s, "x", perm), "x")
        return _smap(body, mesh)(v)

    lane = {"lane": "fixture-lane"}
    a = trace_program("lane_a", jax.jit(psum_then_permute),
                      (spec((8, 64)),), meta=lane)
    b = trace_program("lane_b", jax.jit(
        permute_then_psum if flip else psum_then_permute),
        (spec((8, 64)),), meta=lane)
    return a, b


def test_jx202_group_fires_on_lane_order_divergence(mesh):
    """Two programs on one lane disagreeing on per-axis collective order
    is the cross-program deadlock: rank A runs P's psum while rank B
    runs Q's ppermute."""
    a, b = _lane_pair(mesh, flip=True)
    assert collective_sequence(a) == {"x": ("psum", "ppermute")}
    assert collective_sequence(b) == {"x": ("ppermute", "psum")}
    found = run_group_rules([a, b], select={"JX202"}, config=SPMD_CFG)
    assert [(f.rule, f.snippet) for f in found] \
        == [("JX202", "lane-order:fixture-lane:x")]


def test_jx202_group_quiet_on_identical_lane_order(mesh):
    a, b = _lane_pair(mesh, flip=False)
    assert run_group_rules([a, b], select={"JX202"}, config=SPMD_CFG) == []


# ---------------------------------------------------------------------------
# JX203 replication-waste
# ---------------------------------------------------------------------------

def test_jx203_fires_on_gathered_output(mesh):
    def prog(v):
        def body(s):
            return jax.lax.all_gather(s, "x", axis=0, tiled=True)
        return _smap(body, mesh, out_specs=P(None, None), check=False)(v)

    assert spmd_rules(prog, "JX203") == [("JX203", "gathered-output:x")]


def test_jx203_quiet_when_gather_is_reduced_before_return(mesh):
    def prog(v):
        def body(s):
            g = jax.lax.all_gather(s, "x", axis=0, tiled=True)
            return jnp.sum(g, axis=0)
        return _smap(body, mesh, out_specs=P(None), check=False)(v)

    assert spmd_rules(prog, "JX203") == []


def test_jx203_quiet_below_replication_threshold(mesh):
    """Same gathered output, production 64 KiB floor: a few-KB fixture
    is below the bar — the rule gates real HBM waste, not toys."""
    def prog(v):
        def body(s):
            return jax.lax.all_gather(s, "x", axis=0, tiled=True)
        return _smap(body, mesh, out_specs=P(None, None), check=False)(v)

    rec = trace_program("fixture", jax.jit(prog), (spec((8, 64)),))
    assert run_rules(rec, select={"JX203"}, config=TraceConfig()) == []


# ---------------------------------------------------------------------------
# JX204 memory-budget
# ---------------------------------------------------------------------------

from mxnet_tpu.lint.tracecheck import (check_memory,  # noqa: E402
                                       measure_programs,
                                       save_mem_baseline)


def _mem_record(name="mem_fixture"):
    def prog(x, w):
        return jnp.tanh(x @ w)
    # 128x128 f32 operands: ~196 KiB total, comfortably above the 4 KiB
    # absolute slack so a halved budget must trip the fractional band
    return trace_program(name, jax.jit(prog),
                         (spec((128, 128)), spec((128, 128))))


def test_jx204_quiet_within_budget(tmp_path):
    rec = _mem_record()
    baseline = save_mem_baseline(measure_programs([rec]),
                                 path=str(tmp_path / "mem.json"))
    findings, report = check_memory([rec], baseline, tolerance=0.25)
    assert findings == []
    entry = report["programs"][0]
    assert entry["name"] == "mem_fixture" and not entry["over_budget"]
    assert entry["budget_total_bytes"] == entry["total_bytes"]


def test_jx204_fires_when_over_budget(tmp_path):
    rec = _mem_record()
    measured = measure_programs([rec])
    measured["mem_fixture"]["total_bytes"] //= 2          # yesterday's
    baseline = save_mem_baseline(measured,                # smaller program
                                 path=str(tmp_path / "mem.json"))
    findings, report = check_memory([rec], baseline, tolerance=0.25)
    assert [(f.rule, f.snippet) for f in findings] \
        == [("JX204", "mem:over")]
    assert report["programs"][0]["over_budget"]


def test_jx204_tolerance_band_absorbs_growth(tmp_path):
    """The same halved budget passes under a wide MXNET_MEM_TOLERANCE:
    the band is the deliberate-growth knob, read per check."""
    rec = _mem_record()
    measured = measure_programs([rec])
    measured["mem_fixture"]["total_bytes"] //= 2
    baseline = save_mem_baseline(measured,
                                 path=str(tmp_path / "mem.json"))
    findings, _report = check_memory([rec], baseline, tolerance=2.0)
    assert findings == []


def test_jx204_tolerance_env_knob(tmp_path, monkeypatch):
    rec = _mem_record()
    measured = measure_programs([rec])
    measured["mem_fixture"]["total_bytes"] //= 2
    baseline = save_mem_baseline(measured,
                                 path=str(tmp_path / "mem.json"))
    monkeypatch.setenv("MXNET_MEM_TOLERANCE", "2.0")
    findings, _report = check_memory([rec], baseline)
    assert findings == []
    monkeypatch.setenv("MXNET_MEM_TOLERANCE", "0.01")
    findings, _report = check_memory([rec], baseline)
    assert [f.snippet for f in findings] == ["mem:over"]


def test_jx204_fires_on_unbudgeted_program(tmp_path):
    rec = _mem_record()
    baseline = save_mem_baseline({}, path=str(tmp_path / "mem.json"))
    findings, report = check_memory([rec], baseline)
    assert [(f.rule, f.snippet) for f in findings] \
        == [("JX204", "mem:unbudgeted")]
    assert report["programs"][0]["unbudgeted"]


def test_jx204_fires_on_specimen_count_drift(tmp_path):
    """Dropping a specimen must be as visible as growing one: the
    count-keyed budget fires when k changes, even if bytes shrink."""
    rec = _mem_record()
    measured = measure_programs([rec, _mem_record()])   # budget: k=2
    baseline = save_mem_baseline(measured,
                                 path=str(tmp_path / "mem.json"))
    findings, _report = check_memory([rec], baseline)   # traced: k=1
    assert "mem:specimens" in {f.snippet for f in findings}


def test_jx204_topology_mismatch_skips_comparison(tmp_path):
    """Memory bytes are a function of device count: a baseline captured
    on a different topology must be SKIPPED (gate exits 4 downstream),
    never compared against."""
    rec = _mem_record()
    measured = measure_programs([rec])
    measured["mem_fixture"]["total_bytes"] //= 2
    baseline = save_mem_baseline(measured, path=str(tmp_path / "mem.json"),
                                 n_devices=2)            # conftest pins 8
    findings, report = check_memory([rec], baseline)
    assert findings == []
    assert not report["topology_match"]
    assert report["programs"][0]["budget_total_bytes"] is None


def test_jx204_stale_budget_listed_on_full_run(tmp_path):
    rec = _mem_record()
    measured = measure_programs([rec])
    measured["renamed_away"] = dict(measured["mem_fixture"])
    baseline = save_mem_baseline(measured,
                                 path=str(tmp_path / "mem.json"))
    _f, report = check_memory([rec], baseline, full=True)
    assert report["stale_budgets"] == ["renamed_away"]
    _f, report = check_memory([rec], baseline, full=False)
    assert report["stale_budgets"] == []
