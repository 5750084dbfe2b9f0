"""Per-op roofline attribution (telemetry.opprof): the cost model sees
what the HLO does.

Three synthetic programs with KNOWN rooflines probe the attribution
end-to-end through the real trace->compile->parse path (no mocked HLO):

* a dot-heavy matmul whose arithmetic intensity sits far above the CPU
  machine balance — must classify ``dot`` (or a dot-bearing fusion) and
  read compute-bound;
* a big elementwise add at intensity ~0.08 FLOP/B — must read
  HBM-bound;
* a psum under the substrate's shard_map on the 8-device test mesh —
  must surface a ``collective`` unit bound by ``comm``.

Plus the candidate ranking over synthetic attributed sets and the
device->timeseries drift feed.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from mxnet_tpu.lint import tracecheck
from mxnet_tpu.parallel import mesh as mesh_mod
from mxnet_tpu.telemetry import costs, opprof


def analyze(name, fn, args):
    rec = tracecheck.trace_program(name, jax.jit(fn), args)
    analysis, compiled = opprof.analyze_record(rec, costs.peaks())
    assert compiled is not None, "%s did not compile" % name
    assert analysis is not None
    return analysis


# ---------------------------------------------------------------------------
# op-class + roofline bucketing
# ---------------------------------------------------------------------------

def test_dot_heavy_program_reads_compute_bound():
    a = jnp.ones((256, 256), jnp.float32)
    analysis = analyze("opprof_dot", lambda x, y: x @ y, (a, a))
    dots = [u for u in analysis["units"]
            if u["op_class"] in ("dot", "fusion") and u["flops"] > 1e6]
    assert dots, "no dot-bearing unit found: %r" % (
        [(u["unit"], u["op_class"]) for u in analysis["units"]])
    top = max(dots, key=lambda u: u["flops"])
    # 2*256^3 flops over ~3*256*256*4 bytes: intensity ~40 FLOP/B,
    # far above the CPU balance of 2
    assert top["intensity"] > costs.machine_balance()
    assert top["bound"] == "compute"
    assert top["flops"] >= 2 * 256 ** 3
    assert top["ceiling"] == costs.peaks()["flops"]


def test_bandwidth_bound_program_reads_hbm():
    x = jnp.ones((1024 * 1024,), jnp.float32)
    analysis = analyze("opprof_bw", lambda a, b: a + b, (x, x))
    adds = [u for u in analysis["units"]
            if u["op_class"] in ("elementwise", "fusion")]
    assert adds
    top = max(adds, key=lambda u: u["bytes"])
    # 1 flop per element over 12 bytes moved: intensity ~0.08
    assert top["intensity"] < costs.machine_balance()
    assert top["bound"] == "hbm"
    # the slope region of the roofline: ceiling = intensity * HBM peak
    assert top["ceiling"] < costs.peaks()["flops"]


def test_collective_program_reads_comm():
    mesh = Mesh(np.array(jax.devices()), ("x",))

    def body(x):
        return jax.lax.psum(x, "x")

    fn = mesh_mod.shard_map(body, mesh=mesh, in_specs=P("x", None),
                            out_specs=P(None, None))
    x = jnp.ones((8, 64), jnp.float32)
    analysis = analyze("opprof_coll", fn, (x,))
    colls = [u for u in analysis["units"]
             if u["op_class"] == "collective"]
    assert colls, "no collective unit in: %r" % (
        [(u["unit"], u["opcode"]) for u in analysis["units"]])
    assert all(u["bound"] == "comm" for u in colls)
    assert all(u["ceiling"] == costs.peaks()["ici_bw"] for u in colls)
    assert all(u["ceiling_kind"] == "bytes_per_s" for u in colls)


def test_shares_sum_to_one_per_program():
    a = jnp.ones((64, 64), jnp.float32)

    def mixed(x, y):
        z = jnp.tanh(x @ y)
        return z.sum() + (x * y).mean()

    analysis = analyze("opprof_mixed", mixed, (a, a))
    assert len(analysis["units"]) > 1
    total = sum(u["share"] for u in analysis["units"])
    assert total == pytest.approx(1.0, abs=1e-6)
    assert all(0.0 <= u["share"] <= 1.0 for u in analysis["units"])


def test_classify_table():
    assert opprof.classify("dot") == "dot"
    assert opprof.classify("convolution") == "conv"
    assert opprof.classify("fusion") == "fusion"
    assert opprof.classify("while") == "fusion"
    assert opprof.classify("all-reduce") == "collective"
    assert opprof.classify("reduce-scatter") == "collective"
    assert opprof.classify("collective-permute") == "collective"
    assert opprof.classify("reduce") == "reduce"
    assert opprof.classify("add") == "elementwise"
    assert opprof.classify("exponential") == "elementwise"
    assert opprof.classify("parameter") == "other"


def test_parse_hlo_handles_tuple_operands_and_fusions():
    text = """\
HloModule m

%fused_computation.1 (p0: f32[16,16], p1: f32[16,16]) -> f32[16,16] {
  %p0 = f32[16,16]{1,0} parameter(0)
  %p1 = f32[16,16]{1,0} parameter(1)
  ROOT %add.1 = f32[16,16]{1,0} add(%p0, %p1)
}

ENTRY %main.9 (a: f32[16,16], t: (s32[], f32[16,16])) -> f32[16,16] {
  %a = f32[16,16]{1,0} parameter(0)
  %t = (s32[], f32[16,16]{1,0}) parameter(1)
  %gte = f32[16,16]{1,0} get-tuple-element((s32[], f32[16,16]{1,0}) %t), index=1
  ROOT %fusion = f32[16,16]{1,0} fusion(%a, %gte), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(f)/add"}
}
"""
    comps, entry = opprof.parse_hlo(text)
    assert entry == "main.9"
    assert set(comps) == {"fused_computation.1", "main.9"}
    fusion = [i for i in comps["main.9"] if i["opcode"] == "fusion"][0]
    assert fusion["called"] == ["fused_computation.1"]
    assert fusion["operands"] == ["a", "gte"]
    assert fusion["op_name"] == "jit(f)/add"
    gte = [i for i in comps["main.9"] if i["name"] == "gte"][0]
    # the tuple-typed operand's internal parens must not truncate the
    # operand scan
    assert "t" in gte["operands"]
    analysis = opprof.analyze_hlo(text, costs.peaks())
    units = {u["unit"]: u for u in analysis["units"]}
    assert "%fusion" in units
    # the fusion recursed into its called computation: 16*16 adds
    assert units["%fusion"]["flops"] == 16 * 16


# ---------------------------------------------------------------------------
# kernel candidates
# ---------------------------------------------------------------------------

def test_kernel_candidates_rank_compute_and_comm():
    programs = {
        "big": {"origin": "o", "specimens": 1, "compiled": True,
                "est_us": 10.0, "flops": 0, "bytes": 0, "units": [
                    {"unit": "%dot.1", "opcode": "dot",
                     "op_class": "dot", "op_name": None,
                     "bound": "compute", "intensity": 40.0,
                     "ceiling": 8e11, "ceiling_kind": "flops_per_s",
                     "est_us": 9.0, "share": 0.9},
                    {"unit": "%all-reduce.1", "opcode": "all-reduce",
                     "op_class": "collective", "op_name": None,
                     "bound": "comm", "intensity": 0.1,
                     "ceiling": 8e10, "ceiling_kind": "bytes_per_s",
                     "est_us": 1.0, "share": 0.1}]},
        "tiny": {"origin": "o", "specimens": 1, "compiled": True,
                 "est_us": 2.0, "flops": 0, "bytes": 0, "units": [
                     {"unit": "%collective-permute.1",
                      "opcode": "collective-permute",
                      "op_class": "collective", "op_name": None,
                      "bound": "comm", "intensity": 0.0,
                      "ceiling": 8e10, "ceiling_kind": "bytes_per_s",
                      "est_us": 2.0, "share": 1.0}]},
    }
    cands = opprof.kernel_candidates(programs)
    kinds = {c["kind"] for c in cands}
    assert kinds == {"compute", "comm"}
    compute = [c for c in cands if c["kind"] == "compute"]
    assert compute[0]["unit"] == "%dot.1"
    # shares are of the roofline estimate over every program: 9 of 12
    assert compute[0]["global_share"] == pytest.approx(0.75)
    assert compute[0]["score"] == pytest.approx(0.75)
    comm = [c for c in cands if c["kind"] == "comm"]
    # ranked within the comm class by their own estimate: the permute's
    # 2us beats the all-reduce's 1us even though both are small next to
    # the dot — the separate tier exists exactly so collective cores are
    # not buried under the matmuls
    assert [c["unit"] for c in comm] == ["%collective-permute.1",
                                         "%all-reduce.1"]
    assert comm[0]["score"] == pytest.approx(2 / 12 * 0.8, abs=1e-6)


# ---------------------------------------------------------------------------
# the device -> timeseries drift feed
# ---------------------------------------------------------------------------

def test_sampled_window_feeds_device_series():
    from mxnet_tpu.telemetry import device, timeseries
    device.reset()
    timeseries.reset()
    device.configure(rate=1, opprof=True)
    try:
        device.open_step_window()
        win = device._tls.window
        assert win is not None and win.sampled
        device.record_program("opprof_feed_prog", 123.0, window=win)
        device.close_step_window(500.0)
        pts = timeseries.series("device/opprof_feed_prog/us")
        assert pts == [(0, 123.0)]
    finally:
        device.configure(rate=0, opprof=True)
        device.reset()
        timeseries.reset()


def test_opprof_flag_gates_the_feed():
    from mxnet_tpu.telemetry import device, timeseries
    device.reset()
    timeseries.reset()
    device.configure(rate=1, opprof=False)
    try:
        assert not device.opprof_enabled()
        device.open_step_window()
        win = device._tls.window
        device.record_program("opprof_gated_prog", 55.0, window=win)
        device.close_step_window(100.0)
        assert timeseries.series("device/opprof_gated_prog/us") == []
    finally:
        device.configure(rate=0, opprof=True)
        device.reset()
        timeseries.reset()


def test_opprof_env_parse(monkeypatch):
    from mxnet_tpu.telemetry import device
    monkeypatch.setenv("MXNET_OPPROF", "0")
    device.refresh_from_env()
    assert not device.opprof_enabled()
    monkeypatch.delenv("MXNET_OPPROF", raising=False)
    device.refresh_from_env()
    assert device.opprof_enabled()   # default on
