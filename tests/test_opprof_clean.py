"""Tier-1 opprof wire: every owned program compiles and attributes.

Mirrors ``test_memcheck_clean.py``.  One module-scoped sweep (AOT-compile
all owned programs on the pinned 8-device CPU mesh — seconds, once, and
nothing executed):

* every owned program is traced, compiled and attributed, with FLOPs,
  bytes and per-unit shares that sum to one;
* the candidate ranking still names >= 2 concrete kernel targets across
  both roofline regimes;
* ``trace_report.py --ops`` renders the artifact the CLI would write —
  the wire, not just the library.
"""
import json
import os
import subprocess
import sys

import pytest

from mxnet_tpu.telemetry import costs, opprof

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_REPORT = os.path.join(REPO, "tools", "trace_report.py")

MIN_PROGRAMS = 32            # same ledger floor as test_memcheck_clean
MIN_CANDIDATES = 2           # the ISSUE's "name >= 2 kernel targets"


@pytest.fixture(scope="module")
def sweep():
    """The real sweep, with every execution of a compiled program
    recorded: the sweep reads HLO, it runs nothing."""
    import jax
    executed = []
    call = jax.stages.Compiled.__call__

    def recording(self, *args, **kwargs):
        executed.append(self)
        return call(self, *args, **kwargs)

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax.stages.Compiled, "__call__", recording)
        programs, problems = opprof.sweep()
    assert problems == [], "sweep problems: %s" % problems
    assert executed == [], "the sweep executed %d program(s)" \
        % len(executed)
    return programs


@pytest.fixture(scope="module")
def artifact(sweep):
    return opprof.build_report(sweep, [], costs.peaks())


def test_all_owned_programs_measured(sweep):
    assert len(sweep) >= MIN_PROGRAMS
    uncompiled = [n for n, p in sweep.items() if not p["compiled"]]
    assert uncompiled == [], "programs that did not compile: %s" \
        % uncompiled
    attributed = {n: p for n, p in sweep.items() if p["units"]}
    # a specimen may be pure plumbing (executor_bwd returns its inputs);
    # the ledger as a whole may not
    assert len(attributed) >= MIN_PROGRAMS - 2, sorted(
        set(sweep) - set(attributed))
    for name, p in attributed.items():
        assert p["bytes"] > 0 and p["est_us"] > 0, name
        assert sum(u["share"] for u in p["units"]) \
            == pytest.approx(1.0, abs=1e-6), name
        assert p["flops"] == sum(u["flops"] for u in p["units"]), name


def test_candidates_named_with_ceilings(artifact):
    cands = artifact["candidates"]
    assert len(cands) >= MIN_CANDIDATES
    kinds = {c["kind"] for c in cands}
    assert kinds == {"compute", "comm"}, (
        "candidate list must span both roofline regimes, got %s" % kinds)
    for c in cands:
        assert c["program"] and c["unit"]
        assert c["ceiling"] > 0 and c["ceiling_kind"] in (
            "flops_per_s", "bytes_per_s")
        assert 0 < c["global_share"] <= 1


def test_trace_report_renders_the_artifact(artifact, tmp_path):
    path = tmp_path / "ops.json"
    path.write_text(json.dumps(artifact))
    proc = subprocess.run(
        [sys.executable, TRACE_REPORT, "--ops", str(path)],
        capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "hot ops: %d program(s)" % len(artifact["programs"]) \
        in proc.stdout
    assert "kernel candidates" in proc.stdout
    top = artifact["candidates"][0]
    assert top["program"] in proc.stdout and top["unit"] in proc.stdout
