"""Tier-1 memcheck gate: the owned-program ledger is SPMD- and
memory-budget-clean, and the budget gate actually bites.

Three layers, one sweep (module-scoped — tracing + compiling all owned
specimens costs seconds, not minutes, but only once):

* every owned program passes the JX2xx rules with ZERO findings — the
  collective-safety invariants (no divergent rendezvous, canonical lane
  order, no replicated-gather outputs) are proven properties of the
  shipped ledger, not aspirations;
* MEM_BASELINE.json is fresh: present, topology-matched to the pinned
  8-device test mesh, every program budgeted, nothing stale;
* ``trace_report.py --gate-memory`` exits 0 on the real report and 3 on
  a deliberately over-budget twin — the CI wire, not just the library.
"""
import json
import os
import subprocess
import sys

import pytest

from mxnet_tpu.lint import tracecheck

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_REPORT = os.path.join(REPO, "tools", "trace_report.py")

# the ledger floor: shrinking coverage must fail this gate, not slide
MIN_PROGRAMS = 32


@pytest.fixture(scope="module")
def sweep():
    findings, names, report = tracecheck.analyze_entry_points()
    assert report is not None, "memory pass did not run"
    return findings, names, report


def gate(report, tmp_path, extra=()):
    path = tmp_path / "mem.json"
    path.write_text(json.dumps(report))
    proc = subprocess.run(
        [sys.executable, TRACE_REPORT, "--memory", str(path),
         "--gate-memory", *extra],
        capture_output=True, text=True)
    return proc.returncode, proc.stdout, proc.stderr


def test_owned_programs_are_spmd_clean(sweep):
    findings, names, _report = sweep
    spmd = [f for f in findings
            if f.rule.startswith("JX2") or f.rule == "JX000"]
    assert spmd == [], (
        "JX2xx findings on owned programs (fix the program or suppress "
        "with justification — do NOT grow the baseline):\n"
        + "\n".join("  %s %s: %s" % (f.rule, f.path, f.message)
                    for f in spmd))
    assert len(set(names)) >= MIN_PROGRAMS


def test_memory_budgets_are_fresh(sweep):
    _findings, _names, report = sweep
    assert report["baseline_present"], \
        "MEM_BASELINE.json missing — run graftcheck --write-mem-baseline"
    assert report["topology_match"], (
        "baseline captured on %s devices, test mesh has %s — the pinned "
        "conftest topology and the committed baseline must agree"
        % (report["baseline_n_devices"], report["n_devices"]))
    assert report["stale_budgets"] == []
    bad = [p["name"] for p in report["programs"]
           if p["over_budget"] or p["unbudgeted"]]
    assert bad == [], "over/unbudgeted programs: %s" % bad
    assert len(report["programs"]) >= MIN_PROGRAMS


def test_gate_memory_passes_on_real_report(sweep, tmp_path):
    _f, _n, report = sweep
    rc, out, _err = gate(report, tmp_path)
    assert rc == 0 and "gate-memory: ok" in out


def test_gate_memory_exits_3_on_over_budget(sweep, tmp_path):
    """The injected regression: shrink one program's budget to a tenth
    and re-run the REAL comparison (check_memory, not a doctored flag) —
    the gate must exit 3 and name the program."""
    _f, _n, report = sweep
    victim = max(report["programs"], key=lambda p: p["total_bytes"])
    baseline = tracecheck.load_mem_baseline()
    doctored = json.loads(json.dumps(baseline))
    doctored["programs"][victim["name"]]["total_bytes"] //= 10
    recs = [item for _g, item in tracecheck.iter_owned_programs(
        entries=tracecheck.groups_for_paths([victim["origin"]]))
            if not isinstance(item, tracecheck.Finding)
            and item.name == victim["name"]]
    assert recs, "victim program %r not re-traceable" % victim["name"]
    findings, bad_report = tracecheck.check_memory(recs, doctored,
                                                   full=False)
    assert any(f.snippet == "mem:over" for f in findings)
    rc, _out, err = gate(bad_report, tmp_path)
    assert rc == 3
    assert "gate-memory: FAIL" in err and victim["name"] in err


def test_gate_memory_exits_3_on_unbudgeted(sweep, tmp_path):
    _f, _n, report = sweep
    doctored = json.loads(json.dumps(report))
    doctored["programs"][0]["unbudgeted"] = True
    rc, _out, err = gate(doctored, tmp_path)
    assert rc == 3 and "unbudgeted" in err


def test_gate_memory_exits_4_when_unmeasurable(sweep, tmp_path):
    """A topology mismatch means the gate cannot compare — it must fail
    loudly as UNMEASURABLE (4), never silently pass."""
    _f, _n, report = sweep
    doctored = json.loads(json.dumps(report))
    doctored["topology_match"] = False
    rc, _out, err = gate(doctored, tmp_path)
    assert rc == 4 and "UNMEASURABLE" in err


def test_gate_memory_requires_memory_json(tmp_path):
    proc = subprocess.run(
        [sys.executable, TRACE_REPORT, "--gate-memory"],
        capture_output=True, text=True)
    assert proc.returncode == 2


def test_combined_gates_report_every_gate(sweep, tmp_path):
    """Regression for the silent-degradation bug: when the memory and
    overlap gates are requested together, BOTH verdict lines print and
    the exit code is the worse of the two — a failing second gate cannot
    hide behind a passing first one."""
    _findings, _names, report = sweep
    mem_path = tmp_path / "mem.json"
    mem_path.write_text(json.dumps(report))
    step = {"wall_us": 100.0, "data_wait_us": 0.0, "host_us": 10.0,
            "device_us": 60.0, "collective_us": 30.0,
            "overlap_ratio": 0.2, "overlap_hidden_us": 6.0,
            "overlap_exposed_us": 24.0}
    snap_path = tmp_path / "snap.json"
    snap_path.write_text(json.dumps({"device": {
        "enabled": True, "sample_period": 1, "timelines": [step],
        "last_step": step, "programs": {}}}))
    trace_path = tmp_path / "trace.json"
    trace_path.write_text(json.dumps({"traceEvents": []}))
    proc = subprocess.run(
        [sys.executable, TRACE_REPORT, str(trace_path),
         "--snapshot", str(snap_path), "--gate-overlap", "0.5",
         "--memory", str(mem_path), "--gate-memory"],
        capture_output=True, text=True)
    both = proc.stdout + proc.stderr
    assert "gate-memory: ok" in both
    assert "gate-overlap: FAIL" in both
    assert proc.returncode == 3
