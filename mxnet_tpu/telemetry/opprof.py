"""Hot-op observatory: per-op roofline attribution over the owned
program ledger, from counts alone.

ROADMAP item 2 (the Pallas kernel tier) picks the 2-3 kernels worth
hand-writing from what each program *does*, not vibes.  This module
walks the **optimized HLO text** of every owned program — AOT-compiled
from the same ``tracecheck_programs()`` specimen ledger the JX2xx trace
tier and the JX204 memory gate already consume
(``tracecheck.compile_record``; zero new jitted entry points, the
graftcheck ledger is unchanged) — and for each top-level instruction or
fusion attributes:

* **flops** via a per-opcode cost-model table (dot = 2·out·contraction,
  reduce = input elements, transcendentals weighted, fusions recursed
  into their called computations);
* **bytes moved** as operand + result bytes at the call site (traffic
  internal to a fusion is exactly what fusion makes free);
* **op class** — dot / conv / elementwise / reduce / collective /
  fusion — and the roofline verdict against the ``costs.peaks()``
  tables: arithmetic intensity above the machine balance is
  compute-bound, below is HBM-bound, collectives are comm (ceilinged by
  the interconnect table, not HBM);
* **est_us** — the least time the peak table allows the unit (the
  larger of flops over peak FLOP/s and bytes over peak bytes/s) — and
  its **share** of the program's total, summing to 1 by construction.

Nothing is executed and no clock is read: FLOPs, bytes and collectives
per program are exact on any host, and how long a program takes is the
chip benchmark's to say (``chipbench/``, ``PERF_LEDGER.jsonl``).

Two consumers: ``tools/trace_report.py --ops`` renders the ranked
hot-op table and the kernel-candidate list from the ``--json`` artifact
this module's CLI writes; ``tests/test_opprof_clean.py`` holds every
owned program to compiling and attributing.

Known approximations, accepted on purpose and recorded here so the
numbers are honest: while-loop bodies are counted once (trip counts are
runtime values); convolution flops assume dense direct convolution;
``est_us`` is a roofline floor under the peak table of the backend the
sweep ran on, so the *shares* and the candidate *ranking* are the
signal.

Import-light: jax loads inside functions only, and nothing here runs on
the step path — the sweep is an offline tool, like the lint driver.
"""
from __future__ import annotations

import json
import re

__all__ = ["parse_hlo", "analyze_hlo", "analyze_record", "classify",
           "sweep", "build_report", "kernel_candidates", "main"]

# --------------------------------------------------------------------------
# optimized-HLO text parsing
# --------------------------------------------------------------------------

# computation headers sit at column 0:
#   %fused_computation.88 (param_0.185: f32[16], ...) -> f32[16,16] {
#   ENTRY %main.1285_spmd (...) -> (f32[...], ...) {
_COMP_RE = re.compile(r"^(ENTRY\s+)?%([\w.\-]+)\s*\(")
# instructions are indented:  [ROOT ]%name = TYPE opcode(OPERANDS), attrs
_INSTR_RE = re.compile(r"^\s+(ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
# the first lowercase-word-then-paren in the RHS is the opcode (type
# portions — f32[16]{1,0}, tuple types — never match first)
_OPCODE_RE = re.compile(r"([a-z][a-z0-9\-_.]*)\(")
_SHAPE_RE = re.compile(
    r"\b(pred|token|bf16|f8e\w+|c64|c128|[fsu]\d+)\[([0-9,]*)\]")
_OPERAND_NAME_RE = re.compile(r"%([\w.\-]+)")
_CALLED_RE = re.compile(
    r"(?:calls|to_apply|body|condition|true_computation|"
    r"false_computation)=%([\w.\-]+)")
_BRANCHES_RE = re.compile(r"branches=\{([^}]*)\}")
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
_CONTRACTING_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")

_DTYPE_BYTES = {"pred": 1, "token": 0, "bf16": 2, "c64": 8, "c128": 16}


def _dtype_bytes(dtype):
    if dtype in _DTYPE_BYTES:
        return _DTYPE_BYTES[dtype]
    if dtype.startswith("f8"):
        return 1
    m = re.match(r"[fsu](\d+)", dtype)
    return max(1, int(m.group(1)) // 8) if m else 4


def _shapes_in(text):
    """[(elems, bytes)] for every shape literal in *text*."""
    out = []
    for dtype, dims in _SHAPE_RE.findall(text):
        elems = 1
        for d in dims.split(","):
            if d:
                elems *= int(d)
        out.append((elems, elems * _dtype_bytes(dtype)))
    return out


def parse_hlo(text):
    """Optimized HLO module text -> ``(computations, entry_name)``.

    ``computations`` maps computation name to an ordered instruction
    list; each instruction is a dict with ``name/opcode/out_elems/
    out_bytes/operands/attrs/called/op_name`` — enough for the cost
    model, deliberately no full graph semantics."""
    comps, entry_name, cur = {}, None, None
    for line in text.splitlines():
        if not line:
            continue
        if line[0] not in " \t":
            m = _COMP_RE.match(line)
            if m and line.rstrip().endswith("{"):
                cur = []
                comps[m.group(2)] = cur
                if m.group(1):
                    entry_name = m.group(2)
            elif line.startswith("}"):
                cur = None
            continue
        if cur is None:
            continue
        m = _INSTR_RE.match(line)
        if not m:
            continue
        name, rhs = m.group(2), m.group(3)
        om = _OPCODE_RE.search(rhs)
        if om is None:
            continue
        opcode = om.group(1)
        # scan the operand section with paren depth (tuple-typed
        # operands like get-tuple-element((s32[], f32[2,8]) %p), carry
        # internal parens)
        depth, i = 1, om.end()
        while i < len(rhs) and depth > 0:
            if rhs[i] == "(":
                depth += 1
            elif rhs[i] == ")":
                depth -= 1
            i += 1
        operand_str = rhs[om.end():i - 1]
        attrs = rhs[i:]
        out_shapes = _shapes_in(rhs[:om.start()])
        out_elems = sum(e for e, _b in out_shapes)
        out_bytes = sum(b for _e, b in out_shapes)
        called = _CALLED_RE.findall(attrs)
        bm = _BRANCHES_RE.search(attrs)
        if bm:
            called.extend(_OPERAND_NAME_RE.findall(bm.group(1)))
        op_name_m = _OP_NAME_RE.search(attrs)
        # dims of the (first) result shape — the dot cost model indexes
        # the lhs def-site's dimension sizes by lhs_contracting_dims
        dm = _SHAPE_RE.search(rhs[:om.start()])
        dims = [int(d) for d in dm.group(2).split(",") if d] \
            if dm else None
        cur.append({
            "name": name, "opcode": opcode,
            "out_elems": out_elems, "out_bytes": out_bytes, "dims": dims,
            "operands": _OPERAND_NAME_RE.findall(operand_str),
            "operand_text": operand_str, "attrs": attrs,
            "called": called,
            "op_name": op_name_m.group(1) if op_name_m else None,
        })
    return comps, entry_name


# --------------------------------------------------------------------------
# per-opcode cost model
# --------------------------------------------------------------------------

# structural plumbing: free at the unit level (no math, and their bytes
# show up as operands of whoever consumes them)
_SKIP_OPS = frozenset({
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "after-all", "partition-id", "replica-id", "opt-barrier",
    "add-dependency", "domain",
})
_COLLECTIVE_OPS = frozenset({
    "all-reduce", "all-gather", "all-to-all", "reduce-scatter",
    "collective-permute", "collective-broadcast", "all-reduce-start",
    "all-reduce-done", "all-gather-start", "all-gather-done",
    "collective-permute-start", "collective-permute-done",
    "send", "send-done", "recv", "recv-done",
})
_COMPOUND_OPS = frozenset({"fusion", "call", "while", "conditional"})
# ~8 flops per element for the polynomial/Newton expansions
_TRANSCENDENTAL_OPS = frozenset({
    "exponential", "exponential-minus-one", "log", "log-plus-one",
    "logistic", "tanh", "sqrt", "rsqrt", "cbrt", "power", "sine",
    "cosine", "tan", "erf", "erf-inv", "atan2",
})
_TRANSCENDENTAL_WEIGHT = 8
# per-element-of-input reductions (regions counted via element count,
# never recursed: the region is the per-element combiner)
_REDUCE_OPS = frozenset({
    "reduce", "reduce-window", "select-and-scatter", "scatter", "sort",
    "map",
})
# pure data movement: zero flops, bytes are the whole story
_DATA_OPS = frozenset({
    "broadcast", "reshape", "transpose", "slice", "concatenate", "pad",
    "reverse", "dynamic-slice", "dynamic-update-slice", "gather",
    "copy", "copy-start", "copy-done", "iota", "convert",
    "rng-bit-generator", "rng-get-and-update-state",
})


def classify(opcode):
    """The six-way op class of the ranked table."""
    if opcode == "dot":
        return "dot"
    if opcode == "convolution":
        return "conv"
    if opcode in _COMPOUND_OPS:
        return "fusion"
    if opcode in _COLLECTIVE_OPS:
        return "collective"
    if opcode in _REDUCE_OPS:
        return "reduce"
    if opcode in _SKIP_OPS:
        return "other"
    if opcode in _DATA_OPS or opcode in _TRANSCENDENTAL_OPS:
        return "elementwise"
    return "elementwise"


def _operand_sizes(ins, by_name):
    """Total (elems, bytes) across *ins*'s operands, resolved through
    the def-site instruction (operands are bare %names in optimized
    HLO; their shapes live on the defining instruction)."""
    elems = nbytes = 0
    seen_inline = _shapes_in(ins["operand_text"])
    if seen_inline and not ins["operands"]:
        return (sum(e for e, _ in seen_inline),
                sum(b for _, b in seen_inline))
    for op in ins["operands"]:
        d = by_name.get(op)
        if d is not None:
            elems += d["out_elems"]
            nbytes += d["out_bytes"]
    return elems, nbytes


def _instr_flops(ins, comps, by_name, memo):
    op = ins["opcode"]
    if op in _SKIP_OPS or op in _DATA_OPS:
        return 0
    if op == "dot":
        cm = _CONTRACTING_RE.search(ins["attrs"])
        contracting = 1
        if cm and ins["operands"]:
            lhs = by_name.get(ins["operands"][0])
            lhs_dims = lhs["dims"] if lhs else None
            if lhs_dims:
                for d in cm.group(1).split(","):
                    if d and int(d) < len(lhs_dims):
                        contracting *= lhs_dims[int(d)]
        return 2 * ins["out_elems"] * max(1, contracting)
    if op == "convolution":
        # dense direct conv: 2 * out * (kernel elems / out channels);
        # rhs (the kernel) is operand 1
        kernel = by_name.get(ins["operands"][1]) \
            if len(ins["operands"]) > 1 else None
        k_elems = kernel["out_elems"] if kernel else 1
        out_ch = (ins.get("dims") or [1])[-1] or 1
        return 2 * ins["out_elems"] * max(1, k_elems // max(1, out_ch))
    if op in _REDUCE_OPS:
        elems, _b = _operand_sizes(ins, by_name)
        return max(elems, ins["out_elems"])
    if op in _COLLECTIVE_OPS:
        # all-reduce does one add per element; pure-movement collectives
        # do none
        return ins["out_elems"] if op.startswith("all-reduce") \
            or op == "reduce-scatter" else 0
    if op in _COMPOUND_OPS:
        total = 0
        for cname in ins["called"]:
            total += _comp_flops(cname, comps, memo)
        return total
    weight = _TRANSCENDENTAL_WEIGHT if op in _TRANSCENDENTAL_OPS else 1
    return weight * ins["out_elems"]


def _comp_flops(cname, comps, memo):
    if cname in memo:
        return memo[cname]
    memo[cname] = 0              # cycle guard; HLO comps are acyclic
    instrs = comps.get(cname, [])
    by_name = {i["name"]: i for i in instrs}
    total = 0
    for ins in instrs:
        total += _instr_flops(ins, comps, by_name, memo)
    memo[cname] = total
    return total


def analyze_hlo(text, peaks):
    """Parse + cost one program's optimized HLO.  Returns
    ``{"units": [...], "flops": F, "bytes": B}`` where units are the
    entry computation's non-structural instructions, each carrying
    flops/bytes/op_class/intensity/bound/ceiling/est_us/share (shares
    sum to 1 over the program by construction)."""
    comps, entry = parse_hlo(text)
    if entry is None or entry not in comps:
        return {"units": [], "flops": 0, "bytes": 0}
    memo = {}
    instrs = comps[entry]
    by_name = {i["name"]: i for i in instrs}
    balance = peaks["flops"] / peaks["hbm_bw"] if peaks["hbm_bw"] else 0
    units = []
    for ins in instrs:
        if ins["opcode"] in _SKIP_OPS:
            continue
        flops = _instr_flops(ins, comps, by_name, memo)
        _oe, obytes = _operand_sizes(ins, by_name)
        nbytes = obytes + ins["out_bytes"]
        op_class = classify(ins["opcode"])
        intensity = (flops / nbytes) if nbytes > 0 else 0.0
        if op_class == "collective":
            bound = "comm"
            ceiling = peaks.get("ici_bw", peaks["hbm_bw"])
            est_s = nbytes / ceiling if ceiling > 0 else 0.0
            ceiling_kind = "bytes_per_s"
        else:
            bound = "compute" if intensity >= balance else "hbm"
            ceiling = min(peaks["flops"], intensity * peaks["hbm_bw"]) \
                if intensity > 0 else 0.0
            est_s = max(flops / peaks["flops"] if peaks["flops"] else 0,
                        nbytes / peaks["hbm_bw"] if peaks["hbm_bw"]
                        else 0)
            ceiling_kind = "flops_per_s"
        units.append({
            "unit": "%" + ins["name"], "opcode": ins["opcode"],
            "op_class": op_class, "op_name": ins["op_name"],
            "flops": int(flops), "bytes": int(nbytes),
            "intensity": round(intensity, 4), "bound": bound,
            "ceiling": ceiling, "ceiling_kind": ceiling_kind,
            "est_us": est_s * 1e6,
        })
    total_est = sum(u["est_us"] for u in units)
    for u in units:
        u["share"] = (u["est_us"] / total_est) if total_est > 0 else 0.0
    return {"units": units,
            "flops": sum(u["flops"] for u in units),
            "bytes": sum(u["bytes"] for u in units)}


def analyze_record(rec, peaks):
    """analyze_hlo over a ProgramRecord's compiled HLO, or None when
    the record cannot be compiled (recorded upstream as a problem, not
    silently skipped)."""
    from ..lint import tracecheck
    compiled = tracecheck.compile_record(rec)
    if compiled is None:
        return None, None
    try:
        text = compiled.as_text()
    except Exception:
        return None, compiled
    return analyze_hlo(text, peaks), compiled


# --------------------------------------------------------------------------
# the sweep: every owned specimen, compiled and attributed
# --------------------------------------------------------------------------

def sweep():
    """Trace, compile and attribute every owned specimen; nothing is
    executed.  Returns ``(programs, problems)``:

    * programs: ``{name: {origin, specimens, compiled, est_us, flops,
      bytes, units}}`` — keyed per program NAME like measure_programs
      (k specimens sum their counts and unit lists);
    * problems: provider/trace/compile failures as strings — a specimen
      the sweep cannot see must be reported, never silently skipped.
    """
    import importlib
    from ..lint import tracecheck
    from . import costs
    pk = costs.peaks()
    programs, problems = {}, []
    for _group, modpath in tracecheck.ENTRY_POINTS:
        origin = modpath.replace(".", "/") + ".py"
        try:
            mod = importlib.import_module(modpath)
            specs = list(mod.tracecheck_programs())
        except Exception as exc:
            problems.append("provider %s failed: %r" % (modpath, exc))
            continue
        for spec in specs:
            name, fn, args, kwargs = spec[:4]
            meta = spec[4] if len(spec) > 4 else None
            try:
                rec = tracecheck.trace_program(
                    name, fn, args, kwargs, origin=origin, meta=meta)
            except Exception as exc:
                problems.append("tracing %s (%s) failed: %r"
                                % (name, origin, exc))
                continue
            entry = programs.setdefault(name, {
                "origin": origin, "specimens": 0, "compiled": True,
                "flops": 0, "bytes": 0, "units": []})
            entry["specimens"] += 1
            analysis, compiled = analyze_record(rec, pk)
            if analysis is None:
                problems.append(("compiling %s failed" if compiled is None
                                 else "%s compiled to no HLO text") % name)
                entry["compiled"] = False
                continue
            tag = "s%d:" % (entry["specimens"] - 1) \
                if entry["specimens"] > 1 else ""
            for u in analysis["units"]:
                entry["units"].append(dict(u, unit=tag + u["unit"]))
            entry["flops"] += analysis["flops"]
            entry["bytes"] += analysis["bytes"]
    for entry in programs.values():
        # renormalize unit shares over the merged specimen set
        entry["est_us"] = sum(u["est_us"] for u in entry["units"])
        for u in entry["units"]:
            u["share"] = (u["est_us"] / entry["est_us"]) \
                if entry["est_us"] else 0.0
        entry["units"].sort(key=lambda u: u["share"], reverse=True)
    return programs, problems


# --------------------------------------------------------------------------
# kernel candidates: the handoff ROADMAP item 2 consumes
# --------------------------------------------------------------------------

# Pallas-candidate score = global share of the roofline estimate × class
# weight.  Compute classes where a hand kernel can beat XLA rank high; raw
# elementwise is usually fused already; "other" is plumbing.
_CLASS_WEIGHT = {"dot": 1.0, "conv": 1.0, "fusion": 0.9, "reduce": 0.8,
                 "collective": 0.8, "elementwise": 0.5, "other": 0.2}
_COMPUTE_CLASSES = ("dot", "conv", "fusion", "reduce")


def kernel_candidates(programs, n_compute=3, n_comm=2):
    """Rank Pallas candidates two ways: the top compute units by
    score = global_share × class weight, and the top collective cores
    ranked within the comm class (their estimates are tiny next to the
    matmuls, but they own the interconnect ceiling — a fused
    chunk-sum kernel is a latency win the global ranking would hide)."""
    total_us = sum(p["est_us"] for p in programs.values()) or 1.0
    pool = []
    for name, p in programs.items():
        for u in p["units"]:
            gshare = u["est_us"] / total_us
            pool.append(dict(
                kind=None, program=name, unit=u["unit"],
                opcode=u["opcode"], op_class=u["op_class"],
                op_name=u["op_name"], bound=u["bound"],
                intensity=u["intensity"], ceiling=u["ceiling"],
                ceiling_kind=u["ceiling_kind"],
                est_us=round(u["est_us"], 4),
                global_share=round(gshare, 6),
                score=round(gshare * _CLASS_WEIGHT.get(
                    u["op_class"], 0.2), 6)))
    compute = sorted(
        (c for c in pool if c["op_class"] in _COMPUTE_CLASSES),
        key=lambda c: c["score"], reverse=True)[:n_compute]
    comm = sorted(
        (c for c in pool if c["op_class"] == "collective"),
        key=lambda c: (c["est_us"], c["score"]),
        reverse=True)[:n_comm]
    for c in compute:
        c["kind"] = "compute"
    for c in comm:
        c["kind"] = "comm"
    return compute + comm


# --------------------------------------------------------------------------
# the artifact
# --------------------------------------------------------------------------

_UNITS_KEPT = 12          # per program in the artifact; counts recorded


def build_report(programs, problems, peaks):
    """The ``--json`` artifact trace_report consumes.  Unit lists are
    capped at the top _UNITS_KEPT per program BY SHARE with the dropped
    tail recorded (units_omitted / share_omitted) — a silent cap would
    read as full coverage."""
    out_programs = {}
    for name, p in sorted(programs.items()):
        kept = p["units"][:_UNITS_KEPT]
        omitted = p["units"][_UNITS_KEPT:]
        out_programs[name] = {
            "origin": p["origin"], "specimens": p["specimens"],
            "compiled": p["compiled"],
            "est_us": round(p["est_us"], 4),
            "flops": p["flops"], "bytes": p["bytes"],
            "units": [
                {k: (round(v, 6 if k in ("share", "intensity") else 4)
                     if isinstance(v, float) else v)
                 for k, v in u.items()} for u in kept],
            "units_total": len(p["units"]),
            "units_omitted": len(omitted),
            "share_omitted": round(sum(u["share"] for u in omitted), 4),
        }
    return {
        "schema": "opprof-ops-v2",
        "n_devices": peaks.get("n_devices"),
        "device_kind": peaks.get("device_kind"),
        "peaks": {"flops": peaks["flops"], "hbm_bw": peaks["hbm_bw"],
                  "ici_bw": peaks.get("ici_bw")},
        "machine_balance": round(
            peaks["flops"] / peaks["hbm_bw"], 4) if peaks["hbm_bw"]
        else 0.0,
        "total_est_us": round(
            sum(p["est_us"] for p in programs.values()), 4),
        "problems": problems,
        "programs": out_programs,
        "candidates": kernel_candidates(programs),
    }


# --------------------------------------------------------------------------
# CLI
# --------------------------------------------------------------------------

def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser(
        prog="python -m mxnet_tpu.telemetry.opprof",
        description="per-op roofline attribution over the owned program "
                    "ledger: FLOPs, bytes and collectives from the "
                    "optimized HLO, nothing executed (the tests' "
                    "topology: JAX_PLATFORMS=cpu XLA_FLAGS="
                    "--xla_force_host_platform_device_count=8)")
    ap.add_argument("--json", metavar="PATH",
                    help="write the ops artifact (trace_report --ops)")
    ap.add_argument("--top", type=int, default=10,
                    help="programs shown in the stdout summary")
    args = ap.parse_args(argv)

    from . import costs
    programs, problems = sweep()
    report = build_report(programs, problems, costs.peaks())

    if args.json:
        with open(args.json, "w", encoding="utf-8") as f:
            json.dump(report, f, indent=1, sort_keys=True)
            f.write("\n")

    # stdout summary: programs by roofline estimate, then the candidates
    progs = sorted(report["programs"].items(),
                   key=lambda kv: kv[1]["est_us"], reverse=True)
    print("opprof: %d programs, %s FLOPs, %s bytes, machine balance "
          "%.2f FLOP/B"
          % (len(progs), sum(p["flops"] for _n, p in progs),
             sum(p["bytes"] for _n, p in progs),
             report["machine_balance"]))
    for name, p in progs[:args.top]:
        top_u = (p["units"] or [{}])[0]
        print("  %-34s %12d FLOPs %12d B  top: %s %s (%s, share %.2f)"
              % (name, p["flops"], p["bytes"],
                 top_u.get("op_class", "-"), top_u.get("unit", "-"),
                 top_u.get("bound", "-"), top_u.get("share", 0.0)))
    print("kernel candidates:")
    for c in report["candidates"]:
        print("  [%s] %s :: %s (%s, %s) share %.4f score %.4f"
              % (c["kind"], c["program"], c["unit"], c["op_class"],
                 c["bound"], c["global_share"], c["score"]))
    for prob in report["problems"]:
        print("problem: %s" % prob)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
