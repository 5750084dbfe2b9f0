"""tracecheck: trace-time jaxpr/HLO analysis of the owned XLA entry points.

graftlint (``rules.py``) works on source text; whole classes of silent
performance/correctness bugs only exist in the *lowered program* and are
invisible to an AST pass — a closure-baked weight matrix, an accidental
f64 widening, a host callback compiled into the train step, a donated
buffer that can never alias an output.  The reference framework closed
the same gap with graph-level passes over NNVM IR rather than C++ lint
(SURVEY layer map; cf. TVM/NNVM graph passes and Grappler's analyzers in
PAPERS.md).  This module is that tier for the JAX rebuild: it lowers the
programs the framework actually ships to XLA — AOT, on CPU, from
``ShapeDtypeStruct`` specimens, no TPU and no real data — and walks the
resulting jaxprs with a rule registry mirroring graftlint's.

Rule catalogue (rationale in docs/LINT.md):

JX101 baked-constant          large arrays captured by closure become
                              jaxpr constants: copied into every compiled
                              variant, silently stale after updates.
JX102 dtype-widening          f64/i64 appearing in a program whose inputs
                              are all <=32-bit: 2x HBM + matmul slowdown,
                              usually one forgotten ``np.float64`` scalar.
JX103 host-callback           ``pure_callback``/``io_callback``/
                              ``debug.print`` compiled into an owned hot
                              program: a host round-trip per step.
JX104 donation-waste          donated args that cannot alias any output
                              (buffer freed for nothing), large
                              non-donated args that alias outputs in a
                              program that already donates, and dead
                              (pass-through / constant) outputs.
JX105 retrace-explainer       on a ``watch_jit`` recompile, diff the new
                              avals/statics against the cached variants
                              and NAME the axis that changed — turns the
                              telemetry retrace-storm warning into a
                              diagnosis.  Runtime-only (``MXNET_TRACECHECK``).

The JX2xx family (ISSUE 18) adds the SPMD/memory tier — collective
safety and device-memory budgets proven AOT over the same ledger:

JX201 collective-divergence   a collective (psum/all_gather/ppermute/
                              all_to_all/reduce_scatter) whose rendezvous
                              depends on a data-dependent branch: the two
                              arms of a ``lax.cond`` disagree on their
                              collective sequence, or a collective sits
                              inside a ``while`` whose trip count ranks
                              can disagree on — one rank enters the
                              collective, its peers never do, the mesh
                              deadlocks.  The guardian ``jnp.where``-skip
                              pattern is the clean twin: every rank runs
                              the same collectives, the *values* branch.
JX202 collective-order        per-mesh-axis collective sequences must be
                              identical across programs sharing a lane
                              (provider ``meta={"lane": ...}``) and must
                              only touch axes the provider declared
                              (``meta={"mesh_axes": ...}``) — the PR-13
                              descending-bucket canonical-order contract
                              as a proven invariant, not a comment.
JX203 replication-waste       an ``all_gather`` whose fully-replicated
                              result is returned as a program/shard_map
                              output: the sharded producer's bytes are
                              multiplied by the axis size in HBM — the
                              accidental gather that blows memory.
JX204 memory-budget           per-program ``compiled.memory_analysis()``
                              (argument/output/temp/generated-code bytes)
                              against the count-keyed MEM_BASELINE.json
                              with an ``MXNET_MEM_TOLERANCE`` band: a
                              program growing past budget is a lint-time
                              finding instead of an OOM at step time.

Two drivers share the registry:

* AOT (``check_entry_points`` / ``tools/graftcheck.py`` /
  ``python -m mxnet_tpu.lint --trace``): every owned jit entry point
  declares a ``tracecheck_programs()`` provider next to the jit itself
  (executor, fused trainer, optimizer, kvstore, module cached step,
  gluon cached op); the driver traces each with specimen shapes and runs
  JX101-JX104.  CI gates on zero findings (tests/test_tracecheck_clean.py).
* Runtime (``on_compile``): ``telemetry._WatchedJit`` calls in on every
  compile event when ``MXNET_TRACECHECK`` is truthy; findings are booked
  into the ``tracecheck_findings`` counter, the flight ring, and one
  structured log line each — JX105 included, because only the runtime
  hook sees *two* variants to diff.

Import-light on purpose: jax is imported inside functions only, so the
stdlib-only lint CLI can show the JX catalogue (``--list-rules``) without
initializing a backend.
"""
from __future__ import annotations

import json
import logging
import os

from .core import Finding

__all__ = ["TRACE_RULES", "GROUP_RULES", "TraceRule", "TraceConfig",
           "ProgramRecord", "trace_program", "run_rules",
           "run_group_rules", "check_entry_points", "analyze_entry_points",
           "iter_owned_programs", "groups_for_paths", "on_compile",
           "signature", "explain_retrace", "ENTRY_POINTS",
           "collective_sequence", "measure_memory", "compile_record",
           "mem_tolerance", "load_mem_baseline", "save_mem_baseline",
           "default_mem_baseline_path", "MEM_FIELDS"]
# NOTE: the MXNET_TRACECHECK gate itself lives in telemetry.core
# (_env_tracecheck) — the hook's caller owns the env parsing.

_LOG = logging.getLogger("mxnet_tpu.lint.tracecheck")

_WIDE_DTYPES = ("float64", "int64", "uint64", "complex128")


class TraceConfig:
    """Thresholds for the size-gated rules.

    The defaults are deliberately conservative: the AOT driver runs tiny
    specimen models, so an owned entry point only fires when it bakes or
    wastes something *structurally* (a closure-captured table, an
    unaliasable donation), never because a real model is large.  Tests
    shrink the thresholds to exercise the rules on toy programs.
    """

    __slots__ = ("const_bytes", "donation_bytes", "passthrough_bytes",
                 "replication_bytes")

    def __init__(self, const_bytes=64 << 10, donation_bytes=1 << 20,
                 passthrough_bytes=64 << 10, replication_bytes=64 << 10):
        self.const_bytes = const_bytes
        self.donation_bytes = donation_bytes
        self.passthrough_bytes = passthrough_bytes
        self.replication_bytes = replication_bytes


DEFAULT_CONFIG = TraceConfig()


# ---------------------------------------------------------------------------
# rule registry (mirrors rules.RULES)
# ---------------------------------------------------------------------------

TRACE_RULES = {}


class TraceRule:
    __slots__ = ("code", "name", "rationale", "_check")

    def __init__(self, code, name, rationale, check):
        self.code, self.name, self.rationale = code, name, rationale
        self._check = check

    def check(self, record, config):
        if self._check is None:        # runtime-only rule (JX105)
            return []
        return list(self._check(record, config))


def trace_rule(code, name, rationale):
    def deco(fn):
        TRACE_RULES[code] = TraceRule(code, name, rationale, fn)
        return fn
    return deco


# ---------------------------------------------------------------------------
# program record: one traced entry point
# ---------------------------------------------------------------------------

def _spec(leaf):
    """ShapeDtypeStruct skeleton of one pytree leaf (python scalars pass
    through and trace as weak-typed scalars, exactly like at runtime)."""
    shape = getattr(leaf, "shape", None)
    dtype = getattr(leaf, "dtype", None)
    if shape is None or dtype is None:
        return leaf
    import jax
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def _aval_nbytes(aval):
    n = 1
    for d in getattr(aval, "shape", ()):
        n *= int(d)
    dtype = getattr(aval, "dtype", None)
    return n * (dtype.itemsize if dtype is not None else 1)


def _aval_key(aval):
    return (tuple(getattr(aval, "shape", ())), str(getattr(aval, "dtype",
                                                           "?")))


def _fmt_aval(aval):
    return "%s[%s]" % (getattr(aval, "dtype", "?"),
                       ",".join(str(d) for d in getattr(aval, "shape", ())))


class ProgramRecord:
    """One owned program, traced: jaxpr + flat arg labels/avals/donation.

    ``lowered`` keeps the AOT lowering so JX204 can compile for
    ``memory_analysis()`` without re-tracing; ``meta`` carries the
    provider's sharding metadata (``lane``/``mesh_axes``) for JX202.
    """

    __slots__ = ("name", "origin", "closed_jaxpr", "arg_labels", "in_avals",
                 "donated", "out_avals", "lowered", "meta")

    def __init__(self, name, origin, closed_jaxpr, arg_labels, in_avals,
                 donated, out_avals, lowered=None, meta=None):
        self.name = name
        self.origin = origin
        self.closed_jaxpr = closed_jaxpr
        self.arg_labels = arg_labels      # flat, parallel to in_avals
        self.in_avals = in_avals
        self.donated = donated            # set of flat arg indices
        self.out_avals = out_avals
        self.lowered = lowered
        self.meta = dict(meta or {})

    @property
    def jaxpr(self):
        return self.closed_jaxpr.jaxpr

    @property
    def consts(self):
        return self.closed_jaxpr.consts

    def label(self, i):
        if 0 <= i < len(self.arg_labels):
            return self.arg_labels[i]
        return "arg[%d]" % i

    def finding(self, rule, message, key=""):
        """A Finding whose fingerprint is stable across runs: the path is
        the program identity, the snippet a short structural key (NOT the
        prose message, which may carry sizes that drift)."""
        return Finding(rule, "trace://%s" % self.name, 0, 0,
                       "%s [%s]: %s" % (self.name, self.origin, message),
                       snippet=key or rule)


def trace_program(name, fn, args, kwargs=None, origin="", meta=None):
    """Trace *fn* (a jitted callable or its watch_jit wrapper) with
    ShapeDtypeStruct skeletons of *args*/*kwargs* and return the
    :class:`ProgramRecord` the JX rules analyze.  Nothing is compiled or
    executed; lowering metadata supplies per-argument donation flags.
    (JX204 compiles *later*, from the kept lowering, only when a memory
    budget is actually being checked.)
    """
    import jax
    kwargs = dict(kwargs or {})
    fn = getattr(fn, "_fn", fn)          # unwrap telemetry._WatchedJit
    sargs, skwargs = jax.tree_util.tree_map(_spec, (tuple(args), kwargs))
    traced = fn.trace(*sargs, **skwargs)
    closed = traced.jaxpr
    lowered = traced.lower()

    flat, _ = jax.tree_util.tree_flatten_with_path((sargs, skwargs))
    labels = []
    for path, _leaf in flat:
        label = jax.tree_util.keystr(path)
        # keystr yields "[0][1]['lr']": [0]=args/[1]=kwargs bucket, next
        # index the position — keep it verbatim but drop the bucket
        labels.append("arg%s" % label[3:] if label.startswith("[0]")
                      else "kwarg%s" % label[3:])

    donated = set()
    info_leaves = jax.tree_util.tree_leaves(
        lowered.args_info, is_leaf=lambda v: hasattr(v, "donated"))
    for i, info in enumerate(info_leaves):
        if getattr(info, "donated", False):
            donated.add(i)

    return ProgramRecord(name, origin, closed, labels,
                         list(closed.in_avals), donated,
                         list(closed.out_avals), lowered=lowered,
                         meta=meta)


def _iter_eqns(jaxpr):
    """Every eqn in *jaxpr* and its nested sub-jaxprs (pjit bodies, scan
    carries, cond branches, custom-vjp closures, ...)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from _iter_eqns(sub)


def _sub_jaxprs(eqn):
    for val in eqn.params.values():
        yield from _extract_jaxprs(val)


def _extract_jaxprs(val):
    # a ClosedJaxpr has .jaxpr; a raw Jaxpr has .eqns
    inner = getattr(val, "jaxpr", None)
    if inner is not None and hasattr(inner, "eqns"):
        yield inner
    elif hasattr(val, "eqns"):
        yield val
    elif isinstance(val, (tuple, list)):
        for item in val:
            yield from _extract_jaxprs(item)


def _all_jaxprs(jaxpr):
    """*jaxpr* and every nested sub-jaxpr, each as its own scope (JX203
    needs per-scope outvars, not just the flat eqn stream)."""
    yield jaxpr
    for eqn in jaxpr.eqns:
        for sub in _sub_jaxprs(eqn):
            yield from _all_jaxprs(sub)


# ---------------------------------------------------------------------------
# collective extraction (shared by JX201/JX202/JX203)
# ---------------------------------------------------------------------------

# jaxpr-level cross-rank primitives.  GSPMD-inserted collectives (from
# jit out_shardings) are out of scope on purpose: the partitioner emits
# them uniformly on every rank — divergence risk lives in hand-written
# shard_map bodies, which is exactly what lowers to these primitives.
_COLLECTIVE_PRIMS = {"psum", "psum_invariant", "pmax", "pmin", "all_gather",
                     "all_to_all", "reduce_scatter", "psum_scatter",
                     "ppermute", "pshuffle", "axis_index"}
# psum_invariant: what lax.psum/pmean bind inside a shard_map body whose
# varying axes are checked (check_vma, the default) — the same all-reduce
# rendezvous under a different primitive name.
# axis_index is rank-local (no rendezvous): tracked for JX202's declared-
# axis check but excluded from order/divergence sequences.
_RENDEZVOUS_PRIMS = _COLLECTIVE_PRIMS - {"axis_index"}


def _collective_axes(eqn):
    """Named mesh axes a collective eqn communicates over.  ``psum``
    carries ``axes``, the permute/gather family ``axis_name``; positional
    (int) axes are vmap-internal, not cross-rank, and are dropped.  An
    empty result means no communication (e.g. ``psum(x, axes=())``)."""
    axes = eqn.params.get("axes")
    if axes is None:
        axes = eqn.params.get("axis_name")
    if axes is None:
        return ()
    if not isinstance(axes, (tuple, list)):
        axes = (axes,)
    return tuple(str(a) for a in axes if not isinstance(a, int))


def _collectives_in(jaxpr):
    """Ordered ``(primitive, axes)`` rendezvous sequence of *jaxpr*
    (nested scopes included, eqn order — the order ranks meet in)."""
    out = []
    for eqn in _iter_eqns(jaxpr):
        prim = eqn.primitive.name
        if prim in _RENDEZVOUS_PRIMS:
            axes = _collective_axes(eqn)
            if axes:
                # one rendezvous, two spellings: sequences must compare
                # equal whether or not the body's varying axes were checked
                out.append(("psum" if prim == "psum_invariant" else prim,
                            axes))
    return tuple(out)


def collective_sequence(record):
    """Per-mesh-axis ordered collective op sequence of a program —
    ``{"pipe": ("ppermute", "psum"), ...}`` — the JX202 comparison key."""
    seq = {}
    for prim, axes in _collectives_in(record.jaxpr):
        for axis in axes:
            seq.setdefault(axis, []).append(prim)
    return {axis: tuple(ops) for axis, ops in seq.items()}


# ---------------------------------------------------------------------------
# JX101 baked-constant
# ---------------------------------------------------------------------------

@trace_rule("JX101", "baked-constant",
            "large arrays captured by closure become jaxpr constants — "
            "copied into every compiled variant and silently stale after "
            "host-side updates; pass them as arguments")
def _jx101(rec, cfg):
    for var, const in zip(rec.jaxpr.constvars, rec.consts):
        nbytes = _aval_nbytes(var.aval)
        if nbytes < cfg.const_bytes:
            continue
        yield rec.finding(
            "JX101",
            "%s constant (%d bytes) baked into the program — a closure "
            "capture; the compiled program holds a frozen copy that host "
            "mutations never reach. Pass it as an argument instead."
            % (_fmt_aval(var.aval), nbytes),
            key="const:%s" % _fmt_aval(var.aval))


# ---------------------------------------------------------------------------
# JX102 dtype-widening
# ---------------------------------------------------------------------------

@trace_rule("JX102", "dtype-widening",
            "f64/i64 values inside a program whose inputs are all "
            "<=32-bit: doubled HBM traffic and slow double-precision "
            "units, usually one forgotten numpy float64 scalar")
def _jx102(rec, cfg):
    def wide(aval):
        return str(getattr(aval, "dtype", "")) in _WIDE_DTYPES

    if any(wide(a) for a in rec.in_avals):
        return          # wide inputs: the caller asked for 64-bit
    seen = set()
    for var, _const in zip(rec.jaxpr.constvars, rec.consts):
        if wide(var.aval):
            key = ("const", str(var.aval.dtype))
            if key not in seen:
                seen.add(key)
                yield rec.finding(
                    "JX102",
                    "closure constant is %s while every program input is "
                    "<=32-bit — the widening happens before the program "
                    "boundary" % _fmt_aval(var.aval),
                    key="widen-const:%s" % var.aval.dtype)
    for eqn in _iter_eqns(rec.jaxpr):
        for var in eqn.outvars:
            aval = getattr(var, "aval", None)
            if aval is None or not wide(aval):
                continue
            key = (eqn.primitive.name, str(aval.dtype))
            if key in seen:
                continue
            seen.add(key)
            yield rec.finding(
                "JX102",
                "'%s' produces %s in a program whose inputs are all "
                "<=32-bit — check for a python float / np.float64 scalar "
                "or an explicit astype widening the lattice"
                % (eqn.primitive.name, _fmt_aval(aval)),
                key="widen:%s:%s" % (eqn.primitive.name, aval.dtype))


# ---------------------------------------------------------------------------
# JX103 host-callback-in-hot-program
# ---------------------------------------------------------------------------

_CALLBACK_PRIMS = {"pure_callback", "io_callback", "debug_callback",
                   "debug_print"}

@trace_rule("JX103", "host-callback",
            "pure_callback/io_callback/debug.print compiled into an owned "
            "hot program: every execution round-trips through the host — "
            "the async dispatch pipeline stalls behind python")
def _jx103(rec, cfg):
    seen = set()
    for eqn in _iter_eqns(rec.jaxpr):
        prim = eqn.primitive.name
        if prim not in _CALLBACK_PRIMS or prim in seen:
            continue
        seen.add(prim)
        yield rec.finding(
            "JX103",
            "'%s' is compiled into this program: a host python call per "
            "execution. Debug prints belong outside the jit; data-dependent "
            "host logic belongs between programs, not inside them." % prim,
            key="callback:%s" % prim)


# ---------------------------------------------------------------------------
# JX104 donation-waste
# ---------------------------------------------------------------------------

@trace_rule("JX104", "donation-waste",
            "donated buffers that cannot alias any output (freed for "
            "nothing), large aliasable args left undonated in a program "
            "that already donates, and dead pass-through/constant outputs")
def _jx104(rec, cfg):
    # multiset of output avals available for aliasing
    pool = {}
    for aval in rec.out_avals:
        key = _aval_key(aval)
        pool[key] = pool.get(key, 0) + 1

    # donated args consume matching outputs first (they will alias)
    for i in sorted(rec.donated):
        aval = rec.in_avals[i]
        key = _aval_key(aval)
        if pool.get(key, 0) > 0:
            pool[key] -= 1
        else:
            yield rec.finding(
                "JX104",
                "%s (%s) is donated but no output has a matching "
                "shape/dtype — XLA frees the buffer without reusing it, "
                "and the caller lost the ability to read it for nothing"
                % (rec.label(i), _fmt_aval(aval)),
                key="donate-unaliasable:%s" % rec.label(i))

    # a program that already donates, leaving a LARGE aliasable arg
    # undonated, is leaving HBM on the table (grads kept for grad_req=add
    # are the legitimate exception — suppress or baseline those)
    if rec.donated:
        for i, aval in enumerate(rec.in_avals):
            if i in rec.donated:
                continue
            nbytes = _aval_nbytes(aval)
            if nbytes < cfg.donation_bytes:
                continue
            key = _aval_key(aval)
            if pool.get(key, 0) > 0:
                pool[key] -= 1
                yield rec.finding(
                    "JX104",
                    "%s (%s, %d bytes) aliases an output aval but is not "
                    "donated in a program that donates other args — "
                    "donating it would save one HBM-resident copy"
                    % (rec.label(i), _fmt_aval(aval), nbytes),
                    key="donate-missed:%s" % rec.label(i))

    # dead outputs: identity pass-through of an input, or a constant
    invar_pos = {id(v): i for i, v in enumerate(rec.jaxpr.invars)}
    for k, var in enumerate(rec.jaxpr.outvars):
        aval = getattr(var, "aval", None)
        if aval is None or _aval_nbytes(aval) < cfg.passthrough_bytes:
            continue
        if id(var) in invar_pos:
            i = invar_pos[id(var)]
            if i in rec.donated:
                continue   # donated pass-through: XLA aliases it, free
            yield rec.finding(
                "JX104",
                "output #%d (%s) is an unmodified pass-through of input "
                "%s — XLA must still materialize a fresh output copy; "
                "drop it from the returns and reuse the input at the "
                "call site" % (k, _fmt_aval(aval), rec.label(i)),
                key="dead-output:passthrough:%d" % k)
        elif hasattr(var, "val"):     # Literal output
            yield rec.finding(
                "JX104",
                "output #%d (%s) is a compile-time constant — computed "
                "nowhere, transferred every call" % (k, _fmt_aval(aval)),
                key="dead-output:const:%d" % k)


# ---------------------------------------------------------------------------
# JX201 collective-divergence
# ---------------------------------------------------------------------------

def _branch_label(i, n):
    if n == 2:
        return ("false-branch", "true-branch")[i]
    return "branch %d" % i


@trace_rule("JX201", "collective-divergence",
            "a collective under a data-dependent branch: lax.cond arms "
            "that disagree on their collective sequence, or a collective "
            "inside a while whose trip count ranks can disagree on — one "
            "rank enters the rendezvous, its peers never do, the mesh "
            "deadlocks; branch the VALUES with jnp.where instead")
def _jx201(rec, cfg):
    # Conservative on purpose: a cond predicate we could prove uniform
    # across ranks would be safe, but nothing at the jaxpr level proves
    # uniformity — suppress/baseline the (rare) justified case.
    for eqn in _iter_eqns(rec.jaxpr):
        prim = eqn.primitive.name
        if prim == "cond":
            branches = eqn.params.get("branches", ())
            sigs = [_collectives_in(br) for br in _extract_jaxprs(
                tuple(branches))]
            if len(set(sigs)) <= 1:
                continue          # all arms rendezvous identically: safe
            parts = []
            for i, sig in enumerate(sigs):
                shown = ",".join("%s@%s" % (p, "/".join(a))
                                 for p, a in sig) or "none"
                parts.append("%s: %s" % (_branch_label(i, len(sigs)),
                                         shown))
            yield rec.finding(
                "JX201",
                "lax.cond arms disagree on their collective sequence "
                "(%s) — a data-dependent predicate lets ranks take "
                "different arms and deadlock on the missing rendezvous; "
                "run the collective unconditionally and jnp.where the "
                "values" % "; ".join(parts),
                key="cond-divergence")
        elif prim == "while":
            colls = []
            for pkey in ("cond_jaxpr", "body_jaxpr"):
                sub = eqn.params.get(pkey)
                if sub is not None:
                    for j in _extract_jaxprs(sub):
                        colls.extend(_collectives_in(j))
            if not colls:
                continue
            shown = ",".join("%s@%s" % (p, "/".join(a))
                             for p, a in colls)
            yield rec.finding(
                "JX201",
                "collective(s) %s inside a lax.while_loop: the trip "
                "count is data-dependent by construction, so ranks can "
                "run the rendezvous a different number of times and "
                "deadlock — use a static-length scan (mask the tail) or "
                "hoist the collective out of the loop" % shown,
                key="while-collective")


# ---------------------------------------------------------------------------
# JX202 collective-order (per-record declared-axis check + lane groups)
# ---------------------------------------------------------------------------

@trace_rule("JX202", "collective-order",
            "per-mesh-axis collective sequences must match across "
            "programs sharing a lane and stay on the axes the provider "
            "declared — the canonical reduction order (PR 13) as a "
            "proven invariant")
def _jx202(rec, cfg):
    declared = rec.meta.get("mesh_axes")
    if declared is None:
        return
    declared = {str(a) for a in declared}
    seen = set()
    for eqn in _iter_eqns(rec.jaxpr):
        if eqn.primitive.name not in _COLLECTIVE_PRIMS:
            continue
        for axis in _collective_axes(eqn):
            if axis in declared or axis in seen:
                continue
            seen.add(axis)
            yield rec.finding(
                "JX202",
                "'%s' communicates over mesh axis '%s' which the "
                "provider did not declare (mesh_axes=%s) — an "
                "undeclared axis is invisible to the lane-order "
                "contract; declare it or drop the collective"
                % (eqn.primitive.name, axis, sorted(declared)),
                key="undeclared-axis:%s" % axis)


GROUP_RULES = {}


def _group_rule(code):
    def deco(fn):
        GROUP_RULES[code] = fn
        return fn
    return deco


@_group_rule("JX202")
def _jx202_group(records, cfg):
    """Cross-program half of JX202: programs sharing a provider-declared
    ``lane`` run concurrently on the same serialized collective stream,
    so their per-axis collective sequences must be identical — two
    members disagreeing on order is the classic cross-program deadlock
    (rank A runs program P's psum while rank B runs program Q's
    ppermute).  Today's lane members are collective-free or identical;
    the rule is the tripwire for drift."""
    lanes = {}
    for rec in records:
        lane = rec.meta.get("lane")
        if lane:
            lanes.setdefault(lane, []).append(rec)
    for lane in sorted(lanes):
        recs = lanes[lane]
        if len(recs) < 2:
            continue
        ref, ref_seq = recs[0], collective_sequence(recs[0])
        for rec in recs[1:]:
            seq = collective_sequence(rec)
            axes = sorted(set(ref_seq) | set(seq))
            for axis in axes:
                if ref_seq.get(axis, ()) == seq.get(axis, ()):
                    continue
                yield rec.finding(
                    "JX202",
                    "lane '%s' collective order diverges from '%s' on "
                    "axis '%s': %s vs %s — concurrent programs on one "
                    "lane must rendezvous in one canonical order"
                    % (lane, ref.name, axis,
                       list(seq.get(axis, ())),
                       list(ref_seq.get(axis, ()))),
                    key="lane-order:%s:%s" % (lane, axis))


# ---------------------------------------------------------------------------
# JX203 replication-waste
# ---------------------------------------------------------------------------

# ops that forward a gathered value unchanged (same bytes, new var)
_TRANSPARENT_PRIMS = {"convert_element_type", "reshape", "transpose",
                      "squeeze", "expand_dims", "copy", "stop_gradient",
                      "rev"}


@trace_rule("JX203", "replication-waste",
            "an all_gather whose fully-replicated result is returned as "
            "a program output: the sharded producer's bytes are "
            "multiplied by the axis size in HBM — keep the output "
            "sharded (out_specs) or reduce before returning")
def _jx203(rec, cfg):
    for jaxpr in _all_jaxprs(rec.jaxpr):
        gathered = {}          # id(var) -> (axes, nbytes)
        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            if prim in ("all_gather", "all_gather_invariant"):
                axes = _collective_axes(eqn)
                if not axes:
                    continue
                for ov in eqn.outvars:
                    gathered[id(ov)] = (axes, _aval_nbytes(ov.aval))
            elif prim in _TRANSPARENT_PRIMS and eqn.invars \
                    and id(eqn.invars[0]) in gathered:
                axes, _n = gathered[id(eqn.invars[0])]
                for ov in eqn.outvars:
                    gathered[id(ov)] = (axes, _aval_nbytes(ov.aval))
        seen = set()
        for k, var in enumerate(jaxpr.outvars):
            info = gathered.get(id(var))
            if info is None or id(var) in seen:
                continue
            seen.add(id(var))
            axes, nbytes = info
            if nbytes < cfg.replication_bytes:
                continue
            yield rec.finding(
                "JX203",
                "output #%d (%s, %d bytes) is an all_gather over axis "
                "%s returned fully replicated — every rank materializes "
                "the whole array; shard the output spec or reduce "
                "before returning"
                % (k, _fmt_aval(getattr(var, "aval", None)), nbytes,
                   "/".join(axes)),
                key="gathered-output:%s" % "/".join(axes))


# ---------------------------------------------------------------------------
# JX204 memory-budget (driver-level: needs compile + MEM_BASELINE.json)
# ---------------------------------------------------------------------------

MEM_FIELDS = ("argument_bytes", "output_bytes", "temp_bytes",
              "generated_code_bytes", "alias_bytes")
# the budgeted figure: alias bytes are savings, not spend
_MEM_TOTAL_FIELDS = ("argument_bytes", "output_bytes", "temp_bytes",
                     "generated_code_bytes")

TRACE_RULES["JX204"] = TraceRule(
    "JX204", "memory-budget",
    "per-program compiled.memory_analysis() bytes (argument/output/temp/"
    "generated-code) vs the count-keyed MEM_BASELINE.json budget with an "
    "MXNET_MEM_TOLERANCE band — growth past budget is a lint-time "
    "finding, not an OOM at step time (driver tier: needs a compile)",
    None)


def default_mem_baseline_path():
    from .core import repo_root
    return os.path.join(repo_root(), "MEM_BASELINE.json")


def mem_tolerance(default=0.25):
    """The MXNET_MEM_TOLERANCE fractional band (0.25 = +25% headroom).
    Parsed per call — this only runs in the AOT driver and on compile
    events, never on the step path."""
    # driver/compile-event tier only, never the step path; a fresh read
    # per check lets tests and CI move the band without process restarts
    raw = os.environ.get("MXNET_MEM_TOLERANCE", "")  # graftlint: disable=JG006
    try:
        val = float(raw) if raw else default
    except ValueError:
        return default
    return val if val >= 0 else default


# byte jitter floor: sub-4KiB drift on tiny specimens is allocator noise,
# not a regression — the tolerance band is fractional, this is absolute
_MEM_SLACK_BYTES = 4096


def record_digest(rec):
    """Stable identity of a specimen's trace signature (in/out avals).
    Budgets are per-specimen: the runtime hook only compares a compile
    whose signature matches what the budget was captured from."""
    import hashlib
    sig = ";".join(_fmt_aval(a) for a in rec.in_avals) + "->" + \
        ";".join(_fmt_aval(a) for a in rec.out_avals)
    return hashlib.sha1(sig.encode("utf-8")).hexdigest()[:12]


def compile_record(rec):
    """Compile *rec*'s kept AOT lowering (the JX204 compile path — also
    what ``telemetry.opprof`` reuses for its HLO walk, so attribution
    adds zero new XLA entry points).  Returns the compiled executable,
    or None when there is no lowering or the backend refuses."""
    if rec.lowered is None:
        return None
    try:
        return rec.lowered.compile()
    except Exception:
        return None


def measure_memory(rec):
    """Compile *rec*'s kept lowering and return its memory_analysis()
    byte fields, or None when the backend cannot report them."""
    compiled = compile_record(rec)
    if compiled is None:
        return None
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return None
    if ma is None:
        return None
    out = {}
    for field in MEM_FIELDS:
        xla_name = field.replace("_bytes", "_size_in_bytes")
        try:
            out[field] = int(getattr(ma, xla_name))
        except (AttributeError, TypeError, ValueError):
            out[field] = 0
    out["total_bytes"] = sum(out[f] for f in _MEM_TOTAL_FIELDS)
    return out


def measure_programs(records):
    """Aggregate measured memory per program NAME (count-keyed: a name
    traced from k specimens sums its bytes and records ``specimens: k``
    so dropping a specimen is as visible as growing one).  Returns
    ``{name: entry}``; an unmeasurable specimen is recorded with
    ``measured: False`` rather than silently skipped."""
    import hashlib
    out = {}
    for rec in records:
        entry = out.setdefault(rec.name, dict(
            {f: 0 for f in MEM_FIELDS}, total_bytes=0, specimens=0,
            measured=True, digests=[]))
        entry["specimens"] += 1
        entry["digests"].append(record_digest(rec))
        m = measure_memory(rec)
        if m is None:
            entry["measured"] = False
            continue
        for f in MEM_FIELDS:
            entry[f] += m[f]
        entry["total_bytes"] += m["total_bytes"]
    for entry in out.values():
        digest = hashlib.sha1(
            ",".join(sorted(entry.pop("digests"))).encode()).hexdigest()
        entry["digest"] = digest[:12]
    return out


def _device_count():
    import jax
    return len(jax.devices())


def load_mem_baseline(path=None):
    """MEM_BASELINE.json -> dict, or None when absent/unreadable."""
    path = path or default_mem_baseline_path()
    if not os.path.exists(path):
        return None
    try:
        with open(path, encoding="utf-8") as f:
            payload = json.load(f)
    except (OSError, ValueError):
        return None
    if not isinstance(payload.get("programs"), dict):
        return None
    return payload


def save_mem_baseline(measured, path=None, n_devices=None, prior=None,
                      scoped_names=None):
    """Write *measured* (from :func:`measure_programs`) as the budget.
    A scoped run (``--diff``/entry groups) merges: names outside
    *scoped_names* keep their prior entries untouched, exactly like the
    LINT baseline's out-of-scope preservation."""
    path = path or default_mem_baseline_path()
    programs = {}
    if prior and scoped_names is not None:
        programs.update({k: v for k, v in prior.get("programs", {}).items()
                         if k not in scoped_names})
    programs.update(measured)
    payload = {"version": 1,
               "n_devices": int(n_devices if n_devices is not None
                                else _device_count()),
               "tolerance": mem_tolerance(),
               "programs": {k: programs[k] for k in sorted(programs)}}
    with open(path, "w", encoding="utf-8") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
        f.write("\n")
    return payload


def check_memory(records, baseline=None, tolerance=None, full=True):
    """JX204 over measured *records* vs *baseline* (a loaded
    MEM_BASELINE payload).  Returns ``(findings, report)`` where report
    is the stdlib-renderable dict ``trace_report.py --memory`` consumes.

    Topology honesty: memory bytes are a function of the device count
    the specimens lower against (conftest pins 8 virtual CPU devices);
    when the live topology differs from the baseline's, comparison is
    SKIPPED and the report says so — a gate that cannot measure must
    fail loudly downstream (``--gate-memory`` exits 4), never drift."""
    tol = mem_tolerance() if tolerance is None else tolerance
    n_dev = _device_count()
    measured = measure_programs(records)
    base_progs = (baseline or {}).get("programs", {})
    base_dev = (baseline or {}).get("n_devices")
    topology_match = baseline is not None and int(base_dev or 0) == n_dev
    findings = []
    report_programs = []
    by_name = {}
    for rec in records:
        by_name.setdefault(rec.name, rec)
    for name in sorted(measured):
        entry = dict(measured[name])
        rec = by_name[name]
        budget = base_progs.get(name) if topology_match else None
        entry.update(name=name, origin=rec.origin,
                     budget_total_bytes=None, over_budget=False,
                     unbudgeted=False)
        if not entry.pop("measured"):
            entry["unbudgeted"] = True
            findings.append(rec.finding(
                "JX204", "program could not be compiled for "
                "memory_analysis() — the budget gate cannot see it",
                key="mem:unmeasurable"))
        elif baseline is None or (topology_match and budget is None):
            entry["unbudgeted"] = True
            findings.append(rec.finding(
                "JX204",
                "no memory budget for this program in MEM_BASELINE.json "
                "— every owned program is born budgeted; run "
                "graftcheck --write-mem-baseline", key="mem:unbudgeted"))
        elif budget is not None:
            if int(budget.get("specimens", 1)) != entry["specimens"]:
                findings.append(rec.finding(
                    "JX204",
                    "specimen count changed (%d budgeted, %d traced) — "
                    "the budget no longer describes this program; "
                    "re-run --write-mem-baseline"
                    % (int(budget.get("specimens", 1)),
                       entry["specimens"]), key="mem:specimens"))
            b_total = int(budget.get("total_bytes", 0))
            limit = b_total + max(int(b_total * tol), _MEM_SLACK_BYTES)
            entry["budget_total_bytes"] = b_total
            if entry["total_bytes"] > limit:
                entry["over_budget"] = True
                deltas = ", ".join(
                    "%s %+d" % (f, entry[f] - int(budget.get(f, 0)))
                    for f in _MEM_TOTAL_FIELDS
                    if entry[f] != int(budget.get(f, 0)))
                findings.append(rec.finding(
                    "JX204",
                    "memory over budget: %d bytes vs %d budgeted "
                    "(+%d%% tolerance -> limit %d) [%s] — an HBM "
                    "regression caught at lint time; shrink the program "
                    "or re-budget deliberately with --write-mem-baseline"
                    % (entry["total_bytes"], b_total, int(tol * 100),
                       limit, deltas or "same fields"),
                    key="mem:over"))
        report_programs.append(entry)
    stale = []
    if topology_match and full:
        stale = sorted(set(base_progs) - set(measured))
    report = {"schema": "memcheck-v1", "n_devices": n_dev,
              "tolerance": tol,
              "baseline_n_devices": base_dev,
              "baseline_present": baseline is not None,
              "topology_match": bool(topology_match),
              "stale_budgets": stale,
              "programs": report_programs}
    return findings, report


# ---------------------------------------------------------------------------
# JX105 retrace-explainer (runtime-only; registered for the catalogue)
# ---------------------------------------------------------------------------

TRACE_RULES["JX105"] = TraceRule(
    "JX105", "retrace-explainer",
    "on a watch_jit recompile, diff the new avals/static args against "
    "the cached variants and name the axis that changed (runtime tier, "
    "MXNET_TRACECHECK)", None)


def signature(args, kwargs):
    """Flat trace signature of a call: [(label, kind, detail...)] —
    arrays collapse to shape/dtype, everything else to type + repr."""
    import jax
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        (tuple(args), dict(kwargs or {})))
    sig = []
    for path, leaf in flat:
        label = jax.tree_util.keystr(path)
        label = ("arg%s" % label[3:]) if label.startswith("[0]") \
            else ("kwarg%s" % label[3:])
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is not None and dtype is not None:
            sig.append((label, "array", tuple(shape), str(dtype)))
        else:
            sig.append((label, "static", type(leaf).__name__,
                        repr(leaf)[:80]))
    return sig


def _diff_entries(old, new):
    """Human sentences for what changed between two signature entries."""
    label = new[0]
    if old[1] == "array" and new[1] == "array":
        msgs = []
        if old[2] != new[2]:
            axes = [("axis %d: %s->%s" % (d, o, n))
                    for d, (o, n) in enumerate(zip(old[2], new[2]))
                    if o != n]
            if len(old[2]) != len(new[2]):
                axes.append("rank %d->%d" % (len(old[2]), len(new[2])))
            msgs.append("%s shape %s->%s (%s)"
                        % (label, old[2], new[2], ", ".join(axes)))
        if old[3] != new[3]:
            msgs.append("%s dtype %s->%s" % (label, old[3], new[3]))
        return msgs
    if old[1] != new[1]:
        return ["%s changed kind %s->%s" % (label, old[1], new[1])]
    if old[2:] != new[2:]:
        return ["%s static value %s -> %s (each distinct hashable value "
                "is a separate compiled variant)" % (label, old[3], new[3])]
    return []


def explain_retrace(name, history, new_sig):
    """Diff *new_sig* against its closest cached variant and name the
    axis of change.  Returns the one-line diagnosis."""
    def diffs_against(old):
        old_map = {e[0]: e for e in old}
        new_map = {e[0]: e for e in new_sig}
        out = []
        for label, entry in new_map.items():
            if label in old_map:
                out.extend(_diff_entries(old_map[label], entry))
            else:
                out.append("%s appeared (structure change)" % label)
        for label in old_map:
            if label not in new_map:
                out.append("%s disappeared (structure change)" % label)
        return out

    best = min((diffs_against(old) for old in history), key=len)
    if not best:
        return ("recompile of '%s' with no visible shape/dtype/structure "
                "change — suspect weak_type promotion, sharding change, or "
                "a non-pytree closure input" % name)
    shown = "; ".join(best[:4])
    if len(best) > 4:
        shown += "; ... %d more" % (len(best) - 4)
    return ("recompile of '%s' caused by: %s — pad or bucket the changing "
            "axis so the compiled program is reused" % (name, shown))


# ---------------------------------------------------------------------------
# running rules
# ---------------------------------------------------------------------------

def run_rules(record, select=None, config=None):
    cfg = config or DEFAULT_CONFIG
    findings = []
    for code, rule in sorted(TRACE_RULES.items()):
        if select is not None and code not in select:
            continue
        findings.extend(rule.check(record, cfg))
    return findings


def run_group_rules(records, select=None, config=None):
    """The cross-program rules (JX202 lane order): per-record checks
    cannot see two programs at once, so the driver hands the whole
    record set over after tracing."""
    cfg = config or DEFAULT_CONFIG
    findings = []
    for code in sorted(GROUP_RULES):
        if select is not None and code not in select:
            continue
        findings.extend(GROUP_RULES[code](records, cfg))
    return findings


# ---------------------------------------------------------------------------
# AOT driver over the owned entry points
# ---------------------------------------------------------------------------

# (group, module) — each module owns jits and exposes tracecheck_programs()
# yielding (name, fn, args, kwargs) specimens for every program it ships.
ENTRY_POINTS = (
    ("kvstore", "mxnet_tpu.kvstore"),
    ("collective", "mxnet_tpu.parallel.collective"),
    ("optimizer", "mxnet_tpu.optimizer"),
    ("fused_trainer", "mxnet_tpu.gluon.fused_trainer"),
    ("executor", "mxnet_tpu.executor"),
    ("module_cached_step", "mxnet_tpu.module.cached_step"),
    ("gluon_cached_op", "mxnet_tpu.gluon.block"),
    ("predict", "mxnet_tpu.predict"),
    ("serving", "mxnet_tpu.serving.program"),
    ("guardian", "mxnet_tpu.guardian"),
    ("gluon_utils", "mxnet_tpu.gluon.utils"),
    ("pipeline", "mxnet_tpu.parallel.pipeline"),
    ("ring_attention", "mxnet_tpu.parallel.ring_attention"),
    ("sharded_trainer", "mxnet_tpu.parallel.sharded"),
    ("transformer", "mxnet_tpu.models.transformer"),
    ("model_stats", "mxnet_tpu.model_stats"),
)


def iter_owned_programs(entries=None):
    """Yield (group, ProgramRecord-or-Finding) over every owned entry
    point.  A provider that fails to build/trace yields a JX000 finding —
    silent skips would read as coverage."""
    import importlib
    for group, modpath in ENTRY_POINTS:
        if entries is not None and group not in entries:
            continue
        origin = modpath.replace(".", "/") + ".py"
        try:
            mod = importlib.import_module(modpath)
            programs = list(mod.tracecheck_programs())
        except Exception as exc:
            yield group, Finding(
                "JX000", "trace://%s" % group, 0, 0,
                "entry point provider %s failed: %r" % (modpath, exc),
                snippet="provider:%s" % group)
            continue
        for spec in programs:
            # 4-tuple (name, fn, args, kwargs) or 5-tuple with a trailing
            # sharding-metadata dict ({"lane": ..., "mesh_axes": ...})
            name, fn, args, kwargs = spec[:4]
            meta = spec[4] if len(spec) > 4 else None
            try:
                yield group, trace_program(name, fn, args, kwargs,
                                           origin=origin, meta=meta)
            except Exception as exc:
                yield group, Finding(
                    "JX000", "trace://%s" % name, 0, 0,
                    "tracing '%s' (%s) failed: %r" % (name, origin, exc),
                    snippet="trace:%s" % name)


# beyond lint/ itself, these files steer every trace-tier verdict: the
# opprof HLO walk is an analyzer over the same specimen ledger, and the
# costs peak tables decide its compute/HBM/comm classifications
_FULL_SWEEP_PATHS = frozenset({
    "mxnet_tpu/telemetry/opprof.py",
    "mxnet_tpu/telemetry/costs.py",
})


def groups_for_paths(paths):
    """Map changed repo-relative .py paths onto the ENTRY_POINTS groups
    they provide — the ``--diff`` scope for the trace tier.  A change to
    the analyzer itself (``mxnet_tpu/lint/``), to the opprof attribution
    walk, or to the cost-model peak tables dirties every group: the
    rules changed, so every verdict did."""
    norm = {p.replace(os.sep, "/") for p in paths}
    if any(p.startswith("mxnet_tpu/lint/") or p in _FULL_SWEEP_PATHS
           for p in norm):
        return {g for g, _m in ENTRY_POINTS}
    hit = set()
    for group, modpath in ENTRY_POINTS:
        mod_file = modpath.replace(".", "/") + ".py"
        pkg_init = modpath.replace(".", "/") + "/__init__.py"
        if mod_file in norm or pkg_init in norm:
            hit.add(group)
    return hit


def analyze_entry_points(entries=None, select=None, config=None,
                         memory=True, mem_baseline_path=None):
    """The full JX driver: trace every owned program, run the
    per-record rules, the cross-program lane rules, and (when *memory*)
    the JX204 budget comparison.  Returns ``(findings, names,
    mem_report)`` — mem_report is None when the memory pass was skipped
    or JX204 deselected."""
    findings, names, records = [], [], []
    for _group, item in iter_owned_programs(entries):
        if isinstance(item, Finding):
            findings.append(item)
            continue
        names.append(item.name)
        records.append(item)
        findings.extend(run_rules(item, select=select, config=config))
    findings.extend(run_group_rules(records, select=select, config=config))
    mem_report = None
    if memory and (select is None or "JX204" in select):
        baseline = load_mem_baseline(mem_baseline_path)
        mem_findings, mem_report = check_memory(
            records, baseline, full=entries is None)
        findings.extend(mem_findings)
    findings.sort(key=lambda f: (f.path, f.rule, f.snippet))
    return findings, names, mem_report


def check_entry_points(entries=None, select=None, config=None,
                       memory=True, mem_baseline_path=None):
    """Run the JX rules over every owned program; returns (findings,
    program_names) — names prove coverage to the CI gate."""
    findings, names, _mem = analyze_entry_points(
        entries=entries, select=select, config=config, memory=memory,
        mem_baseline_path=mem_baseline_path)
    return findings, names


# ---------------------------------------------------------------------------
# runtime hook (MXNET_TRACECHECK): called by telemetry on compile events
# ---------------------------------------------------------------------------

_SIG_HISTORY = {}    # (watch name, id(jit)) -> [signature, ...] (last 8)
_SEQ_HISTORY = {}    # (watch name, id(jit)) -> first variant's per-axis seq
_MEM_BASELINE_CACHE = []   # [payload-or-None], loaded once per process
_RUNTIME_CONFIG = DEFAULT_CONFIG


def reset_runtime():
    _SIG_HISTORY.clear()
    _SEQ_HISTORY.clear()
    del _MEM_BASELINE_CACHE[:]


def _runtime_spmd_checks(name, fn, record):
    """The JX2xx runtime slice: JX202 across a program's own compiled
    variants (two variants of one watch name disagreeing on collective
    order is the same lane hazard, caught live), and JX204 only when the
    compile's trace signature matches the digest its budget was captured
    from — a real model compiling under the same watch name is a
    different program and must not be judged by the specimen's budget
    (or pay a second compile)."""
    findings = []
    key = (name, id(fn))
    seq = collective_sequence(record)
    prev = _SEQ_HISTORY.setdefault(key, seq)
    if prev is not seq and prev != seq:
        findings.append(record.finding(
            "JX202",
            "compiled variant changed the collective order: %s vs the "
            "first variant's %s — variants of one program must "
            "rendezvous in one canonical order"
            % ({a: list(s) for a, s in sorted(seq.items())},
               {a: list(s) for a, s in sorted(prev.items())}),
            key="variant-order"))
    if not _MEM_BASELINE_CACHE:
        _MEM_BASELINE_CACHE.append(load_mem_baseline())
    baseline = _MEM_BASELINE_CACHE[0]
    if baseline is not None:
        budget = baseline.get("programs", {}).get(record.name)
        if budget is not None \
                and int(baseline.get("n_devices", 0)) == _device_count() \
                and int(budget.get("specimens", 1)) == 1 \
                and budget.get("digest") == record_digest(record):
            mem_findings, _report = check_memory(
                [record], baseline, full=False)
            findings.extend(mem_findings)
    return findings


def on_compile(name, fn, args, kwargs):
    """Analyze the program a watched jit just compiled.

    Called from ``telemetry._WatchedJit`` on cache growth when
    ``MXNET_TRACECHECK`` is truthy.  JX105 diffs the call signature
    against this name's previous variants; JX101-JX104 re-trace the
    function from specs (cheap next to the XLA compile that just
    happened).  Findings are booked into the ``tracecheck_findings``
    counter, the flight ring, and one structured log line each; this
    function never raises into the training step.
    """
    findings = []
    try:
        sig = signature(args, kwargs)
    except Exception:
        sig = None
    # keyed per jitted fn, not per watch name: distinct programs sharing
    # a name (a cached op's train/eval pair, every optimizer instance
    # under "optimizer_update_step") are separate compile caches — their
    # first compiles are not recompiles of each other
    history = _SIG_HISTORY.setdefault((name, id(fn)), [])
    if sig is not None:
        if history:
            findings.append(Finding(
                "JX105", "trace://%s" % name, 0, 0,
                explain_retrace(name, history, sig), snippet=name))
        history.append(sig)
        del history[:-8]
    try:
        record = trace_program(name, fn, args, kwargs)
        findings.extend(run_rules(record, config=_RUNTIME_CONFIG))
        findings.extend(_runtime_spmd_checks(name, fn, record))
    except Exception:
        pass                   # analysis must never break a step
    _book(findings)
    return findings


def _book(findings):
    if not findings:
        return
    try:
        from .. import telemetry as _tel
        from ..telemetry import flight as _flight
        _tel.bump("tracecheck_findings", len(findings))
        for f in findings:
            _flight.record("tracecheck", f.rule, detail=f.message[:200])
            _LOG.warning("tracecheck %s", json.dumps(
                {"rule": f.rule, "program": f.path[len("trace://"):],
                 "finding": f.message}, sort_keys=True))
    except Exception:
        pass


