"""XLA cost accounting: per-program FLOPs/bytes → MFU and roofline.

"Fast" is meaningless without a denominator.  XLA's compiler already
computes an analytical cost model for every program it emits — the same
style of model TVM (arxiv 1802.04799) and the Julia-to-TPU pipeline
(arxiv 1810.09868) build their schedulers on — and hands it to us for
free via ``compiled.cost_analysis()``.  This module turns that into the
three judgement numbers every perf PR gets measured against:

* ``step_model_flops``  — FLOPs the step's compiled programs executed
* ``step_mfu``          — model FLOP utilization: flops / (dur × peak)
* ``step_hbm_bw_util``  — bytes-accessed / (dur × peak HBM bandwidth)

Capture happens once per compile event (``core._WatchedJit`` calls
:func:`capture`): the freshly compiled program is re-lowered from
``ShapeDtypeStruct`` specs — metadata only, safe even when the call
donated and deleted its input buffers — and its cost analysis cached per
watched-jit name.  Every subsequent watched call inside an open step
span adds its cached cost to the step window; ``core`` closes the window
at step-span exit by calling :func:`finalize_step`.

Peaks come from a per-device-kind table (per JAX device, i.e. per TPU
core on v2/v3 and per chip from v4 on), multiplied by the local device
count — MFU of an 8-chip step is measured against 8 chips.  Override
with ``MXNET_PEAK_FLOPS`` / ``MXNET_PEAK_HBM_BW`` (aggregate values,
used verbatim), which is also how CPU runs get an honest denominator:
the CPU table entry is a placeholder, not a measurement.

Known approximations, accepted on purpose:

* cost is cached per watched-jit *name*; a name whose cache holds many
  shape variants reports its most recently compiled variant.
* ``cost_analysis`` counts model FLOPs (what the HLO asks for), not
  hardware FLOPs — that is exactly what MFU wants (padding and
  recomputation are waste, not work).
"""
from __future__ import annotations

import os

from . import core

__all__ = ["capture", "analyze_compiled", "finalize_step", "peaks",
           "peaks_if_resolved", "refresh_from_env", "machine_balance",
           "device_peaks", "PEAK_TABLE", "ICI_TABLE"]

_TRUTHY = ("1", "true", "on", "yes")

# (peak FLOP/s, peak HBM bytes/s) per JAX device, keyed on device_kind
# exactly as jax reports it — THE peak table of the package (the
# benchmark keeps its own v5e row in chipbench/peaks.py on purpose, so a
# change here cannot move the yardstick).  bf16/dense numbers
# from the published per-chip specs, halved for the two-core-per-chip
# generations where jax exposes cores as devices.  The one row measured
# against so far: a v5e chip reports device_kind "TPU v5 lite"
# (chip run, PR 21); 197 TFLOP/s bf16 and 819 GB/s HBM are from Google
# Cloud's "TPU v5e" system-architecture page.  A device_kind missing
# here is an error (see peaks()), never a default row.
PEAK_TABLE = {
    "TPU v2":      (22.5e12, 350e9),
    "TPU v3":      (61.5e12, 450e9),
    "TPU v4":      (275e12, 1228e9),
    "TPU v4 lite": (137.5e12, 614e9),
    "TPU v5":      (459e12, 2765e9),
    "TPU v5p":     (459e12, 2765e9),
    "TPU v5 lite": (197e12, 819e9),
    "TPU v5e":     (197e12, 819e9),
    "TPU v6 lite": (918e12, 1640e9),
    "TPU v6e":     (918e12, 1640e9),
    # CPU: order-of-magnitude placeholder (a modern server socket's f32
    # peak); pin MXNET_PEAK_FLOPS for a real CPU MFU
    "cpu":         (1e11, 50e9),
}

# peak interconnect bytes/s per JAX device (aggregate over a chip's ICI
# links) — the denominator for the "comm" leg of the opprof roofline.
# Same caveat as PEAK_TABLE: spec-sheet order-of-magnitude numbers, not
# measurements; pin MXNET_PEAK_ICI_BW (aggregate, verbatim) for honesty.
ICI_TABLE = {
    "TPU v2":      (62e9,),
    "TPU v3":      (82e9,),
    "TPU v4":      (300e9,),
    "TPU v4 lite": (150e9,),
    "TPU v5":      (600e9,),
    "TPU v5p":     (600e9,),
    "TPU v5 lite": (200e9,),
    "TPU v5e":     (200e9,),
    "TPU v6 lite": (400e9,),
    "TPU v6e":     (400e9,),
    # CPU: virtual devices share one memory system; collectives are
    # memcpys, so the "interconnect" placeholder sits below HBM peak
    "cpu":         (10e9,),
}


def _env_float(name):
    raw = os.environ.get(name, "").strip()
    if not raw:
        return None
    try:
        value = float(raw)
    except ValueError:
        return None
    return value if value > 0 else None


def _env_capture_enabled():
    return os.environ.get("MXNET_COST_ANALYSIS", "1").strip().lower() \
        not in ("0", "false", "off", "no")


# cached at import (JG006 cached-value pattern: finalize_step is on the
# step path); core.refresh_from_env() funnels into refresh_from_env()
_ENV_PEAK_FLOPS = _env_float("MXNET_PEAK_FLOPS")
_ENV_PEAK_BW = _env_float("MXNET_PEAK_HBM_BW")
_ENV_PEAK_ICI = _env_float("MXNET_PEAK_ICI_BW")
_CAPTURE = _env_capture_enabled()
_peaks = None                   # resolved {"flops","hbm_bw",...} or None


def refresh_from_env():
    """Re-read MXNET_PEAK_FLOPS / MXNET_PEAK_HBM_BW / MXNET_PEAK_ICI_BW
    / MXNET_COST_ANALYSIS and drop the resolved-peak cache."""
    global _ENV_PEAK_FLOPS, _ENV_PEAK_BW, _ENV_PEAK_ICI, _CAPTURE, _peaks
    _ENV_PEAK_FLOPS = _env_float("MXNET_PEAK_FLOPS")
    _ENV_PEAK_BW = _env_float("MXNET_PEAK_HBM_BW")
    _ENV_PEAK_ICI = _env_float("MXNET_PEAK_ICI_BW")
    _CAPTURE = _env_capture_enabled()
    _peaks = None


# --------------------------------------------------------------------------
# per-program capture
# --------------------------------------------------------------------------

def _spec(leaf):
    """Shape/dtype skeleton of one pytree leaf.  Works on donated (and
    already deleted) jax arrays: aval metadata survives buffer death."""
    shape = getattr(leaf, "shape", None)
    dtype = getattr(leaf, "dtype", None)
    if shape is None or dtype is None:
        return leaf              # python scalar etc: trace as-is
    import jax
    # a committed array's placement is part of the jit's cache key: with
    # it the re-lower below finds the lowering (and the executable) the
    # call itself just built, and nothing compiles a second time
    sharding = leaf.sharding if getattr(leaf, "committed", False) else None
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding,
                                weak_type=getattr(leaf, "weak_type", False))


def _normalize(analysis):
    """cost_analysis() shape varies by jax version: dict, or a
    one-per-partition list of dicts."""
    if isinstance(analysis, (list, tuple)):
        analysis = analysis[0] if analysis else {}
    if not isinstance(analysis, dict):
        return None
    flops = float(analysis.get("flops", 0.0) or 0.0)
    nbytes = float(analysis.get("bytes accessed", 0.0) or 0.0)
    if flops <= 0 and nbytes <= 0:
        return None
    return (flops, nbytes)


def capture(fn, args, kwargs, force=False):
    """(flops, bytes_accessed) of *fn* compiled for *args*/*kwargs*, or
    None.  Called by the watchdog ON COMPILE EVENTS ONLY — the re-lower
    here finds the trace, the lowering and the executable the call just
    made (the specs carry the arguments' placement, the rest of jit's
    cache key), and buys shape-safe AOT introspection.
    *force* bypasses the ``MXNET_COST_ANALYSIS`` gate for explicit API
    calls (``Executor.cost_analysis``).
    """
    if not (_CAPTURE or force):
        return None
    import jax
    sargs, skwargs = jax.tree_util.tree_map(_spec, (tuple(args),
                                                    dict(kwargs)))
    compiled = fn.lower(*sargs, **skwargs).compile()
    return analyze_compiled(compiled)


def analyze_compiled(compiled):
    """(flops, bytes_accessed) of an ALREADY-compiled executable, or
    None — the AOT twin of :func:`capture` for callers that hold the
    executable themselves (the serving bucket table compiles its
    variants ahead of time and should not pay a second lower+compile
    just to read the cost model)."""
    try:
        return _normalize(compiled.cost_analysis())
    except Exception:
        return None


# --------------------------------------------------------------------------
# peaks + step finalization
# --------------------------------------------------------------------------

def device_peaks(kind):
    """(FLOP/s, HBM bytes/s, ICI bytes/s) of ONE device of ``kind`` from
    the tables.  An unknown ``device_kind`` raises: a utilization
    measured against another device's peak is wrong, not approximate."""
    if kind not in PEAK_TABLE or kind not in ICI_TABLE:
        from ..base import MXNetError
        raise MXNetError(
            "no peak FLOP/s / bandwidth known for device_kind %r: add a "
            "sourced row to telemetry.costs.PEAK_TABLE / ICI_TABLE, or pin "
            "MXNET_PEAK_FLOPS, MXNET_PEAK_HBM_BW and MXNET_PEAK_ICI_BW"
            % (kind,))
    return PEAK_TABLE[kind] + ICI_TABLE[kind]


def peaks():
    """The aggregate (all local devices) peak FLOP/s and HBM bytes/s this
    process is measured against, resolved once and cached: the
    ``MXNET_PEAK_*`` pins where set, else :func:`device_peaks` x devices."""
    global _peaks
    if _peaks is not None:
        return _peaks
    import jax
    devs = jax.local_devices()
    n_dev = len(devs)
    kind = devs[0].device_kind
    # the tables are consulted (and an unknown kind raises) only when
    # some pin is missing
    table_flops, table_bw, table_ici = \
        device_peaks(kind) if None in (_ENV_PEAK_FLOPS, _ENV_PEAK_BW,
                                       _ENV_PEAK_ICI) else (0.0, 0.0, 0.0)
    flops = _ENV_PEAK_FLOPS if _ENV_PEAK_FLOPS is not None \
        else table_flops * n_dev
    bw = _ENV_PEAK_BW if _ENV_PEAK_BW is not None else table_bw * n_dev
    ici = _ENV_PEAK_ICI if _ENV_PEAK_ICI is not None else table_ici * n_dev
    _peaks = {"flops": flops, "hbm_bw": bw, "ici_bw": ici,
              "device_kind": kind, "n_devices": n_dev,
              "source": {"flops": "env" if _ENV_PEAK_FLOPS is not None
                         else "table",
                         "hbm_bw": "env" if _ENV_PEAK_BW is not None
                         else "table",
                         "ici_bw": "env" if _ENV_PEAK_ICI is not None
                         else "table"}}
    return _peaks


def machine_balance():
    """Peak FLOP/s over peak HBM bytes/s — the arithmetic-intensity
    knee of the roofline.  A unit whose FLOP/byte sits above this is
    compute-bound; below, HBM-bound."""
    pk = peaks()
    return pk["flops"] / pk["hbm_bw"] if pk["hbm_bw"] > 0 else 0.0


def peaks_if_resolved():
    """The cached peak dict without triggering device discovery (jax
    may not even be initialized when a snapshot is taken)."""
    return _peaks


def finalize_step(flops, nbytes, dur_us):
    """Close one step's cost window into the three gauges."""
    core.set_gauge("step_model_flops", flops)
    dur_s = dur_us / 1e6
    if dur_s <= 0:
        return
    pk = peaks()
    if flops > 0 and pk["flops"] > 0:
        core.set_gauge("step_mfu", flops / (dur_s * pk["flops"]))
    if nbytes > 0 and pk["hbm_bw"] > 0:
        core.set_gauge("step_hbm_bw_util", nbytes / (dur_s * pk["hbm_bw"]))
