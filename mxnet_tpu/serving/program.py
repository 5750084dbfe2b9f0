"""AOT predict programs: bucket-padded batch variants of one Predictor.

The deployment unit of the reference framework is an ahead-of-time
artifact (`c_predict_api` over the amalgamation build — PAPER layer 9);
TVM (arxiv 1802.04799) and the Julia-to-TPU pipeline (arxiv 1810.09868)
make the same argument for XLA: serve *compiled programs*, not graphs.
This module is that unit for the TPU build:

* At model load, the predictor's eval program (the executor's
  ``executor_eval`` jit — already watched, cost-accounted, and
  graftcheck-covered) is lowered **ahead of time** from
  ``ShapeDtypeStruct`` specimens at every bucket batch size and compiled
  into a table of XLA executables.  No data is touched and nothing runs
  at load beyond the compiles themselves.
* At request time a batch of n rows is padded up to the smallest bucket
  ``b >= n`` and dispatched straight to the bucket's executable.  There
  is **no jit dispatch on the request path**, so a retrace is
  structurally impossible — the property the PR-2 retrace watchdog can
  only report after the fact, made unrepresentable.
* A request larger than the biggest bucket takes the *straight-through*
  path: one unpadded call through the watched jit (which may compile a
  new variant, booked by the watchdog like any other compile).  That is
  the explicit escape hatch, not the normal path.

Bucket policy: a power-of-two ladder ``1, 2, 4, ... max_batch``
(``MXNET_SERVE_MAX_BATCH``, default 32), or an explicit
``MXNET_SERVE_BUCKETS=1,4,16`` list.  Padding waste is bounded by 2x on
the ladder; latency cost of the waste is what ``serving_padded_rows``
and the occupancy histogram make visible.

Batch-dependent *non-input* args (the zero-bound ``*_label`` loss heads
a checkpoint carries) are re-inferred per bucket and zero-filled once at
compile time; parameters are captured as live device buffers — swap the
whole program (``ModelSlot.reload``) to pick up new weights.
"""
from __future__ import annotations

import logging
import os
import threading

import numpy as np

from .. import random as _random
from .. import telemetry as _telemetry
from ..base import MXNetError
from ..lint import lockwitness as _lockwitness

__all__ = ["PredictProgram", "bucket_sizes", "refresh_from_env",
           "DEFAULT_MAX_BATCH", "tracecheck_programs"]

_LOG = logging.getLogger("mxnet_tpu.serving")

DEFAULT_MAX_BATCH = 32


def _env_max_batch():
    try:
        return max(1, int(os.environ.get("MXNET_SERVE_MAX_BATCH",
                                         DEFAULT_MAX_BATCH)))
    except ValueError:
        return DEFAULT_MAX_BATCH


def _env_buckets():
    raw = os.environ.get("MXNET_SERVE_BUCKETS", "").strip()
    if not raw:
        return None
    try:
        sizes = tuple(sorted({int(tok) for tok in raw.split(",") if tok}))
    except ValueError:
        return None
    return sizes if sizes and all(s > 0 for s in sizes) else None


# cached at import (JG006 cached-value pattern); serving.refresh_from_env()
# re-reads for tests / long-lived operators
_MAX_BATCH = _env_max_batch()
_BUCKETS = _env_buckets()


def refresh_from_env():
    global _MAX_BATCH, _BUCKETS
    _MAX_BATCH = _env_max_batch()
    _BUCKETS = _env_buckets()


def bucket_sizes(max_batch=None, buckets=None):
    """The bucket ladder: explicit *buckets* win, else powers of two up
    to (and always including) *max_batch*."""
    if buckets is None:
        buckets = _BUCKETS
    if buckets is not None:
        return tuple(sorted({int(b) for b in buckets}))
    if max_batch is None:
        max_batch = _MAX_BATCH
    sizes, b = [], 1
    while b < max_batch:
        sizes.append(b)
        b *= 2
    sizes.append(max_batch)
    return tuple(sorted(set(sizes)))


def _pad_rows(arr, b):
    """Zero-pad axis 0 of *arr* up to *b* rows (no-op when full)."""
    n = arr.shape[0]
    if n == b:
        return arr
    pad = np.zeros((b - n,) + arr.shape[1:], arr.dtype)
    return np.concatenate([arr, pad], axis=0)


class PredictProgram:
    """The bucket table of AOT-compiled eval executables for one model.

    Build (and :meth:`warmup`) once per checkpoint load; ``run`` is then
    pad → executable → slice, with zero tracing.  Thread-safe for
    concurrent ``run`` calls (executables are immutable; XLA execution
    is reentrant) — write-serialization per model is the batcher's job.
    """

    def __init__(self, predictor, buckets=None, max_batch=None,
                 name="model", warmup=True):
        ex = predictor._exe
        self.name = name
        self._ex = ex
        self._symbol = predictor._symbol
        self._input_shapes = dict(predictor._input_shapes)
        self._input_names = list(predictor._input_names)
        self._arg_pos = {n: i for i, n in enumerate(ex.arg_names)}
        self._dev = ex._ctx.jax_device
        # one fixed key for the whole program lifetime: eval-mode graphs
        # are deterministic (dropout is identity), and a per-call key
        # would make identical requests non-reproducible
        import jax
        self._key = jax.device_put(_random.next_key(), self._dev)
        self._aux_vals = [ex.aux_dict[n]._data for n in ex.aux_names]
        self.buckets = bucket_sizes(max_batch=max_batch, buckets=buckets)
        self.max_batch = self.buckets[-1]
        self._variants = {}          # b -> (executable, fixed_args, cost)
        self._lock = _lockwitness.make_lock("PredictProgram._lock")
        if warmup:
            self.warmup()

    # -- AOT build ---------------------------------------------------------

    def _arg_shapes_for(self, b):
        """Inferred shape of every executor arg at input batch *b*."""
        shapes = {n: (b,) + self._input_shapes[n][1:]
                  for n in self._input_names}
        arg_shapes, _, _ = self._symbol.infer_shape(**shapes)
        return dict(zip(self._ex.arg_names, arg_shapes))

    def _specs_for(self, b):
        """ShapeDtypeStruct specimens of the eval program at bucket *b*
        — what the AOT lower (and the graftcheck provider) traces.  They
        carry the predictor's device, so the executable is compiled for
        the context the model was loaded on, whatever jax's default
        device is."""
        import jax
        here = jax.sharding.SingleDeviceSharding(self._dev)
        shapes = self._arg_shapes_for(b)
        arg_specs = [jax.ShapeDtypeStruct(tuple(shapes[n]),
                                          self._ex.arg_dict[n].dtype,
                                          sharding=here)
                     for n in self._ex.arg_names]
        aux_specs = [jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=here)
                     for v in self._aux_vals]
        key_spec = jax.ShapeDtypeStruct(self._key.shape, self._key.dtype,
                                        sharding=here)
        return arg_specs, aux_specs, key_spec

    def _build_variant(self, b):
        """Lower + compile the bucket-*b* executable and its fixed
        (non-input) argument values."""
        import jax
        import jax.numpy as jnp
        ex = self._ex
        shapes = self._arg_shapes_for(b)
        arg_specs, aux_specs, key_spec = self._specs_for(b)
        fixed = []
        for n in ex.arg_names:
            cur = ex.arg_dict[n]
            if n in self._input_shapes:
                fixed.append(None)                 # filled per call
            elif tuple(shapes[n]) == tuple(cur.shape):
                fixed.append(cur._data)            # parameter buffer
            else:
                # batch-dependent non-input: a zero-bound loss label —
                # rebuilt at the bucket's batch size, once
                fixed.append(jax.device_put(
                    jnp.zeros(tuple(shapes[n]), cur.dtype), self._dev))
        compiled = ex._eval_jit.lower(arg_specs, aux_specs,
                                      key_spec).compile()
        from ..telemetry import costs as _costs
        return compiled, fixed, _costs.analyze_compiled(compiled)

    def warmup(self):
        """Compile every bucket variant AOT (idempotent).  This is the
        load-time cost that buys a retrace-free request path."""
        import time
        for b in self.buckets:
            with self._lock:
                if b in self._variants:
                    continue
            t0 = time.perf_counter()
            variant = self._build_variant(b)
            with self._lock:
                self._variants[b] = variant
            _telemetry.bump("serving_warmup_compiles")
            _telemetry.flight.record(
                "serving_warmup", self.name, bucket=b,
                wall_ms=round((time.perf_counter() - t0) * 1e3, 1))
        return self

    # -- request path ------------------------------------------------------

    def bucket_for(self, n):
        """Smallest bucket >= n, or None (straight-through territory)."""
        for b in self.buckets:
            if b >= n:
                return b
        return None

    def check_rows(self, inputs):
        """Row count of a request's input dict, fully shape-validated —
        run BEFORE the request occupies queue capacity, so one malformed
        request fails at submit instead of poisoning every innocent
        request coalesced into its batch."""
        rows = None
        for name in self._input_names:
            if name not in inputs:
                raise MXNetError("missing input %r (need %s)"
                                 % (name, self._input_names))
            shape = getattr(inputs[name], "shape", None)
            if shape is None or len(shape) == 0:
                raise MXNetError("input %r must be a batched array" % name)
            want = self._input_shapes[name][1:]
            if len(shape) != len(want) + 1 or tuple(shape[1:]) != want:
                raise MXNetError(
                    "input %r has shape %s; expected (batch,)+%s"
                    % (name, tuple(shape), want))
            if rows is None:
                rows = int(shape[0])
            elif int(shape[0]) != rows:
                raise MXNetError(
                    "ragged batch: %r has %d rows, expected %d"
                    % (name, shape[0], rows))
        unknown = set(inputs) - set(self._input_names)
        if unknown:
            raise MXNetError("unknown inputs %s (have %s)"
                             % (sorted(unknown), self._input_names))
        if rows is None or rows <= 0:
            raise MXNetError("empty batch")
        return rows

    def _gather_inputs(self, inputs, n):
        """Canonicalize the per-input host arrays and re-validate via
        :meth:`check_rows` (one validator, two call sites: submit-time
        rejection and dispatch-time defense)."""
        arrs = {}
        for key, val in inputs.items():
            if key in self._input_shapes:
                arrs[key] = np.ascontiguousarray(
                    np.asarray(val, self._ex.arg_dict[key].dtype))
            else:
                arrs[key] = val          # unknown key: check_rows names it
        rows = self.check_rows(arrs)
        if rows != n:
            raise MXNetError("batch has %d rows, expected %d" % (rows, n))
        return {name: arrs[name] for name in self._input_names}

    def run(self, inputs, n, timings=None):
        """Pad a batch of *n* rows to its bucket and execute the AOT
        executable.  Returns ``(outputs, bucket, cost)`` with outputs a
        list of per-output numpy arrays sliced back to *n* rows.  No
        tracing happens here, ever.

        *timings* (optional dict) is filled with the request-span
        decomposition: ``pad_us`` (host pad + device_put),
        ``execute_us`` (the executable call — dispatch wall normally;
        true device time when the MXNET_DEVICE_TIME sampler blocks this
        batch, flagged ``device_blocked``), ``slice_us`` (result
        host-transfer + per-request slicing)."""
        import jax
        b = self.bucket_for(n)
        if b is None:
            raise MXNetError(
                "batch of %d exceeds max bucket %d; use run_straight"
                % (n, self.max_batch))
        with self._lock:
            variant = self._variants.get(b)
        if variant is None:                     # lazy warmup (load raced)
            variant = self._build_variant(b)
            with self._lock:
                self._variants.setdefault(b, variant)
            _telemetry.bump("serving_warmup_compiles")
        compiled, fixed, cost = variant
        t0 = _telemetry.now_us()
        vals = self._gather_inputs(inputs, n)
        arg_vals = list(fixed)
        for name in self._input_names:
            arg_vals[self._arg_pos[name]] = jax.device_put(
                _pad_rows(vals[name], b), self._dev)
        t1 = _telemetry.now_us()
        outs, _new_aux = compiled(arg_vals, self._aux_vals, self._key)
        blocked = _telemetry.device.take_serving_sample()
        if blocked:
            # sampled batch: wait for the device so execute_us is true
            # execution time (and book it in the device-time table, the
            # serving twin of the watched-jit sampler)
            jax.block_until_ready(outs)
        t2 = _telemetry.now_us()
        sliced = [np.asarray(o)[:n] for o in outs]
        t3 = _telemetry.now_us()
        if blocked:
            _telemetry.device.record_program(
                "serving:%s:b%d" % (self.name, b), t2 - t1,
                collective=False)
        if timings is not None:
            timings["pad_us"] = t1 - t0
            timings["execute_us"] = t2 - t1
            timings["slice_us"] = t3 - t2
            timings["device_blocked"] = blocked
        return sliced, b, cost

    def run_straight(self, inputs, n):
        """Oversize escape hatch: run *n* rows unpadded through the
        watched jit.  May trace+compile a fresh variant — visible to the
        retrace watchdog as an ``executor_eval`` compile event."""
        import jax
        import jax.numpy as jnp
        ex = self._ex
        shapes = self._arg_shapes_for(n)
        vals = self._gather_inputs(inputs, n)
        arg_vals = []
        for name in ex.arg_names:
            cur = ex.arg_dict[name]
            if name in self._input_shapes:
                arg_vals.append(jax.device_put(vals[name], self._dev))
            elif tuple(shapes[name]) == tuple(cur.shape):
                arg_vals.append(cur._data)
            else:
                arg_vals.append(jax.device_put(
                    jnp.zeros(tuple(shapes[name]), cur.dtype), self._dev))
        _telemetry.bump("serving_straight_through")
        outs, _new_aux = ex._eval_jit(arg_vals, self._aux_vals, self._key)
        return [np.asarray(o) for o in outs], n, None

    @property
    def output_names(self):
        return list(self._ex.output_names)

    def costs(self):
        """{bucket: {"flops", "bytes_accessed"}} for the compiled table."""
        with self._lock:
            return {b: ({"flops": c[0], "bytes_accessed": c[1]}
                        if c else None)
                    for b, (_e, _f, c) in sorted(self._variants.items())}


def tracecheck_programs():
    """graftcheck provider: the serving-shaped eval program — the
    specimen predictor's forward lowered at a bucket batch size, exactly
    what every warmed serving variant is.  Covers the serving tier with
    the JX rules automatically (params stay arguments: JX101 proves no
    weight matrix is baked into the deployable)."""
    from ..predict import _tracecheck_predictor
    pred = _tracecheck_predictor()
    program = PredictProgram(pred, buckets=(4,), name="tracecheck",
                             warmup=False)
    arg_specs, aux_specs, key_spec = program._specs_for(4)
    return [("serving_predict", program._ex._eval_jit,
             (arg_specs, aux_specs, key_spec), {})]
