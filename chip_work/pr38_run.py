#!/usr/bin/env python3
"""PR 38's chip work: the sequence convolution alone, a cell's run with the
kernel counter read after it, and runs of a cell from several checkouts in
turn (parent, change, change, parent).

    python3 chip_work/pr38_run.py bench [rows x lanes ...]
        one layer of both cells' convolutions, forward and forward +
        backward, ms a call: (a) the jnp taps, (b) XLA's depthwise
        convolution, (c) the Pallas kernels by tile; each against the
        float32 truth and against the bytes' floor.  Writes
        chiprun_out/pr38/bench.json; off a TPU it rehearses at toy shapes
        in interpret mode and writes bench_rehearsal.json, no device's
        numbers.
    python3 chip_work/pr38_run.py cell --workload C --seed N --seconds S --trace T
        chipbench/run.py of the checkout in the working directory, then
        ``causal_conv_kernel_traced`` and its two neighbours on stderr; a
        traced run's ``kernel_trace`` also gets the scope ``short_conv``,
        by a patch of the harness's tracer made here, in this process: a
        reading of this script's, not the benchmark's.
    python3 chip_work/pr38_run.py runs CELL DIR:TAG:SEED:TRACE ...
        the ``cell`` mode once a spec, each in a process of its own from
        the checkout DIR, output under chiprun_out/pr38/TAG.{out,err}, a
        few lines a run on stdout (``show`` prints them again from a file).
"""
import functools
import json
import os
import runpy
import subprocess
import sys
import time

mode = sys.argv.pop(1)
root = os.path.abspath(".")
sys.path.insert(0, root)


def show(path):
    """One run's output file in a few lines: the result's numbers, the
    check, the counters, and (traced) ms a step by trace pattern."""
    lines, last = {}, ""
    for ln in open(path, errors="replace"):
        if ln.startswith("chipbench: "):
            key, _, rest = ln[11:].partition(" ")
            try:
                lines[key] = json.loads(rest)
            except ValueError:
                pass
        if ln.strip():
            last = ln
    try:
        res = json.loads(last)
    except ValueError:
        print("  no result line:", last[:300])
        return
    dev = res["device"]
    print("  correct", res["correct"], "failed", res["failed"], "peak",
          dev["memory_peak_bytes"], "busy", dev.get("busy_s"), "of",
          dev.get("window_s"))
    print("  ", {k: round(v["value"], 4) for k, v in res["metrics"].items()})
    check = lines.get("check", {})
    print("   check", {k: v for k, v in check.items()
                      if k != "closest_to_limit"})
    win = lines.get("window", {})
    print("   batches", win.get("batches"), "traced_in_first_step",
          win.get("traced_in_first_step"),
          {k: v for k, v in win.get("counters", {}).items() if "conv" in k})
    steps = lines.get("trace", {}).get("span_counts", {}).get("fit_step")
    kt = win.get("kernel_trace")
    if kt and steps:
        print("   ms a step (%d steps):" % steps, {
            k: round(v["seconds"] / steps * 1e3, 2) for k, v in kt.items()})
        for k in ("causal_conv", "short_conv"):
            if k in kt:
                print("   ", k, [(n, round(s / steps * 1e3, 3))
                                 for n, s in kt[k]["longest_ops"][:8]])
    print("   setup phases", [(p, round(s, 1)) for p, s in
                              lines.get("setup", {}).get("phases", [])])


if mode == "show":
    for path in sys.argv[1:]:
        print("==", path)
        show(path)
    raise SystemExit(0)
if mode == "runs":
    cell = sys.argv[1]
    out_dir = os.path.join(root, "chiprun_out", "pr38")
    os.makedirs(out_dir, exist_ok=True)
    # no compile cache is named here: a checkout keeps its own
    # (<checkout>/.jax_cache) unless the machine came with one set
    for spec in sys.argv[2:]:
        where, tag, seed, trace = spec.split(":")
        t0 = time.time()
        base = os.path.join(out_dir, tag)
        with open(base + ".out", "w") as out, open(base + ".err", "w") as err:
            rc = subprocess.call(
                [sys.executable, os.path.abspath(__file__), "cell",
                 "--workload", cell, "--seed", seed, "--seconds", "20",
                 "--trace", trace], cwd=where, stdout=out, stderr=err,
                timeout=1800)
        print("== %s %s %s seed %s trace %s rc=%d %.0f s" % (
            where, tag, cell, seed, trace, rc, time.time() - t0), flush=True)
        for ln in open(base + ".err", errors="replace"):
            if ln.startswith("pr38: counters"):
                print("  ", ln.strip())
        show(base + ".out")
        sys.stdout.flush()
    raise SystemExit(0)
if mode == "cell":
    sys.argv[0] = os.path.join(root, "chipbench", "run.py")
    if sys.argv[-2:] == ["--trace", "1"]:
        # a traced run also sums the scope ``short_conv``, which no
        # configuration's trace_patterns name (lfm2_8b_a1b.fit's five gated
        # convolutions); the early import moves ~12 s out of setup_s
        from chipbench.runners import module_fit_lm
        plain = module_fit_lm.KernelTracer.__init__

        def with_short_conv(self, base, patterns):
            plain(self, base, dict(patterns, short_conv="short_conv"))
        module_fit_lm.KernelTracer.__init__ = with_short_conv
    try:
        runpy.run_path(sys.argv[0], run_name="__main__")
    finally:
        from mxnet_tpu import telemetry
        print("pr38: counters %s" % json.dumps({
            name: telemetry.counter(name) for name in (
                "causal_conv_kernel_traced", "causal_conv_traced",
                "short_conv_traced")}), file=sys.stderr)
    raise SystemExit(0)
if mode != "bench":
    raise SystemExit("unknown mode %r" % mode)

import jax                                                  # noqa: E402
import jax.numpy as jnp                                     # noqa: E402
import numpy as np                                          # noqa: E402

import mxnet_tpu                                            # noqa: E402,F401
from mxnet_tpu.ops import lm                                # noqa: E402
from mxnet_tpu.ops import pallas_kernels as pk              # noqa: E402

BW = 819e9
CASES = {  # name: (data shape, channels, taps, silu, gated, has bias)
    "granite": ((1, 16384, 4352), 4352, 4, True, False, True),
    "lfm2": ((2, 8192, 6144), 2048, 3, False, True, False),
}


def taps_form(data, w, b, silu, gated, begin=0):
    lm._kernel_backend = lambda: False
    return lm.sequence_conv(data, w, b, silu, gated, begin)


def xla_conv(data, w, b, silu, gated, operands=None):
    """XLA's depthwise convolution; JAX transposes it only with operands
    of the result's dtype, so forward + backward is timed on float32
    casts of them (``xla_conv_f32``)."""
    f32 = jnp.float32
    x = data
    if gated:
        bg, cg, u = jnp.split(data, 3, axis=-1)
        x = bg * u
    if operands is not None:
        x, w = x.astype(operands), w.astype(operands)
    c = jax.lax.conv_general_dilated(
        x, w.T[:, None, :], (1,), [(w.shape[1] - 1, 0)],
        dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=w.shape[0], preferred_element_type=f32)
    if b is not None:
        c = c + b.astype(f32)
    if silu:
        c = c * jax.nn.sigmoid(c)
    c = c.astype(data.dtype)
    return cg * c if gated else c


def kernels(data, w, b, silu, gated, begin=0):
    lm._kernel_backend = lambda: True
    return lm.sequence_conv(data, w, b, silu, gated, begin)


def timed(fn, args, n=20):
    out = fn(*args)
    jax.block_until_ready(out)
    t = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) / n * 1e3, out


def rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / (np.linalg.norm(b) + 1e-30))


def programs(form, silu, gated, biased):
    def fwd(d, w, b):
        return form(d, w, b if biased else None, silu, gated)

    def both(d, w, b, dy):
        out, vjp = jax.vjp(fwd, d, w, b)
        return (out,) + vjp(dy)
    return jax.jit(fwd), jax.jit(both)


on_chip = jax.default_backend() == "tpu"
if not on_chip:                       # rehearsal: toy shapes, interpreted
    CASES = {"granite": ((1, 1024, 256), 256, 4, True, False, True),
             "lfm2": ((2, 512, 384), 128, 3, False, True, False)}
    for impl in ("_causal_conv_fwd_impl", "_causal_conv_bwd_impl"):
        def interpreted(*a, _f=getattr(pk, impl)):
            return _f(*a[:-1], True)
        setattr(pk, impl, interpreted)

tiles = [tuple(map(int, a.split("x"))) for a in sys.argv[1:]] or \
    [(512, 512)]
rows_out = []
for name, (shape, C, K, silu, gated, biased) in CASES.items():
    rng = np.random.RandomState(38)
    bf = jnp.bfloat16
    data = jnp.asarray(rng.randn(*shape) * 0.7, bf)
    w = jnp.asarray(rng.randn(C, K) * 0.5, bf)
    b = jnp.asarray(rng.randn(C) * 0.3, bf)
    dy = jnp.asarray(rng.randn(*shape[:2], C), bf)
    nbytes = 2 * (data.size + dy.size)
    floor_f = nbytes / BW * 1e3
    # forward + backward: the forward's read and write, then data and dy
    # read and d data written
    floor_fb = (nbytes + 2 * (2 * data.size + dy.size)) / BW * 1e3
    f32s = tuple(v.astype(jnp.float32) for v in (data, w, b, dy))
    tf, tb = programs(taps_form, silu, gated, biased)
    truth = tb(*f32s)
    jax.block_until_ready(truth)
    forms = [("taps", taps_form, None), ("xla_conv", xla_conv, None),
             ("xla_conv_f32", functools.partial(
                 xla_conv, operands=jnp.float32), None)] + \
        [("kernels", kernels, t) for t in tiles]
    for label, form, tile in forms:
        if tile:
            pk._CONV_ROWS, pk._CONV_LANES = tile
        row = dict(case=name, form=label, tile=tile,
                   floor_fwd_ms=floor_f, floor_fwd_bwd_ms=floor_fb)
        try:
            f, fb = programs(form, silu, gated, biased)
            row["fwd_ms"], _ = timed(f, (data, w, b))
            row["fwd_bwd_ms"], got = timed(fb, (data, w, b, dy))
            row["err_vs_f32"] = [rel(g, t) for g, t in zip(got, truth)]
        except Exception as e:                      # noqa: BLE001
            row["error"] = repr(e)[:600]
        rows_out.append(row)
        print(json.dumps(row), flush=True)
    pk._CONV_ROWS, pk._CONV_LANES = 512, 512
# Granite's op as its graph hands it over: in_proj's whole output with the
# channels at column 4,096 (a whole channel tile: read in place) and at
# column 64 (inside a tile: the kernels are handed a slice)
shape, C, K, silu, gated, biased = CASES["granite"]
wide = shape[:2] + (C + (4160 if on_chip else 192),)
rng = np.random.RandomState(39)
data = jnp.asarray(rng.randn(*wide) * 0.7, jnp.bfloat16)
w = jnp.asarray(rng.randn(C, K) * 0.5, jnp.bfloat16)
b = jnp.asarray(rng.randn(C) * 0.3, jnp.bfloat16)
dy = jnp.asarray(rng.randn(*shape), jnp.bfloat16)
for begin in (wide[2] - C - 64, 64):
    before = mxnet_tpu.telemetry.counter("causal_conv_kernel_traced")
    row = dict(case="granite in %d columns" % wide[2], begin=begin)
    for label, form in (("taps", taps_form), ("kernels", kernels)):
        _, fb = programs(functools.partial(form, begin=begin), silu, gated,
                         biased)
        if label == "taps":
            truth = fb(*(v.astype(jnp.float32) for v in (data, w, b, dy)))
        row[label + "_fwd_bwd_ms"], got = timed(fb, (data, w, b, dy))
        row[label + "_err_vs_f32"] = [rel(g, t) for g, t in zip(got, truth)]
    row["kernel_traced"] = mxnet_tpu.telemetry.counter(
        "causal_conv_kernel_traced") - before
    rows_out.append(row)
    print(json.dumps(row), flush=True)
os.makedirs("chiprun_out/pr38", exist_ok=True)
with open("chiprun_out/pr38/bench%s.json" % ("" if on_chip else "_rehearsal"),
          "w") as f:
    json.dump(dict(device=jax.devices()[0].device_kind, rows=rows_out), f,
              indent=1)
