#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python chip_smoke.py               # on a machine with a TPU: the contract
    python chip_smoke.py --rehearse-cpu  # typo check at toy size, see below

ONE process drives the main path once through the entry points a user
calls, at the full width of the model the repo names as its north star
(model-zoo ``resnet50_v1``, 1000 classes, 3x224x224, batch 32, bf16,
random weights from a seed):

``train``    symbol from the zoo block -> ``mx.mod.Module(context=mx.tpu())``
             -> bind / init_params / init_optimizer(sgd, momentum, wd) ->
             fused ``_fit_step``s (``CachedTrainStep``: one donated program).
``serve``    ``save_checkpoint`` -> ``serving.load(..., ctx=mx.tpu())`` with a
             short bucket ladder -> ``telemetry.server.start_server(port=0)``
             -> ``POST /v1/models/resnet50/predict`` with 1, 3 and 8 rows,
             each checked against ``Predictor.forward``.
``kernels``  the Pallas tier compiled by Mosaic: ``flash_attention`` over the
             shape set against ``local_attention`` at highest matmul
             precision, the ``mx.pallas.register`` docstring kernel, and a
             transformer ``make_train_step`` at S=1024 through the kernel.
``four_chip`` (>= 4 devices only) the same ResNet-50 Module over
             ``[mx.tpu(i) for i in range(4)]`` (fused SPMD group) and the
             transformer on a data=2 x model=2 mesh (flash under shard_map).

Every phase runs un-caught: a failing phase is a traceback and a non-zero
exit.  The per-phase seconds / milliseconds / bytes printed on the way are
smoke observations, not benchmark metrics.  Without a TPU the script exits
2 before doing any work — there is no CPU fallback, no retry and no probe
child (a chip belongs to one process; this process is it).  The last line
of stdout on success is one JSON object::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

(the line before it, ``chip_smoke: observations {...}``, carries the
per-phase numbers).

``--rehearse-cpu`` runs the same code at toy size on the host (Pallas in
interpret mode) so chip time is not spent on typos.  It is loudly labelled
and can never print a passing chip result: its last line says
``"ok": false, "rehearsal": "cpu"``.
"""
import argparse
import functools
import gc
import json
import os
import sys
import tempfile
import time
import urllib.request

import numpy as np

# full width on the chip; toy width for the CPU rehearsal
CHIP = dict(model="resnet50_v1", classes=1000, image=224, batch=32, lr=0.01,
            steps=5, buckets=(1, 4, 8), rows=(1, 3, 8),
            flash_seqs=(128, 1024, 4096, 1000), flash_dims=(64, 128),
            lm=dict(vocab=8192, d_model=512, n_heads=8, d_ff=2048,
                    n_layers=2, max_len=1024), lm_batch=4, lm_steps=3,
            quad_batch=128, quad_steps=3)
REHEARSAL = dict(model="resnet18_v1", classes=10, image=32, batch=4,
                 lr=0.002, steps=3, buckets=(1, 4, 8), rows=(1, 3, 8),
                 flash_seqs=(128, 200), flash_dims=(64,),
                 lm=dict(vocab=64, d_model=128, n_heads=2, d_ff=128,
                         n_layers=1, max_len=128), lm_batch=4, lm_steps=3,
                 quad_batch=8, quad_steps=2)

# flash kernel vs local_attention(precision=highest), max |diff| on
# outputs of magnitude <= 1 (a softmax-weighted mean of N(0,1) values):
# bf16 — the output and the P matrix are rounded to 8 mantissa bits
#   (2^-9 relative each) where the reference keeps f32 throughout: 2e-2.
# f32 — the kernel contracts at fp32 precision like the reference; what
#   is left is summation order over up to 4096 keys: 1e-4.
FLASH_ATOL = {"bfloat16": 2e-2, "float32": 1e-4}
# the same seed on 1 chip and on 4 differs in reduction order only
# (per-shard partial sums, all-reduce trees) — 2^-9 relative per rounded
# bf16 activation — but training amplifies it step over step, so the
# bound is loose enough for step 3 and the learning rate is kept small:
QUAD_LOSS_RTOL = 5e-2


def say(msg):
    print(msg, flush=True)


def mem_stat(dev, key):
    stats = dev.memory_stats()          # None on the CPU backend
    return stats[key] if stats else None


def peak_bytes(dev):
    return mem_stat(dev, "peak_bytes_in_use")


def cache_entries(path):
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def on_devices(arr, want):
    """True when jax array ``arr`` lives exactly on the device set ``want``."""
    return set(arr.devices()) == set(want)


def resnet_symbol(cfg):
    from mxnet_tpu import symbol as S
    from mxnet_tpu.gluon.model_zoo import vision
    net = vision.get_model(cfg["model"], classes=cfg["classes"])
    net.cast("bfloat16")
    # f32 in, f32 out: the casts are part of the checkpointed graph, so
    # the trainer, the Predictor and the server feed the same f32 rows
    out = net(S.Cast(S.Variable("data"), dtype="bfloat16"))
    out = S.Cast(out, dtype="float32")
    return S.SoftmaxOutput(out, S.Variable("softmax_label"), name="softmax")


def make_module(mx, cfg, ctx, batch):
    from mxnet_tpu.io import DataDesc
    mx.random.seed(0)
    mod = mx.mod.Module(resnet_symbol(cfg), context=ctx)
    shape = (batch, 3, cfg["image"], cfg["image"])
    mod.bind(data_shapes=[DataDesc("data", shape, dtype=np.float32)],
             label_shapes=[DataDesc("softmax_label", (batch,),
                                    dtype=np.float32)])
    mod.init_params(initializer=mx.initializer.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params=(("learning_rate", cfg["lr"]),
                                         ("momentum", 0.9), ("wd", 1e-4)))
    return mod


def synthetic_batch(mx, cfg, batch):
    from mxnet_tpu.io import DataBatch
    rng = np.random.RandomState(1)
    x = rng.rand(batch, 3, cfg["image"], cfg["image"]).astype(np.float32)
    y = rng.randint(0, cfg["classes"], (batch,)).astype(np.float32)
    return DataBatch([mx.nd.array(x)], [mx.nd.array(y)]), y.astype(int)


def fit_steps(mod, db, labels, steps):
    """Run fused steps; returns (losses, first-step s, later-step ms list,
    compiles during step 1, compiles after it)."""
    import jax
    from mxnet_tpu import telemetry
    losses, walls, compiles = [], [], []
    for _ in range(steps):
        c0 = telemetry.counter("jit_compiles")
        t0 = time.perf_counter()
        mod._fit_step(db)
        probs = mod.get_outputs()[0]
        jax.block_until_ready(probs._data)
        walls.append(time.perf_counter() - t0)
        compiles.append(telemetry.counter("jit_compiles") - c0)
        p = probs.asnumpy().astype(np.float64)
        losses.append(float(-np.log(
            np.maximum(p[np.arange(len(labels)), labels], 1e-30)).mean()))
    if mod._cached_step is None:
        raise AssertionError("Module fell off the CachedTrainStep fast path")
    return losses, walls[0], [w * 1e3 for w in walls[1:]], \
        compiles[0], sum(compiles[1:])


def module_buffers(mod):
    """Every jax buffer the bound trainer owns: params, aux, optimizer
    state, outputs."""
    import jax
    from mxnet_tpu.optimizer import _state_raw
    ex = mod._exec_group.execs[0]
    bufs = [a._data for a in ex.arg_dict.values()]
    bufs += [a._data for a in ex.aux_dict.values()]
    bufs += [o._data for o in mod.get_outputs()]
    states = [_state_raw(s) for s in mod._updater.states.values()]
    if not states:
        raise AssertionError("optimizer created no state")
    bufs += [leaf for leaf in jax.tree_util.tree_leaves(states)
             if hasattr(leaf, "devices")]
    return bufs


def phase_train(mx, cfg, dev, ctx):
    mod = make_module(mx, cfg, ctx, cfg["batch"])
    db, labels = synthetic_batch(mx, cfg, cfg["batch"])
    losses, first_s, step_ms, c_first, c_later = fit_steps(
        mod, db, labels, cfg["steps"])
    say("  losses: %s" % " ".join("%.4f" % v for v in losses))
    if not all(np.isfinite(losses)):
        raise AssertionError("loss is not finite: %r" % (losses,))
    if len(set(round(v, 6) for v in losses)) < 2:
        raise AssertionError("loss does not change: %r" % (losses,))
    if (c_first, c_later) != (1, 0):
        raise AssertionError(
            "expected exactly one compile, in step 1; got %d in step 1 and "
            "%d after" % (c_first, c_later))
    bufs = module_buffers(mod)
    stray = [b for b in bufs if not on_devices(b, [dev])]
    if stray:
        raise AssertionError("%d trainer buffer(s) not on %s, e.g. %s"
                             % (len(stray), dev, stray[0].devices()))
    say("  all %d param/aux/optimizer/output buffers on %s"
        % (len(bufs), dev))
    obs = {"compile_s": round(first_s, 2),
           "step_ms": round(float(np.median(step_ms)), 2),
           "peak_bytes": peak_bytes(dev), "final_loss": losses[-1]}
    return mod, obs


def http_predict(port, rows):
    body = json.dumps({"inputs": {"data": rows.tolist()}}).encode()
    req = urllib.request.Request(
        "http://127.0.0.1:%d/v1/models/resnet50/predict" % port, data=body,
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=300) as resp:
        return json.loads(resp.read())


def phase_serve(mx, cfg, dev, ctx, mod):
    import mxnet_tpu.serving as serving
    from mxnet_tpu import engine, telemetry
    image = (3, cfg["image"], cfg["image"])
    rng = np.random.RandomState(2)
    obs = {}
    with tempfile.TemporaryDirectory(prefix="chip-smoke-ckpt-") as ckpt:
        prefix = os.path.join(ckpt, "resnet50")
        mod.save_checkpoint(prefix, 1)
        t0 = time.perf_counter()
        slot = serving.load("resnet50", prefix=prefix, epoch=1,
                            input_shapes={"data": (1,) + image}, ctx=ctx,
                            buckets=cfg["buckets"])
        obs["compile_s"] = round(time.perf_counter() - t0, 2)
        say("  native host engine in use: %s" % engine.engine().native)
        if not engine.engine().native:
            raise AssertionError("serving runs on the python engine twin")
        server = telemetry.start_server(port=0)
        try:
            # the reference names NO context: it must land where the
            # default context says (the chip when one is attached)
            refs = {b: mx.Predictor.load(prefix, 1, {"data": (b,) + image})
                    for b in cfg["buckets"]}
            for b, ref in refs.items():
                ref.forward(data=np.zeros((b,) + image, np.float32))
            warm = http_predict(server.port,
                                rng.rand(1, *image).astype(np.float32))
            c0 = telemetry.counter("jit_compiles") + \
                telemetry.counter("serving_warmup_compiles")
            lat_ms = []
            for n in cfg["rows"]:
                x = rng.rand(n, *image).astype(np.float32)
                t0 = time.perf_counter()
                reply = http_predict(server.port, x)
                lat_ms.append((time.perf_counter() - t0) * 1e3)
                got = np.asarray(reply["outputs"]["softmax_output"],
                                 np.float32)
                b = slot.program.bucket_for(n)
                pad = np.zeros((b,) + image, np.float32)
                pad[:n] = x
                want = refs[b].forward(data=pad)[0].asnumpy()[:n]
                if got.shape != (n, cfg["classes"]) or \
                        not np.isfinite(got).all():
                    raise AssertionError("bad reply for %d rows: shape %s"
                                         % (n, got.shape))
                # same executable shape, same rows: bf16 leaves no room
                # for more than rounding noise between the two dispatches
                if not np.allclose(got, want, rtol=2e-2, atol=1e-5):
                    raise AssertionError(
                        "served != Predictor.forward for %d rows: max "
                        "|diff| %g" % (n, np.abs(got - want).max()))
                say("  %d row(s) -> bucket %d: %.1f ms over HTTP, "
                    "bitwise equal to Predictor.forward: %s"
                    % (n, b, lat_ms[-1], np.array_equal(got, want)))
            delta = telemetry.counter("jit_compiles") + \
                telemetry.counter("serving_warmup_compiles") - c0
            if delta:
                raise AssertionError("%d compile(s) after warm-up" % delta)
            placed = slot.program._aux_vals + [
                v for v in slot.program._variants[cfg["buckets"][0]][1]
                if v is not None]
            for ref in refs.values():
                placed += [a._data for a in ref._exe.arg_dict.values()]
            stray = [b for b in placed if not on_devices(b, [dev])]
            if stray or slot.program._dev != dev:
                raise AssertionError("serving buffers off %s: %s"
                                     % (dev, stray[:1]))
            say("  server and default-context Predictor both on %s; zero "
                "compiles after warm-up (warm-up reply batch=%d)"
                % (dev, warm["batch"]))
        finally:
            telemetry.stop_server()
            serving.unload("resnet50")
    obs["request_ms"] = round(float(np.median(lat_ms)), 2)
    obs["peak_bytes"] = peak_bytes(dev)
    return obs


def check_flash(cfg, interpret):
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.pallas_kernels import flash_attention
    from mxnet_tpu.parallel.ring_attention import local_attention
    worst, bad = {}, []
    first_compile_s = None
    for dtype in (jnp.bfloat16, jnp.float32):
        name = jnp.dtype(dtype).name
        for seq in cfg["flash_seqs"]:
            for dim in cfg["flash_dims"]:
                rng = np.random.RandomState(seq + dim)
                q, k, v = (jnp.asarray(rng.randn(1, 2, seq, dim), dtype)
                           for _ in range(3))
                for causal in (True, False):
                    fn = jax.jit(functools.partial(
                        flash_attention, causal=causal, interpret=interpret))
                    if not interpret:
                        text = fn.lower(q, k, v).as_text()
                        if "tpu_custom_call" not in text:
                            raise AssertionError(
                                "flash_attention did not lower to a Mosaic "
                                "kernel (interpret mode or jnp reference)")
                    t0 = time.perf_counter()
                    got = np.asarray(jax.block_until_ready(fn(q, k, v)),
                                     np.float32)
                    if first_compile_s is None:
                        first_compile_s = time.perf_counter() - t0
                    with jax.default_matmul_precision("highest"):
                        want = np.asarray(local_attention(
                            q.astype(jnp.float32), k.astype(jnp.float32),
                            v.astype(jnp.float32), causal=causal))
                    err = float(np.abs(got - want).max())
                    worst[name] = max(worst.get(name, 0.0), err)
                    if not np.isfinite(got).all() or err > FLASH_ATOL[name]:
                        bad.append("%s S=%d D=%d causal=%s: max |diff| %g > "
                                   "%g" % (name, seq, dim, causal, err,
                                           FLASH_ATOL[name]))
    if bad:
        raise AssertionError("flash_attention disagrees with "
                             "local_attention:\n  " + "\n  ".join(bad))
    say("  flash_attention (%s): %d shapes ok; worst |diff| %s"
        % ("INTERPRET" if interpret else "Mosaic, interpret=False",
           2 * 2 * len(cfg["flash_seqs"]) * len(cfg["flash_dims"]),
           " ".join("%s=%.2e" % kv for kv in sorted(worst.items()))))
    return first_compile_s, worst


def check_registered_kernel(mx, interpret):
    """The kernel from mx.pallas's docstring, through the op registry."""
    import jax
    from jax.experimental import pallas as pl

    def _scale_kernel(x_ref, o_ref, *, alpha):
        o_ref[...] = x_ref[...] * alpha

    @mx.pallas.register("chip_smoke_scale", interpret=interpret)
    def chip_smoke_scale(x, alpha=2.0, interpret=False):
        return pl.pallas_call(
            functools.partial(_scale_kernel, alpha=float(alpha)),
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            interpret=interpret)(x)

    try:
        x = np.arange(8 * 128, dtype=np.float32).reshape(8, 128)
        y = mx.nd.chip_smoke_scale(mx.nd.array(x), alpha=3.0)
        if not np.array_equal(y.asnumpy(), x * 3.0):
            raise AssertionError("registered Pallas kernel gave a wrong "
                                 "result")
    finally:
        mx.pallas.unregister("chip_smoke_scale")
    say("  mx.pallas.register kernel ok (interpret=%s)" % interpret)


def lm_train(cfg, mesh, want_flash):
    """``lm_steps`` transformer train steps; returns (losses, compile s,
    step ms, params)."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.models.transformer import (
        TransformerLMConfig, init_transformer_params, make_train_step,
        place_batch)
    lm = TransformerLMConfig(dtype=jnp.bfloat16, **cfg["lm"])
    params = init_transformer_params(jax.random.PRNGKey(0), lm, mesh)
    rng = np.random.RandomState(3)
    shape = (cfg["lm_batch"], lm.max_len)
    tokens = jnp.asarray(rng.randint(0, lm.vocab, shape), jnp.int32)
    labels = jnp.asarray(rng.randint(0, lm.vocab, shape), jnp.int32)
    if mesh is not None:
        tokens, labels = place_batch(tokens, labels, mesh)
    step = make_train_step(lm, mesh, lr=0.05)
    kernels = step.lower(params, tokens, labels).as_text().count(
        "tpu_custom_call")
    if want_flash and kernels < lm.n_layers:
        raise AssertionError(
            "transformer step holds %d Mosaic kernels, expected one per "
            "layer (%d): the flash path is not live" % (kernels, lm.n_layers))
    losses, walls = [], []
    for _ in range(cfg["lm_steps"]):
        t0 = time.perf_counter()
        params, loss = step(params, tokens, labels)
        losses.append(float(jax.block_until_ready(loss)))
        walls.append(time.perf_counter() - t0)
    if not all(np.isfinite(losses)) or losses[-1] >= losses[0]:
        raise AssertionError("transformer loss not finite/falling: %r"
                             % (losses,))
    say("  transformer S=%d hd=%d bf16 mesh=%s: %d Mosaic kernel(s) in the "
        "step, losses %s"
        % (lm.max_len, lm.d_model // lm.n_heads,
           dict(mesh.shape) if mesh is not None else None, kernels,
           " ".join("%.4f" % v for v in losses)))
    return losses, walls[0], float(np.median(walls[1:])) * 1e3, params


def phase_kernels(mx, cfg, dev, on_chip):
    interpret = not on_chip
    first_compile_s, worst = check_flash(cfg, interpret)
    check_registered_kernel(mx, interpret)
    losses, compile_s, step_ms, _ = lm_train(cfg, None, want_flash=on_chip)
    obs = {"flash_first_compile_s": round(first_compile_s, 2),
           "flash_worst_abs_diff": worst,
           "lm_compile_s": round(compile_s, 2),
           "lm_step_ms": round(step_ms, 2), "peak_bytes": peak_bytes(dev)}
    return losses, obs


def in_use(devs):
    gc.collect()
    return [mem_stat(d, "bytes_in_use") for d in devs]


def spread(devs, before):
    """Bytes each device gained since ``before`` (what this leg placed
    there): every device must hold a share, none more than 2x another."""
    used = in_use(devs)
    if None in used:
        say("  bytes_in_use per device: not reported by this backend")
        return None
    gained = [u - b for u, b in zip(used, before)]
    say("  bytes_in_use per device: %s (this leg: %s)" % (used, gained))
    if min(gained) <= 0 or max(gained) > 2 * min(gained):
        raise AssertionError("device memory shares differ by more than 2x: "
                             "%r" % (gained,))
    return gained


def phase_four_chip(mx, cfg, devs, lm_losses_1chip, on_chip):
    import jax
    from mxnet_tpu.parallel.mesh import make_mesh
    ctxs = [mx.tpu(i) if on_chip else mx.cpu(i) for i in range(4)]
    obs = {}
    # (a) ResNet-50: one chip and four, same seed, same global batch
    db, labels = synthetic_batch(mx, cfg, cfg["quad_batch"])
    single = make_module(mx, cfg, ctxs[0], cfg["quad_batch"])
    ref_losses = fit_steps(single, db, labels, cfg["quad_steps"])[0]
    del single
    before = in_use(devs)
    quad = make_module(mx, cfg, ctxs, cfg["quad_batch"])
    if type(quad._exec_group).__name__ != "FusedExecutorGroup":
        raise AssertionError("4-context Module did not bind the fused SPMD "
                             "group")
    losses, first_s, step_ms, _, c_later = fit_steps(
        quad, db, labels, cfg["quad_steps"])
    say("  resnet 4-chip losses %s | 1-chip %s"
        % (" ".join("%.4f" % v for v in losses),
           " ".join("%.4f" % v for v in ref_losses)))
    if c_later:
        raise AssertionError("%d recompile(s) after step 1" % c_later)
    if not np.allclose(losses, ref_losses, rtol=QUAD_LOSS_RTOL):
        raise AssertionError("4-chip losses %r != 1-chip %r"
                             % (losses, ref_losses))
    narrow = [b for b in module_buffers(quad) if not on_devices(b, devs)]
    if narrow:
        raise AssertionError("%d buffer(s) do not span the 4 devices, e.g. "
                             "%s" % (len(narrow), narrow[0].sharding))
    obs["resnet_bytes_gained"] = spread(devs, before)
    obs["resnet_compile_s"] = round(first_s, 2)
    obs["resnet_step_ms"] = round(float(np.median(step_ms)), 2)
    del quad, db
    # (b) transformer on data=2 x model=2, flash under shard_map
    before = in_use(devs)
    mesh = make_mesh({"data": 2, "model": 2}, devs)
    losses, compile_s, lm_ms, params = lm_train(cfg, mesh,
                                                want_flash=on_chip)
    if not np.allclose(losses, lm_losses_1chip, rtol=QUAD_LOSS_RTOL):
        raise AssertionError("mesh losses %r != 1-chip %r"
                             % (losses, lm_losses_1chip))
    narrow = [n for n, p in params.items()
              if len(p.sharding.device_set) != 4]
    if narrow:
        raise AssertionError("params not on 4 devices: %s" % narrow[:3])
    obs["lm_bytes_gained"] = spread(devs, before)
    obs["lm_compile_s"] = round(compile_s, 2)
    obs["lm_step_ms"] = round(lm_ms, 2)
    obs["peak_bytes"] = [peak_bytes(d) for d in devs]
    jax.block_until_ready(list(params.values()))
    return obs


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="toy-size host run of the same code; never prints "
                         "a passing chip result")
    args = ap.parse_args()

    import jax
    import jaxlib
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    from importlib import metadata
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = None
    say("chip_smoke: platform=%s device_kind=%r count=%d jax=%s jaxlib=%s "
        "libtpu=%s" % (device["platform"], device["kind"], device["count"],
                       jax.__version__, jaxlib.__version__, libtpu))
    on_chip = device["platform"] == "tpu"
    if args.rehearse_cpu:
        if on_chip:
            sys.exit("chip_smoke: --rehearse-cpu on a TPU host; run without "
                     "it")
        say("chip_smoke: *** CPU REHEARSAL at toy size — NOT a chip result, "
            "Pallas in interpret mode, timings meaningless ***")
        cfg = REHEARSAL
    elif not on_chip:
        print("chip_smoke: needs a TPU, jax found platform %r (%s); nothing "
              "was run" % (device["platform"], device["kind"]),
              file=sys.stderr)
        sys.exit(2)
    else:
        cfg = CHIP

    import mxnet_tpu as mx
    from mxnet_tpu import telemetry
    telemetry.set_enabled(True)        # arms the jit_compiles watchdog
    cache_dir = jax.config.jax_compilation_cache_dir
    cache_before = cache_entries(cache_dir)
    say("chip_smoke: compile cache %s (%s), %d entries before"
        % (cache_dir, "placed by JAX_COMPILATION_CACHE_DIR"
           if os.environ.get("JAX_COMPILATION_CACHE_DIR")
           else "repo default", cache_before))
    say("chip_smoke: default context %s" % mx.current_context())
    ctx = mx.tpu() if on_chip else mx.cpu()
    dev = ctx.jax_device
    if on_chip and mx.current_context() != mx.tpu(0):
        raise AssertionError("default context is %s on a TPU host"
                             % mx.current_context())

    phases = {}
    t_all = time.perf_counter()

    def run(name, fn, *a):
        say("== phase %s" % name)
        t0 = time.perf_counter()
        out = fn(*a)
        obs = out[-1] if isinstance(out, tuple) else out
        obs["wall_s"] = round(time.perf_counter() - t0, 1)
        phases[name] = obs
        say("== phase %s ok: %s" % (name, json.dumps(obs, sort_keys=True)))
        return out

    mod, _ = run("train", phase_train, mx, cfg, dev, ctx)
    run("serve", phase_serve, mx, cfg, dev, ctx, mod)
    del mod
    gc.collect()
    lm_losses, _ = run("kernels", phase_kernels, mx, cfg, dev, on_chip)
    if len(devs) >= 4:
        run("four_chip", phase_four_chip, mx, cfg, devs[:4], lm_losses,
            on_chip)
    else:
        say("== phase four_chip not run: %d device" % len(devs))
        phases["four_chip"] = "not run: %d device" % len(devs)

    cache_after = cache_entries(cache_dir)
    say("chip_smoke: compile cache %s, %d entries before, %d after"
        % (cache_dir, cache_before, cache_after))
    say("chip_smoke: observations %s" % json.dumps({
        "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                     "libtpu": libtpu},
        "compile_cache": {"dir": cache_dir, "before": cache_before,
                          "after": cache_after},
        "wall_s": round(time.perf_counter() - t_all, 1),
        "phases": phases}, sort_keys=True))
    result = {"ok": on_chip, "device": device}
    if not on_chip:
        result["rehearsal"] = "cpu"
        say("chip_smoke: *** CPU REHEARSAL finished — NOT a chip result ***")
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
