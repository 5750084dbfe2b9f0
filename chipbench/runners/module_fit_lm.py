"""Runner ``module_fit_lm``: one ``Module.fit`` epoch of a language model
over a seeded pool of device-resident token batches — the same loop,
window, callbacks, spans and facts as ``module_fit`` (its iterator and
window are imported, not copied), with a loss-valued graph and
``eval_metric`` ``loss``, which fetches one number a batch.

``correct`` is decided on what the timed module itself produced at the
timed shape, by ``correct.py``'s rule: the loss and the probed retention
output of the first fused step, and the gradients of the configuration's
``check.gradient_subset`` through the module's own backward, against the
plain reference in float32 and in the configuration's dtype.  Only one
bound module is ever alive; the reference runs between the module's
backward and its first fused step and its buffers are freed before the
window.
"""
import fnmatch
import importlib
import shutil
import time

import jax
import jax.numpy as jnp
import mxnet_tpu as mx
import numpy as np
from mxnet_tpu import telemetry
from mxnet_tpu.io import DataBatch, DataDesc

from chipbench import build, correct, harness, kernel_time, traffic_lm
from chipbench import trace as trace_mod
from chipbench.runners.module_fit import PoolIter, Window


class KernelTracer(harness.Tracer):
    """The harness's profiler window, which on closing also sums the
    device time inside the configuration's named kernels (the trace
    directory is gone once ``stop`` returns)."""

    def __init__(self, base, patterns):
        super().__init__(base.wanted, base.after_s, base.length_s)
        self.patterns, self.kernel_s = patterns, {}

    def stop(self):
        if self.t_start is None or self.done:
            return
        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.done = True
        try:
            self.rows = trace_mod.read_xplane(self.dir)
            self.kernel_s = kernel_time.seconds_by_pattern(self.dir,
                                                           self.patterns)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def named(cfg, key):
    module, _, fn = cfg[key].partition(":")
    try:
        return getattr(importlib.import_module(module), fn)
    except (ImportError, AttributeError) as exc:
        raise SystemExit("chipbench: this program cannot run configuration "
                         "%r: %s" % (cfg["name"], exc))


def bound_module(cfg, ctx, shape, seed):
    """The model bound for training at *shape* = (sequences, tokens),
    Xavier weights from the seed, optimizer set."""
    sym = named(cfg, "model")(cfg, probe_layer=cfg["check"]["probe_layer"])
    mx.random.seed(build.fold_seed(seed))
    mod = mx.mod.Module(sym, context=ctx)
    mod.bind(data_shapes=[DataDesc("data", shape, dtype=np.float32)],
             label_shapes=[DataDesc("softmax_label", shape,
                                    dtype=np.float32)],
             for_training=True)
    mod.init_params(initializer=mx.initializer.Xavier())
    mod.init_optimizer(optimizer=cfg["optimizer"]["name"],
                       optimizer_params=build.optimizer_params(cfg))
    return mod


def subsets(names, groups):
    """The parameter names each group of patterns picks, group by group."""
    return [sorted(n for n in names
                   if any(fnmatch.fnmatchcase(n, p) for p in patterns))
            for patterns in groups]


def reference_quantities(cfg, params, groups, x, y, dtype):
    """(loss, probed retention output, gradients of the tensors in
    *groups*) of the plain reference in *dtype*, as float32 numpy.
    *params* are the module's own device buffers; a picked tensor enters
    in *dtype*, so its gradient comes back in it.  One program a group:
    the float32 gradients of every picked tensor at once do not fit beside
    the module, and a group that holds only the last layer and the head
    needs no backward pass through the layers below."""
    ref = importlib.import_module("chipbench.reference." + cfg["reference"])
    loss = probe = None
    grads = {}
    for picked in groups:
        layer = cfg["check"]["probe_layer"] if probe is None else None

        @jax.jit
        def fn(params, x, y, picked=picked, layer=layer):
            chosen = {k: params[k].astype(dtype) for k in picked}
            return jax.value_and_grad(
                lambda chosen: ref.loss(cfg, dict(params, **chosen), x, y,
                                        dtype, layer), has_aux=True)(chosen)

        with jax.default_matmul_precision("highest"):
            (loss, out), part = fn(params, x, y)
        if out is not None:
            probe = np.asarray(out.astype(jnp.float32))
        grads.update({k: np.asarray(v.astype(jnp.float32))
                      for k, v in part.items()})
        loss = float(loss)
        del out, part
    return loss, probe, grads


def verdict(env, rows):
    known = [d["name"] for d in env.cfg.get("known_deviations", [])]
    ok, report = correct.summarise(rows, env.cfg["check"]["must_pass"],
                                   skip=known)
    env.say("deviations", {r["name"]: [float("%.3g" % r["dev_sys"]),
                                       float("%.3g" % r["dev_plain"]),
                                       r["verdict"]] for r in rows})
    env.say("check", report)
    return ok


def run(env):
    cfg, tr = env.cfg, env.traffic
    ctx = env.contexts(mx)
    ctx0 = ctx if env.chips == 1 else ctx[0]
    shape = (tr["sequences_per_step"] * env.chips, tr["seq_len"])
    tokens = shape[0] * shape[1]
    if tr["batch_per_chip"] * env.chips != tokens:
        raise SystemExit("chipbench: batch_per_chip is tokens a chip a step")
    env.tracer = KernelTracer(env.tracer, cfg.get("trace_patterns", {}))
    env.phases.append(("import", time.perf_counter() - env.t_process))

    with env.phase("build_bind_init"):
        mod = bound_module(cfg, ctx, shape, env.seed)
        ex = mod._exec_group.execs[0]
    with env.phase("data"):
        xs, ys = traffic_lm.token_pool(env.seed, tr["pool_batches"],
                                       shape[0], shape[1], cfg["vocab_size"])
        pool_x = [mx.nd.NDArray(xs[i], ctx=ctx0) for i in range(len(xs))]
        pool_y = [mx.nd.NDArray(ys[i], ctx=ctx0) for i in range(len(ys))]
        jax.block_until_ready([p._data for p in pool_x + pool_y])
        del xs, ys
    first = DataBatch([pool_x[0]], [pool_y[0]])

    # (b) gradients through the module's own backward at the timed shape;
    # what is kept goes to the host and the gradient buffers are dropped
    with env.phase("check_gradients"):
        groups = subsets(mod._exec_group.param_names,
                         cfg["check"]["gradient_subset"])
        picked = sorted(n for group in groups for n in group)
        mod.forward_backward(first)
        sys_grads = {n: ex.grad_dict[n].asnumpy().astype(np.float32)
                     for n in picked}
        ex.release_grads()
    # the reference, in float32 and in the configuration's dtype, on the
    # module's own parameter buffers (the first step has not run yet)
    with env.phase("reference"):
        params = {n: ex.arg_dict[n]._data
                  for n in mod._exec_group.param_names}
        x0, y0 = pool_x[0]._data, pool_y[0]._data
        want = reference_quantities(cfg, params, groups, x0, y0, "float32")
        plain = reference_quantities(cfg, params, groups, x0, y0,
                                     cfg["dtype"])
        del params, x0, y0
    # (a) the first fused step's loss and probed retention output
    with env.phase("first_step"):
        mod._fit_step(first)
        outs = mod.get_outputs()
        sys_loss = float(outs[0].asnumpy()[0])
        sys_probe = outs[1].asnumpy().astype(np.float32)
        del outs
    with env.phase("check_forward"):
        rows = [correct.judge("loss", sys_loss, want[0], plain[0]),
                correct.judge("retention_output", sys_probe, want[1],
                              plain[1])]
        rows += [correct.judge("grad:" + n, sys_grads[n], want[2][n],
                               plain[2][n]) for n in picked]
        ok = verdict(env, rows)
    del want, plain, rows, sys_grads, sys_probe

    window = Window(env, mod, tr["warmup_batches"])
    it = PoolIter(pool_x, pool_y, window)
    it.next = env.spans.wrap("next_batch", it.next)
    mod._fit_step = env.spans.wrap("fit_step", mod._fit_step)
    mod.update_metric = env.spans.wrap("update_metric", mod.update_metric)
    # the graph's second output is the probe: the metric reads the loss
    metric = mx.metric.create(tr["eval_metric"])
    update = metric.update
    metric.update = lambda labels, preds: update(labels, preds[:1])
    t_fit = time.perf_counter()
    mod.fit(it, eval_metric=metric, batch_end_callback=window,
            num_epoch=1, optimizer=cfg["optimizer"]["name"],
            optimizer_params=build.optimizer_params(cfg))
    env.tracer.stop()
    env.phases.append(("warmup", window.t_open - t_fit))

    # (c) what must hold over the window
    batches = len(window.stamps) - window.warmup
    seconds = window.stamps[-1] - window.t_open
    in_window = env.compiles.n - window.compiles_at_open
    final_arg, final_aux = mod.get_params()
    finite = all(bool(jnp.isfinite(v._data.astype(jnp.float32)).all())
                 for d in (final_arg, final_aux) for v in d.values())
    last_loss = float(mod.get_outputs()[0].asnumpy()[0])
    counters = {k: telemetry.counter(k) for k in (
        "power_retention_traced", "power_retention_chunks",
        "executor_remat_segments", "module_train_step",
        "module_step_carried")}
    held = {"reference_rule": ok, "zero_compiles_in_window": in_window == 0,
            "fused_step_taken": mod._cached_step is not None,
            "state_form_traced": counters["power_retention_traced"] > 0,
            "params_finite": bool(finite),
            "last_loss_finite": bool(np.isfinite(last_loss)),
            "buffers_on_cell_devices": window.stray == [],
            "batches": batches > 0}
    env.say("window", {"held": held, "batches": batches, "seconds": seconds,
                       "last_loss": last_loss, "batch": tokens,
                       "counters": counters,
                       "kernel_trace": env.tracer.kernel_s})
    return {
        "correct": all(held.values()), "attempted": batches, "failed": 0,
        "t_open": window.t_open, "compiles_before": window.compiles_at_open,
        "compiles_in_window": in_window,
        "end_to_end": {"train_items_s_per_chip":
                       batches * tokens / seconds / env.chips},
        "facts": {"batch_per_chip": tr["batch_per_chip"],
                  "fit_step_s": env.spans.durations("fit_step",
                                                    window.t_open),
                  "kernel_s": env.tracer.kernel_s},
    }
