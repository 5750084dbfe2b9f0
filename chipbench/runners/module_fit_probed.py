"""Runner ``module_fit_probed``: one ``Module.fit`` epoch of a language
model whose checks are named by its configuration — the probes judged
against the reference (``check.probes``), the expert choices compared
with it (``check.choice_probes``), the counters printed and those that
must have counted (``check.counters``, ``check.traced_counters``) and
``check.must_pass``.  The module, the pool iterator, the window, the
reference's loss and gradients, the verdict and the kernel-time tracer
are ``module_fit_lm``'s and ``module_fit``'s, imported.

``correct`` is decided on what the timed module itself produced at the
timed shape, by ``correct.py``'s rule: the loss and the probes of the
first fused step, and the gradients of ``check.gradient_subset`` through
the module's own backward, against the plain reference in float32 and in
the configuration's dtype.  The runner also prints, from that first
step, the rows every expert received in each expert layer (the imbalance
the grouped product met) and the share of (token, slot) choices on which
the system and the float32 reference agree.
"""
import importlib
import time

import jax
import jax.numpy as jnp
import mxnet_tpu as mx
import numpy as np
from mxnet_tpu import telemetry
from mxnet_tpu.io import DataBatch

from chipbench import build, correct, traffic_lm
from chipbench.runners.module_fit import PoolIter, Window
from chipbench.runners.module_fit_lm import (KernelTracer, bound_module,
                                             named, reference_quantities,
                                             subsets, verdict)


def all_probes(cfg):
    return list(cfg["check"]["probes"]) + \
        list(cfg["check"].get("choice_probes", []))


def probed_symbol(cfg, probe_layer=None):
    """The configuration's ``model`` for ``bound_module``: its ``symbol``
    function with the probes the configuration names as outputs."""
    return named(cfg, "symbol")(cfg, probes=all_probes(cfg))


def reference_probes(cfg, params, x, y, dtype):
    """{probe: float32 numpy} of the plain reference in *dtype*, by one
    forward pass."""
    ref = importlib.import_module("chipbench.reference." + cfg["reference"])
    fn = jax.jit(lambda p, x, y: ref.loss(cfg, p, x, y, dtype,
                                          tuple(all_probes(cfg)))[1])
    with jax.default_matmul_precision("highest"):
        out = fn(params, x, y)
    return {k: np.asarray(v.astype(jnp.float32)) for k, v in out.items()}


def routing_report(cfg, sys_probes, ref_probes):
    """Rows an expert received (largest, mean, smallest) in each expert
    layer of the system's first step, and the share of the system's
    (token, slot) choices that the float32 reference made for the same
    token."""
    report = {}
    for name in cfg["check"].get("choice_probes", []):
        mine = sys_probes[name].astype(np.int64)
        theirs = ref_probes[name].astype(np.int64)
        loads = np.bincount(mine.ravel(), minlength=cfg["num_experts"])
        agree = (mine[..., :, None] == theirs[..., None, :]).any(-1)
        report[name] = {"rows": int(loads.sum()), "max": int(loads.max()),
                        "mean": float(loads.mean()), "min": int(loads.min()),
                        "agree": float(agree.mean())}
    return report


def run(env):
    cfg, tr = env.cfg, env.traffic
    chk = cfg["check"]
    ctx = env.contexts(mx)
    ctx0 = ctx if env.chips == 1 else ctx[0]
    shape = (tr["sequences_per_step"] * env.chips, tr["seq_len"])
    tokens = shape[0] * shape[1]
    if tr["batch_per_chip"] * env.chips != tokens:
        raise SystemExit("chipbench: batch_per_chip is tokens a chip a step")
    if cfg.get("seq_len", tr["seq_len"]) != tr["seq_len"]:
        raise SystemExit("chipbench: the configuration counts attention at "
                         "%d tokens a sequence, the traffic has %d"
                         % (cfg["seq_len"], tr["seq_len"]))
    env.tracer = KernelTracer(env.tracer, cfg.get("trace_patterns", {}))
    env.phases.append(("import", time.perf_counter() - env.t_process))

    with env.phase("build_bind_init"):
        mod = bound_module(cfg, ctx, shape, env.seed)
        ex = mod._exec_group.execs[0]
        names = ["loss"] + all_probes(cfg)
    with env.phase("data"):
        xs, ys = traffic_lm.token_pool(env.seed, tr["pool_batches"],
                                       shape[0], shape[1], cfg["vocab_size"])
        pool_x = [mx.nd.NDArray(x, ctx=ctx0) for x in xs]
        pool_y = [mx.nd.NDArray(y, ctx=ctx0) for y in ys]
        jax.block_until_ready([p._data for p in pool_x + pool_y])
        del xs, ys
    first = DataBatch([pool_x[0]], [pool_y[0]])
    x0, y0 = pool_x[0]._data, pool_y[0]._data

    # gradients through the module's own backward at the timed shape; what
    # is kept goes to the host and the gradient buffers are dropped
    with env.phase("check_gradients"):
        groups = subsets(mod._exec_group.param_names, chk["gradient_subset"])
        picked = sorted(n for group in groups for n in group)
        mod.forward_backward(first)
        sys_grads = {n: ex.grad_dict[n].asnumpy().astype(np.float32)
                     for n in picked}
        ex.release_grads()
    # the reference on the module's own buffers, auxiliary states
    # included, before the first step changes them
    with env.phase("reference"):
        params = {n: a._data for d in (ex.arg_dict, ex.aux_dict)
                  for n, a in d.items() if n not in ("data", "softmax_label")}
        want = reference_quantities(cfg, params, groups, x0, y0, "float32")
        plain = reference_quantities(cfg, params, groups, x0, y0,
                                     cfg["dtype"])
        want_probes = reference_probes(cfg, params, x0, y0, "float32")
        plain_probes = reference_probes(cfg, params, x0, y0, cfg["dtype"])
        del params
    with env.phase("first_step"):
        before = {k: telemetry.counter(k)
                  for k in chk["traced_counters"] + ["sparse_moe_rows"]}
        mod._fit_step(first)
        outs = dict(zip(names, (o.asnumpy().astype(np.float32)
                                for o in mod.get_outputs())))
        traced = {k: telemetry.counter(k) - n for k, n in before.items()}
        rows_a_step = traced.pop("sparse_moe_rows")
    with env.phase("check_forward"):
        rows = [correct.judge("loss", float(outs["loss"][0]), want[0],
                              plain[0])]
        rows += [correct.judge("probe:" + n, outs[n], want_probes[n],
                               plain_probes[n]) for n in chk["probes"]]
        rows += [correct.judge("grad:" + n, sys_grads[n], want[2][n],
                               plain[2][n]) for n in picked]
        ok = verdict(env, rows)
        routing = routing_report(cfg, outs, want_probes)
        env.say("routing", routing)
    del want, plain, want_probes, plain_probes, rows, sys_grads, outs

    window = Window(env, mod, tr["warmup_batches"])
    it = PoolIter(pool_x, pool_y, window)
    it.next = env.spans.wrap("next_batch", it.next)
    mod._fit_step = env.spans.wrap("fit_step", mod._fit_step)
    mod.update_metric = env.spans.wrap("update_metric", mod.update_metric)
    # only the graph's first output is the loss; the others are probes
    metric = mx.metric.create(tr["eval_metric"])
    update = metric.update
    metric.update = lambda labels, preds: update(labels, preds[:1])
    t_fit = time.perf_counter()
    mod.fit(it, eval_metric=metric, batch_end_callback=window,
            num_epoch=1, optimizer=cfg["optimizer"]["name"],
            optimizer_params=build.optimizer_params(cfg))
    env.tracer.stop()
    env.phases.append(("warmup", window.t_open - t_fit))

    # what must hold over the window
    batches = len(window.stamps) - window.warmup
    seconds = window.stamps[-1] - window.t_open
    in_window = env.compiles.n - window.compiles_at_open
    finite = all(bool(jnp.isfinite(v._data.astype(jnp.float32)).all())
                 for d in mod.get_params() for v in d.values())
    last_loss = float(mod.get_outputs()[0].asnumpy()[0])
    counters = {k: telemetry.counter(k) for k in chk["counters"]}
    expert_layers = len(chk.get("choice_probes", []))
    routed = tokens * cfg.get("num_experts_per_tok", 0)
    held = {"reference_rule": ok, "zero_compiles_in_window": in_window == 0,
            "fused_step_taken": mod._cached_step is not None,
            "ops_traced": all(n > 0 for n in traced.values()),
            "no_routed_row_dropped": rows_a_step == routed * expert_layers
            and all(r["rows"] == routed for r in routing.values()),
            "params_finite": bool(finite),
            "last_loss_finite": bool(np.isfinite(last_loss)),
            "buffers_on_cell_devices": window.stray == [],
            "batches": batches > 0}
    env.say("window", {"held": held, "batches": batches, "seconds": seconds,
                       "last_loss": last_loss, "batch": tokens,
                       "counters": counters, "traced_in_first_step": traced,
                       "routed_rows_a_step": rows_a_step,
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
