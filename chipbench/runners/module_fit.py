"""Runner ``module_fit``: one ``Module.fit`` epoch over a seeded pool of
device-resident batches — iterator, fused step, ``acc`` metric, batch-end
callback — which is what a trainer calls.  The window opens at the
callback of the last warm-up batch and closes at the last callback before
the iterator ends the epoch.
"""
import time

import jax
import mxnet_tpu as mx
import numpy as np
from mxnet_tpu.io import DataBatch, DataDesc, DataIter
from mxnet_tpu.optimizer import _state_raw

from chipbench import build, check, traffic


class PoolIter(DataIter):
    """Batches from a pool already on the device; ends the epoch once the
    window's deadline has passed."""

    def __init__(self, pool_x, pool_y, window):
        super().__init__(batch_size=pool_x[0].shape[0])
        self.pool_x, self.pool_y, self.window = pool_x, pool_y, window
        self.provide_data = [DataDesc("data", pool_x[0].shape,
                                      dtype=np.float32)]
        self.provide_label = [DataDesc("softmax_label", pool_y[0].shape,
                                       dtype=np.float32)]
        self.served = 0

    def iter_next(self):
        deadline = self.window.deadline
        if deadline is not None and time.perf_counter() >= deadline:
            return False
        self.served += 1
        return True

    def slot(self):
        return (self.served - 1) % len(self.pool_x)

    def getdata(self):
        return [self.pool_x[self.slot()]]

    def getlabel(self):
        return [self.pool_y[self.slot()]]

    def getpad(self):
        return 0

    def getindex(self):
        return None


class Window:
    """Callback stamps; opens at warm-up batch K's callback."""

    def __init__(self, env, mod, warmup):
        self.env, self.mod, self.warmup = env, mod, warmup
        self.stamps = []
        self.t_open = self.deadline = None
        self.compiles_at_open = self.stray = None

    def __call__(self, param):
        if len(self.stamps) + 1 == self.warmup:
            # steady state, just outside the window (fit's epoch end
            # re-places the parameters, so afterwards is too late)
            self.stray = [b for b in module_buffers(self.mod)
                          if set(b.devices()) != set(self.env.devices)]
        with self.env.spans.span("callback"):
            now = time.perf_counter()
            self.stamps.append(now)
            if len(self.stamps) == self.warmup:
                self.t_open = now
                self.deadline = now + self.env.seconds
                self.compiles_at_open = self.env.compiles.n
        # the device is drained here (the metric just synced on this
        # batch's outputs), so a profiler window cut at callbacks holds
        # whole batches
        self.env.tracer.tick(now, self.t_open)


def module_buffers(mod):
    """Every jax buffer the bound trainer owns (chip_smoke.py's list)."""
    ex = mod._exec_group.execs[0]
    bufs = [a._data for a in ex.arg_dict.values()]
    bufs += [a._data for a in ex.aux_dict.values()]
    bufs += [o._data for o in mod.get_outputs()]
    states = [_state_raw(s) for s in mod._updater.states.values()]
    bufs += [leaf for leaf in jax.tree_util.tree_leaves(states)
             if hasattr(leaf, "devices")]
    return bufs


def run(env):
    cfg, tr = env.cfg, env.traffic
    ctx = env.contexts(mx)
    ctx0 = ctx if env.chips == 1 else ctx[0]
    batch = tr["batch_per_chip"] * env.chips
    env.phases.append(("import", time.perf_counter() - env.t_process))

    with env.phase("build_bind_init"):
        sym = build.train_symbol(build.zoo_net(cfg), cfg["dtype"])
        mod = build.seeded_module(mx, cfg, sym, ctx, batch, env.seed)
        arg_nd, aux_nd = mod.get_params()
        arg_nd, aux_nd = dict(arg_nd), dict(aux_nd)
        params, aux = build.host_params(mod)
    with env.phase("data"):
        xs, ys = traffic.image_pool(env.seed, tr["pool_batches"], batch,
                                    cfg["image"], cfg["classes"])
        pool_x = [mx.nd.NDArray(xs[i], ctx=ctx0) for i in range(len(xs))]
        pool_y = [mx.nd.NDArray(ys[i], ctx=ctx0) for i in range(len(ys))]
        jax.block_until_ready([p._data for p in pool_x])
        del xs                  # the pool's slices are copies
        x0, ys = pool_x[0]._data, np.asarray(ys)

    # (b) gradients through the Module's own backward on a batch small
    # enough for the float32 jax.grad; before the timed module's first
    # step so that its buffers are the only large ones alive in the window
    with env.phase("check_gradients"):
        n = tr["check_batch"]
        x_chk, y_chk = x0[:n], ys[0][:n]
        chk = build.bind_module(mx, sym, ctx, n, cfg["image"])
        chk.init_params(arg_params=arg_nd, aux_params=aux_nd)
        chk.forward_backward(DataBatch([mx.nd.NDArray(x_chk, ctx=ctx0)],
                                       [mx.nd.array(y_chk, ctx=ctx0)]))
        group = chk._exec_group
        sys_grads = {name: per_dev[0].asnumpy().astype(np.float32)
                     for name, per_dev in zip(group.param_names,
                                              group.grad_arrays)}
        rows = check.grad_rows(env, params, aux, env.shard_rows(x_chk),
                               env.shard_rows(y_chk), sys_grads)
        del chk, group
    # (a) at the timed shape: the first fused step's training-mode outputs
    with env.phase("first_step"):
        mod._fit_step(DataBatch([pool_x[0]], [pool_y[0]]))
        sys_probs = mod.get_outputs()[0].asnumpy()
    with env.phase("check_forward"):
        rows = check.forward_rows(env, params, aux, env.shard_rows(x0),
                                  env.shard_rows(ys[0]), sys_probs) + rows
        ok = check.verdict(env, rows)
    del params, aux, arg_nd, aux_nd, rows, x0

    window = Window(env, mod, tr["warmup_batches"])
    it = PoolIter(pool_x, pool_y, window)
    it.next = env.spans.wrap("next_batch", it.next)
    mod._fit_step = env.spans.wrap("fit_step", mod._fit_step)
    mod.update_metric = env.spans.wrap("update_metric", mod.update_metric)
    t_fit = time.perf_counter()
    mod.fit(it, eval_metric=tr["eval_metric"], batch_end_callback=window,
            num_epoch=1, optimizer=cfg["optimizer"]["name"],
            optimizer_params=build.optimizer_params(cfg))
    env.tracer.stop()
    env.phases.append(("warmup", window.t_open - t_fit))

    # (c) what must hold over the window
    batches = len(window.stamps) - window.warmup
    seconds = window.stamps[-1] - window.t_open
    in_window = env.compiles.n - window.compiles_at_open
    final_arg, final_aux = mod.get_params()
    finite = all(np.isfinite(v.asnumpy().astype(np.float32)).all()
                 for d in (final_arg, final_aux) for v in d.values())
    last_loss = check.mean_nll(mod.get_outputs()[0].asnumpy(),
                               ys[it.slot()])
    held = {"reference_rule": ok, "zero_compiles_in_window": in_window == 0,
            "fused_step_taken": mod._cached_step is not None,
            "params_finite": bool(finite),
            "last_loss_finite": bool(np.isfinite(last_loss)),
            "buffers_on_cell_devices": window.stray == [],
            "batches": batches > 0}
    env.say("window", {"held": held, "batches": batches, "seconds": seconds,
                       "last_loss": last_loss, "batch": batch})
    return {
        "correct": all(held.values()), "attempted": batches, "failed": 0,
        "t_open": window.t_open, "compiles_before": window.compiles_at_open,
        "compiles_in_window": in_window,
        "end_to_end": {"train_items_s_per_chip":
                       batches * batch / seconds / env.chips},
        "facts": {"batch_per_chip": tr["batch_per_chip"],
                  "fit_step_s": env.spans.durations("fit_step",
                                                    window.t_open)},
    }
