"""CachedTrainStep: fwd + bwd + optimizer update as ONE donated program.

The reference's training loop after bind does zero graph work per step —
``GraphExecutor::RunOps`` (graph_executor.cc:1403) pushes cached engine ops
and the fused optimizer kernels (``src/operator/optimizer_op.cc``) mutate
weights in place. The TPU equivalent is one jitted XLA program per bound
(shapes, optimizer) pair:

    (params, data, aux, opt_states, hyper, root_key)
        -> (outputs, new_params, new_aux, new_opt_states, new_root_key)

with parameter / aux / state buffers **donated**, so XLA updates weights
in place in HBM exactly like the reference's in-place optimizer kernels.
Gradients are consumed inside the program and never materialise at a
program boundary — the step is fwd+bwd+update with nothing in between.

Hyper-parameters (per-param lr/wd after scheduler + multipliers, the
update count ``t``) enter as *traced* host arrays: a changing
learning-rate schedule never causes a retrace.

The PRNG key is threaded through the program: it takes the global
stream's root key (``random.lend_root_key``) and returns the advanced
one, and derives the graph's key and one key per parameter (for
stochastic optimizers like SGLD) inside the trace by the very splits the
host would make — the stream is bit-identical to drawing
``random.next_key()`` once a step, whatever the model and optimizer use
of it.

What a step needs that the previous step produced is carried, not
re-derived: the step remembers the buffers it wrote back, and an input
whose handle still holds the remembered buffer is this program's own
output, placed where the program put it — it skips the executor's
``_place``.  Anything else (first step, ``set_params``, a bucket switch,
``set_states``, a user's ``_set_data``) takes ``_place`` for that tensor.
In steady state the host launches no device program and makes no
``device_put`` before the step program.  (Where buffers are not donated —
host-CPU contexts — a step that is not running, e.g. another bucket's,
keeps its last outputs alive until it runs again.)

Used automatically by ``Module.fit`` when the update placement allows it
(single logical parameter copy, optimizer-on-worker — the single-chip and
fused-SPMD cases); any kvstore-mediated placement falls back to the
split path. Opt out with ``MXNET_MODULE_FUSED_STEP=0``.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

from .. import random as _random
from .. import telemetry as _tel
from ..ndarray import NDArray
from ..optimizer import _state_raw, _state_writeback

__all__ = ["CachedTrainStep", "fused_step_enabled"]


def fused_step_enabled():
    # deliberate re-read: called once per Module.fit bind (not per step),
    # and tests toggle MXNET_MODULE_FUSED_STEP at runtime
    # graftlint: disable=JG006
    return os.environ.get("MXNET_MODULE_FUSED_STEP", "1").strip().lower() \
        not in ("0", "false", "off", "no")


def tracecheck_programs():
    """AOT specimens for graftcheck: the whole fwd+bwd+update program
    Module.fit ships, bound to the specimen executor with a momentum-SGD
    updater (same construction path as the real bind; the constructor
    never executes anything)."""
    import jax as _jax
    from .. import optimizer as opt_mod
    from ..executor import _tracecheck_executor
    ex = _tracecheck_executor()
    updater = opt_mod.get_updater(opt_mod.SGD(momentum=0.9,
                                              learning_rate=0.05))
    pnames = [n for n in ex.arg_names if n in set(ex._grad_names)]
    cts = CachedTrainStep(ex, updater, ["data"] + pnames)
    spec = lambda a: _jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
    params = [spec(ex.arg_dict[n]) for n in cts._pnames]
    rest = [spec(ex.arg_dict[n]) for n in cts._rest_names]
    aux_vals = [spec(ex.aux_dict[n]) for n in ex.aux_names]
    states = [_jax.tree_util.tree_map(
        spec, _state_raw(updater.optimizer.create_state(
            i, ex.arg_dict[n])))
        for i, n in enumerate(cts._pnames)]
    n = len(cts._pnames)
    hyper = {"lr": np.zeros(n, np.float32), "wd": np.zeros(n, np.float32),
             "t": np.ones(n, np.int32)}
    root = _jax.eval_shape(_jax.random.PRNGKey, 0)
    return [("module_cached_step", cts._step_jit,
             (params, rest, aux_vals, states, hyper, root), {})]


def _same_buffers(raw, carried):
    """Is the optimizer-state pytree *raw* leaf for leaf the remembered
    one?"""
    if isinstance(raw, tuple):
        return isinstance(carried, tuple) and len(raw) == len(carried) \
            and all(map(_same_buffers, raw, carried))
    return raw is carried


class CachedTrainStep:
    """One compiled train step bound to (executor, updater, param set)."""

    def __init__(self, executor, updater, param_names):
        self._exec = executor
        self._updater = updater
        self._opt = updater.optimizer
        # updatable params = the executor's grad-bearing args, in the
        # module's param order so optimizer indices match the slow path
        grad_set = set(executor._grad_names)
        self._pnames = [n for n in param_names if n in grad_set]
        if set(self._pnames) != grad_set:
            raise ValueError("fused step needs grads on params only")
        arg_names = executor.arg_names
        self._ppos = [arg_names.index(n) for n in self._pnames]
        self._rest_names = [n for n in arg_names if n not in grad_set]
        rest_pos = [arg_names.index(n) for n in self._rest_names]
        self._pidx = {n: i for i, n in enumerate(param_names)}

        fn_train = executor._train_fn
        n_args = len(arg_names)
        ppos, opt = self._ppos, self._opt

        def step(params, rest, aux_vals, states, hyper, root):
            # random.next_key()'s split, then the step's own: one key for
            # the graph, one per parameter for the optimizer
            new_root, sub = jax.random.split(root)
            ukeys = jax.random.split(sub, len(ppos) + 1)

            def g(ps):
                full = [None] * n_args
                for p, v in zip(ppos, ps):
                    full[p] = v
                for p, v in zip(rest_pos, rest):
                    full[p] = v
                return fn_train(full, aux_vals, ukeys[0])
            outs, vjp_fn, new_aux = jax.vjp(g, params, has_aux=True)
            (grads,) = vjp_fn(tuple(jnp.ones_like(o) for o in outs))
            new_params, new_states = [], []
            for i, (w, gr) in enumerate(zip(params, grads)):
                h = {"lr": jnp.asarray(hyper["lr"][i], dtype=w.dtype),
                     "wd": jnp.asarray(hyper["wd"][i], dtype=w.dtype),
                     "t": hyper["t"][i], "key": ukeys[1 + i]}
                nw, ns = opt.update_step(w, gr.astype(w.dtype),
                                         states[i], h)
                new_params.append(nw.astype(w.dtype))
                new_states.append(ns)
            return outs, new_params, new_aux, new_states, new_root

        donate = (0, 2, 3) if executor._ctx.device_type != "cpu" else ()
        self._step_jit = _tel.watch_jit(
            jax.jit(step, donate_argnums=donate), "module_cached_step")
        # what the last step wrote back, by position: params, aux, state
        # pytrees, and the root key it handed to random
        self._carried = ([None] * len(self._pnames),
                         [None] * len(executor.aux_names),
                         [None] * len(self._pnames), None)
        self._compiled = False     # no run yet: the next one is set-up
        self.setup_args = {}       # its owner's tag for the set-up span

    def _gather(self, names, table, carried):
        """Raw buffers of ``table[name]`` for *names*, and how many went
        through the executor's ``_place`` because their handle no longer
        holds what the last step wrote back."""
        place = self._exec._place
        bufs, placed = [], 0
        for name, was in zip(names, carried):
            arr = table[name]
            data = arr._data
            if data is not was:
                data = place(name, arr)
                placed += 1
            bufs.append(data)
        return bufs, placed

    def _ensure_states(self):
        """Create optimizer state through the Updater so checkpoint
        save/load (updater.get_states/set_states) sees the same layout
        as the slow path."""
        for name in self._pnames:
            idx = self._pidx[name]
            if idx not in self._updater.states:
                self._updater.states[idx] = self._opt.create_state(
                    idx, self._exec.arg_dict[name])
                self._updater.states_synced[idx] = True

    def run(self, feed):
        """Execute one step; *feed* maps data/label names to NDArrays."""
        _tel.bump("module_train_step")
        if not self._compiled:
            # the run that traces, lowers and compiles (or loads) the step
            # program: set-up, told apart from every later step and timed
            # to the end of the program's first run
            self._compiled = True
            with _tel.span("module_first_step", cat="setup",
                           args=dict(self.setup_args,
                                     params=len(self._pnames))):
                outputs = self._step(feed)
                jax.block_until_ready([o._data for o in outputs])
            return outputs
        return self._step(feed)

    def _step(self, feed):
        with _tel.span("module_train_step", cat="step",
                       hist="step_time_us", memory=True,
                       args={"params": len(self._pnames)}):
            return self._run(feed)

    def _run(self, feed):
        # one child span per host phase, back to back, so that
        # module_train_step has no self time to hide work in.  The step's
        # root span has just asked whether anything records; the children
        # read that answer once, and with it false each is one bool test
        ex = self._exec
        span, idle = _tel.span, _tel.NO_SPAN
        rec = _tel.trace_active()
        with span("module_step_feed", cat="host") if rec else idle:
            for k, v in feed.items():
                if k in ex.arg_dict:
                    src = v._data if isinstance(v, NDArray) \
                        else jnp.asarray(v)
                    ex.arg_dict[k]._set_data(
                        src.astype(ex.arg_dict[k].dtype))
        with span("module_step_place_batch", cat="host") if rec else idle:
            rest = [ex._place(n, ex.arg_dict[n]) for n in self._rest_names]

        opt = self._opt
        prev_num_update = opt.num_update
        with span("module_step_hyper", cat="host") if rec else idle:
            self._ensure_states()
            lrs, wds, ts = [], [], []
            for name in self._pnames:
                idx = self._pidx[name]
                opt._update_count(idx)
                lrs.append(opt._get_lr(idx))
                wds.append(opt._get_wd(idx))
                ts.append(opt._index_update_count[idx])
            hyper = {"lr": np.asarray(lrs, np.float32),
                     "wd": np.asarray(wds, np.float32),
                     "t": np.asarray(ts, np.int32)}

        was_params, was_aux, was_states, was_root = self._carried
        with span("module_step_place_params", cat="host") if rec else idle:
            params, placed = self._gather(self._pnames, ex.arg_dict,
                                          was_params)
            aux_vals, n = self._gather(ex.aux_names, ex.aux_dict, was_aux)
            placed += n
            states = []
            for name, w, was in zip(self._pnames, params, was_states):
                raw = _state_raw(self._updater.states[self._pidx[name]])
                if not _same_buffers(raw, was):
                    # optimizer state must live where its weight lives
                    # (sharded executors replicate params over a mesh
                    # AFTER create_state ran)
                    raw = jax.tree_util.tree_map(
                        lambda leaf, w=w: leaf
                        if getattr(w, "sharding", None) in (
                            None, getattr(leaf, "sharding", None))
                        else jax.device_put(leaf, w.sharding), raw)
                    placed += 1
                states.append(raw)
            if not placed:
                _tel.bump("module_step_carried")

        try:
            with _random.lend_root_key() as loan:
                with span("module_step_rng", cat="host") if rec else idle:
                    root = loan.key
                    if root is not was_root:
                        root = ex._place_rng(root)
                        if not root.committed:
                            # the program returns its key committed: an
                            # uncommitted one is a second signature, and
                            # the step program would compile twice
                            root = jax.device_put(root, ex._ctx.jax_device)
                with span("module_step_enqueue", cat="program"):
                    outs, new_params, new_aux, new_states, new_root = \
                        self._step_jit(params, rest, aux_vals, states,
                                       hyper, root)
                loan.key = new_root
        except NotImplementedError:
            # optimizer lacks a pure update_step (discovered at trace
            # time): roll back the count bookkeeping so the slow-path
            # retry of this same batch doesn't double-count the step
            # (the random stream has not moved: the loan raised)
            for name in self._pnames:
                opt._index_update_count[self._pidx[name]] -= 1
            opt.num_update = prev_num_update
            raise

        with span("module_step_writeback", cat="host") if rec else idle:
            for n, v in zip(self._pnames, new_params):
                ex.arg_dict[n]._set_data(v)
            for n, v in zip(ex.aux_names, new_aux):
                ex.aux_dict[n]._set_data(v)
            for n, s in zip(self._pnames, new_states):
                _state_writeback(self._updater.states[self._pidx[n]], s)
            from ..ndarray.ndarray import _wrap
            ex._outputs = [_wrap(o, ex._ctx) for o in outs]
            ex._vjp = None
            self._carried = (new_params, new_aux, new_states, new_root)
            # drop the step's inputs here, not at the frame's exit: the
            # several hundred handles (the donated buffers' among them)
            # take ~0.6 ms to free for ResNet-50 on the chip's host, which
            # is this phase's work and not module_train_step's self time
            del params, rest, aux_vals, states, hyper, root, outs, \
                was_params, was_aux, was_states, was_root
        return ex._outputs
