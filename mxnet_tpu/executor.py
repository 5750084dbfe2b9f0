"""Executor: a bound symbolic graph compiled to single XLA programs.

Parity surface: reference ``python/mxnet/executor.py`` (forward :113,
backward :154, outputs, arg/grad/aux dicts, reshape, monitor) over
``src/executor/graph_executor.cc`` (Init :507/916, RunOps :1403).

TPU-native redesign (SURVEY §7 step 4): the entire GraphExecutor machinery —
gradient-graph synthesis (nnvm Gradient pass), memory planning
(PlanMemory/DetectInplaceAddTo), op-executor attachment, bulk segmenting —
collapses into *one jitted function per (train/eval) mode*:

    eval:  jit(graph_fn)                         — XLA plans memory, fuses
    train: jit(vjp(graph_fn))                    — replaces pass::Gradient.

The train path compiles exactly TWO programs per bind, traced once and
cached for the executor's lifetime (reference parity: after
GraphExecutor::Init the per-step RunOps loop at graph_executor.cc:1403
does no graph work, it only pushes cached engine ops):

    _fwd_train_jit: (args, aux, rng) -> (outputs, new_aux, vjp_fn)
        jax.vjp runs INSIDE the jit; the returned ``vjp_fn`` is a
        jax.tree_util.Partial — a pytree whose leaves are the on-device
        residuals — so it crosses the jit boundary as data.
    _bwd_jit: (vjp_fn, out_grads) -> input_grads
        applies the residual pytree; same treedef every step, so this
        compiles once too.

``forward_backward`` additionally fuses both legs (and the ones-like
head gradient) into ONE XLA program — the Module.fit hot path, where XLA
schedules forward and backward together and residual layouts never
round-trip through program boundaries.

Auxiliary state (BatchNorm moving stats) flows functionally: graph_fn
returns updated aux values, forward writes them back into the aux NDArrays
(reference mutates aux in-kernel).
"""
from __future__ import annotations

from functools import partial

import numpy as np
import jax
import jax.numpy as jnp

from .base import MXNetError
from .context import Context, current_context
from . import random as _random
from . import telemetry as _telemetry
from .ndarray import NDArray, _wrap, zeros as nd_zeros
from .ops import remat as _remat
from .symbol.symbol import Symbol, _topo

__all__ = ["Executor"]


class _LazyZeros(NDArray):
    """A gradient buffer that is zeros until something reads it.  The
    executor hands one out per grad-bearing argument at bind; a training
    path that never reads them (the fused Module step consumes gradients
    inside its program) then holds no device memory for them — at 6 bytes
    a parameter they are a third of a large model's state."""
    __slots__ = ("_spec", "_buf")

    def __init__(self, shape, ctx, dtype):
        self._spec = (tuple(shape), np.dtype(dtype))
        self._buf = None
        super().__init__(None, ctx)

    @property
    def _data(self):
        if self._buf is None:
            self._buf = nd_zeros(self._spec[0], ctx=self._ctx,
                                 dtype=self._spec[1])._data
        return self._buf

    @_data.setter
    def _data(self, value):
        self._buf = value

    @property
    def shape(self):
        return self._spec[0] if self._buf is None else tuple(self._buf.shape)

    @property
    def dtype(self):
        return self._spec[1] if self._buf is None \
            else np.dtype(self._buf.dtype)


def _mirror_stage(node):
    """The recomputation segment an op node belongs to, or None.  A node
    is marked by the reference's own attributes, set through
    ``AttrScope(force_mirroring="True", mirror_stage="<k>")``: nodes that
    share a ``mirror_stage`` form one segment."""
    if node.op is None or str(node.attrs.get(
            "__force_mirroring__", "")).lower() not in ("true", "1"):
        return None
    return str(node.attrs.get("__mirror_stage__", ""))


def _execution_units(nodes, heads):
    """Op nodes in execution order, a recomputation segment's nodes as one
    unit: [(stage or None, [nodes])].  Without segments this is the
    topological order itself.  A segment has to be convex: no path may
    leave it and come back."""
    stage_of = {id(n): _mirror_stage(n) for n in nodes}
    members = {}
    for n in nodes:
        if stage_of[id(n)] is not None:
            members.setdefault(stage_of[id(n)], []).append(n)
    if not members:
        return [(None, [n]) for n in nodes if n.op is not None]
    units, state = [], {}

    def visit(node):
        stage = stage_of[id(node)]
        key = ("stage", stage) if stage is not None else id(node)
        if state.get(key) == "done":
            return
        if state.get(key) == "open":
            raise MXNetError(
                "recomputation segment %r is not convex: a path leaves it "
                "and comes back (node %s)" % (stage, node.name))
        state[key] = "open"
        group = members[stage] if stage is not None else [node]
        for member in group:
            for src, _ in member.inputs:
                if stage is None or stage_of[id(src)] != stage:
                    visit(src)
        state[key] = "done"
        if node.op is not None:
            units.append((stage, group))

    for node, _ in heads:
        visit(node)
    return units


def _build_graph_fn(symbol, train_mode):
    """Build pure fn(arg_vals, aux_vals, rng) -> (outputs, new_aux)."""
    nodes = _topo(symbol._outputs)
    arg_nodes = [n for n in nodes if n.op is None and not n.is_aux]
    aux_nodes = [n for n in nodes if n.op is None and n.is_aux]
    rng_nodes = [n for n in nodes if n.op is not None and n.op.needs_rng]
    arg_pos = {id(n): i for i, n in enumerate(arg_nodes)}
    aux_pos = {id(n): i for i, n in enumerate(aux_nodes)}
    rng_pos = {id(n): i for i, n in enumerate(rng_nodes)}

    # map aux var node -> (producing op node, output index of new value)
    aux_update_src = {}
    for node in nodes:
        if node.op is None or not node.op.aux_updates:
            continue
        for aux_in, out_idx in node.op.aux_updates.items():
            if aux_in < len(node.inputs):
                src, _ = node.inputs[aux_in]
                if src.op is None and src.is_aux:
                    aux_update_src[id(src)] = (node, out_idx)

    heads = list(symbol._outputs)
    # recomputation matters to a backward pass only: the inference
    # program is the plain walk
    units = _execution_units(nodes, heads) if train_mode else \
        [(None, [n]) for n in nodes if n.op is not None]
    segments = [u for u in units if u[0] is not None]
    if segments:
        _telemetry.bump("executor_remat_segments", len(segments))
    final = [(id(n), oi) for n, oi in heads] + \
        [(id(n), oi) for n, oi in aux_update_src.values()]

    def segment_plan(group):
        """(reads, writes): the values a segment takes from outside it and
        those of its own that something outside it, or the graph's end,
        takes."""
        inside = {id(n) for n in group}
        reads = dict.fromkeys((id(s), oi) for n in group
                              for s, oi in n.inputs if id(s) not in inside)
        taken = [(id(s), oi) for n in nodes if id(n) not in inside
                 for s, oi in n.inputs] + final
        writes = dict.fromkeys(k for k in taken if k[0] in inside)
        return list(reads), list(writes)

    plans = {stage: segment_plan(group) for stage, group in segments}

    def run_node(node, env, keys):
        ins = [env[(id(s), oi)] for s, oi in node.inputs]
        key = keys[rng_pos[id(node)]] if node.op.needs_rng else None
        fn = node.op.traceable(node.attrs, train_mode=train_mode, rng=key)
        # AttrScope(trace_scope="<name>") names a group of plain ops in
        # the device trace, as an op's own jax.named_scope names a kernel
        scope = node.attrs.get("__trace_scope__")
        if scope:
            with jax.named_scope(str(scope)):
                outs = fn(*ins)
        else:
            outs = fn(*ins)
        if not isinstance(outs, tuple):
            outs = (outs,)
        for i, o in enumerate(outs):
            env[(id(node), i)] = o

    def run_segment(stage, group, env, keys):
        """The segment's forward under ``jax.checkpoint``: what it reads
        from outside is saved, and of what it computes the values its ops
        hand to ``ops/remat.py:keep`` (a flash forward's ``out`` and
        ``lse``, a routing's indices); everything else inside is computed
        again in the backward pass.  A segment whose ops keep nothing
        compiles to the program it would without the policy."""
        reads, writes = plans[stage]

        def forward(vals, keys):
            local = dict(zip(reads, vals))
            with jax.named_scope("remat_segment_%s" % stage):
                for n in group:
                    run_node(n, local, keys)
            return tuple(local[k] for k in writes)

        with _remat.segment():
            outs = jax.checkpoint(forward, policy=_remat.POLICY)(
                tuple(env[k] for k in reads), keys)
        env.update(zip(writes, outs))

    def graph_fn(arg_vals, aux_vals, rng):
        env = {}
        for n in arg_nodes:
            env[(id(n), 0)] = arg_vals[arg_pos[id(n)]]
        for n in aux_nodes:
            env[(id(n), 0)] = aux_vals[aux_pos[id(n)]]
        keys = (jax.random.split(rng, len(rng_nodes))
                if rng_nodes else None)
        for stage, group in units:
            if stage is None:
                run_node(group[0], env, keys)
            else:
                run_segment(stage, group, env, keys)
        outputs = tuple(env[(id(n), oi)] for n, oi in heads)
        new_aux = tuple(
            env[(id(aux_update_src[id(n)][0]), aux_update_src[id(n)][1])]
            if id(n) in aux_update_src else env[(id(n), 0)]
            for n in aux_nodes)
        return outputs, new_aux

    return graph_fn, arg_nodes, aux_nodes


class Executor:
    """A bound computation graph (create via Symbol.bind / simple_bind)."""

    def __init__(self, symbol, ctx, arg_dict, grad_dict, grad_req, aux_dict,
                 group2ctx=None):
        self._symbol = symbol
        self._ctx = ctx if isinstance(ctx, Context) else Context(ctx)
        self.arg_names = symbol.list_arguments()
        self.aux_names = symbol.list_auxiliary_states()
        self.output_names = symbol.list_outputs()
        missing = [n for n in self.arg_names if n not in arg_dict]
        if missing:
            raise MXNetError("bind: missing arguments %s" % missing)
        self.arg_dict = {n: arg_dict[n] for n in self.arg_names}
        self.aux_dict = {n: aux_dict.get(n) for n in self.aux_names}
        for n in self.aux_names:
            if self.aux_dict[n] is None:
                raise MXNetError("bind: missing auxiliary state %s" % n)
        if isinstance(grad_req, str):
            grad_req = {n: grad_req for n in self.arg_names}
        elif isinstance(grad_req, (list, tuple)):
            grad_req = dict(zip(self.arg_names, grad_req))
        self.grad_req = {n: grad_req.get(n, "null") for n in self.arg_names}
        self.grad_dict = {n: (grad_dict or {}).get(n) for n in self.arg_names}
        for n, req in self.grad_req.items():
            if req != "null" and self.grad_dict[n] is None:
                self.grad_dict[n] = _LazyZeros(self.arg_dict[n].shape,
                                               self._ctx,
                                               self.arg_dict[n].dtype)
        self._grad_names = [n for n in self.arg_names
                            if self.grad_req[n] != "null"]

        fn_eval, self._arg_nodes, self._aux_nodes = _build_graph_fn(
            symbol, train_mode=False)
        fn_train, _, _ = _build_graph_fn(symbol, train_mode=True)
        # every jit product goes through the retrace watchdog: a bound
        # executor that keeps recompiling (shape-unstable feed) is exactly
        # the storm the telemetry layer exists to surface
        self._eval_jit = _telemetry.watch_jit(jax.jit(fn_eval),
                                              "executor_eval")
        self._train_fn = fn_train  # raw, for the debug (monitor/group) paths
        self._train_jit = _telemetry.watch_jit(jax.jit(fn_train),
                                               "executor_train")

        gpos = tuple(self.arg_names.index(n) for n in self._grad_names)
        self._gpos = gpos

        def _fwd_vjp(arg_vals, aux_vals, rng):
            def g(grad_vals):
                full = list(arg_vals)
                for p, v in zip(gpos, grad_vals):
                    full[p] = v
                return fn_train(full, aux_vals, rng)
            outs, vjp_fn, new_aux = jax.vjp(
                g, [arg_vals[p] for p in gpos], has_aux=True)
            return outs, new_aux, vjp_fn

        def _fwd_bwd(arg_vals, aux_vals, rng, ograds):
            outs, new_aux, vjp_fn = _fwd_vjp(arg_vals, aux_vals, rng)
            (in_grads,) = vjp_fn(tuple(ograds))
            return outs, new_aux, in_grads

        def _fwd_bwd_ones(arg_vals, aux_vals, rng):
            outs, new_aux, vjp_fn = _fwd_vjp(arg_vals, aux_vals, rng)
            (in_grads,) = vjp_fn(tuple(jnp.ones_like(o) for o in outs))
            return outs, new_aux, in_grads

        self._fwd_train_jit = _telemetry.watch_jit(
            jax.jit(_fwd_vjp), "executor_fwd_vjp")
        self._bwd_jit = _telemetry.watch_jit(
            jax.jit(lambda vjp_fn, og: vjp_fn(og)), "executor_bwd")
        self._fwd_bwd_jit = _telemetry.watch_jit(
            jax.jit(_fwd_bwd), "executor_fwd_bwd")
        self._fwd_bwd_ones_jit = _telemetry.watch_jit(
            jax.jit(_fwd_bwd_ones), "executor_fwd_bwd_ones")
        self._vjp = None
        self._vjp_jitted = False
        self._outputs = None
        self._monitor = None
        self._group2ctx = group2ctx

    # -- array views -------------------------------------------------------
    @property
    def arg_arrays(self):
        return [self.arg_dict[n] for n in self.arg_names]

    @property
    def grad_arrays(self):
        return [self.grad_dict[n] for n in self.arg_names]

    @property
    def aux_arrays(self):
        return [self.aux_dict[n] for n in self.aux_names]

    @property
    def outputs(self):
        if self._outputs is None:
            raise MXNetError("run forward() first")
        return self._outputs

    @property
    def output_dict(self):
        return dict(zip(self.output_names, self.outputs))

    # -- execution ---------------------------------------------------------
    def forward(self, is_train=False, **kwargs):
        for k, v in kwargs.items():
            if k not in self.arg_dict:
                raise MXNetError("unknown argument %s" % k)
            src = v._data if isinstance(v, NDArray) else jnp.asarray(v)
            self.arg_dict[k]._set_data(src.astype(self.arg_dict[k].dtype))
        arg_vals = [self._place(n, self.arg_dict[n]) for n in self.arg_names]
        aux_vals = [self._place(n, self.aux_dict[n]) for n in self.aux_names]
        rng = self._place_rng(_random.next_key())

        if self._group2ctx:
            # manual model parallelism (__ctx_group__ + group2ctx, ref
            # graph_executor.cc:403 PlaceDevice): run node-by-node with
            # per-group device placement; eager dispatch inserts the
            # cross-device copies the reference's _CrossDeviceCopy did
            outs, new_aux = self._forward_grouped(arg_vals, aux_vals, rng,
                                                  is_train)
            if is_train and self._grad_names:
                gpos = [self.arg_names.index(n) for n in self._grad_names]

                def f_grp(grad_vals):
                    full = list(arg_vals)
                    for p, v in zip(gpos, grad_vals):
                        full[p] = v
                    return self._train_jit(full, aux_vals, rng)

                _o, self._vjp, _na = jax.vjp(
                    f_grp, [arg_vals[p] for p in gpos], has_aux=True)
                self._vjp_jitted = False
        elif self._monitor is not None and \
                getattr(self._monitor, "is_active", lambda: True)():
            outs, new_aux = self._forward_monitored(arg_vals, aux_vals, rng,
                                                    is_train)
            if is_train and self._grad_names:
                # monitor path is observation-only; still set up the vjp so
                # backward() works (costs one extra forward, debug mode only)
                gpos = [self.arg_names.index(n) for n in self._grad_names]

                def f_mon(grad_vals):
                    full = list(arg_vals)
                    for p, v in zip(gpos, grad_vals):
                        full[p] = v
                    return self._train_jit(full, aux_vals, rng)

                _outs, self._vjp, _na = jax.vjp(
                    f_mon, [arg_vals[p] for p in gpos], has_aux=True)
                self._vjp_jitted = False
        elif is_train and self._grad_names:
            # hot path: ONE cached compiled program; the vjp residuals come
            # back as a Partial pytree and stay on device for _bwd_jit
            outs, new_aux, self._vjp = self._fwd_train_jit(
                arg_vals, aux_vals, rng)
            self._vjp_jitted = True
        elif is_train:
            outs, new_aux = self._train_jit(arg_vals, aux_vals, rng)
        else:
            outs, new_aux = self._eval_jit(arg_vals, aux_vals, rng)

        for n, v in zip(self.aux_names, new_aux):
            self.aux_dict[n]._set_data(v)
        self._outputs = [_wrap(o, self._ctx) for o in outs]
        return self._outputs

    def _place_rng(self, key):
        """Hook: the PRNG key where this executor's programs run (sharded
        executors re-place it on their mesh).  A key drawn from
        ``random.next_key()`` is uncommitted and follows the other
        arguments; the root key lent by ``random.lend_root_key()`` may be
        the output of a fused step bound elsewhere, committed there."""
        dev = self._ctx.jax_device
        if getattr(key, "committed", False) and key.devices() != {dev}:
            key = jax.device_put(key, dev)
        return key

    def cost_analysis(self):
        """Analytical XLA cost of THIS executor's programs, ahead of time.

        Lowers the bound inference and train-step programs from
        shape/dtype specs (no buffers touched, nothing executed, the
        global PRNG stream not consumed) and returns
        ``{"eval": {"flops", "bytes_accessed"}, "fwd_bwd": {...}}`` —
        the numbers the MFU gauges are built from, per bound executor
        instead of per process.  Entries are omitted where XLA reports
        no cost (e.g. an empty graph).
        """
        import jax
        from .telemetry import costs as _costs
        key = jax.random.PRNGKey(0)
        arg_specs = [jax.ShapeDtypeStruct(self.arg_dict[n].shape,
                                          self.arg_dict[n].dtype)
                     for n in self.arg_names]
        aux_specs = [jax.ShapeDtypeStruct(self.aux_dict[n].shape,
                                          self.aux_dict[n].dtype)
                     for n in self.aux_names]
        key_spec = jax.ShapeDtypeStruct(key.shape, key.dtype)
        out = {}
        programs = [("eval", self._eval_jit)]
        if self._grad_names:
            programs.append(("fwd_bwd", self._fwd_bwd_ones_jit))
        for label, watched in programs:
            try:
                cost = _costs.capture(
                    watched._fn, (arg_specs, aux_specs, key_spec), {},
                    force=True)
            except Exception:
                cost = None
            if cost is not None:
                out[label] = {"flops": cost[0], "bytes_accessed": cost[1]}
        return out

    def _place(self, name, arr):
        """Ensure the buffer is committed to this executor's device (cross-
        device inputs arrive when the user loads data on another context —
        reference engine would insert a CrossDeviceCopy node). Sharded
        executors override this per-name to spread batches over a mesh."""
        dev = self._ctx.jax_device
        data = arr._data
        arr_dev = getattr(data, "devices", lambda: {None})()
        if arr_dev != {dev}:
            data = jax.device_put(data, dev)
            arr._set_data(data)
        return data

    def _eager_walk(self, arg_vals, aux_vals, rng, is_train,
                    place_fn=None, observe_fn=None):
        """Node-by-node eager execution of the bound graph.

        Shared by the monitor path (observe_fn taps every output,
        ref ExecuteMonCallback graph_executor.cc:1380) and the group2ctx
        path (place_fn pins each node's compute to its __ctx_group__
        device, ref PlaceDevice graph_executor.cc:403). RNG keys follow
        the SAME split-by-rng-node-index scheme as the jitted graph_fn so
        stochastic ops agree between this walk and the vjp's replay.
        """
        from .symbol.symbol import _topo as topo
        nodes = topo(self._symbol._outputs)
        env = {}
        ai = {id(n): i for i, n in enumerate(self._arg_nodes)}
        xi = {id(n): i for i, n in enumerate(self._aux_nodes)}
        rng_nodes = [n for n in nodes if n.op is not None and n.op.needs_rng]
        rng_pos = {id(n): i for i, n in enumerate(rng_nodes)}
        keys = jax.random.split(rng, len(rng_nodes)) if rng_nodes else None

        for n in nodes:
            if n.op is None:
                val = arg_vals[ai[id(n)]] if id(n) in ai \
                    else aux_vals[xi[id(n)]]
                if place_fn is not None:
                    val = jax.device_put(val, place_fn(n))
                env[(id(n), 0)] = val
        aux_new = {id(n): None for n in self._aux_nodes}
        for node in nodes:
            if node.op is None:
                continue
            ins = [env[(id(s), oi)] for s, oi in node.inputs]
            if place_fn is not None:
                dev = place_fn(node)
                ins = [jax.device_put(v, dev) for v in ins]
            sub = keys[rng_pos[id(node)]] if node.op.needs_rng else None
            outs = node.op.traceable(node.attrs, train_mode=is_train,
                                     rng=sub)(*ins)
            outs = outs if isinstance(outs, tuple) else (outs,)
            for i, o in enumerate(outs):
                env[(id(node), i)] = o
                if observe_fn is not None:
                    observe_fn(node, i, o)
            for aux_in, oidx in (node.op.aux_updates or {}).items():
                if aux_in < len(node.inputs):
                    src, _ = node.inputs[aux_in]
                    if id(src) in aux_new:
                        aux_new[id(src)] = outs[oidx]
        outs = tuple(env[(id(n), oi)] for n, oi in self._symbol._outputs)
        new_aux = tuple(aux_new[id(n)] if aux_new[id(n)] is not None
                        else env[(id(n), 0)] for n in self._aux_nodes)
        return outs, new_aux

    def _forward_monitored(self, arg_vals, aux_vals, rng, is_train):
        """Monitor path: eager walk tapping every intermediate.

        THE SLOW PATH, by design: a compiled XLA program has no per-op
        boundaries, so an armed monitor abandons whole-program
        compilation for this batch and runs node by node.  Reserve it
        for per-activation ``pattern=`` taps; for per-parameter health
        (grad/weight norms, update ratios, loss) set
        ``MXNET_MODEL_STATS`` instead — the Monitor's compiled mode
        reads those out of the training program itself and
        ``is_active()`` keeps this walk dormant (mxnet_tpu/model_stats,
        docs/OBSERVABILITY.md §model-health)."""
        def observe(node, i, o):
            name = node.output_name(i) if i < node.num_outputs() \
                else "%s_aux%d" % (node.name, i)
            self._monitor(name, _wrap(o, self._ctx))
        return self._eager_walk(arg_vals, aux_vals, rng, is_train,
                                observe_fn=observe)

    def _forward_grouped(self, arg_vals, aux_vals, rng, is_train):
        """group2ctx path: eager walk with per-group device placement."""
        def place(node):
            group = (node.attrs or {}).get("__ctx_group__")
            ctx = self._group2ctx.get(group) if group else None
            return (ctx or self._ctx).jax_device
        return self._eager_walk(arg_vals, aux_vals, rng, is_train,
                                place_fn=place)

    def backward(self, out_grads=None, is_train=True):
        if self._vjp is None:
            if not self._grad_names:
                return  # nothing requires grad
            raise MXNetError("backward called before forward(is_train=True)")
        if out_grads is None:
            grads_in = tuple(jnp.ones_like(o._data) for o in self._outputs)
        else:
            if isinstance(out_grads, NDArray):
                out_grads = [out_grads]
            grads_in = tuple(
                g._data if isinstance(g, NDArray) else jnp.asarray(g)
                for g in out_grads)
        if self._vjp_jitted:
            (in_grads,) = self._bwd_jit(self._vjp, grads_in)
        else:
            (in_grads,) = self._vjp(grads_in)
        self._write_grads(in_grads)

    def release_grads(self):
        """Free the gradient buffers this executor allocated; they read
        as zeros again until a backward pass writes them."""
        for grad in self.grad_dict.values():
            if isinstance(grad, _LazyZeros):
                grad._buf = None

    def _write_grads(self, in_grads):
        for n, g in zip(self._grad_names, in_grads):
            dst = self.grad_dict[n]
            if self.grad_req[n] == "add":
                dst._set_data(dst._data + g.astype(dst.dtype))
            else:
                dst._set_data(g.astype(dst.dtype))

    def forward_backward(self, out_grads=None, **kwargs):
        """Forward + backward as ONE compiled XLA program (Module.fit hot
        path). Equivalent to ``forward(is_train=True)`` + ``backward()``
        but with no program boundary between the legs: XLA schedules the
        whole step, residual layouts never materialize at a program edge.
        Falls back to the two-call path under a monitor or group2ctx."""
        if self._group2ctx or (self._monitor is not None and getattr(
                self._monitor, "is_active", lambda: True)()):
            self.forward(is_train=True, **kwargs)
            self.backward(out_grads)
            return self._outputs
        if not self._grad_names:
            self.forward(is_train=True, **kwargs)
            return self._outputs
        for k, v in kwargs.items():
            if k not in self.arg_dict:
                raise MXNetError("unknown argument %s" % k)
            src = v._data if isinstance(v, NDArray) else jnp.asarray(v)
            self.arg_dict[k]._set_data(src.astype(self.arg_dict[k].dtype))
        arg_vals = [self._place(n, self.arg_dict[n]) for n in self.arg_names]
        aux_vals = [self._place(n, self.aux_dict[n]) for n in self.aux_names]
        rng = self._place_rng(_random.next_key())
        if out_grads is None:
            outs, new_aux, in_grads = self._fwd_bwd_ones_jit(
                arg_vals, aux_vals, rng)
        else:
            if isinstance(out_grads, NDArray):
                out_grads = [out_grads]
            ograds = tuple(
                g._data if isinstance(g, NDArray) else jnp.asarray(g)
                for g in out_grads)
            outs, new_aux, in_grads = self._fwd_bwd_jit(
                arg_vals, aux_vals, rng, ograds)
        for n, v in zip(self.aux_names, new_aux):
            self.aux_dict[n]._set_data(v)
        self._outputs = [_wrap(o, self._ctx) for o in outs]
        self._vjp = None  # grads already written; stale vjp must not linger
        self._write_grads(in_grads)
        return self._outputs

    # -- params ------------------------------------------------------------
    def copy_params_from(self, arg_params, aux_params=None,
                         allow_extra_params=False):
        for name, array in (arg_params or {}).items():
            if name in self.arg_dict:
                array.copyto(self.arg_dict[name])
            elif not allow_extra_params:
                raise MXNetError("Found name \"%s\" that is not in the "
                                 "arguments" % name)
        for name, array in (aux_params or {}).items():
            if name in self.aux_dict:
                array.copyto(self.aux_dict[name])
            elif not allow_extra_params:
                raise MXNetError("Found name \"%s\" that is not in the "
                                 "auxiliary states" % name)

    def reshape(self, partial_shaping=False, allow_up_sizing=False, **kwargs):
        """Return a new executor with new input shapes, sharing parameter
        values (reference executor.py reshape)."""
        new_shapes = {}
        for n in self.arg_names:
            new_shapes[n] = kwargs.get(n, self.arg_dict[n].shape)
        ex = Executor._simple_bind(self._symbol, self._ctx, self.grad_req,
                                   None, self._group2ctx,
                                   {n: kwargs[n] for n in kwargs})
        for n in ex.arg_names:
            if n not in kwargs and n in self.arg_dict and \
                    ex.arg_dict[n].shape == self.arg_dict[n].shape:
                self.arg_dict[n].copyto(ex.arg_dict[n])
        for n in ex.aux_names:
            if ex.aux_dict[n].shape == self.aux_dict[n].shape:
                self.aux_dict[n].copyto(ex.aux_dict[n])
        return ex

    def set_monitor_callback(self, callback, monitor_all=False):
        self._monitor = callback

    def debug_str(self):
        lines = ["Symbol outputs: %s" % self.output_names]
        for n in self.arg_names:
            lines.append("arg %s: %s %s" % (n, self.arg_dict[n].shape,
                                            self.grad_req[n]))
        for n in self.aux_names:
            lines.append("aux %s: %s" % (n, self.aux_dict[n].shape))
        return "\n".join(lines)

    # -- binding entry points ---------------------------------------------
    @staticmethod
    def _bind(symbol, ctx, args, args_grad, grad_req, aux_states, group2ctx):
        arg_names = symbol.list_arguments()
        aux_names = symbol.list_auxiliary_states()
        if isinstance(args, (list, tuple)):
            arg_dict = dict(zip(arg_names, args))
        else:
            arg_dict = dict(args)
        if isinstance(args_grad, (list, tuple)):
            grad_dict = dict(zip(arg_names, args_grad))
        else:
            grad_dict = dict(args_grad or {})
        if isinstance(aux_states, (list, tuple)):
            aux_dict = dict(zip(aux_names, aux_states))
        else:
            aux_dict = dict(aux_states or {})
        return Executor(symbol, ctx, arg_dict, grad_dict, grad_req, aux_dict,
                        group2ctx)

    @staticmethod
    def _simple_bind(symbol, ctx, grad_req, type_dict, group2ctx,
                     shape_kwargs):
        a, o, x = symbol._infer(shape_kwargs=shape_kwargs,
                                dtype_kwargs=type_dict)
        arg_names = symbol.list_arguments()
        aux_names = symbol.list_auxiliary_states()
        unknown = [n for n, s in zip(arg_names, a) if s is None]
        if unknown:
            raise MXNetError("simple_bind could not infer shapes for %s; "
                             "pass their shapes as kwargs" % unknown)
        ctx = ctx if isinstance(ctx, Context) else Context(ctx)
        arg_dict = {n: nd_zeros(tuple(s.shape), ctx=ctx, dtype=s.dtype)
                    for n, s in zip(arg_names, a)}
        aux_dict = {n: nd_zeros(tuple(s.shape), ctx=ctx, dtype=s.dtype)
                    for n, s in zip(aux_names, x)}
        return Executor(symbol, ctx, arg_dict, None, grad_req, aux_dict,
                        group2ctx)


def _profiled(method, label):
    """Wrap an Executor method with a program span (SURVEY §5.1: the
    reference stamps engine ops; here the unit of execution is the whole
    compiled program, so that's what gets a trace event).  Spans nest —
    a forward issued inside a ``trainer_step`` span records it as parent."""
    def wrapper(self, *args, **kwargs):
        with _telemetry.span(label, cat="program"):
            return method(self, *args, **kwargs)
    wrapper.__name__ = method.__name__
    wrapper.__doc__ = method.__doc__
    return wrapper


Executor.forward = _profiled(Executor.forward, "executor_forward")
Executor.backward = _profiled(Executor.backward, "executor_backward")
Executor.forward_backward = _profiled(Executor.forward_backward,
                                      "executor_forward_backward")


def _tracecheck_executor():
    """Specimen bound executor for graftcheck: a tiny two-layer MLP with
    grads on the weights (data stays grad_req null, like Module binds)."""
    from . import symbol as S
    data = S.var("data")
    net = S.FullyConnected(data, num_hidden=8, name="tc_fc1")
    net = S.relu(net)
    net = S.FullyConnected(net, num_hidden=4, name="tc_fc2")
    net = S.sum(net)
    grad_req = {"data": "null"}
    ex = net.simple_bind(Context("cpu"), grad_req=grad_req, data=(4, 16))
    return ex


def tracecheck_programs():
    """AOT specimens for graftcheck: every program a bound executor
    ships — eval, train, fwd_vjp (residuals out), bwd (residuals in),
    and both fused fwd+bwd forms (implicit ones-grads used by Module.fit,
    explicit out_grads used by ``forward_backward(out_grads=...)``).

    The bwd program's input is the vjp residual pytree; its avals come
    from ``jax.eval_shape`` over the fwd_vjp program — shape metadata
    only, nothing executed.
    """
    ex = _tracecheck_executor()
    key = _random.next_key()
    arg_specs = [jax.ShapeDtypeStruct(ex.arg_dict[n].shape,
                                      ex.arg_dict[n].dtype)
                 for n in ex.arg_names]
    aux_specs = [jax.ShapeDtypeStruct(ex.aux_dict[n].shape,
                                      ex.aux_dict[n].dtype)
                 for n in ex.aux_names]
    key_spec = jax.ShapeDtypeStruct(key.shape, key.dtype)
    fwd = (arg_specs, aux_specs, key_spec)
    outs_spec, _aux_spec, vjp_spec = jax.eval_shape(
        ex._fwd_train_jit._fn, *fwd)
    return [
        ("executor_eval", ex._eval_jit, fwd, {}),
        ("executor_train", ex._train_jit, fwd, {}),
        ("executor_fwd_vjp", ex._fwd_train_jit, fwd, {}),
        ("executor_bwd", ex._bwd_jit, (vjp_spec, tuple(outs_spec)), {}),
        ("executor_fwd_bwd_ones", ex._fwd_bwd_ones_jit, fwd, {}),
        ("executor_fwd_bwd", ex._fwd_bwd_jit,
         fwd + (tuple(outs_spec),), {}),
    ]
