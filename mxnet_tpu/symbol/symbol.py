"""Symbol: the declarative graph IR.

Parity surface: reference ``python/mxnet/symbol/symbol.py`` (composition,
``infer_shape`` :1515-area, ``simple_bind``/``bind`` :1251+, JSON save/load)
over NNVM's graph (``src/c_api/c_api_symbolic.cc``).

TPU-native redesign: a Symbol is a lightweight Python DAG over the same op
registry the eager path uses.  There are no NNVM passes — shape/dtype
inference is ``jax.eval_shape`` over each op's pure function (the compiler's
own abstract evaluation, so inference can never diverge from execution), and
"compilation" (bind) lowers the whole graph into one jitted XLA program in
``executor.py`` (replacing GraphExecutor's memory planner / op scheduler,
which XLA subsumes).

JSON graph format is reference-compatible (nodes/arg_nodes/heads with
stringified attrs) so reference ``-symbol.json`` checkpoints round-trip.
"""
from __future__ import annotations

import json

import numpy as np
import jax

from ..base import MXNetError, dtype_np
from ..ops.registry import (OP_REGISTRY, get_op, parse_attr_string,
                            attr_to_string)
from .. import name as _name_mod
from .. import attribute as _attr_mod
from .op_meta import op_input_names, infer_param_shapes, HINTS

__all__ = ["Symbol", "var", "Variable", "Group", "load", "load_json",
           "zeros", "ones", "arange"]


class SymNode:
    """One graph node: an op application or a variable (op=None)."""
    __slots__ = ("op", "name", "attrs", "inputs", "is_aux", "_num_outputs")

    def __init__(self, op, name, attrs, inputs, is_aux=False):
        self.op = op            # Op or None for variables
        self.name = name
        self.attrs = attrs      # python-typed attrs
        self.inputs = inputs    # list[(SymNode, out_idx)]
        self.is_aux = is_aux
        self._num_outputs = None

    def num_outputs(self):
        if self.op is None:
            return 1
        if self._num_outputs is None:
            self._num_outputs = self.op.n_visible_outputs(self.attrs)
        return self._num_outputs

    def output_name(self, idx):
        if self.op is None:
            return self.name
        if self.num_outputs() == 1:
            return self.name + "_output"
        return "%s_output%d" % (self.name, idx)


def _topo(heads):
    """Post-order DFS over the graph of the given head nodes."""
    order, seen = [], set()

    def visit(node):
        if id(node) in seen:
            return
        seen.add(id(node))
        for inp, _ in node.inputs:
            visit(inp)
        order.append(node)

    for node, _ in heads:
        visit(node)
    return order


class Symbol:
    """Immutable handle over one or more graph outputs."""
    __slots__ = ("_outputs",)

    def __init__(self, outputs):
        self._outputs = list(outputs)  # list[(SymNode, out_idx)]

    # -- construction ------------------------------------------------------
    @staticmethod
    def _from_op(op_name, input_syms, attrs, name=None):
        op = get_op(op_name)
        hint = HINTS.get(op_name, op_name.lower().replace("_", ""))
        name = _name_mod.current().get(name, hint)
        str_attrs = {k: v for k, v in attrs.items() if v is not None}
        # the enclosing AttrScope marks every op composed inside it,
        # operators included (reference: AttrScope.current.get(attr));
        # the call's own attributes win
        for k, v in _attr_mod.current().get(None).items():
            str_attrs.setdefault("__%s__" % k, v)
        inputs = []
        for s in input_syms:
            if len(s._outputs) != 1:
                raise MXNetError(
                    "cannot compose op %s with a multi-output symbol; "
                    "select one output first" % op_name)
            inputs.append(s._outputs[0])
        node = SymNode(op, name, str_attrs, inputs)
        n = node.num_outputs()
        return Symbol([(node, i) for i in range(n)])

    @property
    def name(self):
        if len(self._outputs) == 1:
            return self._outputs[0][0].name
        return None

    # -- listing -----------------------------------------------------------
    def _arg_nodes(self):
        return [n for n in _topo(self._outputs) if n.op is None and not n.is_aux]

    def _aux_nodes(self):
        return [n for n in _topo(self._outputs) if n.op is None and n.is_aux]

    def list_arguments(self):
        return [n.name for n in self._arg_nodes()]

    def list_auxiliary_states(self):
        return [n.name for n in self._aux_nodes()]

    def list_outputs(self):
        return [n.output_name(i) for n, i in self._outputs]

    def list_inputs(self):
        return self.list_arguments() + self.list_auxiliary_states()

    @property
    def num_outputs(self):
        return len(self._outputs)

    def __len__(self):
        return len(self._outputs)

    # -- selection ---------------------------------------------------------
    def __getitem__(self, index):
        if isinstance(index, str):
            matches = [i for i, (n, oi) in enumerate(self._outputs)
                       if n.output_name(oi) == index or n.name == index]
            if not matches:
                raise ValueError("no output named %r in %s"
                                 % (index, self.list_outputs()))
            index = matches[0]
        if isinstance(index, slice):
            return Symbol(self._outputs[index])
        return Symbol([self._outputs[index]])

    def get_internals(self):
        outs = []
        for node in _topo(self._outputs):
            if node.op is None:
                outs.append((node, 0))
            else:
                for i in range(node.num_outputs()):
                    outs.append((node, i))
        return Symbol(outs)

    def get_children(self):
        nodes = {id(n): n for n, _ in self._outputs}
        kids = []
        for n, _ in self._outputs:
            kids.extend(n.inputs)
        return Symbol(kids) if kids else None

    # -- attrs -------------------------------------------------------------
    def attr(self, key):
        node = self._outputs[0][0]
        v = node.attrs.get("__" + key + "__", node.attrs.get(key))
        return attr_to_string(v) if v is not None else None

    def list_attr(self):
        node = self._outputs[0][0]
        return {k.strip("_"): attr_to_string(v) for k, v in node.attrs.items()}

    def attr_dict(self):
        """Per-node attrs, keys as stored — special attrs KEEP their
        dunder form (``__init__``/``__lr_mult__``/...): that is what the
        initializer's variable-override and the optimizer's multiplier
        lookups key on (reference symbol.py attr_dict contract)."""
        out = {}
        for node in _topo(self._outputs):
            if node.attrs:
                out[node.name] = {k: attr_to_string(v)
                                  for k, v in node.attrs.items()}
        return out

    def _set_attr(self, **kwargs):
        for node, _ in self._outputs:
            node.attrs.update(kwargs)

    # -- arithmetic --------------------------------------------------------
    def _binary(self, op_name, scalar_op, other, reverse=False):
        if isinstance(other, Symbol):
            a, b = (other, self) if reverse else (self, other)
            return Symbol._from_op(op_name, [a, b], {})
        if isinstance(other, (int, float, np.generic)):
            return Symbol._from_op(scalar_op, [self], {"scalar": float(other)})
        raise TypeError("unsupported operand %r" % (type(other),))

    def __add__(self, o): return self._binary("elemwise_add", "_plus_scalar", o)
    def __radd__(self, o): return self._binary("elemwise_add", "_plus_scalar", o, True)
    def __sub__(self, o):
        return self._binary("elemwise_sub", "_minus_scalar", o)
    def __rsub__(self, o):
        if isinstance(o, Symbol):
            return o.__sub__(self)
        return Symbol._from_op("_rminus_scalar", [self], {"scalar": float(o)})
    def __mul__(self, o): return self._binary("elemwise_mul", "_mul_scalar", o)
    def __rmul__(self, o): return self._binary("elemwise_mul", "_mul_scalar", o, True)
    def __truediv__(self, o): return self._binary("elemwise_div", "_div_scalar", o)
    def __rtruediv__(self, o):
        if isinstance(o, Symbol):
            return o.__truediv__(self)
        return Symbol._from_op("_rdiv_scalar", [self], {"scalar": float(o)})
    __div__ = __truediv__
    __rdiv__ = __rtruediv__
    def __pow__(self, o): return self._binary("elemwise_power", "_power_scalar", o)
    def __neg__(self): return Symbol._from_op("negative", [self], {})
    def __eq__(self, o):
        if isinstance(o, (Symbol, int, float, np.generic)):
            return self._binary("_equal", "_equal_scalar", o)
        return NotImplemented
    def __ne__(self, o):
        if isinstance(o, (Symbol, int, float, np.generic)):
            return self._binary("_not_equal", "_not_equal_scalar", o)
        return NotImplemented
    def __gt__(self, o): return self._binary("_greater", "_greater_scalar", o)
    def __ge__(self, o): return self._binary("_greater_equal", "_greater_equal_scalar", o)
    def __lt__(self, o): return self._binary("_lesser", "_lesser_scalar", o)
    def __le__(self, o): return self._binary("_lesser_equal", "_lesser_equal_scalar", o)
    __hash__ = object.__hash__

    def __copy__(self):
        return Symbol(list(self._outputs))

    def __deepcopy__(self, memo):
        return load_json(self.tojson())

    # -- convenience methods mirroring NDArray ----------------------------
    def reshape(self, *shape, **kw):
        if len(shape) == 1 and isinstance(shape[0], (tuple, list)):
            shape = tuple(shape[0])
        shape = kw.get("shape", shape)
        return Symbol._from_op("Reshape", [self], {"shape": tuple(shape)})

    def astype(self, dtype):
        return Symbol._from_op("Cast", [self], {"dtype": str(dtype)})

    def sum(self, axis=None, keepdims=False):
        return Symbol._from_op("sum", [self], {"axis": axis, "keepdims": keepdims})

    def mean(self, axis=None, keepdims=False):
        return Symbol._from_op("mean", [self], {"axis": axis, "keepdims": keepdims})

    def transpose(self, axes=None):
        return Symbol._from_op("transpose", [self], {"axes": axes})

    def flatten(self):
        return Symbol._from_op("Flatten", [self], {})

    def slice_axis(self, axis, begin, end):
        return Symbol._from_op("slice_axis", [self],
                               {"axis": axis, "begin": begin, "end": end})

    def softmax(self, axis=-1):
        return Symbol._from_op("softmax", [self], {"axis": axis})

    def __repr__(self):
        outs = self.list_outputs()
        return "<Symbol %s>" % (self.name if len(outs) == 1 else outs)

    # -- inference ---------------------------------------------------------
    def _infer(self, shape_kwargs=None, dtype_kwargs=None, partial=False):
        """Joint shape+dtype inference via jax.eval_shape per node.

        Returns (arg_structs, out_structs, aux_structs) — each a list of
        jax.ShapeDtypeStruct or None (unknown).
        """
        shape_kwargs = dict(shape_kwargs or {})
        dtype_kwargs = dict(dtype_kwargs or {})
        nodes = _topo(self._outputs)
        vals = {}  # id(node) -> list[ShapeDtypeStruct|None]
        var_struct = {}

        def struct_of(node):
            shape = shape_kwargs.get(node.name)
            if shape is None and "__shape__" in node.attrs:
                shape = node.attrs["__shape__"]
            if isinstance(shape, (int, np.integer)):
                # files written before the 1-tuple stringify fix stored
                # "(64)" which parses back as a bare int
                shape = (int(shape),)
            dtype = dtype_kwargs.get(node.name)
            if dtype is None:
                dtype = node.attrs.get("__dtype__", np.float32)
            if shape is None:
                return None
            return jax.ShapeDtypeStruct(tuple(shape), dtype_np(dtype))

        for node in nodes:
            if node.op is None:
                s = struct_of(node)
                vals[id(node)] = [s]
                var_struct[id(node)] = s

        for node in nodes:
            if node.op is None:
                continue
            in_structs = [vals[id(n)][oi] for n, oi in node.inputs]
            if any(s is None for s in in_structs):
                # try param-shape inference from known inputs (simple_bind)
                inferred = infer_param_shapes(node, in_structs)
                if inferred is not None:
                    for pos, st in enumerate(inferred):
                        if st is not None and in_structs[pos] is None:
                            in_structs[pos] = st
                            src, soi = node.inputs[pos]
                            if src.op is None:
                                vals[id(src)][soi] = st
                                var_struct[id(src)] = st
            if any(s is None for s in in_structs):
                if partial:
                    vals[id(node)] = [None] * node.num_outputs()
                    continue
                missing = [node.inputs[i][0].name
                           for i, s in enumerate(in_structs) if s is None]
                raise MXNetError(
                    "cannot infer shape for inputs %s of node %s; provide "
                    "their shapes" % (missing, node.name))
            fn = node.op.traceable(node.attrs, train_mode=False,
                                   rng=_dummy_key())
            try:
                out = jax.eval_shape(lambda *a: fn(*a), *in_structs)
            except Exception as e:
                raise MXNetError(
                    "shape inference failed at node %s (op %s): %s"
                    % (node.name, node.op.name, e))
            out = list(out) if isinstance(out, (tuple, list)) else [out]
            vals[id(node)] = out[:node.num_outputs()] + out[node.num_outputs():]

        args = [var_struct.get(id(n)) for n in self._arg_nodes()]
        auxs = [var_struct.get(id(n)) for n in self._aux_nodes()]
        outs = []
        for n, oi in self._outputs:
            v = vals.get(id(n))
            outs.append(v[oi] if v else None)
        return args, outs, auxs

    def infer_shape(self, *args, **kwargs):
        if args:
            kwargs = dict(zip(self.list_arguments(), args), **kwargs)
        kwargs = {k: v for k, v in kwargs.items() if v is not None}
        a, o, x = self._infer(shape_kwargs=kwargs)
        if any(s is None for s in a + o + x):
            return None, None, None
        return ([tuple(s.shape) for s in a], [tuple(s.shape) for s in o],
                [tuple(s.shape) for s in x])

    def infer_shape_partial(self, *args, **kwargs):
        if args:
            kwargs = dict(zip(self.list_arguments(), args), **kwargs)
        kwargs = {k: v for k, v in kwargs.items() if v is not None}
        a, o, x = self._infer(shape_kwargs=kwargs, partial=True)
        f = lambda s: tuple(s.shape) if s is not None else None
        return [f(s) for s in a], [f(s) for s in o], [f(s) for s in x]

    def infer_type(self, *args, **kwargs):
        """Shape-free dtype propagation (reference: nnvm InferType pass).

        Forward-propagates known dtypes through homogeneous ops and
        back-fills unknown variable dtypes from their consumers (the rule
        that makes conv/fc weights inherit the data dtype).
        """
        if args:
            kwargs = dict(zip(self.list_arguments(), args), **kwargs)
        nodes = _topo(self._outputs)
        dt = {}  # id(node) -> np.dtype or None
        for n in nodes:
            if n.op is None:
                d = kwargs.get(n.name, n.attrs.get("__dtype__"))
                dt[id(n)] = np.dtype(d) if d is not None else None
        for _ in range(2):  # fwd then (after back-fill) fwd again
            for n in nodes:
                if n.op is None:
                    continue
                if "dtype" in n.attrs and n.attrs["dtype"] is not None:
                    dt[id(n)] = dtype_np(n.attrs["dtype"])
                    continue
                known = [dt.get(id(s)) for s, _ in n.inputs]
                known = [k for k in known if k is not None]
                dt[id(n)] = known[0] if known else dt.get(id(n))
            # back-fill: unknown var inputs inherit their consumer's dtype
            for n in nodes:
                if n.op is None or dt.get(id(n)) is None:
                    continue
                for s, _ in n.inputs:
                    if s.op is None and dt.get(id(s)) is None:
                        dt[id(s)] = dt[id(n)]

        f = lambda node: dt.get(id(node)) or np.dtype(np.float32)
        return ([f(n) for n in self._arg_nodes()],
                [f(n) for n, _ in self._outputs],
                [f(n) for n in self._aux_nodes()])

    # -- serialization -----------------------------------------------------
    def tojson(self):
        nodes = _topo(self._outputs)
        nid = {id(n): i for i, n in enumerate(nodes)}
        jnodes = []
        for n in nodes:
            jnodes.append({
                "op": "null" if n.op is None else n.op.name,
                "name": n.name,
                "attrs": {k: attr_to_string(v) for k, v in n.attrs.items()},
                "inputs": [[nid[id(s)], oi, 0] for s, oi in n.inputs],
            })
        graph = {
            "nodes": jnodes,
            "arg_nodes": [i for i, n in enumerate(nodes) if n.op is None],
            "heads": [[nid[id(n)], oi, 0] for n, oi in self._outputs],
            "attrs": {"mxnet_version": ["int", 1200],
                      "framework": ["str", "mxnet_tpu"]},
        }
        return json.dumps(graph, indent=2)

    def save(self, fname):
        from ..stream import open_stream
        with open_stream(fname, "w") as f:
            f.write(self.tojson())

    def debug_str(self):
        """Human-readable graph dump (reference ``Symbol.debug_str`` —
        one line per node in topological order with op, name, and input
        wiring; SURVEY §5.5 graph introspection)."""
        nodes = _topo(self._outputs)
        nid = {id(n): i for i, n in enumerate(nodes)}
        lines = ["Symbol Outputs:"]
        for pos, (n, oi) in enumerate(self._outputs):
            lines.append("\toutput[%d]=%s(%d)"
                         % (pos, n.output_name(oi), nid[id(n)]))
        for n in nodes:
            if n.op is None:
                lines.append("Variable:%s" % n.name)
                continue
            attrs = ", ".join("%s=%s" % (k, attr_to_string(v))
                              for k, v in sorted(n.attrs.items()))
            lines.append("--------------------")
            lines.append("Op:%s, Name=%s%s"
                         % (n.op.name, n.name,
                            (" {%s}" % attrs) if attrs else ""))
            for k, (s, oi) in enumerate(n.inputs):
                lines.append("\targ[%d]=%s(%d)"
                             % (k, s.output_name(oi), nid[id(s)]))
        return "\n".join(lines) + "\n"

    # -- binding (implemented in executor.py) ------------------------------
    def bind(self, ctx, args, args_grad=None, grad_req="write",
             aux_states=None, group2ctx=None, shared_exec=None):
        from ..executor import Executor
        return Executor._bind(self, ctx, args, args_grad, grad_req,
                              aux_states, group2ctx)

    def simple_bind(self, ctx, grad_req="write", type_dict=None,
                    stype_dict=None, group2ctx=None, shared_arg_names=None,
                    shared_exec=None, shared_buffer=None, **kwargs):
        from ..executor import Executor
        return Executor._simple_bind(self, ctx, grad_req, type_dict,
                                     group2ctx, kwargs)

    def eval(self, ctx=None, **kwargs):
        from ..context import current_context
        ex = self.bind(ctx or current_context(), kwargs)
        return ex.forward()

    # gradient symbol (reference Symbol.gradient is rarely used; omitted)


def var(name, attr=None, shape=None, lr_mult=None, wd_mult=None, dtype=None,
        init=None, stype=None, **kwargs):
    """Create a variable symbol (reference mx.sym.var / Variable)."""
    if not isinstance(name, str):
        raise TypeError("Expect a string for variable name")
    attrs = _attr_mod.current().get(attr)
    attrs = {k: v for k, v in (attrs or {}).items()}
    if shape is not None:
        attrs["__shape__"] = tuple(shape)
    if dtype is not None:
        attrs["__dtype__"] = str(np.dtype(dtype))
    if lr_mult is not None:
        attrs["__lr_mult__"] = lr_mult
    if wd_mult is not None:
        attrs["__wd_mult__"] = wd_mult
    if init is not None:
        from ..initializer import Initializer
        attrs["__init__"] = init.dumps() if isinstance(init, Initializer) else str(init)
    if stype is not None:
        attrs["__storage_type__"] = stype
    attrs.update({k: attr_to_string(v) for k, v in kwargs.items()})
    return Symbol([(SymNode(None, name, attrs, []), 0)])


Variable = var


def Group(symbols):
    outs = []
    for s in symbols:
        outs.extend(s._outputs)
    return Symbol(outs)


def load_json(json_str):
    graph = json.loads(json_str)
    graph = _upgrade_json(graph)
    nodes = []
    aux_hint = set()
    # first pass: find aux inputs by walking op input-name metadata
    for jn in graph["nodes"]:
        node = SymNode(None if jn["op"] == "null" else get_op(jn["op"]),
                       jn["name"],
                       {k: parse_attr_string(v)
                        for k, v in _node_attrs(jn).items()},
                       [])
        nodes.append(node)
    for jn, node in zip(graph["nodes"], nodes):
        node.inputs = [(nodes[i], oi) for i, oi, *_ in jn["inputs"]]
        if node.op is not None:
            _, aux_names = op_input_names(node.op, node.attrs)
            n_in = len(node.inputs)
            n_aux = len(aux_names)
            for (src, _), pos in zip(node.inputs, range(n_in)):
                if pos >= n_in - n_aux and src.op is None:
                    src.is_aux = True
    heads = [(nodes[i], oi) for i, oi, *_ in graph["heads"]]
    return Symbol(heads)


def _node_attrs(jn):
    """Node attr dict across JSON generations: modern ``attrs``, 0.9-era
    ``attr``, pre-0.9 ``param`` (reference legacy_json_util.cc upgrades the
    same progression in place)."""
    return jn.get("attrs") or jn.get("attr") or jn.get("param") or {}


def _upgrade_json(graph):
    """Upgrade legacy symbol JSON in place (reference
    src/nnvm/legacy_json_util.cc:1-200, UpgradeJSON_* chain).

    Handled: (a) node attrs under ``attr``/``param`` keys (rewritten to
    ``attrs``); (b) pre-0.9 graphs where op params lived on the *op node*
    but variable metadata (init/lr_mult) was stored flat — moved to
    ``__key__`` form; (c) dropped long-gone bookkeeping attrs the modern
    parser rejects (``ctx_group``-era keys are kept, unknown ``mojo``-era
    parse blockers are not fatal because attrs parse lazily here).
    """
    version = 0
    g_attrs = graph.get("attrs") or {}
    if isinstance(g_attrs.get("mxnet_version"), (list, tuple)) \
            and len(g_attrs["mxnet_version"]) == 2:
        version = int(g_attrs["mxnet_version"][1])
    for jn in graph.get("nodes", []):
        attrs = _node_attrs(jn)
        if jn.get("op") == "null":
            # legacy variable nodes store their metadata flat; the modern
            # node model namespaces it (__shape__/__dtype__/... is what
            # _infer and the optimizer multiplier lookups read)
            for key in ("init", "lr_mult", "wd_mult", "dtype", "shape"):
                if key in attrs:
                    attrs["__%s__" % key] = attrs.pop(key)
        elif version < 900:
            # pre-0.9: *variable* metadata could be stranded on the
            # consuming op node — namespace it out of the op's kwargs
            # (reference UpgradeJSON_FixParsing:56-86). dtype/shape stay:
            # on an op node those are real parameters (e.g. Cast(dtype)).
            for key in ("init", "lr_mult", "wd_mult"):
                if key in attrs:
                    attrs["__%s__" % key] = attrs.pop(key)
        jn.pop("param", None)
        jn.pop("attr", None)
        jn["attrs"] = attrs
    return graph


def load(fname):
    from ..stream import open_stream
    with open_stream(fname, "r") as f:
        return load_json(f.read())


def _dummy_key():
    return jax.random.PRNGKey(0)


# --- creation symbols -------------------------------------------------------
def zeros(shape, dtype=None, **kwargs):
    return Symbol._from_op("_zeros", [],
                           {"shape": shape, "dtype": str(dtype or "float32")})


def ones(shape, dtype=None, **kwargs):
    return Symbol._from_op("_ones", [],
                           {"shape": shape, "dtype": str(dtype or "float32")})


def arange(start, stop=None, step=1.0, repeat=1, dtype=None, **kwargs):
    return Symbol._from_op("_arange", [],
                           {"start": start, "stop": stop, "step": step,
                            "repeat": repeat, "dtype": str(dtype or "float32")})
