"""Graph pieces the language models share (``lfm2.py``, ``trinity.py``,
``granite.py`` and ``smallthinker.py``): bias-free projections, per-head
reshapes, the SwiGLU MLP with separate or one fused input matrix, stacked
expert tensors, the sparse-expert op with its variables, the
one-segment-a-layer recomputation scope and the probe outputs."""
from __future__ import annotations

from .. import attribute, initializer
from .. import symbol as S


def dense(x, width, name):
    return S.FullyConnected(x, num_hidden=width, flatten=False,
                            no_bias=True, name=name)


def heads(x, n, d):
    return S.Reshape(x, shape=(0, 0, n, d))


def swiglu(h, width, hidden, pre):
    act = dense(h, width, pre + "w1")
    act = act * S.Activation(act, act_type="sigmoid") * \
        dense(h, width, pre + "w3")
    return dense(act, hidden, pre + "w2")


def fused_swiglu(h, width, hidden, pre):
    """SwiGLU stored as one input matrix: [g | u] = h W_input, halves of
    *width* in that order; (silu(g) * u) W_output."""
    both = dense(h, 2 * width, pre + "input")
    gate = S.slice_axis(both, axis=-1, begin=0, end=width)
    up = S.slice_axis(both, axis=-1, begin=width, end=None)
    return dense(gate * S.Activation(gate, act_type="sigmoid") * up, hidden,
                 pre + "output")


def stacked(name, shape, dtype):
    # [experts, in, out]: Xavier draws each expert's matrix on its own
    return S.Variable(name, shape=shape, dtype=dtype, __stacked__=True)


def sparse_experts(h, pre, dtype, hidden, width, num_experts, held=None,
                   route=None, selection_bias=True, **attrs):
    """(the held experts' part of the layer's result, the chosen expert
    ids) of ``_contrib_SparseMoE`` on h: a router over *num_experts* that
    reads *route* (h, if None), the selection bias as a float32 auxiliary
    state drawn uniformly in +-0.1 unless not *selection_bias*, and *held*
    (all, if None) stacked experts of *width*; *attrs* go to the op
    (``act_type="relu"`` for ReGLU experts)."""
    held = num_experts if held is None else held
    inputs = dict(
        router_weight=S.Variable(pre + "router_weight",
                                 shape=(num_experts, hidden), dtype=dtype),
        w1_weight=stacked(pre + "experts_w1_weight", (held, hidden, width),
                          dtype),
        w3_weight=stacked(pre + "experts_w3_weight", (held, hidden, width),
                          dtype),
        w2_weight=stacked(pre + "experts_w2_weight", (held, width, hidden),
                          dtype))
    if route is not None:
        inputs["route_data"] = route
        attrs["route_apart"] = True
    if selection_bias:
        inputs["expert_bias"] = S.Variable(
            pre + "expert_bias", shape=(num_experts,), dtype="float32",
            init=initializer.Uniform(0.1))
    else:
        attrs["no_bias"] = True
    moe = S.contrib.SparseMoE(h, **inputs, num_experts=num_experts,
                              name=pre + "moe", **attrs)
    return moe[0], moe[1]


def layer_kinds(cfg, who):
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    if len(kinds) != cfg["num_hidden_layers"]:
        raise ValueError("%s: %d layer_types for %d layers"
                         % (who, len(kinds), cfg["num_hidden_layers"]))
    return kinds


def layer_scope(i, recompute):
    """One layer, one recomputation segment: the backward pass computes
    the layer again but for what its ops keep (``ops/remat.py``: the
    attention kernel's output and row statistic, the routing's
    indices, the state-space scan's output)."""
    return attribute.AttrScope(force_mirroring="True",
                               mirror_stage=str(i)) if recompute \
        else attribute.AttrScope()


def with_probes(loss, taken, probes, who):
    """The graph's output: the loss, then the named probes without a
    gradient, in order."""
    missing = [p for p in probes if p not in taken]
    if missing:
        raise ValueError("%s: no probe %s (there are %s)"
                         % (who, missing, sorted(taken)))
    return S.Group([loss] + [S.BlockGrad(taken[p], name=p + "_probe")
                             for p in probes]) if probes else loss
