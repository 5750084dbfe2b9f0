"""Per-op metadata for symbolic composition.

Reference analogue: NNVM's ``FListInputNames``/``FListOutputNames`` op
attributes plus the bidirectional ``InferShape`` functions each operator
registers (``src/operator/*-inl.h``).  Here forward shape flow is free
(jax.eval_shape); this module supplies the two things jax cannot derive:
(1) canonical input/aux names so ``sym.Convolution(data=d, ...)``
auto-creates ``conv0_weight``/``conv0_bias`` variables, and (2) data→param
shape inference so ``simple_bind`` can allocate parameters from the data
shape alone (the workhorse behind Module).
"""
from __future__ import annotations

import numpy as np
import jax

from ..base import dtype_np

__all__ = ["op_input_names", "infer_param_shapes", "HINTS"]

# name hints for auto-naming (reference: lowercase op name)
HINTS = {
    "FullyConnected": "fullyconnected", "Convolution": "convolution",
    "Deconvolution": "deconvolution", "BatchNorm": "batchnorm",
    "Pooling": "pooling", "Activation": "activation", "Dropout": "dropout",
    "SoftmaxOutput": "softmaxoutput", "Embedding": "embedding", "RNN": "rnn",
    "Concat": "concat", "Flatten": "flatten", "Reshape": "reshape",
    "LeakyReLU": "leakyrelu", "RMSNorm": "rmsnorm",
    "_contrib_PowerRetention": "powerretention",
    "_contrib_RotaryEmbedding": "rotaryembedding",
    "_contrib_BlockedSoftmaxCE": "blockedsoftmaxce",
    "_contrib_CausalAttention": "causalattention",
    "_contrib_ShortConv": "shortconv",
    "_contrib_CausalConv1D": "causalconv1d",
    "_contrib_StateSpaceScan": "statespacescan",
    "_contrib_SparseMoE": "sparsemoe",
    "elemwise_add": "_plus", "elemwise_sub": "_minus",
    "elemwise_mul": "_mul", "elemwise_div": "_div",
}


def _gates(mode):
    return {"rnn_relu": 1, "rnn_tanh": 1, "lstm": 4, "gru": 3}[mode]


def op_input_names(op, attrs):
    """Return (input_names, aux_names); aux_names are the trailing inputs."""
    name = op.name
    a = attrs
    if name in ("Convolution", "Convolution_v1", "Deconvolution"):
        base = ["data", "weight"]
        # reference defaults: Convolution no_bias=False, Deconvolution True
        if not a.get("no_bias", name == "Deconvolution"):
            base.append("bias")
        return base, []
    if name == "FullyConnected":
        return (["data", "weight"] if a.get("no_bias", False)
                else ["data", "weight", "bias"]), []
    if name in ("BatchNorm", "BatchNorm_v1", "CuDNNBatchNorm"):
        return ["data", "gamma", "beta"], ["moving_mean", "moving_var"]
    if name in ("InstanceNorm", "LayerNorm"):
        return ["data", "gamma", "beta"], []
    if name == "RMSNorm":
        return ["data", "gamma"], []
    if name == "_contrib_PowerRetention":
        return ["query", "key", "value", "log_gate"], []
    if name == "_contrib_BlockedSoftmaxCE":
        return ["data", "weight", "label"], []
    if name == "_contrib_CausalAttention":
        return ["query", "key", "value"], []
    if name == "_contrib_ShortConv":
        return ["data", "weight"], []
    if name == "_contrib_CausalConv1D":
        return (["data", "weight"] if a.get("no_bias", False)
                else ["data", "weight", "bias"]), []
    if name == "_contrib_StateSpaceScan":
        return ["data", "dt", "a_log", "b", "c", "d"], []
    if name == "_contrib_SparseMoE":
        return ["data", "router_weight", "w1_weight", "w3_weight",
                "w2_weight"] + (["route_data"] if a.get("route_apart")
                                else []), \
            [] if a.get("no_bias", False) else ["expert_bias"]
    if name == "Embedding":
        return ["data", "weight"], []
    if name == "RNN":
        ins = ["data", "parameters", "state"]
        if a.get("mode", "lstm") == "lstm":
            ins.append("state_cell")
        return ins, []
    if name == "LeakyReLU":
        if a.get("act_type", "leaky") == "prelu":
            return ["data", "gamma"], []
        return ["data"], []
    if name in ("SoftmaxOutput", "Softmax", "LinearRegressionOutput",
                "LogisticRegressionOutput", "MAERegressionOutput",
                "SVMOutput", "softmax_cross_entropy"):
        return ["data", "label"], []
    if name in ("dot", "batch_dot") or name.startswith("elemwise_") \
            or name.startswith("broadcast_") or name in (
                "_plus", "_minus", "_mul", "_div", "_grad_add", "_maximum",
                "_minimum", "_power", "_mod", "_hypot"):
        return ["lhs", "rhs"], []
    if name in ("Concat", "add_n", "stack", "elemwise_sum", "ElementWiseSum",
                "UpSampling"):
        n = int(a.get("num_args", a.get("num_args", 1)) or 1)
        return ["arg%d" % i for i in range(n)], []
    if name == "where":
        return ["condition", "x", "y"], []
    if name == "ROIPooling":
        return ["data", "rois"], []
    if name in ("take", "batch_take", "gather_nd", "scatter_nd"):
        return ["a", "indices"], []
    if name in ("SequenceMask", "SequenceLast", "SequenceReverse"):
        if a.get("use_sequence_length", False):
            return ["data", "sequence_length"], []
        return ["data"], []
    if name in ("SpatialTransformer",):
        return ["data", "loc"], []
    if name in ("BilinearSampler",):
        return ["data", "grid"], []
    if name in ("Crop",):
        n = int(a.get("num_args", 1))
        return ["data"] + (["crop_like"] if n > 1 else []), []
    return ["data"], []


def infer_param_shapes(node, in_structs):
    """Given a node whose data input shape is known, infer missing
    parameter/aux input shapes.  Returns list aligned to inputs or None."""
    op = node.op
    a = node.attrs
    name = op.name
    if not in_structs or in_structs[0] is None:
        return None
    data = in_structs[0]
    dshape = tuple(data.shape)
    dt = data.dtype
    S = lambda sh: jax.ShapeDtypeStruct(tuple(sh), dt)
    out = [None] * len(in_structs)

    if name in ("Convolution", "Convolution_v1"):
        k = tuple(a.get("kernel", ()))
        nf = int(a.get("num_filter", 1))
        g = int(a.get("num_group", 1))
        out[1] = S((nf, dshape[1] // g) + k)
        if len(in_structs) > 2:
            out[2] = S((nf,))
    elif name == "Deconvolution":
        k = tuple(a.get("kernel", ()))
        nf = int(a.get("num_filter", 1))
        g = int(a.get("num_group", 1))
        out[1] = S((dshape[1], nf // g) + k)
        if len(in_structs) > 2:
            out[2] = S((nf,))
    elif name == "FullyConnected":
        nh = int(a.get("num_hidden", 1))
        flat = a.get("flatten", True)
        in_dim = int(np.prod(dshape[1:])) if flat else dshape[-1]
        out[1] = S((nh, in_dim))
        if len(in_structs) > 2:
            out[2] = S((nh,))
    elif name in ("BatchNorm", "BatchNorm_v1", "CuDNNBatchNorm"):
        ax = int(a.get("axis", 1)) % len(dshape)
        c = dshape[ax]
        for i in range(1, len(in_structs)):
            out[i] = S((c,))
    elif name in ("InstanceNorm",):
        c = dshape[1]
        out[1] = S((c,))
        out[2] = S((c,))
    elif name == "LayerNorm":
        ax = int(a.get("axis", -1)) % len(dshape)
        c = dshape[ax]
        out[1] = S((c,))
        out[2] = S((c,))
    elif name == "RMSNorm":
        out[1] = S((dshape[int(a.get("axis", -1)) % len(dshape)],))
    elif name == "_contrib_ShortConv":
        out[1] = S((dshape[-1] // 3, int(a.get("kernel", 3))))
    elif name == "_contrib_CausalConv1D":
        end = a.get("end")
        channels = (dshape[-1] if end is None else int(end)) - \
            int(a.get("begin", 0))
        out[1] = S((channels, int(a.get("kernel", 4))))
        if len(in_structs) > 2:
            out[2] = S((channels,))
    elif name == "_contrib_BlockedSoftmaxCE":
        out[1] = S((int(a.get("num_hidden")), dshape[-1]))
        out[2] = jax.ShapeDtypeStruct(dshape[:-1], np.float32)
    elif name == "Embedding":
        out[1] = S((int(a.get("input_dim")), int(a.get("output_dim"))))
    elif name == "LeakyReLU" and a.get("act_type") == "prelu":
        out[1] = S((dshape[1],))
    elif name == "RNN":
        from ..ops.nn import rnn_param_size
        h = int(a.get("state_size"))
        L = int(a.get("num_layers", 1))
        bi = bool(a.get("bidirectional", False))
        d = 2 if bi else 1
        t, n, c = dshape
        out[1] = S((rnn_param_size(L, c, h, a.get("mode", "lstm"), bi),))
        out[2] = S((L * d, n, h))
        if len(in_structs) > 3:
            out[3] = S((L * d, n, h))
    elif name in ("SoftmaxOutput", "Softmax"):
        if a.get("multi_output", False):
            out[1] = S((dshape[0],) + dshape[2:])
        else:
            out[1] = S((dshape[0],))
    elif name in ("LinearRegressionOutput", "LogisticRegressionOutput",
                  "MAERegressionOutput"):
        out[1] = S(dshape)
    elif name == "SVMOutput":
        out[1] = S((dshape[0],))
    elif name == "softmax_cross_entropy":
        out[1] = S((dshape[0],))
    else:
        return None
    return out
