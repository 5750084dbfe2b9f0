"""LFM2-MoE: gated short convolutions and grouped-query attention over
sparse-expert MLPs, as a ``Symbol`` for ``Module.fit``.

One layer, for hidden states x [B, S, H] (``docs/LM_OPS.md`` has the
equations and what is assumed beyond the published ``config.json``):

    x <- x + Op(RMSNorm(x)),  x <- x + FFN(RMSNorm(x))
    Op, conv layers:       (Bg, Cg, u) = h W_in;  (Cg * conv3(Bg * u)) W_out
    Op, attention layers:  q, k <- RoPE(RMSNorm_per_head(h Wq, h Wk));
                           CausalAttention(q, k, h Wv) Wo  (Hq / Hkv heads)
    FFN, the leading num_dense_layers:  (silu(h W1) * (h W3)) W2
    FFN, the others:       SparseMoE: the top k of sigmoid(h Wr) + bias,
                           their normalised scores times the experts' SwiGLU

and after the last layer RMSNorm, then the blocked head on the tied
embedding: the graph's output is the mean next-token negative
log-likelihood, shape (1,).  Every layer is one recomputation segment
(``force_mirroring``): its backward computes the layer again, except the
attention kernel's ``out`` and ``lse`` and the routing's indices, which
the segment keeps (``ops/remat.py``); the experts' rows are replayed.
"""
from __future__ import annotations

from .. import initializer
from .. import symbol as S
from .lm_blocks import (dense, heads, layer_kinds, layer_scope,
                        sparse_experts, swiglu, with_probes)

__all__ = ["lfm2_moe_symbol", "LFM2_MOE_TINY"]

# a toy of the same shape of graph, for CPU tests and the example
LFM2_MOE_TINY = {
    "hidden_size": 32, "num_attention_heads": 4, "num_key_value_heads": 2,
    "intermediate_size": 64, "moe_intermediate_size": 24,
    "num_hidden_layers": 4, "num_dense_layers": 1,
    "layer_types": ["conv", "full_attention", "conv", "full_attention"],
    "num_experts": 8, "num_experts_per_tok": 2, "norm_topk_prob": True,
    "routed_scaling_factor": 1.0, "use_expert_bias": True,
    "conv_L_cache": 3, "conv_bias": False, "vocab_size": 50,
    "norm_eps": 1e-5, "rope_theta": 1000000, "head_block": 16,
    "dtype": "float32",
}


def lfm2_moe_symbol(cfg, recompute=True, probes=()):
    """``Symbol`` of the causal LM with its loss.  Token ids arrive as
    float32 ``data`` [B, S], next-token labels as ``softmax_label`` [B, S].
    *cfg* holds the published ``config.json`` keys (the layer kinds are the
    first ``num_hidden_layers`` of ``layer_types``), ``head_block`` and
    ``dtype``.  *probes* names gradient-free further outputs, in order:
    ``layer<i>_op`` (the sequence operator's output, before its output
    projection), ``layer<i>_ffn`` (the MLP's or the expert layer's
    output) and ``layer<i>_choice`` (the expert ids each token chose)."""
    dtype = cfg["dtype"]
    hidden = cfg["hidden_size"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = hidden // hq
    eps, theta = cfg["norm_eps"], cfg["rope_theta"]
    experts, width = cfg["num_experts"], cfg["moe_intermediate_size"]
    if cfg.get("conv_bias"):
        raise ValueError("lfm2_moe_symbol: conv_bias is not implemented")
    taken = {}

    def short_conv(h, pre):
        conv_weight = S.Variable(
            pre + "conv_weight", shape=(hidden, cfg["conv_L_cache"]),
            dtype=dtype,
            init=initializer.Xavier(factor_type="in", magnitude=1))
        return S.contrib.ShortConv(dense(h, 3 * hidden, pre + "conv_in"),
                                   weight=conv_weight, name=pre + "conv")

    def attention(h, pre):
        q = S.RMSNorm(heads(dense(h, hq * d, pre + "q"), hq, d), eps=eps,
                      name=pre + "q_norm")
        k = S.RMSNorm(heads(dense(h, hkv * d, pre + "k"), hkv, d), eps=eps,
                      name=pre + "k_norm")
        q = S.contrib.RotaryEmbedding(q, base=theta)
        k = S.contrib.RotaryEmbedding(k, base=theta)
        v = heads(dense(h, hkv * d, pre + "v"), hkv, d)
        o = S.contrib.CausalAttention(q, k, v, scale=d ** -0.5,
                                      name=pre + "attention")
        return S.Reshape(o, shape=(0, 0, -1))

    embed = S.Variable("embed_weight", shape=(cfg["vocab_size"], hidden),
                       dtype=dtype)
    x = S.Embedding(S.Variable("data"), weight=embed,
                    input_dim=cfg["vocab_size"], output_dim=hidden,
                    name="embed")
    for i, kind in enumerate(layer_kinds(cfg, "lfm2_moe_symbol")):
        pre = "layer%d_" % i
        with layer_scope(i, recompute):
            h = S.RMSNorm(x, eps=eps, name=pre + "op_norm")
            if kind == "conv":
                op = short_conv(h, pre)
                x = x + dense(op, hidden, pre + "conv_out")
            elif kind == "full_attention":
                op = attention(h, pre)
                x = x + dense(op, hidden, pre + "o")
            else:
                raise ValueError("lfm2_moe_symbol: layer type %r" % kind)
            h = S.RMSNorm(x, eps=eps, name=pre + "ffn_norm")
            if i < cfg["num_dense_layers"]:
                ffn = swiglu(h, cfg["intermediate_size"], hidden,
                             pre + "mlp_")
            else:
                ffn, taken[pre + "choice"] = sparse_experts(
                    h, pre, dtype, hidden, width, experts,
                    num_experts_per_tok=cfg["num_experts_per_tok"],
                    norm_topk_prob=cfg["norm_topk_prob"],
                    routed_scaling_factor=cfg["routed_scaling_factor"])
            x = x + ffn
            taken[pre + "op"], taken[pre + "ffn"] = op, ffn
    x = S.RMSNorm(x, eps=eps, name="final_norm")
    loss = S.contrib.BlockedSoftmaxCE(
        x, weight=embed, label=S.Variable("softmax_label"),
        num_hidden=cfg["vocab_size"], block=cfg["head_block"],
        name="lm_head")
    return with_probes(loss, taken, probes, "lfm2_moe_symbol")
