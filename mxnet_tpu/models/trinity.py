"""AFMoE (Trinity): sliding-window and full attention mixed, gated
attention outputs, sandwich norms and a shared expert beside routed
sparse experts, as a ``Symbol`` for ``Module.fit``.

One layer, for hidden states x [B, S, H] (``docs/LM_OPS.md`` has the
equations and what is assumed beyond the published ``config.json``):

    x <- x + RMSNorm(Attn(RMSNorm(x))),  x <- x + RMSNorm(FFN(RMSNorm(x)))
    Attn:  q, k <- RMSNorm_per_head(h Wq, h Wk), RoPE on sliding layers only;
           o = CausalAttention(q, k, h Wv; window on sliding layers);
           (o * sigmoid(h Wg)) Wo  (Hq / Hkv heads of head_dim)
    FFN, the leading num_dense_layers:  (silu(h W1) * (h W3)) W2
    FFN, the others:  SwiGLU_shared(h) + SparseMoE: the top k of
           sigmoid(h Wr) + bias over all published experts, their
           normalised scores times route_scale times the held experts' SwiGLU

The input is the embedding times sqrt(H) (``mup_enabled``); after the
last layer RMSNorm, then the blocked head on an untied ``lm_head_weight``:
the graph's output is the mean next-token negative log-likelihood, shape
(1,).  Every layer is one recomputation segment (``force_mirroring``):
its backward computes the layer again, except the attention kernel's
``out`` and ``lse`` and the routing's indices, which the segment keeps
(``ops/remat.py``; 136 MB and under 2 MB a layer at 16,384 tokens).
"""
from __future__ import annotations

from .. import attribute
from .. import symbol as S
from .lm_blocks import (dense, heads, layer_kinds, layer_scope,
                        sparse_experts, swiglu, with_probes)

__all__ = ["afmoe_symbol", "AFMOE_TINY"]

# a toy of the same shape of graph, for CPU tests and the example: two
# periods of the 3 : 1 pattern, a window shorter than the toy sequences,
# 4 of 8 routed experts held from the third on, one shared expert
AFMOE_TINY = {
    "hidden_size": 32, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 8, "intermediate_size": 64, "moe_intermediate_size": 16,
    "num_hidden_layers": 8, "num_dense_layers": 2,
    "layer_types": ["sliding_attention", "sliding_attention",
                    "sliding_attention", "full_attention"] * 2,
    "sliding_window": 5, "num_experts": 4, "first_expert": 2,
    "published": {"num_experts": 8}, "num_experts_per_tok": 3,
    "num_shared_experts": 1, "route_norm": True, "route_scale": 2.826,
    "score_func": "sigmoid", "mup_enabled": True, "vocab_size": 50,
    "rms_norm_eps": 1e-5, "rope_theta": 10000, "head_block": 16,
    "dtype": "float32",
}

ROUTE_NORM_EPS = 1e-20      # the family adds it to the chosen scores' sum


def afmoe_symbol(cfg, recompute=True, probes=()):
    """``Symbol`` of the causal LM with its loss.  Token ids arrive as
    float32 ``data`` [B, S], next-token labels as ``softmax_label`` [B, S].
    *cfg* holds the published ``config.json`` keys (the layer kinds are the
    first ``num_hidden_layers`` of ``layer_types``), ``head_block`` and
    ``dtype``; ``num_experts`` counts the routed experts held here, from
    ``first_expert`` (0 if absent), and ``published.num_experts`` (the
    same if absent) is what the router scores.  *probes* names
    gradient-free further outputs, in order: ``layer<i>_op`` (the gated
    heads' output, before the output projection), ``layer<i>_ffn`` (the
    MLP's, or the shared plus the held routed experts', output) and
    ``layer<i>_choice`` (the expert ids each token chose)."""
    dtype = cfg["dtype"]
    hidden, d = cfg["hidden_size"], cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    width, held = cfg["moe_intermediate_size"], cfg["num_experts"]
    routed = cfg.get("published", {}).get("num_experts", held)
    taken = {}

    def norm(x, name):
        return S.RMSNorm(x, eps=eps, name=name)

    def attention(h, pre, kind):
        q = norm(heads(dense(h, hq * d, pre + "q"), hq, d), pre + "q_norm")
        k = norm(heads(dense(h, hkv * d, pre + "k"), hkv, d), pre + "k_norm")
        v = heads(dense(h, hkv * d, pre + "v"), hkv, d)
        if kind == "sliding_attention":
            q = S.contrib.RotaryEmbedding(q, base=theta)
            k = S.contrib.RotaryEmbedding(k, base=theta)
            o = S.contrib.CausalAttention(q, k, v, scale=d ** -0.5,
                                          window=cfg["sliding_window"],
                                          name=pre + "attention")
        elif kind == "full_attention":
            o = S.contrib.CausalAttention(q, k, v, scale=d ** -0.5,
                                          name=pre + "attention")
        else:
            raise ValueError("afmoe_symbol: layer type %r" % kind)
        gate = S.Activation(dense(h, hq * d, pre + "gate"),
                            act_type="sigmoid")
        return S.Reshape(o, shape=(0, 0, -1)) * gate

    def experts(h, pre):
        part, choice = sparse_experts(
            h, pre, dtype, hidden, width, routed, held,
            first_expert=cfg.get("first_expert", 0),
            num_experts_per_tok=cfg["num_experts_per_tok"],
            scoring=cfg["score_func"], norm_topk_prob=cfg["route_norm"],
            routed_scaling_factor=cfg["route_scale"],
            norm_topk_eps=ROUTE_NORM_EPS)
        with attribute.AttrScope(trace_scope="moe_shared"):
            shared = swiglu(h, cfg["num_shared_experts"] * width, hidden,
                            pre + "shared_")
        return shared + part, choice

    embed = S.Variable("embed_weight", shape=(cfg["vocab_size"], hidden),
                       dtype=dtype)
    x = S.Embedding(S.Variable("data"), weight=embed,
                    input_dim=cfg["vocab_size"], output_dim=hidden,
                    name="embed")
    if cfg["mup_enabled"]:
        x = x * hidden ** 0.5
    for i, kind in enumerate(layer_kinds(cfg, "afmoe_symbol")):
        pre = "layer%d_" % i
        with layer_scope(i, recompute):
            op = attention(norm(x, pre + "input_norm"), pre, kind)
            x = x + norm(dense(op, hidden, pre + "o"),
                         pre + "post_attention_norm")
            h = norm(x, pre + "pre_mlp_norm")
            if i < cfg["num_dense_layers"]:
                ffn = swiglu(h, cfg["intermediate_size"], hidden,
                             pre + "mlp_")
            else:
                ffn, taken[pre + "choice"] = experts(h, pre)
            x = x + norm(ffn, pre + "post_mlp_norm")
            taken[pre + "op"], taken[pre + "ffn"] = op, ffn
    x = norm(x, "final_norm")
    loss = S.contrib.BlockedSoftmaxCE(
        x, weight=S.Variable("lm_head_weight",
                             shape=(cfg["vocab_size"], hidden), dtype=dtype),
        label=S.Variable("softmax_label"), num_hidden=cfg["vocab_size"],
        block=cfg["head_block"], name="lm_head")
    return with_probes(loss, taken, probes, "afmoe_symbol")
