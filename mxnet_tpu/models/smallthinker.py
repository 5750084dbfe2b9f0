"""SmallThinker: a router that reads the attention's input, softmax top-k
of ReGLU experts with no shared expert and no selection bias, and
sliding-window layers with rope beside NoPE full layers, as a ``Symbol``
for ``Module.fit``.

One layer, for hidden states x [B, S, H] (``docs/LM_OPS.md`` has the
equations and what is assumed beyond the published ``config.json``):

    a = RMSNorm(x);  x <- x + Attn(a);  h = RMSNorm(x);  x <- x + MoE(a, h)
    Attn:  q, k, v = a Wq, a Wk, a Wv  (Hq / Hkv heads of head_dim, no
           per-head norm); rope on q and k where rope_layout is 1;
           o = CausalAttention(q, k, v; window where sliding_window_layout
           is 1, every earlier key elsewhere);  reshape(o) Wo
    MoE:   the top k of a Wr (the router reads a, not h); their weights the
           softmax over the k chosen logits; the held experts'
           (relu(h W1_e) * (h W3_e)) W2_e, weighted

The input is the embedding, unscaled; after the last layer RMSNorm, then
the blocked head on an untied ``lm_head_weight``: the graph's output is
the mean next-token negative log-likelihood, shape (1,).  Every layer is
one recomputation segment (``force_mirroring``): its backward computes the
layer again, except the attention kernel's ``out`` and ``lse`` and the
routing's indices, which the segment keeps (``ops/remat.py``).
"""
from __future__ import annotations

from .. import symbol as S
from .lm_blocks import dense, heads, layer_scope, sparse_experts, with_probes

__all__ = ["smallthinker_symbol", "SMALLTHINKER_TINY",
           "SMALLTHINKER_TINY_SHARE"]

# a toy of the same shape of graph, for CPU tests: two periods of the
# published layouts (a NoPE full layer, then three sliding ones with
# rope), a window shorter than the toy sequences, 7 query heads a
# key/value head, 3 of 8 experts a token, every expert held
SMALLTHINKER_TINY = {
    "hidden_size": 32, "num_attention_heads": 7, "num_key_value_heads": 1,
    "head_dim": 8, "moe_ffn_hidden_size": 16, "num_hidden_layers": 8,
    "sliding_window_layout": [0, 1, 1, 1] * 2, "rope_layout": [0, 1, 1, 1] * 2,
    "sliding_window_size": 5, "moe_num_primary_experts": 8,
    "moe_num_active_primary_experts": 3,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "vocab_size": 50, "rms_norm_eps": 1e-6, "rope_theta": 1500000,
    "head_block": 16, "dtype": "float32",
}

# the same toy holding experts 2-5 of the 8 its router scores
SMALLTHINKER_TINY_SHARE = dict(SMALLTHINKER_TINY, held_experts=4,
                               first_expert=2)


def layer_kinds(cfg):
    """[(window, rope)] of the layers held here: the first
    ``num_hidden_layers`` entries of the two published layouts."""
    n = cfg["num_hidden_layers"]
    window, rope = (cfg[k][:n] for k in ("sliding_window_layout",
                                         "rope_layout"))
    if len(window) != n or len(rope) != n:
        raise ValueError("smallthinker_symbol: layouts of %d and %d for %d "
                         "layers" % (len(window), len(rope), n))
    return [(bool(w), bool(r)) for w, r in zip(window, rope)]


def smallthinker_symbol(cfg, recompute=True, probes=()):
    """``Symbol`` of the causal LM with its loss.  Token ids arrive as
    float32 ``data`` [B, S], next-token labels as ``softmax_label`` [B, S].
    *cfg* holds the published ``config.json`` keys, ``head_block`` and
    ``dtype``; ``held_experts`` (all of ``moe_num_primary_experts`` if
    absent) counts the experts held here, from ``first_expert`` (0 if
    absent).  *probes* names gradient-free further outputs, in order:
    ``layer<i>_op`` (the heads' output, before the output projection),
    ``layer<i>_ffn`` (the held experts' part of the MoE) and
    ``layer<i>_choice`` (the expert ids each token chose)."""
    if not cfg["moe_primary_router_apply_softmax"]:
        raise ValueError("smallthinker_symbol: only the softmax router is "
                         "implemented")
    dtype = cfg["dtype"]
    hidden, d = cfg["hidden_size"], cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    experts = cfg["moe_num_primary_experts"]
    taken = {}

    def norm(x, name):
        return S.RMSNorm(x, eps=eps, name=name)

    def attention(a, pre, window, rope):
        q = heads(dense(a, hq * d, pre + "q"), hq, d)
        k = heads(dense(a, hkv * d, pre + "k"), hkv, d)
        v = heads(dense(a, hkv * d, pre + "v"), hkv, d)
        if rope:
            q = S.contrib.RotaryEmbedding(q, base=theta)
            k = S.contrib.RotaryEmbedding(k, base=theta)
        o = S.contrib.CausalAttention(
            q, k, v, scale=d ** -0.5, name=pre + "attention",
            **({"window": cfg["sliding_window_size"]} if window else {}))
        return S.Reshape(o, shape=(0, 0, -1))

    embed = S.Variable("embed_weight", shape=(cfg["vocab_size"], hidden),
                       dtype=dtype)
    x = S.Embedding(S.Variable("data"), weight=embed,
                    input_dim=cfg["vocab_size"], output_dim=hidden,
                    name="embed")
    for i, (window, rope) in enumerate(layer_kinds(cfg)):
        pre = "layer%d_" % i
        with layer_scope(i, recompute):
            a = norm(x, pre + "input_norm")
            op = attention(a, pre, window, rope)
            x = x + dense(op, hidden, pre + "o")
            h = norm(x, pre + "post_attention_norm")
            # softmax over all experts renormalised on the k chosen, with
            # no epsilon: the softmax over the k chosen logits
            ffn, taken[pre + "choice"] = sparse_experts(
                h, pre, dtype, hidden, cfg["moe_ffn_hidden_size"], experts,
                cfg.get("held_experts", experts), route=a,
                selection_bias=False, act_type="relu",
                first_expert=cfg.get("first_expert", 0),
                num_experts_per_tok=cfg["moe_num_active_primary_experts"],
                scoring="softmax", norm_topk_prob=cfg["norm_topk_prob"],
                norm_topk_eps=0.0)
            x = x + ffn
            taken[pre + "op"], taken[pre + "ffn"] = op, ffn
    x = norm(x, "final_norm")
    loss = S.contrib.BlockedSoftmaxCE(
        x, weight=S.Variable("lm_head_weight",
                             shape=(cfg["vocab_size"], hidden), dtype=dtype),
        label=S.Variable("softmax_label"), num_hidden=cfg["vocab_size"],
        block=cfg["head_block"], name="lm_head")
    return with_probes(loss, taken, probes, "smallthinker_symbol")
