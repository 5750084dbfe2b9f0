"""GraniteMoeHybrid (Granite 4.0-H) without experts: Mamba-2 state-space
mixers with one NoPE grouped-query attention layer among every ten, a
fused-input SwiGLU MLP and the family's four multipliers, as a ``Symbol``
for ``Module.fit``.

One layer, for hidden states x [B, S, H] (``docs/LM_OPS.md`` has the
equations and what is assumed beyond the published ``config.json``), r =
``residual_multiplier``:

    x <- x + r Op(RMSNorm(x)),  x <- x + r MLP(RMSNorm(x))
    Op, mamba layers:  [z | xBC | dt] = h W_in;
           xBC <- silu(conv_K(xBC) + b_conv);  [x' | B | C] = xBC;
           dt <- softplus(dt + dt_bias) in float32;
           y = StateSpaceScan(x', dt, A_log, B, C, D)   (heads of P, state N);
           RMSNorm(y * silu(z); w_norm) W_out            (float32 inside)
    Op, attention layers:  CausalAttention(h Wq, h Wk, h Wv;
           scale = attention_multiplier) Wo  (Hq / Hkv heads, no rope, no
           q/k norm)
    MLP:   [g | u] = h W_input;  (silu(g) * u) W_output

The input is the embedding times ``embedding_multiplier``; after the last
layer RMSNorm, the hidden state divided by ``logits_scaling``, then the
blocked head on the tied embedding: the graph's output is the mean
next-token negative log-likelihood, shape (1,).  Every layer is one
recomputation segment (``force_mirroring``): its backward computes the
layer again, except the scan's output (134 MB a layer at 16,384 tokens
and 64 heads of 64) and the attention kernel's ``out`` and ``lse``, which
the segment keeps (``ops/remat.py``).
"""
from __future__ import annotations

from .. import attribute, initializer
from .. import symbol as S
from .lm_blocks import (dense, fused_swiglu, heads, layer_kinds, layer_scope,
                        with_probes)

__all__ = ["granite_hybrid_symbol", "GRANITE_TINY"]

# a toy of the same shape of graph, for CPU tests and the example: one
# period of the published pattern, chunks of 8 so that a sequence of 40
# crosses several chunk boundaries
GRANITE_TINY = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "shared_intermediate_size": 96, "num_hidden_layers": 10,
    "layer_types": ["mamba"] * 5 + ["attention"] + ["mamba"] * 4,
    "mamba_n_heads": 4, "mamba_d_head": 16, "mamba_d_state": 16,
    "mamba_n_groups": 1, "mamba_d_conv": 4, "mamba_expand": 1,
    "mamba_chunk_size": 8, "mamba_conv_bias": True,
    "mamba_proj_bias": False, "attention_bias": False,
    "num_local_experts": 0, "position_embedding_type": "nope",
    "embedding_multiplier": 12, "residual_multiplier": 0.22,
    "attention_multiplier": 0.0625, "logits_scaling": 8,
    "tie_word_embeddings": True, "rms_norm_eps": 1e-5, "vocab_size": 96,
    "head_block": 16, "dtype": "float32",
}

# the scan's initial range (Mamba-2's, arXiv:2405.21060): a token's decay
# spans ~0.2 to ~0.999 over the heads, so state crosses chunk boundaries
A_RANGE = (1.0, 16.0)
DT_RANGE = (0.001, 0.1)


def granite_hybrid_symbol(cfg, recompute=True, probes=()):
    """``Symbol`` of the causal LM with its loss.  Token ids arrive as
    float32 ``data`` [B, S], next-token labels as ``softmax_label`` [B, S].
    *cfg* holds the published ``config.json`` keys (the layer kinds are the
    first ``num_hidden_layers`` of ``layer_types``), ``head_block`` and
    ``dtype``.  *probes* names gradient-free further outputs, in order:
    ``layer<i>_op`` (the sequence operator's output before its output
    projection: a mamba layer's gated and normed scan, an attention
    layer's heads) and ``layer<i>_ffn`` (the MLP's output)."""
    dtype = cfg["dtype"]
    hidden, eps = cfg["hidden_size"], cfg["rms_norm_eps"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    nh, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    groups, state = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    taps, inner = cfg["mamba_d_conv"], nh * p
    conv_dim = inner + 2 * groups * state
    branch = cfg["residual_multiplier"]
    if inner != cfg["mamba_expand"] * hidden:
        raise ValueError("granite_hybrid_symbol: %d heads of %d are not "
                         "mamba_expand x hidden_size" % (nh, p))
    for key, want in (("mamba_proj_bias", False), ("attention_bias", False),
                      ("mamba_conv_bias", True), ("num_local_experts", 0),
                      ("position_embedding_type", "nope"),
                      ("tie_word_embeddings", True)):
        if cfg.get(key, want) != want:
            raise ValueError("granite_hybrid_symbol: %s = %r is not "
                             "implemented (%r is)" % (key, cfg[key], want))
    taken = {}

    def sliced(x, begin, end):
        return S.slice_axis(x, axis=-1, begin=begin, end=end)

    def scan_parameter(name, init):
        return S.Variable(name, shape=(nh,), dtype="float32", init=init)

    def mamba(h, pre):
        proj = dense(h, inner + conv_dim + nh, pre + "in_proj")
        z = sliced(proj, 0, inner)
        xbc = S.contrib.CausalConv1D(
            proj, begin=inner, end=inner + conv_dim,
            weight=S.Variable(
                pre + "conv_weight", shape=(conv_dim, taps), dtype=dtype,
                init=initializer.Xavier(factor_type="in", magnitude=1)),
            bias=S.Variable(pre + "conv_bias", shape=(conv_dim,),
                            dtype=dtype,
                            init=initializer.Uniform(taps ** -0.5)),
            act_type="silu", name=pre + "conv")
        dt = S.Cast(sliced(proj, inner + conv_dim, None), dtype="float32")
        dt_bias = scan_parameter(
            pre + "dt_bias", initializer.StateSpaceInit("dt_bias", *DT_RANGE))
        dt = S.Activation(
            S.broadcast_add(dt, S.Reshape(dt_bias, shape=(1, 1, -1))),
            act_type="softrelu")
        y = S.contrib.StateSpaceScan(
            heads(sliced(xbc, 0, inner), nh, p), dt=dt,
            a_log=scan_parameter(
                pre + "a_log", initializer.StateSpaceInit("a_log", *A_RANGE)),
            b=heads(sliced(xbc, inner, inner + groups * state), groups,
                    state),
            c=heads(sliced(xbc, inner + groups * state, None), groups,
                    state),
            d=scan_parameter(pre + "d", initializer.One()),
            chunk=cfg["mamba_chunk_size"], name=pre + "scan")
        with attribute.AttrScope(trace_scope="gated_rms_norm"):
            gate = S.Cast(z, dtype="float32")
            gated = S.Cast(S.Reshape(y, shape=(0, 0, -1)),
                           dtype="float32") * \
                (gate * S.Activation(gate, act_type="sigmoid"))
            normed = S.RMSNorm(
                gated, gamma=S.Variable(pre + "mixer_norm_gamma",
                                        shape=(inner,), dtype=dtype),
                eps=eps, name=pre + "mixer_norm")
            return S.Cast(normed, dtype=dtype)

    def attention(h, pre):
        d = hidden // hq
        o = S.contrib.CausalAttention(
            heads(dense(h, hq * d, pre + "q"), hq, d),
            heads(dense(h, hkv * d, pre + "k"), hkv, d),
            heads(dense(h, hkv * d, pre + "v"), hkv, d),
            scale=cfg["attention_multiplier"], name=pre + "attention")
        return S.Reshape(o, shape=(0, 0, -1))

    embed = S.Variable("embed_weight", shape=(cfg["vocab_size"], hidden),
                       dtype=dtype)
    x = S.Embedding(S.Variable("data"), weight=embed,
                    input_dim=cfg["vocab_size"], output_dim=hidden,
                    name="embed") * cfg["embedding_multiplier"]
    for i, kind in enumerate(layer_kinds(cfg, "granite_hybrid_symbol")):
        pre = "layer%d_" % i
        with layer_scope(i, recompute):
            h = S.RMSNorm(x, eps=eps, name=pre + "input_norm")
            if kind == "mamba":
                op = mamba(h, pre)
                x = x + dense(op, hidden, pre + "out_proj") * branch
            elif kind == "attention":
                op = attention(h, pre)
                x = x + dense(op, hidden, pre + "o") * branch
            else:
                raise ValueError("granite_hybrid_symbol: layer type %r"
                                 % kind)
            ffn = fused_swiglu(S.RMSNorm(x, eps=eps, name=pre + "post_norm"),
                               cfg["shared_intermediate_size"], hidden,
                               pre + "mlp_")
            x = x + ffn * branch
            taken[pre + "op"], taken[pre + "ffn"] = op, ffn
    x = S.RMSNorm(x, eps=eps, name="final_norm") / cfg["logits_scaling"]
    loss = S.contrib.BlockedSoftmaxCE(
        x, weight=embed, label=S.Variable("softmax_label"),
        num_hidden=cfg["vocab_size"], block=cfg["head_block"],
        name="lm_head")
    return with_probes(loss, taken, probes, "granite_hybrid_symbol")
