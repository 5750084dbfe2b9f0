"""Brumby: the Qwen3 block with softmax attention replaced by power
retention, as a ``Symbol`` for ``Module.fit``.

One layer, for hidden states x [B, S, H] (``docs/LM_OPS.md`` has the
equations and what is assumed beyond the published ``config.json``):

    h  = RMSNorm(x);  q, k, v = h Wq, h Wk, h Wv   (Hq / Hkv / Hkv heads)
    q, k <- RoPE(RMSNorm_per_head(q, k))
    a  = log sigmoid(h Wg + bg)                    (float32, one per kv head)
    x <- x + PowerRetention(q, k, v, a) Wo
    x <- x + (silu(h2 Wgate) * (h2 Wup)) Wdown,    h2 = RMSNorm(x)

and after the last layer RMSNorm, then the blocked head: the graph's
output is the mean next-token negative log-likelihood, shape (1,).  Every
layer is one recomputation segment (``force_mirroring``) that keeps the
retention op's output and nothing else: the op's backward remakes the
chunk states it reads (``ops/pallas_kernels.py:power_retention``), so the
segment's replay runs the projections again and no retention forward.
"""
from __future__ import annotations

from .. import attribute, initializer
from .. import symbol as S

__all__ = ["brumby_symbol", "BRUMBY_TINY"]

# a toy of the same shape of graph, for CPU tests and the example
BRUMBY_TINY = {
    "hidden_size": 32, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "intermediate_size": 64, "num_hidden_layers": 2,
    "vocab_size": 50, "rms_norm_eps": 1e-6, "rope_theta": 1000000,
    "retention": {"degree": 2, "chunk": 8, "eps": 1e-6,
                  "gate_bias_init": 3.5, "head_block": 16},
    "dtype": "float32",
}


def brumby_symbol(cfg, recompute=True, probe_layer=None):
    """``Symbol`` of the causal LM with its loss.  Token ids arrive as
    float32 ``data`` [B, S], next-token labels as ``softmax_label`` [B, S].
    *cfg* holds the published ``config.json`` keys, ``retention`` (degree,
    chunk, eps, gate_bias_init, head_block) and ``dtype``.  With
    *probe_layer* the output of that layer's retention op is a second,
    gradient-free output (for checks against a reference)."""
    dtype = cfg["dtype"]
    hidden, d = cfg["hidden_size"], cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    ret, eps = cfg["retention"], cfg["rms_norm_eps"]

    def dense(x, width, name, **kw):
        return S.FullyConnected(x, num_hidden=width, flatten=False,
                                no_bias="bias" not in kw, name=name, **kw)

    def heads(x, n):
        return S.Reshape(x, shape=(0, 0, n, d))

    embed = S.Variable("embed_weight", shape=(cfg["vocab_size"], hidden),
                       dtype=dtype)
    x = S.Embedding(S.Variable("data"), weight=embed,
                    input_dim=cfg["vocab_size"], output_dim=hidden,
                    name="embed")
    probe = None
    for i in range(cfg["num_hidden_layers"]):
        pre = "layer%d_" % i
        scope = attribute.AttrScope(force_mirroring="True",
                                    mirror_stage=str(i)) if recompute \
            else attribute.AttrScope()
        with scope:
            h = S.RMSNorm(x, eps=eps, name=pre + "input_norm")
            q = S.RMSNorm(heads(dense(h, hq * d, pre + "q"), hq), eps=eps,
                          name=pre + "q_norm")
            k = S.RMSNorm(heads(dense(h, hkv * d, pre + "k"), hkv), eps=eps,
                          name=pre + "k_norm")
            q = S.contrib.RotaryEmbedding(q, base=cfg["rope_theta"])
            k = S.contrib.RotaryEmbedding(k, base=cfg["rope_theta"])
            v = heads(dense(h, hkv * d, pre + "v"), hkv)
            gate_bias = S.Variable(
                pre + "gate_bias", shape=(hkv,), dtype=dtype,
                init=initializer.Constant(ret["gate_bias_init"]))
            gate = S.Cast(dense(h, hkv, pre + "gate", bias=gate_bias),
                          dtype="float32")
            # log sigmoid(g) = -softplus(-g)
            log_gate = -S.Activation(-gate, act_type="softrelu")
            o = S.contrib.PowerRetention(
                q, k, v, log_gate, degree=ret["degree"], chunk=ret["chunk"],
                eps=ret["eps"], name=pre + "retention")
            if i == probe_layer:
                probe = S.BlockGrad(o, name=pre + "retention_probe")
            x = x + dense(S.Reshape(o, shape=(0, 0, -1)), hidden, pre + "o")
            h = S.RMSNorm(x, eps=eps, name=pre + "post_norm")
            up = dense(h, cfg["intermediate_size"], pre + "mlp_up")
            act = dense(h, cfg["intermediate_size"], pre + "mlp_gate")
            act = act * S.Activation(act, act_type="sigmoid") * up
            x = x + dense(act, hidden, pre + "mlp_down")
    x = S.RMSNorm(x, eps=eps, name="final_norm")
    loss = S.contrib.BlockedSoftmaxCE(
        x, label=S.Variable("softmax_label"), num_hidden=cfg["vocab_size"],
        block=ret["head_block"], name="lm_head")
    return loss if probe is None else S.Group([loss, probe])
