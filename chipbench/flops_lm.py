"""Operations and bytes a language-model configuration requires, from its
shapes alone.  Multiply-accumulates ("macs") throughout; a FLOP count is
2 x macs.  Nothing here looks at how the program computes anything: the
retention layer is counted in its minimal state form — phi(u) has
d (d + 1) / 2 distinct entries — whatever the kernel holds.
"""
import importlib


def matmul_macs_per_token(cfg):
    """Matrix products of the forward pass for one token: q, k, v, gate and
    o projections and the gated MLP of every layer, and the head over the
    vocabulary held here.  The embedding is a lookup."""
    hidden, d = cfg["hidden_size"], cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    layer = hidden * hq * d + 2 * hidden * hkv * d + hidden * hkv \
        + hq * d * hidden + 3 * hidden * cfg["intermediate_size"]
    return cfg["num_hidden_layers"] * layer + hidden * cfg["vocab_size"]


def retention_state_size(cfg):
    d = cfg["head_dim"]
    return d * (d + 1) // 2


def retention_macs_per_token(cfg):
    """One layer's power retention for one token, chunked state form with
    the configuration's chunk C: every query head reads the state
    (D x dv) and z (D), every key/value head adds phi(k) v^T and phi(k),
    phi is formed once for each q and k, and inside a chunk a token meets
    C / 2 others on average in the quadratic form (q.k and w.v)."""
    d = cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    size, chunk = retention_state_size(cfg), cfg["retention"]["chunk"]
    query = hq * size * d
    update = hkv * size * d
    within = hq * (chunk // 2) * 2 * d
    phi_and_z = (hq + hkv) * size * 2
    return query + update + within + phi_and_z


def brumby_forward_macs(cfg):
    """Forward multiply-accumulates a token (the configuration's
    ``flops`` function; harness: x 2 x 3 for a training step)."""
    return matmul_macs_per_token(cfg) + \
        cfg["num_hidden_layers"] * retention_macs_per_token(cfg)


def retention_forward_work(cfg, tokens):
    """(FLOPs, HBM bytes) the retention layers of one forward pass over
    *tokens* tokens of one sequence require: the operations above, and
    q, k, v and the log-gate read once and the output written once (the
    state of a chunked scan can stay on the chip)."""
    d = cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    layers = cfg["num_hidden_layers"]
    width = 2 if cfg["dtype"] == "bfloat16" else 4
    flops = 2 * retention_macs_per_token(cfg) * tokens * layers
    per_token = (2 * hq * d + 2 * hkv * d) * width + hkv * 4
    return flops, per_token * tokens * layers


def retention_train_work(cfg, tokens):
    """A training step: the forward and a backward of twice its cost.
    What recomputation repeats is not required work."""
    module, _, fn = cfg["retention_work"].partition(":")
    flops, nbytes = getattr(importlib.import_module(module), fn)(cfg, tokens)
    return 3 * flops, 3 * nbytes
