"""Operations and bytes the ``granitemoehybrid`` configuration (Granite
4.0-H without experts) requires, from its shapes alone.
Multiply-accumulates ("macs") throughout; a FLOP count is 2 x macs.
Nothing here looks at how the program computes anything: the scan is
counted in the chunked dual form at the published ``mamba_chunk_size``
whatever chunk a kernel takes, attention's score and value products by
the exact causal count at ``seq_len``, and recomputation is never
counted.
"""
from chipbench.flops_lfm2 import width


def kinds(cfg):
    return cfg["layer_types"][:cfg["num_hidden_layers"]]


def mamba_widths(cfg):
    """(inner width, channels the convolution sees, heads)."""
    inner = cfg["mamba_n_heads"] * cfg["mamba_d_head"]
    return inner, inner + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"], \
        cfg["mamba_n_heads"]


def mamba_projection_macs_per_token(cfg):
    """in_proj to [z | xBC | dt] and out_proj."""
    inner, conv_dim, heads = mamba_widths(cfg)
    return cfg["hidden_size"] * (inner + conv_dim + heads) + \
        inner * cfg["hidden_size"]


def conv_macs_per_token(cfg):
    return mamba_widths(cfg)[1] * cfg["mamba_d_conv"]


def scan_macs_per_token(cfg):
    """The scan of one layer for one token in chunks of Q =
    ``mamba_chunk_size``: inside a chunk a token meets (Q + 1) / 2 tokens
    on average, once in C . B (N a group) and once in the decayed product
    with x (P a head); the state's advance and its read are N x P a head
    each."""
    q, n = cfg["mamba_chunk_size"], cfg["mamba_d_state"]
    inner, _, heads = mamba_widths(cfg)
    return (q + 1) / 2 * (cfg["mamba_n_groups"] * n + inner) + \
        2 * n * cfg["mamba_d_head"] * heads


def attention_head_dim(cfg):
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def attention_projection_macs_per_token(cfg):
    h, d = cfg["hidden_size"], attention_head_dim(cfg)
    return 2 * h * cfg["num_attention_heads"] * d + \
        2 * h * cfg["num_key_value_heads"] * d


def attention_core_macs_per_token(cfg):
    """q.k and p.v of the causal layer for one token, on average over a
    sequence of ``seq_len`` (s (s + 1) / 2 visible pairs), in every query
    head."""
    s = cfg["seq_len"]
    return 2 * cfg["num_attention_heads"] * attention_head_dim(cfg) * \
        (s * (s + 1) // 2) / s


def mlp_macs_per_token(cfg):
    """The fused input matrix (two halves) and the output matrix."""
    return 3 * cfg["hidden_size"] * cfg["shared_intermediate_size"]


def granite_forward_macs(cfg):
    """Forward multiply-accumulates a token (the configuration's
    ``flops`` function; harness: x 2 x 3 for a training step).  The head
    is the tied embedding; the embedding itself is a lookup."""
    total = cfg["hidden_size"] * cfg["vocab_size"]
    for kind in kinds(cfg):
        total += mlp_macs_per_token(cfg)
        total += mamba_projection_macs_per_token(cfg) + \
            conv_macs_per_token(cfg) + scan_macs_per_token(cfg) \
            if kind == "mamba" else \
            attention_projection_macs_per_token(cfg) + \
            attention_core_macs_per_token(cfg)
    return total


def parameters(cfg):
    """Trained parameters of the model as cut: the tied embedding once."""
    h = cfg["hidden_size"]
    inner, conv_dim, heads = mamba_widths(cfg)
    total = cfg["vocab_size"] * h + h               # embedding, final norm
    for kind in kinds(cfg):
        total += 2 * h + mlp_macs_per_token(cfg)
        total += mamba_projection_macs_per_token(cfg) + \
            conv_dim * (cfg["mamba_d_conv"] + 1) + 3 * heads + inner \
            if kind == "mamba" else attention_projection_macs_per_token(cfg)
    return total


def state_space_train_work(cfg, tokens):
    """(FLOPs, HBM bytes) a training step over *tokens* tokens requires of
    the scans (the projections, the convolution and the gated norm are
    outside them): 3 x the forward's operations; x, B, C and dt (float32)
    read and y written once in the forward; those and dy read and dx, dB,
    dC and d dt written once in the backward."""
    layers = sum(kind == "mamba" for kind in kinds(cfg))
    inner = mamba_widths(cfg)[0]
    bc = 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    operand = (inner + bc) * width(cfg) + cfg["mamba_n_heads"] * 4
    y = inner * width(cfg)
    per_token = (operand + y) + (operand + 2 * y) + operand
    flops = 3 * 2 * scan_macs_per_token(cfg) * tokens
    return layers * flops, layers * per_token * tokens
