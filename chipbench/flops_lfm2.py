"""Operations and bytes the ``lfm2_moe`` configuration requires, from its
shapes alone.  Multiply-accumulates ("macs") throughout; a FLOP count is
2 x macs.  Nothing here looks at how the program computes anything: a
token's experts are its ``num_experts_per_tok`` SwiGLU MLPs whatever
orders, groups or pads the rows, and recomputation is never counted.
"""


def kinds(cfg):
    """[(sequence operator, MLP kind)] of the layers held here."""
    types = cfg["layer_types"][:cfg["num_hidden_layers"]]
    return [(t, "dense" if i < cfg["num_dense_layers"] else "experts")
            for i, t in enumerate(types)]


def head_dim(cfg):
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def conv_macs_per_token(cfg):
    """Input projection to (Bg, Cg, u), the depthwise taps, output
    projection."""
    h = cfg["hidden_size"]
    return h * 3 * h + h * cfg["conv_L_cache"] + h * h


def attention_projection_macs_per_token(cfg):
    h, d = cfg["hidden_size"], head_dim(cfg)
    return 2 * h * cfg["num_attention_heads"] * d + \
        2 * h * cfg["num_key_value_heads"] * d


def attention_core_macs_per_token(cfg):
    """q.k and p.v of one causal layer for one token of a sequence of
    ``seq_len``: a token meets seq_len / 2 keys on average, in every
    query head, twice."""
    return cfg["num_attention_heads"] * head_dim(cfg) * cfg["seq_len"]


def dense_mlp_macs_per_token(cfg):
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def expert_macs_per_token(cfg):
    """The router over all experts and the token's own experts."""
    h = cfg["hidden_size"]
    return h * cfg["num_experts"] + cfg["num_experts_per_tok"] * 3 * h * \
        cfg["moe_intermediate_size"]


def lfm2_forward_macs(cfg):
    """Forward multiply-accumulates a token (the configuration's
    ``flops`` function; harness: x 2 x 3 for a training step).  The head
    is the tied embedding; the embedding itself is a lookup."""
    total = cfg["hidden_size"] * cfg["vocab_size"]
    for op, mlp in kinds(cfg):
        total += conv_macs_per_token(cfg) if op == "conv" else \
            attention_projection_macs_per_token(cfg) + \
            attention_core_macs_per_token(cfg)
        total += dense_mlp_macs_per_token(cfg) if mlp == "dense" else \
            expert_macs_per_token(cfg)
    return total


def parameters(cfg):
    """Trained parameters of the model as cut (the selection bias is a
    buffer and is not one)."""
    h, d = cfg["hidden_size"], head_dim(cfg)
    total = cfg["vocab_size"] * h + h
    for op, mlp in kinds(cfg):
        total += 2 * h + (conv_macs_per_token(cfg) if op == "conv" else
                          attention_projection_macs_per_token(cfg) + 2 * d)
        total += dense_mlp_macs_per_token(cfg) if mlp == "dense" else \
            h * cfg["num_experts"] + cfg["num_experts"] * 3 * h * \
            cfg["moe_intermediate_size"]
    return total


def width(cfg):
    return 2 if cfg["dtype"] == "bfloat16" else 4


def expert_train_work(cfg, tokens):
    """(FLOPs, HBM bytes) a training step over *tokens* tokens requires of
    the expert layers: 3 x the forward's router and routed SwiGLU
    products; the held expert weights read once in the forward and once in
    the backward and their gradient written once, and the routed rows
    read and written once a pass (hidden-wide, in and out)."""
    layers = sum(mlp == "experts" for _, mlp in kinds(cfg))
    rows = tokens * cfg["num_experts_per_tok"]
    stacks = cfg["num_experts"] * 3 * cfg["hidden_size"] * \
        cfg["moe_intermediate_size"]
    flops = 3 * 2 * expert_macs_per_token(cfg) * tokens
    nbytes = (3 * stacks + 4 * rows * cfg["hidden_size"]) * width(cfg)
    return layers * flops, layers * nbytes


def attention_train_work(cfg, tokens):
    """(FLOPs, HBM bytes) a training step requires of the attention
    layers' score and value products (the projections are plain matrix
    products outside the op): 3 x the causal forward; q, k, v read and
    the output written once in the forward, and twice that in the
    backward."""
    layers = sum(op == "full_attention" for op, _ in kinds(cfg))
    d = head_dim(cfg)
    flops = 3 * 2 * attention_core_macs_per_token(cfg) * tokens
    per_token = 2 * (cfg["num_attention_heads"] +
                     cfg["num_key_value_heads"]) * d * width(cfg)
    return layers * flops, layers * 3 * per_token * tokens
