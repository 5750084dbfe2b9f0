"""Operations and bytes the ``afmoe`` configuration (Trinity) requires,
from its shapes alone.  Multiply-accumulates ("macs") throughout; a FLOP
count is 2 x macs.  Nothing here looks at how the program computes
anything: a sliding layer's score and value products are counted over the
keys its window shows, whatever grid visits them; a token's routed
experts are those of its ``num_experts_per_tok`` choices that are held
here at the share uniform routing gives (``num_experts`` of
``published.num_experts``), whatever orders, groups or pads the rows; and
recomputation is never counted.
"""
from chipbench.flops_lfm2 import width


def kinds(cfg):
    """[(attention kind, MLP kind)] of the layers held here."""
    types = cfg["layer_types"][:cfg["num_hidden_layers"]]
    return [(t, "dense" if i < cfg["num_dense_layers"] else "experts")
            for i, t in enumerate(types)]


def routed_experts(cfg):
    """Experts the router scores (all the published ones)."""
    return cfg.get("published", {}).get("num_experts", cfg["num_experts"])


def attention_projection_macs_per_token(cfg):
    """q, the output gate and o (hidden x heads x head size each), k and
    v (hidden x key/value heads x head size each)."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    return 3 * h * cfg["num_attention_heads"] * d + \
        2 * h * cfg["num_key_value_heads"] * d


def visible_keys(cfg, kind):
    """(query, key) pairs one sequence of ``seq_len`` shows one head: every
    earlier key and the token itself in a full layer, the newest
    ``sliding_window`` of them in a sliding one."""
    s, w = cfg["seq_len"], cfg["sliding_window"]
    if kind == "full_attention" or w >= s:
        return s * (s + 1) // 2
    return w * (w + 1) // 2 + (s - w) * w


def attention_core_macs_per_token(cfg, kind):
    """q.k and p.v of one layer for one token, on average over a
    sequence of ``seq_len``, in every query head."""
    return 2 * cfg["num_attention_heads"] * cfg["head_dim"] * \
        visible_keys(cfg, kind) / cfg["seq_len"]


def dense_mlp_macs_per_token(cfg):
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def expert_macs(cfg):
    """One expert's three products for one token."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def routed_macs_per_token(cfg):
    """The router over every published expert and the token's chosen
    experts that are held here, at the share uniform routing gives."""
    return cfg["hidden_size"] * routed_experts(cfg) + \
        cfg["num_experts_per_tok"] * expert_macs(cfg) * \
        cfg["num_experts"] / routed_experts(cfg)


def afmoe_forward_macs(cfg):
    """Forward multiply-accumulates a token (the configuration's
    ``flops`` function; harness: x 2 x 3 for a training step).  The head
    is over the vocabulary held here; the embedding is a lookup."""
    total = cfg["hidden_size"] * cfg["vocab_size"]
    for kind, mlp in kinds(cfg):
        total += attention_projection_macs_per_token(cfg) + \
            attention_core_macs_per_token(cfg, kind)
        total += dense_mlp_macs_per_token(cfg) if mlp == "dense" else \
            routed_macs_per_token(cfg) + \
            cfg["num_shared_experts"] * expert_macs(cfg)
    return total


def parameters(cfg):
    """Trained parameters of the model as cut (the selection bias is a
    buffer and is not one)."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    total = 2 * cfg["vocab_size"] * h + h           # embedding, head, norm
    for _, mlp in kinds(cfg):
        total += 4 * h + 2 * d + attention_projection_macs_per_token(cfg)
        total += dense_mlp_macs_per_token(cfg) if mlp == "dense" else \
            h * routed_experts(cfg) + \
            (cfg["num_experts"] + cfg["num_shared_experts"]) * \
            expert_macs(cfg)
    return total


def expert_train_work(cfg, tokens):
    """(FLOPs, HBM bytes) a training step over *tokens* tokens requires of
    the routed expert layers (the shared expert is a plain MLP outside
    them): 3 x the forward's router and held experts' products at the
    expected rows; the held stacks read once in the forward and once in
    the backward and their gradient written once, and the held experts'
    rows read and written once a pass (hidden-wide, in and out)."""
    layers = sum(mlp == "experts" for _, mlp in kinds(cfg))
    rows = tokens * cfg["num_experts_per_tok"] * cfg["num_experts"] / \
        routed_experts(cfg)
    stacks = cfg["num_experts"] * expert_macs(cfg)
    flops = 3 * 2 * routed_macs_per_token(cfg) * tokens
    nbytes = (3 * stacks + 4 * rows * cfg["hidden_size"]) * width(cfg)
    return layers * flops, layers * nbytes


def _attention_work(cfg, tokens, kind):
    layers = sum(op == kind for op, _ in kinds(cfg))
    flops = 3 * 2 * attention_core_macs_per_token(cfg, kind) * tokens
    per_token = 2 * (cfg["num_attention_heads"] +
                     cfg["num_key_value_heads"]) * cfg["head_dim"] * \
        width(cfg)
    return layers * flops, layers * 3 * per_token * tokens


def attention_train_work(cfg, tokens):
    """(FLOPs, HBM bytes) a training step requires of the full layers'
    score and value products (the projections and the gate are plain
    products outside the op): 3 x the causal forward; q, k, v read and
    the output written once in the forward, and twice that in the
    backward."""
    return _attention_work(cfg, tokens, "full_attention")


def window_attention_train_work(cfg, tokens):
    """The same for the sliding layers: 3 x the banded forward."""
    return _attention_work(cfg, tokens, "sliding_attention")
