"""Operations and bytes the SmallThinker configuration requires, from its
shapes alone.  Multiply-accumulates ("macs") throughout; a FLOP count is
2 x macs.  Nothing here looks at how the program computes anything: a
sliding layer's score and value products are counted over the keys its
window shows, whatever grid visits them; a token's experts are its
``moe_num_active_primary_experts`` ReGLU MLPs at the share of the held
experts that uniform routing gives, whatever orders, groups or pads the
rows; and recomputation is never counted.
"""
from chipbench.flops_lfm2 import width


def layer_windows(cfg):
    """Whether each layer held here is a sliding one."""
    return [bool(w) for w in
            cfg["sliding_window_layout"][:cfg["num_hidden_layers"]]]


def experts(cfg):
    """(experts the router scores, experts held here)."""
    routed = cfg["moe_num_primary_experts"]
    return routed, cfg.get("held_experts", routed)


def attention_projection_macs_per_token(cfg):
    """q and o (hidden x heads x head size each), k and v (hidden x
    key/value heads x head size each)."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    return 2 * h * cfg["num_attention_heads"] * d + \
        2 * h * cfg["num_key_value_heads"] * d


def visible_keys(cfg, window):
    """(query, key) pairs one sequence of ``seq_len`` shows one head: every
    earlier key and the token itself in a full layer, the newest
    ``sliding_window_size`` of them in a sliding one."""
    s, w = cfg["seq_len"], cfg["sliding_window_size"]
    if not window or w >= s:
        return s * (s + 1) // 2
    return w * (w + 1) // 2 + (s - w) * w


def attention_core_macs_per_token(cfg, window):
    """q.k and p.v of one layer for one token, on average over a
    sequence of ``seq_len``, in every query head."""
    return 2 * cfg["num_attention_heads"] * cfg["head_dim"] * \
        visible_keys(cfg, window) / cfg["seq_len"]


def expert_macs(cfg):
    """One expert's three products for one token."""
    return 3 * cfg["hidden_size"] * cfg["moe_ffn_hidden_size"]


def routed_macs_per_token(cfg):
    """The router over every published expert and the token's chosen
    experts that are held here, at the share uniform routing gives."""
    routed, held = experts(cfg)
    return cfg["hidden_size"] * routed + \
        cfg["moe_num_active_primary_experts"] * expert_macs(cfg) * \
        held / routed


def forward_macs(cfg):
    """Forward multiply-accumulates a token (the configuration's
    ``flops`` function; harness: x 2 x 3 for a training step).  The head
    is over the vocabulary held here; the embedding is a lookup."""
    total = cfg["hidden_size"] * cfg["vocab_size"]
    for window in layer_windows(cfg):
        total += attention_projection_macs_per_token(cfg) + \
            attention_core_macs_per_token(cfg, window) + \
            routed_macs_per_token(cfg)
    return total


def parameters(cfg):
    """Trained parameters of the model as cut."""
    h = cfg["hidden_size"]
    routed, held = experts(cfg)
    layer = 2 * h + attention_projection_macs_per_token(cfg) + \
        h * routed + held * expert_macs(cfg)
    return 2 * cfg["vocab_size"] * h + h + \
        cfg["num_hidden_layers"] * layer


def expert_train_work(cfg, tokens):
    """(FLOPs, HBM bytes) a training step over *tokens* tokens requires of
    the expert layers: 3 x the forward's router and held experts' products
    at the expected rows; the held stacks read once in the forward and
    once in the backward and their gradient written once, and the held
    experts' rows read and written once a pass (hidden-wide, in and
    out)."""
    layers = cfg["num_hidden_layers"]
    routed, held = experts(cfg)
    rows = tokens * cfg["moe_num_active_primary_experts"] * held / routed
    flops = 3 * 2 * routed_macs_per_token(cfg) * tokens
    nbytes = (3 * held * expert_macs(cfg) + 4 * rows * cfg["hidden_size"]) \
        * width(cfg)
    return layers * flops, layers * nbytes


def _attention_work(cfg, tokens, window):
    layers = sum(w == window for w in layer_windows(cfg))
    flops = 3 * 2 * attention_core_macs_per_token(cfg, window) * tokens
    per_token = 2 * (cfg["num_attention_heads"] +
                     cfg["num_key_value_heads"]) * cfg["head_dim"] * \
        width(cfg)
    return layers * flops, layers * 3 * per_token * tokens


def attention_train_work(cfg, tokens):
    """(FLOPs, HBM bytes) a training step requires of the full layers'
    score and value products (the projections are plain products outside
    the op): 3 x the causal forward; q, k, v read and the output written
    once in the forward, and twice that in the backward."""
    return _attention_work(cfg, tokens, False)


def window_attention_train_work(cfg, tokens):
    """The same for the sliding layers: 3 x the banded forward."""
    return _attention_work(cfg, tokens, True)
