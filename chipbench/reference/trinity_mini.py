"""Plain reference for ``trinity_mini``: sliding-window and full softmax
attention with gated outputs, sandwich norms, and a shared expert beside
routed sparse experts (arcee-ai/Trinity-Mini ``config.json``,
``model_type`` ``afmoe``), written from the layer equations in the
configuration file.  Straight ``jax.numpy``: no kernel, no banded grid,
no routing by sorting, no grouped product, no code of ``mxnet_tpu``.  The
only things taken from the system under test are its seeded tensors, by
name.

One ``dtype`` for everything between the token ids and the float32 loss,
except what the equations state in float32: the router's scores, the
choice of experts and their weights, the softmax of attention and the
log-softmax.  Weights arrive in the dtype the system holds them in and
are cast where they are used.

Attention is the masked softmax over blocks of queries against every
key, one key/value head at a time; the window is a mask on positions and
nothing else.  The routed experts are computed densely: every held
expert on every token of a block, times the token's weight for that
expert (zero unless it is among the token's top k), routing over all
published experts.  Blocks, experts and layers are under
``jax.checkpoint`` so that ``jax.grad`` fits beside the system at 16,384
tokens; that changes what is kept, not what is computed.
"""
import jax
import jax.numpy as jnp

from chipbench.reference.brumby_14b_base import over_blocks, rms_norm, rope
from chipbench.reference.layers import Taker
from chipbench.reference.lfm2_8b_a1b import swiglu

TOKEN_BLOCK = 2048      # rows of the per-token maps
QUERY_BLOCK = 256       # queries of one block of the masked softmax
ROUTE_EPS = 1e-20       # added to the chosen scores' sum (configuration:
#                         assumed.route_norm_eps)

ATTENTION_TENSORS = ("q_weight", "k_weight", "v_weight", "gate_weight",
                     "q_norm_gamma", "k_norm_gamma", "o_weight")
DENSE_TENSORS = ("mlp_w1_weight", "mlp_w3_weight", "mlp_w2_weight")
EXPERT_TENSORS = ("router_weight", "expert_bias", "experts_w1_weight",
                  "experts_w3_weight", "experts_w2_weight",
                  "shared_w1_weight", "shared_w3_weight", "shared_w2_weight")
NORM_TENSORS = ("input_norm_gamma", "post_attention_norm_gamma",
                "pre_mlp_norm_gamma", "post_mlp_norm_gamma")


def layer_kinds(cfg):
    """[(attention kind, MLP kind)] of the layers held here."""
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    return [(kind, "dense" if i < cfg["num_dense_layers"] else "experts")
            for i, kind in enumerate(kinds)]


def layer_tensors(mlp):
    return NORM_TENSORS + ATTENTION_TENSORS + \
        (DENSE_TENSORS if mlp == "dense" else EXPERT_TENSORS)


def attention(cfg, w, h, kind, dtype):
    """reshape(o) * sigmoid(h Wg) before the output projection, h
    [S, hidden]: o_t = softmax over the visible s of (q_t . k_s /
    sqrt(d)) v_s; visible is s <= t in a full layer and t - window < s <=
    t in a sliding one, where q and k also carry the rotary embedding."""
    hq, hkv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    eps = cfg["rms_norm_eps"]
    cast = lambda name: w[name].astype(dtype)           # noqa: E731
    s = h.shape[0]

    def project(hb):
        rows = hb.shape[0]
        q = (hb @ cast("q_weight").T).reshape(rows, hq, d)
        k = (hb @ cast("k_weight").T).reshape(rows, hkv, d)
        v = (hb @ cast("v_weight").T).reshape(rows, hkv, d)
        gate = jax.nn.sigmoid(hb @ cast("gate_weight").T)
        return rms_norm(q, cast("q_norm_gamma"), eps), \
            rms_norm(k, cast("k_norm_gamma"), eps), v, gate

    q, k, v, gate = over_blocks(project, TOKEN_BLOCK, h)
    if kind == "sliding_attention":
        q, k = rope(q, float(cfg["rope_theta"])), \
            rope(k, float(cfg["rope_theta"]))
        reach = cfg["sliding_window"]
    elif kind == "full_attention":
        reach = s               # every earlier key
    else:
        raise ValueError("layer type %r" % (kind,))
    pos = jnp.arange(s)
    scale = jnp.asarray(d ** -0.5, dtype)

    def one_head(args):
        qh, kh, vh = args                   # [S, G, d], [S, d], [S, d]

        def block(qb, tb):
            score = (jnp.einsum("tgd,sd->gts", qb, kh) * scale) \
                .astype(jnp.float32)
            # the mask goes in by addition: the gradient of a sum keeps
            # nothing, where a select would keep its [G, T, S] predicate
            # for every block
            behind = tb[:, None] - pos[None, :]
            score = score + jnp.where((behind >= 0) & (behind < reach),
                                      0.0, -jnp.inf).astype(jnp.float32)
            p = jax.nn.softmax(score, axis=-1).astype(dtype)
            return jnp.einsum("gts,sd->tgd", p, vh)

        return over_blocks(block, QUERY_BLOCK, qh, pos)

    group = hq // hkv
    out = jax.lax.map(one_head, (
        q.reshape(s, hkv, group, d).transpose(1, 0, 2, 3),
        k.transpose(1, 0, 2), v.transpose(1, 0, 2)))    # [Hkv, S, G, d]
    return out.transpose(1, 0, 2, 3).reshape(s, hq * d) * gate


def route(cfg, w, h):
    """(expert ids [S, k], weights [S, k] float32) of each token: the top
    k of sigmoid(h Wr) + b over every published expert, the scores there
    (without b) over their sum + 1e-20, times route_scale.  Float32
    whatever h is held in."""
    scores = jax.nn.sigmoid(h.astype(jnp.float32) @
                            w["router_weight"].astype(jnp.float32).T)
    _, idx = jax.lax.top_k(
        scores + w["expert_bias"].astype(jnp.float32),
        cfg["num_experts_per_tok"])
    weight = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg["route_norm"]:
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) +
                           ROUTE_EPS)
    return idx, weight * cfg["route_scale"]


def routed(cfg, w, h, dtype, first=0):
    """(sum over the held experts e of weight_e (silu(h W1_e) * (h W3_e))
    W2_e, the chosen ids) for h [S, hidden]: every held expert on every
    token, times the token's weight for it, a block of tokens at a time.
    The stacks hold the experts ``first .. first + held - 1`` of those
    the router scores."""
    idx, weight = route(cfg, w, h)
    held = w["experts_w1_weight"].shape[0]
    table = jnp.sum(jax.nn.one_hot(idx - first, held, dtype=jnp.float32) *
                    weight[..., None], axis=1)          # [S, held]

    def block(hb, tb):
        @jax.checkpoint
        def one(total, xs):
            w1, w3, w2, share = xs
            y = swiglu(hb, w1.astype(dtype), w3.astype(dtype),
                       w2.astype(dtype))
            return total + y * share[:, None].astype(dtype), None

        return jax.lax.scan(one, jnp.zeros_like(hb), (
            w["experts_w1_weight"], w["experts_w3_weight"],
            w["experts_w2_weight"], tb.T))[0]

    return over_blocks(block, TOKEN_BLOCK, h, table), idx


def shared(w, h, dtype):
    cast = lambda name: w[name].astype(dtype)           # noqa: E731
    return over_blocks(
        lambda hb: swiglu(hb, cast("shared_w1_weight").T,
                          cast("shared_w3_weight").T,
                          cast("shared_w2_weight").T), TOKEN_BLOCK, h)


def layer(cfg, w, kind, mlp, x, dtype):
    """One layer on one sequence x [S, hidden]; *w* maps the layer's short
    tensor names to the system's tensors.  Returns (x, the gated heads'
    output before its projection, the MLP's output, the chosen expert ids
    or None)."""
    eps = cfg["rms_norm_eps"]
    cast = lambda name: w[name].astype(dtype)           # noqa: E731
    op = attention(cfg, w, rms_norm(x, cast("input_norm_gamma"), eps), kind,
                   dtype)
    out_weight = cast("o_weight")
    attn = over_blocks(lambda ob: ob @ out_weight.T, TOKEN_BLOCK, op)
    x = x + rms_norm(attn, cast("post_attention_norm_gamma"), eps)
    h = rms_norm(x, cast("pre_mlp_norm_gamma"), eps)
    if mlp == "dense":
        ffn = over_blocks(
            lambda hb: swiglu(hb, cast("mlp_w1_weight").T,
                              cast("mlp_w3_weight").T,
                              cast("mlp_w2_weight").T), TOKEN_BLOCK, h)
        choice = None
    else:
        part, choice = routed(cfg, w, h, dtype, cfg.get("first_expert", 0))
        ffn = shared(w, h, dtype) + part
    return x + rms_norm(ffn, cast("post_mlp_norm_gamma"), eps), op, ffn, \
        choice


def final_hidden(cfg, params, tokens, dtype, probes=()):
    """(hidden states after the last RMSNorm [B, S, hidden], the head's
    weight, {probe name: value} or None without probes); probes are named
    as ``afmoe_symbol`` names them."""
    take = Taker(params)
    dtype = jnp.dtype(dtype)
    ids = tokens.astype(jnp.int32)
    x = jnp.take(take("embed_weight"), ids, axis=0).astype(dtype)
    if cfg["mup_enabled"]:
        x = x * jnp.asarray(cfg["hidden_size"] ** 0.5, dtype)
    seen = {}
    for i, (kind, mlp) in enumerate(layer_kinds(cfg)):
        w = {name: take("layer%d_%s" % (i, name))
             for name in layer_tensors(mlp)}
        step = jax.checkpoint(lambda xs, w, kind=kind, mlp=mlp: jax.vmap(
            lambda x1: layer(cfg, w, kind, mlp, x1, dtype))(xs))
        x, op, ffn, choice = step(x, w)
        seen.update({"layer%d_op" % i: op, "layer%d_ffn" % i: ffn,
                     "layer%d_choice" % i: choice})
    x = rms_norm(x, take("final_norm_gamma").astype(dtype),
                 cfg["rms_norm_eps"])
    head = take("lm_head_weight").astype(dtype)
    take.assert_all_taken()
    return x, head, {name: seen[name] for name in probes or ()} or None


def logits(cfg, params, tokens, dtype, probes=()):
    """Float32 logits [B, S, vocabulary held] of the whole model on the
    untied head (the CPU tests' entry; the chip never holds them whole)."""
    hidden, head, seen = final_hidden(cfg, params, tokens, dtype, probes)
    return (hidden @ head.T).astype(jnp.float32), seen


def loss(cfg, params, tokens, labels, dtype, probes=()):
    """(mean next-token negative log-likelihood over the vocabulary held,
    float32 log-softmax of x W_head^T; {probe name: value})."""
    hidden, head, seen = final_hidden(cfg, params, tokens, dtype, probes)

    def nll(hb, yb):
        logp = jax.nn.log_softmax((hb @ head.T).astype(jnp.float32), axis=-1)
        return -jnp.take_along_axis(logp, yb[:, None], axis=-1)[:, 0]

    rows = hidden.reshape(-1, hidden.shape[-1])
    each = over_blocks(nll, TOKEN_BLOCK, rows,
                       labels.reshape(-1).astype(jnp.int32))
    return jnp.mean(each), seen
