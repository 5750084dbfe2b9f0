"""Plain reference for ``smallthinker_21ba3b``: sliding-window layers with
rope beside NoPE full layers, and sparse ReGLU experts chosen by a router
that reads the attention's input (PowerInfer/SmallThinker-21BA3B-Instruct
``config.json``), written from the layer equations in the configuration
file.  Straight ``jax.numpy``: no kernel, no banded grid, no routing by
sorting, no grouped product, no code of ``mxnet_tpu``.  The only things
taken from the system under test are its seeded tensors, by name.

One ``dtype`` for everything between the token ids and the float32 loss,
except what the equations state in float32: the router's logits, the
choice of experts and their weights, the softmax of attention and the
log-softmax.  Weights arrive in the dtype the system holds them in and
are cast where they are used.

Attention is the masked softmax over blocks of queries against every
key, one key/value head at a time; the window is a mask on positions and
nothing else.  The experts are computed densely: every held expert on
every token of a block, times the token's weight for that expert (zero
unless it is among the token's top k).  The weights are the softmax over
the k chosen logits, written as such (the system takes the softmax over
all experts and divides by the chosen ones' sum).  Blocks, experts and
layers are under ``jax.checkpoint`` so that ``jax.grad`` fits beside the
system at 16,384 tokens; that changes what is kept, not what is computed.
"""
import jax
import jax.numpy as jnp

from chipbench.reference.brumby_14b_base import over_blocks, rms_norm, rope
from chipbench.reference.layers import Taker

TOKEN_BLOCK = 2048      # rows of the per-token maps
QUERY_BLOCK = 256       # queries of one block of the masked softmax

LAYER_TENSORS = ("input_norm_gamma", "q_weight", "k_weight", "v_weight",
                 "o_weight", "post_attention_norm_gamma", "router_weight",
                 "experts_w1_weight", "experts_w3_weight",
                 "experts_w2_weight")


def layer_kinds(cfg):
    """[(window, rope)] of the layers held here, from the first
    ``num_hidden_layers`` entries of the two published layouts."""
    n = cfg["num_hidden_layers"]
    return [(bool(w), bool(r)) for w, r in
            zip(cfg["sliding_window_layout"][:n], cfg["rope_layout"][:n])]


def attention(cfg, w, a, window, with_rope, dtype):
    """reshape(o) before the output projection, a [S, hidden]: o_t =
    softmax over the visible s of (q_t . k_s / sqrt(d)) v_s; visible is
    s <= t, and t - window < s <= t in a sliding layer; q and k carry the
    rotary embedding where the layer has rope."""
    hq, hkv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    cast = lambda name: w[name].astype(dtype)           # noqa: E731
    s = a.shape[0]

    def project(ab):
        rows = ab.shape[0]
        return ((ab @ cast("q_weight").T).reshape(rows, hq, d),
                (ab @ cast("k_weight").T).reshape(rows, hkv, d),
                (ab @ cast("v_weight").T).reshape(rows, hkv, d))

    q, k, v = over_blocks(project, TOKEN_BLOCK, a)
    if with_rope:
        q, k = rope(q, float(cfg["rope_theta"])), \
            rope(k, float(cfg["rope_theta"]))
    reach = cfg["sliding_window_size"] if window else s
    pos = jnp.arange(s)
    scale = jnp.asarray(d ** -0.5, dtype)

    def one_head(args):
        qh, kh, vh = args                   # [S, G, d], [S, d], [S, d]

        def block(qb, tb):
            score = (jnp.einsum("tgd,sd->gts", qb, kh) * scale) \
                .astype(jnp.float32)
            # the mask goes in by addition: the gradient of a sum keeps
            # nothing, where a select would keep its predicate
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
    return out.transpose(1, 0, 2, 3).reshape(s, hq * d)


def route(cfg, w, r):
    """(expert ids [S, k], weights [S, k] float32) of each token: the top
    k of the logits r Wr over every published expert, weighted by the
    softmax over those k logits.  Float32 whatever r is held in."""
    logits = r.astype(jnp.float32) @ w["router_weight"].astype(jnp.float32).T
    top, idx = jax.lax.top_k(logits, cfg["moe_num_active_primary_experts"])
    return idx, jax.nn.softmax(top, axis=-1)


def reglu(x, w1, w3, w2):
    return (jax.nn.relu(x @ w1) * (x @ w3)) @ w2


def experts(cfg, w, r, h, dtype, first=0):
    """(sum over the held experts e of weight_e (relu(h W1_e) * (h W3_e))
    W2_e, the chosen ids): the router reads r, the experts h [S, hidden];
    every held expert on every token, times the token's weight for it, a
    block of tokens at a time.  The stacks hold the experts ``first ..
    first + held - 1`` of those the router scores."""
    idx, weight = route(cfg, w, r)
    held = w["experts_w1_weight"].shape[0]
    table = jnp.sum(jax.nn.one_hot(idx - first, held, dtype=jnp.float32) *
                    weight[..., None], axis=1)          # [S, held]

    def block(hb, tb):
        @jax.checkpoint
        def one(total, xs):
            w1, w3, w2, share = xs
            y = reglu(hb, w1.astype(dtype), w3.astype(dtype),
                      w2.astype(dtype))
            return total + y * share[:, None].astype(dtype), None

        return jax.lax.scan(one, jnp.zeros_like(hb), (
            w["experts_w1_weight"], w["experts_w3_weight"],
            w["experts_w2_weight"], tb.T))[0]

    return over_blocks(block, TOKEN_BLOCK, h, table), idx


def layer(cfg, w, window, with_rope, x, dtype):
    """One layer on one sequence x [S, hidden]; *w* maps the layer's short
    tensor names to the system's tensors.  Returns (x, the heads' output
    before its projection, the experts' output, the chosen expert ids)."""
    eps = cfg["rms_norm_eps"]
    cast = lambda name: w[name].astype(dtype)           # noqa: E731
    a = rms_norm(x, cast("input_norm_gamma"), eps)
    op = attention(cfg, w, a, window, with_rope, dtype)
    out_weight = cast("o_weight")
    x = x + over_blocks(lambda ob: ob @ out_weight.T, TOKEN_BLOCK, op)
    h = rms_norm(x, cast("post_attention_norm_gamma"), eps)
    # the router reads the attention's normed input a (configuration:
    # assumed.router_input), the experts h
    ffn, choice = experts(cfg, w, a, h, dtype, cfg.get("first_expert", 0))
    return x + ffn, op, ffn, choice


def final_hidden(cfg, params, tokens, dtype, probes=()):
    """(hidden states after the last RMSNorm [B, S, hidden], the head's
    weight, {probe name: value} or None without probes); probes are named
    as ``smallthinker_symbol`` names them."""
    take = Taker(params)
    dtype = jnp.dtype(dtype)
    ids = tokens.astype(jnp.int32)
    x = jnp.take(take("embed_weight"), ids, axis=0).astype(dtype)
    seen = {}
    for i, (window, with_rope) in enumerate(layer_kinds(cfg)):
        w = {name: take("layer%d_%s" % (i, name)) for name in LAYER_TENSORS}
        step = jax.checkpoint(
            lambda xs, w, window=window, with_rope=with_rope: jax.vmap(
                lambda x1: layer(cfg, w, window, with_rope, x1, dtype))(xs))
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
