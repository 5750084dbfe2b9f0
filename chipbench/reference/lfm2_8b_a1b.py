"""Plain reference for ``lfm2_8b_a1b``: gated short convolutions and
grouped-query softmax attention over dense and sparse-expert SwiGLU MLPs
(LiquidAI/LFM2-8B-A1B ``config.json``, ``model_type`` ``lfm2_moe``),
written from the layer equations in the configuration file.  Straight
``jax.numpy``: no kernel, no routing by sorting, no grouped product, no
code of ``mxnet_tpu``.  The only things taken from the system under test
are its seeded tensors, by name.

One ``dtype`` for everything between the token ids and the float32 loss,
except what the equations state in float32: the router's scores, the
choice of experts and their weights, the softmax of attention and the
log-softmax.  Weights arrive in the dtype the system holds them in and
are cast where they are used.

The expert layer is computed densely: every held expert on every token,
times the token's weight for that expert (zero unless it is among the
token's top k), one expert at a time.  Attention is the masked softmax
over blocks of queries, one key/value head at a time; the convolution is
three shifted products.  Each expert, block and layer is under
``jax.checkpoint`` so that ``jax.grad`` fits beside the system at 16,384
tokens; that changes what is kept, not what is computed.
"""
import jax
import jax.numpy as jnp

from chipbench.reference.brumby_14b_base import over_blocks, rms_norm, rope
from chipbench.reference.layers import Taker

TOKEN_BLOCK = 2048      # rows of the per-token maps
QUERY_BLOCK = 1024      # queries of one block of the masked softmax

CONV_TENSORS = ("conv_in_weight", "conv_weight", "conv_out_weight")
ATTENTION_TENSORS = ("q_weight", "k_weight", "v_weight", "q_norm_gamma",
                     "k_norm_gamma", "o_weight")
DENSE_TENSORS = ("mlp_w1_weight", "mlp_w3_weight", "mlp_w2_weight")
EXPERT_TENSORS = ("router_weight", "expert_bias", "experts_w1_weight",
                  "experts_w3_weight", "experts_w2_weight")
NORM_TENSORS = ("op_norm_gamma", "ffn_norm_gamma")


def layer_kinds(cfg):
    """[(sequence operator, MLP kind)] of the layers held here."""
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    return [(kind, "dense" if i < cfg["num_dense_layers"] else "experts")
            for i, kind in enumerate(kinds)]


def layer_tensors(kind, mlp):
    return NORM_TENSORS + \
        (CONV_TENSORS if kind == "conv" else ATTENTION_TENSORS) + \
        (DENSE_TENSORS if mlp == "dense" else EXPERT_TENSORS)


def short_conv(cfg, w, h, dtype):
    """(Cg * c) before the output projection, h [S, hidden]."""
    cast = lambda name: w[name].astype(dtype)           # noqa: E731
    taps = cfg["conv_L_cache"]
    bg, cg, u = jnp.split(
        over_blocks(lambda hb: hb @ cast("conv_in_weight").T, TOKEN_BLOCK,
                    h), 3, axis=-1)
    bu = bg * u
    kernel = cast("conv_weight")                        # [hidden, taps]
    c = sum(kernel[:, j] * jnp.pad(bu, [(taps - 1 - j, 0), (0, 0)])
            [:bu.shape[0]] for j in range(taps))
    return cg * c


def attention(cfg, w, h, dtype):
    """softmax_{s<=t}(q_t . k_s / sqrt(d)) v_s, h [S, hidden] ->
    [S, Hq * d] before the output projection."""
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // hq
    eps = cfg["norm_eps"]
    cast = lambda name: w[name].astype(dtype)           # noqa: E731
    s = h.shape[0]

    def project(hb):
        rows = hb.shape[0]
        q = (hb @ cast("q_weight").T).reshape(rows, hq, d)
        k = (hb @ cast("k_weight").T).reshape(rows, hkv, d)
        v = (hb @ cast("v_weight").T).reshape(rows, hkv, d)
        return rms_norm(q, cast("q_norm_gamma"), eps), \
            rms_norm(k, cast("k_norm_gamma"), eps), v

    q, k, v = over_blocks(project, TOKEN_BLOCK, h)
    q, k = rope(q, float(cfg["rope_theta"])), rope(k, float(cfg["rope_theta"]))
    pos = jnp.arange(s)
    scale = jnp.asarray(d ** -0.5, dtype)

    def one_head(args):
        qh, kh, vh = args                   # [S, G, d], [S, d], [S, d]

        def block(qb, tb):
            score = (jnp.einsum("tgd,sd->gts", qb, kh) * scale) \
                .astype(jnp.float32)
            score = jnp.where(tb[:, None] >= pos[None, :], score, -jnp.inf)
            p = jax.nn.softmax(score, axis=-1).astype(dtype)
            return jnp.einsum("gts,sd->tgd", p, vh)

        return over_blocks(block, QUERY_BLOCK, qh, pos)

    group = hq // hkv
    out = jax.lax.map(one_head, (
        q.reshape(s, hkv, group, d).transpose(1, 0, 2, 3),
        k.transpose(1, 0, 2), v.transpose(1, 0, 2)))    # [Hkv, S, G, d]
    return out.transpose(1, 0, 2, 3).reshape(s, hq * d)


def swiglu(x, w1, w3, w2):
    return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2


def route(cfg, w, h):
    """(expert ids [S, k], weights [S, k] float32) of each token: the top
    k of sigmoid(h Wr) + b, the scores there (without b) over their sum +
    1e-6, times the scaling factor.  Float32 whatever h is held in."""
    scores = jax.nn.sigmoid(h.astype(jnp.float32) @
                            w["router_weight"].astype(jnp.float32).T)
    _, idx = jax.lax.top_k(
        scores + w["expert_bias"].astype(jnp.float32),
        cfg["num_experts_per_tok"])
    weight = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-6)
    return idx, weight * cfg["routed_scaling_factor"]


def experts(cfg, w, h, dtype, first=0):
    """(sum over the held experts e of weight_e (silu(h W1_e) * (h W3_e))
    W2_e, the chosen ids) for h [S, hidden]: every held expert on every
    token, times the token's weight for it.  The stacks hold the experts
    ``first .. first + held - 1`` of ``num_experts``."""
    idx, weight = route(cfg, w, h)
    held = w["experts_w1_weight"].shape[0]
    table = jnp.sum(jax.nn.one_hot(idx - first, held, dtype=jnp.float32) *
                    weight[..., None], axis=1)          # [S, held]

    @jax.checkpoint
    def one(total, xs):
        w1, w3, w2, share = xs
        y = swiglu(h, w1.astype(dtype), w3.astype(dtype), w2.astype(dtype))
        return total + y * share[:, None].astype(dtype), None

    total, _ = jax.lax.scan(
        one, jnp.zeros_like(h),
        (w["experts_w1_weight"], w["experts_w3_weight"],
         w["experts_w2_weight"], table.T))
    return total, idx


def layer(cfg, w, kind, mlp, x, dtype):
    """One layer on one sequence x [S, hidden]; *w* maps the layer's short
    tensor names to the system's tensors.  Returns (x, the operator's
    output before its projection, the MLP's output, the chosen expert ids
    or None)."""
    eps = cfg["norm_eps"]
    cast = lambda name: w[name].astype(dtype)           # noqa: E731
    h = rms_norm(x, cast("op_norm_gamma"), eps)
    if kind == "conv":
        op = short_conv(cfg, w, h, dtype)
        out_weight = cast("conv_out_weight")
    elif kind == "full_attention":
        op = attention(cfg, w, h, dtype)
        out_weight = cast("o_weight")
    else:
        raise ValueError("layer type %r" % (kind,))
    x = x + over_blocks(lambda ob: ob @ out_weight.T, TOKEN_BLOCK, op)
    h = rms_norm(x, cast("ffn_norm_gamma"), eps)
    if mlp == "dense":
        ffn = over_blocks(
            lambda hb: swiglu(hb, cast("mlp_w1_weight").T,
                              cast("mlp_w3_weight").T,
                              cast("mlp_w2_weight").T), TOKEN_BLOCK, h)
        choice = None
    else:
        ffn, choice = experts(cfg, w, h, dtype)
    return x + ffn, op, ffn, choice


def final_hidden(cfg, params, tokens, dtype, probes=()):
    """(hidden states after the last RMSNorm [B, S, hidden], {probe name:
    value} or None without probes); probes are named as
    ``lfm2_moe_symbol`` names them."""
    take = Taker(params)
    dtype = jnp.dtype(dtype)
    ids = tokens.astype(jnp.int32)
    x = jnp.take(take("embed_weight"), ids, axis=0).astype(dtype)
    seen = {}
    for i, (kind, mlp) in enumerate(layer_kinds(cfg)):
        w = {name: take("layer%d_%s" % (i, name))
             for name in layer_tensors(kind, mlp)}
        step = jax.checkpoint(lambda xs, w, kind=kind, mlp=mlp: jax.vmap(
            lambda x1: layer(cfg, w, kind, mlp, x1, dtype))(xs))
        x, op, ffn, choice = step(x, w)
        seen.update({"layer%d_op" % i: op, "layer%d_ffn" % i: ffn,
                     "layer%d_choice" % i: choice})
    x = rms_norm(x, take("final_norm_gamma").astype(dtype), cfg["norm_eps"])
    take.assert_all_taken()
    return x, {name: seen[name] for name in probes or ()} or None


def logits(cfg, params, tokens, dtype, probes=()):
    """Float32 logits [B, S, vocabulary] of the whole model on the tied
    embedding (the CPU tests' entry; the chip never holds them whole)."""
    hidden, seen = final_hidden(cfg, params, tokens, dtype, probes)
    head = params["embed_weight"].astype(dtype)
    return (hidden @ head.T).astype(jnp.float32), seen


def loss(cfg, params, tokens, labels, dtype, probes=()):
    """(mean next-token negative log-likelihood, float32 log-softmax of
    x E^T with the embedding E; {probe name: value})."""
    hidden, seen = final_hidden(cfg, params, tokens, dtype, probes)
    head = params["embed_weight"].astype(jnp.dtype(dtype))

    def nll(hb, yb):
        logp = jax.nn.log_softmax((hb @ head.T).astype(jnp.float32), axis=-1)
        return -jnp.take_along_axis(logp, yb[:, None], axis=-1)[:, 0]

    rows = hidden.reshape(-1, hidden.shape[-1])
    each = over_blocks(nll, TOKEN_BLOCK, rows,
                       labels.reshape(-1).astype(jnp.int32))
    return jnp.mean(each), seen
