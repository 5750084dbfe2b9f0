"""Plain reference for ``granite_4_0_h_micro``: Mamba-2 state-space mixers
with one NoPE grouped-query attention layer among every ten, a fused-input
SwiGLU MLP and the family's four multipliers (ibm-granite/granite-4.0-h-micro
``config.json``, ``model_type`` ``granitemoehybrid``; the scan is Dao and
Gu's, arXiv:2405.21060), written from the layer equations in the
configuration file.  Straight ``jax.numpy``: no kernel, no chunked form,
no carried state, no code of ``mxnet_tpu``.  The only things taken from
the system under test are its seeded tensors, by name.

One ``dtype`` for everything between the token ids and the float32 loss,
except what the equations state in float32: dt after its softplus, a = dt
A, its running sum over the whole sequence, the decay exp(c_t - c_s), the
gated norm, the softmax of attention and the log-softmax.  Weights arrive
in the dtype the system holds them in and are cast where they are used.

The scan is computed in its dual (masked-attention) form,

    y_t = sum_{s<=t} exp(c_t - c_s) (C_t . B_s) dt_s x_s + D x_t,

over blocks of queries against every key: C B^T of the block once, then a
head at a time its decay, masked to s <= t, times that, times dt_s, times
the head's x.  Nothing is cut into chunks and no state exists, so the form
shares no structure with the kernel's.  It is quadratic in the sequence.
Attention is the masked softmax over blocks of queries, one key/value
head at a time; the convolution is four shifted products.  Each head,
block and layer is under ``jax.checkpoint`` so that ``jax.grad`` fits
beside the system at 16,384 tokens; that changes what is kept, not what
is computed.
"""
import jax
import jax.numpy as jnp

from chipbench.reference.brumby_14b_base import over_blocks, rms_norm
from chipbench.reference.layers import Taker

TOKEN_BLOCK = 2048      # rows of the per-token maps
QUERY_BLOCK = 256       # queries of one block of the scan's dual form
ATTENTION_BLOCK = 1024  # queries of one block of the masked softmax

MAMBA_TENSORS = ("in_proj_weight", "conv_weight", "conv_bias", "dt_bias",
                 "a_log", "d", "mixer_norm_gamma", "out_proj_weight")
ATTENTION_TENSORS = ("q_weight", "k_weight", "v_weight", "o_weight")
SHARED_TENSORS = ("input_norm_gamma", "post_norm_gamma", "mlp_input_weight",
                  "mlp_output_weight")


def layer_kinds(cfg):
    return cfg["layer_types"][:cfg["num_hidden_layers"]]


def layer_tensors(kind):
    return SHARED_TENSORS + \
        (MAMBA_TENSORS if kind == "mamba" else ATTENTION_TENSORS)


def causal_conv(u, kernel, bias):
    """conv(u)_t = sum_j kernel[:, j] u_{t-K+1+j} + bias, zeros before the
    sequence: u [S, C], kernel [C, K], bias [C]."""
    taps = kernel.shape[1]
    return sum(kernel[:, j] * jnp.pad(u, [(taps - 1 - j, 0), (0, 0)])
               [:u.shape[0]] for j in range(taps)) + bias


def dual_scan(x, dt, a_log, b, c, d):
    """The scan in the dual form: x [S, H, P] in the working dtype, dt
    [S, H] float32 (after the softplus), a_log and d [H] float32, b and c
    [S, G, N]; head h reads group h // (H // G).  Returns [S, H, P]."""
    s, heads, p = x.shape
    groups = b.shape[1]
    dtype = x.dtype
    run = jnp.cumsum(dt * -jnp.exp(a_log), axis=0)      # [S, H] float32
    pos = jnp.arange(s)
    per_group = heads // groups
    x_heads = x.transpose(1, 0, 2)                      # [H, S, P]
    by_head = (x_heads.reshape(groups, per_group, s, p),
               run.T.reshape(groups, per_group, s),
               dt.T.reshape(groups, per_group, s))

    def block(cb, rb, tb):
        # cb [T, G, N], rb [T, H] the block's own running sums, tb [T]
        score = jnp.einsum("tgn,sgn->gts", cb, b)        # [G, T, S]
        visible = jnp.where(tb[:, None] >= pos[None, :], 0.0, -jnp.inf)
        rb = rb.T.reshape(groups, per_group, -1)

        def group(args):
            sc, xg, rg, dg, rq = args

            @jax.checkpoint
            def head(args):
                xh, rh, dh, rt = args                   # [S, P], [S], [S], [T]
                decay = jnp.exp(rt[:, None] - rh[None, :] + visible)
                w = decay.astype(dtype) * sc * dh.astype(dtype)[None, :]
                return w @ xh                           # [T, P]

            return jax.lax.map(head, (xg, rg, dg, rq))

        out = jax.lax.map(group, (score,) + by_head + (rb,))
        return out.reshape(heads, -1, p).transpose(1, 0, 2)

    y = over_blocks(block, QUERY_BLOCK, c, run, pos)
    return y + d.astype(dtype)[None, :, None] * x


def mamba(cfg, w, h, dtype):
    """The mixer before its output projection, h [S, hidden]: the gated
    and normed scan."""
    nh, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    groups, state = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    inner = nh * p
    f32 = jnp.float32
    cast = lambda name: w[name].astype(dtype)           # noqa: E731
    proj = over_blocks(lambda hb: hb @ cast("in_proj_weight").T,
                       TOKEN_BLOCK, h)
    z = proj[:, :inner]
    xbc = proj[:, inner:2 * inner + 2 * groups * state]
    dt = proj[:, 2 * inner + 2 * groups * state:]
    assert dt.shape[1] == nh, dt.shape
    xbc = jax.nn.silu(causal_conv(xbc, cast("conv_weight"),
                                  cast("conv_bias")))
    x = xbc[:, :inner].reshape(-1, nh, p)
    b = xbc[:, inner:inner + groups * state].reshape(-1, groups, state)
    c = xbc[:, inner + groups * state:].reshape(-1, groups, state)
    dt = jax.nn.softplus(dt.astype(f32) + w["dt_bias"].astype(f32))
    y = dual_scan(x, dt, w["a_log"].astype(f32), b, c, w["d"].astype(f32))

    def gated_norm(yb, zb):
        g = yb.reshape(yb.shape[0], inner).astype(f32) * \
            jax.nn.silu(zb.astype(f32))
        ms = jnp.mean(jnp.square(g), axis=-1, keepdims=True)
        return (cast("mixer_norm_gamma").astype(f32) * g *
                jax.lax.rsqrt(ms + cfg["rms_norm_eps"])).astype(dtype)

    return over_blocks(gated_norm, TOKEN_BLOCK, y, z)


def attention(cfg, w, h, dtype):
    """softmax_{s<=t}(q_t . k_s * attention_multiplier) v_s, h [S, hidden]
    -> [S, Hq * d] before the output projection: no rotary embedding, no
    norm on q or k."""
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // hq
    cast = lambda name: w[name].astype(dtype)           # noqa: E731
    s = h.shape[0]

    def project(hb):
        rows = hb.shape[0]
        return (hb @ cast("q_weight").T).reshape(rows, hq, d), \
            (hb @ cast("k_weight").T).reshape(rows, hkv, d), \
            (hb @ cast("v_weight").T).reshape(rows, hkv, d)

    q, k, v = over_blocks(project, TOKEN_BLOCK, h)
    pos = jnp.arange(s)
    scale = jnp.asarray(cfg["attention_multiplier"], dtype)

    def one_head(args):
        qh, kh, vh = args                   # [S, G, d], [S, d], [S, d]

        def block(qb, tb):
            score = (jnp.einsum("tgd,sd->gts", qb, kh) * scale) \
                .astype(jnp.float32)
            score = score + jnp.where(tb[:, None] >= pos[None, :], 0.0,
                                      -jnp.inf).astype(jnp.float32)
            prob = jax.nn.softmax(score, axis=-1).astype(dtype)
            return jnp.einsum("gts,sd->tgd", prob, vh)

        return over_blocks(block, ATTENTION_BLOCK, qh, pos)

    group = hq // hkv
    out = jax.lax.map(one_head, (
        q.reshape(s, hkv, group, d).transpose(1, 0, 2, 3),
        k.transpose(1, 0, 2), v.transpose(1, 0, 2)))    # [Hkv, S, G, d]
    return out.transpose(1, 0, 2, 3).reshape(s, hq * d)


def fused_swiglu(h, w_input, w_output):
    both = h @ w_input.T
    width = both.shape[-1] // 2
    return (jax.nn.silu(both[:, :width]) * both[:, width:]) @ w_output.T


def layer(cfg, w, kind, x, dtype):
    """One layer on one sequence x [S, hidden]; *w* maps the layer's short
    tensor names to the system's tensors.  Returns (x, the sequence
    operator's output before its projection, the MLP's output)."""
    eps = cfg["rms_norm_eps"]
    branch = jnp.asarray(cfg["residual_multiplier"], dtype)
    cast = lambda name: w[name].astype(dtype)           # noqa: E731
    h = rms_norm(x, cast("input_norm_gamma"), eps)
    if kind == "mamba":
        op, out_weight = mamba(cfg, w, h, dtype), cast("out_proj_weight")
    elif kind == "attention":
        op, out_weight = attention(cfg, w, h, dtype), cast("o_weight")
    else:
        raise ValueError("layer type %r" % (kind,))
    x = x + branch * over_blocks(lambda ob: ob @ out_weight.T, TOKEN_BLOCK,
                                 op)
    ffn = over_blocks(
        lambda xb: fused_swiglu(rms_norm(xb, cast("post_norm_gamma"), eps),
                                cast("mlp_input_weight"),
                                cast("mlp_output_weight")), TOKEN_BLOCK, x)
    return x + branch * ffn, op, ffn


def final_hidden(cfg, params, tokens, dtype, probes=()):
    """(hidden states after the last RMSNorm divided by logits_scaling
    [B, S, hidden], the tied head's weight, {probe name: value} or None
    without probes); probes are named as ``granite_hybrid_symbol`` names
    them."""
    take = Taker(params)
    dtype = jnp.dtype(dtype)
    ids = tokens.astype(jnp.int32)
    embed = take("embed_weight")
    x = jnp.take(embed, ids, axis=0).astype(dtype) * \
        jnp.asarray(cfg["embedding_multiplier"], dtype)
    seen = {}
    for i, kind in enumerate(layer_kinds(cfg)):
        w = {name: take("layer%d_%s" % (i, name))
             for name in layer_tensors(kind)}
        step = jax.checkpoint(lambda xs, w, kind=kind: jax.vmap(
            lambda x1: layer(cfg, w, kind, x1, dtype))(xs))
        x, op, ffn = step(x, w)
        seen.update({"layer%d_op" % i: op, "layer%d_ffn" % i: ffn})
    x = rms_norm(x, take("final_norm_gamma").astype(dtype),
                 cfg["rms_norm_eps"]) / \
        jnp.asarray(cfg["logits_scaling"], dtype)
    take.assert_all_taken()
    return x, embed.astype(dtype), \
        {name: seen[name] for name in probes or ()} or None


def logits(cfg, params, tokens, dtype, probes=()):
    """Float32 logits [B, S, vocabulary] of the whole model on the tied
    head (the CPU tests' entry; the chip never holds them whole)."""
    hidden, head, seen = final_hidden(cfg, params, tokens, dtype, probes)
    return (hidden @ head.T).astype(jnp.float32), seen


def loss(cfg, params, tokens, labels, dtype, probes=()):
    """(mean next-token negative log-likelihood, float32 log-softmax of
    (x / logits_scaling) E^T; {probe name: value})."""
    hidden, head, seen = final_hidden(cfg, params, tokens, dtype, probes)

    def nll(hb, yb):
        logp = jax.nn.log_softmax((hb @ head.T).astype(jnp.float32), axis=-1)
        return -jnp.take_along_axis(logp, yb[:, None], axis=-1)[:, 0]

    rows = hidden.reshape(-1, hidden.shape[-1])
    each = over_blocks(nll, TOKEN_BLOCK, rows,
                       labels.reshape(-1).astype(jnp.int32))
    return jnp.mean(each), seen
