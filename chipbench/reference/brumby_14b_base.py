"""Plain reference for ``brumby_14b_base``: the Qwen3-14B block with softmax
attention replaced by power retention (manifestai/Brumby-14B-Base
``config.json``; Buckman, Gelada, Zhang, arXiv:2507.04239), written from the
layer equations in the configuration file.  Straight ``jax.numpy``: no
kernel, no state form, no code of ``mxnet_tpu``.  The only things taken from
the system under test are its seeded tensors, by name.

One ``dtype`` for everything between the token ids and the float32 loss,
except what the equations state in float32: the log-gate, its running sum
and the log-softmax.  Weights arrive in the dtype the system holds them in
and are cast where they are used, one layer at a time.

Power retention is computed in its quadratic form,

    w_ts = (q_t . k_s / sqrt(d))^2 exp(c_t - c_s)   for s <= t
    o_t  = sum_s w_ts v_s / (sum_s w_ts + eps),

over blocks of queries and one key/value head at a time; everything that
acts on tokens one by one runs over blocks of tokens.  Each block and each
layer is under ``jax.checkpoint`` so that ``jax.grad`` fits beside the
system at 16,384 tokens; that changes what is kept, not what is computed.
"""
import jax
import jax.numpy as jnp

from chipbench.reference.layers import Taker

TOKEN_BLOCK = 2048      # rows of the per-token maps
QUERY_BLOCK = 1024      # queries of one block of the quadratic form


def rms_norm(x, gain, eps):
    """x / sqrt(mean(x^2) + eps) * gain over the last axis."""
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + jnp.asarray(eps, x.dtype)) * gain


def rope(x, theta):
    """Rotate-half rotary embedding on [S, heads, d], positions 0..S-1."""
    s, _, d = x.shape
    half = d // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freq[None, :]
    cos = jnp.cos(ang)[:, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[:, None, :].astype(x.dtype)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def over_blocks(fn, block, *rows):
    """``fn`` over blocks of the leading axis of *rows* (padded with
    zeros to whole blocks), each block under ``jax.checkpoint``."""
    n = rows[0].shape[0]
    block = min(block, n)
    count = -(-n // block)
    pad = count * block - n

    def cut(x):
        if pad:
            x = jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))
        return x.reshape((count, block) + x.shape[1:])

    out = jax.lax.map(lambda xs: jax.checkpoint(fn)(*xs),
                      tuple(cut(x) for x in rows))
    return jax.tree_util.tree_map(
        lambda y: y.reshape((count * block,) + y.shape[2:])[:n], out)


def power_retention(q, k, v, c, eps):
    """The quadratic form.  q [S, Hq, d], k and v [S, Hkv, d], c [S, Hkv]
    the float32 running sum of the log-gate; query head i reads key/value
    head i // (Hq // Hkv).  Returns [S, Hq, d]."""
    s, hq, d = q.shape
    hkv = k.shape[1]
    group = hq // hkv
    scale = 1.0 / (d ** 0.5)
    pos = jnp.arange(s)

    def one_head(args):
        qh, kh, vh, ch = args               # [S, G, d], [S, d], [S, d], [S]

        def block(qb, cb, tb):
            score = jnp.einsum("tgd,sd->gts", qb, kh) * \
                jnp.asarray(scale, qb.dtype)
            reach = jnp.where(tb[:, None] >= pos[None, :],
                              cb[:, None] - ch[None, :], -jnp.inf)
            w = score * score * jnp.exp(reach).astype(qb.dtype)[None]
            num = jnp.einsum("gts,sd->tgd", w, vh)
            den = jnp.sum(w, axis=-1).T                     # [t, G]
            return num / (den + jnp.asarray(eps, qb.dtype))[..., None]

        return over_blocks(block, QUERY_BLOCK, qh, ch, pos)

    heads = (q.reshape(s, hkv, group, d).transpose(1, 0, 2, 3),
             k.transpose(1, 0, 2), v.transpose(1, 0, 2), c.T)
    out = jax.lax.map(one_head, heads)                      # [Hkv, S, G, d]
    return out.transpose(1, 0, 2, 3).reshape(s, hq, d)


def layer(cfg, w, x, dtype):
    """One layer on one sequence x [S, hidden]; *w* maps the layer's short
    tensor names to the system's tensors.  Returns (x, retention output)."""
    d, hq, hkv = cfg["head_dim"], cfg["num_attention_heads"], \
        cfg["num_key_value_heads"]
    eps, ret = cfg["rms_norm_eps"], cfg["retention"]
    cast = lambda name: w[name].astype(dtype)           # noqa: E731

    def project(xb):
        h = rms_norm(xb, cast("input_norm_gamma"), eps)
        rows = xb.shape[0]
        q = (h @ cast("q_weight").T).reshape(rows, hq, d)
        k = (h @ cast("k_weight").T).reshape(rows, hkv, d)
        v = (h @ cast("v_weight").T).reshape(rows, hkv, d)
        q = rms_norm(q, cast("q_norm_gamma"), eps)
        k = rms_norm(k, cast("k_norm_gamma"), eps)
        gate = h @ cast("gate_weight").T + cast("gate_bias")
        return q, k, v, jax.nn.log_sigmoid(gate.astype(jnp.float32))

    q, k, v, a = over_blocks(project, TOKEN_BLOCK, x)
    q, k = rope(q, float(cfg["rope_theta"])), rope(k, float(cfg["rope_theta"]))
    o = power_retention(q, k, v, jnp.cumsum(a, axis=0), ret["eps"])

    def mix(xb, ob):
        xb = xb + ob.reshape(ob.shape[0], hq * d) @ cast("o_weight").T
        h = rms_norm(xb, cast("post_norm_gamma"), eps)
        act = jax.nn.silu(h @ cast("mlp_gate_weight").T) * \
            (h @ cast("mlp_up_weight").T)
        return xb + act @ cast("mlp_down_weight").T

    return over_blocks(mix, TOKEN_BLOCK, x, o), o


LAYER_TENSORS = ("input_norm_gamma", "q_weight", "k_weight", "v_weight",
                 "q_norm_gamma", "k_norm_gamma", "gate_weight", "gate_bias",
                 "o_weight", "post_norm_gamma", "mlp_gate_weight",
                 "mlp_up_weight", "mlp_down_weight")


def logits(cfg, params, tokens, dtype, probe_layer=None):
    """Float32 logits [B, S, vocabulary held here] of the whole model (the
    CPU tests' entry; the chip never holds them whole) and the retention
    output of *probe_layer*."""
    hidden, probe = final_hidden(cfg, params, tokens, dtype, probe_layer)
    head = params["lm_head_weight"].astype(dtype)
    return (hidden @ head.T).astype(jnp.float32), probe


def final_hidden(cfg, params, tokens, dtype, probe_layer=None):
    """Hidden states after the last RMSNorm, [B, S, hidden], and the
    retention output of *probe_layer* ([B, S, Hq, d]) or None."""
    take = Taker(params)
    dtype = jnp.dtype(dtype)
    ids = tokens.astype(jnp.int32)
    x = jnp.take(take("embed_weight"), ids, axis=0).astype(dtype)
    probe = None
    for i in range(cfg["num_hidden_layers"]):
        w = {name: take("layer%d_%s" % (i, name)) for name in LAYER_TENSORS}
        step = jax.checkpoint(
            lambda xs, w: jax.vmap(lambda x1: layer(cfg, w, x1, dtype))(xs))
        x, o = step(x, w)
        if i == probe_layer:
            probe = o
    x = rms_norm(x, take("final_norm_gamma").astype(dtype),
                 cfg["rms_norm_eps"])
    take("lm_head_weight")
    take.assert_all_taken()
    return x, probe


def loss(cfg, params, tokens, labels, dtype, probe_layer=None):
    """(mean next-token negative log-likelihood over the vocabulary held
    here, float32 log-softmax; retention output of *probe_layer*)."""
    hidden, probe = final_hidden(cfg, params, tokens, dtype, probe_layer)
    head = params["lm_head_weight"].astype(jnp.dtype(dtype))

    def nll(hb, yb):
        logp = jax.nn.log_softmax((hb @ head.T).astype(jnp.float32), axis=-1)
        return -jnp.take_along_axis(logp, yb[:, None], axis=-1)[:, 0]

    rows = hidden.reshape(-1, hidden.shape[-1])
    each = over_blocks(nll, TOKEN_BLOCK, rows,
                       labels.reshape(-1).astype(jnp.int32))
    return jnp.mean(each), probe
