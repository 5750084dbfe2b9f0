"""Transformer LM with 3D (data × sequence × tensor) parallelism.

The reference framework predates attention (SURVEY §5.7 — its long-sequence
answer was bucketing); this model is the TPU-native long-context flagship:

- **data parallel**: batch sharded over the ``data`` mesh axis; gradient
  all-reduce inserted by XLA (replaces kvstore push/pull, SURVEY §2.5).
- **tensor parallel**: attention heads and MLP hidden sharded over
  ``model``; the pair of matmuls per block keeps one all-reduce per
  sub-layer (Megatron layout), compiled to ICI collectives.
- **sequence parallel**: activations sharded over ``seq``; exact attention
  across shards via the ring-attention ppermute pipeline
  (``parallel/ring_attention.py``) inside a ``shard_map`` island.

Everything else is plain ``jit`` + ``NamedSharding`` annotations: pick a
mesh, annotate, let XLA insert collectives (the scaling-book recipe).
"""
from __future__ import annotations

import dataclasses
import functools
import math

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..parallel import mesh as mesh_mod
from ..parallel.ring_attention import ring_attention

__all__ = ["TransformerLMConfig", "init_transformer_params",
           "transformer_forward", "make_train_step",
           "make_train_step_zero1"]


@dataclasses.dataclass(frozen=True)
class TransformerLMConfig:
    vocab: int = 256
    d_model: int = 64
    n_heads: int = 4
    d_ff: int = 256
    n_layers: int = 2
    max_len: int = 128
    dtype: object = jnp.float32


def _param_specs(cfg):
    """name -> (shape, PartitionSpec). Megatron TP layout over 'model'."""
    hd = cfg.d_model // cfg.n_heads
    specs = {
        "embed": ((cfg.vocab, cfg.d_model), P(None, None)),
        "pos_embed": ((cfg.max_len, cfg.d_model), P(None, None)),
        "out_norm_scale": ((cfg.d_model,), P(None)),
        "out_proj": ((cfg.d_model, cfg.vocab), P(None, None)),
    }
    for i in range(cfg.n_layers):
        pre = "layer%d_" % i
        specs.update({
            # QKV/out projections: head dim sharded over 'model'
            pre + "wq": ((cfg.d_model, cfg.n_heads, hd), P(None, "model", None)),
            pre + "wk": ((cfg.d_model, cfg.n_heads, hd), P(None, "model", None)),
            pre + "wv": ((cfg.d_model, cfg.n_heads, hd), P(None, "model", None)),
            pre + "wo": ((cfg.n_heads, hd, cfg.d_model), P("model", None, None)),
            # MLP: hidden sharded over 'model' (col- then row-parallel)
            pre + "w1": ((cfg.d_model, cfg.d_ff), P(None, "model")),
            pre + "b1": ((cfg.d_ff,), P("model")),
            pre + "w2": ((cfg.d_ff, cfg.d_model), P("model", None)),
            pre + "norm1_scale": ((cfg.d_model,), P(None)),
            pre + "norm2_scale": ((cfg.d_model,), P(None)),
        })
    return specs


# the spec/placement helpers moved into the sharding substrate
# (parallel/mesh.py); these names remain the model-layer spelling
_filter_spec = mesh_mod.filter_spec
global_put = mesh_mod.shard_put


def init_transformer_params(key, cfg, mesh=None):
    """Initialize params; placed with TP shardings when a mesh is given."""
    specs = _param_specs(cfg)
    params = {}
    for name, (shape, spec) in sorted(specs.items()):
        spec = _filter_spec(spec, mesh)
        key, sub = jax.random.split(key)
        if name.endswith(("_scale",)):
            v = jnp.ones(shape, cfg.dtype)
        elif name.endswith(("b1",)):
            v = jnp.zeros(shape, cfg.dtype)
        else:
            # fan-in = the contracted dims: leading axis for wq/wk/wv/w1/w2
            # (they contract shape[0]), all-but-last for wo (contracts h,k)
            if name.endswith("wo"):
                fan_in = int(np.prod(shape[:-1]))
            else:
                fan_in = shape[0]
            v = (jax.random.normal(sub, shape, cfg.dtype)
                 * (1.0 / math.sqrt(max(fan_in, 1))))
        if mesh is not None:
            v = global_put(np.asarray(v), NamedSharding(mesh, spec))
        params[name] = v
    return params


def _rmsnorm(x, scale):
    from ..ops.lm import rms_norm       # the registered RMSNorm's own
    return rms_norm(x, scale, axis=-1, eps=1e-6)


def transformer_forward(params, tokens, cfg, mesh=None, seq_axis="seq"):
    """Causal LM forward: tokens [B, S] int32 -> logits [B, S, vocab].

    With a mesh, attention runs as a shard_map ring over ``seq_axis`` and
    activations carry (data, seq, -) shardings; without one it is plain
    single-device jax (used by tests and the single-chip entry).
    """
    b, s = tokens.shape
    x = params["embed"][tokens] + params["pos_embed"][:s][None, :, :]
    use_ring = mesh is not None and mesh.shape.get(seq_axis, 1) > 1

    if use_ring:
        qkv_spec = _filter_spec(P("data", "model", seq_axis, None), mesh)
        attn = mesh_mod.shard_map(
            functools.partial(ring_attention, axis_name=seq_axis,
                              causal=True),
            mesh=mesh,
            in_specs=(qkv_spec, qkv_spec, qkv_spec), out_specs=qkv_spec,
            check=False)
    else:
        attn = functools.partial(_causal_attn_local, mesh=mesh)

    for i in range(cfg.n_layers):
        pre = "layer%d_" % i
        h = _rmsnorm(x, params[pre + "norm1_scale"])
        q = jnp.einsum("bsd,dhk->bhsk", h, params[pre + "wq"])
        k = jnp.einsum("bsd,dhk->bhsk", h, params[pre + "wk"])
        v = jnp.einsum("bsd,dhk->bhsk", h, params[pre + "wv"])
        o = attn(q, k, v)
        x = x + jnp.einsum("bhsk,hkd->bsd", o, params[pre + "wo"])
        h = _rmsnorm(x, params[pre + "norm2_scale"])
        h = jax.nn.gelu(h @ params[pre + "w1"] + params[pre + "b1"])
        x = x + h @ params[pre + "w2"]

    x = _rmsnorm(x, params["out_norm_scale"])
    return x @ params["out_proj"]


def _use_flash(s):
    # the Pallas kernel compiles with Mosaic, i.e. on TPU only; elsewhere
    # (the CPU test mesh) XLA fuses the jnp reference.  Below one lane
    # tile the kernel has nothing to stream.
    return jax.default_backend() == "tpu" and s >= 128


def _causal_attn_local(q, k, v, mesh=None):
    if _use_flash(q.shape[2]):
        from ..ops.pallas_kernels import flash_attention
        fn = functools.partial(flash_attention, causal=True)
        if mesh is not None:
            # pallas_call is opaque to GSPMD: shard batch/heads explicitly
            # so the TP split survives (each shard runs the kernel locally)
            spec = _filter_spec(P("data", "model", None, None), mesh)
            return mesh_mod.shard_map(lambda a, b_, c: fn(a, b_, c),
                                      mesh=mesh, in_specs=(spec,) * 3,
                                      out_specs=spec, check=False)(q, k, v)
        return fn(q, k, v)
    from ..parallel.ring_attention import local_attention
    return local_attention(q, k, v, causal=True)


def _take_labels(logp, labels):
    """``logp[b, s, labels[b, s]]`` as one gather on the labels' own
    dtype.  ``jnp.take_along_axis`` widens int32 indices to int64 under
    the package's x64 (emulated on a TPU); labels are token ids, in range
    by construction, so nothing has to wrap or clamp them."""
    dnums = jax.lax.GatherDimensionNumbers(
        offset_dims=(), collapsed_slice_dims=(2,), start_index_map=(2,),
        operand_batching_dims=(0, 1), start_indices_batching_dims=(0, 1))
    return jax.lax.gather(logp, labels[..., None], dnums, (1, 1, 1),
                          mode="promise_in_bounds")


def _lm_loss_fn(cfg, mesh, seq_axis):
    """Mean next-token NLL in fp32 — the loss shared by every train-step
    builder in this module."""

    def loss_of(params, tokens, labels):
        logits = transformer_forward(params, tokens, cfg, mesh, seq_axis)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -_take_labels(logp, labels)
        return jnp.mean(nll)

    return loss_of


def make_train_step(cfg, mesh, lr=0.1, seq_axis="seq"):
    """Build the jitted SPMD train step: (params, tokens, labels) ->
    (new_params, loss).  Batch is sharded P('data', seq_axis); gradient
    reduction, TP collectives and the loss mean are all XLA-inserted."""
    loss_of = _lm_loss_fn(cfg, mesh, seq_axis)

    def step(params, tokens, labels):
        loss, grads = jax.value_and_grad(loss_of)(params, tokens, labels)
        new_params = jax.tree_util.tree_map(
            lambda p, g: p - lr * g.astype(p.dtype), params, grads)
        return new_params, loss

    return mesh_mod.jit_sharded(step, "transformer_train_step",
                                donate_argnums=(0,))


def make_train_step_zero1(cfg, mesh, params, lr=0.1, momentum=0.9,
                          seq_axis="seq"):
    """SGD-momentum train step with cross-replica weight-update sharding
    (ZeRO-1, arXiv:2004.13336) layered on the dp x sp x tp shardings.

    Momentum buffers for replicated (non-TP) parameters shard over the
    ``data`` axis when the leading dim divides evenly; the sharding
    constraints make XLA reduce-scatter those gradients, update 1/N of
    the rows per data replica, and all-gather the weights back.  Returns
    ``(step, momenta)`` where ``step(params, momenta, tokens, labels) ->
    (new_params, new_momenta, loss)``.
    """
    from ..parallel.zero import sharded_update, update_sharding

    upd_shardings = {
        n: update_sharding(mesh, p.shape, "data",
                           getattr(p.sharding, "spec", P()))
        for n, p in params.items()}
    param_shardings = {n: p.sharding for n, p in params.items()}
    momenta = {
        n: jax.device_put(jnp.zeros_like(p),
                          upd_shardings[n] or p.sharding)
        for n, p in params.items()}

    loss_of = _lm_loss_fn(cfg, mesh, seq_axis)

    def momentum_sgd(p, g, m, hyper):
        new_m = momentum * m + g.astype(m.dtype)
        return p - lr * new_m.astype(p.dtype), new_m

    def step(ps, ms, tokens, labels):
        loss, grads = jax.value_and_grad(loss_of)(ps, tokens, labels)
        # the shared ZeRO-1 placement core (parallel/zero.py): the same
        # wsc sandwich the fused Trainer's MXNET_ZERO path and
        # ShardedTrainer compile
        new_p, new_m = {}, {}
        for n in ps:
            new_p[n], new_m[n] = sharded_update(
                momentum_sgd, ps[n], grads[n], ms[n], {},
                upd_shardings[n], param_shardings[n])
        return new_p, new_m, loss

    return mesh_mod.jit_sharded(step, "transformer_train_step_zero1",
                                donate_argnums=(0, 1)), momenta


def place_batch(tokens, labels, mesh, seq_axis="seq"):
    """Shard a [B, S] token batch over (data, seq)."""
    spec = NamedSharding(mesh, _filter_spec(P("data", seq_axis), mesh))
    return global_put(tokens, spec), global_put(labels, spec)


# the provider's programs close over live params/momenta; keep them
# alive until the driver traces (same idiom as gluon/fused_trainer)
_TRACECHECK_KEEPALIVE = []


def tracecheck_programs():
    """graftcheck provider: the plain and ZeRO-1 train steps of a tiny
    LM over the live 3D mesh (whatever device count the process has —
    auto_mesh collapses absent axes to size 1)."""
    mesh = mesh_mod.auto_mesh(("data", "seq", "model"))
    dp, sp, tp = (mesh.shape[a] for a in ("data", "seq", "model"))
    cfg = TransformerLMConfig(vocab=32, d_model=8 * max(tp, 1),
                              n_heads=max(tp, 2), d_ff=16 * max(tp, 1),
                              n_layers=1, max_len=8 * max(sp, 1))
    params = init_transformer_params(jax.random.PRNGKey(0), cfg, mesh)
    b, s = 2 * dp, 8 * sp
    rng = np.random.RandomState(0)
    tokens = rng.randint(0, cfg.vocab, (b, s)).astype(np.int32)
    labels = rng.randint(0, cfg.vocab, (b, s)).astype(np.int32)
    tokens, labels = place_batch(tokens, labels, mesh)

    step = make_train_step(cfg, mesh, lr=0.1)
    step_z, momenta = make_train_step_zero1(cfg, mesh, params, lr=0.1)
    _TRACECHECK_KEEPALIVE.append((params, momenta, tokens, labels))
    axes = {"mesh_axes": ("data", "seq", "model")}
    return [
        ("transformer_train_step", step, (params, tokens, labels), {},
         axes),
        ("transformer_train_step_zero1", step_z,
         (params, momenta, tokens, labels), {}, axes),
    ]
