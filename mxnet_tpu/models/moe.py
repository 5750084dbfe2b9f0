"""Top-1 switch layer with a capacity, for expert parallelism (EP).

What this is: every token goes to ONE expert (the argmax of a softmax
router), every expert has room for ``capacity_factor * tokens / experts``
tokens, and a token over its expert's capacity is DROPPED (it passes
through the caller's residual only).  The functional SPMD models use it
outside ``Module``.  The dropless top-k layer — every token keeps all k
of its experts whatever the load, grouped matrix product, a graph op for
``Module.fit`` — is ``_contrib_SparseMoE`` in ``ops/moe.py``; the
expert choice here is that module's ``topk_route`` with k = 1.

Beyond-reference capability (the reference predates MoE): a
switch-style top-1 routed expert FFN in the Mesh-TensorFlow dispatch
formulation — routing produces static-shape dispatch/combine tensors,
expert compute is one batched einsum over the expert dimension, and
placing the expert dim on a mesh axis makes the XLA SPMD partitioner
insert the all-to-all exchanges that NCCL-based frameworks hand-code.

Design notes (TPU-first):
* Static shapes everywhere: capacity-based routing (tokens over an
  expert's capacity are dropped and pass through the residual), so one
  compiled program serves every batch.
* ``expert_axis`` defaults to ``"model"`` — EP reuses the tensor-
  parallel axis the way production MoE stacks overlap EP with TP.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.moe import topk_route
from ..parallel import mesh as mesh_mod

__all__ = ["init_moe_params", "moe_ffn", "moe_param_specs"]


def moe_param_specs(d_model, d_ff, n_experts, expert_axis="model"):
    """name -> (shape, PartitionSpec) with the expert dim sharded."""
    e = expert_axis
    return {
        "gate_w": ((d_model, n_experts), P()),
        "expert_w1": ((n_experts, d_model, d_ff), P(e, None, None)),
        "expert_b1": ((n_experts, d_ff), P(e, None)),
        "expert_w2": ((n_experts, d_ff, d_model), P(e, None, None)),
    }


def init_moe_params(key, d_model, d_ff, n_experts, mesh=None,
                    dtype=jnp.float32, expert_axis="model"):
    params = {}
    for name, (shape, spec) in sorted(
            moe_param_specs(d_model, d_ff, n_experts,
                            expert_axis).items()):
        key, sub = jax.random.split(key)
        if name == "expert_b1":
            v = jnp.zeros(shape, dtype)
        else:
            fan_in = shape[-2] if name != "gate_w" else shape[0]
            v = (jax.random.normal(sub, shape, dtype)
                 * (1.0 / math.sqrt(max(fan_in, 1))))
        if mesh is not None:
            v = mesh_mod.shard_put(v, mesh_mod.named_sharding(mesh, spec))
        params[name] = v
    return params


def _route_top1(logits, capacity):
    """Switch routing: per-token argmax expert with capacity cutoff.

    Returns (dispatch [n, E, C] in {0,1}, combine [n, E, C] floats):
    ``dispatch`` scatters token n into its expert's buffer slot,
    ``combine`` gathers the expert output back scaled by the gate
    probability. Tokens beyond an expert's capacity drop (all-zero
    rows) — the caller's residual connection carries them through.
    """
    n, num_experts = logits.shape
    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    expert_idx, gate_val = topk_route(gates, 1, normalise=False)
    onehot = jax.nn.one_hot(expert_idx[:, 0], num_experts,
                            dtype=jnp.float32)              # [n, E]
    # position of each token within its chosen expert's queue
    pos = jnp.cumsum(onehot, axis=0) * onehot               # [n, E], 1-based
    within = (pos > 0) & (pos <= capacity)
    slot = jnp.clip(pos - 1, 0, capacity - 1).astype(jnp.int32)
    slot_oh = jax.nn.one_hot(jnp.max(slot, axis=-1), capacity,
                             dtype=jnp.float32)             # [n, C]
    dispatch = (onehot * within)[:, :, None] * slot_oh[:, None, :]
    combine = dispatch * gate_val[:, :, None]
    return dispatch, combine


def moe_ffn(x, params, capacity_factor=1.25, mesh=None,
            expert_axis="model"):
    """Apply the routed expert FFN to ``x`` [B, S, D] -> [B, S, D].

    With a mesh, expert weights and the expert compute shard over
    ``expert_axis``; the dispatch/combine einsums become the token
    all-to-all. Add the result to a residual: dropped tokens contribute
    zero here.
    """
    b, s, d = x.shape
    n = b * s
    num_experts = params["expert_w1"].shape[0]
    capacity = max(1, int(math.ceil(
        capacity_factor * n / num_experts)))
    flat = x.reshape(n, d)
    logits = flat @ params["gate_w"]
    dispatch, combine = _route_top1(logits, capacity)

    expert_in = jnp.einsum("nec,nd->ecd", dispatch, flat)   # [E, C, D]
    if mesh is not None and expert_axis in mesh.shape:
        espec = mesh_mod.named_sharding(mesh, P(expert_axis, None, None))
        expert_in = jax.lax.with_sharding_constraint(expert_in, espec)
    h = jax.nn.gelu(
        jnp.einsum("ecd,edf->ecf", expert_in, params["expert_w1"])
        + params["expert_b1"][:, None, :])
    out_e = jnp.einsum("ecf,efd->ecd", h, params["expert_w2"])
    if mesh is not None and expert_axis in mesh.shape:
        out_e = jax.lax.with_sharding_constraint(out_e, espec)
    out = jnp.einsum("nec,ecd->nd", combine.astype(out_e.dtype), out_e)
    return out.reshape(b, s, d)
