"""The sharding substrate itself (parallel/mesh.py, PR 16).

Topology construction (single device, N local devices, faked multi-host),
the MXNET_MESH_* env selection, spec/sharding round-trips, the
shard_map entry point, and the bitwise port gate: the
transformer train steps built through the substrate must match a plain
``jax.jit`` of the same math exactly — porting onto the substrate is a
refactor, not a numerics change.  Also enforces the single-substrate
rule: no module outside parallel/mesh.py touches jax's shard_map surface
directly.
"""
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from mxnet_tpu.parallel import mesh as mesh_mod


# ---------------------------------------------------------------------------
# topology construction
# ---------------------------------------------------------------------------

def test_topology_report():
    topo = mesh_mod.topology()
    assert topo["n_devices"] == len(jax.devices())
    assert topo["n_local_devices"] == len(jax.local_devices())
    assert topo["n_hosts"] == jax.process_count()
    assert topo["process_index"] == jax.process_index()
    assert topo["platform"] == "cpu"


def test_make_mesh_single_device():
    mesh = mesh_mod.make_mesh({"data": -1}, devices=jax.devices()[:1])
    assert dict(mesh.shape) == {"data": 1}


def test_make_mesh_infers_minus_one():
    n = len(jax.devices())
    mesh = mesh_mod.make_mesh({"data": -1, "model": 2})
    assert dict(mesh.shape) == {"data": n // 2, "model": 2}
    with pytest.raises(ValueError):
        mesh_mod.make_mesh({"data": -1, "model": 3})   # 8 % 3 != 0


def test_auto_mesh_balances_local_devices():
    mesh = mesh_mod.auto_mesh(("data", "model"))
    shape = dict(mesh.shape)
    assert shape["data"] * shape["model"] == len(jax.devices())
    assert shape["data"] >= shape["model"]             # largest-first


def test_multihost_mesh_faked_fleet():
    # one process, 4 virtual hosts over the 8 tier-1 CPU devices: the
    # injectable devices/n_hosts make the dist_ps topology testable here
    mesh = mesh_mod.multihost_mesh({"data": -1}, devices=jax.devices(),
                                   n_hosts=4)
    assert mesh.axis_names == ("host", "data")
    assert dict(mesh.shape) == {"host": 4,
                                "data": len(jax.devices()) // 4}


def test_multihost_mesh_rejects_uneven_fleet():
    with pytest.raises(ValueError):
        mesh_mod.multihost_mesh({"data": -1}, devices=jax.devices(),
                                n_hosts=3)
    with pytest.raises(ValueError):
        mesh_mod.multihost_mesh({"host": 2}, devices=jax.devices(),
                                n_hosts=2)             # axis-name collision


def test_multihost_mesh_live_fleet_is_single_host():
    # no injection: the live jax.distributed view (1 process under tier-1)
    mesh = mesh_mod.multihost_mesh()
    assert dict(mesh.shape) == {"host": 1, "data": len(jax.devices())}


# ---------------------------------------------------------------------------
# MXNET_MESH_* env selection
# ---------------------------------------------------------------------------

@pytest.fixture
def _mesh_env(monkeypatch):
    yield monkeypatch
    # monkeypatch restored the env; re-sync the import-time cache
    mesh_mod.refresh_from_env()


def test_mesh_from_env_unset_is_none(_mesh_env):
    _mesh_env.delenv("MXNET_MESH_SHAPE", raising=False)
    mesh_mod.refresh_from_env()
    assert mesh_mod.mesh_from_env() is None


def test_mesh_from_env_shape(_mesh_env):
    _mesh_env.setenv("MXNET_MESH_SHAPE", "data=-1,model=2")
    _mesh_env.setenv("MXNET_MESH_SPAN_HOSTS", "0")
    mesh_mod.refresh_from_env()
    mesh = mesh_mod.mesh_from_env()
    assert dict(mesh.shape) == {"data": len(jax.devices()) // 2,
                                "model": 2}


def test_mesh_from_env_span_hosts(_mesh_env):
    _mesh_env.setenv("MXNET_MESH_SHAPE", "data=-1")
    _mesh_env.setenv("MXNET_MESH_SPAN_HOSTS", "1")
    mesh_mod.refresh_from_env()
    mesh = mesh_mod.mesh_from_env()
    assert mesh.axis_names == ("host", "data")
    assert mesh.shape["host"] == jax.process_count()


def test_mesh_from_env_rejects_garbage(_mesh_env):
    _mesh_env.setenv("MXNET_MESH_SHAPE", "data:4")
    with pytest.raises(ValueError):
        mesh_mod.refresh_from_env()
    _mesh_env.setenv("MXNET_MESH_SHAPE", "data=-1")
    mesh_mod.refresh_from_env()    # leave the cache in a valid state


def test_default_mesh_precedence(_mesh_env):
    _mesh_env.setenv("MXNET_MESH_SHAPE", "data=2")
    mesh_mod.refresh_from_env()
    scoped = mesh_mod.auto_mesh(("data", "model"))
    with mesh_mod.using_mesh(scoped):
        assert mesh_mod.default_mesh() is scoped       # scope beats env
    assert dict(mesh_mod.default_mesh().shape) == {"data": 2}
    _mesh_env.delenv("MXNET_MESH_SHAPE")
    mesh_mod.refresh_from_env()
    auto = mesh_mod.default_mesh(("data",))            # fallback: all devices
    assert dict(auto.shape) == {"data": len(jax.devices())}


# ---------------------------------------------------------------------------
# spec / sharding round-trips
# ---------------------------------------------------------------------------

def test_filter_spec_drops_absent_axes():
    mesh = mesh_mod.make_mesh({"data": -1})
    assert (mesh_mod.filter_spec(P("data", "model", "seq"), mesh)
            == P("data", None, None))
    assert mesh_mod.filter_spec(P("model"), mesh) == P(None)
    assert mesh_mod.filter_spec(P("data"), None) == P("data")


def test_named_sharding_and_shard_put_round_trip():
    mesh = mesh_mod.auto_mesh(("data", "model"))
    host = np.arange(8 * 4, dtype=np.float32).reshape(8, 4)
    sharding = mesh_mod.named_sharding(mesh, P("data", "seq"))
    arr = mesh_mod.shard_put(host, sharding)
    assert arr.sharding.spec == P("data", None)        # 'seq' filtered out
    np.testing.assert_array_equal(np.asarray(arr), host)
    # Mesh + spec spelling, and the replicated helper
    arr2 = mesh_mod.shard_put(host, mesh, spec=P("data", None))
    assert arr2.sharding.spec == P("data", None)
    rep = mesh_mod.shard_put(host, mesh_mod.replicated(mesh))
    assert rep.sharding.spec == P()
    np.testing.assert_array_equal(np.asarray(rep), host)


# ---------------------------------------------------------------------------
# the shard_map entry point
# ---------------------------------------------------------------------------

def test_shard_map_psum():
    mesh = mesh_mod.make_mesh({"data": -1})
    n = mesh.shape["data"]
    x = np.arange(4 * n, dtype=np.float32).reshape(4 * n)

    fn = mesh_mod.shard_map(
        lambda a: lax.psum(jnp.sum(a), "data") * jnp.ones_like(a),
        mesh=mesh, in_specs=(P("data"),), out_specs=P("data"),
        check=False)
    out = np.asarray(fn(x))
    np.testing.assert_allclose(out, np.full_like(x, x.sum()))


def test_shard_map_uses_scope_mesh():
    mesh = mesh_mod.make_mesh({"data": -1})
    with mesh_mod.using_mesh(mesh):
        fn = mesh_mod.shard_map(lambda a: a * 2.0,
                                in_specs=(P("data"),),
                                out_specs=P("data"), check=False)
    x = np.ones(len(jax.devices()), np.float32)
    np.testing.assert_array_equal(np.asarray(fn(x)), x * 2.0)
    with pytest.raises(ValueError):
        mesh_mod.shard_map(lambda a: a, in_specs=(P("data"),),
                           out_specs=P("data"))        # no mesh anywhere


def test_no_shard_map_outside_the_substrate():
    """The single-substrate rule (ISSUE 16 acceptance): parallel/mesh.py
    is the only module that touches jax's shard_map surface."""
    root = os.path.join(os.path.dirname(__file__), os.pardir)
    offenders = []
    pat = re.compile(
        r"jax\.shard_map|jax\.experimental\.shard_map"
        r"|from\s+jax\.experimental\.shard_map|from\s+jax\s+import\s+"
        r"[^\n]*\bshard_map\b")
    for base in ("mxnet_tpu", "tools"):
        for dirpath, _, files in os.walk(os.path.join(root, base)):
            for fname in files:
                if not fname.endswith(".py"):
                    continue
                path = os.path.join(dirpath, fname)
                if path.endswith(os.path.join("parallel", "mesh.py")):
                    continue
                # the analyzer (JG008) names the forbidden spellings in
                # its matcher strings — mentions, not uses
                if os.sep + "lint" + os.sep in path:
                    continue
                with open(path) as f:
                    if pat.search(f.read()):
                        offenders.append(os.path.relpath(path, root))
    assert not offenders, (
        "direct jax shard_map use outside parallel/mesh.py: %s"
        % sorted(offenders))


def test_single_substrate_rule_is_a_lint_rule():
    """ISSUE 18 satellite: the grep above is promoted to graftlint JG008
    — the rule must fire on the exact spellings the regex hunts, so the
    invariant is enforced at lint time (pre-commit, --diff) too, not
    only when this test file runs."""
    from mxnet_tpu.lint import lint_source
    bad = "from jax.experimental.shard_map import shard_map\n"
    assert [f.rule for f in lint_source(bad, path="mxnet_tpu/foo.py",
                                        select={"JG008"})] == ["JG008"]
    # and the substrate module itself stays exempt
    assert lint_source(bad, path="mxnet_tpu/parallel/mesh.py",
                       select={"JG008"}) == []


# ---------------------------------------------------------------------------
# the bitwise port gate: substrate-built programs == plain jax.jit
# ---------------------------------------------------------------------------

def _tiny_lm(mesh):
    from mxnet_tpu.models.transformer import (
        TransformerLMConfig, init_transformer_params, place_batch)
    dp = mesh.shape.get("data", 1)
    sp = mesh.shape.get("seq", 1)
    tp = mesh.shape.get("model", 1)
    cfg = TransformerLMConfig(vocab=32, d_model=8 * max(tp, 1),
                              n_heads=max(tp, 2), d_ff=16 * max(tp, 1),
                              n_layers=1, max_len=8 * max(sp, 1))
    params = init_transformer_params(jax.random.PRNGKey(0), cfg, mesh)
    rng = np.random.RandomState(0)
    b, s = 2 * dp, 8 * sp
    tokens = rng.randint(0, cfg.vocab, (b, s)).astype(np.int32)
    labels = rng.randint(0, cfg.vocab, (b, s)).astype(np.int32)
    tokens, labels = place_batch(tokens, labels, mesh)
    return cfg, params, tokens, labels


def test_transformer_step_bitwise_matches_plain_jit():
    from mxnet_tpu.models import transformer as tfm
    mesh = mesh_mod.auto_mesh(("data", "seq", "model"))
    cfg, params, tokens, labels = _tiny_lm(mesh)

    # the pre-port spelling: plain jax.jit around the identical math
    # (no watch_jit, no substrate) — the port must not change a bit
    loss_of = tfm._lm_loss_fn(cfg, mesh, "seq")

    def raw_step(ps, tk, lb):
        loss, grads = jax.value_and_grad(loss_of)(ps, tk, lb)
        new = jax.tree_util.tree_map(
            lambda p, g: p - 0.1 * g.astype(p.dtype), ps, grads)
        return new, loss

    ref_params, ref_loss = jax.jit(raw_step)(params, tokens, labels)
    jax.block_until_ready(ref_loss)

    step = tfm.make_train_step(cfg, mesh, lr=0.1)      # donates params
    new_params, loss = step(params, tokens, labels)
    assert np.asarray(loss).tobytes() == np.asarray(ref_loss).tobytes()
    for name in ref_params:
        assert (np.asarray(new_params[name]).tobytes()
                == np.asarray(ref_params[name]).tobytes()), name


def test_transformer_zero1_step_bitwise_matches_plain_jit():
    from mxnet_tpu.models import transformer as tfm
    from mxnet_tpu.parallel.zero import sharded_update, update_sharding
    mesh = mesh_mod.auto_mesh(("data", "seq", "model"))
    cfg, params, tokens, labels = _tiny_lm(mesh)

    loss_of = tfm._lm_loss_fn(cfg, mesh, "seq")
    upd = {n: update_sharding(mesh, p.shape, "data",
                              getattr(p.sharding, "spec", P()))
           for n, p in params.items()}
    pshard = {n: p.sharding for n, p in params.items()}
    momenta = {n: jax.device_put(jnp.zeros_like(p), upd[n] or p.sharding)
               for n, p in params.items()}

    def momentum_sgd(p, g, m, hyper):
        new_m = 0.9 * m + g.astype(m.dtype)
        return p - 0.1 * new_m.astype(p.dtype), new_m

    def raw_step(ps, ms, tk, lb):
        loss, grads = jax.value_and_grad(loss_of)(ps, tk, lb)
        new_p, new_m = {}, {}
        for n in ps:
            new_p[n], new_m[n] = sharded_update(
                momentum_sgd, ps[n], grads[n], ms[n], {}, upd[n],
                pshard[n])
        return new_p, new_m, loss

    ref_p, ref_m, ref_loss = jax.jit(raw_step)(params, momenta, tokens,
                                               labels)
    jax.block_until_ready(ref_loss)

    step, momenta2 = tfm.make_train_step_zero1(cfg, mesh, params, lr=0.1)
    new_p, new_m, loss = step(params, momenta2, tokens, labels)
    assert np.asarray(loss).tobytes() == np.asarray(ref_loss).tobytes()
    for name in ref_p:
        assert (np.asarray(new_p[name]).tobytes()
                == np.asarray(ref_p[name]).tobytes()), name
        assert (np.asarray(new_m[name]).tobytes()
                == np.asarray(ref_m[name]).tobytes()), name
