"""Pallas kernel tests: interpret mode on CPU vs jnp reference (SURVEY §4
doctrine: interpret-mode Pallas ↔ compiled cross-check), plus the
no-chip half of the compiled story: the kernels must LOWER for TPU from
this CPU sandbox with the package's x64 setting as shipped (Mosaic has no
64-bit types; chip_smoke.py runs the compiled kernels on the chip)."""
import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import mxnet_tpu  # noqa: F401
from mxnet_tpu.ops import pallas_kernels as pk
from mxnet_tpu.parallel.ring_attention import local_attention


def _rand(b, h, s, d, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_reference(causal):
    q, k, v = _rand(2, 3, 64, 16)
    out = pk.flash_attention(q, k, v, causal, None, 32, 32, True)
    ref = local_attention(q, k, v, causal=causal)
    assert np.allclose(np.asarray(out), np.asarray(ref), atol=2e-5), \
        np.abs(np.asarray(out) - np.asarray(ref)).max()


def test_flash_uneven_blocks():
    # seq not a multiple of the block size exercises the tail path
    q, k, v = _rand(1, 2, 48, 8, seed=1)
    out = pk.flash_attention(q, k, v, True, None, 32, 32, True)
    ref = local_attention(q, k, v, causal=True)
    assert np.allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_gradients_match_reference():
    q, k, v = _rand(1, 2, 32, 8, seed=2)

    def loss_pallas(q, k, v):
        return jnp.sum(pk.flash_attention(q, k, v, True, None, 16, 16,
                                          True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(local_attention(q, k, v, causal=True) ** 2)

    gp = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gp, gr):
        assert np.allclose(np.asarray(a), np.asarray(b), atol=1e-4), \
            np.abs(np.asarray(a) - np.asarray(b)).max()


def test_flash_sm_scale():
    q, k, v = _rand(1, 1, 16, 4, seed=3)
    out = pk.flash_attention(q, k, v, False, 0.5, 16, 16, True)
    ref = local_attention(q, k, v, sm_scale=0.5)
    assert np.allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def _lowers_for_tpu(fn, *specs):
    text = jax.jit(fn).trace(*specs).lower(
        lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text      # Mosaic, not interpret/jnp
    return text


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("head_dim", [64, 128])
@pytest.mark.parametrize("seq,causal", [(128, True), (1000, True),
                                        (1024, False)])
def test_flash_lowers_for_tpu_with_x64_on(dtype, head_dim, seq, causal):
    assert jax.config.jax_enable_x64      # the package's real config
    spec = jax.ShapeDtypeStruct((1, 2, seq, head_dim), dtype)
    _lowers_for_tpu(functools.partial(pk.flash_attention, causal=causal),
                    spec, spec, spec)


def test_flash_rejects_untileable_shapes():
    q = jnp.zeros((1, 1, 256, 192), jnp.float32)
    with pytest.raises(ValueError, match="head dim"):
        pk.flash_attention(q, q, q, False, None, 128, 128, True)
    q = jnp.zeros((1, 1, 512, 64), jnp.float32)
    with pytest.raises(ValueError, match="block_k"):
        pk.flash_attention(q, q, q, False, None, 128, 192, True)
