"""The sequence convolution's Pallas kernels (``causal_conv_fwd`` /
``causal_conv_bwd``) in interpret mode against the ``jnp`` form, which
stays the definition: forward and every gradient by taps, bias, SiLU and
gates in float32 and bfloat16; the K - 1 token tail at a tile's edge and at
a batch row's start; the zeros before the sequence; the shapes the kernels
do not take; the counters of both ops on either path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.ops import lm
from mxnet_tpu.ops import pallas_kernels as pk

ROWS = 32          # tokens a tile in these tests: sequences of 96 are three
COUNTERS = ("causal_conv_traced", "short_conv_traced",
            "causal_conv_kernel_traced")


@pytest.fixture
def kernel_path(monkeypatch):
    """Both ops on their kernel path as a TPU traces it, runnable here:
    tiles of 32 tokens by 128 channels, the kernels in interpret mode."""
    monkeypatch.setattr(lm, "_kernel_backend", lambda: True)
    monkeypatch.setattr(pk, "_CONV_ROWS", ROWS)
    monkeypatch.setattr(pk, "_CONV_LANES", 128)
    for name in ("_causal_conv_fwd_impl", "_causal_conv_bwd_impl"):
        impl = getattr(pk, name)

        def interpreted(*args, _impl=impl):
            return _impl(*args[:-1], True)

        monkeypatch.setattr(pk, name, interpreted)


def jnp_form(*args):
    """``sequence_conv`` off the kernel path, whatever the fixture set."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(lm, "_kernel_backend", lambda: False)
        return lm.sequence_conv(*args)


def operands(seed, dtype, taps, gated, batch=2, seq=3 * ROWS, channels=256):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(batch, seq, channels * (3 if gated else 1)),
                        dtype),
            jnp.asarray(rng.randn(channels, taps) * 0.5, dtype),
            jnp.asarray(rng.randn(channels), dtype),
            jnp.asarray(rng.randn(batch, seq, channels), jnp.float32))


def outputs(form, data, weight, bias, weights_out, silu, gated):
    """(result, d data, d weight[, d bias]) of *form* under the cotangent
    *weights_out*."""
    args = (data, weight) + (() if bias is None else (bias,))

    def loss(*a):
        out = form(a[0], a[1], a[2] if len(a) > 2 else None, silu, gated)
        return jnp.sum(out.astype(jnp.float32) * weights_out), out

    grads, out = jax.grad(loss, tuple(range(len(args))), has_aux=True)(*args)
    return (out,) + grads


def assert_close(got, want, dtype):
    """float32: to 1e-6 of the largest entry; bfloat16: to one rounding
    of the result (an ulp of each entry, the sums' order being free)."""
    assert got.dtype == want.dtype and got.shape == want.shape
    got, want = (np.asarray(v, np.float32) for v in (got, want))
    if dtype == "float32":
        np.testing.assert_allclose(got, want, rtol=0,
                                   atol=1e-6 * np.abs(want).max())
    else:
        np.testing.assert_allclose(got, want, rtol=2.0 ** -7,
                                   atol=2.0 ** -9 * np.abs(want).max())


@pytest.mark.parametrize("gated", [False, True], ids=["plain", "gated"])
@pytest.mark.parametrize("silu", [False, True], ids=["linear", "silu"])
@pytest.mark.parametrize("biased", [False, True], ids=["nobias", "bias"])
@pytest.mark.parametrize("taps", [3, 4])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_kernels_give_the_jnp_forms_results_and_gradients(
        kernel_path, dtype, taps, biased, silu, gated):
    data, weight, bias, cot = operands(taps, dtype, taps, gated)
    bias = bias if biased else None
    before = telemetry.counter("causal_conv_kernel_traced")
    got = outputs(lm.sequence_conv, data, weight, bias, cot, silu, gated)
    assert telemetry.counter("causal_conv_kernel_traced") == before + 1
    want = outputs(jnp_form, data, weight, bias, cot, silu, gated)
    assert len(got) == len(want) == (4 if biased else 3)
    for a, b in zip(got, want):
        assert_close(a, b, dtype)


def oldest_tap(channels, taps):
    """A weight that reads only x_{t-K+1}: the result is the input moved
    K - 1 tokens down the sequence."""
    return jnp.zeros((channels, taps), jnp.float32).at[:, 0].set(1.0)


def apart(fn, x, *rest):
    """*fn* with every tile of the sequence handed over as a sequence of
    its own: what kernels that drop the tail between tiles compute."""
    batch, seq, channels = x.shape
    return fn(x.reshape(batch * seq // ROWS, ROWS, channels),
              *rest).reshape(x.shape)


@pytest.mark.parametrize("taps", [3, 4])
def test_the_tail_crosses_tile_edges_and_stops_at_a_batch_row(kernel_path,
                                                              taps):
    """Three tiles and two batch rows under a weight that reads the oldest
    tap alone: rows 0..K-2 of a tile come from the tile before, the first
    K - 1 of a batch row are the zeros before the sequence (not the row
    before's last tokens), and dx_t = dy_{t+K-1} reads the tile after."""
    rng = np.random.RandomState(taps)
    x = jnp.asarray(rng.randn(2, 3 * ROWS, 128) + 3.0, jnp.float32)
    dy = jnp.asarray(rng.randn(2, 3 * ROWS, 128) + 3.0, jnp.float32)
    w, lag = oldest_tap(128, taps), taps - 1

    def conv(v):
        return lm.sequence_conv(v, w)

    out, vjp = jax.vjp(conv, x)
    (dx,) = vjp(dy)
    moved = np.zeros(x.shape, np.float32)
    moved[:, lag:] = np.asarray(x)[:, :-lag]
    back = np.zeros(x.shape, np.float32)
    back[:, :-lag] = np.asarray(dy)[:, lag:]
    assert np.array_equal(np.asarray(out), moved)
    assert np.array_equal(np.asarray(dx), back)
    assert not np.asarray(out)[:, :lag].any()
    # kernels without the carried tail are told apart, both directions
    lost = apart(conv, x)
    assert np.array_equal(np.asarray(lost)[:, :ROWS], moved[:, :ROWS])
    assert not np.asarray(lost)[:, ROWS:ROWS + lag].any()
    assert np.abs(moved[:, ROWS:ROWS + lag]).min() > 0
    dx_lost = apart(lambda v: jax.vjp(conv, v)[1](
        dy.reshape(v.shape))[0], x)
    assert not np.asarray(dx_lost)[:, ROWS - lag:ROWS].any()
    assert np.abs(back[:, ROWS - lag:ROWS]).min() > 0


def test_the_gated_kernels_carry_the_product_of_both_gates(kernel_path):
    """The gated op's tail is (Bg u) of the tile before, and its one
    [B, S, 3C] gradient holds dBg, dCg, du in the data's column order."""
    data, weight, _, cot = operands(5, "float32", 3, True, channels=128)
    got = outputs(lm.sequence_conv, data, weight, None, cot, False, True)
    want = outputs(jnp_form, data, weight, None, cot, False, True)
    for a, b in zip(got, want):
        assert_close(a, b, "float32")
    bg, cg, u = jnp.split(data, 3, axis=-1)
    moved = np.zeros(bg.shape, np.float32)
    moved[:, 2:] = np.asarray(bg * u)[:, :-2]
    out = lm.sequence_conv(data, oldest_tap(128, 3), None, False, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(cg) * moved,
                               rtol=1e-6)
    for part in np.split(np.asarray(got[1]), 3, axis=-1):
        assert np.abs(part).min() > 0


@pytest.mark.parametrize("shape, taps, begin", [
    ((2, 11, 6), 4, 0), ((1, 96, 6), 3, 0), ((1, 40, 128), 3, 0),
    ((1, 96, 128), 9, 0), ((1, 40, 256), 3, 64)])
def test_shapes_the_kernels_do_not_take_fall_back(kernel_path, shape, taps,
                                                  begin):
    rng = np.random.RandomState(0)
    channels = 128 if begin else shape[2]
    x = jnp.asarray(rng.randn(*shape), jnp.float32)
    w = jnp.asarray(rng.randn(channels, taps), jnp.float32)
    assert not pk.causal_conv_kernel_fits(shape[1], channels, taps)
    before = {name: telemetry.counter(name) for name in COUNTERS}
    got = mx.nd.contrib.CausalConv1D(mx.nd.array(x), mx.nd.array(w),
                                     no_bias=True, begin=begin,
                                     end=begin + channels)
    after = {name: telemetry.counter(name) for name in COUNTERS}
    assert after["causal_conv_traced"] == before["causal_conv_traced"] + 1
    assert after["causal_conv_kernel_traced"] == \
        before["causal_conv_kernel_traced"]
    assert np.array_equal(got.asnumpy(), np.asarray(jnp_form(
        x[..., begin:begin + channels], w, None, True, False)))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_op_reads_its_channels_where_they_lie(kernel_path, dtype):
    """``begin`` / ``end``: channels 128..384 of a tensor of 640 are the
    input (Granite's xBC inside ``in_proj``'s output), read by the block
    map on the kernel path and sliced on the other; the gradient has the
    tensor's width and zeros where the op read nothing."""
    rng = np.random.RandomState(7)
    wide = jnp.asarray(rng.randn(2, 2 * ROWS, 640), dtype)
    w = jnp.asarray(rng.randn(256, 4) * 0.5, dtype)
    b = jnp.asarray(rng.randn(256), dtype)
    cot = jnp.asarray(rng.randn(2, 2 * ROWS, 256), jnp.float32)

    def loss(form):
        def fn(v, w, b):
            out = form(v, w, b, True, False, 128)
            return jnp.sum(out.astype(jnp.float32) * cot), out
        return jax.grad(fn, (0, 1, 2), has_aux=True)

    before = telemetry.counter("causal_conv_kernel_traced")
    grads, out = loss(lm.sequence_conv)(wide, w, b)
    assert telemetry.counter("causal_conv_kernel_traced") == before + 1
    want_grads, want = loss(jnp_form)(wide, w, b)
    assert_close(out, want, dtype)
    assert_close(out, jnp_form(wide[..., 128:384], w, b, True, False),
                 dtype)
    for a, b_ in zip(grads, want_grads):
        assert_close(a, b_, dtype)
    dwide = np.asarray(grads[0], np.float32)
    assert not dwide[..., :128].any() and not dwide[..., 384:].any()
    assert np.abs(dwide[..., 128:384]).min() > 0
    with pytest.raises(ValueError, match="do not match"):
        mx.nd.contrib.CausalConv1D(
            mx.nd.array(np.zeros((1, 8, 640), np.float32)),
            mx.nd.array(np.zeros((256, 4), np.float32)), no_bias=True,
            begin=128, end=400)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_a_begin_inside_a_channel_tile_keeps_the_kernels(kernel_path, dtype):
    """Channels 64..320 of a tensor of 448 start inside a tile of 128: the
    block map cannot start there, so the kernels are handed a slice (the
    one copy ``begin`` was to save) and still run, forward and backward;
    the gradient has the tensor's width and zeros where the op read
    nothing."""
    assert pk.causal_conv_kernel_fits(2 * ROWS, 256, 4)
    assert not pk.causal_conv_reads_in_place(256, 64)
    rng = np.random.RandomState(11)
    wide = jnp.asarray(rng.randn(2, 2 * ROWS, 448), dtype)
    w = jnp.asarray(rng.randn(256, 4) * 0.5, dtype)
    b = jnp.asarray(rng.randn(256), dtype)
    cot = jnp.asarray(rng.randn(2, 2 * ROWS, 256), jnp.float32)

    def grads(form):
        def fn(v, w, b):
            out = form(v, w, b, True, False, 64)
            return jnp.sum(out.astype(jnp.float32) * cot), out
        return jax.grad(fn, (0, 1, 2), has_aux=True)(wide, w, b)

    before = telemetry.counter("causal_conv_kernel_traced")
    got, out = grads(lm.sequence_conv)
    assert telemetry.counter("causal_conv_kernel_traced") == before + 1
    want, want_out = grads(jnp_form)
    assert_close(out, want_out, dtype)
    for a, b_ in zip(got, want):
        assert_close(a, b_, dtype)
    dwide = np.asarray(got[0], np.float32)
    assert dwide.shape == wide.shape
    assert not dwide[..., :64].any() and not dwide[..., 320:].any()
    assert np.abs(dwide[..., 64:320]).min() > 0


def run_ops(seq):
    rng = np.random.RandomState(1)
    x = mx.nd.array(rng.randn(1, seq, 128).astype(np.float32))
    data = mx.nd.array(rng.randn(1, seq, 384).astype(np.float32))
    w = mx.nd.array(rng.randn(128, 3).astype(np.float32))
    before = [telemetry.counter(name) for name in COUNTERS]
    plain = mx.nd.contrib.CausalConv1D(x, w, no_bias=True)
    middle = [telemetry.counter(name) for name in COUNTERS]
    gated = mx.nd.contrib.ShortConv(data, w)
    after = [telemetry.counter(name) for name in COUNTERS]
    return (plain.asnumpy(), gated.asnumpy(),
            [b - a for a, b in zip(before, middle)],
            [b - a for a, b in zip(middle, after)])


def test_both_ops_count_on_either_path_and_the_kernel_counter_on_one(
        request):
    """``causal_conv_traced`` / ``short_conv_traced`` once an op whatever
    runs it; ``causal_conv_kernel_traced`` once an op on the kernel path
    alone; the counter has its line in the telemetry table."""
    assert "Pallas" in telemetry.core.COUNTERS["causal_conv_kernel_traced"]
    plain, gated, by_plain, by_gated = run_ops(2 * ROWS)
    assert by_plain == [1, 0, 0] and by_gated == [0, 1, 0]
    request.getfixturevalue("kernel_path")
    k_plain, k_gated, by_plain, by_gated = run_ops(2 * ROWS)
    assert by_plain == [1, 0, 1] and by_gated == [0, 1, 1]
    np.testing.assert_allclose(k_plain, plain, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(k_gated, gated, rtol=1e-5, atol=1e-6)


def test_the_choice_reads_shapes_and_the_backend_alone():
    """Off a TPU the ``jnp`` form runs whatever the shape; on one the
    kernels take whole lane tiles of channels, whole sequence tiles and at
    most eight taps."""
    assert not lm._kernel_backend()
    assert pk.causal_conv_kernel_fits(16384, 4352, 4)
    assert pk.causal_conv_kernel_fits(8192, 2048, 3)
    assert pk._conv_lanes(4352) == 256 and pk._conv_lanes(2048) == 512
    for seq, channels, taps in (
            (16384, 4352, 9), (16000, 4352, 4), (16384, 4300, 4),
            (11, 6, 4)):
        assert not pk.causal_conv_kernel_fits(seq, channels, taps)
    assert pk.causal_conv_reads_in_place(4352, 4096)
    assert not pk.causal_conv_reads_in_place(4352, 4224)
