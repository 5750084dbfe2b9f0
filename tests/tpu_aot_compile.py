"""Worker for tests/test_tpu_bringup.py: compile the Pallas tier for a
TPU v5e WITHOUT a chip.

libtpu can describe a topology with no hardware behind it
(``jax.experimental.topologies``); lowering against its devices runs the
real Mosaic and XLA:TPU compilers.  That catches what lowering alone
(``lower(lowering_platforms=("tpu",))``) cannot — e.g. an i64 block index
that only Mosaic's legalizer refuses — for no chip time.  Nothing is
executed.  Runs in its own process because it loads libtpu.

Prints one ``AOT ok <what>`` line per compiled program; exit 3 means
libtpu could not describe the topology here (the caller skips).
"""
import functools
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
# quiet libtpu's metadata-server probing; there is no TPU VM around us
os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
os.environ.setdefault("TPU_SKIP_MDS_QUERY", "true")

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import NamedSharding, SingleDeviceSharding  # noqa: E402

import mxnet_tpu  # noqa: E402,F401 (x64 + cache placement)
from mxnet_tpu.models import transformer as T  # noqa: E402
from mxnet_tpu.ops.pallas_kernels import (flash_attention,  # noqa: E402
                                          power_retention)
from mxnet_tpu.parallel.mesh import filter_spec, make_mesh  # noqa: E402


def main():
    assert jax.config.jax_enable_x64        # the package's real config
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:                # no usable libtpu here
        print("no compile-only TPU topology: %r" % (exc,))
        sys.exit(3)
    one = SingleDeviceSharding(topo.devices[0])

    for dtype in (jnp.bfloat16, jnp.float32):
        for seq, dim, causal in ((128, 64, True), (1000, 64, True),
                                 (1024, 128, False), (4096, 128, True)):
            spec = jax.ShapeDtypeStruct((1, 2, seq, dim), dtype,
                                        sharding=one)
            jax.jit(functools.partial(flash_attention, causal=causal)) \
                .lower(spec, spec, spec).compile()
            print("AOT ok flash %s S=%d D=%d causal=%s"
                  % (jnp.dtype(dtype).name, seq, dim, causal), flush=True)

    # grouped key/value heads at the LFM2 widths (32 query heads over 8,
    # head size 64, 8,192 tokens, blocks of 512) through the graph op's
    # forward and its chunked backward, and the sparse-expert layer at the
    # published widths (8 of its experts, 2,048 tokens): every grouped
    # product a kernel — JAX's Pallas gmm/tgmm as on a TPU (the selector
    # looks at the live backend, which is the CPU here: force its choice),
    # and XLA:TPU's own behind ragged_dot, never one dense product a group
    from mxnet_tpu.ops import lm, moe
    q = jax.ShapeDtypeStruct((1, 8192, 32, 64), jnp.bfloat16, sharding=one)
    kv = jax.ShapeDtypeStruct((1, 8192, 8, 64), jnp.bfloat16, sharding=one)
    lowered = jax.jit(jax.value_and_grad(
        lambda *x: lm.causal_attention(*x, 0.125, True, True)
        .astype(jnp.float32).sum(), (0, 1, 2))).lower(q, kv, kv)
    assert "tpu_custom_call" in lowered.as_text()
    lowered.compile()
    print("AOT ok causal_attention grouped heads grad", flush=True)

    # the banded grids at the Trinity widths (32 query heads over 4, head
    # size 128, a window of 2,048 in 16,384 tokens): five of 32 tiles on
    # the innermost axis of all three kernels
    q = jax.ShapeDtypeStruct((1, 16384, 32, 128), jnp.bfloat16, sharding=one)
    kv = jax.ShapeDtypeStruct((1, 16384, 4, 128), jnp.bfloat16, sharding=one)
    lowered = jax.jit(jax.value_and_grad(
        lambda *x: lm.causal_attention(*x, 128 ** -0.5, True, True, 2048)
        .astype(jnp.float32).sum(), (0, 1, 2))).lower(q, kv, kv)
    assert "tpu_custom_call" in lowered.as_text()
    text = lowered.compile().as_text()
    assert "window_attention_bwd" in text and \
        "causal_attention" not in text
    print("AOT ok window attention banded grad", flush=True)

    def spec(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    # a quarter share at the Trinity widths (32 of 128 experts of 1,024,
    # top 8, 4,096 tokens): chunks of 12,288 of the 32,768 routed rows,
    # every grouped product a Pallas kernel, the loop over later chunks
    # in the program
    moe._kernel_backend = lambda: True
    text = jax.jit(jax.value_and_grad(
        lambda *x: moe.sparse_moe(*x, num_experts=128, num_experts_per_tok=8)
        [0].astype(jnp.float32).sum(), (0, 1, 2, 3, 4))).lower(
        spec(4096, 2048), spec(128, 2048), spec(32, 2048, 1024),
        spec(32, 2048, 1024), spec(32, 1024, 2048),
        spec(128, dtype=jnp.float32)).compile().as_text()
    assert text.count("%gmm") >= 9 and "while" in text
    assert "bf16[12288,2048]" in text and "bf16[32768,2048]" not in text
    print("AOT ok sparse_moe quarter share, bounded chunks", flush=True)

    def layer(*x):
        return moe.sparse_moe(*x, num_experts=8, num_experts_per_tok=4)[0] \
            .astype(jnp.float32).sum()
    shapes = (spec(2048, 2048), spec(8, 2048), spec(8, 2048, 1792),
              spec(8, 2048, 1792), spec(8, 1792, 2048),
              spec(8, dtype=jnp.float32))
    for on_tpu, kernel in ((True, "gmm"), (False, "ragged-dot-none")):
        moe._kernel_backend = lambda on_tpu=on_tpu: on_tpu
        # a fresh function each time: a trace is cached by function
        text = jax.jit(jax.value_and_grad(functools.partial(layer),
                                          (0, 1, 2, 3, 4))) \
            .lower(*shapes).compile().as_text()
        assert text.count("%" + kernel) >= 9, (kernel, text.count(kernel))
        assert "convolution-base-dilated" not in text
        print("AOT ok sparse_moe grad, %s kernels only" % kernel,
              flush=True)

    # power retention at the Brumby widths (5 query heads a key/value
    # head of 128, chunks of 1024): the Pallas forward, which writes the
    # outputs alone, and through the gradient the states kernel that
    # remakes the chunk states before the chunked jnp backward reads them
    for seq, grad in ((4096, False), (3000, True)):
        q = jax.ShapeDtypeStruct((1, seq, 10, 128), jnp.bfloat16,
                                 sharding=one)
        kv = jax.ShapeDtypeStruct((1, seq, 2, 128), jnp.bfloat16,
                                  sharding=one)
        gate = jax.ShapeDtypeStruct((1, seq, 2), jnp.float32, sharding=one)

        def retain(q, k, v, a):
            return power_retention(q, k, v, a, 1024, 1e-6, True)
        fn = jax.grad(lambda *x: retain(*x).astype(jnp.float32).sum(),
                      (0, 1, 2, 3)) if grad else retain
        lowered = jax.jit(fn).lower(q, kv, kv, gate)
        assert "tpu_custom_call" in lowered.as_text()
        compiled = lowered.compile()
        kernels = [name for name in ("power_retention_fwd",
                                     "power_retention_bwd_states")
                   if "%" + name in compiled.as_text()]
        # the gradient alone reads no output: its program holds the
        # states kernel and no forward
        assert kernels == ["power_retention_bwd_states" if grad
                           else "power_retention_fwd"], kernels
        mem = compiled.memory_analysis()
        print("AOT ok power_retention S=%d grad=%s: %s, %.3f GB of "
              "arguments, outputs and temporaries"
              % (seq, grad, kernels[0],
                 (mem.argument_size_in_bytes + mem.output_size_in_bytes +
                  mem.temp_size_in_bytes) / 1e9), flush=True)

    # the state-space scan at the Granite widths (64 heads of 64 in one
    # group, state 128, chunks of 256, 16,384 tokens, 32 heads a
    # program): the forward kernel alone, and through the gradient the
    # states pass and the reverse pass, which read no output
    from mxnet_tpu.ops.pallas_kernels import state_space_scan
    operands = (spec(1, 16384, 64, 64),
                spec(1, 16384, 64, dtype=jnp.float32),
                spec(64, dtype=jnp.float32), spec(1, 16384, 1, 128),
                spec(1, 16384, 1, 128), spec(64, dtype=jnp.float32))

    def scan(*x):
        return state_space_scan(*x, 256, True)
    for grad in (False, True):
        fn = jax.grad(lambda *x: scan(*x).astype(jnp.float32).sum(),
                      tuple(range(6))) if grad else scan
        text = jax.jit(fn).lower(*operands).compile().as_text()
        kernels = [name for name in ("state_space_fwd",
                                     "state_space_bwd_states",
                                     "state_space_bwd")
                   if "%" + name + "." in text or "%" + name + " " in text]
        assert kernels == (["state_space_bwd_states", "state_space_bwd"]
                           if grad else ["state_space_fwd"]), kernels
        print("AOT ok state_space_scan grad=%s: %s" % (grad, kernels),
              flush=True)

    # the sequence convolution at both cells' widths through the graph
    # ops' own function: Granite's (4,352 channels of in_proj's 8,512 read
    # at column 4,096, 4 taps, bias, SiLU, one document of 16,384) and
    # LFM2's (gated, 2,048 channels in a [2, 8192, 6144] input, 3 taps),
    # forward and backward kernels, and no slice of the wide input left
    # beside them; then Granite's with the channels at column 64, inside
    # a channel tile, where the kernels stay and are handed a slice
    lm._kernel_backend = lambda: True
    for name, data, weight, bias, kw in (
            ("granite", spec(1, 16384, 8512), spec(4352, 4), spec(4352),
             dict(silu=True, begin=4096)),
            ("lfm2", spec(2, 8192, 6144), spec(2048, 3), None,
             dict(gated=True)),
            ("granite at column 64", spec(1, 16384, 8512), spec(4352, 4),
             spec(4352), dict(silu=True, begin=64))):
        def conv(d, w, *b, kw=kw):
            return lm.sequence_conv(d, w, *b, **kw).astype(jnp.float32).sum()
        args = (data, weight) + (() if bias is None else (bias,))
        text = jax.jit(jax.value_and_grad(conv, tuple(range(len(args))))) \
            .lower(*args).compile().as_text()
        assert "causal_conv_fwd" in text and "%causal_conv_bwd" in text
        assert (" slice(" in text.split("ENTRY")[1]) == \
            bool(kw.get("begin", 0) % 256), name
        print("AOT ok sequence convolution %s grad" % name, flush=True)

    # the kernel from mx.pallas's docstring, through the op registry
    # (same kernel and helper the interpret-mode tests use)
    from test_pallas_register import _register_scale, _registered_fn
    _register_scale("aot_scale", interpret=False)
    jax.jit(functools.partial(_registered_fn("aot_scale"), alpha=3.0)) \
        .lower(jax.ShapeDtypeStruct((8, 128), jnp.float32, sharding=one)) \
        .compile()
    print("AOT ok registered kernel", flush=True)

    # a transformer train step through the kernel, under shard_map on a
    # data=2 x model=2 mesh (the selector looks at the live backend,
    # which is the CPU here: force the TPU choice)
    T._use_flash = lambda s: s >= 128
    lm = T.TransformerLMConfig(vocab=512, d_model=256, n_heads=4, d_ff=512,
                               n_layers=1, max_len=1024, dtype=jnp.bfloat16)
    mesh = make_mesh({"data": 2, "model": 2}, topo.devices)
    params = {
        n: jax.ShapeDtypeStruct(shape, lm.dtype, sharding=NamedSharding(
            mesh, filter_spec(spec, mesh)))
        for n, (shape, spec) in T._param_specs(lm).items()}
    tokens = jax.ShapeDtypeStruct(
        (4, lm.max_len), jnp.int32, sharding=NamedSharding(
            mesh, filter_spec(T.P("data", "seq"), mesh)))
    lowered = T.make_train_step(lm, mesh).lower(params, tokens, tokens)
    assert "tpu_custom_call" in lowered.as_text()
    lowered.compile()
    print("AOT ok transformer step on %s" % dict(mesh.shape), flush=True)


if __name__ == "__main__":
    main()
