"""The no-chip half of the TPU bring-up (ISSUE 21): what can be proven
about the chip path from a host without one.

* the chip drivers refuse to run — and run nothing — without a TPU;
* an accelerator context never resolves to a CPU device;
* the compile cache is placed from outside or at one fixed path;
* the Pallas tier compiles for a v5e with the real Mosaic/XLA:TPU
  compilers against libtpu's compile-only topology.

The chip half is ``python chip_smoke.py`` on a machine with a TPU.
"""
import os
import subprocess
import sys

import pytest

import mxnet_tpu as mx

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, env=None, cwd=REPO, timeout=300):
    """Run python with JAX_PLATFORMS=cpu; an env value of None unsets."""
    full_env = dict(os.environ, JAX_PLATFORMS="cpu")
    for key, val in (env or {}).items():
        if val is None:
            full_env.pop(key, None)
        else:
            full_env[key] = val
    return subprocess.run([sys.executable] + args, cwd=cwd, env=full_env,
                          capture_output=True, text=True, timeout=timeout)


@pytest.mark.parametrize("script", ["chip_smoke.py"])
def test_chip_drivers_refuse_to_run_on_cpu(script):
    proc = _run([os.path.join(REPO, script)])
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr            # names the platform it found
    assert "== phase" not in proc.stdout     # executed no phase
    last = proc.stdout.strip().splitlines()[-1:] or [""]
    assert not last[0].startswith("{")       # and printed no result


def test_accelerator_context_never_resolves_to_cpu():
    assert mx.context.num_gpus() == 0 and mx.context.num_tpus() == 0
    for ctx in (mx.tpu(0), mx.gpu(0), mx.tpu(3)):
        with pytest.raises(mx.MXNetError, match="not attached"):
            ctx.jax_device
    # constructing the name is still free, and the default stays the host
    assert str(mx.tpu(1)) == "tpu(1)"
    assert mx.current_context() == mx.cpu(0)
    assert mx.cpu(0).jax_device.platform == "cpu"


def test_deferred_init_of_a_cast_net_sees_the_input_dtype():
    """examples/train_imagenet.py --dtype bfloat16: the first forward of
    a cast() net finishes deferred init by tracing with the INPUT's
    dtype (it failed the conv dtype check against default-f32 vars)."""
    import numpy as np
    from mxnet_tpu import gluon
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Conv2D(4, 3, padding=1), gluon.nn.BatchNorm(),
            gluon.nn.Conv2D(4, 3, padding=1, in_channels=4),
            gluon.nn.Dense(3))
    net.collect_params().initialize(mx.init.Xavier())
    net.cast("bfloat16")
    net.hybridize()
    out = net(mx.nd.array(np.ones((2, 3, 8, 8), np.float32))
              .astype("bfloat16"))
    assert out.shape == (2, 3) and str(out.dtype) == "bfloat16"


_PRINT_CACHE_DIR = ("import mxnet_tpu, jax; "
                    "print(jax.config.jax_compilation_cache_dir)")


def test_compile_cache_default_is_one_fixed_path(tmp_path):
    seen = set()
    for cwd in (REPO, str(tmp_path)):        # two interpreter starts
        proc = _run(["-c", _PRINT_CACHE_DIR], cwd=cwd,
                    env={"JAX_COMPILATION_CACHE_DIR": None,
                         "PYTHONPATH": REPO})
        assert proc.returncode == 0, proc.stderr
        seen.add(proc.stdout.strip())
    assert seen == {os.path.join(REPO, ".jax_cache")}


def test_compile_cache_placed_from_outside_is_left_alone(tmp_path):
    placed = str(tmp_path / "placed")
    proc = _run(["-c", _PRINT_CACHE_DIR],
                env={"JAX_COMPILATION_CACHE_DIR": placed, "PYTHONPATH": REPO},
                cwd=str(tmp_path))
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip() == placed


def test_pallas_tier_compiles_for_v5e_without_a_chip():
    proc = _run([os.path.join(REPO, "tests", "tpu_aot_compile.py")],
                timeout=600)
    if proc.returncode == 3:
        pytest.skip(proc.stdout.strip().splitlines()[-1])
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-4000:]
    done = [ln for ln in proc.stdout.splitlines() if ln.startswith("AOT ok")]
    assert len(done) == 22, done


@pytest.mark.slow
def test_chip_smoke_cpu_rehearsal_passes_but_is_not_a_chip_result():
    """Same code at toy size on 8 virtual host devices (all four phases);
    the result line can never be mistaken for a chip pass."""
    import json
    proc = _run([os.path.join(REPO, "chip_smoke.py"), "--rehearse-cpu"],
                env={"XLA_FLAGS": "--xla_force_host_platform_device_count=8"},
                timeout=900)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-4000:]
    lines = proc.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert result == {"ok": False, "rehearsal": "cpu",
                      "device": {"platform": "cpu", "kind": "cpu",
                                 "count": 8}}
    obs = json.loads(lines[-3].split("observations ", 1)[1])
    assert set(obs["phases"]) == {"train", "serve", "kernels", "four_chip"}
    assert isinstance(obs["phases"]["four_chip"], dict)    # it ran
    assert "CPU REHEARSAL" in proc.stdout
