"""Preemption-safe training (ISSUE 7): async sharded checkpoints,
SIGTERM-to-resume, elastic restart.

Acceptance contract: ``kill -TERM`` mid-run in a subprocess → final
synchronous checkpoint at the next step boundary → resume → bitwise-
identical loss trajectory on CPU, including a resume with a different
(faked, ``MXNET_CKPT_SHARDS``) device count; a corrupt shard falls back
to the previous complete checkpoint without crashing; and no
``flight_*.json`` is ever tracked at the repo root.
"""
import glob
import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, checkpoint, gluon, telemetry
from mxnet_tpu.checkpoint import hooks, reshard
from mxnet_tpu.gluon import nn

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# in-process helpers
# ---------------------------------------------------------------------------

def _build(seed=7):
    mx.random.seed(seed)
    np.random.seed(seed)
    net = nn.Sequential()
    net.add(nn.Dense(8, activation="relu"))
    net.add(nn.Dense(4))
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 0.05})
    rs = np.random.RandomState(3)
    data = mx.nd.array(rs.randn(32, 6).astype(np.float32))
    label = mx.nd.array(rs.randn(32, 4).astype(np.float32))
    it = mx.io.NDArrayIter(data, label, batch_size=8, shuffle=True,
                           last_batch_handle="discard")
    return net, trainer, it


def _run_steps(net, trainer, it, n):
    loss_fn = gluon.loss.L2Loss()
    losses = []
    for _ in range(n):
        try:
            batch = it.next()
        except StopIteration:
            it.reset()
            batch = it.next()
        with autograd.record():
            loss = loss_fn(net(batch.data[0]), batch.label[0])
        loss.backward()
        trainer.step(8)
        losses.append(float(np.float64(loss.asnumpy().sum())))
    return losses


@pytest.fixture(autouse=True)
def _detach_manager():
    """No CheckpointManager may leak into other tests' Trainer.step."""
    yield
    m = hooks.active()
    if m is not None:
        hooks.unregister(m)


# ---------------------------------------------------------------------------
# async snapshot + elastic restore (in-process)
# ---------------------------------------------------------------------------

def test_async_save_restore_bitwise(tmp_path):
    """Resume from an async snapshot — with a CHANGED shard count — and
    the loss trajectory is bitwise-identical to an uninterrupted run."""
    net, tr, it = _build()
    ref = _run_steps(net, tr, it, 8)

    d = str(tmp_path / "ckpt")
    net, tr, it = _build()
    first = _run_steps(net, tr, it, 4)
    mgr = checkpoint.CheckpointManager(d, trainer=tr, data_iter=it,
                                       num_shards=4)
    assert mgr.save(4, sync=True), mgr.last_error
    mgr.close()

    net2, tr2, it2 = _build()
    mgr2 = checkpoint.CheckpointManager(d, trainer=tr2, data_iter=it2,
                                        num_shards=2)   # elastic: 4 -> 2
    assert mgr2.restore() == 4
    rest = _run_steps(net2, tr2, it2, 4)
    mgr2.close()
    assert first + rest == ref


def test_manifest_shards_and_checksums(tmp_path):
    net, tr, it = _build()
    _run_steps(net, tr, it, 2)
    mgr = checkpoint.CheckpointManager(str(tmp_path), trainer=tr,
                                       data_iter=it, num_shards=4)
    assert mgr.save(2, sync=True)
    view = checkpoint.http_view()
    assert view["active"] and view["checkpoints"][0]["step"] == 2
    mgr.close()
    (cdir,) = glob.glob(str(tmp_path / "ckpt-*"))
    manifest = json.loads(open(os.path.join(cdir, "manifest.json")).read())
    assert manifest["complete"] and manifest["step"] == 2
    assert manifest["n_shards"] == 4
    optim_shards = [n for n in manifest["files"] if n.startswith("optim-")]
    assert len(optim_shards) == 4          # one shard per (faked) replica
    for name, meta in manifest["files"].items():
        path = os.path.join(cdir, name)
        assert os.path.getsize(path) == meta["bytes"]
    assert telemetry.gauge("checkpoint_last_step") == 2
    assert telemetry.gauge("checkpoint_bytes") > 0


def test_corrupt_shard_falls_back_to_previous(tmp_path):
    """A torn/corrupt newest checkpoint is skipped, not fatal."""
    d = str(tmp_path)
    net, tr, it = _build()
    _run_steps(net, tr, it, 2)
    mgr = checkpoint.CheckpointManager(d, trainer=tr, data_iter=it,
                                       num_shards=2, keep=5)
    assert mgr.save(2, sync=True)
    want = {i: p.data().asnumpy().copy()
            for i, p in enumerate(tr._params)}
    _run_steps(net, tr, it, 2)
    assert mgr.save(4, sync=True)
    mgr.close()

    # flip one byte in the newest checkpoint's first optimizer shard
    (shard,) = glob.glob(os.path.join(d, "ckpt-*4", "optim-00000-*"))
    blob = bytearray(open(shard, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    open(shard, "wb").write(bytes(blob))

    before = telemetry.counter("checkpoint_restore_fallbacks")
    net2, tr2, it2 = _build()
    mgr2 = checkpoint.CheckpointManager(d, trainer=tr2, data_iter=it2,
                                        num_shards=2)
    assert mgr2.restore() == 2             # fell back, did not crash
    mgr2.close()
    assert telemetry.counter("checkpoint_restore_fallbacks") > before
    for i, p in enumerate(tr2._params):
        np.testing.assert_array_equal(p.data().asnumpy(), want[i])


def test_missing_manifest_falls_back(tmp_path):
    d = str(tmp_path)
    net, tr, it = _build()
    _run_steps(net, tr, it, 1)
    mgr = checkpoint.CheckpointManager(d, trainer=tr, data_iter=it,
                                       num_shards=1, keep=5)
    assert mgr.save(1, sync=True)
    _run_steps(net, tr, it, 1)
    assert mgr.save(2, sync=True)
    mgr.close()
    os.remove(glob.glob(os.path.join(d, "ckpt-*2", "manifest.json"))[0])
    net2, tr2, it2 = _build()
    mgr2 = checkpoint.CheckpointManager(d, trainer=tr2, data_iter=it2,
                                        num_shards=1)
    assert mgr2.restore() == 1
    mgr2.close()


def test_retention_keeps_newest_complete(tmp_path):
    net, tr, it = _build()
    mgr = checkpoint.CheckpointManager(str(tmp_path), trainer=tr,
                                       data_iter=it, num_shards=1, keep=2)
    for step in (1, 2, 3, 4):
        _run_steps(net, tr, it, 1)
        assert mgr.save(step, sync=True)
    mgr.close()
    steps = sorted(int(os.path.basename(p).split("-")[1])
                   for p in glob.glob(str(tmp_path / "ckpt-*")))
    assert steps == [3, 4]


def test_write_retries_with_backoff(tmp_path, monkeypatch):
    """A transient commit failure retries (with the counter bumped) and
    the checkpoint still lands."""
    net, tr, it = _build()
    _run_steps(net, tr, it, 1)
    mgr = checkpoint.CheckpointManager(str(tmp_path), trainer=tr,
                                       data_iter=it, num_shards=1,
                                       retries=3)
    real = mgr._commit
    calls = {"n": 0}

    def flaky(snap):
        calls["n"] += 1
        if calls["n"] == 1:
            raise OSError("transient ENOSPC")
        return real(snap)

    monkeypatch.setattr(mgr, "_commit", flaky)
    before = telemetry.counter("checkpoint_write_retries")
    assert mgr.save(1, sync=True)
    mgr.close()
    assert calls["n"] == 2
    assert telemetry.counter("checkpoint_write_retries") == before + 1


def test_failed_save_can_be_reattempted(tmp_path, monkeypatch):
    """Exhausting all retries must not dedupe the step forever: an
    explicit later save of the same step re-captures and commits."""
    net, tr, it = _build()
    _run_steps(net, tr, it, 1)
    mgr = checkpoint.CheckpointManager(str(tmp_path), trainer=tr,
                                       data_iter=it, num_shards=1,
                                       retries=2)
    real = mgr._commit
    fail = {"on": True}

    def flaky(snap):
        if fail["on"]:
            raise OSError("transient ENOSPC")
        return real(snap)

    monkeypatch.setattr(mgr, "_commit", flaky)
    monkeypatch.setattr(checkpoint.manager.time, "sleep", lambda s: None)
    assert not mgr.save(1, sync=True)      # both attempts fail
    assert mgr.last_error is not None
    fail["on"] = False                     # "disk freed"
    assert mgr.save(1, sync=True), "retry of a failed step was deduped"
    assert mgr.last_committed_step == 1
    mgr.close()


def test_restore_survives_incompatible_iterator_state(tmp_path):
    """A checkpoint whose cursor cannot be applied to the CURRENT
    iterator type still restores the model state (no fallback onto
    already-applied params, no crash) — the stream just restarts."""
    net, tr, it = _build()
    _run_steps(net, tr, it, 2)
    mgr = checkpoint.CheckpointManager(str(tmp_path), trainer=tr,
                                       data_iter=it, num_shards=1)
    assert mgr.save(2, sync=True)
    want = {i: p.data().asnumpy().copy() for i, p in enumerate(tr._params)}
    mgr.close()

    class AlienIter:
        def get_checkpoint_state(self):
            return {"alien": True}

        def set_checkpoint_state(self, state):
            raise KeyError("cur")          # foreign cursor dict

    net2, tr2, _ = _build()
    mgr2 = checkpoint.CheckpointManager(str(tmp_path), trainer=tr2,
                                        data_iter=AlienIter(),
                                        num_shards=1)
    assert mgr2.restore() == 2
    mgr2.close()
    for i, p in enumerate(tr2._params):
        np.testing.assert_array_equal(p.data().asnumpy(), want[i])


def test_periodic_saves_from_step_boundaries(tmp_path):
    """every_steps rides the Trainer.step hook: no manual save calls."""
    net, tr, it = _build()
    mgr = checkpoint.CheckpointManager(str(tmp_path), trainer=tr,
                                       data_iter=it, num_shards=1,
                                       every_steps=2, keep=10)
    _run_steps(net, tr, it, 5)
    mgr.wait()
    mgr.close()
    steps = sorted(int(os.path.basename(p).split("-")[1])
                   for p in glob.glob(str(tmp_path / "ckpt-*")))
    assert steps == [2, 4]
    assert mgr.step == 5


def test_close_restores_sigterm_chain(tmp_path):
    """A closed manager must not keep owning SIGTERM: its boundaries
    will never fire again, so the signal must flow to the previous
    handler (the flight recorder's) instead of being swallowed."""
    net, tr, it = _build()
    prev = signal.getsignal(signal.SIGTERM)
    mgr = checkpoint.CheckpointManager(str(tmp_path), trainer=tr,
                                       data_iter=it, num_shards=1)
    mgr.install_preemption_handler()
    assert signal.getsignal(signal.SIGTERM) == mgr._on_sigterm
    mgr._grace_secs = 3600                 # regression must not kill pytest
    mgr._on_sigterm(signal.SIGTERM, None)  # preemption pending, timer armed
    assert mgr.preempt_pending()
    mgr.close()
    assert signal.getsignal(signal.SIGTERM) == prev
    assert not mgr._writer.is_alive()     # thread actually stopped
    # the armed grace timer must die with the manager, not os._exit a
    # process that moved on to post-run work
    assert mgr._grace_timer is None and not mgr.preempt_pending()


def test_restore_nothing_returns_none(tmp_path):
    net, tr, it = _build()
    mgr = checkpoint.CheckpointManager(str(tmp_path), trainer=tr,
                                       data_iter=it, num_shards=1)
    assert mgr.restore() is None
    mgr.close()


# ---------------------------------------------------------------------------
# reshard layout (pure)
# ---------------------------------------------------------------------------

def test_reshard_layout_deterministic_and_complete():
    slots = [9, 1, 5, 0, 3]
    # layout is a pure function of (slots, n): round-robin over sorted ids
    assert reshard.assign_slots(slots, 3) == [[0, 5], [1, 9], [3]]
    assert sorted(sum(reshard.assign_slots(slots, 3), [])) == sorted(slots)
    # every slot lands in exactly one target shard for any m/n
    for n_from in (1, 2, 4, 8):
        for n_to in (1, 3, 5):
            parts = reshard.assign_slots(range(11), n_to)
            seen = sum(parts, [])
            assert sorted(seen) == list(range(11))
            moves = reshard.redistribution_plan(range(11), n_from, n_to)
            assert all(src != dst for _, src, dst in moves)


def test_reshard_merge_rejects_duplicate_slots():
    with pytest.raises(ValueError):
        reshard.merge_into({0: "a"}, {0: "b"})


def test_module_path_snapshot_restore(tmp_path):
    """The module/ fit-loop wiring: boundary saves fire from fit, and a
    module checkpoint restores params + optimizer state into a fresh
    Module (kvstore-resident updater included)."""
    from mxnet_tpu import symbol as sym

    def _mlp():
        net = sym.var("data")
        net = sym.FullyConnected(net, num_hidden=8, name="fc1")
        net = sym.Activation(net, act_type="relu", name="relu1")
        net = sym.FullyConnected(net, num_hidden=4, name="fc2")
        return sym.SoftmaxOutput(net, name="softmax")

    rng = np.random.RandomState(0)
    X = rng.randn(40, 6).astype(np.float32)
    y = rng.randint(0, 4, 40).astype(np.float32)
    train = mx.io.NDArrayIter(X, y, batch_size=10)
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    mgr = checkpoint.CheckpointManager(str(tmp_path), module=mod,
                                       data_iter=train, num_shards=2,
                                       every_steps=2, keep=10)
    mod.fit(train, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
            num_epoch=1)
    mgr.wait()
    assert mgr.step == 4                  # fit-loop boundaries observed
    assert glob.glob(str(tmp_path / "ckpt-*")), "no boundary saves"
    assert mgr.save(mgr.step, sync=True), mgr.last_error
    mgr.close()
    want_arg, want_aux = mod.get_params()

    mod2 = mx.mod.Module(_mlp(), context=mx.cpu())
    mod2.bind(data_shapes=train.provide_data,
              label_shapes=train.provide_label, for_training=True)
    mod2.init_params()
    mod2.init_optimizer(optimizer="sgd",
                        optimizer_params={"learning_rate": 0.1,
                                          "momentum": 0.9})
    mgr2 = checkpoint.CheckpointManager(str(tmp_path), module=mod2,
                                        data_iter=None, num_shards=1)
    assert mgr2.restore() == 4
    mgr2.close()
    got_arg, _ = mod2.get_params()
    for k in want_arg:
        np.testing.assert_array_equal(got_arg[k].asnumpy(),
                                      want_arg[k].asnumpy())


@pytest.mark.parametrize("order", ["overlapped", "serial"])
def test_module_mid_epoch_boundary_resumes_exactly(tmp_path, order):
    """A fused Module's fit runs one step ahead of its metric (ISSUE 30).
    The boundary after step 3 must still hold three steps' parameters and
    a cursor past batch 3: restoring it into a fresh module and finishing
    the epoch replays no step and skips none."""
    from mxnet_tpu import symbol as sym

    def build():
        net = sym.var("data")
        net = sym.FullyConnected(net, num_hidden=8, name="fc1")
        net = sym.Activation(net, act_type="relu", name="relu1")
        net = sym.FullyConnected(net, num_hidden=4, name="fc2")
        mod = mx.mod.Module(sym.SoftmaxOutput(net, name="softmax"),
                            context=mx.cpu())
        if order == "serial":
            fit_step = mod._fit_step

            def serial_step(batch):     # hands nothing back: settled at once
                fit_step(batch)

            mod._fit_step = serial_step
        rng = np.random.RandomState(0)
        train = mx.io.NDArrayIter(rng.randn(48, 6).astype(np.float32),
                                  rng.randint(0, 4, 48).astype(np.float32),
                                  batch_size=8, shuffle=True)
        return mod, train

    def fit(mod, train, callback=None):
        mod.fit(train, optimizer="sgd", num_epoch=1, eval_metric="acc",
                optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
                batch_end_callback=callback)
        return {k: v.asnumpy() for k, v in mod.get_params()[0].items()}

    mx.random.seed(5)
    mod, train = build()
    mgr = checkpoint.CheckpointManager(str(tmp_path), module=mod,
                                       data_iter=train, every_steps=3,
                                       keep=10)
    before = telemetry.counter("fit_step_overlapped")
    want = fit(mod, train)
    assert telemetry.counter("fit_step_overlapped") - before \
        == (5 if order == "overlapped" else 0)
    mgr.wait()
    assert mgr.step == 6
    mgr.close()

    mx.random.seed(99)              # the checkpoint brings its own stream
    mod2, train2 = build()
    mod2.bind(data_shapes=train2.provide_data,
              label_shapes=train2.provide_label, for_training=True)
    mod2.init_params()
    mod2.init_optimizer(optimizer="sgd",
                        optimizer_params={"learning_rate": 0.1,
                                          "momentum": 0.9})
    mgr2 = checkpoint.CheckpointManager(str(tmp_path), module=mod2,
                                        data_iter=train2)
    assert mgr2.restore(step=3) == 3
    assert (mgr2._epoch, mgr2._batch) == (0, 2)
    mgr2.close()
    seen = []
    with pytest.warns(UserWarning, match="already initialized"):
        got = fit(mod2, train2, callback=lambda p: seen.append(p.nbatch))
    assert seen == [0, 1, 2]        # the epoch's other three batches
    for name in want:
        np.testing.assert_array_equal(got[name], want[name], err_msg=name)


def test_kvstore_checkpoint_state_string_keyed_updater():
    """update_on_kvstore updaters key by param NAME (kvstore._updater_key
    falls through to the string): the checkpoint blob must round-trip
    string-keyed update counts, not assume int slots."""
    from mxnet_tpu import kvstore as kvs, optimizer as opt_mod

    def make_store():
        store = kvs.create("local")
        store.set_optimizer(opt_mod.create("adam", learning_rate=0.01))
        store.init("fc1_weight", mx.nd.ones((4, 3)))
        return store

    store = make_store()
    g = mx.nd.ones((4, 3))
    store.push("fc1_weight", [g])      # updater runs, t -> 1 (str key)
    blob = store.get_checkpoint_state()
    assert blob is not None

    fresh = make_store()
    fresh.set_checkpoint_state(blob)
    srv_opt = fresh._updater.optimizer
    assert srv_opt._index_update_count == {"fc1_weight": 1}
    assert srv_opt.num_update == 1
    st = store._updater.states["fc1_weight"]
    st2 = fresh._updater.states["fc1_weight"]
    np.testing.assert_array_equal(st[0].asnumpy(), st2[0].asnumpy())


def test_iterator_checkpoint_state_roundtrip():
    _, _, it = _build()
    it.next()
    it.next()
    state = it.get_checkpoint_state()
    a = it.next().data[0].asnumpy()
    it.set_checkpoint_state(state)
    b = it.next().data[0].asnumpy()
    np.testing.assert_array_equal(a, b)


def test_iterator_rejects_cursor_after_dataset_resize():
    """A cursor saved over N samples must not be silently applied to an
    M-sample dataset (stale permutation → garbage batches); the raise
    routes into the manager's non-fatal stream restart."""
    _, _, it = _build()
    state = it.get_checkpoint_state()
    rs = np.random.RandomState(0)
    bigger = mx.io.NDArrayIter(rs.randn(48, 6).astype(np.float32),
                               rs.randn(48, 4).astype(np.float32),
                               batch_size=8)
    with pytest.raises(ValueError):
        bigger.set_checkpoint_state(state)


# ---------------------------------------------------------------------------
# satellite: no flight dump may ever be tracked at the repo root
# ---------------------------------------------------------------------------

def test_no_flight_dumps_tracked_at_root():
    try:
        out = subprocess.run(["git", "-C", REPO, "ls-files"],
                             capture_output=True, text=True, timeout=60,
                             check=True).stdout
    except Exception:
        pytest.skip("git unavailable")
    tracked = [line for line in out.splitlines()
               if "/" not in line and line.startswith("flight_")
               and line.endswith(".json")]
    assert not tracked, "stray flight dumps tracked at repo root: %s" \
        % tracked
    # and the ignore rule that keeps them untracked must stay in place
    with open(os.path.join(REPO, ".gitignore")) as fh:
        assert "flight_*.json" in fh.read().split()


# ---------------------------------------------------------------------------
# SIGTERM fault injection (subprocess): the acceptance criteria
# ---------------------------------------------------------------------------

_TRAIN_SCRIPT = """
import json, os, sys, time
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import autograd, checkpoint, gluon
from mxnet_tpu.gluon import nn

total = int(os.environ["CKPT_TOTAL_STEPS"])
sleep_s = float(os.environ.get("CKPT_SLEEP_S", "0"))
mx.random.seed(11)
np.random.seed(11)
net = nn.Sequential()
net.add(nn.Dense(8, activation="relu"))
net.add(nn.Dense(4))
net.initialize()
trainer = gluon.Trainer(net.collect_params(), "adam",
                        {"learning_rate": 0.05})
rs = np.random.RandomState(3)
data = mx.nd.array(rs.randn(32, 6).astype(np.float32))
label = mx.nd.array(rs.randn(32, 4).astype(np.float32))
it = mx.io.NDArrayIter(data, label, batch_size=8, shuffle=True,
                       last_batch_handle="discard")
loss_fn = gluon.loss.L2Loss()
mgr = checkpoint.CheckpointManager(os.environ["CKPT_DIR"],
                                   trainer=trainer, data_iter=it,
                                   every_steps=1)
start = mgr.restore() or 0
checkpoint.install_preemption_handler(mgr)
out = open(os.environ["CKPT_LOSS_FILE"], "a")
print("START %d" % start, flush=True)
step = start
while step < total:
    try:
        batch = it.next()
    except StopIteration:
        it.reset()
        batch = it.next()
    with autograd.record():
        loss = loss_fn(net(batch.data[0]), batch.label[0])
    loss.backward()
    trainer.step(8)
    step += 1
    out.write(json.dumps({"step": step,
                          "loss": float(np.float64(
                              loss.asnumpy().sum()))}) + "\\n")
    out.flush()
    os.fsync(out.fileno())
    if sleep_s:
        time.sleep(sleep_s)
mgr.wait()
print("DONE", flush=True)
"""

_HANG_SCRIPT = """
import os, time
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import autograd, checkpoint, gluon
from mxnet_tpu.gluon import nn

net = nn.Sequential()
net.add(nn.Dense(4))
net.initialize()
trainer = gluon.Trainer(net.collect_params(), "sgd",
                        {"learning_rate": 0.1})
mgr = checkpoint.CheckpointManager(os.environ["CKPT_DIR"],
                                   trainer=trainer)
checkpoint.install_preemption_handler(mgr)
x = mx.nd.array(np.ones((2, 3), np.float32))
y = mx.nd.array(np.ones((2, 4), np.float32))
loss_fn = gluon.loss.L2Loss()
with autograd.record():
    loss = loss_fn(net(x), y)
loss.backward()
trainer.step(2)
print("READY", flush=True)
time.sleep(300)          # wedged: no step boundary will ever arrive
"""


def _spawn(tmp_path, body, name, extra_env=None):
    script = tmp_path / ("%s.py" % name)
    script.write_text(body)
    env = dict(os.environ,
               CKPT_DIR=str(tmp_path / "ckpt"),
               CKPT_LOSS_FILE=str(tmp_path / "losses.jsonl"),
               MXNET_FLIGHT_DIR=str(tmp_path),
               PYTHONPATH=REPO + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    env.update(extra_env or {})
    return subprocess.Popen([sys.executable, str(script)],
                            cwd=str(tmp_path), env=env,
                            stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE)


def _losses(path):
    if not os.path.exists(path):
        return {}
    table = {}
    with open(path) as fh:
        for line in fh:
            if line.strip():
                rec = json.loads(line)
                table[rec["step"]] = rec["loss"]
    return table


def test_kill_term_resume_bitwise_trajectory(tmp_path):
    """The acceptance run: SIGTERM mid-step → final checkpoint → resume
    with a DIFFERENT faked device count → bitwise-matching loss
    trajectory vs an uninterrupted run."""
    total = 10
    # uninterrupted reference
    ref_dir = tmp_path / "ref"
    ref_dir.mkdir()
    proc = _spawn(ref_dir, _TRAIN_SCRIPT, "ref",
                  {"CKPT_TOTAL_STEPS": str(total)})
    out, err = proc.communicate(timeout=240)
    assert proc.returncode == 0, err.decode()[-2000:]
    ref = _losses(str(ref_dir / "losses.jsonl"))
    assert sorted(ref) == list(range(1, total + 1))

    # interrupted run: 4 optimizer shards, SIGTERM after a few steps
    run_dir = tmp_path / "run"
    run_dir.mkdir()
    loss_file = str(run_dir / "losses.jsonl")
    proc = _spawn(run_dir, _TRAIN_SCRIPT, "victim",
                  {"CKPT_TOTAL_STEPS": str(total), "CKPT_SLEEP_S": "0.3",
                   "MXNET_CKPT_SHARDS": "4"})
    try:
        deadline = time.monotonic() + 180
        while time.monotonic() < deadline and len(_losses(loss_file)) < 3:
            if proc.poll() is not None:
                raise AssertionError("victim died early: %s"
                                     % proc.communicate()[1][-2000:])
            time.sleep(0.05)
        assert len(_losses(loss_file)) >= 3, "victim made no progress"
        proc.send_signal(signal.SIGTERM)
        out, err = proc.communicate(timeout=120)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.communicate(timeout=30)
    # final checkpoint written at the step boundary, then the chained
    # flight handler re-raised: exit status still says SIGTERM
    assert proc.returncode == -signal.SIGTERM, (proc.returncode,
                                                err.decode()[-2000:])
    assert glob.glob(str(run_dir / "flight_*.json")), \
        "chained flight dump missing"
    manifests = glob.glob(str(run_dir / "ckpt" / "ckpt-*" / "manifest.json"))
    assert manifests, "no final checkpoint committed"
    interrupted = _losses(loss_file)

    # resume in a fresh process with a DIFFERENT faked device count
    proc = _spawn(run_dir, _TRAIN_SCRIPT, "resume",
                  {"CKPT_TOTAL_STEPS": str(total),
                   "MXNET_CKPT_SHARDS": "2"})
    out, err = proc.communicate(timeout=240)
    assert proc.returncode == 0, err.decode()[-2000:]
    first_line = out.decode().splitlines()[0]
    resumed_from = int(first_line.split()[1])
    assert resumed_from >= 3, first_line   # resumed, not restarted

    merged = _losses(loss_file)
    # at most one step's loss line is missing: the boundary that
    # performed the final checkpoint died before its write
    assert len(merged) >= total - 1
    for step, loss in merged.items():
        assert loss == ref[step], \
            "step %d diverged after resume: %r != %r" \
            % (step, loss, ref[step])


def test_sigterm_grace_window_never_hangs(tmp_path):
    """A job wedged outside step boundaries (mid-collective, stuck
    engine push) still dies within the grace window — with a flight
    dump — instead of hanging the preemption."""
    proc = _spawn(tmp_path, _HANG_SCRIPT, "wedged",
                  {"MXNET_CKPT_GRACE_SECS": "1"})
    try:
        assert proc.stdout.readline().strip() == b"READY"
        t0 = time.monotonic()
        proc.send_signal(signal.SIGTERM)
        out, err = proc.communicate(timeout=60)
        took = time.monotonic() - t0
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.communicate(timeout=30)
    assert proc.returncode == 128 + signal.SIGTERM, \
        (proc.returncode, err.decode()[-2000:])
    assert took < 30, "grace expiry took %.1fs" % took
    dumps = glob.glob(str(tmp_path / "flight_*.json"))
    assert dumps
    dump = json.loads(open(dumps[0]).read())
    assert dump["reason"] == "preempt:grace-expired"


# ---------------------------------------------------------------------------
# last-good pinning + targeted restore (ISSUE 10, guardian rollback)
# ---------------------------------------------------------------------------

def test_targeted_restore_past_newer_checkpoints(tmp_path):
    """restore(step=) loads the TARGET even when newer checkpoints
    exist, and the continuation is bitwise-identical to the original
    run from that step."""
    d = str(tmp_path)
    net, tr, it = _build()
    _run_steps(net, tr, it, 2)
    mgr = checkpoint.CheckpointManager(d, trainer=tr, data_iter=it,
                                       num_shards=2, keep=5)
    assert mgr.save(2, sync=True)
    later = _run_steps(net, tr, it, 2)       # steps 3-4 of the original
    assert mgr.save(4, sync=True)
    mgr.close()

    net2, tr2, it2 = _build()
    mgr2 = checkpoint.CheckpointManager(d, trainer=tr2, data_iter=it2,
                                        num_shards=2)
    assert mgr2.restore(step=2) == 2
    assert mgr2.step == 2
    rest = _run_steps(net2, tr2, it2, 2)
    mgr2.close()
    assert rest == later


def test_pin_survives_retention_and_restart(tmp_path):
    """The last_good pin protects its checkpoint from the MXNET_CKPT_KEEP
    sweep and survives a process restart via the marker file."""
    net, tr, it = _build()
    mgr = checkpoint.CheckpointManager(str(tmp_path), trainer=tr,
                                       data_iter=it, num_shards=1, keep=2)
    for step in (1, 2, 3, 4, 5):
        _run_steps(net, tr, it, 1)
        assert mgr.save(step, sync=True)
        if step == 1:
            assert mgr.pin_last_good() == 1      # defaults to newest
    assert mgr.last_good_step == 1
    assert mgr.describe()["last_good_step"] == 1
    mgr.close()
    steps = sorted(int(os.path.basename(p).split("-")[1])
                   for p in glob.glob(str(tmp_path / "ckpt-*")))
    assert steps == [1, 4, 5]                    # pinned + newest keep=2

    net2, tr2, it2 = _build()
    mgr2 = checkpoint.CheckpointManager(str(tmp_path), trainer=tr2,
                                        data_iter=it2, num_shards=1)
    assert mgr2.last_good_step == 1              # marker file reloaded
    mgr2.close()


def test_corrupt_pinned_falls_back_nonfatally(tmp_path):
    """A corrupt pinned checkpoint must not crash the rollback: the
    targeted restore falls back to the remaining checkpoints."""
    d = str(tmp_path)
    net, tr, it = _build()
    _run_steps(net, tr, it, 2)
    mgr = checkpoint.CheckpointManager(d, trainer=tr, data_iter=it,
                                       num_shards=1, keep=5)
    assert mgr.save(2, sync=True)
    mgr.pin_last_good(2)
    _run_steps(net, tr, it, 2)
    assert mgr.save(4, sync=True)
    mgr.close()

    (params,) = glob.glob(os.path.join(d, "ckpt-*2", "params.pkl"))
    blob = bytearray(open(params, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    open(params, "wb").write(bytes(blob))

    before = telemetry.counter("checkpoint_restore_fallbacks")
    net2, tr2, it2 = _build()
    mgr2 = checkpoint.CheckpointManager(d, trainer=tr2, data_iter=it2,
                                        num_shards=1)
    assert mgr2.restore(step=2) == 4             # fell back, non-fatal
    mgr2.close()
    assert telemetry.counter("checkpoint_restore_fallbacks") > before


def test_restore_step_prefers_older_fallback_over_newer(tmp_path):
    """With the target corrupt, the fallback order is older-first (the
    newer checkpoints are exactly the unverified ones a rollback is
    fleeing) — newer only as the last resort."""
    d = str(tmp_path)
    net, tr, it = _build()
    _run_steps(net, tr, it, 1)
    mgr = checkpoint.CheckpointManager(d, trainer=tr, data_iter=it,
                                       num_shards=1, keep=5)
    assert mgr.save(1, sync=True)
    _run_steps(net, tr, it, 1)
    assert mgr.save(2, sync=True)
    _run_steps(net, tr, it, 1)
    assert mgr.save(3, sync=True)
    mgr.close()
    (params,) = glob.glob(os.path.join(d, "ckpt-*2", "params.pkl"))
    os.remove(params)

    net2, tr2, it2 = _build()
    mgr2 = checkpoint.CheckpointManager(d, trainer=tr2, data_iter=it2,
                                        num_shards=1)
    assert mgr2.restore(step=2) == 1             # older beats newer
    mgr2.close()


def test_restore_step_newer_last_resort_is_oldest_first(tmp_path):
    """No older checkpoint survives and the target is corrupt: the
    newer-group fallback takes the OLDEST newer checkpoint (closest to
    the last verified state), not the newest."""
    d = str(tmp_path)
    net, tr, it = _build()
    _run_steps(net, tr, it, 1)
    mgr = checkpoint.CheckpointManager(d, trainer=tr, data_iter=it,
                                       num_shards=1, keep=5)
    assert mgr.save(1, sync=True)
    _run_steps(net, tr, it, 1)
    assert mgr.save(2, sync=True)
    _run_steps(net, tr, it, 1)
    assert mgr.save(3, sync=True)
    mgr.close()
    (params,) = glob.glob(os.path.join(d, "ckpt-*1", "params.pkl"))
    os.remove(params)

    net2, tr2, it2 = _build()
    mgr2 = checkpoint.CheckpointManager(d, trainer=tr2, data_iter=it2,
                                        num_shards=1)
    assert mgr2.restore(step=1) == 2             # oldest of {2, 3}
    mgr2.close()
