"""Example-script smoke gates: every shipped example must run end-to-end
on the CI backend (virtual 8-device CPU mesh) with tiny arguments.

Reference analogue: the runnable ``example/`` surface (SURVEY Appendix
B) that doubles as integration coverage — here executed in-process via
runpy so the scripts inherit the conftest-pinned backend.

The heavier examples (train_mnist / train_cifar10 / lstm_bucketing /
train_ssd_toy / numpy_ops) are exercised with real convergence
thresholds in test_train_convergence.py and test_custom_op.py; this
file covers the rest of the surface cheaply.
"""
import os
import runpy
import sys

import pytest

EXAMPLES = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "examples")


def run_example(script, argv, capsys):
    old_argv = sys.argv
    sys.argv = [script] + argv
    try:
        runpy.run_path(os.path.join(EXAMPLES, script), run_name="__main__")
    finally:
        sys.argv = old_argv
    return capsys.readouterr().out


def test_matrix_factorization_learns(capsys):
    out = run_example("matrix_factorization.py",
                      ["--num-epochs", "2", "--num-obs", "4096"], capsys)
    rmse = float(out.strip().rsplit(" ", 1)[-1])
    assert rmse < 0.2          # planted-model noise floor is ~0.05


def test_word_language_model_beats_uniform(capsys):
    out = run_example("word_language_model.py",
                      ["--num-epochs", "1", "--max-batches", "20"], capsys)
    ppl = float(out.strip().rsplit(" ", 1)[-1])
    assert ppl < 64.0          # uniform baseline on the synthetic vocab


def test_brumby_lm_trains_through_module_fit(capsys):
    out = run_example("brumby_lm.py", ["--num-epochs", "6"], capsys)
    words = out.strip().splitlines()[-1].split()
    assert float(words[2]) < 0.5 * float(words[4])


def test_lfm2_moe_lm_trains_through_module_fit(capsys):
    out = run_example("lfm2_moe_lm.py", ["--num-epochs", "5"], capsys)
    words = out.strip().splitlines()[-1].split()
    assert float(words[2]) < 0.5 * float(words[4])


def test_trinity_lm_trains_through_module_fit(capsys):
    out = run_example("trinity_lm.py", ["--num-epochs", "4"], capsys)
    words = out.strip().splitlines()[-1].split()
    assert float(words[2]) < 0.5 * float(words[4])


def test_granite_lm_trains_through_module_fit(capsys):
    out = run_example("granite_lm.py", ["--num-epochs", "6"], capsys)
    words = out.strip().splitlines()[-1].split()
    assert float(words[2]) < 0.5 * float(words[4])


def test_model_parallel_lstm_group2ctx(capsys):
    out = run_example("model_parallel_lstm.py", ["--num-steps", "40"],
                      capsys)
    assert "final-loss" in out


@pytest.mark.slow
def test_inception_v3_multi_device_kvstore_device(capsys):
    """BASELINE workload #4: inception-v3, ctx list, kvstore='device'
    (shrunken input so CPU CI stays fast)."""
    out = run_example(
        "train_inception_v3.py",
        ["--num-devices", "2", "--num-batches", "2", "--batch-size", "4",
         "--image-size", "147", "--num-classes", "4"], capsys)
    assert "final-throughput" in out


def test_actor_critic_policy_improves(capsys):
    out = run_example("actor_critic.py", ["--num-episodes", "100"], capsys)
    ret = float(out.strip().rsplit(" ", 1)[-1])
    assert ret > 0.5          # corridor optimum is ~0.97; chance is < 0


def test_dcgan_adversarial_loop_runs(capsys):
    """GAN training is too unstable for a convergence gate at this
    scale; the gate is: the adversarial loop completes with finite
    losses and produces the metric line (ref example/gluon/dcgan.py)."""
    out = run_example("dcgan.py", ["--num-iters", "12"], capsys)
    assert "final-mean-gap" in out


def test_fine_tune_beats_scratch(capsys):
    """Checkpoint-based transfer: fine-tuned features beat from-scratch
    on the same small budget (ref fine-tune workflow, README.md:199)."""
    out = run_example("fine_tune.py", [], capsys)
    last = out.strip().splitlines()[-1]
    tuned = float(last.split()[1])
    scratch = float(last.split()[-1].rstrip(")"))
    assert tuned > scratch + 0.05


def test_super_resolution_beats_nearest(capsys):
    """ESPCN sub-pixel conv beats nearest-neighbour upsampling in PSNR
    on held-out images (ref example/gluon/super_resolution.py)."""
    out = run_example("super_resolution.py", [], capsys)
    last = out.strip().splitlines()[-1]
    model = float(last.split()[1])
    base = float(last.split()[-1].rstrip(")"))
    assert model > base + 0.5


def test_sparse_linear_classification_learns(capsys):
    out = run_example("sparse_linear_classification.py",
                      ["--num-epochs", "3", "--num-obs", "512",
                       "--num-features", "300"], capsys)
    line = [l for l in out.splitlines() if l.startswith("FINAL")][-1]
    fields = dict(kv.split("=") for kv in line.split()[1:])
    assert float(fields["last_nll"]) < float(fields["first_nll"])
    assert float(fields["acc"]) > 0.5


def test_rcnn_toy_detector_learns(capsys):
    """Proposal -> ROIPooling -> head end-to-end learnability
    (reference example/rcnn/train_end2end.py skeleton)."""
    out = run_example("train_rcnn_toy.py",
                      ["--num-epochs", "4", "--lr", "4e-3"], capsys)
    miou = float(out.strip().rsplit(" ", 1)[-1])
    assert miou > 0.3, "refined-proposal IoU %.3f too low" % miou


def test_cnn_text_classification_learns(capsys):
    out = run_example("cnn_text_classification.py",
                      ["--num-epochs", "3"], capsys)
    acc = float(out.strip().rsplit(" ", 1)[-1])
    assert acc > 0.8


def test_nce_word_embeddings_cluster(capsys):
    out = run_example("nce_word_embeddings.py", ["--num-epochs", "4"],
                      capsys)
    margin = float(out.strip().rsplit(" ", 1)[-1])
    assert margin > 0.2, "topic clustering margin %.3f" % margin


def test_vae_toy_elbo_improves(capsys):
    out = run_example("vae_toy.py", ["--num-epochs", "8"], capsys)
    line = out.strip().splitlines()[-1].split()
    untrained, trained = float(line[2]), float(line[4])
    assert trained > untrained + 5.0


def test_publish_and_serve_zoo_artifact(capsys, tmp_path, monkeypatch):
    """Zoo artifact round trip: train -> publish (gluon .params + symbol
    JSON + V2 checkpoint) -> model_store resolves it -> both load paths
    reproduce the recorded accuracy surface (VERDICT r3 #10)."""
    import json
    import numpy as np
    # lr tuned so 3 epochs clears the bar with margin (0.91 on the
    # seeded corpus) — each mobilenet epoch costs ~40s on the 1-core CI
    out = run_example("train_publish_cifar.py",
                      ["--num-epochs", "3", "--lr", "0.01",
                       "--publish", str(tmp_path),
                       "--min-acc", "0.5"], capsys)
    assert "published" in out
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.gluon.model_zoo.model_store import get_model_file
    sys.path.insert(0, EXAMPLES)
    from train_publish_cifar import NAME
    from train_cifar10 import synthetic_cifar

    meta = json.load(open(tmp_path / (NAME + ".json")))
    _, (va_x, va_y) = synthetic_cifar()
    va_x = np.repeat(np.repeat(va_x, 2, axis=2), 2, axis=3)  # per meta

    # gluon path through model_store (MXNET_GLUON_REPO as local dir)
    monkeypatch.setenv("MXNET_GLUON_REPO", str(tmp_path))
    net = vision.get_model("mobilenet0.25", classes=10)
    net.load_params(get_model_file(NAME), ctx=mx.cpu())
    out = net(mx.nd.array(va_x[:256])).asnumpy()
    acc = float((out.argmax(axis=1) == va_y[:256]).mean())
    assert abs(acc - meta["val_accuracy"]) < 0.08

    # symbolic path: Module.load from the published checkpoint
    mod = mx.mod.Module.load(str(tmp_path / NAME), 0,
                             context=mx.cpu())
    mod.bind(data_shapes=[("data", (256, 3, 64, 64))], for_training=False)
    mod.forward(mx.io.DataBatch([mx.nd.array(va_x[:256])], None),
                is_train=False)
    out2 = mod.get_outputs()[0].asnumpy()
    acc2 = float((out2.argmax(axis=1) == va_y[:256]).mean())
    assert abs(acc2 - acc) < 0.02


def test_ctc_ocr_learns(capsys):
    """LSTM + CTC through the symbolic Module path (reference lstm_ocr);
    greedy decode must reach near-zero label error."""
    out = run_example("ctc_ocr_toy.py", ["--num-epochs", "40"], capsys)
    rate = float(out.strip().rsplit(" ", 1)[-1])
    assert rate < 0.15, "label error rate %.3f" % rate


def test_bi_lstm_sort_learns(capsys):
    out = run_example("bi_lstm_sort.py", ["--num-epochs", "40"], capsys)
    token_acc = float(out.split("token acc")[1].split()[0])
    assert token_acc > 0.85, "token accuracy %.3f" % token_acc


def test_adversary_fgsm_attack_works(capsys):
    out = run_example("adversary_fgsm.py", ["--num-epochs", "6"], capsys)
    parts = out.split()
    clean = float(parts[parts.index("acc") + 1])
    adv = float(parts[parts.index("acc", parts.index("acc") + 1) + 1])
    assert clean > 0.9, "clean accuracy %.3f" % clean
    assert adv < clean - 0.5, "FGSM barely moved accuracy (%.3f -> %.3f)" \
        % (clean, adv)


def test_multi_task_both_heads_learn(capsys):
    out = run_example("multi_task.py", ["--num-epochs", "8"], capsys)
    digit = float(out.split("digit acc")[1].split()[0])
    parity = float(out.split("parity acc")[1].split()[0])
    assert digit > 0.9 and parity > 0.9


def test_svm_mnist_learns(capsys):
    out = run_example("svm_mnist.py", ["--num-epochs", "6"], capsys)
    acc = float(out.strip().rsplit(" ", 1)[-1])
    assert acc > 0.9, "svm accuracy %.3f" % acc


def test_factorization_machine_learns_interactions(capsys):
    out = run_example("factorization_machine.py",
                      ["--num-epochs", "8"], capsys)
    parts = out.split()
    first = float(parts[parts.index("first_loss") + 1])
    last = float(parts[parts.index("last_loss") + 1])
    acc = float(parts[parts.index("acc") + 1])
    assert last < first * 0.5
    assert acc > 0.8


@pytest.mark.slow
def test_lstm_crf_learns_tags_and_transitions(capsys):
    out = run_example("lstm_crf.py",
                      ["--num-epochs", "6", "--lr", "0.01"], capsys)
    parts = out.split()
    crf = float(parts[parts.index("acc") + 1])
    margin = float(parts[parts.index("margin") + 1])
    assert crf > 0.7, "crf tag accuracy %.3f" % crf
    assert margin > 0.3, "transition matrix did not learn stickiness"


# ---- round-5 example families (VERDICT r4 Missing #2) ----

def test_fcn_xs_segmentation_learns(capsys):
    """fcn8s skip-fusion segmentation beats the majority-class baseline
    on pixel accuracy and triples chance mIoU (ref example/fcn-xs/)."""
    out = run_example("fcn_xs.py",
                      ["--num-epochs", "3", "--num-images", "256"], capsys)
    lines = dict(l.rsplit(" ", 1) for l in out.strip().splitlines())
    majority = float(lines["majority-baseline"])
    assert float(lines["final-pixel-acc"]) > majority + 0.03
    assert float(lines["final-miou"]) > 0.40


@pytest.mark.slow
def test_tree_lstm_pearson(capsys):
    """Child-sum Tree-LSTM relatedness: Pearson r on held-out tree pairs
    (ref example/gluon/tree_lstm/ main.py metric). The levelized forest
    batching is what makes this trainable in test time."""
    out = run_example("tree_lstm.py",
                      ["--num-pairs", "400", "--num-epochs", "10"], capsys)
    r = float(out.strip().rsplit(" ", 1)[-1])
    assert r > 0.55, "pearson %.3f" % r


def test_dqn_windy_grid(capsys):
    """DQN with replay + target net reaches the goal reliably
    (ref example/reinforcement-learning/dqn/)."""
    out = run_example("dqn.py", ["--num-episodes", "200"], capsys)
    ret = float(out.strip().rsplit(" ", 1)[-1])
    assert ret > 0.5, "greedy return %.3f" % ret


def test_a3c_parallel_envs(capsys):
    """Batched advantage actor-critic: mean per-step reward climbs well
    above the random-walk level (ref example/reinforcement-learning/
    a3c + parallel_actor_critic)."""
    out = run_example("a3c_parallel.py", ["--num-updates", "120"], capsys)
    r = float(out.strip().rsplit(" ", 1)[-1])
    assert r > 0.08, "mean step reward %.4f" % r


def test_autoencoder_dec_clusters(capsys):
    """Stacked-AE pretrain + DEC: reconstruction error drops 3x and the
    DEC refinement does not regress k-means accuracy
    (ref example/autoencoder + example/dec)."""
    out = run_example("autoencoder_dec.py",
                      ["--num-points", "500", "--dec-epochs", "40"], capsys)
    lines = dict(l.rsplit(" ", 1) for l in out.strip().splitlines()
                 if " " in l)
    e0, e1 = (float(v) for v in
              [w for w in out.splitlines() if w.startswith("recon")][0]
              .split()[1::2])
    assert e1 < e0 / 3.0, "recon %.4f -> %.4f" % (e0, e1)
    kacc = float(lines["kmeans-acc"])
    dacc = float(lines["final-dec-acc"])
    assert dacc >= kacc - 1e-6 and dacc > 0.6, (kacc, dacc)


def test_stochastic_depth_trains(capsys):
    """Randomly-dropped residual blocks still train to well above chance
    on the 4-class texture task (ref example/stochastic-depth/)."""
    out = run_example("stochastic_depth.py",
                      ["--num-epochs", "2", "--num-images", "512"], capsys)
    acc = float(out.strip().rsplit(" ", 1)[-1])
    assert acc > 0.6, "accuracy %.3f vs 0.25 chance" % acc


def test_rnn_time_major_layout_equivalence(capsys):
    """Time-major and batch-major training reach close perplexities on
    the deterministic corpus, and both learn it (ref
    example/rnn-time-major/)."""
    out = run_example("rnn_time_major.py", ["--num-epochs", "2"], capsys)
    lines = dict(l.rsplit(" ", 1) for l in out.strip().splitlines()
                 if " " in l)
    assert float(lines["final-time-major-ppl"]) < 12.0   # uniform = 16
    assert float(lines["layout-ppl-gap"]) < 1.5


def test_bayesian_sgld_calibrated(capsys):
    """SGLD posterior predictive matches grid-quadrature truth and the
    chain explores (ref example/bayesian-methods/)."""
    out = run_example("bayesian_sgld.py", [], capsys)
    lines = dict(l.rsplit(" ", 1) for l in out.strip().splitlines())
    assert float(lines["predictive-gap"]) < 0.08
    assert float(lines["mean-gap"]) < 0.8
    assert float(lines["sample-std"]) > 0.1, "sampler collapsed to MAP"


def test_captcha_multi_head(capsys):
    """Grouped 4-head captcha CNN: per-char accuracy well above the 0.1
    chance level (ref example/captcha/)."""
    out = run_example("captcha.py",
                      ["--num-epochs", "6", "--num-images", "1024"],
                      capsys)
    acc = float(out.strip().rsplit(" ", 1)[-1])
    assert acc > 0.6, "char acc %.3f" % acc


def test_dsd_training_flow(capsys):
    """Dense->Sparse->Dense: pruning to 30% density barely hurts, and
    the final dense retrain matches or beats the dense baseline
    (ref example/dsd/)."""
    out = run_example("dsd_training.py", [], capsys)
    lines = dict(l.rsplit(" ", 1) for l in out.strip().splitlines())
    assert abs(float(lines["density-after-prune"]) - 0.30) < 0.02
    dense = float(lines["acc-dense"])
    sparse = float(lines["acc-sparse"])
    dsd = float(lines["final-dsd-acc"])
    assert sparse > dense - 0.06, (dense, sparse)
    assert dsd >= dense - 0.02, (dense, dsd)


def test_neural_collaborative_filtering(capsys):
    """NeuMF with negative sampling: HR@10 well above the 0.1 chance
    level under the leave-one-out protocol (ref example/recommenders/)."""
    out = run_example("neural_collaborative_filtering.py", [], capsys)
    lines = dict(l.rsplit(" ", 1) for l in out.strip().splitlines())
    assert float(lines["final-hr10"]) > 0.3
    assert float(lines["final-ndcg10"]) > 0.15


def test_speech_acoustic_model(capsys):
    """BiLSTM frame-wise phoneme posteriors: near-ceiling accuracy on
    the synthetic formant corpus (ref example/speech-demo +
    example/speech_recognition)."""
    out = run_example("speech_acoustic_model.py", [], capsys)
    acc = float(out.strip().rsplit(" ", 1)[-1])
    assert acc > 0.9, "frame acc %.3f" % acc


@pytest.mark.slow
def test_long_context_ring_attention(capsys):
    """Sequence-parallel ring attention: exact vs dense, and the model
    recalls a needle planted in a DIFFERENT sequence shard — cross-shard
    attention demonstrably works (parallel/ring_attention.py; beyond the
    reference's capability set, SURVEY §2.5)."""
    out = run_example("long_context_ring_attention.py", [], capsys)
    lines = dict(l.rsplit(" ", 1) for l in out.strip().splitlines()
                 if " " in l)
    assert float(lines["ring-vs-dense-max-gap"]) < 1e-3
    assert float(lines["final-needle-accuracy"]) > 0.9


@pytest.mark.slow
def test_ddpg_continuous_control(capsys):
    """DDPG with target networks + replay: deterministic eval return far
    above the random baseline on the docking task
    (ref example/reinforcement-learning/ddpg/)."""
    out = run_example("ddpg.py", ["--num-episodes", "60"], capsys)
    ret = float(out.strip().rsplit(" ", 1)[-1])
    assert ret > -10.0, "eval return %.2f (random ~ -25)" % ret


def test_kaggle_ndsb_pipeline(capsys):
    """Full rec pipeline: pack_img -> .rec -> native threaded decode ->
    Module CNN; val accuracy well above 0.25 chance
    (ref example/kaggle-ndsb1/)."""
    out = run_example("kaggle_ndsb_pipeline.py",
                      ["--num-epochs", "10"], capsys)
    acc = float(out.strip().rsplit(" ", 1)[-1])
    assert acc > 0.55, "val acc %.3f vs 0.25 chance" % acc


def test_memcost_remat_saves_memory(capsys):
    """jax.checkpoint on the scanned residual body (the
    MXNET_BACKWARD_DO_MIRROR analogue) must cut XLA's measured temp
    allocation with bit-identical gradients (ref example/memcost/)."""
    out = run_example("memcost.py", [], capsys)
    lines = dict(l.rsplit(" ", 1) for l in out.strip().splitlines())
    assert float(lines["grad-max-gap"]) < 1e-5
    assert float(lines["final-memory-ratio"]) < 0.7


def test_profiling_demo(capsys, tmp_path):
    """Chrome-trace profiler walkthrough: eager per-op spans, Module
    per-program spans, user markers, valid trace JSON
    (ref example/profiler/)."""
    out = run_example("profiling_demo.py",
                      ["--out", str(tmp_path / "p.json")], capsys)
    lines = dict(l.rsplit(" ", 1) for l in out.strip().splitlines())
    assert int(lines["final-total-events"]) > 20
    assert int(lines["has-marker"]) == 1
    assert int(lines["spans operator"]) > 0
    assert int(lines["spans program"]) > 0
