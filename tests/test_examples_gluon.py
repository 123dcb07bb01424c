"""The sequence example (example/gluon transformer LM) stays
runnable: trains the causal flash-attention decoder on synthetic patterns."""
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script, *args, timeout=420, extra_env=None):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env.update(extra_env or {})
    return subprocess.run(
        [sys.executable, os.path.join(REPO, script), *args],
        env=env, cwd=REPO, timeout=timeout, capture_output=True, text=True)


def test_transformer_lm_example_trains():
    res = _run("example/gluon/transformer_lm.py", "--steps", "40",
               "--seq-len", "32", "--dim", "32")
    assert res.returncode == 0, res.stderr[-2000:]
    assert "next-token accuracy" in res.stdout


def test_transformer_lm_sequence_parallel_mode():
    res = _run("example/gluon/transformer_lm.py", "--steps", "10",
               "--seq-len", "32", "--dim", "32",
               "--sequence-parallel", "4",
               extra_env={"XLA_FLAGS":
                          "--xla_force_host_platform_device_count=4"})
    assert res.returncode == 0, res.stderr[-2000:]
    assert "ring vs fused attention" in res.stdout


def test_ctc_ocr_example_learns():
    """LSTM+CTC OCR (example/ctc/lstm_ocr.py): CTC loss drives the op
    end-to-end (reference example/ctc/lstm_ocr.py + ctc_loss.cc:38) and
    greedy-decoded sequence accuracy must rise well above the untrained
    net on held-out synthetic captchas."""
    import re
    res = _run("example/ctc/lstm_ocr.py", "--steps", "800")
    assert res.returncode == 0, res.stderr[-2000:]
    m = re.search(r"sequence accuracy: ([\d.]+) \(untrained ([\d.]+)\)",
                  res.stdout)
    assert m, res.stdout[-2000:]
    acc, acc0 = float(m.group(1)), float(m.group(2))
    assert acc > 0.4, "trained seq acc %.3f too low\n%s" % (acc, res.stdout)
    assert acc > acc0 + 0.3, "no meaningful learning: %.3f -> %.3f" % (acc0, acc)


def test_dcgan_example_learns():
    """DCGAN (example/gan/dcgan.py): Deconvolution generator + conv
    discriminator trained adversarially; the generator's sample moments
    must move decisively toward the real distribution (reference
    example/gan/dcgan.py, measured instead of eyeballed)."""
    import re
    res = _run("example/gan/dcgan.py", "--steps", "500")
    assert res.returncode == 0, res.stderr[-2000:]
    m = re.search(r"real=\(([\d.]+), ([\d.]+)\) fake=\(([\d.]+), ([\d.]+)\) "
                  r"untrained=\(([\d.]+), ([\d.]+)\)", res.stdout)
    assert m, res.stdout[-2000:]
    real_mean, real_std, fake_mean, fake_std, un_mean, un_std = map(
        float, m.groups())
    assert abs(fake_mean - real_mean) < 0.15, res.stdout
    # spatial structure emerged: far above the untrained near-constant output
    assert fake_std > max(4 * un_std, 0.08), res.stdout


def test_bi_lstm_sort_example_learns():
    """Bidirectional LSTM sorts digit sequences (reference
    example/bi-lstm-sort): held-out per-position accuracy must be near
    exact — the task is fully determined given both directions."""
    import re
    res = _run("example/bi-lstm-sort/sort_lstm.py", "--steps", "600")
    assert res.returncode == 0, res.stderr[-2000:]
    m = re.search(r"sort accuracy: ([\d.]+) \(untrained ([\d.]+)\)",
                  res.stdout)
    assert m, res.stdout[-2000:]
    acc, acc0 = float(m.group(1)), float(m.group(2))
    assert acc > 0.85, res.stdout
    assert acc0 < 0.3, res.stdout


def test_neural_style_example_optimizes_input():
    """Neural style (example/neural-style/nstyle.py): gradient descent on
    the INPUT image through VGG feature taps + Gram losses — the combined
    loss must collapse from the noise init (reference nstyle.py)."""
    import re
    res = _run("example/neural-style/nstyle.py", "--steps", "80")
    assert res.returncode == 0, res.stderr[-2000:]
    m = re.search(r"loss: ([\d.]+) -> ([\d.]+) \(([\d.]+)x reduction\)",
                  res.stdout)
    assert m, res.stdout[-2000:]
    assert float(m.group(3)) > 5.0, res.stdout


def test_quantization_example_int8_matches_fp32():
    """Post-training int8 quantization example (reference
    example/quantization): calibrated int8 inference must keep accuracy
    and agree with fp32 top-1 on held-out data."""
    import re
    res = _run("example/quantization/quantize_infer.py")
    assert res.returncode == 0, res.stderr[-2000:]
    m = re.search(r"fp32 accuracy: ([\d.]+)\s+int8 accuracy: ([\d.]+)\s+"
                  r"top-1 agreement: ([\d.]+)", res.stdout)
    assert m, res.stdout[-2000:]
    fp_acc, q_acc, agree = map(float, m.groups())
    assert fp_acc > 0.9, res.stdout
    assert q_acc > fp_acc - 0.1, res.stdout
    assert agree > 0.9, res.stdout


def test_deepspeech_toy_example_learns():
    """Speech CTC (example/speech_recognition/deepspeech_toy.py): the
    deepspeech-shaped Conv1D + BiLSTM acoustic net must drive the phone
    error rate on held-out variable-duration synthetic utterances well
    below the untrained net's (reference example/speech_recognition/
    arch_deepspeech.py scored by stt_metric.py's CTC label error rate)."""
    import re
    res = _run("example/speech_recognition/deepspeech_toy.py",
               "--steps", "250")
    assert res.returncode == 0, res.stderr[-2000:]
    m = re.search(r"phone error rate: ([\d.]+) \(untrained ([\d.]+)\)",
                  res.stdout)
    assert m, res.stdout[-2000:]
    per, per0 = float(m.group(1)), float(m.group(2))
    assert per < 0.35, "trained PER %.3f too high\n%s" % (per, res.stdout)
    assert per < per0 / 2, "no meaningful learning: %.3f -> %.3f" % (per0, per)


def test_vae_example_learns():
    """VAE (example/vae/vae_mnist_like.py): the reparameterized stochastic
    layer trains under the autograd tape (RNG inside record()), and the
    trained ELBO + posterior-mean reconstructions must beat the untrained
    net decisively (reference example/vae/VAE.py's MLP VAE on MNIST)."""
    import re
    res = _run("example/vae/vae_mnist_like.py", "--steps", "400")
    assert res.returncode == 0, res.stderr[-2000:]
    m = re.search(r"elbo: (-?[\d.]+) \(untrained (-?[\d.]+)\), "
                  r"recon mode accuracy: ([\d.]+)", res.stdout)
    assert m, res.stdout[-2000:]
    elbo, elbo0, acc = (float(m.group(i)) for i in (1, 2, 3))
    assert elbo > elbo0 + 50, "ELBO barely moved: %.1f -> %.1f" % (elbo0, elbo)
    assert acc > 0.9, "reconstructions off-mode: %.3f\n%s" % (acc, res.stdout)


def test_multitask_example_learns_both_heads():
    """Multi-task (example/multi-task/multitask.py): one shared conv trunk
    must drive BOTH the 10-class head and the independent parity head to
    high held-out accuracy through a joint loss (reference
    example/multi-task/example_multi_task.py)."""
    import re
    res = _run("example/multi-task/multitask.py", "--steps", "250")
    assert res.returncode == 0, res.stderr[-2000:]
    m = re.search(r"class acc: ([\d.]+) \(untrained ([\d.]+)\), "
                  r"parity acc: ([\d.]+) \(untrained ([\d.]+)\)", res.stdout)
    assert m, res.stdout[-2000:]
    a_cls, a0_cls, a_inv, a0_inv = (float(m.group(i)) for i in (1, 2, 3, 4))
    assert a_cls > 0.9, "class head stuck at %.3f\n%s" % (a_cls, res.stdout)
    assert a_inv > 0.9, "parity head stuck at %.3f\n%s" % (a_inv, res.stdout)
    assert a_cls > a0_cls + 0.3 and a_inv > a0_inv + 0.2


def test_reinforce_example_learns_policy():
    """REINFORCE (example/reinforcement-learning/reinforce_track.py):
    return-weighted log-prob ascent on on-policy rollouts must take the
    greedy policy from ~0 return to near-optimal (reference
    example/reinforcement-learning's policy-gradient loops)."""
    import re
    res = _run("example/reinforcement-learning/reinforce_track.py",
               "--updates", "120")
    assert res.returncode == 0, res.stderr[-2000:]
    m = re.search(r"greedy avg return: ([\d.]+) \(untrained ([\d.]+)\)",
                  res.stdout)
    assert m, res.stdout[-2000:]
    ret, ret0 = float(m.group(1)), float(m.group(2))
    assert ret > 0.5, "policy return %.3f too low\n%s" % (ret, res.stdout)
    assert ret > ret0 + 0.3, "no learning: %.3f -> %.3f" % (ret0, ret)


def test_text_cnn_example_learns():
    """Kim-CNN (example/cnn_text_classification/text_cnn.py): parallel
    multi-width convs + max-over-time pooling must detect the positional-
    invariant trigram signal to high held-out accuracy (reference
    example/cnn_text_classification/text_cnn.py)."""
    import re
    res = _run("example/cnn_text_classification/text_cnn.py",
               "--steps", "300")
    assert res.returncode == 0, res.stderr[-2000:]
    m = re.search(r"sentence accuracy: ([\d.]+) \(untrained ([\d.]+)\)",
                  res.stdout)
    assert m, res.stdout[-2000:]
    acc, acc0 = float(m.group(1)), float(m.group(2))
    assert acc > 0.9, "accuracy %.3f too low\n%s" % (acc, res.stdout)
    assert acc > acc0 + 0.3, "no learning: %.3f -> %.3f" % (acc0, acc)


def test_dec_example_improves_purity():
    """DEC (example/deep-embedded-clustering/dec.py): AE pretraining,
    Lloyd centroid init, then the student-t/KL self-sharpening phase
    training encoder AND a first-class centroid Parameter jointly must
    end at near-perfect cluster purity (reference
    example/deep-embedded-clustering/dec.py)."""
    import re
    res = _run("example/deep-embedded-clustering/dec.py")
    assert res.returncode == 0, res.stderr[-2000:]
    m = re.search(r"cluster purity: ([\d.]+) \(kmeans-on-pretrained "
                  r"([\d.]+)\)", res.stdout)
    assert m, res.stdout[-2000:]
    pur = float(m.group(1))
    assert pur > 0.85, "purity %.3f too low\n%s" % (pur, res.stdout)


def test_nce_example_learns_embeddings():
    """NCE (example/nce-loss/nce_lm.py): the sampled binary objective —
    no full-vocab logits matrix ever built — must still organize the
    input embedding by topic, far above the 1/8 chance coherence
    (reference example/nce-loss/nce.py)."""
    import re
    res = _run("example/nce-loss/nce_lm.py", "--steps", "400")
    assert res.returncode == 0, res.stderr[-2000:]
    m = re.search(r"topic coherence: ([\d.]+) \(untrained ([\d.]+)",
                  res.stdout)
    assert m, res.stdout[-2000:]
    coh, coh0 = float(m.group(1)), float(m.group(2))
    assert coh > 0.5, "coherence %.3f too low\n%s" % (coh, res.stdout)
    assert coh > coh0 + 0.3, "no learning: %.3f -> %.3f" % (coh0, coh)


def test_stochastic_depth_example_learns():
    """Stochastic depth (example/stochastic-depth/sd_resnet.py): per-batch
    Bernoulli-gated residual blocks (fresh random graph every step through
    the tape) must still train to high held-out accuracy, with inference
    switching to the expectation path (reference
    example/stochastic-depth/sd_cifar10.py)."""
    import re
    res = _run("example/stochastic-depth/sd_resnet.py", "--steps", "300")
    assert res.returncode == 0, res.stderr[-2000:]
    m = re.search(r"accuracy: ([\d.]+) \(untrained ([\d.]+)\)", res.stdout)
    assert m, res.stdout[-2000:]
    acc, acc0 = float(m.group(1)), float(m.group(2))
    assert acc > 0.8, "accuracy %.3f too low\n%s" % (acc, res.stdout)
    assert acc > acc0 + 0.4, "no learning: %.3f -> %.3f" % (acc0, acc)


def test_lstnet_example_beats_naive():
    """LSTNet (example/multivariate_time_series/lstnet.py): conv + GRU +
    seasonal skip-GRU + AR highway must forecast the held-out window far
    below the naive last-value RSE (reference
    example/multivariate_time_series/src/lstnet.py, scored like its
    metrics.py RSE)."""
    import re
    res = _run("example/multivariate_time_series/lstnet.py",
               "--steps", "200")
    assert res.returncode == 0, res.stderr[-2000:]
    m = re.search(r"held-out RSE: ([\d.]+) \(naive last-value ([\d.]+)\)",
                  res.stdout)
    assert m, res.stdout[-2000:]
    model, naive = float(m.group(1)), float(m.group(2))
    assert model < 0.6, "RSE %.3f too high\n%s" % (model, res.stdout)
    assert model < naive / 2, "no edge over naive: %.3f vs %.3f" % (
        model, naive)


def test_fcn_xs_example_segments():
    """FCN-16s segmentation (example/fcn-xs/fcn_xs.py): Deconvolution
    upsampling + Crop-to-reference + skip fusion + multi-output softmax
    through the symbolic Module path must push held-out mean IoU well
    above the untrained net's (reference example/fcn-xs/symbol_fcnxs.py)."""
    import re
    res = _run("example/fcn-xs/fcn_xs.py", timeout=600)
    assert res.returncode == 0, res.stderr[-2000:]
    assert "FCN_XS OK" in res.stdout, res.stdout[-2000:]
    m = re.search(r"mean IoU before ([\d.]+) after ([\d.]+)", res.stdout)
    assert m and float(m.group(2)) > 0.55


def test_matrix_fact_example_generalizes():
    """Matrix-factorization recommender (example/recommenders/
    matrix_fact.py): embedding-dot-product MF must recover the noise floor
    on HELD-OUT (user, item) pairs, not just fit the training triples
    (reference example/recommenders/matrix_fact.py)."""
    res = _run("example/recommenders/matrix_fact.py", timeout=600)
    assert res.returncode == 0, res.stderr[-2000:]
    assert "MATRIX_FACT OK" in res.stdout, res.stdout[-2000:]


def test_fgsm_example_attacks():
    """FGSM adversary (example/adversary/fgsm.py): input-gradient attack
    must collapse accuracy while an equal-magnitude random-sign
    perturbation does not (reference example/adversary/
    adversary_generation.ipynb) — exercising autograd w.r.t. DATA."""
    res = _run("example/adversary/fgsm.py", timeout=600)
    assert res.returncode == 0, res.stderr[-2000:]
    assert "FGSM OK" in res.stdout, res.stdout[-2000:]


def test_lstm_crf_example_finds_structure():
    """BiLSTM-CRF (example/gluon/lstm_crf.py): I-tokens are emission-
    identical to O-tokens, so only the CRF's transition structure can
    find them.  The script's own exit gates (lstm_crf.py main) are
    crf_f1 > ablation_f1 + 0.15 (structure, not emissions, drives the
    margin) and BIO-violation rate < 1% of eval positions (reference
    example/gluon/lstm_crf.py)."""
    res = _run("example/gluon/lstm_crf.py", timeout=800)
    assert res.returncode == 0, res.stderr[-2000:]
    assert "LSTM_CRF OK" in res.stdout, res.stdout[-2000:]


def test_sgld_example_samples_posterior():
    """SGLD toy (example/bayesian-methods/sgld_toy.py, reference
    example/bayesian-methods/sgld.ipynb): batched 4-chain sampling must
    keep >60% pooled mass within 1.0 of a posterior mode, visit both
    modes across chains, and hold within-chain spread >4x the no-noise
    SGD ablation's (the sampler-vs-point-estimator signature)."""
    res = _run("example/bayesian-methods/sgld_toy.py", timeout=800)
    assert res.returncode == 0, res.stderr[-2000:]
    assert "SGLD_TOY OK" in res.stdout, res.stdout[-2000:]


def test_svm_mnist_example_learns():
    """SVMOutput end-to-end (example/svm_mnist/svm_mnist.py, reference
    example/svm_mnist + svm_output-inl.h): both the squared-hinge and the
    use_linear hinge heads must clear 0.8 held-out accuracy through the
    Module API."""
    res = _run("example/svm_mnist/svm_mnist.py", timeout=800)
    assert res.returncode == 0, res.stderr[-2000:]
    assert "SVM_MNIST OK" in res.stdout, res.stdout[-2000:]


def test_numpy_softmax_custom_op_example():
    """Custom numpy softmax op drives a training run to parity with the
    built-in SoftmaxOutput (example/numpy-ops/numpy_softmax.py, reference
    example/numpy-ops/numpy_softmax.py over src/operator/custom/)."""
    res = _run("example/numpy-ops/numpy_softmax.py", timeout=800)
    assert res.returncode == 0, res.stderr[-2000:]
    assert "NUMPY_SOFTMAX OK" in res.stdout, res.stdout[-2000:]


def test_capsnet_example_routes_and_classifies():
    """CapsNet dynamic routing (example/capsnet/capsnet.py, reference
    example/capsnet/capsulelayers.py): >0.9 held-out accuracy on jittered
    glyphs AND the margin-loss capsule-length structure (winner ~0.9,
    losers <0.25)."""
    res = _run("example/capsnet/capsnet.py", timeout=800)
    assert res.returncode == 0, res.stderr[-2000:]
    assert "CAPSNET OK" in res.stdout, res.stdout[-2000:]


def test_memcost_example_remat_memory():
    """memcost (reference example/memcost over note_memory.md): gradient
    parity between plain and remat builds everywhere; the temp-memory
    ratio assertion is TPU-only (XLA:CPU scheduling — see docstring)."""
    res = _run("example/memcost/memcost.py", timeout=800)
    assert res.returncode == 0, res.stderr[-2000:]
    assert "MEMCOST OK" in res.stdout, res.stdout[-2000:]


def test_dsd_example_prunes_and_regrows():
    """DSD (reference example/dsd): the SparseSGD schedule must hit the
    50% per-layer mask in the sparse phase, release it in the final dense
    phase, and keep held-out accuracy high throughout."""
    res = _run("example/dsd/mlp_dsd.py", timeout=800)
    assert res.returncode == 0, res.stderr[-2000:]
    assert "DSD OK" in res.stdout, res.stdout[-2000:]


def test_gradcam_example_saliency_is_localized():
    """Grad-CAM (example/cnn_visualization/gradcam.py, reference
    example/cnn_visualization): on a quadrant-localization task the
    class-discriminative saliency must concentrate in the true quadrant
    (mean mass >0.55 vs 0.25 uniform), with the classifier itself >0.9."""
    res = _run("example/cnn_visualization/gradcam.py", timeout=800)
    assert res.returncode == 0, res.stderr[-2000:]
    assert "GRADCAM OK" in res.stdout, res.stdout[-2000:]


def test_rbm_example_learns_energy_model():
    """Binary RBM via CD-1 (example/restricted-boltzmann-machine, reference
    same dir): no-backprop contrastive-divergence training must cut the
    held-out reconstruction error >3x and open a clear free-energy gap
    between noise and data."""
    res = _run("example/restricted-boltzmann-machine/binary_rbm.py",
               timeout=800)
    assert res.returncode == 0, res.stderr[-2000:]
    assert "RBM OK" in res.stdout, res.stdout[-2000:]
