"""bench.py must always produce a valid JSON line, so every mode gets a
tiny-config CPU rehearsal through the real entrypoint; off the TPU every
line says so (``platform``) and carries no utilization."""
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_bench(extra_env, timeout=420):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.update({"JAX_PLATFORMS": "cpu", "BENCH_ITERS": "2"})
    env.update(extra_env)
    res = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                         env=env, cwd=REPO, timeout=timeout,
                         capture_output=True, text=True)
    lines = [l for l in res.stdout.splitlines() if l.strip().startswith("{")]
    assert lines, "no JSON line\nstdout:%s\nstderr:%s" % (
        res.stdout, res.stderr[-1500:])
    return res, json.loads(lines[-1])


TINY_RESNET = {"BENCH_BATCH": "2", "BENCH_IMG": "32", "BENCH_LAYOUT": "NCHW"}
TINY_TFM = {"BENCH_MODE": "transformer", "BENCH_TFM_BATCH": "2",
            "BENCH_TFM_SEQ": "128", "BENCH_TFM_DIM": "64",
            "BENCH_TFM_DEPTH": "2", "BENCH_TFM_VOCAB": "256"}


def test_bench_train_mode_smoke():
    res, rec = _run_bench(TINY_RESNET)
    assert res.returncode == 0, res.stdout
    assert rec["value"] and rec["value"] > 0
    assert rec["unit"] == "images/sec"
    assert rec["metric"] == "resnet50_train_imgs_per_sec_bs2_img32"
    assert rec["layout"] == "NCHW" and rec["mode"] == "train"
    assert rec["step_flops"] > 0      # XLA's cost model
    assert rec["platform"] == "cpu" and rec["mfu"] is None   # a rehearsal


def test_bench_inference_mode_smoke():
    res, rec = _run_bench(dict(TINY_RESNET, BENCH_MODE="inference"))
    assert res.returncode == 0, res.stdout
    assert rec["value"] > 0 and rec["mode"] == "inference"
    assert "infer" in rec["metric"]


def test_bench_transformer_mode_smoke():
    res, rec = _run_bench(TINY_TFM)
    assert res.returncode == 0, res.stdout
    assert rec["value"] > 0 and rec["unit"] == "tokens/sec"
    assert rec["metric"].startswith("transformer_lm_train_tokens_per_sec")
    assert rec["config"]["depth"] == 2


def test_bench_bad_mode_still_emits_json():
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu", "BENCH_MODE": "nonsense"})
    res = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                         env=env, cwd=REPO, timeout=60,
                         capture_output=True, text=True)
    rec = json.loads(res.stdout.splitlines()[-1])
    assert rec["value"] is None and "BENCH_MODE" in rec["error"]


def test_bench_int8_mode_smoke():
    """BENCH_MODE=int8: export -> quantize_model -> executor path stays
    runnable and reports the timed window it measured."""
    res, rec = _run_bench(dict(TINY_RESNET, BENCH_MODE="int8",
                               BENCH_IMG="64"), timeout=560)
    assert res.returncode == 0, res.stdout
    assert rec["value"] > 0 and rec["mode"] == "int8"
    assert rec["metric"] == "resnet50_int8_infer_imgs_per_sec_bs2"
    assert rec["calib"] == "minmax"
    assert rec["timed_window"]["iters"] >= 1
