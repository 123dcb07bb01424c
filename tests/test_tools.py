"""Operator tooling parity: parse_log / rec2idx / diagnose (reference
tools/parse_log.py, tools/rec2idx.py, tools/diagnose.py)."""
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))


def _run_tool(name, *args, stdin=None):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", name), *args],
        input=stdin, env=env, cwd=REPO, capture_output=True, text=True,
        timeout=300)


LOG = """\
INFO:root:Epoch[0] Batch [20]\tSpeed: 5000.00 samples/sec\taccuracy=0.5
INFO:root:Epoch[0] Batch [40]\tSpeed: 7000.00 samples/sec\taccuracy=0.55
INFO:root:Epoch[0] Train-accuracy=0.620000
INFO:root:Epoch[0] Time cost=3.200
INFO:root:Epoch[0] Validation-accuracy=0.600000
INFO:root:Epoch[1] Train-accuracy=0.910000
INFO:root:Epoch[1] Time cost=2.900
INFO:root:Epoch[1] Validation-accuracy=0.880000
"""


def test_parse_log_table():
    """Module.fit's exact log lines parse into a per-epoch table with mean
    throughput (reference tools/parse_log.py over the same format)."""
    import parse_log
    table = parse_log.parse(LOG.splitlines())
    assert table[0]["train"]["accuracy"] == 0.62
    assert table[0]["val"]["accuracy"] == 0.60
    assert table[0]["time"] == 3.2
    assert table[0]["speeds"] == [5000.0, 7000.0]
    assert table[1]["val"]["accuracy"] == 0.88

    res = _run_tool("parse_log.py", "-", "--format", "tsv", stdin=LOG)
    assert res.returncode == 0, res.stderr
    lines = res.stdout.strip().splitlines()
    assert lines[0].split("\t") == ["epoch", "train-accuracy",
                                    "val-accuracy", "time(s)", "samples/sec"]
    assert lines[1].split("\t") == ["0", "0.62", "0.6", "3.2", "6000.0"]


def test_rec2idx_rebuilds_usable_index(tmp_path):
    """An index rebuilt from a bare .rec must drive random access
    (reference tools/rec2idx.py -> MXIndexedRecordIO)."""
    from mxnet_tpu import recordio
    rec = str(tmp_path / "data.rec")
    w = recordio.MXRecordIO(rec, "w")
    payloads = [("payload-%d-" % i).encode() * (i + 1) for i in range(7)]
    for p in payloads:
        w.write(p)
    w.close()

    res = _run_tool("rec2idx.py", rec)
    assert res.returncode == 0, res.stderr
    assert "wrote 7 entries" in res.stdout

    r = recordio.MXIndexedRecordIO(str(tmp_path / "data.idx"), rec, "r")
    for i in (6, 0, 3):
        assert r.read_idx(i) == payloads[i]
    r.close()


def test_diagnose_runs():
    """diagnose.py prints every section, the devices JAX sees and the
    compile cache directory (reference tools/diagnose.py minus network
    checks)."""
    res = _run_tool("diagnose.py")
    assert res.returncode == 0, res.stderr[-2000:]
    for needle in ("Platform", "Package versions", "Environment knobs",
                   "Native libraries", "Devices", "platform=cpu",
                   "compile cache:", "diagnose done"):
        assert needle in res.stdout, res.stdout


def test_flakiness_checker_detects_and_reports(tmp_path):
    """flakiness_checker (reference tools/flakiness_checker.py): runs a
    test under N seeds, reports the failure rate, exits nonzero with the
    reproducing seeds when any fail."""
    victim = tmp_path / "test_seeded.py"
    victim.write_text(
        "import os\n"
        "def test_fails_on_odd_seed():\n"
        "    assert int(os.environ.get('MXNET_TEST_SEED', 0)) % 2 == 0\n")
    res = _run_tool("flakiness_checker.py", str(victim), "--trials", "4",
                    "--timeout", "120")
    assert res.returncode == 1, res.stdout + res.stderr
    assert "2/4 failed (50.0%)" in res.stdout, res.stdout
    assert "failing seeds: [1, 3]" in res.stdout, res.stdout
    assert "MXNET_TEST_SEED=1" in res.stdout

    res = _run_tool("flakiness_checker.py", str(victim), "--trials", "2",
                    "--seed-start", "0", "--timeout", "120")
    assert res.returncode == 1  # seed 1 fails
    res = _run_tool("flakiness_checker.py", str(victim), "--trials", "1",
                    "--seed-start", "2", "--timeout", "120")
    assert res.returncode == 0 and "no flakiness" in res.stdout


def test_tpu_consistency_self_test(tmp_path):
    """The consistency battery's plumbing validated without hardware:
    cpu-vs-cpu must pass all cases with zero diffs, and without a TPU the
    real mode must exit 3 with value null."""
    import json
    out = str(tmp_path / "cons.json")
    res = _run_tool("tpu_consistency.py", "--self-test", "--out", out)
    assert res.returncode == 0, res.stdout + res.stderr
    data = json.load(open(out))
    assert data["passed"] == data["total"] == len(data["cases"])
    assert all(c["max_abs_diff"] == 0.0 for c in data["cases"])

    res = _run_tool("tpu_consistency.py", "--out", out)
    assert res.returncode == 3
    assert '"value": null' in res.stdout


def test_kill_mxnet_finds_and_kills_fingerprinted_workers():
    """kill_mxnet (reference tools/kill-mxnet.py): a process carrying the
    launcher's MX_KV_RANK env fingerprint is listed by --dry-run and
    terminated by the real run; unrelated processes are untouched."""
    import signal
    import time
    # a unique cmdline token scopes the kill: the fingerprint sweep would
    # also hit any REAL launch.py workers alive on this machine
    token = "stray_worker_decoy_%d" % os.getpid()  # must not contain "kill_mxnet" (tool self-exclusion)
    env = dict(os.environ, MX_KV_RANK="0", MX_KV_NUM_WORKERS="1")
    victim = subprocess.Popen([sys.executable, "-c",
                               "import time; time.sleep(300) # " + token],
                              env=env)
    bystander = subprocess.Popen([sys.executable, "-c",
                                  "import time; time.sleep(300)"])
    try:
        res = _run_tool("kill_mxnet.py", "--dry-run", "--pattern", token)
        assert ("pid %d" % victim.pid) in res.stdout, res.stdout
        assert ("pid %d" % bystander.pid) not in res.stdout
        # the env-fingerprint detector also sees the victim (dry-run only,
        # so concurrent real workers are merely listed, never touched)
        res = _run_tool("kill_mxnet.py", "--dry-run")
        assert ("pid %d" % victim.pid) in res.stdout, res.stdout

        res = _run_tool("kill_mxnet.py", "--pattern", token)
        assert res.returncode == 0, res.stderr
        for _ in range(50):
            if victim.poll() is not None:
                break
            time.sleep(0.1)
        assert victim.poll() is not None, "fingerprinted worker survived"
        assert bystander.poll() is None, "bystander was killed"
    finally:
        for p in (victim, bystander):
            if p.poll() is None:
                p.send_signal(signal.SIGKILL)


def test_kill_mxnet_remote_scanner_runs_locally():
    """The '-H hostfile' fingerprint mode ships a /proc scanner string to
    remote pythons; run that EXACT string locally against a decoy worker.
    Pins the round-4 advisor bug: .decode('replace') passed 'replace' as
    the encoding, so every /proc read raised LookupError and the scanner
    always printed 'killed 0'."""
    import signal
    import time
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import kill_mxnet
    finally:
        sys.path.pop(0)
    sentinel = "MX_KV_TEST_TOKEN=decoy%d" % os.getpid()
    env = dict(os.environ, MX_KV_RANK="7", MX_KV_NUM_WORKERS="1",
               MX_KV_TEST_TOKEN="decoy%d" % os.getpid())
    victim = subprocess.Popen(
        [sys.executable, "-c", "import time; time.sleep(300)"], env=env)
    try:
        # dry-run variant runs the EXACT production string: must COUNT the
        # fingerprinted decoy (>= 1; real launch.py workers on the box may
        # add to the count, but nothing is killed)
        res = subprocess.run(
            [sys.executable, "-c", kill_mxnet.scanner_src(
                signal.SIGTERM, dry_run=True)],
            capture_output=True, text=True, timeout=60)
        assert res.returncode == 0, res.stderr
        n = int(res.stdout.split()[-1])
        assert n >= 1, "scanner found no fingerprinted workers: %r" % \
            res.stdout

        # kill variant: same scanner with a per-run sentinel ANDed into
        # the fingerprint so the os.kill path is exercised WITHOUT
        # touching unrelated fingerprinted workers (e.g. a concurrent
        # suite run or a live launch.py job on this host)
        res = subprocess.run(
            [sys.executable, "-c", kill_mxnet.scanner_src(
                signal.SIGTERM, extra_env_token=sentinel)],
            capture_output=True, text=True, timeout=60)
        assert res.returncode == 0, res.stderr
        assert int(res.stdout.split()[-1]) == 1, res.stdout
        for _ in range(50):
            if victim.poll() is not None:
                break
            time.sleep(0.1)
        assert victim.poll() is not None, "remote scanner did not kill " \
            "the fingerprinted decoy"
    finally:
        if victim.poll() is None:
            victim.send_signal(signal.SIGKILL)


# ---------------------------------------------------------------------------
# chip_smoke.py and the compile cache it reports
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("chips,phases", [
    (1, ["train", "train_bf16", "serve", "kernel"]),
    (4, ["dp4", "tp4", "replicas"]),
])
def test_chip_smoke_rehearsal_runs_every_phase_and_never_says_ok(chips,
                                                                 phases):
    """chip_smoke.py at its tiny rehearsal sizes on the CPU (4 virtual
    devices for --chips 4): every phase prints its line and passes, yet off
    the TPU the exit code is non-zero and no ok line is printed — with the
    rehearsal switch or, as the driver runs it, without."""
    import json
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%d" % chips
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    script = os.path.join(REPO, "chip_smoke.py")

    res = subprocess.run([sys.executable, script, "--chips", str(chips)],
                         env=env, cwd=REPO, capture_output=True, text=True,
                         timeout=120)
    assert res.returncode != 0 and res.stdout == "", res.stdout
    assert "not a TPU" in res.stderr

    res = subprocess.run(
        [sys.executable, script, "--rehearse", "--chips", str(chips)],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=600)
    lines = [json.loads(l) for l in res.stdout.splitlines()]
    assert lines[0]["compile_cache_dir"] == os.path.join(REPO, ".jax_cache")
    assert [l["phase"] for l in lines[1:]] == phases, res.stdout
    for line in lines[1:]:
        assert line["ok"], line
        for key in ("seconds", "compile_seconds", "cache_hits",
                    "peak_bytes"):
            assert key in line
    assert res.returncode == 2, res.stderr[-2000:]
    assert not any("ok" in l and "device" in l for l in lines)
    assert '"ok": true, "device"' not in res.stdout


def test_compile_cache_dir_is_fixed_or_placed_from_outside(monkeypatch):
    """One rule: JAX_COMPILATION_CACHE_DIR, where set, is left to JAX and
    nothing is set in code; otherwise the cache sits at a fixed path in
    the checkout (a directory that moves never hits)."""
    import jax
    from mxnet_tpu import util
    fixed = os.path.join(REPO, ".jax_cache")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert util.compile_cache_dir() == fixed
    assert util.compile_cache_dir() == fixed
    assert jax.config.jax_compilation_cache_dir == fixed

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    jax.config.update("jax_compilation_cache_dir", None)
    try:
        assert util.compile_cache_dir() == "/some/dir"
        assert jax.config.jax_compilation_cache_dir is None   # untouched
    finally:
        jax.config.update("jax_compilation_cache_dir", fixed)


def test_chip_smoke_failed_phase_is_reported_and_fails_the_run(capsys):
    """A phase that raises prints its line with ok false and the error,
    and run_phase says so; main() turns any such phase into exit code 1."""
    import json
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    meter = chip_smoke.CompileMeter()

    def refused(rec):
        rec["shape"] = [1, 16, 8192, 128]
        raise chip_smoke.SmokeFailure("the compiler refused the kernel")

    assert chip_smoke.run_phase("kernel", refused, meter) is False
    assert chip_smoke.run_phase("fine", lambda rec: rec.update(x=1),
                                meter) is True
    bad, good = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert bad["phase"] == "kernel" and bad["ok"] is False
    assert "refused the kernel" in bad["error"]
    assert bad["shape"] == [1, 16, 8192, 128]   # what it found, it keeps
    assert good["ok"] is True and good["x"] == 1


# ---------------------------------------------------------------------------
# serve_bench exit gates: counts decide, a clock never does
# ---------------------------------------------------------------------------

def _engine_leg(streams, **extra):
    return dict({"statuses": {"OK": streams}, "steady_state_recompiles": 0,
                 "kv_leaked_blocks": 0}, **extra)


def _sound_deploy_report():
    # a swap window 100 times slower than steady state
    return "_deploy_bench_ok", {
        "workload": {"arrivals": 8, "fired": 8},
        "statuses": {"OK": 8}, "conserved": True, "pools_whole": True,
        "torn_streams": 0, "ok_by_generation": {1: 3, 2: 5},
        "probes": {"bitwise": True, "generation": 2},
        "swap": {"status": "deployed", "error": None, "generation": 2,
                 "streams_during_swap": 2,
                 "ttft_p99_during_swap_ms": 640.0, "ttft_p99_steady_ms": 6.4},
        "engines": {"r0": _engine_leg(8, generation=2)},
        "retired_engines": {"old": {"steady_state_recompiles": 0}},
        "memory": {"balanced": True}}, ("engines", "r0")


def _sound_prefix_spec_report():
    return "_prefix_spec_ok", {
        "workload": {"streams": 8},
        "baseline": _engine_leg(8, prefill_chunks=32),
        "optimized": _engine_leg(8, prefill_chunks=10, prefix_hits=7,
                                 full_prompt_prefills=1, spec_proposed=40,
                                 spec_accepted=30),
        "speedup_tokens_per_s": 0.1}, ("optimized",)


def _sound_sharded_decode_report():
    leg = dict(token_equal_reference=True, devices=2, tp_degree=2)
    return "_sharded_decode_ok", {
        "workload": {"tp": 2},
        "tp1": _engine_leg(8, **leg), "tp2": _engine_leg(8, **leg),
        "collectives": {"static_matches_runtime": True,
                        "gathers_per_step": 0},
        "memory": {"static_matches_runtime": True,
                   "runtime_peak_bytes": 4096, "live_bytes_after": 0},
        "relative_tokens_per_s": 0.1}, ("tp2",)


@pytest.mark.parametrize("sound", [_sound_deploy_report,
                                   _sound_prefix_spec_report,
                                   _sound_sharded_decode_report],
                         ids=["deploy", "prefix-spec", "sharded-decode"])
def test_serve_bench_exit_gate_reads_counts_and_no_clock(sound):
    """Sound counts with absurd times pass; the same report with one
    leaked KV block, or one steady-state recompile, fails."""
    import copy
    import serve_bench
    gate, report, engine_at = sound()
    gate = getattr(serve_bench, gate)
    assert gate(report) is True
    for count in ("kv_leaked_blocks", "steady_state_recompiles"):
        broken = copy.deepcopy(report)
        leg = broken
        for key in engine_at:
            leg = leg[key]
        leg[count] = 1
        assert gate(broken) is False, count
