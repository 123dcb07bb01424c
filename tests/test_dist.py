"""Multi-process distributed kvstore tests.

Launches N real worker processes on localhost through tools/launch.py (the
reference's dmlc-tracker 'local' mode, used by
tests/nightly/dist_sync_kvstore.py + ci/docker/runtime_functions.sh:911-941)
and checks they complete with the expected reduced values."""
import os
import socket
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _launch(n, script, timeout=240, extra_env=None, script_args=()):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # each worker is its own process with its own (single) cpu device;
    # the conftest's 8-device XLA flag must not leak in
    env.pop("XLA_FLAGS", None)
    env.update(extra_env or {})
    cmd = [sys.executable, os.path.join(REPO, "tools", "launch.py"),
           "-n", str(n), "--launcher", "local",
           "--env-server-port", str(_free_port()),
           sys.executable, os.path.join(REPO, script)] + list(script_args)
    return subprocess.run(cmd, env=env, cwd=REPO, timeout=timeout,
                          capture_output=True, text=True)


def test_dist_sync_kvstore_4_workers(tmp_path):
    """4 real worker processes: dense (3 dtypes), row_sparse, 2-bit
    compressed push/pull with per-rank numeric asserts (the asserts live in
    tests/dist/dist_sync_kvstore.py and run inside every worker), plus a
    per-rank profile dump merged into one op table (reference
    tests/nightly/test_server_profiling.py analog)."""
    res = _launch(4, "tests/dist/dist_sync_kvstore.py",
                  extra_env={"DIST_PROFILE_DIR": str(tmp_path)})
    assert res.returncode == 0, \
        "launcher failed\nstdout:\n%s\nstderr:\n%s" % (res.stdout, res.stderr)
    for rank in range(4):
        assert "dist_sync_kvstore rank %d/4: OK" % rank in res.stdout
    # every rank left its own trace; the merged table sees all 4 workers
    from mxnet_tpu import profiler
    traces = sorted(tmp_path.glob("dist_profile_rank*.json"))
    assert len(traces) == 4, [t.name for t in traces]
    table = profiler.merge_dumps([str(t) for t in traces],
                                 out=str(tmp_path / "merged_trace.json"))
    assert "push_dense" in table and "pull_dense" in table
    # 3 iterations x 4 ranks
    push_row = next(l for l in table.splitlines() if "push_dense" in l)
    assert push_row.split()[1] == "12", table
    # the kvstore-internal per-key spans (eager-path cost surfacing) merge
    # across ranks too
    assert "KVStoreDist.push(3)" in table, table
    assert (tmp_path / "merged_trace.json").exists()


def test_dist_bandwidth_tool_2_workers():
    """tools/bandwidth.py --kv dist_sync measures the cross-process
    allreduce (the reference tools/bandwidth distributed measurement) and
    prints one JSON line from rank 0."""
    import json
    res = _launch(2, "tools/bandwidth.py",
                  script_args=["--kv", "dist_sync", "--size-mb", "1",
                               "--iters", "4"])
    assert res.returncode == 0, \
        "launcher failed\nstdout:\n%s\nstderr:\n%s" % (res.stdout, res.stderr)
    line = next(l for l in res.stdout.splitlines() if l.startswith("{"))
    rec = json.loads(line)
    assert rec["metric"] == "kvstore_dist_sync_allreduce"
    assert rec["workers"] == 2
    assert "value" in rec       # a rate: reported, never gated here


def test_dist_rendezvous_timeout_diagnosis():
    """A worker whose peers never arrive fails FAST instead of hanging
    (SURVEY §5 barrier health at init).  jax's coordination client
    terminates the process from C++ on deadline (LOG(FATAL) in client.h),
    so the contract observable from outside is: non-zero exit within the
    configured timeout, stderr naming the deadline; the MXNetError wrapper
    in kvstore._init_distributed covers the python-visible failure modes
    (bad address, misconfiguration)."""
    import time
    env = dict(os.environ)
    # rank 1 = a CLIENT whose coordinator never comes up (rank 0's own
    # failure is a hard abort inside the C++ coordination service)
    env.update({"JAX_PLATFORMS": "cpu", "MX_KV_NUM_WORKERS": "2",
                "MX_KV_RANK": "1", "MX_KV_ROOT_URI": "127.0.0.1",
                "MX_KV_ROOT_PORT": str(_free_port()),
                "MX_KV_INIT_TIMEOUT": "5"})
    env.pop("XLA_FLAGS", None)
    code = ("import jax; jax.config.update('jax_platforms','cpu');"
            "import mxnet_tpu as mx; mx.kv.create('dist_sync')")
    t0 = time.monotonic()
    res = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                         timeout=120, capture_output=True, text=True)
    elapsed = time.monotonic() - t0
    assert res.returncode != 0
    assert elapsed < 60, "rendezvous hung instead of timing out: %gs" % elapsed
    assert ("DEADLINE_EXCEEDED" in res.stderr
            or "rendezvous failed" in res.stderr), res.stderr[-500:]


def test_dist_fused_step_2_workers():
    """The compiled-step multi-host path (make_data_parallel_train_step over
    a 2-process global mesh, grad psum in-graph): the distributed
    trajectory must match a single-process run over the full batch — the
    fused-path counterpart of the per-key kvstore checks above."""
    res = _launch(2, "tests/dist/dist_fused_step.py")
    assert res.returncode == 0, \
        "launcher failed\nstdout:\n%s\nstderr:\n%s" % (res.stdout, res.stderr)
    for rank in range(2):
        assert "dist_fused_step rank %d/2: OK" % rank in res.stdout
