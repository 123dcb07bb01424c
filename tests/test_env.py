"""Env-var knob registry tests (mxnet_tpu/env.py, the env_var.md analog)."""
import io

from mxnet_tpu import env


def test_env_defaults(monkeypatch):
    monkeypatch.delenv("DMLC_NUM_WORKER", raising=False)
    assert env.get("DMLC_NUM_WORKER") == 1
    assert env.get("MX_KV_INIT_TIMEOUT") == 120.0


def test_env_override(monkeypatch):
    monkeypatch.setenv("DMLC_NUM_WORKER", "4")
    assert env.get("DMLC_NUM_WORKER") == 4
    monkeypatch.setenv("MXNET_PROFILER_AUTOSTART", "0")
    assert env.get("MXNET_PROFILER_AUTOSTART") is False
    monkeypatch.setenv("MXNET_PROFILER_AUTOSTART", "1")
    assert env.get("MXNET_PROFILER_AUTOSTART") is True


def test_env_describe():
    buf = io.StringIO()
    env.describe(file=buf)
    text = buf.getvalue()
    assert "MXNET_HOME" in text and "absorbed" in text


def test_kvstore_reads_registry(monkeypatch):
    import mxnet_tpu as mx
    monkeypatch.setenv("DMLC_NUM_WORKER", "1")
    monkeypatch.setenv("DMLC_WORKER_ID", "0")
    kv = mx.kv.create("dist_sync")
    assert kv.num_workers == 1 and kv.rank == 0
