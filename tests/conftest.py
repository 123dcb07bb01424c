"""Test configuration: tests run on 8 virtual CPU devices."""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault(
    "XLA_FLAGS",
    (os.environ.get("XLA_FLAGS", "") +
     " --xla_force_host_platform_device_count=8").strip())

import logging
import random as _pyrandom

import jax
import numpy as _np
import pytest

# tests run on 8 virtual CPU devices
jax.config.update("jax_platforms", "cpu")


@pytest.fixture(autouse=True)
def with_seed(request):
    """Seed np/python/framework per test and log it for reproduction
    (reference tests/python/unittest/common.py:112-206 @with_seed)."""
    seed = os.environ.get("MXNET_TEST_SEED")
    seed = int(seed) if seed else _np.random.randint(0, 2 ** 31)
    _np.random.seed(seed)
    _pyrandom.seed(seed)
    try:
        import mxnet_tpu as mx
        mx.random.seed(seed)
    except ImportError:
        pass
    yield
    if request.node.rep_call.failed if hasattr(request.node, "rep_call") else False:
        logging.error("Test failed with MXNET_TEST_SEED=%d", seed)


@pytest.hookimpl(tryfirst=True, hookwrapper=True)
def pytest_runtest_makereport(item, call):
    outcome = yield
    rep = outcome.get_result()
    setattr(item, "rep_" + rep.when, rep)
