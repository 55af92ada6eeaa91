"""TPU-suite harness: unlike tests/conftest.py this does NOT force the CPU
mesh, and it makes ``tpu(0)`` the default context of every test — the
framework's own default is the host, so the re-exported CPU tests (which
name no context) would otherwise run on the host's cores beside an idle
chip.

Reference parity: tests/python/gpu/test_operator_gpu.py's
import-and-rerun trick (SURVEY.md §4.3) — the cheapest possible
backend-parity harness: the CPU suite IS the TPU suite.

Run, on a machine with a chip, in ONE process (a chip belongs to one
process at a time: no ``-n`` workers, and nothing else that touches jax
beside it):

    python -m pytest tests_tpu/ -q -p no:cacheprovider

Without a chip every test here skips.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# numerical-parity harness: TPU matmuls default to bf16 operand
# truncation; op tests compare against fp64/numpy references, so pin
# full fp32 precision (the check_consistency discipline of SURVEY §4)
import jax  # noqa: E402

jax.config.update("jax_default_matmul_precision", "highest")

# one seed formula + failure-replay hook for both harnesses (the shared
# module is import-side-effect free: it must not trigger tests/conftest's
# CPU forcing here)
from tests._seedutil import attach_replay_section, test_seed  # noqa: E402


@pytest.fixture(scope="session")
def chip():
    """The chip's context, its health decided HERE, in the process that
    goes on to use it: a probing child would hold the chip its parent then
    needs.  One real round trip, not just a device listing."""
    import jax.numpy as jnp
    import mxnet_tpu as mx
    devices = jax.devices()
    if devices[0].platform != "tpu":
        pytest.skip(f"no TPU: jax sees {devices}")
    ones = jnp.ones((64, 64))
    assert float((ones @ ones).block_until_ready()[0, 0]) == 64.0
    return mx.tpu(0)


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_makereport(item, call):
    outcome = yield
    attach_replay_section(item, outcome.get_result())


@pytest.fixture(autouse=True)
def _seeded_on_the_chip(request, chip):
    seed = test_seed(request.node.nodeid)
    np.random.seed(seed)
    import mxnet_tpu as mx
    mx.random.seed(seed)
    with chip:
        yield
