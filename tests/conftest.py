"""Test harness configuration.

Tests run on a virtual 8-device CPU mesh (the TPU analog of the reference's
CPU test suite; real-TPU runs use the same tests via the import-and-rerun
trick — SURVEY.md §4.3).

force_cpu_mesh sets XLA_FLAGS and jax's platform before the first backend
query, whatever JAX_PLATFORMS says: the suite never reaches for a chip.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from mxnet_tpu.base import force_cpu_mesh  # noqa: E402

# MXNET_TEST_ON_TPU=1 leaves the TPU backend live so the TPU-gated file
# (test_kernels_tpu.py) can reach the chip — in ONE process: no xdist
# workers, a chip belongs to one process at a time.  Default is the
# virtual CPU mesh.
if os.environ.get("MXNET_TEST_ON_TPU", "") != "1":
    force_cpu_mesh(8)

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from tests._seedutil import attach_replay_section, test_seed  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: excluded from tier-1 (`-m 'not slow'`) — perf guards and "
        "long-haul checks")


@pytest.fixture(autouse=True)
def _seed_everything(request):
    """Reference parity: tests/python/unittest/common.py @with_seed —
    seed numpy + framework RNG per test; honor MXNET_TEST_SEED for replay.

    The seed is derived with crc32 (NOT Python hash(), which is salted per
    interpreter run) so every run of the suite sees identical seeds, and it
    is printed on failure so `MXNET_TEST_SEED=<n> pytest <nodeid>` replays
    the exact failing draw — both halves of the @with_seed contract.
    """
    np.random.seed(test_seed(request.node.nodeid))
    import mxnet_tpu as mx
    mx.random.seed(test_seed(request.node.nodeid))
    yield


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_makereport(item, call):
    outcome = yield
    attach_replay_section(item, outcome.get_result())
