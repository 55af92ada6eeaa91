"""Re-run the core operator/NDArray/autograd/gluon suites on the TPU
backend (reference: tests/python/gpu/test_operator_gpu.py imports the
entire CPU unittest module and re-runs it on gpu(0) — SURVEY.md §4.3).

The CPU files name no context, so a straight re-export under this
directory's conftest (default context tpu(0)) re-executes every op on the
chip.
"""
from tests.test_ndarray import *          # noqa: F401,F403
from tests.test_autograd import *         # noqa: F401,F403
from tests.test_linalg_spatial import *   # noqa: F401,F403
from tests.test_contrib_misc import *     # noqa: F401,F403
from tests.test_ctc import *              # noqa: F401,F403
from tests.test_quantization import *     # noqa: F401,F403
from tests.test_ops_misc import *         # noqa: F401,F403
from tests.test_op_sweep import *         # noqa: F401,F403
from tests.test_control_flow import *     # noqa: F401,F403
from tests.test_random_ops import *       # noqa: F401,F403
from tests.test_sparse import *           # noqa: F401,F403
from tests.test_large_array import *      # noqa: F401,F403
from tests.test_image import *            # noqa: F401,F403
from tests.test_kernels import *          # noqa: F401,F403
from tests.test_kernels_tpu import *      # noqa: F401,F403
from tests.test_ops_tail import *         # noqa: F401,F403
from tests.test_sldwin import *           # noqa: F401,F403
from tests.test_dgl import *              # noqa: F401,F403
from tests.test_numpy_frontend import *   # noqa: F401,F403
