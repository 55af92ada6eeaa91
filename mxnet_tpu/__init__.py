"""mxnet_tpu: a TPU-native deep-learning framework with MXNet 1.x's
capabilities (reference: thomelane/incubator-mxnet — see SURVEY.md).

Not a port: the compute path is JAX/XLA/Pallas and parallelism is
`jax.sharding` over device meshes; the *user-facing surface* (NDArray,
autograd, Gluon, Symbol/Module, KVStore, io, metric, optimizer) mirrors the
reference so model code carries over.

Conventional entry point::

    import mxnet_tpu as mx
    x = mx.nd.ones((2, 3), ctx=mx.tpu(0))
"""
from __future__ import annotations

__version__ = "0.1.0"

from .base import MXNetError, register_env, get_env, list_env

# numerics-parity escape hatch: TPU matmuls default to bf16-precision
# accumulation (the MXU fast path); set MXNET_MATMUL_PRECISION=highest to
# force full fp32 (reference-exact numerics, ~3x slower matmuls).
# Resolved through the knob table BEFORE the first jax import below.
_prec = get_env("MXNET_MATMUL_PRECISION")
if _prec:
    import jax as _jax
    _jax.config.update("jax_default_matmul_precision", _prec)
from . import faults
from .context import Context, cpu, gpu, tpu, cpu_pinned, num_gpus, num_tpus, \
    current_context
from . import context
from . import engine
from . import ndarray
from . import ndarray as nd
from .ndarray import NDArray
from . import autograd
from . import random
from . import initializer
from . import init
from . import optimizer
from .optimizer import Optimizer
from . import lr_scheduler
from . import metric
from . import kvstore
from . import kvstore as kv
from . import recordio
from . import sparse
ndarray.sparse = sparse          # reference surface: mx.nd.sparse
from . import io
from . import image
from . import model
from . import callback
from . import monitor
from .monitor import Monitor
from . import rnn
from . import name
from . import attribute
from .attribute import AttrScope
from . import gluon
from . import parallel
from . import symbol
from . import symbol as sym
from . import numpy as np          # the numpy-compatible frontend (mx.np)
from . import numpy_extension as npx  # DL ops for numpy-frontend code
from . import module
from . import module as mod
from . import contrib
from . import profiler
from . import runtime
from . import visualization
from . import visualization as viz
from . import operator
ndarray.Custom = operator.Custom     # reference surface: mx.nd.Custom
from . import rtc
from . import test_utils
from . import observability
from . import serving
from . import tuning
# opt-in persistent compile cache: wiring the disk tier (segment hooks)
# costs nothing when the environment names no cache directory
if tuning.compile_cache.cache_dir():
    tuning.compile_cache.active()
# opt-in exporters: a Prometheus /metrics endpoint when
# MXTPU_METRICS_PORT is set, a periodic JSONL snapshot writer when
# MXTPU_METRICS_JSONL is set; no cost (export never even imports)
# otherwise
if get_env("MXTPU_METRICS_PORT") or get_env("MXTPU_METRICS_JSONL"):
    observability.export.maybe_start_from_env()
# opt-in continuous stack sampler: a daemon folding all-thread stacks
# into rotating flamegraph windows when MXTPU_PROF_SAMPLE_HZ > 0 (the
# trainer/server constructors re-probe, so late env changes also take;
# unset = the sampler module never even imports here)
if get_env("MXTPU_PROF_SAMPLE_HZ"):
    observability.sampler.maybe_start_from_env()


def waitall() -> None:
    """Block until all queued computation finishes (reference: mx.nd.waitall)."""
    engine.wait_all()
