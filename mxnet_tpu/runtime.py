"""Runtime feature discovery (reference: src/libinfo.cc +
python/mxnet/runtime.py, SURVEY.md §2.1).

``feature_list()`` / ``Features`` report what this build can do, resolved
lazily from the live JAX install instead of compile-time flags.

Large-tensor support: the reference gates int64 tensor sizes behind the
MXNET_ENABLE_LARGE_TENSOR *compile* flag (reported as INT64_TENSOR_SIZE in
runtime.Features); here it is a *runtime* switch — JAX truncates int64 to
int32 unless ``jax_enable_x64`` is on, so ``enable_large_tensor()`` flips
that config and the feature report follows the live value.
"""
from __future__ import annotations

from typing import Dict, List

__all__ = ["Features", "feature_list", "is_enabled",
           "enable_large_tensor", "large_tensor_enabled"]


def enable_large_tensor(enabled: bool = True) -> None:
    """Enable true int64 tensors/indices (reference: the
    MXNET_ENABLE_LARGE_TENSOR build, tests/nightly/test_large_array.py).
    Affects computations traced after the call; existing compiled graphs
    keep their dtypes."""
    import jax
    jax.config.update("jax_enable_x64", bool(enabled))


def large_tensor_enabled() -> bool:
    import jax
    return bool(jax.config.jax_enable_x64)


class Feature:
    def __init__(self, name: str, enabled: bool):
        self.name = name
        self.enabled = enabled

    def __repr__(self):
        return f"[{'✔' if self.enabled else '✖'} {self.name}]"


import functools


@functools.lru_cache(maxsize=1)
def _detect_cached():
    return tuple(sorted(_detect().items()))


def _detect() -> Dict[str, bool]:
    feats: Dict[str, bool] = {}
    try:
        import jax
        feats["XLA"] = True
        platforms = {d.platform for d in jax.devices()}
        feats["TPU"] = "tpu" in platforms
        feats["CPU"] = True
        feats["CUDA"] = "gpu" in platforms or "cuda" in platforms
    except Exception:
        feats.update({"XLA": False, "TPU": False, "CPU": True,
                      "CUDA": False})
    try:
        import jax.experimental.pallas  # noqa: F401
        feats["PALLAS"] = True
    except Exception:
        feats["PALLAS"] = False
    try:
        import jax.experimental.sparse  # noqa: F401
        feats["SPARSE"] = True
    except Exception:
        feats["SPARSE"] = False
    try:
        from PIL import Image  # noqa: F401
        feats["IMAGE_DECODE"] = True     # reference: OPENCV
    except Exception:
        feats["IMAGE_DECODE"] = False
    feats["BF16"] = True                  # native on TPU; emulated on CPU
    feats["DIST_KVSTORE"] = True          # jax.distributed collectives
    try:
        from . import _native               # noqa: F401
        feats["NATIVE_RUNTIME"] = _native.available()
    except Exception:
        feats["NATIVE_RUNTIME"] = False
    return feats


class Features(dict):
    """Mapping name -> Feature (reference: mx.runtime.Features)."""

    def __init__(self):
        # feature set is fixed per process — detect once (lru_cache);
        # INT64_TENSOR_SIZE alone is live (a runtime switch here)
        super().__init__({k: Feature(k, v) for k, v in _detect_cached()})
        self["INT64_TENSOR_SIZE"] = Feature("INT64_TENSOR_SIZE",
                                            large_tensor_enabled())

    def is_enabled(self, name: str) -> bool:
        f = self.get(name)
        return bool(f and f.enabled)

    def __repr__(self):
        return ", ".join(repr(v) for v in self.values())


def feature_list() -> List[Feature]:
    return list(Features().values())


def is_enabled(name: str) -> bool:
    return Features().is_enabled(name)
