"""NDArray: a mutable, asynchronous tensor over immutable XLA buffers.

Reference role: include/mxnet/ndarray.h + src/ndarray/ — ref-counted Chunk,
zero-copy views, async read/write ordered by the dependency engine
(SURVEY.md §2.1, §7 "Design stance").

TPU-native design (the survey's hardest-ranked problem): a ``jax.Array`` is
immutable and asynchronously computed.  ``NDArray`` therefore holds
``(buffer, version)``; every in-place op produces a *new* buffer and bumps the
version — XLA donation makes this cheap under jit, and conflicting writes are
serialized by the version update itself, which replaces the reference's
engine-side write-var queueing.  Views (``reshape``/basic slicing) are lazy
``(base, view-spec)`` pairs: reads materialize through the spec and are cached
against the root version; writes scatter back into the root buffer
(``.at[key].set``), so MXNet's write-through aliasing is preserved.  Reads are
async exactly as the reference's: jax values are futures, and ``asnumpy()`` /
``wait_to_read()`` are the sync points.
"""
from __future__ import annotations

from typing import Any, Optional, Sequence, Tuple

import numpy as _np

from ..base import MXNetError, dtype_np, jax_compute_dtype, default_dtype
from ..context import Context, current_context
from ..engine import PendingValue as _PendingValue
from .. import autograd as _autograd

__all__ = ["NDArray", "array", "from_jax", "zeros", "ones", "empty", "full",
           "arange", "zeros_like", "ones_like", "concat_context_check"]


def _jnp():
    import jax.numpy as jnp
    return jnp


def _is_bool_mask(key) -> bool:
    """A 1-D boolean array key (numpy or jax) selecting leading-axis rows."""
    dt = getattr(key, "dtype", None)
    return dt is not None and _np.dtype(dt) == _np.bool_ \
        and getattr(key, "ndim", 0) == 1


def _mask_to_rows(key, shape) -> _np.ndarray:
    """Validate a boolean mask against axis 0 and materialize row indices
    (numpy/reference contract: mismatched length is an IndexError, never a
    silent clamp)."""
    key = _np.asarray(key)
    if key.shape[0] != shape[0]:
        raise IndexError(
            f"boolean index of length {key.shape[0]} does not match "
            f"axis 0 of shape {shape}")
    return _np.nonzero(key)[0]


def _is_basic_index(key) -> bool:
    if isinstance(key, (int, slice, type(Ellipsis), type(None), _np.integer)):
        return True
    if isinstance(key, tuple):
        return all(_is_basic_index(k) for k in key)
    return False


class NDArray:
    """Mutable n-dimensional array resident on a TPU/CPU device."""

    __slots__ = ("_data", "_ctx", "_version", "_ag", "_base", "_viewspec",
                 "_cache", "_shape", "_dtype", "__weakref__")

    # make NDArray win over numpy in mixed binary ops
    __array_priority__ = 1000.0

    def __init__(self, data, ctx: Optional[Context] = None, _base=None,
                 _viewspec=None, _shape=None, _dtype=None):
        self._data = data            # jax.Array (None when this is a view)
        self._ctx = ctx if ctx is not None else current_context()
        self._version = 0
        self._ag = None              # autograd.AGInfo
        self._base = _base           # parent NDArray when this is a view
        self._viewspec = _viewspec   # ("reshape", shape) | ("slice", key)
        self._cache = None           # (root_version, materialized value)
        if _shape is not None:
            self._shape = tuple(_shape)
            self._dtype = _dtype
        else:
            self._shape = tuple(data.shape)
            self._dtype = _np.dtype(data.dtype)

    # ------------------------------------------------------------------
    # buffer discipline
    # ------------------------------------------------------------------
    def _root(self) -> "NDArray":
        nd = self
        while nd._base is not None:
            nd = nd._base
        return nd

    def _read(self):
        """Current jax value (possibly an in-flight future).

        The pending-value barrier: if this array's producer sits in an
        unflushed bulk segment (register.py), the whole segment executes
        as one fused dispatch before the value is returned — reads are
        sync points exactly as in the reference engine."""
        if self._base is None:
            d = self._data
            if type(d) is _PendingValue:
                d.segment.flush()
                d = self._data
                if type(d) is _PendingValue:
                    raise MXNetError(
                        "bulked segment failed at an earlier sync point: "
                        f"{d.segment.error!r}")
            return d
        rootver = self._root()._version
        if self._cache is not None and self._cache[0] == rootver:
            return self._cache[1]
        parent = self._base._read()
        op, arg = self._viewspec
        val = parent.reshape(arg) if op == "reshape" else parent[arg]
        self._cache = (rootver, val)
        return val

    def _set_data(self, val) -> None:
        """Replace contents (the in-place write primitive).

        On a view, scatters back through the view chain into the root buffer,
        so sibling views observe the write — MXNet's shared-memory semantics.
        """
        if self._base is None:
            self._data = val
            self._version += 1
        else:
            parent = self._base._read()
            op, arg = self._viewspec
            if op == "reshape":
                newp = val.reshape(parent.shape)
            else:
                newp = parent.at[arg].set(val)
            self._base._set_data(newp)
            self._cache = (self._root()._version, val)

    # ------------------------------------------------------------------
    # basic properties
    # ------------------------------------------------------------------
    @property
    def shape(self) -> Tuple[int, ...]:
        return self._shape

    @property
    def dtype(self):
        return self._dtype

    @property
    def ndim(self) -> int:
        return len(self._shape)

    @property
    def size(self) -> int:
        n = 1
        for s in self._shape:
            n *= s
        return n

    @property
    def context(self) -> Context:
        return self._ctx

    ctx = context

    @property
    def stype(self) -> str:
        return "default"

    def tostype(self, stype: str):
        """Convert storage type (reference: NDArray.tostype)."""
        if stype == "default":
            return self
        from ..sparse import cast_storage
        return cast_storage(self, stype)

    @property
    def grad(self) -> Optional["NDArray"]:
        info = self._ag
        return info.grad if info is not None and info.is_variable else None

    @property
    def T(self) -> "NDArray":
        from . import transpose
        return transpose(self)

    def __repr__(self):
        # repr is an interactive/debug surface — materializing IS
        # the point
        # mxlint: disable=hidden-host-sync — interactive repr
        return (f"\n{self.asnumpy()}\n<NDArray {'x'.join(map(str, self._shape))}"
                f" @{self._ctx}>")

    def __len__(self):
        if not self._shape:
            raise TypeError("len() of 0-d NDArray")
        return self._shape[0]

    # ------------------------------------------------------------------
    # sync / conversion
    # ------------------------------------------------------------------
    def asnumpy(self) -> _np.ndarray:
        """Copy to host memory; blocks until the value is computed
        (reference sync point: NDArray::SyncCopyToCPU)."""
        return _np.asarray(self._read())

    def asscalar(self):
        if self.size != 1:
            raise ValueError("the array is not scalar-sized")
        # THE documented sync point for scalars (reference
        # NDArray::SyncCopyToCPU semantics) — callers opt in
        # mxlint: disable=hidden-host-sync — the sanctioned sync API
        return self.asnumpy().reshape(()).item()

    def item(self):
        return self.asscalar()

    def __float__(self):
        return float(self.asscalar())

    def __int__(self):
        return int(self.asscalar())

    def __bool__(self):
        if self.size == 0:
            return False
        if self.size == 1:
            return bool(self.asscalar())
        raise ValueError("ambiguous truth value of multi-element NDArray")

    def wait_to_read(self) -> None:
        """Block until this array's value is ready (Engine::WaitForVar)."""
        import jax
        jax.block_until_ready(self._read())

    def __array__(self, dtype=None):
        # np-protocol boundary: numpy asked for host memory
        # mxlint: disable=hidden-host-sync — numpy protocol hook
        a = self.asnumpy()
        return a.astype(dtype) if dtype is not None else a

    # ------------------------------------------------------------------
    # movement / copies
    # ------------------------------------------------------------------
    def copyto(self, other) -> "NDArray":
        """Copy into an existing NDArray or onto a Context."""
        import jax
        if isinstance(other, Context):
            val = jax.device_put(self._read(), other.device)
            return NDArray(val, ctx=other)
        if not isinstance(other, NDArray):
            raise TypeError(f"copyto target must be NDArray/Context, got {type(other)}")
        if other.shape != self.shape:
            raise ValueError(f"shape mismatch {self.shape} vs {other.shape}")
        val = self._read()
        if other.dtype != self.dtype:
            val = val.astype(_np.dtype(other.dtype))
        val = jax.device_put(val, other.context.device)
        other._set_data(val)
        return other

    def as_in_context(self, ctx: Context) -> "NDArray":
        if ctx == self._ctx:
            return self
        return self.copyto(ctx)

    as_in_ctx = as_in_context

    def copy(self) -> "NDArray":
        return NDArray(self._read(), ctx=self._ctx)

    def astype(self, dtype, copy: bool = True) -> "NDArray":
        npdt = jax_compute_dtype(dtype)   # documented int64->int32 contract
        if not copy and npdt == self.dtype:
            return self
        return NDArray(self._read().astype(npdt), ctx=self._ctx)

    def detach(self) -> "NDArray":
        out = NDArray(self._read(), ctx=self._ctx) if self._base is None else \
            NDArray(None, ctx=self._ctx, _base=self._base,
                    _viewspec=self._viewspec, _shape=self._shape,
                    _dtype=self._dtype)
        if self._base is None:
            out._data = self._data
        return out

    # ------------------------------------------------------------------
    # autograd
    # ------------------------------------------------------------------
    def attach_grad(self, grad_req: str = "write", stype=None) -> None:
        """Allocate a gradient buffer and mark this array as a variable."""
        g = zeros(self._shape, ctx=self._ctx, dtype=self._dtype)
        self._ag = _autograd.AGInfo(node=None, index=0, grad=g,
                                    grad_req=grad_req)

    def backward(self, out_grad: Optional["NDArray"] = None,
                 retain_graph: bool = False, train_mode: bool = True) -> None:
        _autograd.backward([self], [out_grad], retain_graph=retain_graph,
                           train_mode=train_mode)

    # ------------------------------------------------------------------
    # views & indexing
    # ------------------------------------------------------------------
    def reshape(self, *shape, **kwargs) -> "NDArray":
        if len(shape) == 1 and isinstance(shape[0], (tuple, list)):
            shape = tuple(shape[0])
        shape = kwargs.get("shape", shape)
        shape = _infer_reshape(self._shape, tuple(int(s) for s in shape),
                               reverse=bool(kwargs.get("reverse", False)))
        if _autograd.is_recording():
            from .register import invoke_by_name
            return invoke_by_name("reshape", [self], {"shape": shape})
        dt = self._dtype
        return NDArray(None, ctx=self._ctx, _base=self,
                       _viewspec=("reshape", shape), _shape=shape, _dtype=dt)

    def reshape_like(self, other) -> "NDArray":
        return self.reshape(other.shape)

    def expand_dims(self, axis: int) -> "NDArray":
        from . import expand_dims
        return expand_dims(self, axis=axis)

    def squeeze(self, axis=None) -> "NDArray":
        from . import squeeze
        return squeeze(self, axis=axis)

    def flatten(self) -> "NDArray":
        return self.reshape((self._shape[0], -1)) if self.ndim > 1 else self

    def slice(self, begin, end, step=None) -> "NDArray":
        from . import slice as _slice
        return _slice(self, begin=begin, end=end, step=step)

    def __getitem__(self, key):
        if isinstance(key, NDArray):
            key = key._read()
        if isinstance(key, list):              # bool lists are masks too
            key = _np.asarray(key)
        if _is_bool_mask(key):
            # boolean-mask indexing (reference ndarray.py advanced
            # indexing): data-dependent output shape, so the mask is
            # materialized host-side into integer rows — same eager
            # stance as boolean_mask the op
            key = _mask_to_rows(key, self._shape)
        if _is_basic_index(key):
            if _autograd.is_recording():
                from .register import invoke_by_name
                return invoke_by_name("_basic_index", [self], {"key": _freeze_key(key)})
            val_shape = _index_shape(self._shape, key)
            return NDArray(None, ctx=self._ctx, _base=self,
                           _viewspec=("slice", key), _shape=val_shape,
                           _dtype=self._dtype)
        # advanced indexing → gather copy (differentiable through the op path)
        from .register import invoke_by_name
        return invoke_by_name("_advanced_index", [self, array(key, ctx=self._ctx)], {})

    def __setitem__(self, key, value):
        if isinstance(key, NDArray):
            key = key._read()
        if isinstance(key, list):
            key = _np.asarray(key)
        if _is_bool_mask(key):
            key = _mask_to_rows(key, self._shape)
        if isinstance(value, NDArray):
            value = value._read()
        cur = self._read()
        if isinstance(value, (int, float, bool, _np.generic)):
            new = cur.at[key].set(_jnp().asarray(value, dtype=cur.dtype))
        else:
            new = cur.at[key].set(_jnp().asarray(value).astype(cur.dtype))
        self._set_data(new)

    # ------------------------------------------------------------------
    # arithmetic — routed through the op registry so autograd records them
    # ------------------------------------------------------------------
    def _binop(self, name, other, reverse=False):
        from .register import invoke_binary
        return invoke_binary(name, self, other, reverse=reverse)

    def __add__(self, o):
        return self._binop("broadcast_add", o)

    def __radd__(self, o):
        return self._binop("broadcast_add", o, reverse=True)

    def __iadd__(self, o):
        r = self._binop("broadcast_add", o)
        self._set_data(r._read())
        return self

    def __sub__(self, o):
        return self._binop("broadcast_sub", o)

    def __rsub__(self, o):
        return self._binop("broadcast_sub", o, reverse=True)

    def __isub__(self, o):
        r = self._binop("broadcast_sub", o)
        self._set_data(r._read())
        return self

    def __mul__(self, o):
        return self._binop("broadcast_mul", o)

    def __rmul__(self, o):
        return self._binop("broadcast_mul", o, reverse=True)

    def __imul__(self, o):
        r = self._binop("broadcast_mul", o)
        self._set_data(r._read())
        return self

    def __truediv__(self, o):
        return self._binop("broadcast_div", o)

    def __rtruediv__(self, o):
        return self._binop("broadcast_div", o, reverse=True)

    def __itruediv__(self, o):
        r = self._binop("broadcast_div", o)
        self._set_data(r._read())
        return self

    def __mod__(self, o):
        return self._binop("broadcast_mod", o)

    def __rmod__(self, o):
        return self._binop("broadcast_mod", o, reverse=True)

    def __pow__(self, o):
        return self._binop("broadcast_power", o)

    def __rpow__(self, o):
        return self._binop("broadcast_power", o, reverse=True)

    def __neg__(self):
        from .register import invoke_by_name
        return invoke_by_name("negative", [self], {})

    def __abs__(self):
        from .register import invoke_by_name
        return invoke_by_name("abs", [self], {})

    def __eq__(self, o):
        return self._binop("broadcast_equal", o)

    def __ne__(self, o):
        return self._binop("broadcast_not_equal", o)

    def __lt__(self, o):
        return self._binop("broadcast_lesser", o)

    def __le__(self, o):
        return self._binop("broadcast_lesser_equal", o)

    def __gt__(self, o):
        return self._binop("broadcast_greater", o)

    def __ge__(self, o):
        return self._binop("broadcast_greater_equal", o)

    def __and__(self, o):
        return self._binop("broadcast_logical_and", o)

    def __rand__(self, o):
        return self._binop("broadcast_logical_and", o, reverse=True)

    def __or__(self, o):
        return self._binop("broadcast_logical_or", o)

    def __ror__(self, o):
        return self._binop("broadcast_logical_or", o, reverse=True)

    def __xor__(self, o):
        return self._binop("broadcast_logical_xor", o)

    def __rxor__(self, o):
        return self._binop("broadcast_logical_xor", o, reverse=True)

    def __invert__(self):
        from .register import invoke_by_name
        return invoke_by_name("logical_not", [self], {})

    __hash__ = None  # mutable

    # reductions / convenience mirrors of mx.nd methods
    def sum(self, axis=None, keepdims=False):
        from . import sum as _sum
        return _sum(self, axis=axis, keepdims=keepdims)

    def mean(self, axis=None, keepdims=False):
        from . import mean as _mean
        return _mean(self, axis=axis, keepdims=keepdims)

    def max(self, axis=None, keepdims=False):
        from . import max as _max
        return _max(self, axis=axis, keepdims=keepdims)

    def min(self, axis=None, keepdims=False):
        from . import min as _min
        return _min(self, axis=axis, keepdims=keepdims)

    def argmax(self, axis=None):
        from . import argmax
        return argmax(self, axis=axis)

    def argmin(self, axis=None):
        from . import argmin
        return argmin(self, axis=axis)

    def transpose(self, axes=None):
        from . import transpose
        return transpose(self, axes=axes)

    def dot(self, other):
        from . import dot
        return dot(self, other)

    # clip/relu/sigmoid/exp/log/sqrt/square/softmax/one_hot/tile are
    # attached by the generic fluent loop in __init__.py (full frontend
    # kwargs incl. out=) — hand-written duplicates were deleted

    def broadcast_to(self, shape):
        from . import broadcast_to
        return broadcast_to(self, shape=shape)


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _infer_reshape(old: Tuple[int, ...], new: Tuple[int, ...],
                   reverse: bool = False) -> Tuple[int, ...]:
    """Resolve MXNet reshape placeholders (0/-1/-2/-3/-4) — delegates to the
    shared resolver in base so the op path and this view path agree."""
    from ..base import resolve_reshape_spec
    return resolve_reshape_spec(old, new, reverse)


def _freeze_key(key):
    """Make an index key hashable for the jit cache."""
    if isinstance(key, list):
        return tuple(key)
    if isinstance(key, tuple):
        return tuple(_freeze_key(k) for k in key)
    if isinstance(key, slice):
        return ("__slice__", key.start, key.stop, key.step)
    return key


def _thaw_key(key):
    if isinstance(key, tuple):
        if len(key) == 4 and key[0] == "__slice__":
            return slice(key[1], key[2], key[3])
        return tuple(_thaw_key(k) for k in key)
    return key


def _index_shape(shape, key) -> Tuple[int, ...]:
    return _np.empty(shape, dtype=_np.bool_)[key].shape


# ---------------------------------------------------------------------------
# constructors
# ---------------------------------------------------------------------------

def from_jax(val, ctx: Optional[Context] = None) -> NDArray:
    return NDArray(val, ctx=ctx)


def array(source, ctx: Optional[Context] = None, dtype=None) -> NDArray:
    """Create an NDArray from any array-like (reference: mx.nd.array)."""
    import jax
    ctx = ctx if ctx is not None else current_context()
    if isinstance(source, NDArray):
        val = source._read()
        if dtype is not None:
            val = val.astype(jax_compute_dtype(dtype))
        return NDArray(jax.device_put(val, ctx.device), ctx=ctx)
    if dtype is None:
        if isinstance(source, _np.ndarray):
            npv = source
            if npv.dtype == _np.float64:
                npv = npv.astype(_np.float32)  # MXNet default dtype is float32
        else:
            # python lists/scalars default to float32 (MXNet convention)
            npv = _np.asarray(source)
            if npv.dtype.kind in "ifu" and npv.dtype != _np.float32:
                npv = npv.astype(_np.float32)
    else:
        # build at the REQUESTED width first, then cast to the jax
        # compute dtype: asarray(python_ints, int32) raises OverflowError
        # past 2^31, while the documented large-tensor contract is
        # wraparound truncation (what jax's own canonicalization did)
        npv = _np.asarray(source, dtype=dtype_np(dtype))
        jcd = jax_compute_dtype(dtype)
        if jcd != npv.dtype:
            npv = npv.astype(jcd)
    return NDArray(jax.device_put(npv, ctx.device), ctx=ctx)


def empty(shape, ctx=None, dtype=None) -> NDArray:
    return zeros(shape, ctx=ctx, dtype=dtype)


def _created(ctx: Context, make):
    """Run a creation thunk on ``ctx``'s device and COMMIT the result
    there.  An uncommitted array follows whatever it is next combined
    with — a host value, the process default device — and its context
    tag would then no longer say where it lives: parameters initialized
    on ``tpu(0)`` or ``cpu(1)`` must stay there."""
    import jax
    dev = ctx.device
    with jax.default_device(dev):
        return jax.device_put(make(), dev)


def zeros(shape, ctx=None, dtype=None, **kwargs) -> NDArray:
    ctx = ctx if ctx is not None else current_context()
    shape = (shape,) if isinstance(shape, int) else tuple(shape)
    return NDArray(_created(ctx, lambda: _jnp().zeros(
        shape, dtype=jax_compute_dtype(dtype))), ctx=ctx)


def ones(shape, ctx=None, dtype=None, **kwargs) -> NDArray:
    ctx = ctx if ctx is not None else current_context()
    shape = (shape,) if isinstance(shape, int) else tuple(shape)
    return NDArray(_created(ctx, lambda: _jnp().ones(
        shape, dtype=jax_compute_dtype(dtype))), ctx=ctx)


def full(shape, val, ctx=None, dtype=None) -> NDArray:
    ctx = ctx if ctx is not None else current_context()
    shape = (shape,) if isinstance(shape, int) else tuple(shape)
    return NDArray(_created(ctx, lambda: _jnp().full(
        shape, val, dtype=jax_compute_dtype(dtype))), ctx=ctx)


def arange(start, stop=None, step=1.0, repeat=1, ctx=None, dtype=None) -> NDArray:
    ctx = ctx if ctx is not None else current_context()

    def make():
        val = _jnp().arange(start, stop, step, dtype=jax_compute_dtype(dtype))
        return val if repeat == 1 else _jnp().repeat(val, repeat)
    return NDArray(_created(ctx, make), ctx=ctx)


def eye(N, M=0, k=0, ctx=None, dtype=None) -> NDArray:
    """Identity-like matrix (reference mx.nd.eye: M=0 means square)."""
    ctx = ctx if ctx is not None else current_context()
    return NDArray(_created(ctx, lambda: _jnp().eye(
        int(N), int(M) if M else int(N), k=int(k),
        dtype=jax_compute_dtype(dtype))), ctx=ctx)


def moveaxis(data: "NDArray", source, destination) -> NDArray:
    """Reference mx.nd.moveaxis — thin transpose wrapper."""
    return NDArray(_jnp().moveaxis(data._read(), source, destination),
                   ctx=data.context)


def linspace(start, stop, num, endpoint=True, ctx=None,
             dtype=None) -> NDArray:
    ctx = ctx if ctx is not None else current_context()
    return NDArray(_created(ctx, lambda: _jnp().linspace(
        start, stop, int(num), endpoint=endpoint,
        dtype=jax_compute_dtype(dtype))), ctx=ctx)


def zeros_like(other: NDArray) -> NDArray:
    return zeros(other.shape, ctx=other.context, dtype=other.dtype)


def ones_like(other: NDArray) -> NDArray:
    return ones(other.shape, ctx=other.context, dtype=other.dtype)


def concat_context_check(arrays: Sequence[NDArray]) -> Context:
    ctxs = {a.context for a in arrays}
    if len(ctxs) != 1:
        raise MXNetError(f"arrays live on different contexts: {ctxs}")
    return next(iter(ctxs))
