"""Gluon Block / HybridBlock: composable imperative models with a jit path.

Reference parity: python/mxnet/gluon/block.py (SURVEY.md §2.5, §3.3) —
Block (eager), HybridBlock (`hybridize()` → CachedOp), prefix/name scoping,
parameter collection, save/load.

TPU-native design (the survey's designated XLA lowering point, §7):
``hybridize()`` does NOT build an NNVM graph — it traces ``hybrid_forward``
with tracer-backed NDArrays into ONE jitted XLA computation per input
signature (shape/dtype tuple = the cache key, exactly the reference's
CachedOp signature match).  During the trace every descendant Parameter's
``data()`` is substituted by a function input (so weights are runtime
arguments, not baked constants), RNG draws split from a traced key input
(fresh dropout masks per call), and in-place writes to parameters (BatchNorm
running stats) are captured as extra outputs and written back after the call
— the functional translation of the reference's FMutateInputs.  Autograd
records the whole cached call as a single tape node via ``jax.vjp``,
mirroring CachedOp::Backward.
"""
from __future__ import annotations

import contextlib
import re
import threading
import warnings
from typing import Any, Dict, List, Optional, Tuple

from ..base import MXNetError
from ..context import Context, current_context
from .. import autograd as _autograd
from .. import random as _grandom
from ..ndarray import NDArray
from ..ndarray.register import _BoundedCache
from .. import ndarray as nd_mod
from .parameter import Parameter, ParameterDict, DeferredInitializationError

__all__ = ["Block", "HybridBlock", "SymbolBlock", "CachedGraph",
           "name_scope"]

_naming_counter_lock = threading.Lock()
_naming_counters: Dict[str, int] = {}


def _gen_prefix(hint: str) -> str:
    with _naming_counter_lock:
        idx = _naming_counters.get(hint, 0)
        _naming_counters[hint] = idx + 1
    return f"{hint}{idx}_"


class _BlockScope:
    """Prefix scoping: blocks created inside ``with parent.name_scope():``
    get the parent's prefix prepended (reference name manager)."""

    _current = threading.local()

    def __init__(self, block: "Block"):
        self._block = block
        self._counters: Dict[str, int] = {}

    @staticmethod
    def create(prefix: Optional[str], params, hint: str):
        cur = getattr(_BlockScope._current, "value", None)
        if cur is None:
            if prefix is None:
                prefix = _gen_prefix(hint)
            pd = ParameterDict(prefix, params)
            return prefix, pd
        if prefix is None:
            idx = cur._counters.get(hint, 0)
            cur._counters[hint] = idx + 1
            prefix = f"{hint}{idx}_"
        full = cur._block.prefix + prefix
        pd = ParameterDict(full, params if params is not None
                           else cur._block._params._shared)
        return full, pd

    def __enter__(self):
        self._old = getattr(_BlockScope._current, "value", None)
        _BlockScope._current.value = self
        return self

    def __exit__(self, *a):
        _BlockScope._current.value = self._old


class Block:
    """Base building block (reference: gluon.Block)."""

    def __init__(self, prefix: Optional[str] = None, params=None):
        hint = _camel_to_snake(type(self).__name__)
        self._prefix, self._params = _BlockScope.create(prefix, params, hint)
        self._scope = _BlockScope(self)
        self._children: Dict[str, Block] = {}
        self._reg_params: Dict[str, Parameter] = {}
        self._forward_hooks: List = []

    # -- attribute registration -------------------------------------------
    def __setattr__(self, name, value):
        if isinstance(value, Block):
            existing = self.__dict__.get("_children")
            if existing is not None:
                existing[name] = value
        elif isinstance(value, Parameter):
            reg = self.__dict__.get("_reg_params")
            if reg is not None:
                reg[name] = value
        super().__setattr__(name, value)

    @property
    def prefix(self) -> str:
        return self._prefix

    @property
    def name(self) -> str:
        return self._prefix[:-1] if self._prefix.endswith("_") else self._prefix

    @property
    def params(self) -> ParameterDict:
        return self._params

    def name_scope(self) -> _BlockScope:
        return self._scope

    # -- params ------------------------------------------------------------
    def collect_params(self, select: Optional[str] = None) -> ParameterDict:
        ret = ParameterDict(self._params.prefix)
        if select is None:
            ret.update(self._params)
        else:
            pat = re.compile(select)
            for name, p in self._params.items():
                if pat.match(name):
                    ret._params[name] = p
        for child in self._children.values():
            sub = child.collect_params(select)
            for k, v in sub.items():
                ret._params[k] = v
        return ret

    def initialize(self, init=None, ctx=None, verbose=False,
                   force_reinit=False) -> None:
        self.collect_params().initialize(init, ctx, verbose, force_reinit)

    def param_context(self) -> Context:
        """The context this block's parameters were initialized on (the
        first one, for a multi-context block); the current context for a
        block with no parameters."""
        for p in self.collect_params().values():
            if p._data is not None:
                return next(iter(p._data))
            if p._deferred_init is not None:
                return p._deferred_init[1][0]
        return current_context()

    def example_inputs(self, inputs) -> Tuple[NDArray, ...]:
        """An example batch as NDArrays.  Host values (numpy, lists) go
        where the NDArrays among them are, else where the parameters are:
        a serving thread's default context is the host, and a graph traced
        there could not read parameters that live on the chip."""
        ctx = next((a.context for a in inputs if isinstance(a, NDArray)),
                   None)
        if ctx is None:
            ctx = self.param_context()
        return tuple(a if isinstance(a, NDArray) else nd_mod.array(a, ctx=ctx)
                     for a in inputs)

    def register_child(self, block: "Block", name: Optional[str] = None) -> None:
        self._children[name or str(len(self._children))] = block

    def apply(self, fn) -> "Block":
        for child in self._children.values():
            child.apply(fn)
        fn(self)
        return self

    def cast(self, dtype) -> None:
        for child in self._children.values():
            child.cast(dtype)
        for p in self._params.values():
            p.cast(dtype)

    def hybridize(self, active: bool = True, **kwargs) -> None:
        for child in self._children.values():
            child.hybridize(active, **kwargs)

    # -- persistence ---------------------------------------------------------
    def _collect_params_with_prefix(self, prefix: str = "") -> Dict[str, Parameter]:
        """Structural (attribute-path) parameter names, e.g. ``0.weight`` —
        the reference's save_parameters naming, robust to prefix counters."""
        if prefix:
            prefix += "."
        ret = {prefix + key: p for key, p in self._reg_params.items()}
        for name, child in self._children.items():
            ret.update(child._collect_params_with_prefix(prefix + name))
        return ret

    def save_parameters(self, filename: str, deduplicate: bool = False) -> None:
        from ..ndarray import utils as nd_utils
        params = self._collect_params_with_prefix()
        arrs = {name: p.data() for name, p in params.items()}
        nd_utils.save(filename, arrs)

    def load_parameters(self, filename: str, ctx=None, allow_missing=False,
                        ignore_extra=False, cast_dtype=False,
                        dtype_source="current") -> None:
        from ..ndarray import utils as nd_utils
        loaded = nd_utils.load(filename)
        params = self._collect_params_with_prefix()
        if loaded and params and not any(k in params for k in loaded):
            # fall back: file saved with full prefixed names
            full = self.collect_params()
            loaded = {_strip(k, self.prefix): v for k, v in loaded.items()}
            params = {_strip(k, self.prefix): p for k, p in full.items()}
        for name, arr in loaded.items():
            if name not in params:
                if ignore_extra:
                    continue
                raise MXNetError(f"{filename} contains unknown parameter "
                                 f"{name!r}")
            p = params[name]
            if p._data is None and p._deferred_init is None and ctx is not None:
                p.initialize(ctx=ctx)
            p.set_data(arr)
        if not allow_missing:
            for name in params:
                if name not in loaded:
                    raise MXNetError(f"parameter {name!r} missing from "
                                     f"{filename}")

    save_params = save_parameters
    load_params = load_parameters

    # -- call ----------------------------------------------------------------
    def __call__(self, *args):
        tc = _TraceCtx.active()
        if tc is None:
            out = self.forward(*args)
        else:
            # inside a compiled program every op carries the name of the
            # block that made it (``.../encoder/layer3/attn/...`` in the
            # HLO's op_name metadata); the eager path pays one
            # thread-local read
            with tc.block_scope(self._prefix):
                if id(self) in tc.remat:
                    out = _remat_forward(tc, self, args)
                else:
                    out = self.forward(*args)
        for hook in self._forward_hooks:
            hook(self, args, out)
        return out

    def register_forward_hook(self, hook):
        self._forward_hooks.append(hook)

    def forward(self, *args):
        raise NotImplementedError

    def summary(self, *inputs):
        out = self(*inputs)
        total = sum(int(_prod(p.shape)) for p in self.collect_params().values())
        print(f"{type(self).__name__}: {total} parameters")
        return out

    def __repr__(self):
        lines = [f"{type(self).__name__}("]
        for name, child in self._children.items():
            c = repr(child).replace("\n", "\n  ")
            lines.append(f"  ({name}): {c}")
        lines.append(")")
        return "\n".join(lines)


def _prod(shape):
    n = 1
    for s in shape or ():
        n *= s
    return n


def _strip(name: str, prefix: str) -> str:
    return name[len(prefix):] if name.startswith(prefix) else name


def _camel_to_snake(name: str) -> str:
    return re.sub("([a-z0-9])([A-Z])", r"\1_\2", name).lower()


# ---------------------------------------------------------------------------
# Trace context: Parameter substitution + RNG threading during hybrid trace
# ---------------------------------------------------------------------------

class _TraceCtx:
    _current = threading.local()

    def __init__(self, substitutes: Dict[int, NDArray], remat=()):
        self.substitutes = substitutes   # id(Parameter) -> wrapper NDArray
        self._prefixes: List[str] = []   # of the blocks being traced
        # ids of the blocks whose forward is rematerialised in the backward
        self.remat = frozenset(id(b) for b in remat)

    @contextlib.contextmanager
    def block_scope(self, prefix: str):
        """``jax.named_scope`` of the block's own name: its prefix with
        the enclosing block's prefix cut (``bertmodel0_encoder_`` inside
        ``bertmodel0_`` is ``encoder``)."""
        import jax
        outer = self._prefixes[-1] if self._prefixes else ""
        own = prefix[len(outer):] if prefix.startswith(outer) else prefix
        self._prefixes.append(prefix)
        try:
            with jax.named_scope(own.rstrip("_") or "block"):
                yield
        finally:
            self._prefixes.pop()

    def __enter__(self):
        self._old = getattr(_TraceCtx._current, "value", None)
        _TraceCtx._current.value = self
        return self

    def __exit__(self, *a):
        _TraceCtx._current.value = self._old

    @staticmethod
    def active() -> Optional["_TraceCtx"]:
        return getattr(_TraceCtx._current, "value", None)


def _keep_named():
    """The policy of a rematerialised block: keep the values the program
    has named as dearer to make again than to hold (one so far, the flash
    kernel's output) and nothing else.  Each value it keeps adds its bytes
    to the gauge ``trainer.remat_kept_bytes``, which the trainer sets to 0
    before it traces a step: jax asks the policy once an equation while it
    splits the block's jaxpr into what is kept and what is made again, so
    the block is not traced a second time for the count."""
    import jax
    from ..kernels.flash_attention import KEPT_OUTPUT
    from ..observability.registry import registry
    named = jax.checkpoint_policies.save_only_these_names(KEPT_OUTPUT)
    kept = registry().gauge("trainer.remat_kept_bytes")

    def policy(prim, *avals, **params):
        keep = named(prim, *avals, **params)
        if keep:
            kept.set(kept.value + sum(a.size * a.dtype.itemsize
                                      for a in avals))
        return keep
    return policy


def _remat_forward(tc: _TraceCtx, block: "Block", args):
    """``block.forward(*args)`` under ``jax.checkpoint``: the backward keeps
    the block's inputs and what ``_keep_named`` says, and computes
    everything else inside it again.  The block's parameters ride in as
    closed-over values.  What the block writes in place (a BatchNorm
    statistic, an expert layer's load) and the RNG key it draws from cross
    the boundary as explicit values, so that nothing made inside leaks out
    of the checkpointed trace."""
    import jax
    written = [p for p in block.collect_params().values()
               if p.grad_req == "null" and id(p) in tc.substitutes]
    outer = [tc.substitutes[id(p)] for p in written]
    arrays = [a for a in args if isinstance(a, NDArray)]
    keyed = bool(getattr(_grandom._tls, "stack", None))
    key = _grandom.next_key() if keyed else None
    treedef = []

    def pure(vals, aux, key):
        it = iter(vals)
        inner_args = [NDArray(next(it), ctx=a.context)
                      if isinstance(a, NDArray) else a for a in args]
        inner = [NDArray(v, ctx=o.context) for v, o in zip(aux, outer)]
        saved = {id(p): tc.substitutes[id(p)] for p in written}
        tc.substitutes.update({id(p): w for p, w in zip(written, inner)})
        try:
            with _KeyScope(key) if keyed else contextlib.nullcontext():
                out = block.forward(*inner_args)
        finally:
            tc.substitutes.update(saved)
        flat, tree = jax.tree.flatten(
            out, is_leaf=lambda o: isinstance(o, NDArray))
        treedef[:] = [tree, [o.context for o in flat]]
        return [o._read() for o in flat], \
            [w._read() if w._version > 0 else None for w in inner]

    with jax.named_scope("remat"):
        flat, new_aux = jax.checkpoint(pure, policy=_keep_named())(
            [a._read() for a in arrays], [o._read() for o in outer], key)
    for o, v in zip(outer, new_aux):
        if v is not None:
            o._set_data(v)
    tree, ctxs = treedef
    return jax.tree.unflatten(tree, [NDArray(v, ctx=c)
                                     for v, c in zip(flat, ctxs)])


def _param_data_maybe_traced(param: Parameter, ctx) -> NDArray:
    tc = _TraceCtx.active()
    if tc is not None:
        sub = tc.substitutes.get(id(param))
        if sub is not None:
            return sub
    return Parameter.data(param, ctx)


class HybridBlock(Block):
    """A Block whose forward can be lowered to one XLA computation."""

    #: max cached compiled graphs per block (distinct shape/dtype/mode
    #: signatures).  LRU-evicted beyond this — each entry pins a full XLA
    #: executable, so an unbounded dict under shape-diverse inputs (the
    #: recompile storm) was a process-lifetime memory leak.  Raise it for
    #: genuinely many-bucket workloads (BucketingModule-style).
    CACHED_GRAPH_LIMIT = 32

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._active = False
        self._cached_graph = _BoundedCache(self.CACHED_GRAPH_LIMIT)
        self._flags: Dict[str, Any] = {}
        self._recompile_warned = False

    def hybridize(self, active: bool = True, static_alloc: bool = False,
                  static_shape: bool = False, inline_limit: int = 2,
                  **kwargs) -> None:
        self._active = active
        self._flags = dict(static_alloc=static_alloc,
                           static_shape=static_shape, **kwargs)
        self._cached_graph = _BoundedCache(self.CACHED_GRAPH_LIMIT)
        super().hybridize(False, **kwargs)  # children run inside our trace

    def cast(self, dtype):
        self._cached_graph = _BoundedCache(self.CACHED_GRAPH_LIMIT)
        super().cast(dtype)

    def infer_shape(self, *args) -> None:
        """Layer-specific deferred-shape resolution; subclasses with deferred
        params override (Dense/Conv/BatchNorm/...)."""
        raise MXNetError(
            f"{type(self).__name__} has uninitialized parameters with "
            f"unknown shape and no infer_shape; give explicit in_units/"
            f"in_channels")

    # -- forward dispatch --------------------------------------------------
    def forward(self, x, *args):
        from ..symbol import Symbol
        if isinstance(x, Symbol):
            kwargs = {k: p.var() for k, p in self._reg_params.items()}
            from .. import symbol as sym_mod
            return self.hybrid_forward(sym_mod, x, *args, **kwargs)
        if not isinstance(x, NDArray):
            raise MXNetError(f"forward expects NDArray/Symbol, got {type(x)}")
        ctx = x.context
        try:
            params = {k: _param_data_maybe_traced(p, ctx)
                      for k, p in self._reg_params.items()}
        except DeferredInitializationError:
            self._deferred_infer(x, *args)
            params = {k: _param_data_maybe_traced(p, ctx)
                      for k, p in self._reg_params.items()}
        if self._active and _TraceCtx.active() is None:
            try:
                return self._call_cached(x, *args)
            except DeferredInitializationError:
                pass  # first call runs eagerly to settle child deferred shapes
        return self.hybrid_forward(nd_mod, x, *args, **params)

    def _deferred_infer(self, *args) -> None:
        self.infer_shape(*args)
        for p in self._reg_params.values():
            p._finish_deferred_init()

    def hybrid_forward(self, F, x, *args, **kwargs):
        raise NotImplementedError

    # -- the CachedOp analog ----------------------------------------------
    def _ordered_params(self, ctx) -> List[Parameter]:
        # warm all deferred inits by the eager path having run already
        return list(self.collect_params().values())

    def _call_cached(self, *inputs):
        ctx = inputs[0].context
        training = _autograd.is_training()
        sig = (tuple((tuple(a.shape), str(a.dtype)) for a in inputs),
               training, ctx)
        entry = self._cached_graph.get(sig)
        if entry is None:
            entry = self._build_cached(inputs, training, ctx)
            evicting = (self._cached_graph.cache_info()["currsize"]
                        >= self.CACHED_GRAPH_LIMIT)
            self._cached_graph.put(sig, entry)
            if evicting and not self._recompile_warned:
                self._recompile_warned = True
                warnings.warn(
                    f"HybridBlock {self.name!r} compiled more than "
                    f"{self.CACHED_GRAPH_LIMIT} distinct input "
                    "signatures; oldest executables are now LRU-"
                    "evicted (recompile storm — consider bucketing "
                    "input shapes or raising CACHED_GRAPH_LIMIT)",
                    RuntimeWarning, stacklevel=3)
        jitted, jitted_vjp, params, meta = entry
        n_outs_cell, write_idx_cell, infer_cell = meta

        pvals = [p.data(ctx)._read() for p in params]
        invals = [a._read() for a in inputs]
        key = _grandom.next_key()

        recording = _autograd.is_recording() and (
            any(p.data(ctx)._ag is not None for p in params) or
            any(getattr(a, "_ag", None) is not None for a in inputs))
        if recording:
            flat, vjp_fn = jitted_vjp(key, *pvals, *invals)
        elif infer_cell[0] is not None:
            # persistent-cache path: the AOT executable deserialized (or
            # compiled once) at build time — same computation, no jit
            # re-trace on a fresh process.  AOT calls are
            # signature-strict; an aval surprise (weak-type drift)
            # degrades permanently to the plain jit path rather than
            # failing the forward.
            try:
                flat = infer_cell[0](key, *pvals, *invals)
            except TypeError:
                infer_cell[0] = None
                flat = jitted(key, *pvals, *invals)
        else:
            flat = jitted(key, *pvals, *invals)

        n_outs = n_outs_cell[0]
        write_idx = write_idx_cell[0]
        outs = [NDArray(v, ctx=ctx) for v in flat[:n_outs]]

        # write back captured aux mutations (running stats)
        if write_idx:
            with _autograd.pause():
                for pos, pi in enumerate(write_idx):
                    params[pi].data(ctx)._set_data(flat[n_outs + pos])

        if recording:
            parents = [None]  # rng key input
            for p in params:
                parents.append(p.data(ctx)._ag)
            for a in inputs:
                parents.append(getattr(a, "_ag", None))
            node = _autograd.TapeNode(
                f"CachedOp[{self.name}]", vjp_fn, parents,
                [(o.shape, o.dtype) for o in outs] +
                [(flat[n_outs + i].shape, flat[n_outs + i].dtype)
                 for i in range(len(write_idx))],
                True)
            # tape sees the flat tuple; only real outs get user cotangents
            for i, o in enumerate(outs):
                o._ag = _autograd.AGInfo(node=node, index=i)
        return outs[0] if n_outs == 1 else tuple(outs)

    def _build_cached(self, inputs, training, ctx):
        import jax
        # ensure deferred params are resolved by one eager run if needed
        params = self._ordered_params(ctx)
        n_outs_cell = [None]
        write_idx_cell = [None]
        block = self
        n_params = len(params)

        def pure_fn(key, *vals):
            pvals = vals[:n_params]
            invals = vals[n_params:]
            wrappers = [NDArray(v, ctx=ctx) for v in pvals]
            win = [NDArray(v, ctx=ctx) for v in invals]
            subs = {id(p): w for p, w in zip(params, wrappers)}
            with _TraceCtx(subs) as tc, \
                    _autograd._RecordingScope(False, training), \
                    _KeyScope(key), tc.block_scope(block._prefix):
                out = block.hybrid_forward_entry(*win)
            outs = list(out) if isinstance(out, (list, tuple)) else [out]
            out_vals = [o._read() for o in outs]
            writes = [(i, w._read()) for i, w in enumerate(wrappers)
                      if w._version > 0]
            n_outs_cell[0] = len(out_vals)
            write_idx_cell[0] = [i for i, _ in writes]
            return tuple(out_vals) + tuple(v for _, v in writes)

        jitted = jax.jit(pure_fn)
        # cached vjp wrapper for the training path: a bare jax.vjp would
        # re-linearize the whole graph in Python EVERY step.  vjp of the
        # JITTED fn (not raw pure_fn) keeps the linearized jaxpr a single
        # pjit eqn, so the returned vjp_fn's transpose also runs as ONE
        # compiled call rather than eager per-primitive dispatch.
        jitted_vjp = jax.jit(lambda *a: jax.vjp(jitted, *a))
        # persistent compile cache (MXTPU_COMPILE_CACHE_DIR): AOT-lower
        # the inference executable now and resolve it through the disk
        # tier, keyed on the lowered StableHLO + backend fingerprint —
        # a fresh process deserializes instead of compiling (the
        # ModelServer cold-start / auto-resume fast path).  Inference
        # only: the training vjp closure's pytree is not a stable
        # serialization target (jax's own persistent cache, pointed at
        # the same dir, covers that jit path instead).
        infer_cell = [None]
        if not training:
            try:
                from ..tuning import compile_cache as _cc
                if _cc.active() is not None:
                    # lower against the CONCRETE values (exact avals,
                    # weak types included — an AOT executable is
                    # signature-strict); the sample key has the same
                    # aval as every _grandom.next_key() draw
                    sample_key = jax.random.PRNGKey(0)
                    vals = [p.data(ctx)._read() for p in params] + \
                           [a._read() for a in inputs]
                    lowered = jitted.lower(sample_key, *vals)
                    infer_cell[0] = _cc.aot_compile(
                        lowered, "graph", ctx.device)
            except Exception:   # noqa: BLE001 — AOT/serialization drift
                infer_cell[0] = None   # degrades to the plain jit path
        return jitted, jitted_vjp, params, (n_outs_cell, write_idx_cell,
                                            infer_cell)

    def hybrid_forward_entry(self, *inputs):
        """Entry used during trace: routes through forward so nested blocks
        participate (their params substitute via the trace context)."""
        ctx = inputs[0].context
        params = {k: _param_data_maybe_traced(p, ctx)
                  for k, p in self._reg_params.items()}
        return self.hybrid_forward(nd_mod, *inputs, **params)

    def cached_graph(self, *inputs, entry: str = "forward"
                     ) -> "CachedGraph":
        """Freeze ONE compiled inference signature into a
        :class:`CachedGraph` — the direct cached-graph entry the serving
        subsystem dispatches through (no autograd bookkeeping, no
        per-call parameter re-read, no aux write-back).

        ``inputs`` is an example batch (NDArrays, or anything
        ``nd.array`` accepts) whose shapes/dtypes define the signature.
        The same per-signature cache ``hybridize()`` fills is reused, so
        a block that already served this signature through ``block(x)``
        hands back the *identical* executable; the call compiles (and
        warms) the graph before returning, so the first real request
        never pays the compile.

        ``entry`` selects the traced method: ``"forward"`` (the default,
        ``hybrid_forward``) or a generation variant the block implements
        — ``"prefill"`` traces ``hybrid_prefill`` (prompt pass: scatters
        K/V into the block pool, returns last-position logits) and
        ``"decode"`` traces ``hybrid_decode`` (one token per running
        slot; the carried state is the KV pool, passed in and returned).
        Non-forward entries compile once per input signature — for
        decode that means once per (slot-count, max-blocks) pair — and
        resolve through the persistent compile cache exactly like the
        forward graph, so a warm process restart skips the XLA compile."""
        if entry != "forward":
            return self._cached_entry_graph(entry, inputs)
        inputs = self.example_inputs(inputs)
        ctx = inputs[0].context
        with _autograd.pause():
            # one eager pass settles every deferred shape (children
            # included) exactly as the hybridize path's first call does
            try:
                self(*inputs)
            except DeferredInitializationError:
                self._deferred_infer(*inputs)
                self(*inputs)
            sig = (tuple((tuple(a.shape), str(a.dtype)) for a in inputs),
                   False, ctx)
            entry = self._cached_graph.get(sig)
            if entry is None:
                entry = self._build_cached(inputs, False, ctx)
                self._cached_graph.put(sig, entry)
            jitted, _jitted_vjp, params, meta = entry
            n_outs_cell, _write_idx_cell, infer_cell = meta
            pvals = [p.data(ctx)._read() for p in params]
            # inference mode disables dropout, so the RNG input is dead:
            # pin one key now and __call__ stays allocation-free and
            # deterministic
            key = _grandom.next_key()
            import jax
            # serve through the persistent-cache AOT executable when one
            # resolved at build time — on a warm restart that skipped
            # the XLA compile entirely (the ModelServer cold-start path)
            entry_fn = infer_cell[0] if infer_cell[0] is not None \
                else jitted
            try:
                flat = entry_fn(key, *pvals,
                                *[a._read() for a in inputs])
            except TypeError:
                if entry_fn is jitted:
                    raise
                infer_cell[0] = None       # aval drift: jit path forever
                entry_fn = jitted
                flat = entry_fn(key, *pvals,
                                *[a._read() for a in inputs])
            jax.block_until_ready(flat)        # compile + warm, here
        return CachedGraph(entry_fn, pvals, key, n_outs_cell[0], ctx,
                           self.name)

    def _cached_entry_graph(self, entry: str, inputs) -> "CachedGraph":
        """Non-forward cached-graph entry (``hybrid_prefill`` /
        ``hybrid_decode``): same trace-compile-warm flow as the forward
        path, keyed separately per entry name so one block can hold its
        prompt buckets and its decode-step signatures side by side."""
        import jax
        method_name = "hybrid_" + entry
        if not callable(getattr(self, method_name, None)):
            raise AttributeError(
                f"{type(self).__name__} has no {method_name}(); a "
                f"generation-servable block implements hybrid_prefill "
                f"and hybrid_decode (see serving.ModelServer docs)")
        inputs = self.example_inputs(inputs)
        ctx = inputs[0].context
        with _autograd.pause():
            sig = (entry,
                   tuple((tuple(a.shape), str(a.dtype)) for a in inputs),
                   False, ctx)
            cached = self._cached_graph.get(sig)
            if cached is None:
                cached = self._build_entry_cached(method_name, inputs, ctx)
                self._cached_graph.put(sig, cached)
            jitted, params, n_outs_cell, infer_cell = cached
            pvals = [p.data(ctx)._read() for p in params]
            # generation graphs are inference-only: dropout is off, the
            # RNG input is dead — pin one key (same discipline as the
            # forward path) so dispatch stays allocation-free
            key = _grandom.next_key()
            entry_fn = infer_cell[0] if infer_cell[0] is not None \
                else jitted
            try:
                flat = entry_fn(key, *pvals,
                                *[a._read() for a in inputs])
            except TypeError:
                if entry_fn is jitted:
                    raise
                infer_cell[0] = None   # aval drift: jit path forever
                entry_fn = jitted
                flat = entry_fn(key, *pvals,
                                *[a._read() for a in inputs])
            jax.block_until_ready(flat)    # compile + warm, here
        return CachedGraph(entry_fn, pvals, key, n_outs_cell[0], ctx,
                           f"{self.name}:{entry}")

    def _build_entry_cached(self, method_name, inputs, ctx):
        """Trace one generation entry into a jitted fn (+ AOT cell).
        Mirrors ``_build_cached`` minus everything inference never
        needs: no vjp, no aux write-back (generation entries thread
        their state — the KV pool — explicitly as an output)."""
        import jax
        params = self._ordered_params(ctx)
        n_outs_cell = [None]
        block = self
        n_params = len(params)
        method = getattr(block, method_name)

        def pure_fn(key, *vals):
            pvals = vals[:n_params]
            invals = vals[n_params:]
            wrappers = [NDArray(v, ctx=ctx) for v in pvals]
            win = [NDArray(v, ctx=ctx) for v in invals]
            subs = {id(p): w for p, w in zip(params, wrappers)}
            with _TraceCtx(subs) as tc, \
                    _autograd._RecordingScope(False, False), \
                    _KeyScope(key), tc.block_scope(block._prefix):
                pkw = {k: _param_data_maybe_traced(p, ctx)
                       for k, p in block._reg_params.items()}
                out = method(nd_mod, *win, **pkw)
            outs = list(out) if isinstance(out, (list, tuple)) else [out]
            out_vals = [o._read() for o in outs]
            n_outs_cell[0] = len(out_vals)
            return tuple(out_vals)

        jitted = jax.jit(pure_fn)
        # persistent compile cache: same disk tier as the forward graph
        # (key = lowered StableHLO + backend fingerprint), so a server
        # restart populates every decode-step signature with
        # deserialization instead of XLA compiles
        infer_cell = [None]
        try:
            from ..tuning import compile_cache as _cc
            if _cc.active() is not None:
                sample_key = jax.random.PRNGKey(0)
                vals = [p.data(ctx)._read() for p in params] + \
                       [a._read() for a in inputs]
                lowered = jitted.lower(sample_key, *vals)
                infer_cell[0] = _cc.aot_compile(
                    lowered, "graph", ctx.device)
        except Exception:   # noqa: BLE001 — AOT/serialization drift
            infer_cell[0] = None   # degrades to the plain jit path
        return jitted, params, n_outs_cell, infer_cell

    def export(self, path: str, epoch: int = 0) -> Tuple[str, str]:
        """Reference parity: save -symbol.json + -%04d.params for the
        SymbolBlock / predict path."""
        from ..symbol import Symbol
        from .. import symbol as sym_mod
        data = Symbol.var("data")
        out = self(data)
        sym_file = f"{path}-symbol.json"
        out.save(sym_file)
        params_file = f"{path}-{epoch:04d}.params"
        from ..ndarray import utils as nd_utils
        arrs = {}
        for name, p in self.collect_params().items():
            arrs[f"arg:{name}"] = p.data()
        nd_utils.save(params_file, arrs)
        return sym_file, params_file


class CachedGraph:
    """Inference-only handle over one compiled cached-graph signature —
    the CachedOp artifact a model server wants (PAPER.md L6a), with
    everything the serving hot path must not pay stripped off:

    - **no autograd bookkeeping** — no vjp build, no TapeNode, no
      parent scan; inference never backprops;
    - **no per-call parameter re-read** — parameter device values were
      snapshotted at freeze time (weights are immutable while serving;
      re-freeze after loading new ones);
    - **no aux write-back** — the graph was traced in inference mode
      (``training=False``) and any residual mutation outputs are
      dropped, never written back: a server must not corrupt running
      stats;
    - **pinned RNG key** — dropout is off in inference mode, so the key
      input is dead; pinning it keeps calls allocation-free and
      bit-deterministic.

    ``raw(*values)`` is the lean entry (numpy/jax values in, tuple of
    jax arrays out — what ``serving.ModelServer`` dispatches per
    batch); ``__call__`` wraps NDArrays for parity with ``block(x)``.
    """

    __slots__ = ("_jitted", "_pvals", "_key", "_n_outs", "_ctx", "name")

    def __init__(self, jitted, pvals, key, n_outs, ctx, name):
        self._jitted = jitted
        self._pvals = tuple(pvals)
        self._key = key
        self._n_outs = n_outs
        self._ctx = ctx
        self.name = name

    @property
    def n_outputs(self) -> int:
        return self._n_outs

    def raw(self, *values):
        """One compiled call: raw array values in (numpy or jax), tuple
        of raw jax arrays out.  No NDArray wrappers, no tape, no sync."""
        flat = self._jitted(self._key, *self._pvals, *values)
        return flat[:self._n_outs]

    def __call__(self, *inputs):
        vals = [a._read() if isinstance(a, NDArray) else a
                for a in inputs]
        outs = [NDArray(v, ctx=self._ctx) for v in self.raw(*vals)]
        return outs[0] if len(outs) == 1 else tuple(outs)


class _KeyScope:
    """Push a traced RNG key for the duration of a hybrid trace so random
    ops draw from a runtime input, not a baked constant."""

    def __init__(self, key):
        self._key = key

    def __enter__(self):
        _grandom.push_key(self._key)
        return self

    def __exit__(self, *a):
        _grandom.pop_key()


class SymbolBlock(Block):
    """Construct a Block from a Symbol graph + params (reference:
    gluon.SymbolBlock.imports for serving exported models)."""

    def __init__(self, outputs, inputs, params=None):
        super().__init__(prefix="symbolblock_", params=None)
        self._outputs = outputs
        self._inputs = inputs if isinstance(inputs, (list, tuple)) else [inputs]
        self._arg_params = params or {}

    @staticmethod
    def imports(symbol_file: str, input_names, param_file: Optional[str] = None,
                ctx=None):
        from ..symbol import load as sym_load
        sym = sym_load(symbol_file)
        params = {}
        if param_file:
            from ..ndarray import utils as nd_utils
            loaded = nd_utils.load(param_file)
            for k, v in loaded.items():
                name = k.split(":", 1)[1] if ":" in k else k
                if ctx is not None:
                    v = v.as_in_context(ctx)
                params[name] = v
        if isinstance(input_names, str):
            input_names = [input_names]
        from ..symbol import Symbol
        inputs = [Symbol.var(n) for n in input_names]
        return SymbolBlock(sym, inputs, params)

    def forward(self, *args):
        feed = {s.name: a for s, a in zip(self._inputs, args)}
        feed.update(self._arg_params)
        return self._outputs.eval_dict(feed)


def name_scope():
    raise MXNetError("use block.name_scope() on a Block instance")
