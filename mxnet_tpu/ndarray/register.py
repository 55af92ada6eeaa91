"""Op registry + imperative dispatch.

Reference role: the NNVM op registry (`NNVM_REGISTER_OP` with FCompute/
FGradient/FInferShape attrs — SURVEY.md §2.1) plus the generated Python
wrappers (`python/mxnet/ndarray/register.py`) and the imperative invoke path
(`MXImperativeInvokeEx → Imperative::Invoke → Engine::PushAsync`, §3.1).

TPU-native design: one declarative registry drives everything.  Each op is a
*maker*: ``maker(**params) -> fn(*jax_arrays) -> jax_array(s)``.  Dispatch
jit-compiles the maker result per parameter signature (XLA compile cache keyed
by shape/dtype replaces FInferShape/FInferType), executes asynchronously
(PJRT replaces the threaded engine), and — when autograd is recording —
captures ``jax.vjp`` residuals on the tape (replaces FGradient).  The same
registry backs the Symbol graph composition (mxnet_tpu/symbol) so `mx.nd.*`
and `mx.sym.*` stay in lockstep, mirroring how both reference frontends were
generated from the single C-side registry.
"""
from __future__ import annotations

import collections as _collections
import functools
import threading
import weakref as _weakref
from time import perf_counter as _perf_counter
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as _np

from ..base import MXNetError, hot_path
from ..context import current_context
from ..engine import PendingValue, engine, _install_flush_hook
from ..observability.trace import span as _span
from .. import autograd as _autograd

__all__ = ["Operator", "register_op", "get_op", "list_ops", "invoke",
           "invoke_by_name", "invoke_binary", "make_frontend",
           "flush_segment", "segment_cache_info", "segment_cache_clear"]

_registry: Dict[str, "Operator"] = {}


class _BoundedCache:
    """Tiny LRU with the ``functools.lru_cache`` info surface.

    Replaces the former ``lru_cache(maxsize=None)`` *methods* on Operator:
    those keyed on ``self``, pinning every Operator — and every compiled
    executable it ever produced — for the life of the process.  Eviction
    here drops the last reference to the jitted callable, which releases
    its jit/XLA cache entries with it."""

    __slots__ = ("maxsize", "_d", "hits", "misses")

    def __init__(self, maxsize: int):
        self.maxsize = maxsize
        self._d = _collections.OrderedDict()
        self.hits = 0
        self.misses = 0

    def get(self, key, default=None):
        d = self._d
        try:
            val = d[key]
            d.move_to_end(key)
        except KeyError:
            # miss — or a concurrent eviction raced the move_to_end
            # (DataLoader worker threads dispatch ops too; individual
            # OrderedDict ops are GIL-atomic, sequences are not)
            self.misses += 1
            return default
        self.hits += 1
        return val

    def put(self, key, val) -> None:
        d = self._d
        d[key] = val
        try:
            d.move_to_end(key)
            if len(d) > self.maxsize:
                d.popitem(last=False)
        except KeyError:
            pass                      # concurrent eviction: already gone

    def cache_info(self) -> dict:
        return {"hits": self.hits, "misses": self.misses,
                "maxsize": self.maxsize, "currsize": len(self._d)}

    def cache_clear(self) -> None:
        self._d.clear()
        self.hits = 0
        self.misses = 0


def _canon(v: Any) -> Any:
    """Make a param value hashable/canonical for the compile cache."""
    if isinstance(v, (list, tuple)):
        return tuple(_canon(x) for x in v)
    if isinstance(v, _np.dtype):
        return str(v)
    if isinstance(v, _np.generic):
        # np.generic scalar: already host memory, a pure-host unbox
        # mxlint: disable=hidden-host-sync — np scalar, no device
        return v.item()
    return v


#: per-Operator bound on compiled-fn caches (distinct param signatures per
#: op are few in practice; shape/dtype specialization lives in jax's own
#: per-callable jit cache underneath each entry)
OP_FN_CACHE_SIZE = 128


class Operator:
    """A registered operator (analog of ``nnvm::Op``)."""

    __slots__ = ("name", "maker", "aliases", "differentiable", "use_jit",
                 "doc", "ref", "vjp_maker", "needs_rng", "_fn_cache",
                 "_vjp_cache")

    def __init__(self, name: str, maker: Callable, aliases: Sequence[str] = (),
                 differentiable: bool = True, use_jit: bool = True,
                 doc: str = "", ref: str = "", vjp_maker: Callable = None,
                 needs_rng: bool = False):
        self.name = name
        self.maker = maker
        self.aliases = tuple(aliases)
        self.vjp_maker = vjp_maker
        self.differentiable = differentiable
        self.use_jit = use_jit
        self.doc = doc
        self.ref = ref              # reference file pointer for parity audits
        # sampling ops take a PRNG key as their LAST tensor input (the
        # jax key-threading discipline replacing the reference's per-device
        # resource RNG states, src/resource.cc): eager frontends pass it
        # explicitly; the symbol runner splits one per-forward base key
        self.needs_rng = needs_rng
        self._fn_cache = _BoundedCache(OP_FN_CACHE_SIZE)
        self._vjp_cache = _BoundedCache(OP_FN_CACHE_SIZE)

    def _fn_for_key(self, kwkey: Tuple) -> Callable:
        fn = self._fn_cache.get(kwkey)
        if fn is None:
            import jax
            fn = self.maker(**dict(kwkey))
            if self.use_jit:
                fn = jax.jit(fn)
            self._fn_cache.put(kwkey, fn)
        return fn

    def get_fn(self, kwargs: Dict[str, Any]) -> Callable:
        kwkey = tuple(sorted((k, _canon(v)) for k, v in kwargs.items()))
        try:
            return self._fn_for_key(kwkey)
        except TypeError:
            # unhashable param slipped through; build uncached
            fn = self.maker(**kwargs)
            import jax
            return jax.jit(fn) if self.use_jit else fn

    def _vjp_for_key(self, kwkey: Tuple) -> Callable:
        # the imperative-training hot path (reference stack §3.1): a bare
        # jax.vjp RE-TRACES the op on every invoke; jitting the
        # (primals -> (outs, vjp_fn)) wrapper caches the trace per shape
        # signature (vjp_fn is a jax Partial — a pytree, so jit can
        # return it).  ~3.5x per-op dispatch win measured.
        wrapper = self._vjp_cache.get(kwkey)
        if wrapper is None:
            import jax
            fn = self.maker(**dict(kwkey))
            wrapper = lambda *p: jax.vjp(fn, *p)   # noqa: E731
            if self.use_jit:
                wrapper = jax.jit(wrapper)
            self._vjp_cache.put(kwkey, wrapper)
        return wrapper

    def cache_info(self) -> dict:
        return {"fn": self._fn_cache.cache_info(),
                "vjp": self._vjp_cache.cache_info()}

    def cache_clear(self) -> None:
        self._fn_cache.cache_clear()
        self._vjp_cache.cache_clear()

    def get_vjp_fn(self, kwargs: Dict[str, Any]) -> Tuple[Callable, bool]:
        """Returns (wrapper, runner_safe).  runner_safe is True ONLY for
        the jitted cached wrapper: its returned vjp closures have a
        STABLE pytree treedef across calls, so backward()'s jitted
        runner caches one compiled backward per signature.  The other
        paths produce fresh-treedef Partials or plain closures — running
        those through the runner would recompile every backward."""
        if self.vjp_maker is not None:
            # hand-built (primals -> (outs, vjp_fn)) wrapper — the escape
            # hatch for ops whose output shape depends on input VALUES
            # (jax.vjp cannot trace those); they run eagerly by
            # construction, so no jit cache applies
            return self.vjp_maker(**kwargs), False
        kwkey = tuple(sorted((k, _canon(v)) for k, v in kwargs.items()))
        try:
            return self._vjp_for_key(kwkey), self.use_jit
        except TypeError:
            # unhashable kwargs: uncached — a fresh jax.jit here would be
            # a guaranteed cache miss (keyed on callable identity), i.e.
            # a full XLA compile EVERY invoke; eager vjp through the
            # per-primitive caches is the cheaper fallback
            import jax
            fn = self.maker(**kwargs)
            return (lambda *p: jax.vjp(fn, *p)), False


def register_op(name: str, maker: Optional[Callable] = None, *,
                aliases: Sequence[str] = (), differentiable: bool = True,
                use_jit: bool = True, doc: str = "", ref: str = "",
                vjp_maker: Optional[Callable] = None,
                needs_rng: bool = False):
    """Register an operator.  Usable directly or as a decorator on the maker."""
    def do(mk):
        op = Operator(name, mk, aliases=aliases, differentiable=differentiable,
                      use_jit=use_jit, doc=doc or (mk.__doc__ or ""), ref=ref,
                      vjp_maker=vjp_maker, needs_rng=needs_rng)
        for n in (name,) + tuple(aliases):
            # silent shadowing caused a real regression (round-4 review):
            # a later registration replaced an op under the same name with
            # different semantics.  Double registration is always a bug.
            if n in _registry:
                raise MXNetError(
                    f"operator name {n!r} is already registered "
                    f"(by {_registry[n].name!r})")
            _registry[n] = op
        return mk
    if maker is not None:
        do(maker)
        return maker
    return do


def simple_op(name: str, fn: Callable, **kw):
    """Register an op whose fn has no parameters (pure elementwise etc.)."""
    register_op(name, lambda: fn, **kw)


def add_alias(existing: str, *aliases: str) -> None:
    """Point additional names at an already-registered op (reference: the
    underscore canonical vs public-name dualities, e.g. _linalg_gemm /
    linalg_gemm).  Subject to the same duplicate check as register_op."""
    op = get_op(existing)
    for a in aliases:
        if a in _registry:
            raise MXNetError(
                f"operator name {a!r} is already registered "
                f"(by {_registry[a].name!r})")
        _registry[a] = op


def get_op(name: str) -> Operator:
    op = _registry.get(name)
    if op is None:
        raise MXNetError(f"operator {name!r} is not registered")
    return op


def list_ops() -> List[str]:
    return sorted(set(op.name for op in _registry.values()))


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------

def _as_nd(x, ctx):
    if isinstance(x, _ND_CLS or _nd_cls()):
        return x
    from .ndarray import array
    return array(x, ctx=ctx)


# pre-dispatch rewrite hook (installed by contrib.amp to insert casts):
# fn(op_name, inputs) -> inputs
_invoke_hook = None


def set_invoke_hook(fn) -> None:
    """Install (or clear, with None) the global pre-dispatch input-rewrite
    hook — the seam contrib.amp uses for automatic mixed precision, the
    analog of the reference's amp.init() op-namespace monkey-patch."""
    global _invoke_hook
    _invoke_hook = fn


_SUBGRAPH_OPS = ("_foreach", "_while_loop", "_cond")


def node_takes_key(op_name: str, attrs: Dict[str, Any],
                   training: bool) -> bool:
    """THE single active-sampling predicate: whether one op application
    (given its attrs and the executor's train/eval mode) consumes a PRNG
    key.  Every key decision — eager invoke, the symbol runner's per-node
    split, graph-level needs_rng — routes through here, so key-feeding
    and key-consumption cannot drift apart.
      - Dropout gated to identity at inference consumes nothing.
      - Control-flow ops consume only if a SUBGRAPH samples (recursively)
        — an rng-free foreach must not advance the stream."""
    op = _registry.get(op_name)
    if op is None or not op.needs_rng:
        return False
    if op_name == "Dropout" and not training and \
            attrs.get("mode", "training") != "always":
        return False
    if op_name in _SUBGRAPH_OPS:
        return any(graph_needs_rng(v.sym, training)
                   for v in attrs.values() if hasattr(v, "sym"))
    return True


def graph_needs_rng(sym, training: bool) -> bool:
    """Any active sampling node in the graph (duck-typed Symbol: needs
    only ``_topo()``)?  The cheap form of ``sym.compile(training)
    .needs_rng`` — no runner closures are built just to read the bool."""
    return any(not n.is_var and node_takes_key(n.op, n.attrs, training)
               for n in sym._topo())


def op_takes_key(op: Operator, kwargs: Dict[str, Any]) -> bool:
    """``node_takes_key`` for an imperative invocation: kwargs play the
    role of node attrs (``_training`` rides in them on the symbol path;
    eager control flow runs in eval mode unless told otherwise)."""
    return node_takes_key(op.name, kwargs,
                          bool(kwargs.get("_training", False)))


# ---------------------------------------------------------------------------
# bulked dispatch: lazy op-fusion segments (reference: the engine's
# MXNET_EXEC_BULK_EXEC_* bulking of consecutive pushes — SURVEY.md §2.1)
# ---------------------------------------------------------------------------

_NOT_FUSABLE = object()   # sentinel: op must flush + dispatch eagerly
_EXT, _NODE = 0, 1        # argument-ref kinds inside a segment

_tls = threading.local()

#: fused executables, keyed on (taped?, op-sequence incl. param signatures
#: and wiring, external input shapes/dtypes) — the steady-state training
#: loop hits this every segment
_segment_cache = _BoundedCache(512)


def segment_cache_info() -> dict:
    return _segment_cache.cache_info()


def segment_cache_clear() -> None:
    _segment_cache.cache_clear()


# persistent compile-cache seam (installed by tuning.compile_cache when
# MXTPU_COMPILE_CACHE_DIR is set): (lookup, store) callables consulted
# ONLY on an in-memory segment-cache miss — the cold compile path.  A
# hook indirection, not an import: the frontend layer stays free of a
# tuning dependency, and the calls resolve to no edge in mxlint's call
# graph, keeping the disk tier provably off the dispatch hot path.
_persist_hooks = None


def _install_persist_hooks(lookup, store) -> None:
    global _persist_hooks
    _persist_hooks = (lookup, store)


def _segment_persist_key(device_id, needed, nodes, ext_vals) -> str:
    """Canonical string form of the segment signature for the disk tier:
    the in-memory ``_segment_cache`` key (a serialized executable names
    its device by id, so the id stays in; the cache's backend
    fingerprint covers platform/device kind)."""
    return repr((device_id, needed, nodes,
                 tuple((tuple(v.shape), str(_np.dtype(v.dtype)))
                       for v in ext_vals)))


def clear_op_caches() -> None:
    """Drop every Operator's compiled fn/vjp caches, plus the fused-segment
    executables (which close over per-op fns) and the abstract-eval cache.
    The big hammer for tests and for env-var toggles (e.g.
    MXNET_PALLAS_INTERPRET) that change what a maker compiles to."""
    for op in set(_registry.values()):
        op.cache_clear()
    _segment_cache.cache_clear()
    _infer_out_avals.cache_clear()


# lazily-bound hot-path globals: `from .ndarray import NDArray` / `import
# jax` inside a per-op function costs a sys.modules round-trip per call
# (visible in dispatch profiles as importlib frames)
_ND_CLS = None
_TRACER_CLS = None


def _nd_cls():
    global _ND_CLS
    if _ND_CLS is None:
        from .ndarray import NDArray
        _ND_CLS = NDArray
    return _ND_CLS


def _tracer_type():
    global _TRACER_CLS
    if _TRACER_CLS is None:
        import jax
        _TRACER_CLS = jax.core.Tracer
    return _TRACER_CLS


_SDS_CLS = None


def _sds_cls():
    """jax's SingleDeviceSharding — the fast 'not a multi-chip global
    array' check (its device_set property builds a frozenset per call,
    too slow for the defer path)."""
    global _SDS_CLS
    if _SDS_CLS is None:
        from jax.sharding import SingleDeviceSharding
        _SDS_CLS = SingleDeviceSharding
    return _SDS_CLS


def _n_elems(shape: Tuple) -> int:
    n = 1
    for s in shape:
        n *= s
    return n


#: ops whose arithmetic includes a reduction/contraction even when the
#: output is not smaller than the inputs (dot grows, softmax preserves
#: shape): fusing their PENDING output into a downstream consumer lets
#: XLA re-fuse the internal accumulation (measured: ~1-ulp drift on CPU),
#: so consuming one while pending is a flush point — the same rule the
#: element-shrink heuristic applies to plain reductions
_FUSION_BARRIER_OPS = frozenset({
    "dot", "batch_dot", "FullyConnected", "Convolution", "Deconvolution",
    "Pooling", "softmax", "log_softmax", "softmin", "SoftmaxActivation",
    "SoftmaxOutput", "Softmax", "LayerNorm", "BatchNorm", "InstanceNorm",
    "GroupNorm", "L2Normalization", "LRN", "RNN", "Correlation", "moments",
    "topk", "sort", "argsort", "einsum", "khatri_rao", "Embedding",
})


def _is_barrier_op(name: str) -> bool:
    return name in _FUSION_BARRIER_OPS or name.startswith("linalg_") \
        or name.startswith("_linalg")


@functools.lru_cache(maxsize=4096)
def _infer_out_avals(op_name: str, kwkey: Tuple, in_avals: Tuple):
    """Predicted (shape, dtype) per output WITHOUT executing — the deferred
    path's replacement for the reference's FInferShape/FInferType.  One
    abstract trace per (op, params, input signature), then a dict hit."""
    import jax
    fn = _registry[op_name]._fn_for_key(kwkey)
    structs = [jax.ShapeDtypeStruct(s, d) for s, d in in_avals]
    out = jax.eval_shape(fn, *structs)
    multi = isinstance(out, (tuple, list))
    outs = tuple(out) if multi else (out,)
    return tuple((tuple(o.shape), _np.dtype(o.dtype)) for o in outs), multi


def _build_fused(nodes: Tuple, needed: Optional[Tuple]) -> Callable:
    """The segment as one Python-composable function.  Each node calls its
    op's cached (jitted) fn — under an outer trace the inner jaxprs inline,
    so XLA sees the whole chain as a single computation.

    ``needed`` (untaped segments) lists the flat output slots whose
    NDArrays are still live at flush time: only those are returned, so
    XLA dead-code-eliminates every dropped intermediate.  Taped segments
    return everything — the tape node's cotangent slots index the full
    flat tuple."""
    resolved = [(_registry[name]._fn_for_key(kwkey), refs, multi)
                for name, kwkey, refs, multi in nodes]

    def fused(*ext):
        flat = []
        for fn, refs, multi in resolved:
            args = [ext[i] if kind == _EXT else flat[i] for kind, i in refs]
            out = fn(*args)
            if multi:
                flat.extend(out)
            else:
                flat.append(out)
        if needed is not None:
            return tuple(flat[i] for i in needed)
        return tuple(flat)

    return fused


def _compile_segment(nodes: Tuple, taped: bool,
                     needed: Optional[Tuple]) -> Callable:
    """'aggressive' codegen: one jit over the whole segment — XLA fuses
    freely (FMA contraction ⇒ up to ~1-ulp drift vs unbulked)."""
    import jax
    fused = _build_fused(nodes, needed)
    if taped:
        # one jax.vjp over the fused function — the whole segment becomes
        # ONE tape node; cached per segment signature, so the returned
        # vjp closures have a stable treedef (runner_safe)
        return jax.jit(lambda *p: jax.vjp(fused, *p))
    return jax.jit(fused)


#: XLA passes switched off for an exact-mode segment, so that every node
#: keeps the kernels its per-op jit compiles
_EXACT_COMPILER_OPTIONS = {
    "xla_disable_hlo_passes": "fusion,cpu-instruction-fusion"}


def _compile_segment_exact(nodes: Tuple, needed: Optional[Tuple],
                           ext_vals: Sequence, device,
                           persist_key: Optional[str] = None) -> Callable:
    """'exact' codegen (the default): ONE executable per segment but
    with XLA's fusion passes disabled, so every node keeps the same
    kernels the unbulked per-op path compiles — results are BITWISE
    identical to unbulked (no cross-op FMA contraction, no refused
    reductions) while the host still pays a single dispatch for the whole
    segment (the reference's bulking economics exactly: batch the pushes,
    not the arithmetic).

    With ``persist_key`` set (the persistent compile cache is wired), a
    previously-compiled executable for the same signature+backend is
    deserialized from disk instead of compiled — the restart-without-
    recompile path; a real compile is serialized back for the next
    process.  A compile error is an error: there is no per-op fallback."""
    import jax
    hooks = _persist_hooks if persist_key is not None else None
    exe = hooks[0](persist_key, device) if hooks is not None else None
    if exe is None:
        # keep_unused: liveness-DCE can leave some external inputs
        # unused, and the executable is fed ALL of them.  default_device
        # pins a segment with no committed input (creation ops only) to
        # the context's device rather than the process default.
        with jax.default_device(device):
            exe = jax.jit(_build_fused(nodes, needed), keep_unused=True) \
                .lower(*ext_vals) \
                .compile(compiler_options=_EXACT_COMPILER_OPTIONS)
        if hooks is not None:
            hooks[1](persist_key, exe)
    device_put = jax.device_put

    def run(*vals):
        try:
            return exe(*vals)
        except ValueError:
            # a buffer committed to another device (NDArray ctx tags can
            # diverge from actual placement after cross-device
            # _set_data): align and retry once; a real failure re-raises
            return exe(*[device_put(v, device) for v in vals])

    return run


class _BulkSegment:
    """A lazy run of fusable imperative ops (the reference's bulked engine
    push).  External input VALUES are captured at defer time, so an
    in-place write after the defer cannot be observed — exactly the read
    ordering the unbulked path has.  ``flush`` executes the whole DAG as
    one cached jitted call and fills every pending output in place."""

    __slots__ = ("ctx", "recording", "fuse", "cap", "nodes", "ext_vals",
                 "ext_parents", "_ext_ids", "avals", "barrier", "outs",
                 "tapenode", "flushed", "error", "_lock")

    def __init__(self, ctx, recording: bool, fuse: str, cap: int):
        # re-entrant: guards append-vs-flush races (a cross-thread READ
        # of a pending output flushes this segment from another thread);
        # re-entrancy covers the owner thread's cap/barrier flushes
        # while it already holds the lock in _try_defer
        # one RLock per segment, amortized over bulk_size deferred
        # ops (~1µs for ~15 ops)
        # mxlint: disable=hot-path-purity — per-segment, amortized
        self._lock = threading.RLock()
        self.ctx = ctx
        self.recording = recording    # autograd scope state at creation
        self.fuse = fuse              # 'exact' | 'aggressive' at creation
        self.cap = cap                # MXNET_ENGINE_BULK_SIZE at creation
        self.nodes: List[Tuple] = []  # (op_name, kwkey, refs, multi)
        self.ext_vals: List[Any] = []
        self.ext_parents: List[Any] = []   # AGInfo | None per external
        self._ext_ids: Dict[Tuple, int] = {}
        self.avals: List[Tuple] = []  # (shape, dtype) per flat output
        self.barrier: List[bool] = []  # per flat output: reduction-like?
        self.outs: List[Tuple] = []   # (weakref[NDArray], PendingValue)
        self.tapenode = None          # created when the first op records
        self.flushed = False
        self.error = None

    def add_ext_locked(self, val, parent) -> int:
        # callers (_try_defer's argument-collection loop) hold self._lock
        # — the ``_locked`` suffix is the lint-checked convention
        key = (id(val), id(parent))
        idx = self._ext_ids.get(key)
        if idx is None:
            idx = len(self.ext_vals)
            self._ext_ids[key] = idx
            self.ext_vals.append(val)
            self.ext_parents.append(parent)
        return idx

    @hot_path("dispatch")
    def flush(self) -> None:
        with self._lock:
            self._flush_locked()

    @hot_path("dispatch")
    def _flush_locked(self) -> None:
        if self.flushed:
            return
        self.flushed = True
        if getattr(_tls, "seg", None) is self:
            _tls.seg = None
        if not self.nodes:
            return                    # nothing was deferred
        # always timed: the per-flush latency histogram (engine.flush_us)
        # is the auto-tune signal for MXNET_ENGINE_BULK_SIZE; as a span
        # (the engine observes its duration itself, so no registry lookup
        # here) the flush also lies in a profiler's trace as
        # ``mx.engine.flush_us``
        with _span("engine.flush_us", histogram=False) as sp:
            hit = self._run_locked()
        engine().on_bulk_flush(len(self.nodes), hit, sp.duration_us)

    @hot_path("dispatch")
    def _run_locked(self):
        """Execute the segment; returns whether the fused-executable
        cache hit (None where it was never consulted)."""
        taped = self.tapenode is not None
        # liveness: outputs whose NDArray died (or was overwritten by an
        # in-place write) before the flush need no buffer at all
        live = []
        for ref, marker in self.outs:
            nd = ref()
            if nd is not None and nd._data is marker:
                live.append((nd, marker))
        needed = None if taped else tuple(m.index for _, m in live)
        if not taped and not live:
            # nothing observable: the whole segment is dead code — the
            # executable cache was never consulted
            return None
        # device id in the key: an exact-mode executable is PINNED to its
        # device; same-signature segments on another
        # device must compile their own
        device = self.ctx.device
        key = (self.fuse, taped, needed, device.id,
               tuple(self.nodes),
               tuple((tuple(v.shape), _np.dtype(v.dtype))
                     for v in self.ext_vals))
        fn = _segment_cache.get(key)
        hit = fn is not None
        try:
            if not hit:
                if self.fuse == "exact" and not taped:
                    # the disk-tier key is only built on a true
                    # in-memory miss — the steady-state flush never
                    # pays the repr
                    pkey = None if _persist_hooks is None else \
                        _segment_persist_key(
                            device.id, needed, tuple(self.nodes),
                            self.ext_vals)
                    fn = _compile_segment_exact(
                        tuple(self.nodes), needed, self.ext_vals,
                        device, persist_key=pkey)
                else:
                    fn = _compile_segment(tuple(self.nodes), taped,
                                          needed)
                _segment_cache.put(key, fn)
            if taped:
                vals, vjp_fn = fn(*self.ext_vals)
                node = self.tapenode
                node.vjp_fn = vjp_fn
                node.parents = list(self.ext_parents)
                node.out_avals = list(self.avals)
                for nd, marker in live:
                    nd._data = vals[marker.index]
            else:
                vals = fn(*self.ext_vals)
                for (nd, _), v in zip(live, vals):
                    nd._data = v
        except Exception as e:
            # errors surface at the sync point, as async errors do in the
            # reference engine; later reads of the orphaned outputs raise
            # via NDArray._read's pending barrier
            self.error = e
            raise
        return hit


@hot_path("dispatch")
def flush_segment() -> None:
    """Flush the calling thread's pending bulk segment, if any (the hook
    behind every sync point: reads, wait_for_var/wait_all, non-fusable
    ops, engine-type switches)."""
    seg = getattr(_tls, "seg", None)
    if seg is not None:
        seg.flush()


_install_flush_hook(flush_segment)


@hot_path("dispatch")
def _try_defer(op: Operator, nd_inputs: Sequence, kwargs: Dict[str, Any],
               ctx, eng):
    """Append this op application to the thread's pending segment instead
    of dispatching it.  Returns the pending output NDArray(s), or
    ``_NOT_FUSABLE`` — in which case the caller flushes (a non-fusable op
    is a sync point) and dispatches eagerly."""
    NDArray = _ND_CLS or _nd_cls()
    if not op.use_jit or op.vjp_maker is not None \
            or op.name in _SUBGRAPH_OPS:
        return _NOT_FUSABLE
    if op.needs_rng and op_takes_key(op, kwargs):
        return _NOT_FUSABLE          # sampling advances the RNG stream
    fuse = eng.bulk_fuse_mode
    rec = _autograd.is_recording()
    recording_op = False
    if rec:
        recording_op = any(x._ag is not None for x in nd_inputs)
        if recording_op:
            if fuse != "aggressive":
                # in exact mode the tape stays per-op (its vjp wrappers
                # are already one-dispatch each and trivially bitwise);
                # taped SEGMENTS — one jax.vjp over the fused forward —
                # are the aggressive mode's territory
                return _NOT_FUSABLE
            differentiable = op.differentiable(kwargs) \
                if callable(op.differentiable) else op.differentiable
            if not differentiable:
                # the unbulked path would NOT record this op; fusing it
                # into a taped segment would differentiate through it
                return _NOT_FUSABLE
    kwkey = () if not kwargs else \
        tuple(sorted((k, _canon(v)) for k, v in kwargs.items()))

    seg = getattr(_tls, "seg", None)
    # materialize VIEW inputs and any value not pending on OUR segment
    # BEFORE taking the segment lock: these reads can flush (a view's
    # root, or another thread's segment), and flushing a foreign segment
    # while holding ours would be an ABBA deadlock; our own pendings are
    # handled by reference below, so after this pass no read under the
    # lock can flush anything
    for x in nd_inputs:
        if x._base is not None:
            x._read()
        else:
            d = x._data
            if type(d) is PendingValue and d.segment is not seg:
                x._read()
    if seg is not None and (seg.flushed or seg.recording != rec
                            or seg.fuse != fuse or seg.ctx != ctx):
        # a segment is all-taped or all-untaped, one fuse mode, and
        # single-context.  seg.flushed covers another THREAD having
        # flushed our segment via a cross-thread read — flush() only
        # clears the flushing thread's own _tls pointer.
        seg.flush()
        seg = None
    if seg is None:
        seg = _BulkSegment(ctx, rec, fuse, eng.bulk_size)
        _tls.seg = seg

    # argument collection + node append, under the segment lock so a
    # cross-thread flush cannot interleave (it would capture the node
    # list without our outputs and orphan their pending markers).  A
    # restart happens via the aggressive-mode reduction barrier or a
    # racing flush; both swap in a fresh segment.
    tracer = _TRACER_CLS or _tracer_type()
    sds = _SDS_CLS or _sds_cls()
    seg._lock.acquire()
    try:
        while True:
            if seg.flushed:           # raced a cross-thread flush
                seg._lock.release()
                seg = _BulkSegment(ctx, rec, fuse, eng.bulk_size)
                _tls.seg = seg
                seg._lock.acquire()
            refs = []
            in_avals = []
            restart = False
            for x in nd_inputs:
                d = x._data if x._base is None else None
                if type(d) is PendingValue and d.segment is seg:
                    if seg.barrier[d.index]:
                        # consuming a reduction-like pending output:
                        # XLA's accumulation order inside a fused
                        # consumer is not bitwise-contractual (measured
                        # ~1-ulp drift on CPU for mean fused into its
                        # consumer), so aggressive fusion materializes
                        # the reduction first; exact mode never refuses
                        # kernels, never sets the flag, and its
                        # segments run longer
                        seg._flush_locked()
                        seg._lock.release()
                        seg = _BulkSegment(ctx, rec, fuse,
                                           eng.bulk_size)
                        _tls.seg = seg
                        seg._lock.acquire()
                        restart = True
                        break
                    refs.append((_NODE, d.index))
                    in_avals.append(seg.avals[d.index])
                else:
                    v = x._read()     # concrete (pre-pass): cannot flush
                    if isinstance(v, tracer):
                        return _NOT_FUSABLE  # under a jit trace
                    sh = getattr(v, "sharding", None)
                    if sh is not None and type(sh) is not sds \
                            and len(sh.device_set) > 1:
                        return _NOT_FUSABLE  # multi-chip global arrays
                    refs.append((_EXT, seg.add_ext_locked(
                        v, x._ag if rec else None)))
                    # jax arrays already expose tuple shapes + np dtypes
                    in_avals.append((v.shape, v.dtype))
            if not restart:
                break
        try:
            out_avals, multi = _infer_out_avals(op.name, kwkey,
                                                tuple(in_avals))
        except Exception:  # noqa: BLE001 — let the EAGER path raise
            return _NOT_FUSABLE      # the op's real error (exact parity)

        if recording_op and seg.tapenode is None:
            seg.tapenode = _autograd.TapeNode(
                "_BulkSegment", None, [], [], True, runner_safe=True)

        node_base = len(seg.avals)
        seg.nodes.append((op.name, kwkey, tuple(refs), multi))
        seg.avals.extend(out_avals)
        # aggressive mode only: an output with FEWER elements than the
        # op's largest input is reduction-like (sum/mean/max/slice/...),
        # as is anything in the explicit contraction set — consuming it
        # while still pending forces a flush (see above).
        if fuse != "aggressive":
            seg.barrier.extend(False for _ in out_avals)
        elif _is_barrier_op(op.name):
            seg.barrier.extend(True for _ in out_avals)
        else:
            max_in = max((_n_elems(s) for s, _ in in_avals), default=0)
            seg.barrier.extend(_n_elems(s) < max_in
                               for s, _ in out_avals)
        outs = []
        for i, (shp, dt) in enumerate(out_avals):
            marker = PendingValue(seg, node_base + i)
            nd = NDArray(marker, ctx=ctx, _shape=shp, _dtype=dt)
            seg.outs.append((_weakref.ref(nd), marker))
            if recording_op:
                nd._ag = _autograd.AGInfo(node=seg.tapenode,
                                          index=node_base + i)
            outs.append(nd)

        eng._c_bulked.n += 1          # inlined on_bulk_push (hot-path
        # idiom: a registry Counter's .n is a plain int — same cost as
        # the former private attribute add)
        if len(seg.nodes) >= seg.cap:
            seg._flush_locked()       # MXNET_ENGINE_BULK_SIZE cap
        return outs if multi else outs[0]
    finally:
        seg._lock.release()


def invoke(op: Operator, inputs: Sequence, kwargs: Dict[str, Any],
           out=None):
    """Dispatch an op imperatively (reference stack §3.1).

    Returns one NDArray, or a list for multi-output ops.  ``out=`` writes the
    (first) result into an existing NDArray in place.

    With bulking enabled (MXNET_EXEC_BULK_EXEC_TRAIN, the default), fusable
    ops are DEFERRED into a lazy segment and only materialize at a sync
    point — see ``_try_defer`` / ``_BulkSegment`` above.
    """
    NDArray = _ND_CLS or _nd_cls()
    if _invoke_hook is not None:
        inputs = _invoke_hook(op.name, inputs)

    ctx = None
    for x in inputs:
        if isinstance(x, NDArray):
            ctx = x.context
            break
    if ctx is None:
        # zero-input creation ops carry ctx as an op attribute
        # (reference init_op.cc convention) — honor it for the tag too
        ckw = kwargs.get("ctx")
        if ckw is not None:
            from ..context import Context
            ctx = ckw if isinstance(ckw, Context) else Context.from_str(ckw)
        else:
            ctx = current_context()
    nd_inputs = [_as_nd(x, ctx) for x in inputs]
    eng = engine()
    # listeners (profiler/monitor) need REAL per-op outputs — Monitor's
    # stat_func inspects every dispatched value — so bulking suspends
    # while any listener is installed; engine().stats() still aggregates
    if out is None and not eng._listeners and eng.bulk_enabled:
        res = _try_defer(op, nd_inputs, kwargs, ctx, eng)
        if res is not _NOT_FUSABLE:
            return res
    # a non-fusable op (or out=/disabled bulking/NaiveEngine) is a flush
    # point: the pending segment's effects must precede this dispatch
    flush_segment()
    in_vals = [x._read() for x in nd_inputs]
    if op_takes_key(op, kwargs):
        # sampling ops take a PRNG key as their last input; eager dispatch
        # draws it here (under a hybrid trace, next_key() yields a TRACED
        # subkey of the CachedOp's key argument — push_key in random.py —
        # so compiled graphs stay fresh per call)
        from .. import random as _grandom
        in_vals.append(_grandom.next_key())

    differentiable = op.differentiable(kwargs) \
        if callable(op.differentiable) else op.differentiable
    recording = (_autograd.is_recording() and differentiable
                 and any(getattr(x, "_ag", None) is not None
                         for x in nd_inputs))
    # timing only when someone is listening (profiler) — invoke is the
    # hottest path in the library
    _timed = bool(eng._listeners)
    _t0 = _perf_counter() if _timed else 0.0
    if recording:
        vjp_wrapper, runner_safe = op.get_vjp_fn(kwargs)
        out_vals, vjp_fn = vjp_wrapper(*in_vals)
    else:
        out_vals = op.get_fn(kwargs)(*in_vals)
    _dispatch_us = (_perf_counter() - _t0) * 1e6 if _timed else 0.0

    multi = isinstance(out_vals, (tuple, list))
    raw_outs = list(out_vals) if multi else [out_vals]
    outs = [NDArray(v, ctx=ctx) for v in raw_outs]

    if recording:
        parents = [getattr(x, "_ag", None) for x in nd_inputs]
        node = _autograd.TapeNode(op.name, vjp_fn, parents,
                                  [(o.shape, o.dtype) for o in outs], multi,
                                  runner_safe=runner_safe)
        for i, o in enumerate(outs):
            o._ag = _autograd.AGInfo(node=node, index=i)

    eng.on_push(op.name, raw_outs, _dispatch_us)

    if out is not None:
        outs_for_write = outs if multi else [outs[0]]
        targets = out if isinstance(out, (list, tuple)) else [out]
        for tgt, src in zip(targets, outs_for_write):
            val = src._read()
            # out= keeps the target's dtype (an AMP cast hook may have
            # changed the compute dtype; the write-back contract wins)
            if val.dtype != tgt.dtype:
                val = val.astype(tgt.dtype)
            tgt._set_data(val)
        return out
    return outs if multi else outs[0]


def invoke_by_name(name: str, inputs: Sequence, kwargs: Dict[str, Any],
                   out=None):
    return invoke(get_op(name), inputs, kwargs, out=out)


# scalar fallbacks for the arithmetic dunders: (forward op, reflected op)
_SCALAR_MAP = {
    "broadcast_add": ("_plus_scalar", "_plus_scalar"),
    "broadcast_sub": ("_minus_scalar", "_rminus_scalar"),
    "broadcast_mul": ("_mul_scalar", "_mul_scalar"),
    "broadcast_div": ("_div_scalar", "_rdiv_scalar"),
    "broadcast_mod": ("_mod_scalar", "_rmod_scalar"),
    "broadcast_power": ("_power_scalar", "_rpower_scalar"),
    "broadcast_equal": ("_equal_scalar", "_equal_scalar"),
    "broadcast_not_equal": ("_not_equal_scalar", "_not_equal_scalar"),
    "broadcast_greater": ("_greater_scalar", "_lesser_scalar"),
    "broadcast_greater_equal": ("_greater_equal_scalar", "_lesser_equal_scalar"),
    "broadcast_lesser": ("_lesser_scalar", "_greater_scalar"),
    "broadcast_lesser_equal": ("_lesser_equal_scalar", "_greater_equal_scalar"),
}


def invoke_binary(name: str, lhs, rhs, reverse: bool = False):
    """Binary dunder dispatch: NDArray⊕NDArray uses the broadcast op;
    NDArray⊕scalar uses the ``_*_scalar`` variant with the scalar passed as a
    0-d array input (keeps one XLA compilation per shape, not per constant)."""
    from .ndarray import NDArray
    if isinstance(rhs, NDArray):
        args = [rhs, lhs] if reverse else [lhs, rhs]
        return invoke_by_name(name, args, {})
    if isinstance(rhs, (_np.ndarray, list)):
        args = [rhs, lhs] if reverse else [lhs, rhs]
        return invoke_by_name(name, args, {})
    fwd, rev = _SCALAR_MAP[name]
    sop = rev if reverse else fwd
    scal = _np.asarray(rhs)
    return invoke_by_name(sop, [lhs, scal], {})


@functools.lru_cache(maxsize=None)
def _maker_param_names(op: Operator) -> Tuple[str, ...]:
    import inspect
    try:
        return tuple(
            p.name for p in inspect.signature(op.maker).parameters.values()
            if p.kind in (inspect.Parameter.POSITIONAL_OR_KEYWORD,
                          inspect.Parameter.KEYWORD_ONLY))
    except (TypeError, ValueError):
        return ()


_JAX_ARRAY_CLS = None


def _is_param_value(v) -> bool:
    """Positional values that are op PARAMETERS, not tensor inputs.
    Tuples are parameters (shape/axes); plain lists stay tensor-ish
    (mx.nd converts lists to arrays)."""
    global _JAX_ARRAY_CLS
    if _JAX_ARRAY_CLS is None:
        import jax
        _JAX_ARRAY_CLS = jax.Array
    if isinstance(v, (bool, int, float, str, tuple, _np.generic)):
        return True
    if isinstance(v, (_np.ndarray, _JAX_ARRAY_CLS, list)):
        return False
    if hasattr(v, "_heads"):                # Symbol (duck-typed: symbol
        return False                        # imports this module)
    return not isinstance(v, _ND_CLS or _nd_cls())


def split_positional_params(op: Operator, args: Sequence,
                            kwargs: Dict[str, Any]):
    """Reference-parity calling convention for generated wrappers: the
    C-side registry gave each wrapper an explicit signature
    ``op(data..., param1, param2, ...)``, so trailing non-tensor
    positionals map onto the op's parameters in maker-declaration order
    (``nd.sum(x, 1)`` ≡ ``nd.sum(x, axis=1)``)."""
    inputs = list(args)
    split = len(inputs)
    while split > 0 and _is_param_value(inputs[split - 1]):
        split -= 1
    extra = inputs[split:]
    if not extra:
        return inputs, kwargs
    names = _maker_param_names(op)
    if len(extra) > len(names):
        return inputs, kwargs               # unmappable: legacy behavior
    for n, v in zip(names, extra):
        if n in kwargs:
            raise TypeError(
                f"{op.name}() got multiple values for argument {n!r}")
        kwargs[n] = v
    return inputs[:split], kwargs


def make_frontend(op: Operator) -> Callable:
    """Build the user-facing ``mx.nd.<op>`` function."""
    def frontend(*args, **kwargs):
        out = kwargs.pop("out", None)
        kwargs.pop("name", None)        # accepted for symbol-API symmetry
        inputs, kwargs = split_positional_params(op, args, kwargs)
        return invoke(op, inputs, kwargs, out=out)
    frontend.__name__ = op.name
    frontend.__qualname__ = op.name
    frontend.__doc__ = op.doc
    return frontend
