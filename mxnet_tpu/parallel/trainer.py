"""ShardedTrainer: the whole training step as ONE jitted XLA computation
over a device mesh.

Reference parity: this subsumes the reference's data-parallel machinery —
`split_and_load` + Trainer.step → KVStore push/pull → fused optimizer ops
(python/mxnet/gluon/trainer.py, src/kvstore/comm.h — SURVEY.md §2.3, §3.2).
TPU-native design (the BASELINE north star): instead of object-level
push/pull loops, the step function

    (params, aux, opt_state, key, t, lr, rescale, x, y)
        -> (params', aux', opt_state', loss)

is jitted with `NamedSharding`s: batch sharded over the 'dp' mesh axis,
params replicated (or tensor-parallel via ShardingRules), so XLA emits the
gradient psum over ICI that the reference performed through NCCL, fuses it
with the optimizer update, and donates the param buffers (true in-place
update at the HBM level).  Numerics match the imperative Trainer exactly
(same formulas — parallel/optim.py).

ZeRO scale-out (``zero_stage``, PAPERS.md ZeRO / Megatron-LM lineage):
stage 0 replicates optimizer state on every chip (the reference's
NCCL-KVStore layout, bitwise-identical to the pre-ZeRO step); stage 1
shards optimizer state 1/dp per chip — gradients are reduce-SCATTERED
into each chip's slice instead of psum-replicated, each chip runs its
slice of the functional optimizer update, and the updated params are
all-gathered, all inside the one donated jit so XLA overlaps the
collectives with backward compute; stage 2 additionally keeps the
gradient (accumulation) buffer sharded, so with ``accum_steps > 1`` the
carried grad state costs 1/dp per chip too.  ``accum_steps=N``
microbatches the global batch through a ``lax.scan`` (per-microbatch
RNG split, rescale-correct: the accumulated gradient equals the
full-batch gradient), so global batch scales past per-chip memory.
"""
from __future__ import annotations

import re as _re
import threading as _threading
import time as _time
import warnings as _warnings
from typing import Any, Callable, List, Optional, Sequence

import numpy as _np

from ..base import MXNetError, get_env, hot_path
from ..context import current_context
from .. import autograd as _autograd
from .. import optimizer as opt_mod
from .. import random as _grandom
from ..ndarray import NDArray
from ..gluon.block import _TraceCtx, _KeyScope
from ..gluon.parameter import Parameter
from ..observability.registry import registry as _metrics_registry
from ..observability.trace import span as _span
from ..sparse_grad import SparseGradTrace as _SparseGradTrace
from .mesh import (ShardingRules, axis_size, comm_buckets, default_mesh,
                   replicated, shard, zero_sharding)
from .optim import make_functional_optimizer

__all__ = ["ShardedTrainer"]

# a committed orbax checkpoint dir is exactly `state-<8 digits>` AND carries
# the commit marker; anything else under the root (orbax's
# `*.orbax-checkpoint-tmp-*` rename staging, a dir torn by a crash
# mid-async-write) is an uncommitted partial and must never be restored
_STEP_DIR_RE = _re.compile(r"^state-(\d+)$")
_COMMIT_MARKER = "_CHECKPOINT_METADATA"


class ShardedTrainer:
    """Data/tensor/sequence-parallel trainer over a jax Mesh.

    Parameters
    ----------
    block : gluon.Block — the model (need not be hybridized; the step IS
        the jit).
    loss : callable — `loss(out, y) -> NDArray` (a gluon loss Block works).
    optimizer : str or Optimizer — lowered to a pure update (optim.py).
    mesh : jax.sharding.Mesh — default: all devices on 'dp'.
    rules : ShardingRules — parameter PartitionSpecs (tensor parallelism).
    data_spec / label_spec : PartitionSpec tuples for the batch, default
        ('dp',) — add 'sp' on the sequence dim for context parallelism,
        e.g. data_spec=('dp', 'sp').
    zero_stage : {0, 1, 2} — optimizer-state partitioning over the 'dp'
        axis.  0 (the default) = replicated state (bitwise-identical
        to the pre-ZeRO step); 1 = state sharded, gradients
        reduce-scattered for the update, updated params all-gathered;
        2 = the gradient (accumulation) buffer is sharded too.
        Per-parameter fallback: a tensor whose dim 0 cannot split over
        dp keeps replicated state (see
        :func:`~mxnet_tpu.parallel.mesh.zero_sharding`).
    accum_steps : int — microbatched gradient accumulation (default 1:
        none).  The step consumes the same global batch but runs it as
        N sequential microbatches under a ``lax.scan``; peak activation
        memory drops ~N-fold while the update is rescale-correct against
        the full batch.
    comm_bucket_mb : float — bucketed gradient reduce-scatter (default:
        the ``MXTPU_COMM_BUCKET_MB`` knob).  0 (off) keeps ONE fused
        reduction after the full backward — bitwise-identical to the
        pre-bucketing step; > 0 splits the gradients into buckets of
        at most this many MB (reverse parameter order — the order
        backward materializes them) whose dp-reductions are pinned
        with ``optimization_barrier``-chained sharding constraints so
        XLA's latency-hiding scheduler overlaps each bucket's
        collective with the remaining backward compute.
    remat : sequence of Blocks — the blocks of the model whose forward
        is rematerialised in the backward (``jax.checkpoint`` around each:
        the step keeps a block's inputs and computes its inside again),
        for a model whose activations would not fit beside its state.
        Empty (the default) traces exactly the step without it.
    """

    def __init__(self, block, loss: Callable, optimizer,
                 optimizer_params: Optional[dict] = None, mesh=None,
                 rules: Optional[ShardingRules] = None,
                 data_spec: Sequence = ("dp",),
                 label_spec: Optional[Sequence] = None,
                 zero_stage: int = 0,
                 accum_steps: int = 1,
                 comm_bucket_mb: Optional[float] = None,
                 remat: Sequence = (),
                 guard_nonfinite: bool = False,
                 dynamic_loss_scale: bool = False,
                 init_loss_scale: float = 2.0 ** 15,
                 scale_growth_interval: int = 2000,
                 scale_backoff: float = 0.5,
                 min_loss_scale: float = 1.0,
                 max_loss_scale: float = 2.0 ** 24):
        self._block = block
        self._loss = loss
        self._mesh = mesh if mesh is not None else default_mesh()
        self._rules = rules if rules is not None else ShardingRules()
        self._data_spec = tuple(data_spec)
        self._label_spec = tuple(label_spec) if label_spec is not None \
            else (self._data_spec[0],)
        if zero_stage not in (0, 1, 2):
            raise MXNetError(
                f"zero_stage must be 0, 1 or 2, got {zero_stage!r}")
        self._zero = int(zero_stage)
        if int(accum_steps) < 1:
            raise MXNetError(
                f"accum_steps must be >= 1, got {accum_steps!r}")
        self._accum = int(accum_steps)
        if comm_bucket_mb is None:
            comm_bucket_mb = float(get_env("MXTPU_COMM_BUCKET_MB"))
        if float(comm_bucket_mb) < 0:
            raise MXNetError(
                f"comm_bucket_mb must be >= 0 (0 = one fused "
                f"reduction), got {comm_bucket_mb!r}")
        self._bucket_mb = float(comm_bucket_mb)
        self._grad_buckets = None
        self._remat = tuple(remat)
        # forced checkpoint layout: None = auto (_host_local_checkpoint
        # decides from the process group); tests set True to exercise
        # the self-contained npz writer in a single process
        self.host_local_ckpt: Optional[bool] = None
        self._hl_writer = None       # in-flight async npz commit thread
        self._hl_error = None
        optimizer_params = optimizer_params or {}
        self._optimizer = opt_mod.create(optimizer, **optimizer_params)
        self._scale = self._optimizer.rescale_grad
        self._built = False
        self._t = 0
        self._ctx = current_context()
        self._guard = bool(guard_nonfinite)
        self._dyn_scale = bool(dynamic_loss_scale)
        self._init_ls = float(init_loss_scale) if dynamic_loss_scale else 1.0
        self._growth_interval = int(scale_growth_interval)
        self._scale_backoff = float(scale_backoff)
        self._min_ls = float(min_loss_scale)
        self._max_ls = float(max_loss_scale)
        self._gstate = None          # (loss_scale, clean_step_count) arrays
        self._last_finite = None     # device bool from the last guarded step

    def enable_nonfinite_guard(self, dynamic_loss_scale: bool = False,
                               init_loss_scale: float = 2.0 ** 15,
                               scale_growth_interval: int = 2000,
                               scale_backoff: float = 0.5) -> None:
        """Turn on the in-graph all-finite guard (see step_fn): a step
        whose loss or any gradient is non-finite leaves params, optimizer
        state and aux bit-identical instead of applying the update.  Must
        be called before the first step — the guard changes the jitted
        step function."""
        if self._built:
            raise MXNetError("enable_nonfinite_guard() must be called "
                             "before the first step() builds the jit")
        self._guard = True
        self._dyn_scale = bool(dynamic_loss_scale)
        self._init_ls = float(init_loss_scale) if dynamic_loss_scale else 1.0
        self._growth_interval = int(scale_growth_interval)
        self._scale_backoff = float(scale_backoff)

    # -- lazy build --------------------------------------------------------
    def _ensure_built(self, xs, y: _np.ndarray) -> None:
        if self._built:
            return
        import jax
        import jax.numpy as jnp

        # one tiny eager forward to settle deferred param shapes; a model
        # whose shapes are all given needs none (one row of a long
        # sequence is no tiny forward)
        if any(p._deferred_init is not None
               for p in self._block.collect_params().values()):
            probes = [NDArray(jnp.asarray(v[:1]), ctx=self._ctx) for v in xs]
            self._block(*probes)

        all_params = list(self._block.collect_params().values())
        self._train_params: List[Parameter] = \
            [p for p in all_params if p.grad_req != "null"]
        self._aux_params: List[Parameter] = \
            [p for p in all_params if p.grad_req == "null"]
        self._optimizer.param_dict = {
            i: p for i, p in enumerate(self._train_params)}
        names = [p.name for p in self._train_params]
        self._fopt = make_functional_optimizer(self._optimizer, names)

        # row-sparse gradient layout (sparse_grad.py): params marked
        # grad_stype='row_sparse' whose gradient is produced in-graph as
        # a (values, unique_ids) pair and updated lazily.  The mark is
        # an intent; whether a given trace actually takes the sparse
        # path is decided per-retrace by the eval_shape probe in
        # make_grads (a hybridized table silently stays dense).
        self._sparse_marked = frozenset(
            i for i, p in enumerate(self._train_params)
            if getattr(p, "grad_stype", "default") == "row_sparse")
        if self._sparse_marked and not get_env("MXTPU_SPARSE_GRAD"):
            self._sparse_marked = frozenset()
        if self._sparse_marked and self._accum > 1:
            _warnings.warn(
                "sparse_grad embeddings fall back to dense gradients "
                "under accum_steps > 1 (the scan's carried accumulation "
                "buffer is dense)")
            self._sparse_marked = frozenset()
        if self._sparse_marked and self._fopt.kind not in ("sgd", "adam"):
            _warnings.warn(
                f"optimizer {self._fopt.kind!r} has no lazy row-sparse "
                f"lowering — sparse_grad embeddings fall back to dense")
            self._sparse_marked = frozenset()
        # trace-time record {param_idx: (bucket, vocab)} from the last
        # sparse probe — feeds the sparse.* metrics in step()
        self._sparse_trace_info = {}

        # input/label structure, captured once: reshard() re-derives the
        # shardings and rebuilds the jits on a new mesh without needing
        # fresh example data
        self._x_ndims = tuple(v.ndim for v in xs)
        self._y_multi = isinstance(y, tuple)
        self._y_ndims = tuple(v.ndim for v in y) if self._y_multi \
            else y.ndim

        self._make_shardings()

        # move weights onto the mesh — the trainer owns them from here on
        self._pvals = [jax.device_put(p.data(self._ctx)._read(), s)
                       for p, s in zip(self._train_params, self._p_sh)]
        self._avals = [jax.device_put(p.data(self._ctx)._read(), s)
                       for p, s in zip(self._aux_params, self._a_sh)]
        state = self._fopt.init(self._pvals)
        self._s_sh = self._state_shardings(state)
        self._state = jax.tree.map(
            lambda v, s: jax.device_put(v, s), state, self._s_sh)

        self._build_jits()
        self._built = True

    def _make_shardings(self) -> None:
        """Derive every sharding from the CURRENT mesh: parameter/aux
        (rules), inputs/labels (data_spec), and the ZeRO layout for
        optimizer state + stage-2 gradient buffers.  Split out of the
        lazy build so :meth:`reshard` can re-derive them when the mesh
        (dp size) changes."""
        mesh = self._mesh
        self._dp = axis_size(mesh, "dp")
        self._p_sh = [self._rules.sharding_for(mesh, p.name, p.shape)
                      for p in self._train_params]
        # RowShardedEmbedding: the table itself (not just its state)
        # partitions dim 0 over the marked axis, with zero_sharding's
        # per-parameter fallback (indivisible vocab / axis of size 1 /
        # dim 0 already ruled → replicated as before)
        for i, p in enumerate(self._train_params):
            ax = getattr(p, "_row_shard_axis", None)
            if ax is not None:
                self._p_sh[i] = zero_sharding(
                    mesh, self._rules.spec_for(p.name, p.shape), p.shape,
                    axis=ax)
        self._a_sh = [self._rules.sharding_for(mesh, p.name, p.shape)
                      for p in self._aux_params]
        # ZeRO layout: stage >= 1 partitions optimizer state (and the
        # stage-2 grad buffer) dim-0 over 'dp' — per-parameter fallback
        # to the parameter's own sharding when dim 0 cannot split
        if self._zero >= 1:
            self._z_sh = [
                zero_sharding(mesh, self._rules.spec_for(p.name, p.shape),
                              p.shape)
                for p in self._train_params]
            # a row-sharded table's state lives WITH its weight rows —
            # the param sharding already is the 1/dp layout
            for i, p in enumerate(self._train_params):
                if getattr(p, "_row_shard_axis", None) is not None:
                    self._z_sh[i] = self._p_sh[i]
        else:
            self._z_sh = list(self._p_sh)
        # per-input sharding: the data spec truncated to each input's rank
        self._x_sh = tuple(
            shard(mesh, *self._data_spec[:nd]) for nd in self._x_ndims)
        # tuple labels (multi-stream, e.g. MLM+NSP) shard element-wise
        if self._y_multi:
            self._y_sh = tuple(shard(mesh, *self._label_spec[:nd])
                               for nd in self._y_ndims)
        else:
            self._y_sh = shard(mesh, *self._label_spec[:self._y_ndims])
        self._r_sh = replicated(mesh)

    def _state_shardings(self, state):
        """Optimizer-state shardings: every leaf of param i's state tree
        carries the ZeRO sharding (== param sharding at stage 0)."""
        import jax
        return [jax.tree.map(lambda _, sh=sh: sh, st)
                for st, sh in zip(state, self._z_sh)]

    def _build_jits(self) -> None:
        import jax
        import jax.numpy as jnp

        self._dispatch_metrics = _dispatch_metrics()
        # {(jitted function, the batch's shapes and dtypes): Compiled};
        # whatever rebuilds the jits comes through here and empties it
        self._compiled = {}
        block, loss_blk, remat = self._block, self._loss, self._remat
        _metrics_registry().gauge(
            "trainer.remat_blocks", "blocks the last trainer built "
            "rematerialises in its backward").set(len(remat))
        # what those blocks keep for the backward all the same: each
        # trace of the step counts anew (gluon/block.py:_keep_named adds)
        kept_bytes = _metrics_registry().gauge(
            "trainer.remat_kept_bytes", "bytes of named values that the "
            "rematerialised blocks of the last step traced keep for the "
            "backward")
        # and so do the delta rule's state passes, which add their
        # iterations (kernels/state_pass.py:_count)
        scan_steps = _metrics_registry().gauge("gdn.scan_steps")
        tparams, aparams = self._train_params, self._aux_params
        fopt, ctx = self._fopt, self._ctx

        def apply_fn(pvals, avals, key, xv, training, yv=None):
            """Shared traced forward (+ optional loss) for train and eval.
            xv is a tuple of input arrays (multi-input models: BERT takes
            tokens/token_types/mask)."""
            tw = [NDArray(v, ctx=ctx) for v in pvals]
            aw = [NDArray(v, ctx=ctx) for v in avals]
            subs = {id(p): w for p, w in zip(tparams + aparams, tw + aw)}
            with _TraceCtx(subs, remat if training else ()), \
                    _autograd._RecordingScope(False, training), \
                    _KeyScope(key):
                out = block(*[NDArray(v, ctx=ctx) for v in xv])
                if yv is None:
                    l_nd = None
                else:
                    with jax.named_scope("loss"):
                        if isinstance(yv, tuple):
                            l_nd = loss_blk(out, tuple(NDArray(v, ctx=ctx)
                                                       for v in yv))
                        else:
                            l_nd = loss_blk(out, NDArray(yv, ctx=ctx))
            for w in tw:
                if w._version > 0:
                    raise MXNetError(
                        "in-place write to a trainable parameter inside the "
                        "sharded step is not supported")
            new_avals = [w._read() if w._version > 0 else v
                         for w, v in zip(aw, avals)]
            return out, l_nd, new_avals

        accum, zero = self._accum, self._zero
        dp = self._dp
        marked = self._sparse_marked if accum == 1 else frozenset()
        sparse_info = self._sparse_trace_info
        z_sh, p_sh = list(self._z_sh), list(self._p_sh)
        wsc = jax.lax.with_sharding_constraint
        # communication buckets for the gradient reduction (reverse
        # parameter order — the order backward materializes gradients);
        # a single bucket IS the fused path, kept as None so the
        # pre-bucketing trace stays byte-for-byte the same graph
        cap = self._bucket_mb * 2 ** 20 if self._bucket_mb else 0
        # sparse-marked params never ride the dense reduction buckets —
        # their (values, ids) grads have their own exchange
        dense_i = [i for i in range(len(self._pvals))
                   if i not in self._sparse_marked]
        bks = comm_buckets([int(self._pvals[i].nbytes) for i in dense_i],
                           cap)
        bks = [[dense_i[j] for j in b] for b in bks]
        self._grad_buckets = bks if len(bks) > 1 else None
        buckets = self._grad_buckets

        def constrain_grads(grads):
            """The gradient-reduction schedule.  Fused (``buckets is
            None``): one constraint sweep — at stage >= 1 XLA lowers
            every gradient's dp reduction to a reduce-scatter right
            before the update, all after the full backward (the PR-10
            trace).  Bucketed: each bucket is constrained separately
            and chained through ``jax.lax.optimization_barrier`` —
            bucket k's gradients are tied to bucket k-1's constrained
            output, so XLA can neither merge the per-bucket
            reductions back into one fused collective nor sink them
            all past the backward; the latency-hiding scheduler then
            issues bucket 0's collective (the last layers' grads, the
            first to materialize) while earlier layers' gradients are
            still being computed."""
            if buckets is None:
                # a (values, ids) sparse grad passes through unconstrained
                return [g if isinstance(g, tuple) else wsc(g, s)
                        for g, s in zip(grads, z_sh)]
            out = list(grads)
            prev = None
            for idx in buckets:
                vals = [out[i] for i in idx]
                if prev is not None:
                    tied = jax.lax.optimization_barrier(
                        tuple(vals) + (prev,))
                    vals = list(tied[:-1])
                vals = [wsc(v, z_sh[i]) for v, i in zip(vals, idx)]
                prev = vals[0]
                for i, v in zip(idx, vals):
                    out[i] = v
            return out
        if accum > 1:
            # microbatch shardings: after the (B, ...) -> (accum, B/accum,
            # ...) reshape the batch axis moves to dim 1; the scan axis
            # (dim 0) stays unsharded
            mb_x_sh = tuple(shard(self._mesh, None,
                                  *self._data_spec[:nd])
                            for nd in self._x_ndims)
            if self._y_multi:
                mb_y_sh = tuple(shard(self._mesh, None,
                                      *self._label_spec[:nd])
                                for nd in self._y_ndims)
            else:
                mb_y_sh = shard(self._mesh, None,
                                *self._label_spec[:self._y_ndims])

        def split_mb(v):
            return v.reshape((accum, v.shape[0] // accum) + v.shape[1:])

        def make_grads(scaled):
            """grads_of(pvals, avals, key, xv, yv, ls) ->
            (grads, mean_loss, new_avals) — the gradient of the
            FULL-batch SUM loss (reference semantics: loss.backward()
            seeds ones, Trainer.step(batch_size) folds the 1/batch
            rescale into the optimizer update; the MEAN is what we
            report).  ``scaled`` (trace-time bool) multiplies the
            differentiated loss by ``ls`` — the guarded path's loss
            scaling.  ``accum == 1`` traces EXACTLY the
            pre-accumulation graph (the zero_stage=0 bitwise contract);
            ``accum > 1`` scans the batch as microbatches with a
            per-microbatch RNG split, accumulating gradients — the sum
            over microbatch sum-loss gradients equals the full-batch
            gradient, so the optimizer's rescale is unchanged."""
            def grads_of(pvals, avals, key, xv, yv, ls):
                kept_bytes.set(0)
                scan_steps.set(0)
                if accum == 1:
                    # trace-time probe: which sparse-marked tables does
                    # THIS trace's forward actually reach, and with how
                    # many ids?  eval_shape emits no ops and re-runs on
                    # every retrace, so a new batch shape re-sizes the
                    # id buckets.
                    sparse_idx, zb0 = [], []
                    if marked:
                        probe = _SparseGradTrace("probe")
                        with probe:
                            jax.eval_shape(
                                lambda pv: apply_fn(
                                    pv, avals, key, xv, True, yv)[1]._read(),
                                pvals)
                        for i in sorted(marked):
                            pid = id(tparams[i])
                            if pid in probe.buckets and \
                                    pid not in probe.multi:
                                sparse_idx.append(i)
                                zb0.append(jnp.zeros(
                                    (probe.buckets[pid],
                                     pvals[i].shape[1]), pvals[i].dtype))
                        sparse_info.clear()
                        sparse_info.update(
                            {i: (int(z.shape[0]), int(pvals[i].shape[0]))
                             for i, z in zip(sparse_idx, zb0)})
                    if sparse_idx:
                        def loss_of_sp(pv, zb):
                            tr = _SparseGradTrace("grad", {
                                id(tparams[i]): z
                                for i, z in zip(sparse_idx, zb)})
                            with tr:
                                _, l_nd, new_avals = apply_fn(
                                    pv, avals, key, xv, True, yv)
                            lraw = l_nd._read()
                            total = jnp.sum(lraw)
                            if scaled:
                                total = total * ls
                            uids = [tr.uids[id(tparams[i])]
                                    for i in sparse_idx]
                            return total, (jnp.mean(lraw), new_avals, uids)

                        (_, (lval, new_avals, uids)), (grads, zgrads) = \
                            jax.value_and_grad(loss_of_sp, argnums=(0, 1),
                                               has_aux=True)(pvals, zb0)
                        # the table itself sat behind stop_gradient: its
                        # dense cotangent is an unused zeros buffer XLA
                        # DCEs once we swap in the (values, ids) pair
                        grads = list(grads)
                        for i, zg, u in zip(sparse_idx, zgrads, uids):
                            grads[i] = (zg, u)
                        if zero >= 2:
                            grads = [g if isinstance(g, tuple)
                                     else wsc(g, s)
                                     for g, s in zip(grads, z_sh)]
                        return grads, lval, new_avals

                    def loss_of(pv):
                        _, l_nd, new_avals = apply_fn(pv, avals, key, xv,
                                                      True, yv)
                        lraw = l_nd._read()
                        total = jnp.sum(lraw)
                        if scaled:
                            total = total * ls
                        return total, (jnp.mean(lraw), new_avals)

                    (_, (lval, new_avals)), grads = \
                        jax.value_and_grad(loss_of, has_aux=True)(pvals)
                    if zero >= 2:
                        # ZeRO-2: the gradient is reduce-scattered the
                        # moment it exists — never replicated
                        grads = [wsc(g, s) for g, s in zip(grads, z_sh)]
                    return grads, lval, new_avals

                def mb(v, s):
                    # constrain the microbatched view back onto the dp
                    # layout only when the microbatch still divides the
                    # axis — an uneven constraint would force XLA into a
                    # full rematerialization instead of a local reshape
                    m = split_mb(v)
                    return wsc(m, s) if m.shape[1] % dp == 0 else m

                keys = jax.random.split(key, accum)
                xms = tuple(mb(v, s) for v, s in zip(xv, mb_x_sh))
                if isinstance(yv, tuple):
                    yms = tuple(mb(v, s) for v, s in zip(yv, mb_y_sh))
                else:
                    yms = mb(yv, mb_y_sh)

                @jax.named_scope("microbatch")
                def body(carry, mb):
                    g_acc, av, lsum = carry
                    k_m, xm, ym = mb

                    def loss_of(pv):
                        _, l_nd, new_av = apply_fn(pv, av, k_m, xm, True,
                                                   ym)
                        lraw = l_nd._read()
                        total = jnp.sum(lraw)
                        if scaled:
                            total = total * ls
                        return total, (jnp.mean(lraw).astype(jnp.float32),
                                       new_av)

                    (_, (lmean, new_av)), g = \
                        jax.value_and_grad(loss_of, has_aux=True)(pvals)
                    g_acc = [a + b for a, b in zip(g_acc, g)]
                    if zero >= 2:
                        # ZeRO-2: the carried accumulation buffer stays
                        # sharded — 1/dp of the grads per chip across
                        # the whole scan
                        g_acc = [wsc(a, s) for a, s in zip(g_acc, z_sh)]
                    return (g_acc, new_av, lsum + lmean), None

                g0 = [jnp.zeros_like(p) for p in pvals]
                if zero >= 2:
                    g0 = [wsc(a, s) for a, s in zip(g0, z_sh)]
                (grads, new_avals, lsum), _ = jax.lax.scan(
                    body, (g0, list(avals), jnp.float32(0.0)),
                    (keys, xms, yms))
                # equal microbatches: full-batch mean = mean of means
                return grads, lsum / accum, new_avals
            return grads_of

        def run_update(pvals, grads, state, t, lr, rescale):
            """The (optionally ZeRO-sharded) optimizer update.  Stage 0
            is the plain call — bitwise the pre-ZeRO step.  Stage >= 1
            pins the collective schedule with sharding constraints:
            grads constrained to the ZeRO layout (XLA lowers the dp
            gradient reduction to a reduce-SCATTER into each chip's
            slice instead of a full psum), each chip updates its slice
            of params/state, and the updated params constrained back to
            the parameter layout (the all-gather) — all inside the one
            donated jit, so XLA overlaps the collectives with
            compute."""
            if zero >= 1 or buckets is not None:
                # stage 0 with bucketing on: the constraint target is
                # the param's own (replicated) sharding — the barrier
                # chain still pins WHERE each bucket's psum lands in
                # the schedule
                with jax.named_scope("grad_reduce"):
                    grads = constrain_grads(grads)
            sp = frozenset(i for i, g in enumerate(grads)
                           if isinstance(g, tuple))
            with jax.named_scope("optimizer"):
                new_pvals, new_state = fopt.update(
                    pvals, grads, state, t, lr, rescale, sparse=sp)
                if zero >= 1:
                    new_pvals = [wsc(wsc(w, zs), ps) for w, zs, ps in
                                 zip(new_pvals, z_sh, p_sh)]
            return new_pvals, new_state

        if not self._guard:
            grads_of = make_grads(scaled=False)

            def step_fn(pvals, avals, state, key, t, lr, rescale, xv, yv):
                grads, lval, new_avals = grads_of(pvals, avals, key, xv,
                                                  yv, None)
                new_pvals, new_state = run_update(pvals, grads, state, t,
                                                  lr, rescale)
                return new_pvals, new_avals, new_state, lval

            self._jit_step = jax.jit(
                step_fn,
                in_shardings=(self._p_sh, self._a_sh, self._s_sh,
                              self._r_sh, self._r_sh, self._r_sh,
                              self._r_sh, self._x_sh, self._y_sh),
                out_shardings=(self._p_sh, self._a_sh, self._s_sh,
                               self._r_sh),
                donate_argnums=(0, 1, 2))
        else:
            # guarded step: differentiate loss * loss_scale, unscale inside
            # the optimizer rescale, and gate the WHOLE update on an
            # all-finite reduction over loss+grads — a poisoned step passes
            # params/momenta/aux through bit-identical.  The gate is a
            # jnp.where inside the one XLA computation, so skipping costs
            # no extra host sync or dispatch.
            dyn = self._dyn_scale
            growth_n = self._growth_interval
            backoff = self._scale_backoff
            min_ls, max_ls = self._min_ls, self._max_ls
            grads_of = make_grads(scaled=True)

            def step_fn(pvals, avals, state, key, t, lr, rescale, gstate,
                        xv, yv):
                ls, good = gstate
                grads, lval, new_avals = grads_of(pvals, avals, key, xv,
                                                  yv, ls)
                finite = jnp.isfinite(lval)
                for g in jax.tree.leaves(grads):
                    finite = jnp.logical_and(finite,
                                             jnp.all(jnp.isfinite(g)))
                new_pvals, new_state = run_update(
                    pvals, grads, state, t, lr, rescale / ls)

                def keep(new, old):
                    return jnp.where(finite, new, old)

                new_pvals = [keep(n, o) for n, o in zip(new_pvals, pvals)]
                new_state = jax.tree.map(keep, new_state, state)
                new_avals = [keep(n, o) for n, o in zip(new_avals, avals)]
                if dyn:
                    good = jnp.where(finite, good + 1, 0)
                    grow = jnp.logical_and(finite, good >= growth_n)
                    new_ls = jnp.where(
                        grow, jnp.minimum(ls * 2.0, max_ls),
                        jnp.where(finite, ls,
                                  jnp.maximum(ls * backoff, min_ls)))
                    good = jnp.where(grow, jnp.zeros_like(good), good)
                else:
                    new_ls = ls
                    good = jnp.where(finite, good + 1, 0)
                return (new_pvals, new_avals, new_state, lval,
                        (new_ls, good), finite)

            self._jit_step = jax.jit(
                step_fn,
                in_shardings=(self._p_sh, self._a_sh, self._s_sh,
                              self._r_sh, self._r_sh, self._r_sh,
                              self._r_sh, (self._r_sh, self._r_sh),
                              self._x_sh, self._y_sh),
                out_shardings=(self._p_sh, self._a_sh, self._s_sh,
                               self._r_sh, (self._r_sh, self._r_sh),
                               self._r_sh),
                donate_argnums=(0, 1, 2))
            if self._gstate is None:
                self._gstate = (
                    jax.device_put(jnp.asarray(self._init_ls, jnp.float32),
                                   self._r_sh),
                    jax.device_put(jnp.asarray(0, jnp.int32), self._r_sh))

        def fwd_fn(pvals, avals, key, xv):
            out, _, _ = apply_fn(pvals, avals, key, xv, False)
            if isinstance(out, (list, tuple)):
                return tuple(o._read() for o in out)
            return out._read()

        self._jit_fwd = jax.jit(
            fwd_fn, in_shardings=(self._p_sh, self._a_sh,
                                  self._r_sh, self._x_sh))

    # -- public API --------------------------------------------------------
    @property
    def mesh(self):
        return self._mesh

    @property
    def optimizer(self):
        return self._optimizer

    @property
    def built(self) -> bool:
        """True once the first step() has built the jit and taken
        ownership of the weights."""
        return self._built

    @property
    def num_update(self) -> int:
        """The optimizer update counter (steps taken / restored)."""
        return self._t

    @property
    def guard_enabled(self) -> bool:
        return self._guard

    @property
    def zero_stage(self) -> int:
        """ZeRO optimizer-state partitioning stage (0, 1 or 2)."""
        return self._zero

    @property
    def accum_steps(self) -> int:
        """Microbatches per step (1 = no accumulation)."""
        return self._accum

    @property
    def dp_size(self) -> int:
        """Size of the mesh's 'dp' axis (1 before the first build only
        if the mesh has no dp axis)."""
        return axis_size(self._mesh, "dp")

    @property
    def comm_bucket_mb(self) -> float:
        """Gradient-reduction bucket cap in MB (0 = one fused
        reduction, the pre-bucketing trace)."""
        return self._bucket_mb

    @property
    def grad_buckets(self):
        """The live bucket partition (index lists in reverse parameter
        order), or None on the fused path.  Introspection only."""
        return None if self._grad_buckets is None \
            else [list(b) for b in self._grad_buckets]

    def set_comm_bucket_mb(self, mb: float) -> None:
        """Change the communication bucket cap on a live trainer — the
        CommBucketController's apply target.  Rebuilds the jitted step
        (a recompile) only when the cap actually changes the bucket
        PARTITION; a cap move that lands on the same partition is
        free.  Training state is untouched (the jit closes over
        shardings, not values)."""
        mb = float(mb or 0.0)
        if mb < 0:
            # same contract as the constructor: a negative cap is a
            # caller bug, not a request to turn bucketing off
            raise MXNetError(
                f"comm_bucket_mb must be >= 0 (0 = one fused "
                f"reduction), got {mb!r}")
        if mb == self._bucket_mb:
            return
        self._bucket_mb = mb
        if not self._built:
            return
        cap = mb * 2 ** 20 if mb else 0
        dense_i = [i for i in range(len(self._pvals))
                   if i not in self._sparse_marked]
        bks = comm_buckets([int(self._pvals[i].nbytes) for i in dense_i],
                           cap)
        bks = [[dense_i[j] for j in b] for b in bks]
        new = bks if len(bks) > 1 else None
        if new == self._grad_buckets:
            return
        self._build_jits()

    def _bytes_per_device(self, what: str, arrays) -> dict:
        """``arrays``: a callable giving the arrays, read once built."""
        if not self._built:
            raise MXNetError(f"run at least one step() before {what}()")
        out: dict = {}
        for leaf in arrays():
            for sh in leaf.addressable_shards:
                d = sh.device.id
                out[d] = out.get(d, 0) + int(sh.data.nbytes)
        return out

    def opt_state_bytes_per_device(self) -> dict:
        """Actually-resident optimizer-state bytes per device id — the
        ZeRO acceptance metric.  At stage 0 every chip carries the full
        state; at stage >= 1 each chip carries ~1/dp of every
        partitionable tensor."""
        import jax
        return self._bytes_per_device(
            "opt_state_bytes_per_device",
            lambda: jax.tree.leaves(self._state))

    def param_bytes_per_device(self) -> dict:
        """Actually-resident parameter (and aux) bytes per device id:
        which devices hold the weights the trainer owns, and how much of
        them each."""
        return self._bytes_per_device(
            "param_bytes_per_device", lambda: self._pvals + self._avals)

    def peak_opt_state_bytes(self) -> int:
        """max over devices of :meth:`opt_state_bytes_per_device`."""
        per_dev = self.opt_state_bytes_per_device()
        return max(per_dev.values()) if per_dev else 0

    def table_bytes_per_device(self) -> dict:
        """Actually-resident embedding-table bytes per device id, over
        the ROW-SHARDED tables (RowShardedEmbedding) — the dp-sharded
        table acceptance metric, sibling of
        :meth:`opt_state_bytes_per_device`."""
        return self._bytes_per_device(
            "table_bytes_per_device",
            lambda: [v for p, v in zip(self._train_params, self._pvals)
                     if getattr(p, "_row_shard_axis", None) is not None])

    def peak_table_bytes(self) -> int:
        """max over devices of :meth:`table_bytes_per_device` — what one
        chip actually holds of the row-sharded tables (``vocab/dp``
        rows each when the shard formed, the full table on fallback)."""
        per_dev = self.table_bytes_per_device()
        return max(per_dev.values()) if per_dev else 0

    def reshard(self, mesh=None) -> None:
        """Rebuild shardings and the jitted step on ``mesh`` and
        re-place the live training state onto the new layout.  A
        ``mesh`` equal to the current one (or None) is a no-op on a
        built trainer — safe to call unconditionally after a fleet
        re-form.  This is the in-graph re-shard hook the elastic
        fleet uses after a re-form changes the dp world size, and what
        makes a checkpoint saved at one dp size restorable at another
        (load_checkpoint builds its restore template from the CURRENT
        shardings, so a re-sharded trainer restores any layout).

        Fleet-synchronized like a collective: every host must reshard
        together (the rebuilt step's collectives span the new mesh), so
        the collective-safety lint rule keeps it off rank-divergent
        branches.  Unbuilt trainers just adopt the mesh — the first
        step builds everything on it."""
        unchanged = mesh is None or mesh == self._mesh
        if mesh is not None:
            self._mesh = mesh
        if not self._built or unchanged:
            # identical mesh = identical layout: skip the full state
            # host round-trip and jit rebuild.  The elastic re-form
            # hook calls reshard() unconditionally after every re-form;
            # on host-local meshes (each process owns its devices) the
            # local mesh survives a peer's death unchanged, and paying
            # a recompile for a bit-identical layout would only stretch
            # the re-form timeline
            return
        import jax
        host = jax.device_get({
            "p": list(self._pvals), "a": list(self._avals),
            "s": self._state,
            "g": list(self._gstate) if self._gstate is not None else None,
        })
        self._make_shardings()
        self._s_sh = self._state_shardings(host["s"])
        self._pvals = [jax.device_put(v, s)
                       for v, s in zip(host["p"], self._p_sh)]
        self._avals = [jax.device_put(v, s)
                       for v, s in zip(host["a"], self._a_sh)]
        self._state = jax.tree.map(
            lambda v, s: jax.device_put(v, s), host["s"], self._s_sh)
        if host["g"] is not None:
            self._gstate = tuple(jax.device_put(v, self._r_sh)
                                 for v in host["g"])
        self._build_jits()

    @property
    def last_step_finite(self):
        """Device bool from the last guarded step: False means the update
        was skipped (non-finite loss/grads).  None before the first
        guarded step or with the guard off.  Reading it with bool()/
        device_get syncs — the resilience layer batches these."""
        return self._last_finite

    @property
    def loss_scale(self) -> float:
        """Current (dynamic) loss scale; 1.0 unless the guard was enabled
        with dynamic_loss_scale.  Syncs the device scalar."""
        if self._gstate is None:
            return self._init_ls if self._guard else 1.0
        import jax
        return float(jax.device_get(self._gstate[0]))

    @property
    def learning_rate(self) -> float:
        return self._optimizer.learning_rate

    def set_learning_rate(self, lr: float) -> None:
        self._optimizer.set_learning_rate(lr)

    def shard_batch(self, x, y):
        """Pre-place a batch onto the mesh with the trainer's input
        shardings; feeding the returned arrays to step() skips the
        host→device transfer (how a real input pipeline should feed)."""
        import jax
        xv = _to_vals(x)
        yv = _to_val(y)
        self._ensure_built(xv, yv)
        xs = tuple(jax.device_put(v, s)
                   for v, s in zip(xv, self._x_sh))
        if self._y_multi:
            ys = tuple(jax.device_put(v, s)
                       for v, s in zip(yv, self._y_sh))
        else:
            ys = jax.device_put(yv, self._y_sh)
        return (xs if len(xs) > 1 else xs[0], ys)

    def place_batch(self, batch):
        """Sharding-aware device placement for ONE loader batch — the
        DataLoader device-prefetch stage's ``put_fn``
        (``loader.set_device_put_fn(trainer.place_batch)``; the
        ResilientTrainer wires this automatically for an attached
        loader).  A ``(x, y)`` pair routes through :meth:`shard_batch`
        (building the trainer on first use); any other batch shape
        falls back to leaf-wise default-device placement, so a loader
        that yields something this trainer cannot shard still
        double-buffers plain transfers."""
        if isinstance(batch, (tuple, list)) and len(batch) == 2:
            return self.shard_batch(batch[0], batch[1])
        from ..gluon.data.dataloader import default_device_put
        return default_device_put(batch)

    @hot_path("step")
    def step(self, x, y, batch_size: Optional[int] = None):
        """Run one sharded train step; returns the (device) mean loss.
        `x` may be a single array or a tuple of inputs.

        In a profiler's trace the call is the step ``mx.train`` with its
        number, and its host work lies in three spans (histograms of the
        same names, in microseconds): ``trainer.to_vals_us`` (inputs to
        values, the checks, the step's RNG key), ``trainer.h2d_us`` (the
        ``device_put``s: the batch, the key, the three scalars) and
        ``trainer.jit_call_us`` (the compiled step's call until it returns,
        not waited for).

        The trainer owns the executable it calls (:meth:`_jit_call`): the
        first step, and the first with another batch shape or dtype, traces,
        lowers and compiles the program here, once, under the spans
        ``trainer.step_trace`` / ``.step_lower`` / ``.step_compile``
        (``trainer.compile_call_s`` is that whole call,
        ``trainer.trace_lower_s`` its first two parts).  What the compiled
        step holds in temporaries on a device is in the gauge
        ``trainer.step_temp_bytes``, and ``mx.profiler.step_scopes()`` says
        which scope of the program each of its device instructions belongs
        to.

        The RNG key and the three scalars are placed as the executable was
        compiled to take them (replicated over the mesh), as the batch is:
        a ``Compiled`` refuses an argument committed elsewhere (a key set
        through ``mx.random.set_state`` from a checkpoint, say) where
        ``jax.jit`` moved it."""
        import jax
        if not self._built:
            self._ensure_built(_to_vals(x), _to_val(y))
        with jax.profiler.StepTraceAnnotation("mx.train",
                                              step_num=self._t + 1):
            with _span("trainer.to_vals_us"):
                xv = _to_vals(x)
                yv = _to_val(y)
                if len(xv) != len(self._x_sh):
                    raise MXNetError(
                        f"step() got {len(xv)} inputs but the trainer was "
                        f"built with {len(self._x_sh)} — optional inputs "
                        f"must be passed consistently from the first call")
                if isinstance(yv, tuple) != self._y_multi or \
                        (self._y_multi and len(yv) != len(self._y_sh)):
                    want = (f"a tuple of {len(self._y_sh)} label streams"
                            if self._y_multi else "a single label array")
                    raise MXNetError(
                        f"step() label structure changed: the trainer was "
                        f"built with {want} — labels must keep the first "
                        f"call's shape")
                if self._accum > 1 and int(xv[0].shape[0]) % self._accum:
                    raise MXNetError(
                        f"step() batch of {int(xv[0].shape[0])} does not "
                        f"divide into accum_steps={self._accum} "
                        f"microbatches — pad the batch or change "
                        f"accum_steps")
                if batch_size is None:
                    batch_size = int(xv[0].shape[0])
                self._t += 1
                self._optimizer.num_update = self._t
                key = _grandom.next_key()
            with _span("trainer.h2d_us"):
                xv = tuple(jax.device_put(v, s)
                           for v, s in zip(xv, self._x_sh))
                if self._y_multi:
                    yv = tuple(jax.device_put(v, s)
                               for v, s in zip(yv, self._y_sh))
                else:
                    yv = jax.device_put(yv, self._y_sh)
                key, t, lr, rescale = jax.device_put(
                    (key, _np.int32(self._t),
                     _np.float32(self._optimizer.learning_rate),
                     _np.float32(self._scale / batch_size)), self._r_sh)
            if self._guard:
                (self._pvals, self._avals, self._state, lval, self._gstate,
                 self._last_finite) = self._jit_call(
                    self._jit_step, self._pvals, self._avals, self._state,
                    key, t, lr, rescale, self._gstate, xv, yv, batch=2)
            else:
                self._pvals, self._avals, self._state, lval = \
                    self._jit_call(
                        self._jit_step, self._pvals, self._avals,
                        self._state, key, t, lr, rescale, xv, yv, batch=2)
        if self._sparse_trace_info:
            self._record_sparse_metrics()
        return NDArray(lval, ctx=self._ctx)

    def _jit_call(self, fn, *args, batch: int):
        """``fn(*args)`` through the executable the trainer owns, the call
        un-waited.  ``fn`` is one of the trainer's jitted functions and the
        last ``batch`` arguments are the batch; the table
        ``{(fn, the batch's shapes and dtypes): jax.stages.Compiled}`` is
        what ``jax.jit`` kept out of sight.

        A hit is the ``Compiled`` called under the span
        ``trainer.jit_call_us``.  A miss (the first call, a new batch shape
        or dtype, the first call after the jits were rebuilt) builds the
        program here: ``fn.trace(*args)``, ``.lower()``, ``.compile()``,
        each under a span of its own (``trainer.step_trace``,
        ``.step_lower``, ``.step_compile``: on the profiler's clock, so a
        build inside a traced window owns its idle gap).  Such a call is
        kept out of the ``trainer.jit_call_us`` histogram: its whole
        seconds go to ``trainer.compile_call_s`` /
        ``trainer.compile_calls``, the part no cache saves to
        ``trainer.trace_lower_s``, and the gauge ``trainer.compile_step``
        keeps the number of the last step that did it.  Where the train
        step was built, :func:`_publish_step` says what it holds in memory
        and hands the executable to ``mx.profiler.step_scopes()``."""
        import jax
        m = self._dispatch_metrics
        key = (fn, tuple((v.shape, v.dtype)
                         for v in jax.tree.leaves(args[-batch:])))
        compiled = self._compiled.get(key)
        if compiled is not None:
            with _span("trainer.jit_call_us", histogram=False,
                       args={"step_num": self._t}) as sp:
                out = compiled(*args)
            m.jit_call_us.observe(sp.duration_us)
            return out
        which = {"step_num": self._t}
        with _span("trainer.jit_call_us", histogram=False, args=which) as sp:
            with _span("trainer.step_trace", histogram=False,
                       args=which) as traced:
                stage = fn.trace(*args)
            with _span("trainer.step_lower", histogram=False,
                       args=which) as lowered:
                stage = stage.lower()
            with _span("trainer.step_compile", histogram=False, args=which):
                compiled = stage.compile()
            self._compiled[key] = compiled
            if fn is self._jit_step:
                _publish_step(compiled, self)
            out = compiled(*args)
        m.compile_calls.inc()
        m.compile_call_s.inc(sp.duration_us / 1e6)
        m.trace_lower_s.inc((traced.duration_us + lowered.duration_us) / 1e6)
        m.compile_step.set(self._t)
        return out

    def _record_sparse_metrics(self) -> None:
        """Host-side sparse.* metrics from the last trace's probe record
        — static shapes only, no device sync.  ``exchange_bytes`` counts
        what the sparse layout PUTS ON THE WIRE per step (ids + rows,
        once per dp peer pair is XLA's business; we count the logical
        payload), vs the dense table-sized reduction it replaced."""
        reg = _metrics_registry()
        rows = buckets_b = dense_b = 0
        vocab_sum = 0
        for i, (bucket, vocab) in self._sparse_trace_info.items():
            v = self._pvals[i]
            width = int(v.shape[1])
            item = int(_np.dtype(v.dtype).itemsize)
            # the pow2 bucket can exceed a tiny vocab; a table never
            # carries more live rows than it has
            rows += min(bucket, vocab)
            buckets_b += bucket * (4 + width * item)
            dense_b += vocab * width * item
            vocab_sum += vocab
        reg.counter(
            "sparse.grad_rows",
            "embedding rows carried by row-sparse gradients").inc(rows)
        if self._dp > 1:
            reg.counter(
                "sparse.exchange_bytes",
                "bytes of (ids, rows) row-sparse gradient payload "
                "exchanged instead of dense table reductions").inc(
                    buckets_b)
            reg.counter(
                "sparse.exchange_bytes_dense_equiv",
                "bytes the SAME gradients would have cost as dense "
                "reductions — the wire win denominator").inc(dense_b)
        if vocab_sum:
            reg.gauge(
                "sparse.grad_density",
                "id-bucket rows / vocab across sparse tables (last "
                "step)").set(rows / vocab_sum)

    def aux_values(self) -> dict:
        """``{parameter name: host array}`` of the non-gradient buffers as
        the last step left them (a BatchNorm's running statistics, an
        expert layer's load).  Waits for that step."""
        import jax
        return dict(zip((p.name for p in self._aux_params),
                        jax.device_get(list(self._avals))))

    # -- supervised-retry support (ResilientTrainer) -----------------------
    def step_state(self):
        """Host-side snapshot of everything a FAILED step() attempt may
        have advanced before dying: the update counter and the global RNG
        stream key.  Cheap (two references); taken by the resilience
        layer before every supervised attempt so a mid-step failure can
        be rolled back instead of desyncing the retry (ROADMAP 'Known
        gap' from PR 1)."""
        return (self._t, _grandom.get_state())

    @property
    def donation_consumed(self) -> bool:
        """True once a failed jitted step has consumed (deleted) the
        donated parameter buffers: the training state no longer exists on
        device, so a retry cannot run — restore from a checkpoint
        instead.  Always False before the first build and on backends
        that ignore donation (CPU)."""
        if not self._built:
            return False
        for v in self._pvals:
            is_deleted = getattr(v, "is_deleted", None)
            if is_deleted is not None and is_deleted():
                return True
        return False

    def rollback_step(self, state) -> None:
        """Undo the host-side effects of a failed step() attempt —
        restore the update counter and RNG stream from a
        :meth:`step_state` snapshot so the retry replays the attempt
        bit-for-bit.  Refuses (clear error, not a crash later) when the
        failed attempt already consumed its donated buffers."""
        if self.donation_consumed:
            raise MXNetError(
                "cannot roll back this step: the failed attempt already "
                "consumed (donated) the parameter buffers — the training "
                "state is gone; restore from the newest committed "
                "checkpoint (ResilientTrainer auto_resume) instead of "
                "retrying")
        t, key = state
        self._t = t
        self._optimizer.num_update = t
        _grandom.set_state(key)

    def forward(self, x):
        """Sharded inference forward with the trainer-owned weights (the
        same three spans as :meth:`step`)."""
        import jax
        if not self._built:
            raise MXNetError("run at least one step() before forward(), or "
                             "use the block directly")
        with _span("trainer.to_vals_us"):
            xv = _to_vals(x)
            if len(xv) != len(self._x_sh):
                raise MXNetError(
                    f"forward() got {len(xv)} inputs but the trainer was "
                    f"built with {len(self._x_sh)}")
            key = _grandom.next_key()
        with _span("trainer.h2d_us"):
            xv = tuple(jax.device_put(v, s)
                       for v, s in zip(xv, self._x_sh))
            key = jax.device_put(key, self._r_sh)
        out = self._jit_call(self._jit_fwd, self._pvals, self._avals, key,
                             xv, batch=1)
        if isinstance(out, tuple):
            return tuple(NDArray(o, ctx=self._ctx) for o in out)
        return NDArray(out, ctx=self._ctx)

    def trace_step(self, x, y):
        """The train step for this batch, traced (``jax.stages.Traced``)
        against the live state without running or donating it: its
        ``.jaxpr`` is where a check reads what the backward makes again
        (a ``pallas_call`` under a rematerialised block's ``checkpoint``),
        its ``.lower()`` is ``lower_step``."""
        import jax
        import jax.numpy as jnp
        xv, yv = self.shard_batch(x, y)
        if not isinstance(xv, tuple):
            xv = (xv,)
        scalars = (jax.random.PRNGKey(0), jnp.asarray(1, jnp.int32),
                   jnp.asarray(0.0, jnp.float32),
                   jnp.asarray(1.0, jnp.float32))
        guard = (self._gstate,) if self._guard else ()
        return self._jit_step.trace(
            self._pvals, self._avals, self._state, *scalars, *guard,
            xv, yv)

    def lower_step(self, x, y):
        """The train step for this batch, lowered (``jax.stages.Lowered``)
        against the live state without running or donating it: a second
        lowering beside the one :meth:`step` made, for a check that needs
        the text of a step the trainer has not run (``.compile().as_text()``
        shows which kernels, ``tpu_custom_call``, and collectives,
        ``reduce-scatter``, ``all-gather``, it carries), or the StableHLO.
        A step that holds a Pallas kernel is keyed anew at every lowering,
        so that compile misses the persistent cache.  What the step that
        *runs* holds in temporaries is published where it was compiled
        (the gauge ``trainer.step_temp_bytes``, from its
        ``memory_analysis()``), and ``mx.profiler.step_scopes()`` reads its
        text."""
        return self.trace_step(x, y).lower()

    def _checkpointer(self):
        # one long-lived async checkpointer: save() returns once the
        # arrays are snapshotted and the write overlaps training; call
        # wait_checkpoint() (or let process exit paths flush) to block.
        #
        # Multi-process groups get explicit MultiprocessingOptions:
        # orbax's default process sync is a DEVICE collective
        # (sync_global_devices), which the multi-process CPU backend
        # cannot run at all and which, on any backend, spans the FULL
        # launcher world — a dead host would wedge every later save.
        # Passing active_processes routes every orbax barrier through
        # the coordination service over the ACTIVE member set (the same
        # tiering dist.py uses), and each host is its own primary
        # because checkpoint directories are per-host in this stack
        # (ResilientTrainer's per-rank layout): every host writes its
        # own commit metadata.  Rebuilt whenever a fleet re-form
        # changes the member set — the old instance's barrier set
        # still contains the dead host.
        from . import dist
        members = tuple(dist.active_members()) \
            if dist.is_initialized() else None
        if getattr(self, "_ckptr", None) is not None and \
                getattr(self, "_ckptr_members", None) != members:
            try:
                self._ckptr.wait_until_finished()
            except Exception:   # noqa: BLE001 — an in-flight write
                pass            # racing a re-form is abandoned; resume
            self._ckptr = None  # only ever reads COMMITTED checkpoints
        if getattr(self, "_ckptr", None) is None:
            import orbax.checkpoint as ocp
            if members is not None and len(members) > 1:
                mp = ocp.options.MultiprocessingOptions(
                    primary_host=dist.phys_rank(),
                    active_processes=set(members),
                    barrier_sync_key_prefix=(
                        f"mxtpu_f{dist.fence_generation()}"))
                self._ckptr = ocp.StandardCheckpointer(
                    multiprocessing_options=mp)
            else:
                self._ckptr = ocp.StandardCheckpointer()
            self._ckptr_members = members
        return self._ckptr

    def _ckpt_inflight_gauge(self):
        return _metrics_registry().gauge(
            "resilience.ckpt_inflight",
            help="async checkpoint writes enqueued but not yet "
                 "committed (0 or 1 — one orbax checkpointer per "
                 "trainer process)")

    def wait_checkpoint(self) -> None:
        """Block until any in-flight async checkpoint write commits
        (the orbax writer AND the host-local npz commit thread)."""
        self._wait_host_local()
        if getattr(self, "_ckptr", None) is not None:
            self._ckptr.wait_until_finished()
            self._ckpt_inflight_gauge().set(0)

    def _join_host_local(self) -> None:
        """Drain the background npz commit thread WITHOUT raising —
        the step-path variant: a periodic save must be able to start
        its own write after a failed predecessor (the previous
        committed dir is intact; that is the whole crash contract).
        The stored error stays armed for the next explicit flush."""
        th, self._hl_writer = self._hl_writer, None
        if th is not None:
            th.join()
            self._ckpt_inflight_gauge().set(0)

    def _wait_host_local(self) -> None:
        """Join the background npz commit thread (MXTPU_ASYNC_CKPT)
        and surface its failure, if any, HERE — the same contract as
        orbax's wait_until_finished: the write path never raises into
        the training step, only into the explicit flush."""
        self._join_host_local()
        err, self._hl_error = self._hl_error, None
        if err is not None:
            raise MXNetError(
                f"async host-local checkpoint write failed: "
                f"{err!r}") from err

    def _host_local_checkpoint(self) -> bool:
        """True when this trainer's state must be saved as HOST values:
        a multi-process group whose mesh is local to this host (each
        process trains its own replica — the elastic-fleet CPU layout).
        Orbax refuses to serialize such 'host-local' jax arrays, and
        they carry no cross-host sharding worth preserving anyway.  A
        mesh that genuinely spans processes (TPU pod) keeps the sharded
        orbax path.  ``self.host_local_ckpt`` (a plain attribute)
        overrides the auto-detection either way — how the torn-dir
        tests exercise the npz writer in one process."""
        if self.host_local_ckpt is not None:
            return bool(self.host_local_ckpt)
        from . import dist
        if not dist.is_initialized():
            return False
        import jax
        if jax.process_count() <= 1:
            return False
        local = set(jax.local_devices())
        return all(d in local for d in self._mesh.devices.flat)

    def save_checkpoint(self, directory: str) -> None:
        """Write the trainer-owned SHARDED state (params, aux, optimizer
        state, update counter, RNG stream) with orbax — the §5.4
        'async-writes internally' story for multi-chip training.  Each
        host writes its own shards; the write is ASYNC and lands in a
        step-suffixed subdir, so a crash mid-save never destroys the
        previous checkpoint."""
        import os
        if not self._built:
            raise MXNetError("run at least one step() before "
                             "save_checkpoint()")
        directory = os.path.abspath(directory)
        tree = {"params": list(self._pvals),
                "aux": list(self._avals),
                "opt_state": self._state,
                "rng": _grandom.get_state(),
                "t": self._t}
        if self._guard and self._gstate is not None:
            # loss scale + clean-step counter ride along so a resumed run
            # replays the dynamic-scale trajectory bit-for-bit
            tree["guard"] = list(self._gstate)
        if self._host_local_checkpoint():
            # _save_host_local owns the inflight gauge on the async
            # path (set to 1 before its thread starts — no race with
            # the thread's own set(0)); synchronous writes are
            # committed by the time it returns
            if not self._save_host_local(directory, tree):
                self._ckpt_inflight_gauge().set(0)
            return
        self._checkpointer().save(
            os.path.join(directory, f"state-{self._t:08d}"), tree,
            force=True)
        # the write overlaps training from here until the next
        # wait_checkpoint() — the ROADMAP's checkpoint-in-flight gauge
        self._ckpt_inflight_gauge().set(1)

    _HOST_LOCAL_NPZ = "host_local.npz"

    def _save_host_local(self, directory: str, tree: dict) -> bool:
        """Per-host atomic checkpoint for multi-process groups whose
        mesh is host-local: orbax refuses to serialize host-local jax
        arrays, and its replicated-numpy handler writes on GLOBAL
        process 0 only — neither fits a fleet of independent per-host
        replicas.  This path writes the host's full state itself (npz
        into a tmp dir, commit marker, atomic rename), producing
        exactly the committed-dir shape ``committed_checkpoints`` /
        ``latest_checkpoint`` already filter on.  Barrier-free by
        design: per-host independence is the elastic-fleet story — no
        cross-host coordination can wedge this save when a peer is
        dead.

        Synchronous by default.  With ``MXTPU_ASYNC_CKPT`` the
        device_get SNAPSHOT still happens here, at the step boundary
        (the next donated step invalidates these buffers), but the npz
        serialization + commit rename — the part whose cost scales
        with model size — move to a background thread; the boundary
        stall shrinks to the host copy.  A crash mid-write leaves the
        tmp dir uncommitted (no marker, no rename), which resume
        already filters out, so the previous committed ``state-<t>``
        always survives.  Returns True when the write went async."""
        import os
        import jax
        flat = {f"p{i}": v for i, v in enumerate(tree["params"])}
        flat.update({f"a{i}": v for i, v in enumerate(tree["aux"])})
        flat.update({f"s{i}": v for i, v in
                     enumerate(jax.tree.leaves(tree["opt_state"]))})
        flat["rng"] = tree["rng"]
        flat["t"] = tree["t"]
        if "guard" in tree:
            flat.update({f"g{i}": v for i, v in enumerate(tree["guard"])})
        flat = jax.device_get(flat)          # the boundary snapshot
        final = os.path.join(directory, f"state-{self._t:08d}")
        tmp = f"{final}.mxtpu-tmp-{os.getpid()}"
        if not bool(get_env("MXTPU_ASYNC_CKPT")):
            self._write_host_local(flat, tmp, final)
            return False
        # one write in flight at a time (the orbax contract): a second
        # save first drains the previous commit — without raising (a
        # failed predecessor must not abort the step-path save that
        # replaces it; its error stays armed for the explicit flush)
        self._join_host_local()
        hist = _metrics_registry().histogram(
            "ckpt.async_commit_us",
            help="background npz checkpoint commit time (serialize + "
                 "marker + atomic rename) — the write the async path "
                 "takes OFF the step boundary")

        def commit():
            t0 = _time.perf_counter()
            try:
                self._write_host_local(flat, tmp, final)
                hist.observe((_time.perf_counter() - t0) * 1e6)
            except BaseException as exc:   # noqa: BLE001 — re-raised
                self._hl_error = exc       # by the next wait_checkpoint
            finally:
                self._ckpt_inflight_gauge().set(0)

        th = _threading.Thread(target=commit, name="mxtpu-ckpt-writer",
                               daemon=True)
        self._hl_writer = th
        # gauge up BEFORE the thread starts: a fast commit's set(0)
        # must never be overwritten by a caller-side set(1) racing it
        self._ckpt_inflight_gauge().set(1)
        th.start()
        return True

    @staticmethod
    def _write_host_local(flat: dict, tmp: str, final: str) -> None:
        """The commit sequence: npz into the tmp dir, marker, atomic
        rename.  Interruptible at any point without losing the
        previous committed dir — the marker is written only after the
        full npz, and the rename is the single commit point."""
        import os
        import shutil
        import numpy as _nnp
        os.makedirs(tmp, exist_ok=True)
        _nnp.savez(os.path.join(tmp, ShardedTrainer._HOST_LOCAL_NPZ),
                   **flat)
        with open(os.path.join(tmp, _COMMIT_MARKER), "w") as f:
            f.write("mxtpu host-local checkpoint\n")
        if os.path.isdir(final):
            shutil.rmtree(final, ignore_errors=True)
        os.replace(tmp, final)

    def _load_host_local(self, path: str) -> None:
        """Restore a :meth:`_save_host_local` checkpoint onto this
        trainer's shardings."""
        import os
        import jax
        import jax.numpy as jnp
        import numpy as _nnp
        data = _nnp.load(os.path.join(path, self._HOST_LOCAL_NPZ))
        self._pvals = [jax.device_put(data[f"p{i}"], s)
                       for i, s in enumerate(self._p_sh)]
        self._avals = [jax.device_put(data[f"a{i}"], s)
                       for i, s in enumerate(self._a_sh)]
        s_flat, s_def = jax.tree.flatten(self._state)
        sh_flat = jax.tree.leaves(self._s_sh)
        self._state = jax.tree.unflatten(
            s_def, [jax.device_put(data[f"s{i}"], sh)
                    for i, sh in enumerate(sh_flat[:len(s_flat)])])
        _grandom.set_state(jnp.asarray(data["rng"]))
        self._t = int(data["t"])
        self._optimizer.num_update = self._t
        if "g0" in data and self._guard:
            self._gstate = tuple(
                jax.device_put(jnp.asarray(data[f"g{i}"]), self._r_sh)
                for i in range(2))

    @staticmethod
    def committed_checkpoints(directory: str) -> List[str]:
        """Sorted (oldest → newest) step dirs under ``directory`` that
        orbax fully COMMITTED.  Two filters, both load-bearing for crash
        safety: the name must be exactly ``state-<digits>`` (orbax's
        ``*.orbax-checkpoint-tmp-*`` rename staging also starts with
        ``state-`` and sorts NEWER than its target), and the commit
        marker file must exist (covers torn writes on filesystems where
        the rename is not atomic)."""
        import os
        if not os.path.isdir(directory):
            return []
        steps = []
        for d in os.listdir(directory):
            if not _STEP_DIR_RE.match(d):
                continue
            if not os.path.exists(os.path.join(directory, d,
                                               _COMMIT_MARKER)):
                continue
            steps.append(d)
        return [os.path.join(directory, d) for d in sorted(steps)]

    @staticmethod
    def latest_checkpoint(directory: str):
        """Newest COMMITTED step dir under ``directory`` (or None).  A
        crash mid-async-write leaves a partial dir behind; it is skipped
        and the next-older committed checkpoint wins."""
        steps = ShardedTrainer.committed_checkpoints(directory)
        return steps[-1] if steps else None

    def load_checkpoint(self, directory: str) -> None:
        """Restore the NEWEST checkpoint under ``directory`` directly
        into the trainer's shardings (arrays land on their mesh
        positions — no host round-trip).  The trainer must be built with
        the same model/mesh/rules (run one step on dummy data first, as
        the reference's bind-then-load flow does)."""
        import orbax.checkpoint as ocp   # noqa: F401  (orbax presence)
        if not self._built:
            raise MXNetError("build the trainer (one step on dummy data) "
                             "before load_checkpoint()")
        import jax
        path = self.latest_checkpoint(directory)
        if path is None:
            raise MXNetError(f"no checkpoint under {directory!r}")
        self.wait_checkpoint()
        import os
        if os.path.exists(os.path.join(path, self._HOST_LOCAL_NPZ)):
            # written by _save_host_local (per-host multi-process
            # checkpoint) — restore without orbax
            self._load_host_local(path)
            return
        rng_now = _grandom.get_state()
        if rng_now is None:              # seed the stream so the
            _grandom.next_key()          # template has a concrete leaf
            rng_now = _grandom.get_state()
        template = {
            "params": [jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=s)
                       for v, s in zip(self._pvals, self._p_sh)],
            "aux": [jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=s)
                    for v, s in zip(self._avals, self._a_sh)],
            "opt_state": jax.tree.map(
                lambda v, s: jax.ShapeDtypeStruct(v.shape, v.dtype,
                                                  sharding=s),
                self._state, self._s_sh),
            "rng": rng_now,
            "t": 0,
        }
        # the template must match the SAVED tree exactly (orbax rejects
        # both extra and missing keys), so ask the checkpoint whether it
        # carries guard state rather than assuming this trainer's config:
        # guard-on trainers must restore guard-less checkpoints and vice
        # versa
        try:
            saved_has_guard = \
                "guard" in self._checkpointer().metadata(path)
        except Exception:   # noqa: BLE001 — metadata unavailable: fall
            # back to mirroring this trainer's own configuration
            saved_has_guard = self._guard and self._gstate is not None
        if saved_has_guard:
            import jax.numpy as jnp
            gs = self._gstate if self._gstate is not None else \
                (jnp.float32(1.0), jnp.int32(0))
            template["guard"] = [
                jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=self._r_sh)
                for v in gs]
        tree = self._checkpointer().restore(path, template)
        self._pvals = list(tree["params"])
        self._avals = list(tree["aux"])
        self._state = tree["opt_state"]
        _grandom.set_state(tree["rng"])
        self._t = int(tree["t"])
        self._optimizer.num_update = self._t
        if "guard" in tree and self._guard:
            self._gstate = tuple(tree["guard"])

    def sync_params(self) -> None:
        """Copy trainer-owned (sharded) weights back into the block's
        Parameters (gathered to the default device) — call before
        save_parameters/export."""
        import jax
        if not self._built:
            return   # pre-build, the block still owns the weights
        with _autograd.pause():
            for p, v in zip(self._train_params, self._pvals):
                p.data(self._ctx)._set_data(
                    _np_to_dev(jax.device_get(v), self._ctx))
            for p, v in zip(self._aux_params, self._avals):
                p.data(self._ctx)._set_data(
                    _np_to_dev(jax.device_get(v), self._ctx))


def _dispatch_metrics():
    """What :meth:`ShardedTrainer._jit_call` writes.  The process-wide
    ``compile.*`` counters (``tuning.compile_cache.watch_compiles``) are
    installed beside them; they fire only where jax compiles and nothing
    here reads them."""
    import types
    from ..tuning.compile_cache import watch_compiles
    watch_compiles()
    reg = _metrics_registry()
    return types.SimpleNamespace(
        jit_call_us=reg.histogram(
            "trainer.jit_call_us",
            help="the compiled step's call until it returns, un-waited; "
                 "calls that built a program are not in it"),
        compile_calls=reg.counter(
            "trainer.compile_calls",
            "trainer calls that traced, lowered and compiled a program"),
        compile_call_s=reg.counter(
            "trainer.compile_call_s", "seconds those calls took"),
        trace_lower_s=reg.counter(
            "trainer.trace_lower_s",
            "seconds of them spent tracing and lowering"),
        compile_step=reg.gauge(
            "trainer.compile_step",
            "number of the last step whose call compiled"))


def _publish_step(compiled, trainer) -> None:
    """The account of a train step just compiled: the temporaries it
    holds on a device in the gauge ``trainer.step_temp_bytes``, as
    ``memory_analysis()`` counts them (a cheap call; a loop's carry is in
    that sum twice, so it reads above what the buffer assignment allocates:
    8.64 GB for 7.61 in the SmallThinker cell, PERF.md PR 37), and the
    executable itself to ``mx.profiler.step_scopes()``, which reads its
    text when asked, or when ``trainer`` is gone."""
    from .. import profiler
    mem = compiled.memory_analysis()
    if mem is not None:
        _metrics_registry().gauge(
            "trainer.step_temp_bytes",
            "temporaries a device holds for the train step last compiled, "
            "as memory_analysis() counts them (loop carries twice)").set(
            mem.temp_size_in_bytes)
    profiler.publish_step(compiled, trainer)


def _np_to_dev(val, ctx):
    import jax.numpy as jnp
    return jnp.asarray(val)


def _to_val(y):
    """Normalize the label side.  A TUPLE means multiple label streams
    (e.g. BERT pretraining: mlm_labels, mlm_weights, nsp_labels) — each is
    normalized and the tuple preserved; a python LIST stays one array of
    values (reference mx.nd.array(list) semantics)."""
    import jax

    def one(v):
        if isinstance(v, NDArray):
            return v._read()
        if isinstance(v, jax.Array):
            return v
        # ingestion boundary: reached only for host data (lists /
        # np arrays); NDArray and jax.Array pass through above
        # mxlint: disable=hidden-host-sync — host-data ingestion
        return _np.asarray(v)

    if isinstance(y, tuple):
        return tuple(one(v) for v in y)
    return one(y)


def _to_vals(x):
    """Normalize a single array / NDArray or a tuple of them to a tuple of
    raw values.  jax.Arrays pass through untouched so pre-device_put batches
    skip the host round-trip (device_put on an already-correctly-sharded
    array is a no-op)."""
    import jax
    xs = x if isinstance(x, (tuple, list)) else (x,)
    return tuple(
        v._read() if isinstance(v, NDArray)
        # ingestion boundary: _np.asarray reached only for host data
        # mxlint: disable=hidden-host-sync — host-data ingestion
        else v if isinstance(v, jax.Array) else _np.asarray(v)
        for v in xs)
