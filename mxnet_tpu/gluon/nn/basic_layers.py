"""Core Gluon layers: Sequential, Dense, Dropout, norms, Embedding, etc.

Reference parity: python/mxnet/gluon/nn/basic_layers.py (SURVEY.md §2.5).
Parameter names, shapes ((units, in_units) weights), deferred init on first
forward, and layer defaults (BatchNorm eps=1e-5, momentum=0.9) follow the
reference.
"""
from __future__ import annotations

from typing import Optional

from ...base import MXNetError
from ... import autograd as _autograd
from ..block import Block, HybridBlock
from ..parameter import Parameter

__all__ = ["Sequential", "HybridSequential", "Dense", "Dropout", "BatchNorm",
           "InstanceNorm", "LayerNorm", "RMSNorm", "SwiGLU", "GroupNorm",
           "Embedding",
           "RowShardedEmbedding", "Flatten",
           "Lambda", "HybridLambda", "Activation", "LeakyReLU", "PReLU",
           "ELU", "SELU", "GELU", "Swish", "HybridConcurrent", "Identity",
           "ReflectionPad2D"]


def _prod(it):
    n = 1
    for s in it:
        n *= s
    return n


class Sequential(Block):
    """Eager container stacking blocks sequentially."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)

    def add(self, *blocks):
        for b in blocks:
            self.register_child(b)

    def forward(self, x):
        for child in self._children.values():
            x = child(x)
        return x

    def __len__(self):
        return len(self._children)

    def __getitem__(self, i):
        return list(self._children.values())[i]

    def __iter__(self):
        return iter(self._children.values())


class HybridSequential(HybridBlock):
    """Hybridizable container; lowers the whole stack into one jit."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)

    def add(self, *blocks):
        for b in blocks:
            self.register_child(b)

    def hybrid_forward(self, F, x):
        for child in self._children.values():
            x = child(x)
        return x

    def __len__(self):
        return len(self._children)

    def __getitem__(self, i):
        return list(self._children.values())[i]

    def __iter__(self):
        return iter(self._children.values())


class HybridConcurrent(HybridBlock):
    """Run children on the same input, concatenate outputs along ``axis``
    (reference: python/mxnet/gluon/contrib/nn/basic_layers.py
    HybridConcurrent — the Inception/DenseNet branch container)."""

    def __init__(self, axis=-1, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self.axis = axis

    def add(self, *blocks):
        for b in blocks:
            self.register_child(b)

    def hybrid_forward(self, F, x):
        outs = [child(x) for child in self._children.values()]
        return F.concat(*outs, dim=self.axis)


class Identity(HybridBlock):
    """Pass-through block (reference: gluon.contrib.nn.Identity)."""

    def hybrid_forward(self, F, x):
        return x


class Dense(HybridBlock):
    """Fully-connected layer: out = act(dot(x, W.T) + b); weight shape
    (units, in_units), MXNet convention."""

    def __init__(self, units, activation=None, use_bias=True, flatten=True,
                 dtype="float32", weight_initializer=None,
                 bias_initializer="zeros", in_units=0, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        self._units = units
        self._flatten = flatten
        self._act_type = activation
        with self.name_scope():
            self.weight = self.params.get(
                "weight", shape=(units, in_units), dtype=dtype,
                init=weight_initializer, allow_deferred_init=True)
            if use_bias:
                self.bias = self.params.get(
                    "bias", shape=(units,), dtype=dtype,
                    init=bias_initializer, allow_deferred_init=True)

    def infer_shape(self, x, *args):
        in_units = _prod(x.shape[1:]) if self._flatten else x.shape[-1]
        self.weight.shape = (self._units, in_units)

    def hybrid_forward(self, F, x, weight, bias=None):
        if bias is None:
            out = F.FullyConnected(x, weight, num_hidden=self._units,
                                   no_bias=True, flatten=self._flatten)
        else:
            out = F.FullyConnected(x, weight, bias, num_hidden=self._units,
                                   no_bias=False, flatten=self._flatten)
        if self._act_type:
            out = F.Activation(out, act_type=self._act_type)
        return out


class Dropout(HybridBlock):
    def __init__(self, rate, axes=(), prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._rate = rate
        self._axes = axes

    def hybrid_forward(self, F, x):
        return F.Dropout(x, p=self._rate, axes=self._axes)


class BatchNorm(HybridBlock):
    def __init__(self, axis=1, momentum=0.9, epsilon=1e-5, center=True,
                 scale=True, use_global_stats=False,
                 beta_initializer="zeros", gamma_initializer="ones",
                 running_mean_initializer="zeros",
                 running_variance_initializer="ones", in_channels=0,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._axis = axis
        self._momentum = momentum
        self._epsilon = epsilon
        self._center = center
        self._scale = scale
        self._use_global_stats = use_global_stats
        with self.name_scope():
            self.gamma = self.params.get(
                "gamma", shape=(in_channels,), init=gamma_initializer,
                allow_deferred_init=True,
                grad_req="write" if scale else "null")
            self.beta = self.params.get(
                "beta", shape=(in_channels,), init=beta_initializer,
                allow_deferred_init=True,
                grad_req="write" if center else "null")
            self.running_mean = self.params.get(
                "running_mean", shape=(in_channels,),
                init=running_mean_initializer, grad_req="null",
                allow_deferred_init=True)
            self.running_var = self.params.get(
                "running_var", shape=(in_channels,),
                init=running_variance_initializer, grad_req="null",
                allow_deferred_init=True)

    def infer_shape(self, x, *args):
        c = x.shape[self._axis]
        for p in (self.gamma, self.beta, self.running_mean, self.running_var):
            p.shape = (c,)

    def hybrid_forward(self, F, x, gamma, beta, running_mean, running_var):
        training = _autograd.is_training() and not self._use_global_stats
        outs = F.BatchNorm(x, gamma, beta, running_mean, running_var,
                           eps=self._epsilon, momentum=self._momentum,
                           fix_gamma=not self._scale,
                           use_global_stats=self._use_global_stats,
                           axis=self._axis, _training=training)
        out, new_mean, new_var = outs
        if training:
            with _autograd.pause():
                running_mean._set_data(new_mean._read())
                running_var._set_data(new_var._read())
        return out


class InstanceNorm(HybridBlock):
    def __init__(self, axis=1, epsilon=1e-5, center=True, scale=True,
                 beta_initializer="zeros", gamma_initializer="ones",
                 in_channels=0, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._epsilon = epsilon
        with self.name_scope():
            self.gamma = self.params.get("gamma", shape=(in_channels,),
                                         init=gamma_initializer,
                                         allow_deferred_init=True)
            self.beta = self.params.get("beta", shape=(in_channels,),
                                        init=beta_initializer,
                                        allow_deferred_init=True)

    def infer_shape(self, x, *args):
        self.gamma.shape = (x.shape[1],)
        self.beta.shape = (x.shape[1],)

    def hybrid_forward(self, F, x, gamma, beta):
        return F.InstanceNorm(x, gamma, beta, eps=self._epsilon)


class GroupNorm(HybridBlock):
    """Group normalization (reference: gluon nn.GroupNorm over
    src/operator/nn/group_norm.cc).  gamma/beta are per-GROUP, shape
    (num_groups,) — the reference convention (torch's GroupNorm is
    per-channel instead; checkpoints are not interchangeable)."""

    def __init__(self, num_groups=1, epsilon=1e-5, center=True, scale=True,
                 beta_initializer="zeros", gamma_initializer="ones",
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._num_groups = num_groups
        self._epsilon = epsilon
        with self.name_scope():
            self.gamma = self.params.get("gamma", shape=(num_groups,),
                                         init=gamma_initializer)
            self.beta = self.params.get("beta", shape=(num_groups,),
                                        init=beta_initializer)

    def hybrid_forward(self, F, x, gamma, beta):
        return F.GroupNorm(x, gamma, beta, num_groups=self._num_groups,
                           eps=self._epsilon)


class LayerNorm(HybridBlock):
    def __init__(self, axis=-1, epsilon=1e-5, center=True, scale=True,
                 beta_initializer="zeros", gamma_initializer="ones",
                 in_channels=0, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._axis = axis
        self._epsilon = epsilon
        with self.name_scope():
            self.gamma = self.params.get("gamma", shape=(in_channels,),
                                         init=gamma_initializer,
                                         allow_deferred_init=True)
            self.beta = self.params.get("beta", shape=(in_channels,),
                                        init=beta_initializer,
                                        allow_deferred_init=True)

    def infer_shape(self, x, *args):
        c = x.shape[self._axis]
        self.gamma.shape = (c,)
        self.beta.shape = (c,)

    def hybrid_forward(self, F, x, gamma, beta):
        return F.LayerNorm(x, gamma, beta, axis=self._axis,
                           eps=self._epsilon)


class RMSNorm(HybridBlock):
    """``x * rsqrt(mean(x^2) + epsilon) * gamma`` over ``axis``: LayerNorm
    without the mean and without a shift."""

    def __init__(self, axis=-1, epsilon=1e-5, gamma_initializer="ones",
                 in_channels=0, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._axis = axis
        self._epsilon = epsilon
        with self.name_scope():
            self.gamma = self.params.get("gamma", shape=(in_channels,),
                                         init=gamma_initializer,
                                         allow_deferred_init=True)

    def infer_shape(self, x, *args):
        self.gamma.shape = (x.shape[self._axis],)

    def hybrid_forward(self, F, x, gamma):
        return F.RMSNorm(x, gamma, axis=self._axis, eps=self._epsilon)


class SwiGLU(HybridBlock):
    """Gated feed-forward ``down(silu(gate(x)) * up(x))``, no biases."""

    def __init__(self, units, hidden_size, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        with self.name_scope():
            self.gate = Dense(hidden_size, use_bias=False, flatten=False,
                              in_units=units, prefix="gate_")
            self.up = Dense(hidden_size, use_bias=False, flatten=False,
                            in_units=units, prefix="up_")
            self.down = Dense(units, use_bias=False, flatten=False,
                              in_units=hidden_size, prefix="down_")

    def hybrid_forward(self, F, x):
        return self.down(F.silu(self.gate(x)) * self.up(x))


class Embedding(HybridBlock):
    """Turns integer ids into dense vectors of ``output_dim``.

    Out-of-range ids are CLIPPED into ``[0, input_dim - 1]`` (the
    reference's ``take`` default and the only mode XLA gathers support
    without a branch) — an id ``>= input_dim`` reads the last row and an
    id ``< 0`` reads row 0, never a wrapped-around row.  Pinned by
    ``test_embedding_clips_out_of_range_ids``.

    With ``sparse_grad=True`` the weight is marked
    ``grad_stype='row_sparse'``: under a ``ShardedTrainer`` step (and
    ``MXTPU_SPARSE_GRAD=1``, the default) its gradient is produced
    in-graph as a ``(values, unique_ids)`` pair via a segment-sum over
    the batch's deduplicated ids, and the optimizer touches only those
    rows — see ``sparse_grad.py``.  Outside a sharded step the flag has
    the reference semantics via the gluon ``Trainer``'s row-sparse
    exchange, or is simply dense.
    """

    def __init__(self, input_dim, output_dim, dtype="float32",
                 weight_initializer=None, sparse_grad=False, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        self._input_dim = input_dim
        self._output_dim = output_dim
        self._sparse_grad = bool(sparse_grad)
        with self.name_scope():
            self.weight = self.params.get(
                "weight", shape=(input_dim, output_dim), dtype=dtype,
                init=weight_initializer,
                grad_stype="row_sparse" if sparse_grad else "default")

    def hybrid_forward(self, F, x, weight):
        if self._sparse_grad and hasattr(x, "_read"):
            from ... import sparse_grad as _sg
            ctx = _sg.trace_ctx()
            if ctx is not None and ctx.wants(self.weight):
                val = ctx.embedding(self.weight, x._read(), weight._read(),
                                    self._input_dim)
                return type(x)(val, ctx=x.context)
        return F.Embedding(x, weight, input_dim=self._input_dim,
                           output_dim=self._output_dim,
                           sparse_grad=self._sparse_grad)


class RowShardedEmbedding(Embedding):
    """An :class:`Embedding` whose table is partitioned along dim 0
    (the vocab axis) across the mesh's ``'dp'`` axis, so a table larger
    than one chip's HBM trains — each data-parallel rank holds
    ``input_dim / dp`` rows, and the forward's gather is a cross-rank
    collective XLA derives from the sharding (no manual all-to-all).

    Only meaningful under a ``ShardedTrainer``: the trainer's sharding
    pass sees the marker and places the weight (and, through
    ``zero_sharding``'s fallback discipline, its optimizer state) with
    dim 0 split over ``'dp'``.  Checkpoints save the logical table and
    re-shard on load over whatever mesh restores it (PR-10 machinery).
    Pairs with dense gradients — a row-sharded table's grad is produced
    and reduce-scattered dense, so ``sparse_grad`` is rejected.
    """

    def __init__(self, input_dim, output_dim, dtype="float32",
                 weight_initializer=None, prefix=None, params=None,
                 shard_axis="dp"):
        super().__init__(input_dim, output_dim, dtype=dtype,
                         weight_initializer=weight_initializer,
                         sparse_grad=False, prefix=prefix, params=params)
        self.weight._row_shard_axis = shard_axis


class Flatten(HybridBlock):
    def hybrid_forward(self, F, x):
        return F.flatten(x)


class Lambda(Block):
    def __init__(self, function, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        if isinstance(function, str):
            from ... import ndarray as nd
            function = getattr(nd, function)
        self._fn = function

    def forward(self, *args):
        return self._fn(*args)


class HybridLambda(HybridBlock):
    def __init__(self, function, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._fn_name = function if isinstance(function, str) else None
        self._fn = function

    def hybrid_forward(self, F, *args):
        fn = getattr(F, self._fn_name) if self._fn_name else self._fn
        if self._fn_name is None:
            return fn(F, *args)
        return fn(*args)


class Activation(HybridBlock):
    def __init__(self, activation, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._act_type = activation

    def hybrid_forward(self, F, x):
        return F.Activation(x, act_type=self._act_type)


class LeakyReLU(HybridBlock):
    def __init__(self, alpha, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._alpha = alpha

    def hybrid_forward(self, F, x):
        return F.LeakyReLU(x, act_type="leaky", slope=self._alpha)


class PReLU(HybridBlock):
    def __init__(self, alpha_initializer=None, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        from ... import initializer as init_mod
        with self.name_scope():
            self.alpha = self.params.get(
                "alpha", shape=(1,),
                init=alpha_initializer or init_mod.Constant(0.25))

    def hybrid_forward(self, F, x, alpha):
        return F.LeakyReLU(x, alpha, act_type="prelu")


class ELU(HybridBlock):
    def __init__(self, alpha=1.0, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._alpha = alpha

    def hybrid_forward(self, F, x):
        return F.LeakyReLU(x, act_type="elu", slope=self._alpha)


class SELU(HybridBlock):
    def hybrid_forward(self, F, x):
        return F.LeakyReLU(x, act_type="selu")


class GELU(HybridBlock):
    def hybrid_forward(self, F, x):
        return F.LeakyReLU(x, act_type="gelu")


class Swish(HybridBlock):
    def __init__(self, beta=1.0, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._beta = beta

    def hybrid_forward(self, F, x):
        return x * F.sigmoid(self._beta * x)


class ReflectionPad2D(HybridBlock):
    """Reflection padding on H/W of NCHW input (reference:
    gluon/nn/basic_layers.py ReflectionPad2D over src/operator/pad.cc
    reflect mode)."""

    def __init__(self, padding=0, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        if isinstance(padding, int):
            padding = (padding,) * 4      # (left, right, top, bottom)
        padding = tuple(padding)
        if len(padding) == 8:
            # reference pad_width form (N..., C..., t, b, l, r)
            t, b, l, r = padding[4:]
            padding = (l, r, t, b)
        if len(padding) != 4:
            raise MXNetError(
                "ReflectionPad2D padding must be an int, a 4-tuple "
                "(left, right, top, bottom), or the reference 8-tuple "
                f"pad_width; got {padding}")
        self._padding = padding

    def hybrid_forward(self, F, x):
        l, r, t, b = self._padding
        return F.pad(x, mode="reflect",
                     pad_width=(0, 0, 0, 0, t, b, l, r))
