"""Optimizers.

Reference parity: python/mxnet/optimizer/optimizer.py (SURVEY.md §2.5) —
registry (`mx.optimizer.create``), SGD with momentum + multi_precision
(fp32 master weights), Adam/NAG/RMSProp/AdaGrad/Ftrl/Signum, per-param
lr_mult/wd_mult, lr scheduling, and the ``Updater`` wrapper the KVStore uses
server-side.  Each update step executes as one fused XLA computation via the
registered ``*_update`` ops; the learning rate is a runtime input so
schedules never recompile.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional

import numpy as _np

from .base import MXNetError
from .ndarray import NDArray, zeros as nd_zeros, array as nd_array
from .ndarray.register import invoke_by_name

__all__ = ["Optimizer", "SGD", "NAG", "Adam", "AdaGrad", "RMSProp", "Ftrl",
           "Signum", "AdaDelta", "AdamW", "LARS", "LBSGD", "Adamax",
           "Nadam", "SGLD", "DCASGD", "FTML", "LAMB", "register",
           "create", "Updater", "get_updater"]

_registry: Dict[str, type] = {}


def _is_low_precision(dtype) -> bool:
    """fp16 or bfloat16 — the dtypes multi_precision keeps fp32 masters for
    (bf16 is the TPU-native low precision; fp16 kept for parity)."""
    return dtype == _np.float16 or \
        getattr(_np.dtype(dtype), "name", "") == "bfloat16"


def register(klass):
    _registry[klass.__name__.lower()] = klass
    return klass


def create(name, **kwargs) -> "Optimizer":
    if isinstance(name, Optimizer):
        return name
    if name.lower() not in _registry:
        raise MXNetError(f"unknown optimizer {name!r}")
    return _registry[name.lower()](**kwargs)


class Optimizer:
    """Base optimizer with per-index lr/wd multipliers and update counting."""

    def __init__(self, rescale_grad=1.0, param_idx2name=None, wd=0.0,
                 clip_gradient=None, learning_rate=0.01, lr_scheduler=None,
                 multi_precision=False, param_dict=None, begin_num_update=0,
                 **kwargs):
        self.rescale_grad = rescale_grad
        self.lr = learning_rate
        self.lr_scheduler = lr_scheduler
        if lr_scheduler is not None:
            self.lr_scheduler.base_lr = learning_rate
        self.wd = wd
        self.clip_gradient = clip_gradient
        self.multi_precision = multi_precision
        self.param_idx2name = param_idx2name or {}
        self.param_dict = param_dict or {}
        self.num_update = begin_num_update
        self.begin_num_update = begin_num_update
        self._index_update_count: Dict[int, int] = {}
        self.idx2name = self.param_idx2name
        self.lr_mult: Dict[Any, float] = {}
        self.wd_mult: Dict[Any, float] = {}
        # >1 enables multi-tensor apply in Trainer (reference:
        # MXNET_OPTIMIZER_AGGREGATION_SIZE); only optimizers that
        # implement update_multi (SGD) honor it
        self.aggregate_num = 0

    # -- bookkeeping -------------------------------------------------------
    def extra_state(self):
        """Scalar optimizer state beyond per-param tensors (e.g. Nadam's
        momentum-schedule product) — serialized by Updater.get_states
        (dump_optimizer=True) so time-dependent optimizers resume
        exactly.  Return None when there is nothing extra."""
        return None

    def set_extra_state(self, extra) -> None:
        pass

    def _update_count(self, index) -> None:
        cnt = self._index_update_count.get(index, self.begin_num_update)
        self._index_update_count[index] = cnt + 1
        self.num_update = max(self.num_update, self._index_update_count[index])

    def set_learning_rate(self, lr: float) -> None:
        if self.lr_scheduler is not None:
            raise MXNetError("cannot set lr directly when lr_scheduler is set")
        self.lr = lr

    @property
    def learning_rate(self) -> float:
        if self.lr_scheduler is not None:
            return self.lr_scheduler(self.num_update)
        return self.lr

    def _get_lr(self, index) -> float:
        lr = self.learning_rate
        param = self.param_dict.get(index)
        if param is not None:
            lr *= param.lr_mult
        elif index in self.lr_mult:
            lr *= self.lr_mult[index]
        elif index in self.param_idx2name:
            lr *= self.lr_mult.get(self.param_idx2name[index], 1.0)
        return lr

    def _get_wd(self, index) -> float:
        wd = self.wd
        param = self.param_dict.get(index)
        if param is not None:
            wd *= param.wd_mult
        elif index in self.wd_mult:
            wd *= self.wd_mult[index]
        elif index in self.param_idx2name:
            wd *= self.wd_mult.get(self.param_idx2name[index], 1.0)
        return wd

    def set_lr_mult(self, args_lr_mult: Dict) -> None:
        self.lr_mult = dict(args_lr_mult)

    def set_wd_mult(self, args_wd_mult: Dict) -> None:
        self.wd_mult = dict(args_wd_mult)

    # -- interface ---------------------------------------------------------
    def create_state(self, index, weight):
        return None

    def create_state_multi_precision(self, index, weight):
        if self.multi_precision and _is_low_precision(weight.dtype):
            w32 = weight.astype("float32")
            return (self.create_state(index, w32), w32)
        return self.create_state(index, weight)

    def update(self, index, weight, grad, state):
        raise NotImplementedError

    def update_multi_precision(self, index, weight, grad, state):
        """Generic multi-precision path: run the update on the fp32 master
        weight, then downcast into the live weight (optimizers with a fused
        mp kernel, like SGD, override this)."""
        from .sparse import BaseSparseNDArray
        if isinstance(grad, BaseSparseNDArray) and self.multi_precision:
            grad = grad.todense()
        if self.multi_precision and isinstance(state, tuple) and \
                len(state) == 2 and isinstance(state[1], NDArray) and \
                state[1].dtype == _np.float32 and \
                weight.dtype != _np.float32:
            inner, w32 = state
            self.update(index, w32, grad.astype("float32"), inner)
            weight._set_data(w32._read().astype(weight.dtype))
        else:
            self.update(index, weight, grad, state)

    def _common_kwargs(self, index) -> Dict[str, Any]:
        kw = {"wd": self._get_wd(index), "rescale_grad": self.rescale_grad}
        if self.clip_gradient is not None:
            kw["clip_gradient"] = self.clip_gradient
        return kw

    def _lr_nd(self, index, weight, scale: float = 1.0) -> NDArray:
        # must live on the weight's device: mixed-device jit inputs are an
        # error on real TPU (CPU test meshes mask this)
        return nd_array(_np.float32(self._get_lr(index) * scale),
                        ctx=weight.context)


@register
class SGD(Optimizer):
    """SGD with momentum and multi-precision master weights."""

    def __init__(self, momentum=0.0, lazy_update=True, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum
        self.lazy_update = lazy_update
        from .base import get_env
        self.aggregate_num = int(get_env(
            "MXNET_OPTIMIZER_AGGREGATION_SIZE"))

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return None
        return nd_zeros(weight.shape, ctx=weight.context, dtype=weight.dtype)

    def update(self, index, weight, grad, state):
        from .sparse import RowSparseNDArray
        if isinstance(grad, RowSparseNDArray):
            if self.lazy_update:
                return self._update_row_sparse(index, weight, grad, state)
            grad = grad.todense()      # reference: lazy_update=False path
        self._update_count(index)
        kw = self._common_kwargs(index)
        lr = self._lr_nd(index, weight)
        if self.momentum == 0.0:
            invoke_by_name("sgd_update", [weight, grad, lr], kw, out=weight)
        else:
            kw["momentum"] = self.momentum
            invoke_by_name("sgd_mom_update", [weight, grad, state, lr], kw,
                           out=[weight, state])

    def _update_row_sparse(self, index, weight, grad, state):
        """Lazy update: touch only the rows present in the row_sparse grad
        (reference: sgd_update/sgd_mom_update row_sparse kernels with
        lazy_update=True — src/operator/optimizer_op.cc).  Pure scatter on
        the dense weight: HBM traffic ∝ touched rows."""
        import jax.numpy as jnp
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        rows = jnp.asarray(grad.indices)
        g = jnp.asarray(grad.data) * self.rescale_grad
        if self.clip_gradient is not None:
            g = jnp.clip(g, -self.clip_gradient, self.clip_gradient)
        w = weight._read()
        g = g + wd * w[rows]
        if self.momentum == 0.0:
            weight._set_data(w.at[rows].add(-lr * g))
        else:
            m = state._read()
            m_rows = self.momentum * m[rows] - lr * g
            state._set_data(m.at[rows].set(m_rows))
            weight._set_data(w.at[rows].add(m_rows))

    def update_multi_precision(self, index, weight, grad, state):
        if self.multi_precision and isinstance(state, tuple) and \
                len(state) == 2 and isinstance(state[1], NDArray):
            mom, w32 = state
            self._update_count(index)
            kw = self._common_kwargs(index)
            kw["momentum"] = self.momentum
            if mom is None:
                mom = nd_zeros(w32.shape, ctx=w32.context, dtype=w32.dtype)
            lr = self._lr_nd(index, weight)
            invoke_by_name("mp_sgd_mom_update",
                           [weight, grad, mom, w32, lr], kw,
                           out=[weight, mom, w32])
        else:
            self.update(index, weight, grad, state)

    def update_multi(self, indices, weights, grads, states):
        """Fused multi-tensor apply: ONE Pallas launch updates the whole
        group (reference multi_sgd_update family; kernels/multi_sgd.py).

        Falls back per-tensor for sparse grads, mixed dtypes, or shapes
        the fused path cannot batch.
        """
        from .sparse import BaseSparseNDArray
        dt = weights[0].dtype
        mp = (self.multi_precision and isinstance(states[0], tuple) and
              len(states[0]) == 2 and isinstance(states[0][1], NDArray))
        fallback = (any(isinstance(g, BaseSparseNDArray) for g in grads)
                    or any(w.dtype != dt for w in weights)
                    or (mp and self.momentum == 0.0)
                    or (mp and any(not isinstance(s, tuple)
                                   for s in states)))
        if fallback:
            for i, w, g, s in zip(indices, weights, grads, states):
                self.update_multi_precision(i, w, g, s)
            return
        for i in indices:
            self._update_count(i)
        ctx = weights[0].context
        lrs = nd_array(_np.array([self._get_lr(i) for i in indices],
                                 _np.float32), ctx=ctx)
        wds = nd_array(_np.array([self._get_wd(i) for i in indices],
                                 _np.float32), ctx=ctx)
        kw: Dict[str, Any] = {"rescale_grad": self.rescale_grad,
                              "num_weights": len(indices)}
        # Mosaic vs interpret must be decided OUTSIDE the trace (a traced
        # array has no device); key it on the concrete weight context
        kw["interpret"] = ctx.device.platform != "tpu"
        if self.clip_gradient is not None:
            kw["clip_gradient"] = self.clip_gradient
        data: list = []
        out: list = []
        if mp:
            kw["momentum"] = self.momentum
            for w, g, s in zip(weights, grads, states):
                mom, w32 = s
                if mom is None:
                    mom = nd_zeros(w32.shape, ctx=w32.context,
                                   dtype=w32.dtype)
                data.extend((w, g, mom, w32))
                out.extend((w, mom, w32))
            invoke_by_name("multi_mp_sgd_mom_update", data + [lrs, wds],
                           kw, out=out)
        elif self.momentum != 0.0:
            kw["momentum"] = self.momentum
            for w, g, s in zip(weights, grads, states):
                data.extend((w, g, s))
                out.extend((w, s))
            invoke_by_name("multi_sgd_mom_update", data + [lrs, wds], kw,
                           out=out)
        else:
            for w, g in zip(weights, grads):
                data.extend((w, g))
                out.append(w)
            invoke_by_name("multi_sgd_update", data + [lrs, wds], kw,
                           out=out)


@register
class NAG(Optimizer):
    def __init__(self, momentum=0.0, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return None
        return nd_zeros(weight.shape, ctx=weight.context, dtype=weight.dtype)

    def update(self, index, weight, grad, state):
        self._update_count(index)
        kw = self._common_kwargs(index)
        kw["momentum"] = self.momentum
        lr = self._lr_nd(index, weight)
        if state is None:
            invoke_by_name("sgd_update", [weight, grad, lr],
                           self._common_kwargs(index), out=weight)
        else:
            invoke_by_name("nag_mom_update", [weight, grad, state, lr], kw,
                           out=[weight, state])


@register
class Adam(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, lazy_update=True, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1 = beta1
        self.beta2 = beta2
        self.epsilon = epsilon
        self.lazy_update = lazy_update

    def create_state(self, index, weight):
        return (nd_zeros(weight.shape, ctx=weight.context, dtype=weight.dtype),
                nd_zeros(weight.shape, ctx=weight.context, dtype=weight.dtype))

    def update(self, index, weight, grad, state):
        from .sparse import RowSparseNDArray
        if isinstance(grad, RowSparseNDArray):
            if self.lazy_update:
                return self._update_row_sparse(index, weight, grad, state)
            grad = grad.todense()
        self._update_count(index)
        t = self._index_update_count[index]
        coef1 = 1.0 - self.beta1 ** t
        coef2 = 1.0 - self.beta2 ** t
        lr_t = self._get_lr(index) * math.sqrt(coef2) / coef1
        mean, var = state
        kw = self._common_kwargs(index)
        kw.update(beta1=self.beta1, beta2=self.beta2, epsilon=self.epsilon)
        lr = nd_array(_np.float32(lr_t), ctx=weight.context)
        invoke_by_name("adam_update", [weight, grad, mean, var, lr], kw,
                       out=[weight, mean, var])

    def _update_row_sparse(self, index, weight, grad, state):
        """Lazy Adam: mean/var/weight touched only on the grad's rows
        (reference adam_update row_sparse kernel with lazy_update=True)
        — untouched rows keep their moments frozen, so the update cost
        scales with touched rows, not vocab.  Mirrors
        parallel/optim.py's in-graph row path formula for formula."""
        import jax.numpy as jnp
        self._update_count(index)
        t = self._index_update_count[index]
        lr_t = self._get_lr(index) * \
            math.sqrt(1.0 - self.beta2 ** t) / (1.0 - self.beta1 ** t)
        wd = self._get_wd(index)
        rows = jnp.asarray(grad.indices)
        g = jnp.asarray(grad.data) * self.rescale_grad
        if self.clip_gradient is not None:
            g = jnp.clip(g, -self.clip_gradient, self.clip_gradient)
        w = weight._read()
        g = g + wd * w[rows]
        mean, var = state
        m, v = mean._read(), var._read()
        m_rows = self.beta1 * m[rows] + (1.0 - self.beta1) * g
        v_rows = self.beta2 * v[rows] + (1.0 - self.beta2) * jnp.square(g)
        mean._set_data(m.at[rows].set(m_rows))
        var._set_data(v.at[rows].set(v_rows))
        weight._set_data(w.at[rows].add(
            -lr_t * m_rows / (jnp.sqrt(v_rows) + self.epsilon)))


@register
class AdaGrad(Optimizer):
    def __init__(self, eps=1e-7, **kwargs):
        super().__init__(**kwargs)
        self.float_stable_eps = eps

    def create_state(self, index, weight):
        return nd_zeros(weight.shape, ctx=weight.context, dtype=weight.dtype)

    def update(self, index, weight, grad, state):
        self._update_count(index)
        kw = self._common_kwargs(index)
        kw["epsilon"] = self.float_stable_eps
        lr = self._lr_nd(index, weight)
        invoke_by_name("adagrad_update", [weight, grad, state, lr], kw,
                       out=[weight, state])


@register
class RMSProp(Optimizer):
    def __init__(self, learning_rate=0.001, gamma1=0.9, gamma2=0.9,
                 epsilon=1e-8, centered=False, clip_weights=None, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.gamma1 = gamma1
        self.gamma2 = gamma2
        self.epsilon = epsilon
        self.centered = centered
        self.clip_weights = clip_weights

    def create_state(self, index, weight):
        mk = lambda: nd_zeros(weight.shape, ctx=weight.context,
                              dtype=weight.dtype)
        if self.centered:
            return (mk(), mk(), mk())   # n, g_avg, delta (rmspropalex)
        return mk()

    def update(self, index, weight, grad, state):
        self._update_count(index)
        kw = self._common_kwargs(index)
        kw.update(gamma1=self.gamma1, epsilon=self.epsilon)
        if self.clip_weights is not None:
            kw["clip_weights"] = self.clip_weights
        lr = self._lr_nd(index, weight)
        if self.centered:
            n, g_avg, delta = state
            kw["gamma2"] = self.gamma2
            invoke_by_name("rmspropalex_update",
                           [weight, grad, n, g_avg, delta, lr], kw,
                           out=[weight, n, g_avg, delta])
        else:
            invoke_by_name("rmsprop_update", [weight, grad, state, lr], kw,
                           out=[weight, state])


@register
class Ftrl(Optimizer):
    def __init__(self, lamda1=0.01, learning_rate=0.1, beta=1, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.lamda1 = lamda1
        self.beta = beta

    def create_state(self, index, weight):
        return (nd_zeros(weight.shape, ctx=weight.context, dtype=weight.dtype),
                nd_zeros(weight.shape, ctx=weight.context, dtype=weight.dtype))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        z, n = state
        kw = self._common_kwargs(index)
        kw.update(lamda1=self.lamda1, beta=self.beta)
        lr = self._lr_nd(index, weight)
        invoke_by_name("ftrl_update", [weight, grad, z, n, lr], kw,
                       out=[weight, z, n])


@register
class Signum(Optimizer):
    def __init__(self, learning_rate=0.01, momentum=0.9, wd_lh=0.0, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.momentum = momentum
        self.wd_lh = wd_lh

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return None
        return nd_zeros(weight.shape, ctx=weight.context, dtype=weight.dtype)

    def update(self, index, weight, grad, state):
        self._update_count(index)
        kw = self._common_kwargs(index)
        lr = self._lr_nd(index, weight)
        if state is None:
            invoke_by_name("signsgd_update", [weight, grad, lr], kw,
                           out=weight)
        else:
            kw.update(momentum=self.momentum, wd_lh=self.wd_lh)
            invoke_by_name("signum_update", [weight, grad, state, lr], kw,
                           out=[weight, state])


@register
class AdaDelta(Optimizer):
    def __init__(self, rho=0.9, epsilon=1e-5, **kwargs):
        super().__init__(**kwargs)
        self.rho = rho
        self.epsilon = epsilon

    def create_state(self, index, weight):
        return (nd_zeros(weight.shape, ctx=weight.context, dtype=weight.dtype),
                nd_zeros(weight.shape, ctx=weight.context, dtype=weight.dtype))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        # composed from primitive ops (no fused kernel in the reference either)
        acc_g, acc_d = state
        wd = self._get_wd(index)
        g = grad * self.rescale_grad
        if self.clip_gradient is not None:
            from .ndarray import clip as nd_clip
            g = nd_clip(g, a_min=-self.clip_gradient,
                        a_max=self.clip_gradient)
        from .ndarray import sqrt as nd_sqrt
        acc_g_new = self.rho * acc_g + (1 - self.rho) * g * g
        delta = nd_sqrt(acc_d + self.epsilon) / \
            nd_sqrt(acc_g_new + self.epsilon) * g
        acc_d_new = self.rho * acc_d + (1 - self.rho) * delta * delta
        acc_g._set_data(acc_g_new._read())
        acc_d._set_data(acc_d_new._read())
        weight._set_data((weight - delta - wd * weight)._read())


@register
class AdamW(Optimizer):
    """Adam with decoupled weight decay (reference:
    src/operator/contrib/adamw.cc + python contrib.optimizer.AdamW).

    ``wd`` is applied to the weight directly (scaled by ``eta``), outside
    the adaptive preconditioner; bias correction is folded into the lr
    passed to the fused op, as the reference python wrapper does.
    """

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, eta=1.0, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1, self.beta2, self.epsilon, self.eta = \
            beta1, beta2, epsilon, eta

    def create_state(self, index, weight):
        import numpy as np
        return (nd_zeros(weight.shape, ctx=weight.context,
                         dtype=np.float32),
                nd_zeros(weight.shape, ctx=weight.context,
                         dtype=np.float32))

    def create_state_multi_precision(self, index, weight):
        if self.multi_precision and _is_low_precision(weight.dtype):
            w32 = weight.astype(_np.float32)
            return (self.create_state(index, w32), w32)
        return self.create_state(index, weight)

    def _corrected_lr(self, index):
        t = self._index_update_count[index]
        return self._get_lr(index) * \
            math.sqrt(1.0 - self.beta2 ** t) / (1.0 - self.beta1 ** t)

    def _kw(self, index):
        # decoupled decay is lr-scaled (w -= lr*wd*w, the torch/Loshchilov
        # convention); the op applies eta*wd_in*w, so fold the PLAIN lr
        # into wd_in while the op's lr input carries bias correction
        kw = {"beta1": self.beta1, "beta2": self.beta2,
              "epsilon": self.epsilon,
              "wd": self._get_wd(index) * self._get_lr(index),
              "eta": self.eta, "rescale_grad": self.rescale_grad}
        if self.clip_gradient is not None:
            kw["clip_gradient"] = self.clip_gradient
        return kw

    def update(self, index, weight, grad, state):
        self._update_count(index)
        kw = self._kw(index)
        lr = nd_array(_np.float32(self._corrected_lr(index)),
                      ctx=weight.context)
        mean, var = state
        invoke_by_name("adamw_update", [weight, grad, mean, var, lr], kw,
                       out=[weight, mean, var])

    def update_multi_precision(self, index, weight, grad, state):
        # mp state is ((mean, var), w32); plain fp32 state is (mean, var)
        # — the inner-tuple check keeps them apart
        if self.multi_precision and isinstance(state, tuple) and \
                len(state) == 2 and isinstance(state[0], tuple) and \
                isinstance(state[1], NDArray):
            (mean, var), w32 = state
            self._update_count(index)
            kw = self._kw(index)
            lr = nd_array(_np.float32(self._corrected_lr(index)),
                          ctx=weight.context)
            invoke_by_name("mp_adamw_update",
                           [weight, grad, mean, var, w32, lr], kw,
                           out=[weight, mean, var, w32])
        else:
            self.update(index, weight, grad, state)


@register
class LARS(Optimizer):
    """Layer-wise Adaptive Rate Scaling (reference: the LARS optimizer +
    multi_lars contrib kernels that landed for large-batch ResNet;
    You et al. 2017).

    Per layer: ``local_lr = eta * ||w|| / (||g*rescale|| + wd*||w|| + eps)``
    computed ON DEVICE by the ``lars_trust`` op (no host sync), folded into
    the lr input of the fused sgd(_mom) update.
    """

    def __init__(self, momentum=0.9, eta=0.001, epsilon=1e-8, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum
        self.eta = eta
        self.epsilon = epsilon

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return None
        return nd_zeros(weight.shape, ctx=weight.context,
                        dtype=weight.dtype)

    def update(self, index, weight, grad, state):
        self._update_count(index)
        kw = self._common_kwargs(index)
        trust = invoke_by_name(
            "lars_trust", [weight, grad,
                           nd_array(_np.float32(self._get_wd(index)),
                                    ctx=weight.context)],
            {"eta": self.eta, "epsilon": self.epsilon,
             "rescale_grad": self.rescale_grad})
        lr = self._lr_nd(index, weight) * trust
        if self.momentum == 0.0:
            invoke_by_name("sgd_update", [weight, grad, lr], kw, out=weight)
        else:
            kw["momentum"] = self.momentum
            invoke_by_name("sgd_mom_update", [weight, grad, state, lr], kw,
                           out=[weight, state])


@register
class LBSGD(Optimizer):
    """Large-Batch SGD with warmup + LARS trust scaling (reference:
    python/mxnet/optimizer/optimizer.py LBSGD).

    warmup_strategy: 'linear'/'power2'/'sqrt' ramp the lr over
    ``warmup_epochs``; 'lars' applies the layer-wise trust ratio every
    step (the reference's default large-batch recipe).
    """

    def __init__(self, momentum=0.0, multi_precision=False,
                 warmup_strategy="linear", warmup_epochs=5,
                 batch_scale=1, updates_per_epoch=32, begin_epoch=0,
                 num_epochs=60, eta=0.001, **kwargs):
        super().__init__(multi_precision=multi_precision, **kwargs)
        self.momentum = momentum
        self.warmup_strategy = warmup_strategy
        self.warmup_epochs = warmup_epochs
        self.batch_scale = batch_scale
        self.updates_per_epoch = max(1, updates_per_epoch)
        self.begin_epoch = begin_epoch
        self.num_epochs = num_epochs
        self.eta = eta
        self.epsilon = 1e-8

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return None
        return nd_zeros(weight.shape, ctx=weight.context,
                        dtype=weight.dtype)

    def _warmup_scale(self, index) -> float:
        t = self._index_update_count[index]
        warm_T = self.warmup_epochs * self.updates_per_epoch
        if self.warmup_strategy not in ("linear", "power2", "sqrt") or \
                t >= warm_T:
            return 1.0
        frac = t / warm_T
        if self.warmup_strategy == "linear":
            return frac
        if self.warmup_strategy == "power2":
            return frac * frac
        return math.sqrt(frac)

    def update(self, index, weight, grad, state):
        self._update_count(index)
        kw = self._common_kwargs(index)
        scale = self._warmup_scale(index)
        lr = self._lr_nd(index, weight, scale=scale)
        if self.warmup_strategy == "lars":
            trust = invoke_by_name(
                "lars_trust", [weight, grad,
                               nd_array(_np.float32(self._get_wd(index)),
                                        ctx=weight.context)],
                {"eta": self.eta, "epsilon": self.epsilon,
                 "rescale_grad": self.rescale_grad})
            lr = lr * trust
        if self.momentum == 0.0:
            invoke_by_name("sgd_update", [weight, grad, lr], kw, out=weight)
        else:
            kw["momentum"] = self.momentum
            invoke_by_name("sgd_mom_update", [weight, grad, state, lr], kw,
                           out=[weight, state])


@register
class FTML(Optimizer):
    """Follow The Moving Leader (reference: src/operator/optimizer_op.cc
    ftml_update; python/mxnet/optimizer FTML).  One fused XLA update per
    parameter via the ``ftml_update`` op."""

    def __init__(self, learning_rate=0.0025, beta1=0.6, beta2=0.999,
                 epsilon=1e-8, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon

    def create_state(self, index, weight):
        z = nd_zeros(weight.shape, ctx=weight.context, dtype=weight.dtype)
        d = nd_zeros(weight.shape, ctx=weight.context, dtype=weight.dtype)
        v = nd_zeros(weight.shape, ctx=weight.context, dtype=weight.dtype)
        return (d, v, z)

    def update(self, index, weight, grad, state):
        self._update_count(index)
        t = self._index_update_count[index]
        d, v, z = state
        kw = {"beta1": self.beta1, "beta2": self.beta2,
              "epsilon": self.epsilon, "t": t,
              "wd": self._get_wd(index),
              "rescale_grad": self.rescale_grad}
        if self.clip_gradient is not None:
            kw["clip_grad"] = self.clip_gradient
        lr = self._lr_nd(index, weight)
        invoke_by_name("ftml_update", [weight, grad, d, v, z, lr], kw,
                       out=[weight, d, v, z])


@register
class LAMB(Optimizer):
    """Layer-wise Adaptive Moments for Batch training (reference:
    src/operator/optimizer_op.cc lamb_update_phase1/phase2; python
    optimizer LAMB).  Phase 1 computes the adam-style direction, phase 2
    applies it scaled by the layerwise trust ratio ||w||/||direction||."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-6, lower_bound=None, upper_bound=None,
                 bias_correction=True, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon
        self.lower_bound = lower_bound
        self.upper_bound = upper_bound
        self.bias_correction = bias_correction

    def create_state(self, index, weight):
        return (nd_zeros(weight.shape, ctx=weight.context,
                         dtype=_np.float32),
                nd_zeros(weight.shape, ctx=weight.context,
                         dtype=_np.float32))

    def create_state_multi_precision(self, index, weight):
        if self.multi_precision and _is_low_precision(weight.dtype):
            w32 = weight.astype(_np.float32)
            return (self.create_state(index, w32), w32)
        return self.create_state(index, weight)

    def _phase_kwargs(self, index):
        kw = {"beta1": self.beta1, "beta2": self.beta2,
              "epsilon": self.epsilon,
              "t": self._index_update_count[index],
              "bias_correction": self.bias_correction,
              "wd": self._get_wd(index),
              "rescale_grad": self.rescale_grad}
        if self.clip_gradient is not None:
            kw["clip_gradient"] = self.clip_gradient
        return kw

    def _phase2_kwargs(self):
        kw = {}
        if self.lower_bound is not None:
            kw["lower_bound"] = self.lower_bound
        if self.upper_bound is not None:
            kw["upper_bound"] = self.upper_bound
        return kw

    def update(self, index, weight, grad, state):
        self._update_count(index)
        mean, var = state
        d = invoke_by_name("lamb_update_phase1", [weight, grad, mean, var],
                           self._phase_kwargs(index))
        direction, m_new, v_new = d
        mean._set_data(m_new._read())
        var._set_data(v_new._read())
        from .ndarray import norm as _nd_norm
        r1 = _nd_norm(weight)
        r2 = _nd_norm(direction)
        lr = self._lr_nd(index, weight)
        invoke_by_name("lamb_update_phase2",
                       [weight, direction, r1, r2, lr],
                       self._phase2_kwargs(), out=weight)

    def update_multi_precision(self, index, weight, grad, state):
        if not (self.multi_precision and _is_low_precision(weight.dtype)):
            return self.update(index, weight, grad, state)
        self._update_count(index)
        (mean, var), w32 = state
        d = invoke_by_name("mp_lamb_update_phase1",
                           [weight, grad, mean, var, w32],
                           self._phase_kwargs(index))
        direction, m_new, v_new = d
        mean._set_data(m_new._read())
        var._set_data(v_new._read())
        from .ndarray import norm as _nd_norm
        r1 = _nd_norm(w32)
        r2 = _nd_norm(direction)
        lr = self._lr_nd(index, w32)
        invoke_by_name("mp_lamb_update_phase2",
                       [weight, direction, r1, r2, w32, lr],
                       self._phase2_kwargs(), out=[weight, w32])


class Updater:
    """Callable wrapper used by KVStore to run the optimizer server-side
    (reference: mx.optimizer.get_updater)."""

    def __init__(self, optimizer: Optimizer):
        self.optimizer = optimizer
        self.states: Dict[Any, Any] = {}
        self.states_synced: Dict[Any, bool] = {}

    def __call__(self, index, grad, weight):
        if index not in self.states:
            self.states[index] = \
                self.optimizer.create_state_multi_precision(index, weight)
        self.optimizer.update_multi_precision(index, weight, grad,
                                              self.states[index])

    def get_states(self, dump_optimizer=False):
        """Serialize updater state; with dump_optimizer also the update
        counters (num_update / per-index counts) so time-dependent
        optimizers (Adam bias correction, lr schedules) resume correctly."""
        import pickle
        blob = {"states": {k: _states_to_np(v)
                           for k, v in self.states.items()}}
        if dump_optimizer:
            blob["num_update"] = self.optimizer.num_update
            blob["index_update_count"] = \
                dict(self.optimizer._index_update_count)
            extra = self.optimizer.extra_state()
            if extra is not None:
                blob["optimizer_extra"] = extra
        return pickle.dumps(blob)

    def set_states(self, states) -> None:
        import pickle
        loaded = pickle.loads(states)
        if "states" not in loaded:  # legacy flat format
            loaded = {"states": loaded}
        self.states = {k: _states_from_np(v)
                       for k, v in loaded["states"].items()}
        if "num_update" in loaded:
            self.optimizer.num_update = loaded["num_update"]
            self.optimizer._index_update_count = dict(
                loaded["index_update_count"])
        if "optimizer_extra" in loaded:
            self.optimizer.set_extra_state(loaded["optimizer_extra"])


def _states_to_np(state):
    if state is None:
        return None
    if isinstance(state, tuple):
        return tuple(_states_to_np(s) for s in state)
    # checkpoint serialization boundary (set_states/get_states)
    # mxlint: disable=hidden-host-sync — checkpoint serialization
    return state.asnumpy()


def _states_from_np(state):
    if state is None:
        return None
    if isinstance(state, tuple):
        return tuple(_states_from_np(s) for s in state)
    return nd_array(state)


def get_updater(optimizer: Optimizer) -> Updater:
    return Updater(optimizer)


# ---------------------------------------------------------------------------
# python-composed optimizers (reference optimizer.py implements these from
# primitive ops too — no fused kernels upstream either)
# ---------------------------------------------------------------------------

def _prepped(opt: Optimizer, index, grad, weight, with_wd=True):
    """Python-composed-optimizer gradient prep.  NOTE the order differs
    from the fused kernels' _prep_grad: the reference's python optimizers
    (Adamax/Nadam/...) add wd*weight FIRST and clip the SUM, while its
    C++ update kernels clip first — both conventions are mirrored
    faithfully on their respective paths."""
    g = grad * opt.rescale_grad
    if with_wd:
        wd = opt._get_wd(index)
        if wd:
            g = g + wd * weight
    if opt.clip_gradient is not None:
        from .ndarray import clip as nd_clip
        g = nd_clip(g, a_min=-opt.clip_gradient, a_max=opt.clip_gradient)
    return g


@register
class Adamax(Optimizer):
    """AdaMax (reference optimizer.py Adamax — Adam with the ∞-norm)."""

    def __init__(self, learning_rate=0.002, beta1=0.9, beta2=0.999,
                 **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1, self.beta2 = beta1, beta2

    def create_state(self, index, weight):
        return (nd_zeros(weight.shape, ctx=weight.context,
                         dtype=weight.dtype),
                nd_zeros(weight.shape, ctx=weight.context,
                         dtype=weight.dtype))

    def update(self, index, weight, grad, state):
        from .ndarray import abs as nd_abs, maximum as nd_maximum
        self._update_count(index)
        t = self._index_update_count[index]
        lr = self._get_lr(index) / (1.0 - self.beta1 ** t)
        g = _prepped(self, index, grad, weight)
        m, u = state
        m_new = self.beta1 * m + (1.0 - self.beta1) * g
        u_new = nd_maximum(self.beta2 * u, nd_abs(g))
        m._set_data(m_new._read())
        u._set_data(u_new._read())
        weight._set_data((weight - lr * m_new / (u_new + 1e-8))._read())


@register
class Nadam(Optimizer):
    """Nesterov Adam (reference optimizer.py Nadam — Adam with the
    momentum schedule of Dozat 2016)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, schedule_decay=0.004, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1, self.beta2 = beta1, beta2
        self.epsilon = epsilon
        self.schedule_decay = schedule_decay
        self.m_schedule = 1.0

    def extra_state(self):
        return {"m_schedule": self.m_schedule}

    def set_extra_state(self, extra) -> None:
        self.m_schedule = float(extra["m_schedule"])

    def create_state(self, index, weight):
        return (nd_zeros(weight.shape, ctx=weight.context,
                         dtype=weight.dtype),
                nd_zeros(weight.shape, ctx=weight.context,
                         dtype=weight.dtype))

    def update(self, index, weight, grad, state):
        from .ndarray import sqrt as nd_sqrt
        self._update_count(index)
        t = self._index_update_count[index]
        lr = self._get_lr(index)
        g = _prepped(self, index, grad, weight)
        mu_t = self.beta1 * (1.0 - 0.5 * 0.96 ** (t * self.schedule_decay))
        mu_t1 = self.beta1 * (1.0 - 0.5 * 0.96 **
                              ((t + 1) * self.schedule_decay))
        self.m_schedule = self.m_schedule * mu_t
        m_schedule_next = self.m_schedule * mu_t1
        m, v = state
        m_new = self.beta1 * m + (1.0 - self.beta1) * g
        v_new = self.beta2 * v + (1.0 - self.beta2) * g * g
        g_prime = g / (1.0 - self.m_schedule)
        m_prime = m_new / (1.0 - m_schedule_next)
        v_prime = v_new / (1.0 - self.beta2 ** t)
        m_bar = (1.0 - mu_t) * g_prime + mu_t1 * m_prime
        m._set_data(m_new._read())
        v._set_data(v_new._read())
        weight._set_data(
            (weight - lr * m_bar / (nd_sqrt(v_prime) + self.epsilon))
            ._read())


@register
class SGLD(Optimizer):
    """Stochastic Gradient Langevin Dynamics (reference optimizer.py
    SGLD): gradient step + N(0, sqrt(lr)) noise — the sampling optimizer."""

    def create_state(self, index, weight):
        return None

    def update(self, index, weight, grad, state):
        from .ndarray import random as nd_random
        self._update_count(index)
        lr = self._get_lr(index)
        # reference SGLD: clip the raw rescaled gradient; wd*weight rides
        # OUTSIDE the clip (unlike Adamax/Nadam, which clip the sum)
        g = _prepped(self, index, grad, weight, with_wd=False)
        g = g + self._get_wd(index) * weight
        noise = nd_random.normal(0.0, _np.sqrt(lr), shape=weight.shape,
                                 ctx=weight.context, dtype=weight.dtype)
        weight._set_data((weight - 0.5 * lr * g + noise)._read())


@register
class DCASGD(Optimizer):
    """Delay-compensated async SGD (reference optimizer.py DCASGD):
    compensates stale gradients with the Taylor term
    ``lambda * g² * (w - w_prev)``."""

    def __init__(self, learning_rate=0.01, momentum=0.0, lamda=0.04,
                 **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.momentum = momentum
        self.lamda = lamda

    def create_state(self, index, weight):
        mom = None if self.momentum == 0.0 else nd_zeros(
            weight.shape, ctx=weight.context, dtype=weight.dtype)
        return (mom, weight.copy())

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        # reference formula: wd rides OUTSIDE the squared Taylor term —
        # only the raw (rescaled/clipped) gradient is squared
        g = _prepped(self, index, grad, weight, with_wd=False)
        wd = self._get_wd(index)
        mom, prev = state
        comp = g + wd * weight + self.lamda * g * g * (weight - prev)
        if mom is None:
            step = -lr * comp
        else:
            mom_new = self.momentum * mom - lr * comp
            mom._set_data(mom_new._read())
            step = mom_new
        prev._set_data(weight._read())
        weight._set_data((weight + step)._read())
