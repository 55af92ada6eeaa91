"""Neural-network operators: FC, conv, pooling, normalization, softmax, etc.

Reference parity: src/operator/nn/ (SURVEY.md §2.2) — each reference op had a
cuDNN fast path; here the fast path IS the op: XLA lowers dot/conv straight
onto the MXU, elementwise tails fuse into the matmul, and layouts are chosen
by the compiler.  MXNet conventions preserved: NCHW data layout, OIHW weight
layout, BatchNorm defaults (eps=1e-3, momentum=0.9, fix_gamma=True, channel
axis 1), pooling conventions 'valid'/'full', FullyConnected's flatten rule,
SoftmaxOutput's fused-gradient semantics.
"""
from __future__ import annotations

import math as _math

import numpy as _np

from .register import register_op


def _yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * _math.log(factor) + 1.0


def yarn_frequencies(rotary_dim, base, scaling):
    """YaRN in DeepSeek-V3's form (the ``rope_scaling`` keys ``factor``,
    ``original_max_position_embeddings``, ``beta_fast``, ``beta_slow``,
    ``mscale``, ``mscale_all_dim``): ``(inverse frequencies (rotary_dim //
    2,) float64, the factor on cos and sin)``.  Frequency i is ``base^(-2i /
    d)`` blended towards the same over ``factor`` by a ramp that is 0 up to
    the correction dimension of ``beta_fast`` rotations and 1 from that of
    ``beta_slow``, at ``original_max_position_embeddings`` positions."""
    d, factor = int(rotary_dim), float(scaling["factor"])
    orig = scaling["original_max_position_embeddings"]

    def dim_of(rotations):
        return d * _math.log(orig / (rotations * 2 * _math.pi)) \
            / (2 * _math.log(base))
    low = max(_math.floor(dim_of(scaling.get("beta_fast", 32))), 0)
    high = min(_math.ceil(dim_of(scaling.get("beta_slow", 1))), d - 1)
    if low == high:
        high += 0.001
    ramp = _np.clip((_np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    extra = float(base) ** (-_np.arange(0, d, 2, dtype=_np.float64) / d)
    inv = extra / factor * ramp + extra * (1.0 - ramp)
    return inv, _yarn_mscale(factor, scaling.get("mscale", 1)) \
        / _yarn_mscale(factor, scaling.get("mscale_all_dim", 0))


def yarn_softmax_factor(scaling):
    """What YaRN multiplies the attention's softmax scale by:
    ``mscale(factor, mscale_all_dim)^2`` (1 where ``mscale_all_dim`` is 0 or
    absent)."""
    return _yarn_mscale(float(scaling["factor"]),
                        scaling.get("mscale_all_dim", 0)) ** 2


def _register():
    import jax
    import jax.numpy as jnp
    from jax import lax

    # ---- FullyConnected --------------------------------------------------
    def fc_maker(num_hidden=None, no_bias=False, flatten=True):
        def fn(x, w, *maybe_b):
            if flatten and x.ndim > 2:
                x = x.reshape(x.shape[0], -1)
            y = jnp.matmul(x, w.T)   # MXU path; weight is (num_hidden, in)
            if not no_bias:
                y = y + maybe_b[0]
            return y
        return fn
    register_op("FullyConnected", fc_maker, aliases=("fully_connected",))

    # ---- Convolution -----------------------------------------------------
    def _spatial_dims(kernel):
        return len(kernel)

    from ..base import is_channels_last

    def _conv_dn(nd, layout=None):
        # channels-last data layouts (reference conv layout param:
        # NWC/NHWC/NDHWC) — the TPU-native tiling.  Weights stay OIHW in
        # EVERY layout (lax dimension_numbers maps them; XLA's layout
        # assignment makes it free), so initializer fan math and
        # checkpoints are layout-portable — a deliberate deviation from
        # the reference's weight-follows-layout convention.
        if is_channels_last(layout, nd):
            if nd == 1:
                return ("NWC", "OIW", "NWC")
            if nd == 2:
                return ("NHWC", "OIHW", "NHWC")
            return ("NDHWC", "OIDHW", "NDHWC")
        if nd == 1:
            return ("NCH", "OIH", "NCH")
        if nd == 2:
            return ("NCHW", "OIHW", "NCHW")
        return ("NCDHW", "OIDHW", "NCDHW")

    def conv_maker(kernel=(), stride=None, dilate=None, pad=None,
                   num_filter=None, num_group=1, no_bias=False,
                   layout=None, workspace=None, cudnn_tune=None,
                   cudnn_off=None):
        nd = _spatial_dims(kernel)
        stride_ = tuple(stride) if stride else (1,) * nd
        dilate_ = tuple(dilate) if dilate else (1,) * nd
        pad_ = tuple(pad) if pad else (0,) * nd
        channels_last = is_channels_last(layout, nd)
        bshape = ((1,) + (1,) * nd + (-1,)) if channels_last \
            else ((1, -1) + (1,) * nd)

        def fn(x, w, *maybe_b):
            y = lax.conv_general_dilated(
                x, w, window_strides=stride_,
                padding=[(p, p) for p in pad_],
                rhs_dilation=dilate_,
                feature_group_count=num_group,
                dimension_numbers=_conv_dn(nd, layout))
            if not no_bias:
                b = maybe_b[0]
                y = y + b.reshape(bshape)
            return y
        return fn
    register_op("Convolution", conv_maker, aliases=("convolution",))
    # legacy 0.x surface (src/operator/convolution_v1.cc): same math, kept
    # as a distinct op name for checkpoint/JSON compatibility
    register_op("Convolution_v1", conv_maker)

    def deconv_maker(kernel=(), stride=None, dilate=None, pad=None,
                     adj=None, target_shape=None, num_filter=None,
                     num_group=1, no_bias=True, layout=None, workspace=None,
                     cudnn_tune=None, cudnn_off=None):
        nd = _spatial_dims(kernel)
        stride_ = tuple(stride) if stride else (1,) * nd
        pad_ = tuple(pad) if pad else (0,) * nd
        adj_ = tuple(adj) if adj else (0,) * nd

        def fn(x, w, *maybe_b):
            # transposed conv = dilated input conv with flipped kernel;
            # out = (in-1)*s - 2p + k + adj  (MXNet deconv arithmetic)
            k = kernel
            w_t = jnp.swapaxes(w, 0, 1)            # IO... -> OI...
            w_t = jnp.flip(w_t, axis=tuple(range(2, 2 + nd)))
            padding = [(k[i] - 1 - pad_[i], k[i] - 1 - pad_[i] + adj_[i])
                       for i in range(nd)]
            y = lax.conv_general_dilated(
                x, w_t, window_strides=(1,) * nd,
                padding=padding, lhs_dilation=stride_,
                feature_group_count=num_group,
                dimension_numbers=_conv_dn(nd))
            if not no_bias and maybe_b:
                y = y + maybe_b[0].reshape((1, -1) + (1,) * nd)
            return y
        return fn
    register_op("Deconvolution", deconv_maker, aliases=("deconvolution",))

    # ---- Pooling ---------------------------------------------------------
    def pool_maker(kernel=(), pool_type="max", stride=None, pad=None,
                   global_pool=False, pooling_convention="valid",
                   count_include_pad=True, cudnn_off=None, p_value=2,
                   layout=None):
        nd = len(kernel) if kernel else 2
        channels_last = is_channels_last(layout, nd if kernel else None)

        def fn(x):
            sdims = x.ndim - 2
            sp0 = 1 if channels_last else 2   # first spatial dim index
            if global_pool:
                axes = tuple(range(sp0, sp0 + sdims))
                if pool_type == "max":
                    r = jnp.max(x, axis=axes, keepdims=True)
                elif pool_type == "sum":
                    r = jnp.sum(x, axis=axes, keepdims=True)
                else:
                    r = jnp.mean(x, axis=axes, keepdims=True)
                return r
            k = tuple(kernel)
            s = tuple(stride) if stride else (1,) * sdims
            p = tuple(pad) if pad else (0,) * sdims
            pads = []
            for i in range(sdims):
                lo = hi = p[i]
                if pooling_convention == "full":
                    # ceil convention: pad extra on the high side so the last
                    # partial window is included (reference 'full' pooling)
                    in_sz = x.shape[sp0 + i] + 2 * p[i]
                    out_full = -(-(in_sz - k[i]) // s[i]) + 1
                    hi += max(0, (out_full - 1) * s[i] + k[i] - in_sz)
                pads.append((lo, hi))
            if channels_last:
                window = (1,) + k + (1,)
                strides = (1,) + s + (1,)
                padcfg = [(0, 0)] + pads + [(0, 0)]
            else:
                window = (1, 1) + k
                strides = (1, 1) + s
                padcfg = [(0, 0), (0, 0)] + pads
            if pool_type == "max":
                # init must be a STATIC scalar: a traced init value defeats
                # jax's reduce_window_max autodiff pattern-match
                init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) \
                    else int(jnp.iinfo(x.dtype).min)
                return lax.reduce_window(x, init, lax.max, window, strides,
                                         padcfg)
            zero = 0.0 if jnp.issubdtype(x.dtype, jnp.floating) else 0
            ssum = lax.reduce_window(x, zero, lax.add, window, strides,
                                     padcfg)
            if pool_type == "sum":
                return ssum
            if pool_type == "avg":
                if count_include_pad:
                    denom = 1.0
                    for ki in k:
                        denom *= ki
                    return ssum / jnp.asarray(denom, x.dtype)
                ones = jnp.ones(x.shape, x.dtype)
                cnt = lax.reduce_window(ones, zero, lax.add, window,
                                        strides, padcfg)
                return ssum / cnt
            if pool_type == "lp":
                pw = lax.reduce_window(jnp.abs(x) ** p_value,
                                       jnp.asarray(0, x.dtype), lax.add,
                                       window, strides, padcfg)
                return pw ** (1.0 / p_value)
            raise ValueError(pool_type)
        return fn
    register_op("Pooling", pool_maker, aliases=("pooling",))
    register_op("Pooling_v1", pool_maker)       # legacy pooling_v1.cc name

    # ---- activations -----------------------------------------------------
    def act_maker(act_type="relu"):
        table = {
            "relu": lambda x: jnp.maximum(x, 0),
            "sigmoid": jax.nn.sigmoid,
            "tanh": jnp.tanh,
            "softrelu": jax.nn.softplus,
            "softsign": lambda x: x / (1 + jnp.abs(x)),
        }
        return table[act_type]
    register_op("Activation", act_maker, aliases=("activation",))

    def leaky_maker(act_type="leaky", slope=0.25, lower_bound=0.125,
                    upper_bound=0.334):
        def fn(x, *maybe_gamma):
            if act_type == "leaky":
                return jnp.where(x >= 0, x, slope * x)
            if act_type == "prelu":
                g = maybe_gamma[0]
                g = g.reshape((1, -1) + (1,) * (x.ndim - 2)) if x.ndim > 1 else g
                return jnp.where(x >= 0, x, g * x)
            if act_type == "elu":
                return jnp.where(x >= 0, x, slope * (jnp.exp(x) - 1))
            if act_type == "selu":
                alpha, scale = 1.6732632423543772, 1.0507009873554805
                return scale * jnp.where(x >= 0, x, alpha * (jnp.exp(x) - 1))
            if act_type == "gelu":
                return jax.nn.gelu(x, approximate=False)
            if act_type == "rrelu":
                mid = (lower_bound + upper_bound) / 2.0
                return jnp.where(x >= 0, x, mid * x)
            raise ValueError(act_type)
        return fn
    register_op("LeakyReLU", leaky_maker, aliases=("leaky_relu",))

    # ---- softmax family --------------------------------------------------
    def softmax_maker(axis=-1, temperature=None, length=None, dtype=None,
                      use_length=False):
        def fn(x, *maybe_len):
            xs = x / temperature if temperature else x
            if use_length and maybe_len:
                L = maybe_len[0].astype(jnp.int32)
                pos = jnp.arange(x.shape[axis])
                shape = [1] * x.ndim
                shape[axis] = x.shape[axis]
                mask = pos.reshape(shape) < L.reshape(
                    L.shape + (1,) * (x.ndim - L.ndim))
                xs = jnp.where(mask, xs, -jnp.inf)
                out = jax.nn.softmax(xs, axis=axis)
                return jnp.where(mask, out, 0.0)
            return jax.nn.softmax(xs, axis=axis)
        return fn
    register_op("softmax", softmax_maker)

    def log_softmax_maker(axis=-1, temperature=None, dtype=None,
                          use_length=False):
        def fn(x):
            xs = x / temperature if temperature else x
            return jax.nn.log_softmax(xs, axis=axis)
        return fn
    register_op("log_softmax", log_softmax_maker)

    def softmin_maker(axis=-1, temperature=None, dtype=None):
        def fn(x):
            xs = x / temperature if temperature else x
            return jax.nn.softmax(-xs, axis=axis)
        return fn
    register_op("softmin", softmin_maker)

    # SoftmaxOutput: forward=softmax over axis 1; the *gradient of data* is
    # (p - onehot(label))·grad_scale regardless of head gradient — the
    # reference's fused loss-layer contract (src/operator/softmax_output.cc).
    def softmax_output_maker(grad_scale=1.0, ignore_label=-1,
                             multi_output=False, use_ignore=False,
                             preserve_shape=False, normalization="null",
                             out_grad=False, smooth_alpha=0.0):
        @jax.custom_vjp
        def fwd(x, label):
            return jax.nn.softmax(x, axis=1)

        def fwd_fwd(x, label):
            p = fwd(x, label)
            return p, (p, label)

        def fwd_bwd(res, g):
            p, label = res
            lab = label.astype(jnp.int32)
            oh = jax.nn.one_hot(lab, p.shape[1], dtype=p.dtype)
            if p.ndim > 2:
                # (N, C, d...) with label (N, d...): move class axis last
                perm = (0,) + tuple(range(2, p.ndim)) + (1,)
                pm = jnp.transpose(p, perm)
                grad = pm - oh
                if use_ignore:
                    mask = (lab != ignore_label)[..., None]
                    grad = jnp.where(mask, grad, 0.0)
                inv = tuple(_np.argsort(perm))
                grad = jnp.transpose(grad, inv)
            else:
                grad = p - oh
                if use_ignore:
                    grad = jnp.where((lab != ignore_label)[:, None], grad, 0.0)
            scale = grad_scale
            if normalization == "batch":
                scale = scale / p.shape[0]
            elif normalization == "valid" and use_ignore:
                nvalid = jnp.maximum(jnp.sum(lab != ignore_label), 1)
                grad = grad / nvalid.astype(grad.dtype)
            return (grad * scale, jnp.zeros_like(label))

        fwd.defvjp(fwd_fwd, fwd_bwd)
        return fwd
    register_op("SoftmaxOutput", softmax_output_maker,
                aliases=("softmax_output", "SoftmaxActivation_out"))

    # ---- normalization ---------------------------------------------------
    def batchnorm_maker(eps=1e-3, momentum=0.9, fix_gamma=True,
                        use_global_stats=False, output_mean_var=False,
                        axis=1, cudnn_off=None, _training=True):
        def fn(x, gamma, beta, moving_mean, moving_var):
            ax = axis % x.ndim
            reduce_axes = tuple(i for i in range(x.ndim) if i != ax)
            bshape = [1] * x.ndim
            bshape[ax] = x.shape[ax]
            g = jnp.ones_like(gamma) if fix_gamma else gamma
            if _training and not use_global_stats:
                xf = x.astype(jnp.float32)
                mean = jnp.mean(xf, axis=reduce_axes)
                var = jnp.mean(jnp.square(xf), axis=reduce_axes) - \
                    jnp.square(mean)
                new_mean = momentum * moving_mean + (1 - momentum) * mean
                new_var = momentum * moving_var + (1 - momentum) * var
            else:
                mean, var = moving_mean, moving_var
                new_mean, new_var = moving_mean, moving_var
            inv = lax.rsqrt(var.astype(x.dtype) + jnp.asarray(eps, x.dtype))
            out = (x - mean.astype(x.dtype).reshape(bshape)) * \
                (inv * g.astype(x.dtype)).reshape(bshape) + \
                beta.astype(x.dtype).reshape(bshape)
            return (out, new_mean, new_var)
        return fn
    register_op("BatchNorm", batchnorm_maker, aliases=("batch_norm",))

    def batchnorm_v1_maker(eps=1e-3, momentum=0.9, fix_gamma=True,
                           use_global_stats=False, output_mean_var=False,
                           _training=True):
        # reference src/operator/batch_norm_v1.cc: the pre-0.12 op — NCHW
        # only (channel axis 1), no cudnn/axis options; kept because
        # legacy symbol JSON files reference it by name
        return batchnorm_maker(eps=eps, momentum=momentum,
                               fix_gamma=fix_gamma,
                               use_global_stats=use_global_stats,
                               axis=1, _training=_training)
    register_op("BatchNorm_v1", batchnorm_v1_maker,
                ref="src/operator/batch_norm_v1.cc")


    def layernorm_maker(axis=-1, eps=1e-5, output_mean_var=False):
        def fn(x, gamma, beta):
            mean = jnp.mean(x, axis=axis, keepdims=True)
            var = jnp.mean(jnp.square(x - mean), axis=axis, keepdims=True)
            inv = lax.rsqrt(var + jnp.asarray(eps, x.dtype))
            shape = [1] * x.ndim
            shape[axis % x.ndim] = x.shape[axis % x.ndim]
            out = (x - mean) * inv * gamma.reshape(shape) + beta.reshape(shape)
            if output_mean_var:
                return (out, jnp.squeeze(mean, axis), jnp.squeeze(var, axis))
            return out
        return fn
    register_op("LayerNorm", layernorm_maker, aliases=("layer_norm",))

    def rmsnorm_maker(axis=-1, eps=1e-5):
        """x * rsqrt(mean(x^2) + eps) * gamma over ``axis``; the mean of
        squares is taken in float32 whatever x is kept in."""
        def fn(x, gamma):
            xf = x.astype(jnp.float32)
            inv = lax.rsqrt(jnp.mean(jnp.square(xf), axis=axis,
                                     keepdims=True) + eps)
            shape = [1] * x.ndim
            shape[axis % x.ndim] = x.shape[axis % x.ndim]
            return (xf * inv * gamma.reshape(shape)).astype(x.dtype)
        return fn
    register_op("RMSNorm", rmsnorm_maker, aliases=("rms_norm",))

    def rope_maker(base=10000.0, rotary_dim=None, seq_axis=1, scaling=None):
        """Rotary position embedding, rotate-half convention, on the last
        ``rotary_dim`` lanes of the last axis (all of them by default);
        the lanes before pass through.  Position i is index i of
        ``seq_axis``.  ``scaling``: None, or YaRN's ``rope_scaling`` (a
        mapping or its (key, value) pairs; ``yarn_frequencies``) for the
        frequencies and the factor on cos and sin."""
        yarn = None if scaling is None else dict(scaling)
        if yarn is not None and yarn.pop("type", "yarn") != "yarn":
            raise ValueError(f"rope scaling {dict(scaling)['type']!r} is "
                             "not 'yarn'")

        def fn(x):
            d = x.shape[-1]
            r = d if rotary_dim is None else int(rotary_dim)
            ax = seq_axis % x.ndim
            if yarn is None:
                inv = float(base) ** (-jnp.arange(0, r, 2, dtype=jnp.float32)
                                      / r)
            else:
                inv, mscale = yarn_frequencies(r, float(base), yarn)
                inv = jnp.asarray(inv, jnp.float32)
            ang = jnp.arange(x.shape[ax], dtype=jnp.float32)[:, None] * inv
            shape = [1] * x.ndim
            shape[ax], shape[-1] = x.shape[ax], r
            cos = jnp.tile(jnp.cos(ang), (1, 2)).reshape(shape)
            sin = jnp.tile(jnp.sin(ang), (1, 2)).reshape(shape)
            if yarn is not None and mscale != 1.0:
                cos, sin = cos * mscale, sin * mscale
            keep, rot = x[..., :d - r], x[..., d - r:].astype(jnp.float32)
            half = jnp.concatenate([-rot[..., r // 2:], rot[..., :r // 2]],
                                   axis=-1)
            out = (rot * cos + half * sin).astype(x.dtype)
            return out if r == d else jnp.concatenate([keep, out], axis=-1)
        return fn
    register_op("_contrib_rope", rope_maker, aliases=("rope",))

    def groupnorm_maker(num_groups=1, eps=1e-5, output_mean_var=False):
        def fn(x, gamma, beta):
            # (N, C, ...) -> stats per (N, group); gamma/beta are
            # PER-GROUP, shape (num_groups,) — the reference convention
            # (src/operator/nn/group_norm.cc), unlike torch's
            # per-channel affine
            n, c = x.shape[0], x.shape[1]
            g = int(num_groups)
            rest = x.shape[2:]
            xg = x.reshape((n, g, c // g) + rest)
            axes = tuple(range(2, xg.ndim))
            mean = jnp.mean(xg, axis=axes, keepdims=True)
            var = jnp.mean(jnp.square(xg - mean), axis=axes,
                           keepdims=True)
            out = (xg - mean) * lax.rsqrt(var + jnp.asarray(eps, x.dtype))
            bshape = (1, g, 1) + (1,) * len(rest)
            out = out * gamma.reshape(bshape) + beta.reshape(bshape)
            out = out.reshape(x.shape)
            if output_mean_var:
                return (out, mean.reshape(n, g), var.reshape(n, g))
            return out
        return fn
    register_op("GroupNorm", groupnorm_maker, aliases=("group_norm",))

    def lrn_maker(alpha=1e-4, beta=0.75, knorm=2.0, nsize=5):
        half = int(nsize) // 2

        def fn(x):
            # cross-channel local response normalization (reference:
            # src/operator/nn/lrn.cc): square, box-sum over the channel
            # window, scale.  Asymmetric pad keeps the channel dim for
            # even nsize too.
            sq = jnp.square(x)
            pad = [(0, 0)] * x.ndim
            pad[1] = (half, int(nsize) - 1 - half)
            acc = lax.reduce_window(
                sq, jnp.asarray(0, x.dtype), lax.add,
                (1, int(nsize)) + (1,) * (x.ndim - 2),
                (1,) * x.ndim,
                pad)
            # reference normalizes alpha by the window size (cuDNN
            # convention, same as torch LocalResponseNorm)
            return x / jnp.power(knorm + (alpha / nsize) * acc, beta)
        return fn
    register_op("LRN", lrn_maker, aliases=("lrn",))

    def instancenorm_maker(eps=1e-3):
        def fn(x, gamma, beta):
            axes = tuple(range(2, x.ndim))
            mean = jnp.mean(x, axis=axes, keepdims=True)
            var = jnp.mean(jnp.square(x - mean), axis=axes, keepdims=True)
            inv = lax.rsqrt(var + jnp.asarray(eps, x.dtype))
            shape = (1, -1) + (1,) * (x.ndim - 2)
            return (x - mean) * inv * gamma.reshape(shape) + \
                beta.reshape(shape)
        return fn
    register_op("InstanceNorm", instancenorm_maker, aliases=("instance_norm",))

    def l2norm_maker(eps=1e-10, mode="instance"):
        def fn(x):
            if mode == "instance":
                axes = tuple(range(1, x.ndim))
                keep = True
            elif mode == "channel":
                axes = (1,)
                keep = True
            else:  # spatial
                axes = tuple(range(2, x.ndim))
                keep = True
            norm = jnp.sqrt(jnp.sum(jnp.square(x), axis=axes, keepdims=keep)
                            + eps)
            return x / norm
        return fn
    register_op("L2Normalization", l2norm_maker, aliases=("l2_normalization",))

    # ---- dropout (key passed as input; applied only when layer says so) --
    def dropout_maker(p=0.5, mode="training", axes=(), cudnn_off=None):
        def fn(x, key):
            if p <= 0.0:
                return x
            kp = 1.0 - p
            shape = list(x.shape)
            for a in axes:
                shape[a] = 1
            mask = jax.random.bernoulli(key, kp, tuple(shape))
            return jnp.where(mask, x / kp, 0.0).astype(x.dtype)
        return fn
    register_op("Dropout", dropout_maker, aliases=("dropout",),
                needs_rng=True)

    # ---- resize / upsample ----------------------------------------------
    def upsampling_maker(scale=1, num_filter=0, sample_type="nearest",
                         multi_input_mode="concat", num_args=1,
                         workspace=None):
        def fn(*xs):
            x = xs[0]
            if sample_type == "nearest":
                y = jnp.repeat(jnp.repeat(x, scale, axis=2), scale, axis=3)
                return y
            b, c, h, w = x.shape
            return jax.image.resize(x, (b, c, h * scale, w * scale),
                                    method="linear")
        return fn
    register_op("UpSampling", upsampling_maker, aliases=("upsampling",))

    def bilinear_resize_maker(height=None, width=None, scale_height=None,
                              scale_width=None, mode="size",
                              align_corners=True):
        # align_corners=True is the reference kernel's coordinate mapping
        # (bilinear_resize.cc: src = dst*(in-1)/(out-1)); jax.image.resize
        # only offers half-pixel centers, so that path is hand-gathered
        def fn(x):
            b, c, h, w = x.shape
            nh = height if height else int(h * scale_height)
            nw = width if width else int(w * scale_width)
            if not align_corners:
                return jax.image.resize(x, (b, c, nh, nw), method="linear")
            ys = (jnp.linspace(0.0, h - 1.0, nh) if nh > 1
                  else jnp.zeros((1,)))
            xs = (jnp.linspace(0.0, w - 1.0, nw) if nw > 1
                  else jnp.zeros((1,)))
            y0 = jnp.floor(ys).astype(jnp.int32)
            x0 = jnp.floor(xs).astype(jnp.int32)
            y1 = jnp.minimum(y0 + 1, h - 1)
            x1 = jnp.minimum(x0 + 1, w - 1)
            wy = (ys - y0).astype(x.dtype)[None, None, :, None]
            wx = (xs - x0).astype(x.dtype)[None, None, None, :]
            rows0, rows1 = jnp.take(x, y0, axis=2), jnp.take(x, y1, axis=2)
            r0 = jnp.take(rows0, x0, axis=3) * (1 - wx) \
                + jnp.take(rows0, x1, axis=3) * wx
            r1 = jnp.take(rows1, x0, axis=3) * (1 - wx) \
                + jnp.take(rows1, x1, axis=3) * wx
            return r0 * (1 - wy) + r1 * wy
        return fn
    register_op("BilinearResize2D", bilinear_resize_maker,
                aliases=("_contrib_BilinearResize2D",))

    # ---- RNN (fused multi-layer LSTM/GRU/tanh/relu over lax.scan) -------
    # Reference: src/operator/rnn.cc (cuDNN-fused); the TPU-native form is a
    # scan whose per-step cell is one fused matmul pair on the MXU.
    def rnn_maker(state_size=0, num_layers=1, mode="lstm",
                  bidirectional=False, p=0.0, state_outputs=False,
                  projection_size=None, use_sequence_length=False,
                  lstm_state_clip_min=None, lstm_state_clip_max=None,
                  lstm_state_clip_nan=False):
        ngates = {"lstm": 4, "gru": 3, "rnn_tanh": 1, "rnn_relu": 1}[mode]
        ndir = 2 if bidirectional else 1

        def cell_step(mode_, W_x, W_h, b_x, b_h, x_t, h, c):
            gx = x_t @ W_x.T + b_x
            gh = h @ W_h.T + b_h
            if mode_ == "lstm":
                gates = gx + gh
                i, f, g, o = jnp.split(gates, 4, axis=-1)
                i, f, o = jax.nn.sigmoid(i), jax.nn.sigmoid(f), jax.nn.sigmoid(o)
                g = jnp.tanh(g)
                c_new = f * c + i * g
                h_new = o * jnp.tanh(c_new)
                return h_new, c_new
            if mode_ == "gru":
                # cuDNN GRU formulation: r,z from summed gates; n uses r*(Whn h)
                rx, zx, nx = jnp.split(gx, 3, axis=-1)
                rh, zh, nh = jnp.split(gh, 3, axis=-1)
                r = jax.nn.sigmoid(rx + rh)
                z = jax.nn.sigmoid(zx + zh)
                n = jnp.tanh(nx + r * nh)
                h_new = (1 - z) * n + z * h
                return h_new, c
            act = jnp.tanh if mode_ == "rnn_tanh" else (
                lambda v: jnp.maximum(v, 0))
            h_new = act(gx + gh)
            return h_new, c

        def fn(data, params, state, *maybe_cell):
            # data: (T, N, I); params: flat packed like cuDNN; state: (L*D,N,H)
            T, N, I = data.shape
            H = state_size
            state_c = maybe_cell[0] if mode == "lstm" else None
            offset = 0

            def take(n):
                nonlocal offset
                v = lax.dynamic_slice(params, (offset,), (n,))
                offset += n
                return v

            outs = data
            h_states, c_states = [], []
            layer_in_size = I
            for layer in range(num_layers):
                dir_outs = []
                for d in range(ndir):
                    li = layer * ndir + d
                    Wx = take(ngates * H * layer_in_size).reshape(
                        ngates * H, layer_in_size)
                    Wh = take(ngates * H * H).reshape(ngates * H, H)
                    bx = take(ngates * H)
                    bh = take(ngates * H)
                    h0 = state[li]
                    c0 = state_c[li] if state_c is not None else \
                        jnp.zeros_like(h0)
                    seq = outs if d == 0 else jnp.flip(outs, axis=0)

                    def step(carry, x_t, Wx=Wx, Wh=Wh, bx=bx, bh=bh):
                        h, c = carry
                        h2, c2 = cell_step(mode, Wx, Wh, bx, bh, x_t, h, c)
                        return (h2, c2), h2

                    (hT, cT), ys = lax.scan(step, (h0, c0), seq)
                    if d == 1:
                        ys = jnp.flip(ys, axis=0)
                    dir_outs.append(ys)
                    h_states.append(hT)
                    c_states.append(cT)
                outs = dir_outs[0] if ndir == 1 else jnp.concatenate(
                    dir_outs, axis=-1)
                layer_in_size = H * ndir
            hN = jnp.stack(h_states)
            if mode == "lstm":
                return (outs, hN, jnp.stack(c_states))
            return (outs, hN)
        return fn
    register_op("RNN", rnn_maker, aliases=("rnn",))

    # cuDNN-compatible packed param size helper used by gluon.rnn
    def rnn_param_size(mode, num_layers, input_size, hidden_size,
                      bidirectional=False):
        ngates = {"lstm": 4, "gru": 3, "rnn_tanh": 1, "rnn_relu": 1}[mode]
        ndir = 2 if bidirectional else 1
        total = 0
        lin = input_size
        for _ in range(num_layers):
            for _ in range(ndir):
                total += ngates * hidden_size * lin
                total += ngates * hidden_size * hidden_size
                total += 2 * ngates * hidden_size
            lin = hidden_size * ndir
        return total
    globals()["rnn_param_size"] = rnn_param_size


_register()
