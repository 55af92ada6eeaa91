"""The plain reference of the manifold-constrained hyper-connections and of
YaRN's rotary tables for the tier-1 tests, in numpy float64: written out
from the equations (mHC, DeepSeek-AI 2025; YaRN in DeepSeek-V3's form),
nothing imported from the program under test or from the benchmark's
reference, so that the two are each held to it.  ``X`` is the (..., n, C)
state of a token's streams."""
import math

import numpy as np


def sinkhorn(logits, iters, eps):
    m = np.exp(logits)
    for _ in range(iters):
        m = m / (m.sum(axis=-1, keepdims=True) + eps)
        m = m / (m.sum(axis=-2, keepdims=True) + eps)
    return m


def coefficients(x, phi, bias, alpha, gamma, iters, eps, clamp):
    """``(h_pre (..., n), h_post (..., n), H_res (..., n, n))``; ``phi``
    (n^2 + 2n, n C) rows pre, post, res."""
    n = x.shape[-2]
    flat = x.reshape(x.shape[:-2] + (-1,)).astype(np.float64)
    normed = flat / np.sqrt((flat ** 2).mean(-1, keepdims=True) + eps) * gamma
    proj = normed @ np.asarray(phi, np.float64).T
    sig = lambda t: 1.0 / (1.0 + np.exp(-t))       # noqa: E731
    h_pre = sig(alpha[0] * proj[..., :n] + bias[:n])
    h_post = 2.0 * sig(alpha[1] * proj[..., n:2 * n] + bias[n:2 * n])
    res = np.clip(alpha[2] * proj[..., 2 * n:] + bias[2 * n:], *clamp)
    return h_pre, h_post, sinkhorn(res.reshape(res.shape[:-1] + (n, n)),
                                   iters, eps)


def sublayer(x, fn, phi, bias, alpha, gamma, iters=20, eps=1e-6,
             clamp=(-30.0, 30.0)):
    """``H_res X + h_post fn(h_pre^T X)^T``."""
    h_pre, h_post, h_res = coefficients(x, phi, bias, alpha, gamma, iters,
                                        eps, clamp)
    y = fn(np.einsum("...n,...nc->...c", h_pre, x))
    return np.einsum("...mn,...nc->...mc", h_res, x) \
        + h_post[..., None] * y[..., None, :]


def yarn_inverse_frequencies(dim, base, factor, orig, beta_fast, beta_slow):
    """``theta_i`` blended towards ``theta_i / factor`` by the linear ramp
    between the correction dimensions of ``beta_fast`` and ``beta_slow``
    rotations at ``orig`` positions; also returns the ramp's ends."""
    def correction(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))
    low = max(math.floor(correction(beta_fast)), 0)
    high = min(math.ceil(correction(beta_slow)), dim - 1)
    i = np.arange(dim // 2, dtype=np.float64)
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
    theta = base ** (-2.0 * i / dim)
    return theta * (1.0 - ramp) + theta / factor * ramp, (low, high)


def yarn_mscale(factor, mscale):
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0
