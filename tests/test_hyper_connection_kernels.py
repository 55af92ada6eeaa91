"""The hyper-connection kernels (``kernels/hyper_connection.py``) on the CPU
in interpret mode, at tiny shapes: two and four streams, a width that is a
lane multiple and one that is not, a token count that is not a multiple of
the tile.  The forward against the float64 equations of
``tests/_mhc_reference.py``; all six gradients against the ``jnp``
formulation that the kernels replaced (kept here as the oracle); the
Sinkhorn's ``eps``, which no reading of the benchmark can see."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from mxnet_tpu.kernels import hyper_connection as hc_mod
from tests import _mhc_reference as H

KW = dict(iters=20, eps=1e-6, clamp=(-30.0, 30.0))


# -- the oracle: the hyper-connection as jnp, X (..., n, C) ----------------------

def _sinkhorn(logits, iters, eps):
    m = jnp.exp(logits)
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
    return m


def _pre(x, phi, bias, alpha, gamma, *, iters, eps, clamp):
    n, c = x.shape[-2:]
    flat = x.reshape(x.shape[:-2] + (n * c,))
    normed = flat * lax.rsqrt(
        jnp.mean(jnp.square(flat), axis=-1, keepdims=True) + eps) * gamma
    proj = jnp.einsum("...i,oi->...o", normed, phi,
                      precision=lax.Precision.HIGHEST)
    h_pre = jax.nn.sigmoid(alpha[0] * proj[..., :n] + bias[:n])
    h_post = 2.0 * jax.nn.sigmoid(alpha[1] * proj[..., n:2 * n]
                                  + bias[n:2 * n])
    res = jnp.clip(alpha[2] * proj[..., 2 * n:] + bias[2 * n:], *clamp)
    h_res = _sinkhorn(res.reshape(res.shape[:-1] + (n, n)), iters, eps)
    x_in = sum(h_pre[..., j, None] * x[..., j, :] for j in range(n))
    return x_in, h_post, h_res


def _mix(x, y, h_post, h_res):
    out = h_post[..., None] * y[..., None, :]
    for j in range(x.shape[-2]):
        out = out + h_res[..., j, None] * x[..., None, j, :]
    return out


# -- the cases ----------------------------------------------------------------------

def _leaves(r, n, c):
    phi = 0.02 * r.normal(size=(n * n + 2 * n, n * c))
    bias = np.concatenate([r.normal(0, 0.5, 2 * n), r.normal(0, 1, n * n)])
    alpha = r.uniform(0.01, 0.1, 3)
    gamma = 1.0 + 0.02 * r.normal(size=n * c)
    return [jnp.asarray(a, jnp.float32) for a in (phi, bias, alpha, gamma)]


# (streams, width, tokens, a tile's token budget); 40 tokens at a tile of 16
# leave a last tile of 8
CASES = {"n2_lanes": (2, 128, (1, 40), None),
         "n4_padded": (4, 24, (2, 20), None),
         "n4_ragged_tiles": (4, 128, (1, 40), 1)}


@pytest.fixture(params=sorted(CASES))
def case(request, monkeypatch):
    n, c, lead, budget = CASES[request.param]
    if budget is not None:
        monkeypatch.setattr(hc_mod, "_BLOCK_BYTES", budget)
        assert hc_mod._tile(40, [(n * c, 4)]) == 16
    r = np.random.default_rng(sum(map(ord, request.param)))
    x = jnp.asarray(r.normal(size=lead + (n * c,)), jnp.float32)
    wf = jnp.asarray(0.3 * r.normal(size=(c, c)), jnp.float32)
    return n, c, x, _leaves(r, n, c), wf


def _program(x, phi, bias, alpha, gamma, wf):
    x_in, coef, x = hc_mod.pre(x, phi, bias, alpha, gamma, **KW)
    return hc_mod.mix(x, jnp.tanh(x_in @ wf), coef), x_in, coef


def test_the_kernels_are_the_equations(case):
    """x_in, h_post, H_res and the update against the float64 equations."""
    n, c, x, leaves, wf = case
    with jax.default_matmul_precision("highest"):
        out, x_in, coef = _program(x, *leaves, wf)
    x4 = np.asarray(x, np.float64).reshape(x.shape[:-1] + (n, c))
    f64 = [np.asarray(a, np.float64) for a in leaves]
    h_pre, h_post, h_res = H.coefficients(x4, *f64, KW["iters"], KW["eps"],
                                          KW["clamp"])
    want_in = np.einsum("...n,...nc->...c", h_pre, x4)
    got_pre, got_post, got_res = hc_mod.unpack(coef)
    for got, want in ((got_pre, h_pre), (got_post, h_post),
                      (got_res, h_res), (x_in, want_in)):
        np.testing.assert_allclose(np.asarray(got), want, atol=2e-6, rtol=0)
    want = H.sublayer(x4, lambda t: np.tanh(t @ np.asarray(wf, np.float64)),
                      *f64, **KW)
    np.testing.assert_allclose(np.asarray(out).reshape(want.shape), want,
                               atol=2e-5, rtol=0)


def test_the_kernels_gradients_are_the_jnp_ones(case):
    """The gradients of X, the sublayer's output y, phi, the bias, alpha
    and the gain against autodiff of the jnp formulation."""
    n, c, x, leaves, wf = case
    r = np.random.default_rng(7)
    y = jnp.asarray(r.normal(size=x.shape[:-1] + (c,)), jnp.float32)
    w = jnp.asarray(r.normal(size=x.shape), jnp.float32)
    w_in = jnp.asarray(r.normal(size=y.shape), jnp.float32)

    def program(x, y, *leaves):
        x_in, coef, x = hc_mod.pre(x, *leaves, **KW)
        return (jnp.sum(w * hc_mod.mix(x, jnp.tanh(x_in @ wf) + y, coef))
                + jnp.sum(w_in * x_in))

    def oracle(x, y, *leaves):
        x4 = x.reshape(x.shape[:-1] + (n, c))
        x_in, h_post, h_res = _pre(x4, *leaves, **KW)
        out = _mix(x4, jnp.tanh(x_in @ wf) + y, h_post, h_res)
        return jnp.sum(w * out.reshape(x.shape)) + jnp.sum(w_in * x_in)
    every = tuple(range(6))
    with jax.default_matmul_precision("highest"):
        got = jax.grad(program, argnums=every)(x, y, *leaves)
        want = jax.grad(oracle, argnums=every)(x, y, *leaves)
    for name, g, v in zip(("x", "y", "phi", "bias", "alpha", "gamma"), got,
                          want):
        v = np.asarray(v)
        np.testing.assert_allclose(np.asarray(g), v,
                                   atol=1e-5 * np.abs(v).max(), rtol=1e-4,
                                   err_msg=name)


def test_eps_reaches_the_sinkhorn_sums():
    """At hc_eps = 0.1, where a row's or a column's sum of order 1 moves by
    a tenth, the kernel's H_res is the equations' with eps in both sums of
    every step, and far from the one whose sums leave it out (the norm
    keeps it either way).  At the cell's 1e-6 no reading could tell."""
    r = np.random.default_rng(8)
    n, c, eps = 4, 128, 0.1
    x = jnp.asarray(r.normal(size=(16, n * c)), jnp.float32)
    leaves = _leaves(r, n, c)
    kw = dict(KW, eps=eps)
    got = np.asarray(hc_mod.unpack(hc_mod.pre(x, *leaves, **kw)[1])[2])
    x4 = np.asarray(x, np.float64).reshape(16, n, c)
    f64 = [np.asarray(a, np.float64) for a in leaves]
    want = H.coefficients(x4, *f64, kw["iters"], eps, kw["clamp"])[2]
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=0)
    logits = np.log(H.coefficients(x4, *f64, 0, eps, kw["clamp"])[2])
    assert np.abs(got - H.sinkhorn(logits, kw["iters"], 0.0)).max() > 0.01
