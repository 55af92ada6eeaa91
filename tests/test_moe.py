"""Mixture-of-Experts + expert parallelism tests (beyond-reference
feature; the 'ep' axis of the driver's tp/pp/dp/sp/ep mandate).

Runs on the virtual 8-device CPU mesh from conftest.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
from mxnet_tpu.parallel.moe import EP_RULES, MoEFFN


def _dense_ref(moe, x):
    r = moe.router.data().asnumpy()
    w1 = moe.expert_w1.data().asnumpy()
    w2 = moe.expert_w2.data().asnumpy()
    B, S, D = x.shape
    tok = x.reshape(-1, D)
    logits = tok @ r
    p = np.exp(logits - logits.max(1, keepdims=True))
    p /= p.sum(1, keepdims=True)
    idx, gate = p.argmax(1), p.max(1)
    ref = np.zeros_like(tok)
    for n in range(tok.shape[0]):
        e = idx[n]
        ref[n] = gate[n] * (np.maximum(tok[n] @ w1[e], 0) @ w2[e])
    return ref.reshape(B, S, D)


def test_moe_matches_dense_reference():
    np.random.seed(0)
    moe = MoEFFN(8, 16, 4, capacity_factor=8.0)
    moe.initialize()
    x = np.random.randn(2, 6, 8).astype(np.float32)
    y = moe(mx.nd.array(x)).asnumpy()
    np.testing.assert_allclose(y, _dense_ref(moe, x), rtol=1e-4,
                               atol=1e-5)


def test_moe_capacity_drops_tokens():
    """With capacity 1 slot per expert, most tokens must be dropped to
    zero (the Switch overflow contract) — never mis-routed."""
    np.random.seed(1)
    moe = MoEFFN(4, 8, 2, capacity_factor=0.01)    # C == 1
    moe.initialize()
    x = np.random.randn(1, 10, 4).astype(np.float32)
    y = moe(mx.nd.array(x)).asnumpy().reshape(-1, 4)
    nonzero_rows = (np.abs(y).sum(1) > 1e-9).sum()
    assert nonzero_rows <= 2                      # <=1 token per expert


def test_moe_trains_and_experts_get_grads():
    np.random.seed(2)
    moe = MoEFFN(8, 16, 4, capacity_factor=4.0)
    moe.initialize()
    tr = gluon.Trainer(moe.collect_params(), "adam",
                       {"learning_rate": 1e-2})
    x = mx.nd.array(np.random.randn(4, 8, 8).astype(np.float32))
    tgt = mx.nd.array(np.random.randn(4, 8, 8).astype(np.float32))
    l0 = None
    for _ in range(15):
        with autograd.record():
            L = mx.nd.mean(mx.nd.square(moe(x) + x - tgt))
        L.backward()
        tr.step(4)
        if l0 is None:
            l0 = float(L.asnumpy())
    assert float(L.asnumpy()) < l0


def test_moe_expert_parallel_sharded_step():
    """Experts sharded over an 'ep' mesh axis inside the whole-step jit:
    compiles, runs, and matches the single-device forward."""
    import jax
    from jax.sharding import PartitionSpec as P
    from mxnet_tpu import parallel as par

    np.random.seed(3)

    net = gluon.nn.HybridSequential()
    with net.name_scope():
        net.add(MoEFFN(8, 16, 4, capacity_factor=8.0))
    net.initialize()
    x = np.random.randn(4, 6, 8).astype(np.float32)
    ref = net(mx.nd.array(x)).asnumpy()          # pre-sharding forward

    mesh = par.make_mesh({"dp": 2, "ep": 4},
                         devices=jax.devices()[:8])
    rules = par.ShardingRules(EP_RULES())
    tr = par.ShardedTrainer(
        net, lambda out, y: mx.nd.mean(mx.nd.square(out)), "sgd",
        {"learning_rate": 0.0}, mesh=mesh, rules=rules,
        data_spec=("dp",))
    loss = tr.step(x, np.zeros((4,), np.float32))
    assert np.isfinite(float(loss.asnumpy()))
    out = tr.forward(x)
    np.testing.assert_allclose(out.asnumpy(), ref, rtol=1e-4, atol=1e-5)
    # the expert weights really live sharded over 'ep'
    ew1 = tr._pvals[[p.name for p in tr._train_params]
                    .index(net[0].expert_w1.name)]
    spec = ew1.sharding.spec
    assert spec[0] == "ep", spec


# -- routed_experts: the two routers, the gating, the router's input -------------

def _routed_setup(seed=0, tokens=24, units=16, hidden=12, experts=8, held=3):
    rng = np.random.default_rng(seed)

    def draw(*shape, scale=1.0):
        return (scale * rng.standard_normal(shape)).astype(np.float32)
    return dict(x=draw(tokens, units), router_x=draw(tokens, units),
                router_w=draw(experts, units), router_b=draw(experts,
                                                             scale=0.3),
                w_gate=draw(held, units, hidden, scale=0.3),
                w_up=draw(held, units, hidden, scale=0.3),
                w_down=draw(held, hidden, units, scale=0.3))


def _routed_by_hand(a, top_k, first, score, activation, router_x=None,
                    scale=1.0):
    """The layer written out token by token in float64."""
    x = a["x"].astype(np.float64)
    rx = x if router_x is None else router_x.astype(np.float64)
    logits = rx @ a["router_w"].astype(np.float64).T
    held = a["w_gate"].shape[0]
    y, load = np.zeros_like(x), np.zeros(held)
    act = {"silu": lambda h: h / (1 + np.exp(-h)),
           "relu": lambda h: np.maximum(h, 0)}[activation]
    for t in range(x.shape[0]):
        if score == "softmax":
            sel = np.argsort(-logits[t])[:top_k]
            e = np.exp(logits[t][sel] - logits[t][sel].max())
            gates = e / e.sum()
        else:
            s = 1 / (1 + np.exp(-logits[t]))
            sel = np.argsort(-(s + a["router_b"]))[:top_k]
            gates = s[sel] / s[sel].sum() * scale
        for e_, g in zip(sel, gates):
            j = e_ - first
            if 0 <= j < held:
                load[j] += 1
                h = act(x[t] @ a["w_gate"][j]) * (x[t] @ a["w_up"][j])
                y[t] += g * (h @ a["w_down"][j])
    return y, load


@pytest.mark.parametrize("score,activation,own_input", [
    ("sigmoid", "silu", True), ("softmax", "relu", False),
    ("softmax", "silu", True), ("sigmoid", "relu", False)])
def test_routed_experts_routers_gatings_and_router_input(score, activation,
                                                         own_input):
    """Each router (sigmoid gates selected with the bias, renormalised and
    scaled; the softmax over the selected logits, no bias, no scale), each
    gating, and a router that reads another array than the experts do,
    against the layer written out token by token."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.parallel.moe import routed_experts
    a = _routed_setup()
    rx = None if own_input else a["router_x"]
    with jax.default_matmul_precision("highest"):
        y, load = routed_experts(
            *(jnp.asarray(a[k]) for k in ("x", "router_w", "router_b",
                                          "w_gate", "w_up", "w_down")),
            top_k=3, first=2, scale=1.7, score=score, activation=activation,
            router_x=None if rx is None else jnp.asarray(rx))
    want, want_load = _routed_by_hand(a, 3, 2, score, activation, rx, 1.7)
    np.testing.assert_allclose(np.asarray(y), want, atol=2e-5, rtol=1e-4)
    assert np.asarray(load).tolist() == want_load.tolist()
    assert want_load.sum() > 0


def test_routed_experts_softmax_gates_sum_to_one_and_take_no_bias():
    """Under ``score="softmax"`` the bias and the scale take no part and the
    gradient reaches the router through the gates alone."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.parallel.moe import routed_experts
    a = {k: jnp.asarray(v) for k, v in _routed_setup(held=8).items()}

    def layer(router_w, router_b, scale):
        return routed_experts(a["x"], router_w, router_b, a["w_gate"],
                              a["w_up"], a["w_down"], top_k=2, scale=scale,
                              score="softmax", activation="relu",
                              router_x=a["router_x"])[0]
    base = layer(a["router_w"], a["router_b"], 1.0)
    assert np.array_equal(np.asarray(base), np.asarray(
        layer(a["router_w"], a["router_b"] + 5.0, 3.0)))
    grad = jax.grad(lambda w: jnp.sum(layer(w, a["router_b"], 1.0) ** 2))(
        a["router_w"])
    assert float(jnp.abs(grad).max()) > 0
    with pytest.raises(ValueError, match="score"):
        routed_experts(a["x"], a["router_w"], a["router_b"], a["w_gate"],
                       a["w_up"], a["w_down"], top_k=2, score="tanh")
    with pytest.raises(ValueError, match="activation"):
        routed_experts(a["x"], a["router_w"], a["router_b"], a["w_gate"],
                       a["w_up"], a["w_down"], top_k=2, activation="gelu")


def _routed_experts_before(x, router_w, router_b, w_gate, w_up, w_down, *,
                           top_k, first=0, scale=1.0, norm_topk=True):
    """The function as it was before it took a score, an activation and a
    router input (a copy, to hold the defaults to)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    g = w_gate.shape[0]
    s = jax.nn.sigmoid(jnp.einsum(
        "td,ed->te", x.astype(jnp.float32),
        router_w.astype(jnp.float32), precision=lax.Precision.HIGHEST))
    _, sel = lax.top_k(
        s + lax.stop_gradient(router_b.astype(jnp.float32)), top_k)
    gate = jnp.take_along_axis(s, sel, axis=-1)
    if norm_topk:
        gate = gate / (jnp.sum(gate, axis=-1, keepdims=True) + 1e-20)
    gate = gate * scale
    local = sel.reshape(-1) - first
    local = jnp.where((local >= 0) & (local < g), local, g)
    load = jnp.zeros((g + 1,), jnp.int32).at[local].add(1)[:g]
    order = jnp.argsort(local, stable=True)
    live = jnp.arange(order.shape[0]) < jnp.sum(load)
    token = order // top_k
    rows = jnp.where(live[:, None], jnp.take(x, token, axis=0), 0)
    h = lax.ragged_dot(rows, w_gate.astype(x.dtype), load)
    u = lax.ragged_dot(rows, w_up.astype(x.dtype), load)
    out = lax.ragged_dot(jax.nn.silu(h) * u, w_down.astype(x.dtype), load)
    wgt = jnp.where(live, jnp.take(gate.reshape(-1), order), 0.0)
    out = jnp.where(live[:, None], out, 0) * wgt[:, None].astype(x.dtype)
    y = jnp.zeros_like(x).at[token].add(out)
    return y, load.astype(jnp.float32)


@pytest.mark.parametrize("held,first", [(3, 2), (8, 0)])
def test_routed_experts_defaults_are_what_they_were(held, first):
    """With no score, activation or router input given the function gives
    what the plain form gave before its row movers followed the live count
    (every row of the buffer gathered, masked, weighted and scatter-added),
    forward and the five gradients, within float32 rounding: a token's
    contributions may be added in another order.  ``held=8``: every
    assignment goes to a held expert, so every row of the buffer is live."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.parallel.moe import routed_experts
    a = _routed_setup(seed=4, held=held)
    args = tuple(jnp.asarray(a[k]) for k in (
        "x", "router_w", "router_b", "w_gate", "w_up", "w_down"))
    kw = dict(top_k=3, first=first, scale=1.8)

    def loss(fn):
        def f(*xs):
            y, load = fn(*xs, **kw)
            return jnp.sum(y ** 2), (y, load)
        return jax.value_and_grad(f, argnums=(0, 1, 3, 4, 5),
                                  has_aux=True)(*args)
    for fn in (routed_experts, lambda *xs, **k: routed_experts(
            *xs, score="sigmoid", activation="silu", router_x=None, **k)):
        ((_, (y, load)), grads), ((_, (want, want_load)), want_grads) = \
            loss(fn), loss(_routed_experts_before)
        assert np.array_equal(np.asarray(load), np.asarray(want_load))
        if held == 8:
            assert float(load.sum()) == 3 * a["x"].shape[0]
        np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)
        for g, w in zip(grads, want_grads):
            scale = float(np.abs(np.asarray(w)).max())
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=1e-5, atol=2e-6 * scale)


# -- the row movers: work bounded by the live count, not the buffer -------------

def _plain_dispatch(x, token, n_live):
    import jax.numpy as jnp
    live = jnp.arange(token.shape[0]) < n_live
    rows = jnp.where(live[:, None], jnp.take(x, token, axis=0), 0)
    return rows, rows


def _plain_combine(out, wgt, token, n_live, tokens):
    import jax.numpy as jnp
    live = jnp.arange(token.shape[0]) < n_live
    out = jnp.where(live[:, None], out, 0) * \
        jnp.where(live, wgt, 0.0)[:, None].astype(out.dtype)
    return jnp.zeros((tokens,) + out.shape[1:], out.dtype).at[token].add(out)


@pytest.mark.parametrize("way", ["eager", "jit", "checkpoint"])
@pytest.mark.parametrize("rows,tile,live", [
    (rows, tile, live)
    for rows, tile, lives in (
        (24, 8, (0, 1, 11, 16, 24)),     # a whole number of tiles
        (21, 8, (0, 1, 11, 16, 18, 21)),  # the last tile is moved back
        (6, 6, (0, 1, 3, 6)))             # shorter than the rule's tile
    for live in lives])
def test_row_movers_are_the_plain_forms(rows, tile, live, way):
    """``dispatch`` (which hands its rows out twice and takes a gradient
    for each) and ``combine`` against ``take`` / ``where`` / ``.at[].add``
    over the whole buffer: values and the gradients by ``x``, ``out`` and
    ``wgt``, with NaN in every row (and every cotangent row) at or beyond
    the live count."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.gluon.block import _keep_named
    from mxnet_tpu.parallel.moe import _row_movers, _row_tile
    assert _row_tile(6, 16, 4) == 6 and _row_tile(10 ** 6, 16, 4) % 8 == 0
    tokens, width = 10, 16
    rng = np.random.default_rng(rows * 100 + live)
    x = jnp.asarray(rng.standard_normal((tokens, width)), jnp.float32)
    token = jnp.asarray(rng.integers(0, tokens, rows), jnp.int32)
    dead = (np.arange(rows) >= live)
    out = jnp.asarray(np.where(dead[:, None], np.nan, rng.standard_normal(
        (rows, width))), jnp.float32)
    d_rows = tuple(jnp.asarray(np.where(
        dead[:, None], np.nan, rng.standard_normal((rows, width))),
        jnp.float32) for _ in range(2))
    wgt = jnp.asarray(rng.uniform(0.1, 1.0, rows), jnp.float32)
    dy = jnp.asarray(rng.standard_normal((tokens, width)), jnp.float32)
    dispatch, combine = _row_movers(tokens, tile)

    def both(dispatch, combine):
        def fn(x, out, wgt, n_live):
            return dispatch(x, token, n_live), combine(out, wgt, token,
                                                       n_live)
        if way == "checkpoint":
            fn = jax.checkpoint(fn, policy=_keep_named())

        def run(x, out, wgt, n_live):
            got, vjp = jax.vjp(lambda *a: fn(*a, n_live), x, out, wgt)
            return got, vjp((d_rows, dy))
        return (run if way == "eager" else jax.jit(run))(
            x, out, wgt, jnp.int32(live))
    (got, got_grads), (want, want_grads) = both(dispatch, combine), both(
        _plain_dispatch, lambda o, w, t, n: _plain_combine(o, w, t, n,
                                                           tokens))
    for g, w in zip(got[0], want[0]):
        assert np.array_equal(np.asarray(g), np.asarray(w))
    for g, w in zip(got[1:] + got_grads, want[1:] + want_grads):
        assert np.isfinite(np.asarray(g)).all()
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-6,
                                   atol=1e-6)


def test_no_pass_outside_a_loop_touches_the_whole_buffer():
    """The mechanism engages: in the traced layer, forward and gradient, no
    gather, scatter-add or select outside a ``while`` body reads or writes
    an array of ``top_k`` x T rows by D lanes, and a loop is there."""
    import jax
    import jax.numpy as jnp
    from jax.extend import core as jex_core
    from mxnet_tpu.parallel.moe import routed_experts
    a = _routed_setup(seed=2)
    args = tuple(jnp.asarray(a[k]) for k in (
        "x", "router_w", "router_b", "w_gate", "w_up", "w_down"))
    buffer = (3 * a["x"].shape[0], a["x"].shape[1])
    seen = {"while": 0, "moved": []}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "while":
                seen["while"] += 1
                continue
            if eqn.primitive.name in ("gather", "scatter-add", "scatter_add",
                                      "select_n") and any(
                    getattr(v.aval, "shape", None) == buffer
                    for v in list(eqn.invars) + list(eqn.outvars)):
                seen["moved"].append(str(eqn))
            for sub in jax.tree.leaves(
                    list(eqn.params.values()),
                    is_leaf=lambda p: isinstance(
                        p, (jex_core.Jaxpr, jex_core.ClosedJaxpr))):
                if isinstance(sub, jex_core.ClosedJaxpr):
                    walk(sub.jaxpr)
                elif isinstance(sub, jex_core.Jaxpr):
                    walk(sub)

    def layer(*xs):
        return jnp.sum(routed_experts(*xs, top_k=3, first=2)[0] ** 2)
    walk(jax.make_jaxpr(layer)(*args).jaxpr)
    assert seen["while"] == 2 and not seen["moved"], seen
    walk(jax.make_jaxpr(jax.grad(layer, argnums=(0, 1, 3, 4, 5)))(*args)
         .jaxpr)
    assert seen["while"] >= 6 and not seen["moved"], seen
