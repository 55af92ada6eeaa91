"""Mixture-of-Experts layers with expert parallelism.

BEYOND reference parity: the 2018-era reference has no MoE (SURVEY.md
§2.3 lists EP as absent), but the build mandate makes distributed
first-class, so the framework ships TPU-native expert layers.

``SparseMoE`` is the design: an expert layer that is TOLD WHICH EXPERTS IT
HOLDS (``experts_held=(first, count)``, one chip's share of an
expert-parallel deployment), routes every token over all ``num_experts``
by one of the two routers the architectures have (``score="sigmoid"``:
sigmoid scores, selection by score plus a non-gradient bias, top-k,
renormalised, scaled; ``score="softmax"``: the top-k logits and the
softmax over them, no bias, no scale), and computes its own experts' part
of the result for the tokens routed to them, dropping none: the
token-assignments are sorted by expert, the rows of the held experts
gathered into one buffer and multiplied group by group
(``jax.lax.ragged_dot`` over the stacked expert weights; gated by SiLU or
by ReLU, as the architecture says), weighted and added back into their
tokens' rows.  The buffer is the worst case and the work is the live rows':
the gather in, the weighted scatter-add back and the backward of each are
loops over row tiles that end at the held experts' load, which the device
knows (``_row_movers``); the rows behind it are neither read nor written.
The router reads the experts' input, or another array of the same rows where
the block hands it one (a router that stands before attention reads the
block's input while the experts read the normed state after it).  A shared
expert runs beside them on every token where the architecture has one.
What the experts held elsewhere would add is left out; on one chip the
layer runs without its exchange.  The buffer has ``top_k`` x tokens rows,
one for every assignment, so none can fail to fit.

``MoEFFN`` is the older Switch/GShard dense-dispatch form (top-1, a
capacity limit, one-hot matmuls), kept for the ``ep`` mesh axis: under
``pjit`` with its expert-stacked weights sharded ``P('ep', ...)`` XLA
inserts the dispatch/combine all-to-alls over ICI itself.

    rules = ShardingRules(EP_RULES() + TP_RULES)
"""
from __future__ import annotations

import functools
import math

from ..gluon.block import HybridBlock

__all__ = ["MoEFFN", "SparseMoE", "routed_experts", "publish_routing",
           "EP_RULES"]


def EP_RULES():
    """ShardingRules entries placing stacked expert weights on 'ep'."""
    from jax.sharding import PartitionSpec as P
    return [(r".*expert_w[12]$", P("ep", None, None))]


class MoEFFN(HybridBlock):
    """Switch-style MoE feed-forward: router → top-1 dispatch (capacity
    limited) → per-expert FFN → weighted combine.

    Parameters
    ----------
    units : model dim D (input and output).
    hidden_size : per-expert FFN hidden dim H.
    num_experts : E — shard this axis over the 'ep' mesh axis.
    capacity_factor : per-expert slots = ceil(tokens/E * factor); tokens
        over capacity pass through the residual (standard Switch drop).
    """

    def __init__(self, units, hidden_size, num_experts,
                 capacity_factor=1.25, activation="relu", prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        self._units = units
        self._hidden = hidden_size
        self._E = num_experts
        self._cap_factor = capacity_factor
        self._act = activation
        with self.name_scope():
            self.router = self.params.get(
                "router", shape=(units, num_experts), init="xavier")
            self.expert_w1 = self.params.get(
                "expert_w1", shape=(num_experts, units, hidden_size),
                init="xavier")
            self.expert_w2 = self.params.get(
                "expert_w2", shape=(num_experts, hidden_size, units),
                init="xavier")

    def hybrid_forward(self, F, x, router, expert_w1, expert_w2):
        # x: (B, S, D) -> tokens (N, D)
        B, S, D = x.shape
        E = self._E
        N = B * S
        C = max(1, math.ceil(N / max(E, 1) * self._cap_factor))
        tok = F.reshape(x, shape=(N, D))

        logits = F.dot(tok, router)                     # (N, E)
        probs = F.softmax(logits, axis=-1)
        eidx = F.argmax(probs, axis=-1)                 # (N,)
        gate = F.max(probs, axis=-1)                    # (N,) top-1 prob
        onehot = F.one_hot(eidx, depth=E)                     # (N, E)

        # position of each token within its expert's queue
        pos = F.cumsum(onehot, axis=0) * onehot         # 1-based ranks
        keep = (pos <= C) * onehot                      # capacity mask
        posC = F.one_hot(
            F.where(keep > 0, pos - 1, F.ones_like(pos) * C),
            depth=C)                                    # (N, E, C)
        dispatch = posC * F.reshape(keep, shape=(N, E, 1))    # (N, E, C)

        # dispatch: (E*C, N) @ (N, D) -> (E, C, D); MXU matmuls only
        disp2 = F.transpose(F.reshape(dispatch, shape=(N, E * C)))
        expert_in = F.reshape(F.dot(disp2, tok), shape=(E, C, D))
        h = F.batch_dot(expert_in, expert_w1)           # (E, C, H)
        h = F.Activation(h, act_type=self._act)
        expert_out = F.batch_dot(h, expert_w2)          # (E, C, D)

        # combine, weighted by the gate prob of kept tokens
        combine = dispatch * F.reshape(gate, shape=(N, 1, 1))
        out = F.dot(F.reshape(combine, shape=(N, E * C)),
                    F.reshape(expert_out, shape=(E * C, D)))  # (N, D)
        # dropped (over-capacity) tokens pass through as residual zeros;
        # standard Switch keeps the residual connection outside this block
        return F.reshape(out, shape=(B, S, D))


#: bytes of one row tile of the movers below (``_row_tile``)
_ROW_TILE_BYTES = 4 << 20


def _row_tile(rows, width, itemsize):
    """Rows of the dispatch buffer that one iteration of a row mover takes:
    the whole sublanes (eights of rows) whose bytes fit ``_ROW_TILE_BYTES``,
    and no more than the buffer has."""
    return min(rows, max(8, _ROW_TILE_BYTES // (width * itemsize) // 8 * 8))


@functools.lru_cache(maxsize=None)
def _row_movers(tokens, tile):
    """``(dispatch, combine)``: the two passes between a (``tokens``, D)
    token array and the (R, D) dispatch buffer, each bounded by the live
    count and not by R (built lazily so that importing this module never
    imports jax).

    ``dispatch(x, token, n_live)``: ``rows[i] = x[token[i]]`` for ``i <
    n_live``, nought beyond, handed out twice, once for each grouped matmul
    that reads it: the two gradients then come back apart and are added
    tile by tile, where jax would add them over the whole buffer first.
    ``combine(out, wgt, token, n_live)``:
    ``y[token[i]] += wgt[i] * out[i]`` over ``i < n_live``; what ``out``
    holds beyond may be anything.  Each is a ``lax.while_loop`` over tiles
    of ``tile`` rows that ends with the tile that holds row ``n_live - 1``;
    a trip count the device decides has no transpose, so each has a
    backward of its own, which is the other's loop: dispatch's adds both
    ``d_rows[i]`` into ``dx[token[i]]``, combine's gathers ``dy[token[i]]``
    for ``d_out`` and ``d_wgt``.  In the tile that straddles ``n_live`` the
    dead rows are cut off with ``where``, never multiplied by nought."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def sweep(rows, n_live, carry, body):
        # the last tile of a buffer that is no whole number of them is
        # moved back to end with the buffer: ``fresh`` are its rows that
        # the tile before has not had
        def step(state):
            i, carry = state
            start = jnp.minimum(i * tile, rows - tile)
            at = start + jnp.arange(tile)
            return i + 1, body(carry, start, at < n_live, at >= i * tile)
        return lax.while_loop(lambda state: state[0] * tile < n_live, step,
                              (jnp.int32(0), carry))[1]

    def tile_of(a, start):
        return lax.dynamic_slice_in_dim(a, start, tile)

    def gather(src, token, n_live, wgt=None, dot=None):
        """``wgt[i] * src[token[i]]`` for the live ``i`` into a zeroed
        buffer and, where ``dot`` (R, D) is given, ``<dot[i],
        src[token[i]]>`` beside it."""
        def body(carry, start, live, fresh):
            blk = src.at[tile_of(token, start)].get(
                mode="promise_in_bounds")
            out = blk if wgt is None else \
                blk * tile_of(wgt, start)[:, None].astype(blk.dtype)
            moved = (lax.dynamic_update_slice_in_dim(
                carry[0], jnp.where(live[:, None], out, 0), start, 0),)
            if dot is not None:
                along = jnp.sum((tile_of(dot, start) * blk)
                                .astype(wgt.dtype), axis=-1)
                moved += (lax.dynamic_update_slice_in_dim(
                    carry[1], jnp.where(live, along, 0), start, 0),)
            return moved
        rows = token.shape[0]
        carry = (jnp.zeros((rows,) + src.shape[1:], src.dtype),)
        if dot is not None:
            carry += (jnp.zeros((rows,), wgt.dtype),)
        return sweep(rows, n_live, carry, body)

    def scatter(srcs, token, n_live, wgt=None):
        """``out[token[i]] += wgt[i] * sum(src[i] for src in srcs)`` over
        the live ``i``."""
        def body(out, start, live, fresh):
            blk = sum(tile_of(src, start) for src in srcs)
            if wgt is not None:
                blk = blk * tile_of(wgt, start)[:, None].astype(blk.dtype)
            return out.at[tile_of(token, start)].add(
                jnp.where((live & fresh)[:, None], blk, 0),
                mode="promise_in_bounds")
        return sweep(token.shape[0], n_live, jnp.zeros(
            (tokens,) + srcs[0].shape[1:], srcs[0].dtype), body)

    def dispatch_fwd(x, token, n_live):
        rows = gather(x, token, n_live)[0]
        return (rows, rows), (token, n_live)

    def dispatch_bwd(res, d_rows):
        return scatter(d_rows, *res), None, None

    def combine_fwd(out, wgt, token, n_live):
        return scatter((out,), token, n_live, wgt), (out, wgt, token, n_live)

    def combine_bwd(res, dy):
        out, wgt, token, n_live = res
        return gather(dy, token, n_live, wgt, out) + (None, None)

    @jax.custom_vjp
    def dispatch(x, token, n_live):
        return dispatch_fwd(x, token, n_live)[0]

    @jax.custom_vjp
    def combine(out, wgt, token, n_live):
        return combine_fwd(out, wgt, token, n_live)[0]
    dispatch.defvjp(dispatch_fwd, dispatch_bwd)
    combine.defvjp(combine_fwd, combine_bwd)
    return dispatch, combine


def routed_experts(x, router_w, router_b, w_gate, w_up, w_down, *, top_k,
                   first=0, scale=1.0, norm_topk=True, score="sigmoid",
                   activation="silu", router_x=None):
    """The routed part of a sparse-expert layer on one chip's share.

    x (T, D); router_w (E, D) and router_b (E,) over ALL experts; w_gate,
    w_up (G, D, H) and w_down (G, H, D) the ``G`` experts held here, which
    are experts ``first .. first + G - 1``.  Returns ``(y (T, D), load
    (G,) token-assignments per held expert)``.  The dispatch buffer has
    ``top_k`` x T rows, which every assignment fits; that is the worst
    case, and the passes that move rows between ``x``, the buffer and ``y``
    (forward and backward) sweep row tiles up to ``sum(load)`` only
    (``_row_movers``; the gauges ``moe.buffer_rows`` and ``moe.row_tile``
    are set as this is traced).  The router runs in
    float32 at the highest precision whatever x is kept in: a rounded
    score flips selections.  It reads ``router_x`` (T, D) where that is
    given, else ``x``.

    ``score="sigmoid"``: the gates are the sigmoids of the logits, the
    selection is by gate plus ``router_b`` (which no gradient reaches),
    renormalised over the selected where ``norm_topk``, times ``scale``.
    ``score="softmax"``: the ``top_k`` largest logits are selected and the
    gates are the softmax over those (the softmax over all experts,
    renormalised over the selected); ``router_b``, ``norm_topk`` and
    ``scale`` take no part.  ``activation``: the experts' gating function,
    ``"silu"`` or ``"relu"``."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from ..observability.registry import registry

    if activation not in ("silu", "relu"):
        raise ValueError(f"activation {activation!r} is neither 'silu' nor "
                         "'relu'")
    act = getattr(jax.nn, activation)
    g = w_gate.shape[0]
    with jax.named_scope("router"):
        logits = jnp.einsum(
            "td,ed->te", (x if router_x is None else router_x)
            .astype(jnp.float32), router_w.astype(jnp.float32),
            precision=lax.Precision.HIGHEST)
        if score == "softmax":
            top, sel = lax.top_k(logits, top_k)
            gate = jax.nn.softmax(top, axis=-1)
        elif score == "sigmoid":
            s = jax.nn.sigmoid(logits)
            _, sel = lax.top_k(
                s + lax.stop_gradient(router_b.astype(jnp.float32)), top_k)
            gate = jnp.take_along_axis(s, sel, axis=-1)
            if norm_topk:
                gate = gate / (jnp.sum(gate, axis=-1, keepdims=True) + 1e-20)
            gate = gate * scale
        else:
            raise ValueError(f"score {score!r} is neither 'sigmoid' nor "
                             "'softmax'")
    with jax.named_scope("dispatch"):
        # a token-assignment's key is its expert's index among the held
        # ones; those of experts held elsewhere sort behind them all
        local = sel.reshape(-1) - first
        local = jnp.where((local >= 0) & (local < g), local, g)
        # counted by comparison: the scatter of top_k x T ones into g + 1
        # bins reads 0.94 ms on the chip at 98,304 assignments, this 0.21
        load = jnp.sum(local[:, None] == jnp.arange(g), axis=0,
                       dtype=jnp.int32)
        order = jnp.argsort(local, stable=True)
        n_live = jnp.sum(load)
        token = order // top_k
        tile = _row_tile(*token.shape, x.shape[1], x.dtype.itemsize)
        for name, value, doc in (
                ("buffer_rows", token.shape[0], "rows of the dispatch "
                 "buffer, one for every assignment"),
                ("row_tile", tile, "rows one iteration of a row mover "
                 "takes")):
            registry().gauge(f"moe.{name}", doc + ", last layer traced") \
                .set(value)
        dispatch, combine = _row_movers(x.shape[0], tile)
        # the buffer is the worst case, the work the live rows': rows
        # beyond the held experts' belong to no group, the grouped matmul
        # leaves them (and, in the backward, their gradient) unwritten, and
        # no pass here reads or writes a row tile that holds none but them
        rows, rows_up = dispatch(x, token, n_live)
    with jax.named_scope("experts"):
        h = lax.ragged_dot(rows, w_gate.astype(x.dtype), load)
        u = lax.ragged_dot(rows_up, w_up.astype(x.dtype), load)
        out = lax.ragged_dot(act(h) * u, w_down.astype(x.dtype), load)
    with jax.named_scope("combine"):
        wgt = jnp.take(gate.reshape(-1), order)
        y = combine(out, wgt, token, n_live)
    return y, load.astype(jnp.float32)


class SparseMoE(HybridBlock):
    """One chip's share of a sparse-expert feed-forward (module docstring):
    a top-k router over ``num_experts``, the ``experts_held`` routed
    experts as stacked gated-linear-unit weights, a shared SwiGLU expert
    beside them.  ``net(x)`` routes on ``x``; ``net(x, router_x)`` routes on
    ``router_x`` (the same leading shape) and feeds the experts ``x``.

    Parameters
    ----------
    units, hidden_size : model width, one routed expert's width.
    num_experts, top_k : the router's outputs and the experts a token takes.
    experts_held : ``(first, count)``, the routed experts computed here;
        all of them by default.
    shared_hidden : the shared expert's width; 0 for none.
    routed_scale, norm_topk : the sigmoid router's gates are renormalised
        over the selected experts (held here or not), then scaled.
    score, activation : what the architecture has (``routed_experts``):
        ``"sigmoid"`` gates selected with a bias, or the ``"softmax"`` over
        the selected logits; ``"silu"`` or ``"relu"`` gating in the routed
        experts.
    """

    def __init__(self, units, hidden_size, num_experts, top_k,
                 experts_held=None, shared_hidden=0, routed_scale=1.0,
                 norm_topk=True, score="sigmoid", activation="silu",
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        first, count = experts_held if experts_held is not None \
            else (0, num_experts)
        if not (0 <= first and count >= 1 and first + count <= num_experts):
            raise ValueError(f"experts_held {experts_held!r} is not a run "
                             f"of the {num_experts} experts")
        self._k, self._first, self._count = top_k, first, count
        self._scale, self._norm = routed_scale, norm_topk
        self._score, self._act = score, activation
        from ..observability.registry import registry
        for name, value, doc in (
                ("experts_routed", num_experts, "experts the router scores"),
                ("experts_held", count, "routed experts computed here"),
                ("top_k", top_k, "experts a token takes")):
            registry().gauge(f"moe.{name}", doc + ", last layer built") \
                .set(value)
        from ..gluon.nn import SwiGLU
        with self.name_scope():
            self.router_weight = self.params.get(
                "router_weight", shape=(num_experts, units))
            self.router_bias = self.params.get(
                "router_bias", shape=(num_experts,), init="zeros",
                grad_req="null")
            self.experts_gate = self.params.get(
                "experts_gate", shape=(count, units, hidden_size))
            self.experts_up = self.params.get(
                "experts_up", shape=(count, units, hidden_size))
            self.experts_down = self.params.get(
                "experts_down", shape=(count, hidden_size, units))
            # what the last step routed: overwritten by every forward in
            # training, as a BatchNorm statistic is
            self.expert_load = self.params.get(
                "expert_load", shape=(count,), init="zeros", grad_req="null")
            self.shared = SwiGLU(units, shared_hidden, prefix="shared_") \
                if shared_hidden else None

    def hybrid_forward(self, F, x, router_x=None, *, router_weight,
                       router_bias, experts_gate, experts_up, experts_down,
                       expert_load):
        from .. import autograd
        shape = x.shape
        tok = F.reshape(x, shape=(-1, shape[-1]))
        reads = () if router_x is None else (
            F.reshape(router_x, shape=(-1, shape[-1])),)
        y, load = F.routed_experts(
            tok, router_weight, router_bias, experts_gate, experts_up,
            experts_down, *reads, top_k=self._k, first=self._first,
            scale=self._scale, norm_topk=self._norm, score=self._score,
            activation=self._act)
        if autograd.is_training():
            expert_load._set_data(load._read())
        y = F.reshape(y, shape=shape)
        return y if self.shared is None else y + self.shared(x)


def publish_routing(trainer) -> dict:
    """What the last step of ``trainer`` (a ``ShardedTrainer``) wrote into
    its ``SparseMoE`` layers' aux buffers, as gauges: ``moe.expert_load_max``
    and ``moe.expert_load_mean`` (token-assignments per held expert, in the
    layer where max / mean is worst); ``moe.live_rows`` (rows of the
    dispatch buffer that held an assignment, in the layer with the most)
    and ``moe.rows_moved`` (what one pass of a row mover touched there:
    whole tiles of ``moe.row_tile`` rows up to the live count; the buffer
    itself has ``moe.buffer_rows``).  Waits for the step, so call it where
    the loss is read anyway.  Returns the four."""
    from ..observability.registry import registry
    aux = trainer.aux_values()
    loads = [v for k, v in aux.items() if k.endswith("expert_load")]
    worst = max(loads, key=lambda v: float(v.max()) / max(float(v.mean()),
                                                          1e-9))
    live = max(int(v.sum()) for v in loads)
    tile = int(registry().gauge("moe.row_tile").value) or 1
    load_doc = "token-assignments per held expert in the worst layer"
    rows_doc = "dispatch-buffer rows in the layer with the most live"
    out = {}
    for name, value, doc in (
            ("expert_load_max", float(worst.max()), load_doc),
            ("expert_load_mean", float(worst.mean()), load_doc),
            ("live_rows", live, rows_doc),
            ("rows_moved", tile * -(-live // tile), rows_doc)):
        registry().gauge(f"moe.{name}", "of the last step read: " + doc) \
            .set(value)
        out[name] = value
    return out
