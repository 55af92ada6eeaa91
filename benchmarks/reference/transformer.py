"""Plain reference for the post-LN transformer families (BERT today).

Straightforward ``jax.numpy``: no kernel, no cache, no batching trick, and
nothing imported from the program under test.  The weights are made here,
from the seed, in one jitted call; ``benchmarks/models/<family>.py`` copies
them into the program, so the program and the reference start from the same
numbers and neither takes anything the other has made.

``dtype`` selects the arithmetic: ``float32`` runs every matmul at the
highest precision (the reference proper); ``bfloat16`` is the control, the
nearest precision below the float32 that the configurations state: the
weights and every activation are cast to bfloat16, the optimizer's state
and the master weights stay float32 (what a later PR would be tempted to
do).  PERF.md has the control's readings.
"""
import contextlib
import math

import jax
import jax.numpy as jnp

LN_EPS = 1e-5
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8
INIT_STD = 0.02


# -- weights -----------------------------------------------------------------

def layer_spec(i, units, hidden):
    p = f"l{i}."
    return {p + "qkv_w": (3 * units, units), p + "qkv_b": (3 * units,),
            p + "proj_w": (units, units), p + "proj_b": (units,),
            p + "ln1_g": (units,), p + "ln1_b": (units,),
            p + "ffn1_w": (hidden, units), p + "ffn1_b": (hidden,),
            p + "ffn2_w": (units, hidden), p + "ffn2_b": (units,),
            p + "ln2_g": (units,), p + "ln2_b": (units,)}


def seed_key(seed):
    """A PRNG key from any whole number up to 2**63 (seeds pass 2**31)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def init_weights(spec, seed):
    """Every leaf of ``spec`` (name -> shape), made on the device in one
    jitted call from one draw: N(0, 0.02) for matrices, embeddings and
    biases, and 1 + N(0, 0.02) for LayerNorm gains, so that no leaf is a
    constant the comparison could not see used."""
    names = sorted(spec)
    sizes = [math.prod(spec[n]) for n in names]

    def make(key):
        flat = INIT_STD * jax.random.normal(key, (sum(sizes),), jnp.float32)
        out, lo = {}, 0
        for name, size in zip(names, sizes):
            v = flat[lo:lo + size].reshape(spec[name])
            out[name] = 1.0 + v if name.endswith("_g") else v
            lo += size
        return out

    return jax.jit(make)(seed_key(seed))


# -- layers ------------------------------------------------------------------

@contextlib.contextmanager
def arithmetic(dtype):
    """Trace under the arithmetic of ``dtype``: yields the type the
    activations are kept in and sets the matmul precision."""
    dtype = jnp.dtype(dtype)
    prec = "highest" if dtype == jnp.float32 else "default"
    with jax.default_matmul_precision(prec):
        yield dtype


def _dense(x, w, b=None):
    y = jnp.einsum("...i,oi->...o", x, w.astype(x.dtype))
    return y if b is None else y + b.astype(x.dtype)


def _layer_norm(x, g, b):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + jnp.asarray(LN_EPS, x.dtype)) \
        * g.astype(x.dtype) + b.astype(x.dtype)


def _gelu(x):
    return jax.nn.gelu(x, approximate=False)


def post_ln_layer(w, p, x, keep, heads):
    """One post-LN layer.  x (B, S, U); keep (B, 1, S, S) or (B, 1, 1, S)
    bool, True where a query may see a key."""
    b, s, u = x.shape
    d = u // heads
    qkv = _dense(x, w[p + "qkv_w"], w[p + "qkv_b"])
    q, k, v = (t.reshape(b, s, heads, d).transpose(0, 2, 1, 3)
               for t in jnp.split(qkv, 3, axis=-1))
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(d)
    scores = jnp.where(keep, scores, jnp.asarray(-1e9, scores.dtype))
    att = jax.nn.softmax(scores.astype(jnp.float32), axis=-1) \
        .astype(x.dtype)
    out = jnp.einsum("bhqk,bhkd->bhqd", att, v)
    out = out.transpose(0, 2, 1, 3).reshape(b, s, u)
    x = _layer_norm(x + _dense(out, w[p + "proj_w"], w[p + "proj_b"]),
                    w[p + "ln1_g"], w[p + "ln1_b"])
    h = _gelu(_dense(x, w[p + "ffn1_w"], w[p + "ffn1_b"]))
    return _layer_norm(x + _dense(h, w[p + "ffn2_w"], w[p + "ffn2_b"]),
                       w[p + "ln2_g"], w[p + "ln2_b"])


def run_layers(w, x, keep, layers, heads, remat=False):
    layer = jax.checkpoint(post_ln_layer, static_argnums=(1, 4)) \
        if remat else post_ln_layer
    for i in range(layers):
        x = layer(w, f"l{i}.", x, keep, heads)
    return x


# -- Adam, as the configurations state it --------------------------------------

def adam_step(w, g, m, v, t, lr):
    """One Adam update of every leaf (no weight decay, bias-corrected lr;
    ``t`` is the step's number as a float32), float32 whatever the
    gradients were computed in."""
    coef = jnp.sqrt(1.0 - ADAM_B2 ** t) / (1.0 - ADAM_B1 ** t)
    new_w, new_m, new_v = {}, {}, {}
    for k in w:
        gk = g[k].astype(jnp.float32)
        new_m[k] = ADAM_B1 * m[k] + (1 - ADAM_B1) * gk
        new_v[k] = ADAM_B2 * v[k] + (1 - ADAM_B2) * jnp.square(gk)
        new_w[k] = w[k] - (lr * coef) * new_m[k] \
            / (jnp.sqrt(new_v[k]) + ADAM_EPS)
    return new_w, new_m, new_v
