"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py              # one TPU chip: train, imperative, serve
    python chip_smoke.py --chips 4    # four chips: the sharded step only

Drives the three user-facing paths once through their normal entry points,
each at the full width of a model the repo supports (random weights from
``--seed``), and checks what comes out by the repo's own means:

- train / ResNet-50 — ``resnet50_v1`` -> ``amp.init("bfloat16")`` ->
  ``parallel.ShardedTrainer`` (sgd+momentum), batch 128 at 224x224;
- train / BERT-base — MLM+NSP loss with per-row valid lengths, batch 8,
  seq 256, attention on its default selector (the Pallas flash kernel), and
  the kernel against the full-softmax XLA result at those shapes and at
  the benchmark cell's (batch 16, seq 512, its valid lengths), and over
  tokens-major operands (heads as blocks of the lanes) at the three
  benchmark cells' shapes against the heads-first call;
- imperative / Gluon MLP — un-hybridized 784-128-64-10 inside
  ``with mx.tpu(0):``, ``gluon.Trainer`` sgd+momentum (the ``multi_sgd``
  Mosaic kernel), bulked segments;
- serve / CausalLM at BERT-base widths on ``mx.tpu(0)`` -> ``ModelRegistry``
  -> ``GenerationServer`` -> ``HttpFrontend``, requests over the socket,
  greedy tokens against the whole-sequence forward.

``--chips 4`` runs only ResNet-50 bf16 under ``ShardedTrainer(zero_stage=1)``
on ``make_mesh({"dp": 4})`` against the same model and batch at dp=1.

One process, no child that touches JAX.  Any phase that raises, or whose
outputs are not resident on a TPU device, ends the run with a non-zero exit
and no result line; so does a machine where JAX finds no TPU.  The last
line of stdout is ``{"ok": true, "device": {...}}``.  Every time printed
here is a host-clock time around ``block_until_ready`` work on the device
named in the first line; compile seconds include the first step.
"""
import argparse
import http.client
import json
import os
import socket
import sys
import time
import warnings

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import autograd, gluon, parallel as par  # noqa: E402
from mxnet_tpu.contrib import amp  # noqa: E402
from mxnet_tpu.tuning import compile_cache  # noqa: E402

# bf16 tolerances, stated once.  A bf16 value carries 8 significant bits
# (half an ulp is 2^-9 of the value).
FLASH_BF16_ATOL = 1e-2      # kernel output rounded to bf16, |out| of order 1
FLASH_BF16_RTOL = 1e-2
FLASH_GRAD_RTOL = 2e-2      # backward runs at default (bf16-pass) precision
DP_LOSS_RTOL = 5e-2         # dp=4 vs dp=1: same math, other reduction order
# The comparison steps gently.  At a rate of 0.1 on random labels the
# trajectory amplifies rounding: in float32 on CPU devices a relative
# difference of 1e-5 after step 1 was 4.5% after step 2.
DP_COMPARE_LR = 1e-3
# what ZeRO stage 1 must put into the compiled dp=4 step: gradients
# reduce-scattered to each chip's slice, updated weights all-gathered
ZERO1_COLLECTIVES = ("reduce-scatter", "all-gather")
GREEDY_TIE_ATOL = 5e-2      # logits of std ~1; see serve_causal_lm

CTX = mx.tpu(0)             # where the imperative and serve paths place work
RESNET_BATCH, RESNET_SIZE = 128, 224
BERT_BATCH, BERT_SEQ, BERT_VOCAB = 8, 256, 30522
# BERT-base's heads at this script's batch 8 x seq 256, and at the
# benchmark cell's batch 16 x seq 512 (bert_base.pretrain_s512)
FLASH_SHAPES = ((96, 256, 64), (192, 512, 64))
# the benchmark cells' attention as their models hand it to the kernels,
# tokens-major: (batch, tokens, heads, lanes of a head, causal)
FLASH_CELLS = ((16, 512, 12, 64, False), (1, 8192, 20, 256, True),
               (1, 2048, 30, 128, True))
LM = dict(vocab_size=30522, num_layers=12, units=768, hidden_size=3072,
          num_heads=12, max_length=1024)    # BERT-base widths


# "hit" / "miss" for each program jax looked up in its persistent cache
CACHE_EVENTS = []


def _cache_event(event, **_):
    if event.endswith(("/cache_hits", "/cache_misses")):
        CACHE_EVENTS.append("hit" if event.endswith("hits") else "miss")


def say(phase, **facts):
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in facts.items()),
          flush=True)


def check(cond, msg):
    if not cond:
        raise AssertionError(msg)


def on_chip(what, *arrays):
    """Every array is resident on TPU devices only; returns their ids."""
    ids = set()
    for a in arrays:
        for d in a.devices():
            check(d.platform == "tpu", f"{what} lives on {d}, not on a TPU")
            ids.add(d.id)
    return sorted(ids)


def mosaic(what, example, compiled_text):
    """The kernel was built for Mosaic: its mode chosen from where the data
    is came out "not interpret", and the compiled program holds the call."""
    from mxnet_tpu.kernels.multi_sgd import _interpret
    check(_interpret(example) is False,
          f"{what} chose interpret mode for data on the chip")
    check("tpu_custom_call" in compiled_text,
          f"{what}: no tpu_custom_call in the compiled program")


def trainer_on_chip(what, tr):
    """The devices holding a ShardedTrainer's parameters and optimizer
    state are all TPUs; returns bytes per device of each."""
    tpus = {d.id for d in jax.devices() if d.platform == "tpu"}
    params, state = tr.param_bytes_per_device(), \
        tr.opt_state_bytes_per_device()
    for name, per_dev in (("parameters", params), ("optimizer state", state)):
        check(per_dev and set(per_dev) <= tpus,
              f"{what}: {name} on devices {sorted(per_dev)}, TPUs are "
              f"{sorted(tpus)}")
    return params, state


def timed_steps(step, n):
    """n steps, each waited for: (losses, milliseconds of each)."""
    losses, ms = [], []
    for _ in range(n):
        t0 = time.perf_counter()
        losses.append(float(step().asnumpy()))      # waits for the device
        ms.append(round((time.perf_counter() - t0) * 1e3, 2))
    return losses, ms


# -- train / ResNet-50 ------------------------------------------------------

def resnet50_trainer(seed, mesh=None, zero_stage=0, lr=0.1):
    from mxnet_tpu.gluon.model_zoo.vision import resnet50_v1
    mx.random.seed(seed)
    net = resnet50_v1()
    net.initialize()
    return net, par.ShardedTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": lr, "momentum": 0.9, "wd": 1e-4},
        mesh=mesh, zero_stage=zero_stage)


def resnet50_batch(seed):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal((RESNET_BATCH, 3, RESNET_SIZE, RESNET_SIZE),
                                dtype=np.float32),
            rng.integers(0, 1000, (RESNET_BATCH,)))


def train_resnet50(seed):
    amp.init("bfloat16")
    try:
        net, tr = resnet50_trainer(seed)
        x, y = resnet50_batch(seed)
        t0 = time.perf_counter()
        first = float(tr.step(x, y).asnumpy())       # build + compile
        compile_s = time.perf_counter() - t0
        xs, ys = tr.shard_batch(x, y)                # resident batch
        loss = tr.step(xs, ys)
        losses, step_ms = timed_steps(lambda: tr.step(xs, ys), 4)
        check(np.isfinite([first] + losses).all(),
              f"ResNet-50 loss not finite: {first} .. {losses}")
        loss_dev = on_chip("ResNet-50 loss", loss._read())
        params, state = trainer_on_chip("ResNet-50", tr)
        # donated buffers: the weights must still be readable afterwards
        tr.sync_params()
        w = next(iter(net.collect_params().values())).data().asnumpy()
        check(np.isfinite(w).all(), "ResNet-50 weights not finite after sync")
        # the same step once more with every in-memory cache dropped:
        # traced and lowered anew, compiled out of the persistent cache the
        # first compile filled.  (Measured on this phase because its
        # program holds no Pallas kernel: see PERF.md, Open questions.)
        jax.clear_caches()
        seen = len(CACHE_EVENTS)
        t0 = time.perf_counter()
        lowered = tr.lower_step(x, y)
        t1 = time.perf_counter()
        lowered.compile()
        retrace_s, warm_s = t1 - t0, time.perf_counter() - t1
    finally:
        amp.disable()
    say("train/resnet50", dtype="bf16", batch=RESNET_BATCH, steps=6,
        loss_first=round(first, 4), loss_last=round(losses[-1], 4),
        compile_s=round(compile_s, 1), step_ms=step_ms,
        loss_on_tpu=loss_dev, param_bytes=params, opt_state_bytes=state)
    say("compile_cache", dir=compile_cache.active().path,
        measured_on="train/resnet50",
        first_build_trace_compile_step_s=round(compile_s, 1),
        again_trace_and_lower_s=round(retrace_s, 1),
        again_compile_s=round(warm_s, 1),
        persistent_cache=f"{CACHE_EVENTS[seen:].count('hit')} hit, "
                         f"{CACHE_EVENTS[seen:].count('miss')} miss")


# -- train / BERT-base, and the flash kernel at its shapes -------------------

def full_softmax_attention(q, k, v, valid_len=None):
    """softmax(QK^T/sqrt(d) + key-padding mask)V through plain XLA, in
    float32 at the highest matmul precision: the reference."""
    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    with jax.default_matmul_precision("highest"):
        s = jnp.einsum("bqd,bkd->bqk", q, k) / np.sqrt(q.shape[-1])
        if valid_len is not None:
            keep = jnp.arange(k.shape[1])[None, None, :] \
                < valid_len[:, None, None]
            s = jnp.where(keep, s, -1e30)
        return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(s, axis=-1), v)


def flash_lengths(rng, bh, seq):
    """One valid length per sequence of 12 heads: at seq 512 the benchmark
    cell's own set (``bert_base.pretrain_s512``, read from its file and
    dealt by the seed), else uniform over the upper half."""
    n = bh // 12
    if seq == 512:
        from benchmarks.harness import loader
        from benchmarks.models import bert
        _, cell, _ = loader.cell_and_config(loader.benchmark(),
                                            "bert_base.pretrain_s512")
        lens = rng.permutation(bert.lengths(cell["traffic_params"]))[:n]
    else:
        lens = rng.integers(seq // 2, seq + 1, (n,))
    return np.repeat(lens, 12).astype(np.float32)


def flash_kernel_check(shape, seed):
    """The kernel on the chip against the reference, at BERT-base's heads
    (12 of 64) for one of FLASH_SHAPES."""
    from mxnet_tpu.kernels import flash_attention
    from mxnet_tpu.observability.registry import registry
    bh, seq, _ = shape
    rng = np.random.default_rng(seed)
    q, k, v = (jax.device_put(
        rng.standard_normal(shape, dtype=np.float32), CTX.device)
        for _ in range(3))
    vl = jax.device_put(flash_lengths(rng, bh, seq), CTX.device)
    mosaic("flash attention", q,
           jax.jit(flash_attention).lower(q, k, v).compile().as_text())
    errs = {}
    for dtype in (jnp.float32, jnp.bfloat16):
        qd, kd, vd = (a.astype(dtype) for a in (q, k, v))
        for name, lens in (("plain", None), ("valid_len", vl)):
            out = flash_attention(qd, kd, vd, valid_len=lens)
            on_chip("flash attention output", out)
            ref = full_softmax_attention(qd, kd, vd, lens)
            err = np.abs(np.asarray(out, np.float32) - np.asarray(ref))
            if dtype == jnp.float32:
                check(err.max() <= 3e-5,
                      f"flash fp32 {name} {shape}: max |err| {err.max()} "
                      "> 3e-5")
            else:
                bound = FLASH_BF16_ATOL + FLASH_BF16_RTOL * np.abs(
                    np.asarray(ref))
                check((err <= bound).all(),
                      f"flash bf16 {name} {shape}: max |err| {err.max()} "
                      f"beyond {FLASH_BF16_ATOL}+{FLASH_BF16_RTOL}*|ref|")
            errs[f"{jnp.dtype(dtype).name}_{name}"] = float(f"{err.max():.2e}")
    # the backward (two Pallas kernels of its own behind the kernel's
    # custom VJP, at the chip's default matmul precision) against the
    # reference's gradients, relative to the largest of them
    cot = jax.device_put(rng.standard_normal(shape, dtype=np.float32),
                         CTX.device)
    grads = [jax.jit(jax.grad(
        lambda a, b, c, f=f: jnp.sum(f(a, b, c, valid_len=vl) * cot),
        argnums=(0, 1, 2)))(q, k, v)
        for f in (flash_attention, full_softmax_attention)]
    gerr = max(float(jnp.max(jnp.abs(g - r)) / jnp.max(jnp.abs(r)))
               for g, r in zip(*grads))
    check(gerr <= FLASH_GRAD_RTOL,
          f"flash gradients {shape} off by {gerr} of the largest reference "
          "gradient")
    mosaic("flash attention backward", q, jax.jit(jax.grad(
        lambda a, b, c: jnp.sum(flash_attention(a, b, c, valid_len=vl) * cot),
        argnums=(0, 1, 2))).lower(q, k, v).compile().as_text())
    say("kernel/flash_attention", lowering="tpu_custom_call",
        shape=shape, max_abs_err=errs,
        tol=f"fp32 3e-5; bf16 {FLASH_BF16_ATOL}+{FLASH_BF16_RTOL}*|ref|",
        grad_max_rel_err=float(f"{gerr:.2e}"), grad_tol=FLASH_GRAD_RTOL,
        tiling={n: int(registry().get(f"kernels.flash_attention.{n}").read())
                for n in ("block_q", "block_k", "kv_resident", "grid_steps")},
        backward_tiling={
            n: int(registry().get(f"kernels.flash_attention_bwd.{n}").read())
            for n in ("block_q", "block_k", "grid_steps")})


def tokens_major_against_heads_first(rng, b, seq, heads, d, causal,
                                     dtype=jnp.float32):
    """The kernels over tokens-major operands, (B, L, heads * d): a head is
    a block of the lanes (two 64-lane heads to a block of 128), against the
    heads-first call on the transposed operands, forward and gradients,
    from separate q, k, v and from one fused array read in place.  Returns
    the largest output difference, the gradients' largest difference over
    the reference's largest entry and as vectors, the gauges the
    tokens-major build set, and its compiled text."""
    from mxnet_tpu.kernels import flash_attention
    from mxnet_tpu.observability.registry import registry
    q, k, v, cot = (jax.device_put(rng.standard_normal(
        (b, seq, heads * d), dtype=np.float32), CTX.device).astype(dtype)
        for _ in range(4))
    vl = None if causal else jax.device_put(
        flash_lengths(rng, b * 12, seq)[::12], CTX.device)

    def heads_first(t):
        return t.reshape(b, seq, heads, d).transpose(0, 2, 1, 3)

    def reference(q, k, v):
        out = flash_attention(heads_first(q), heads_first(k), heads_first(v),
                              causal=causal, valid_len=vl)
        return out.transpose(0, 2, 1, 3).reshape(b, seq, heads * d)

    def lanes(q, k, v):
        return flash_attention(q, k, v, causal=causal, valid_len=vl,
                               num_heads=heads)

    def fused(x):
        return flash_attention(x, x, x, causal=causal, valid_len=vl,
                               num_heads=heads, head_dim=d,
                               first_head=(0, heads, 2 * heads))

    def both(f):
        return jax.jit(lambda *a: (f(*a), jax.vjp(f, *a)[1](cot)))

    want_out, want = both(reference)(q, k, v)
    got_out, got = both(lanes)(q, k, v)
    gauges = {n.replace("_bwd.", "bwd_").lstrip("."): int(
        registry().get(f"kernels.flash_attention{n}").read())
        for n in (".lane_heads", ".tokens_major", ".grid_steps",
                  "_bwd.lane_heads")}
    fused_out, (fused_grad,) = both(fused)(jnp.concatenate([q, k, v], -1))
    on_chip("tokens-major flash attention output", got_out, fused_out)
    f32 = [[np.asarray(x, np.float32) for x in xs] for xs in (
        (got_out, fused_out), (want_out, want_out),
        got + tuple(jnp.split(fused_grad, 3, -1)), want + want)]
    return dict(
        out_err=max(np.abs(a - r).max() for a, r in zip(*f32[:2])),
        grad_max_rel=max(np.abs(a - r).max() / np.abs(r).max()
                         for a, r in zip(*f32[2:])),
        grad_vector_rel=max(np.linalg.norm(a - r) / np.linalg.norm(r)
                            for a, r in zip(*f32[2:])),
        gauges=gauges,
        text=both(lanes).lower(q, k, v).compile().as_text())


def flash_tokens_major_check(seed):
    """``tokens_major_against_heads_first`` at the three benchmark cells'
    shapes, as their models hand them to the kernels."""
    rng = np.random.default_rng(seed)
    for b, seq, heads, d, causal in FLASH_CELLS:
        shape = (b, seq, heads, d)
        read = tokens_major_against_heads_first(rng, *shape, causal)
        mosaic("tokens-major flash attention",
               jax.device_put(np.zeros(1, np.float32), CTX.device),
               read.pop("text"))
        gauges = read["gauges"]
        check(gauges["tokens_major"] == 1
              and gauges["lane_heads"] == gauges["bwd_lane_heads"]
              == max(128 // d, 1),
              f"tokens-major flash at {shape}: gauges {gauges}")
        check(read["out_err"] <= 3e-5, f"tokens-major flash {shape}: max "
              f"|err| {read['out_err']} from the heads-first call > 3e-5")
        check(read["grad_max_rel"] <= FLASH_GRAD_RTOL,
              f"tokens-major flash gradients {shape} off by "
              f"{read['grad_max_rel']} of the largest heads-first gradient")
        say("kernel/flash_attention", operands="tokens-major", shape=shape,
            causal=causal, **{k: float(f"{v:.2e}") for k, v in read.items()
                              if k != "gauges"}, **gauges)


def hybrid_attention_block_check(seed):
    """The hybrid decoder's attention block at the benchmark cell's shape
    (``olmo_hybrid_7b_l4.pretrain_s2k``: 30 heads of 128 over 2048 tokens,
    q/k norms, causal, no rotary) takes the flash path on its default
    selector: the last kernel built has K and V resident and 30 x 4 query
    tiles (the shapes checked before it leave other grids)."""
    from mxnet_tpu.gluon.model_zoo.transformer import QKNormAttention
    from mxnet_tpu.observability.registry import registry
    block = QKNormAttention(3840, 30, prefix="smoke_attn_")
    block.initialize(ctx=CTX)
    block.hybridize()
    x = mx.nd.array(np.random.default_rng(seed).standard_normal(
        (1, 2048, 3840), dtype=np.float32), ctx=CTX)
    out = block(x)
    on_chip("hybrid attention block output", out._read())
    check(np.isfinite(out.asnumpy()).all(),
          "hybrid attention block: output not finite")
    tiling = {n: int(registry().get(f"kernels.flash_attention.{n}").read())
              for n in ("block_q", "block_k", "kv_resident", "grid_steps")}
    check(tiling["kv_resident"] == 1 and tiling["grid_steps"] == 120,
          f"hybrid attention block at (30, 2048, 128) did not take the flash "
          f"path: tiling {tiling}")
    say("kernel/flash_attention", block="QKNormAttention",
        shape=(30, 2048, 128), causal=True, tiling=tiling)


def make_bert():
    from mxnet_tpu.gluon.model_zoo.transformer import bert_base
    return bert_base(dropout=0.0)


def train_bert_base(seed):
    mask_id, vocab, batch, seq = 103, BERT_VOCAB, BERT_BATCH, BERT_SEQ
    mx.random.seed(seed)
    net = make_bert()
    net.initialize()

    def mlm_nsp_loss(out, ys):
        mlm, nsp = out
        labels, weights, nsp_y = ys
        ce = -mx.nd.pick(mx.nd.log_softmax(mlm, axis=-1), labels, axis=-1)
        mlm_l = mx.nd.sum(ce * weights) / mx.nd.sum(weights)
        nsp_l = -mx.nd.mean(mx.nd.pick(mx.nd.log_softmax(nsp, axis=-1),
                                       nsp_y, axis=-1))
        return mlm_l + nsp_l

    tr = par.ShardedTrainer(net, mlm_nsp_loss, "adam",
                            {"learning_rate": 1e-4})
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, vocab, (batch, seq))
    valid_lens = rng.integers(seq // 2, seq + 1, (batch,))
    valid = np.arange(seq)[None, :] < valid_lens[:, None]
    mask_pos = (rng.random((batch, seq)) < 0.15) & valid
    mask_pos[:, 0] = True                    # >=1 masked position per row
    x = (np.where(mask_pos, mask_id, tokens), np.zeros((batch, seq), np.int64),
         valid_lens.astype(np.float32))
    y = (tokens, mask_pos.astype(np.float32), rng.integers(0, 2, (batch,)))

    t0 = time.perf_counter()
    loss = tr.step(x, y)                             # build + compile
    first = float(loss.asnumpy())
    cold_s = time.perf_counter() - t0
    losses, step_ms = timed_steps(lambda: tr.step(x, y), 2)
    check(np.isfinite([first] + losses).all(),
          f"BERT loss not finite: {first} .. {losses}")
    params, state = trainer_on_chip("BERT-base", tr)
    # the compiled step's text (an in-memory cache read: nothing compiles)
    text = tr.lower_step(x, y).compile().as_text()
    mosaic("BERT step (attention on its default selector)", loss._read(),
           text)
    say("train/bert_base", dtype="fp32", batch=batch, seq=seq, steps=3,
        attention="flash (default selector)", lowering="tpu_custom_call",
        loss_first=round(first, 4), loss_last=round(losses[-1], 4),
        compile_s=round(cold_s, 1), step_ms=step_ms,
        param_bytes=params, opt_state_bytes=state)


# -- imperative / Gluon MLP --------------------------------------------------

def imperative_mlp(seed):
    from mxnet_tpu.engine import engine
    from mxnet_tpu.kernels import multi_sgd
    batch = 64
    rng = np.random.default_rng(seed)
    eng = engine()
    eng.reset_stats()
    built0, twin0 = (multi_sgd._build_pallas.cache_info().currsize,
                     multi_sgd._jnp_dual.cache_info().currsize)
    with CTX:
        mx.random.seed(seed)
        net = gluon.nn.HybridSequential()
        with net.name_scope():
            net.add(gluon.nn.Dense(128, activation="relu"),
                    gluon.nn.Dense(64, activation="relu"),
                    gluon.nn.Dense(10))
        net.initialize()
        trainer = gluon.Trainer(net.collect_params(), "sgd",
                                {"learning_rate": 0.1, "momentum": 0.9})
        loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
        pixels = mx.nd.array(rng.integers(0, 256, (batch, 784))
                             .astype(np.float32))
        y = mx.nd.array(rng.integers(0, 10, (batch,)))
        losses, t_steps = [], []
        for _ in range(20):
            t0 = time.perf_counter()
            x = (pixels / 255.0 - 0.5) * 2.0        # a bulked segment
            with autograd.record():
                loss = loss_fn(net(x), y)
            loss.backward()
            trainer.step(batch)
            losses.append(float(mx.nd.mean(loss).asnumpy()))
            t_steps.append(time.perf_counter() - t0)
        check(loss.context == CTX, f"loss context is {loss.context}")
        weights = [p.data() for p in net.collect_params().values()]
        check(all(w.context == CTX for w in weights),
              f"MLP parameters are not on {CTX}")
        devs = on_chip("MLP parameters and loss", loss._read(),
                       *[w._read() for w in weights])
    check(np.isfinite(losses).all() and losses[-1] < losses[0],
          f"MLP loss did not fall: {losses[0]} -> {losses[-1]}")
    st = eng.stats()
    check(st["segments_flushed"] >= 20 and st["mean_segment_length"] > 1.0,
          f"no fused segments were executed: {st}")
    # the update went through the Pallas kernel, never its jnp twin
    built, twin = (multi_sgd._build_pallas.cache_info().currsize - built0,
                   multi_sgd._jnp_dual.cache_info().currsize - twin0)
    check(built >= 1 and twin == 0,
          f"multi_sgd: {built} Pallas calls built, {twin} jnp twins built")
    say("imperative/mlp", net="784-128-64-10", batch=batch, steps=20,
        loss_first=round(losses[0], 4), loss_last=round(losses[-1], 4),
        first_step_s=round(t_steps[0], 1),
        step_ms_median=round(float(np.median(t_steps[5:])) * 1e3, 2),
        on_tpu=devs, multi_sgd_pallas_calls_built=built, jnp_twins_built=twin,
        segments_flushed=st["segments_flushed"],
        mean_segment_length=st["mean_segment_length"],
        ops_dispatched=st["ops_dispatched"])


def multi_sgd_kernel_check(seed):
    """The multi-tensor apply on the chip: Mosaic, and equal to its jnp
    twin (the reference the host path runs) on the MLP's tensors."""
    from mxnet_tpu.kernels import fused_multi_sgd_mom
    rng = np.random.default_rng(seed)
    shapes = [(128, 784), (128,), (64, 128), (64,), (10, 64), (10,)]
    ws, gs, ms = ([jax.device_put(rng.standard_normal(s, dtype=np.float32),
                                  CTX.device) for s in shapes]
                  for _ in range(3))
    lrs, wds = np.full(6, 0.1, np.float32), np.full(6, 1e-4, np.float32)

    def update(interpret):
        return jax.jit(lambda w, g, m: fused_multi_sgd_mom(
            w, g, m, lrs, wds, momentum=0.9, rescale_grad=1 / 64,
            interpret=interpret))

    mosaic("multi_sgd", ws[0],
           update(False).lower(ws, gs, ms).compile().as_text())
    w_k, m_k = update(False)(ws, gs, ms)
    w_r, m_r = update(True)(ws, gs, ms)          # interpret=True: the twin
    on_chip("multi_sgd output", *w_k, *m_k)
    err = max(float(jnp.max(jnp.abs(a - b)))
              for a, b in zip(w_k + m_k, w_r + m_r))
    check(err <= 1e-6, f"multi_sgd kernel vs jnp twin: max |err| {err}")
    say("kernel/multi_sgd", lowering="tpu_custom_call", tensors=6,
        max_abs_err_vs_jnp=float(f"{err:.2e}"))


# -- serve / CausalLM ---------------------------------------------------------

def sse_generate(port, name, prompt, max_new):
    """POST one generation; returns (tokens, seconds to the first token
    event on the socket, seconds to the end of the stream)."""
    body = json.dumps({"prompt": [int(t) for t in prompt],
                       "max_new_tokens": max_new, "timeout_s": 600})
    with socket.create_connection(("127.0.0.1", port), timeout=600) as s:
        t0 = time.perf_counter()
        s.sendall((f"POST /v1/models/{name}/generate HTTP/1.1\r\n"
                   f"Host: x\r\nContent-Length: {len(body)}\r\n\r\n"
                   f"{body}").encode())
        buf, ttft = b"", None
        while True:
            chunk = s.recv(65536)
            buf += chunk
            if ttft is None and b"data:" in buf:
                ttft = time.perf_counter() - t0
            if not chunk:
                break
        total = time.perf_counter() - t0
    check(b" 200 " in buf.split(b"\r\n", 1)[0], f"generate: {buf[:200]!r}")
    check(b"event: done" in buf, f"stream did not finish: {buf[-300:]!r}")
    toks = [json.loads(line.partition(b":")[2])["token"]
            for line in buf.split(b"\n")
            if line.startswith(b"data:") and b'"token"' in line]
    return toks, ttft, total


def serve_causal_lm(seed):
    from mxnet_tpu.gluon.model_zoo.transformer import CausalLM
    from mxnet_tpu.serving import (GenerationServer, HttpFrontend,
                                   ModelRegistry)
    vocab, bucket, max_new, slots = LM["vocab_size"], 32, 16, 4
    ctx = CTX
    mx.random.seed(seed)
    lm = CausalLM(**LM)
    lm.initialize(ctx=ctx)
    lm.hybridize()
    registry = ModelRegistry()
    gen = GenerationServer(lm, slots=slots, kv_block=16, kv_blocks=64,
                           max_new_tokens=max_new, prompt_buckets=(bucket,),
                           queue_depth=64, deadline_ms=0)
    t0 = time.perf_counter()
    registry.load("lm", gen, warm=True)      # one prefill, one decode graph
    warm_s = time.perf_counter() - t0
    check(gen.stats()["executables"] == 2,
          f"expected 2 executables, have {gen.stats()['executables']}")
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, vocab, (n,)) for n in (12, 5, 20, 9)]
    frontend = HttpFrontend(registry, port=0).start()
    try:
        c = http.client.HTTPConnection("127.0.0.1", frontend.port, timeout=60)
        c.request("GET", "/readyz")
        ready = c.getresponse()
        ready.read()
        c.close()
        check(ready.status == 200, "frontend not ready")
        served = [sse_generate(frontend.port, "lm", p, max_new)
                  for p in prompts]
        # private, for the check only: where the KV pool lives
        pool_dev = on_chip("KV pool", gen._pool)
        pool_mb = round(gen._pool.nbytes / 2 ** 20, 1)
    finally:
        frontend.stop(drain=True)
    check(all(len(t) == max_new for t, _, _ in served),
          f"token counts {[len(t) for t, _, _ in served]}, want {max_new}")
    check(gen.stats()["kv_blocks_used"] == 0, "KV blocks leaked after drain")

    # greedy tokens of the first prompt against the whole-sequence forward,
    # teacher-forced: position i of the full pass sees prompt + served[:i].
    # Both run at the chip's default matmul precision through different
    # graphs (paged decode vs one causal pass), so a served token may differ
    # from the full pass's argmax where the two top logits tie within
    # rounding; it must then be within GREEDY_TIE_ATOL of that maximum
    # (logits have a standard deviation of about 1 here).
    toks = served[0][0]
    seq = np.zeros((1, bucket), np.int32)
    n_p = len(prompts[0])
    seq[0, :n_p] = prompts[0]
    seq[0, n_p:n_p + max_new - 1] = toks[:-1]
    logits = lm(mx.nd.array(seq, ctx=ctx))
    logits_dev = on_chip("whole-sequence logits", logits._read())
    check(logits.shape == (1, bucket, vocab), f"logits shape {logits.shape}")
    rows = logits.asnumpy()[0, n_p - 1:n_p - 1 + max_new]
    check(np.isfinite(rows).all(), "logits not finite")
    exact = int(np.sum(rows.argmax(-1) == np.asarray(toks)))
    gaps = rows.max(-1) - rows[np.arange(max_new), toks]
    check((gaps <= GREEDY_TIE_ATOL).all(),
          f"served tokens {toks} vs full-forward argmax "
          f"{rows.argmax(-1).tolist()}: logit gaps {gaps.tolist()}")
    say("serve/causal_lm", layers=LM["num_layers"], units=LM["units"],
        vocab=vocab, slots=slots,
        prompt_bucket=bucket, requests=len(prompts), tokens_each=max_new,
        warmup_compile_s=round(warm_s, 1), executables=2,
        ttft_ms=[round(t * 1e3, 1) for _, t, _ in served],
        request_ms=[round(t * 1e3, 1) for _, _, t in served],
        greedy_vs_full_forward=f"{exact}/{max_new} equal, rest within "
                               f"{GREEDY_TIE_ATOL} of the max logit",
        kv_pool_on_tpu=pool_dev, kv_pool_mb=pool_mb,
        logits_on_tpu=logits_dev)


# -- four chips: the sharded step against one device --------------------------

def sharded_resnet50(seed):
    devices = jax.devices()
    x, y = resnet50_batch(seed)
    amp.init("bfloat16")
    try:
        runs = {}
        for dp in (1, 4):
            _, tr = resnet50_trainer(
                seed, mesh=par.make_mesh({"dp": dp}, devices[:dp]),
                zero_stage=1 if dp > 1 else 0, lr=DP_COMPARE_LR)
            t0 = time.perf_counter()
            first = float(tr.step(x, y).asnumpy())
            compile_s = time.perf_counter() - t0
            xs, ys = tr.shard_batch(x, y)
            losses, step_ms = timed_steps(lambda: tr.step(xs, ys), 2)
            losses = [first] + losses
            runs[dp] = (tr, losses)
            say(f"train/resnet50 dp={dp}", dtype="bf16",
                global_batch=RESNET_BATCH, zero_stage=tr.zero_stage,
                lr=DP_COMPARE_LR,
                losses=[round(v, 4) for v in losses],
                compile_s=round(compile_s, 1), step_ms=step_ms)
        tr, losses = runs[4]
        check(np.isfinite(losses).all(), f"dp=4 losses {losses}")
        ref = np.asarray(runs[1][1])
        rel = np.abs(np.asarray(losses) - ref) / np.abs(ref)
        check((rel <= DP_LOSS_RTOL).all() and losses[-1] < losses[0],
              f"dp=4 losses {losses} vs dp=1 {ref.tolist()}: relative "
              f"difference {rel.tolist()}, bound {DP_LOSS_RTOL}")
        params, state = trainer_on_chip("ResNet-50 dp=4", tr)
        ids = sorted(d.id for d in devices)
        check(sorted(params) == ids and sorted(state) == ids,
              f"shards on {sorted(params)} / {sorted(state)}, devices {ids}")
        full = runs[1][0].opt_state_bytes_per_device()[devices[0].id]
        check(max(state.values()) < 0.5 * full,
              f"optimizer state not sharded: {state} vs {full} at dp=1")
        in_use = {d.id: d.memory_stats()["bytes_in_use"] for d in devices}
        check(all(v > 0 for v in in_use.values()), f"memory_stats {in_use}")
        text = tr.lower_step(x, y).compile().as_text()
        found = [c for c in ("reduce-scatter", "all-gather", "all-reduce")
                 if c in text]
        check(all(c in found for c in ZERO1_COLLECTIVES),
              f"dp=4 step carries {found}, wants {ZERO1_COLLECTIVES}")
    finally:
        amp.disable()
    say("sharded/zero1 dp=4",
        loss_rel_diff_vs_dp1=[float(f"{v:.1e}") for v in rel],
        bound=DP_LOSS_RTOL,
        param_bytes=params, opt_state_bytes=state,
        opt_state_bytes_dp1=full, bytes_in_use=in_use, collectives=found)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded ResNet-50 step, dp=4 against "
                         "dp=1 (default 1: the three one-chip paths)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (devices: {devices})")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but JAX sees "
                 f"{len(devices)} device(s)")
    # where JAX_COMPILATION_CACHE_DIR says, else one fixed git-ignored
    # directory in the checkout (a path that moved would never hit)
    compile_cache.configure(os.path.join(REPO, ".compile_cache"))
    jax.monitoring.register_event_listener(_cache_event)
    # nothing on these paths may quietly give way to a host fallback
    warnings.filterwarnings("error", message=".*(falling back|fall back).*")
    t0 = time.perf_counter()
    say("device", platform=dev.platform, kind=repr(dev.device_kind),
        count=len(devices), jax=jax.__version__, seed=args.seed,
        compile_cache=compile_cache.active().path)
    np.random.seed(args.seed)
    if args.chips == 4:
        sharded_resnet50(args.seed)
    else:
        train_resnet50(args.seed)
        for shape in FLASH_SHAPES:
            flash_kernel_check(shape, args.seed)
        flash_tokens_major_check(args.seed)
        hybrid_attention_block_check(args.seed)
        train_bert_base(args.seed)
        imperative_mlp(args.seed)
        multi_sgd_kernel_check(args.seed)
        serve_causal_lm(args.seed)
    say("done", seconds=round(time.perf_counter() - t0, 1))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
