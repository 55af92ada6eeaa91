"""The program's own names on the profiler's clock.

``trace.span`` as a ``jax.profiler.TraceAnnotation`` (found, nested, in an
``.xplane.pb`` that jax's profiler wrote on the CPU backend), the Gluon
blocks' ``jax.named_scope`` inside every compiled program and nowhere on
the eager path, ``ShardedTrainer``'s dispatch spans and compile counters,
and ``mx.profiler``'s reduction of a device trace: the span table from a
round trip here, the by-scope and idle-owner tables from two steps recorded
on a TPU v5e (``tests/data/bert_step_v5e.scoped.json``).
"""
import contextlib
import json
import os
import re

import jax
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import parallel as par
from mxnet_tpu import profiler
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.model_zoo.transformer import BERTModel
from mxnet_tpu.observability import trace
from mxnet_tpu.observability.registry import registry
from mxnet_tpu.tuning.compile_cache import watch_compiles

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(HERE, "data", "bert_step_v5e.scoped.json")


# -- helpers ------------------------------------------------------------------

def mlm_nsp_loss(out, ys):
    mlm, nsp = out
    labels, weights, nsp_y = ys
    ce = -mx.nd.pick(mx.nd.log_softmax(mlm, axis=-1), labels, axis=-1)
    return mx.nd.sum(ce * weights) / mx.nd.sum(weights) - mx.nd.mean(
        mx.nd.pick(mx.nd.log_softmax(nsp, axis=-1), nsp_y, axis=-1))


def tiny_bert(batch=4, seq=32):
    net = BERTModel(vocab_size=64, num_layers=2, units=32, hidden_size=64,
                    num_heads=2, max_length=seq, type_vocab_size=2,
                    dropout=0.0)
    net.initialize()
    tr = par.ShardedTrainer(net, mlm_nsp_loss, "adam",
                            {"learning_rate": 1e-3}, mesh=one_device())
    return tr, bert_batch(batch, seq)


def one_device():
    return par.make_mesh({"dp": 1}, devices=jax.local_devices()[:1])


def bert_batch(batch, seq):
    rng = np.random.default_rng(0)
    x = (rng.integers(0, 64, (batch, seq)), np.zeros((batch, seq), np.int64),
         np.full((batch,), 20, np.float32))
    y = (rng.integers(0, 64, (batch, seq)),
         (rng.random((batch, seq)) < 0.2).astype(np.float32),
         rng.integers(0, 2, (batch,)))
    return x, y


TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")


def program_text(txt):
    """The optimized HLO without what a scope may change: the instructions'
    ``metadata={...}`` and the source-location tables at the module's head."""
    out, skip = [], False
    for line in txt.splitlines():
        if line.strip() in TABLES:
            skip = True
        elif skip and not line.strip():
            skip = False
        elif not skip:
            out.append(re.sub(r",? ?metadata=\{[^}]*\}", "", line))
    return out


@contextlib.contextmanager
def jax_trace(tmp_path):
    """jax's own profiler around the body; yields a dict that holds the
    ``.xplane.pb``'s host events afterwards: {line: [(name, start, end)]}."""
    got = {}
    jax.profiler.start_trace(str(tmp_path))
    try:
        yield got
    finally:
        jax.profiler.stop_trace()
    data = jax.profiler.ProfileData.from_file(
        profiler.find_xplane(str(tmp_path)))
    for plane in data.planes:
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                    dict(e.stats)) for e in line.events
                   if e.name.startswith("mx.")]
            if evs:
                got.setdefault(line.name, []).extend(evs)


def find(events, name):
    return [e for evs in events.values() for e in evs if e[0] == name]


@pytest.fixture
def trainer_metrics():
    registry().reset("trainer.")
    yield registry()
    registry().reset("trainer.")


# -- trace.span on the profiler's clock ----------------------------------------

def test_span_and_nested_span_are_mx_events_in_the_xplane(tmp_path):
    with jax_trace(tmp_path) as events:
        with trace.span("t.xp_outer_us", args={"step": 7}):
            with trace.span("t.xp_inner_us"):
                np.ones(4).sum()
    (outer,), (inner,) = (find(events, "mx.t.xp_outer_us"),
                          find(events, "mx.t.xp_inner_us"))
    assert outer[1] <= inner[1] and inner[2] <= outer[2]      # nested
    assert outer[3].get("step") == 7           # the span's args ride along
    # and on one thread's line
    assert any({"mx.t.xp_outer_us", "mx.t.xp_inner_us"} <=
               {e[0] for e in evs} for evs in events.values())


def test_engine_flush_is_a_span_in_the_xplane(tmp_path):
    hist = registry().histogram("engine.flush_us")
    flushed = hist.count
    with jax_trace(tmp_path) as events:
        y = mx.nd.ones((16,))
        for _ in range(4):
            y = mx.nd.tanh(y * 2.0)
        y.wait_to_read()
    n = hist.count - flushed
    assert n >= 1
    assert len(find(events, "mx.engine.flush_us")) == n


def test_span_without_profiler_keeps_histogram_and_listeners():
    seen = []
    fn = lambda name, t_end, us, args: seen.append((name, args))  # noqa: E731
    trace.add_span_listener(fn)
    try:
        with trace.span("t.plain_us", args={"k": 1}) as sp:
            pass
    finally:
        trace.remove_span_listener(fn)
    assert seen == [("t.plain_us", {"k": 1})]
    assert sp.duration_us >= 0.0
    assert registry().get("t.plain_us").count >= 1


# -- names inside the compiled program -------------------------------------------

def test_compiled_step_holds_the_scopes_and_is_the_same_program(monkeypatch):
    monkeypatch.setenv("MXNET_ATTENTION_KERNEL", "flash")
    tr, (x, y) = tiny_bert()
    scoped = tr.lower_step(x, y).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', scoped))
    for needle in ("/enc/", "/attn/", "jvp(loss)", "/optimizer/",
                   "flash_attention_bwd", "flash_attention_pad",
                   "transpose(jvp(bertmodel"):
        assert any(needle in n for n in names), needle
    # the first name after the jit's is the root block, the parent's
    # prefix is cut from every child
    assert any(re.match(r"jit\(step_fn\)/jvp\(bertmodel\d+\)/enc/"
                        r"layers_transformer_encoder_cell0/attn/", n)
               for n in names)

    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    tr2, _ = tiny_bert()
    plain = tr2.lower_step(x, y).compile().as_text()
    assert not any("/enc/" in n or "/optimizer/" in n
                   for n in re.findall(r'op_name="([^"]*)"', plain))
    assert program_text(scoped) == program_text(plain)


def test_xla_attention_path_has_its_scope(monkeypatch):
    monkeypatch.setenv("MXNET_ATTENTION_KERNEL", "xla")
    tr, (x, y) = tiny_bert()
    txt = tr.lower_step(x, y).compile().as_text()
    assert "/attn/attention_xla/" in txt
    assert "flash_attention" not in txt


def test_accumulation_scan_has_the_microbatch_scope():
    net = nn.Dense(4, in_units=8)
    net.initialize()
    tr = par.ShardedTrainer(net, mx.gluon.loss.L2Loss(), "sgd",
                            {"learning_rate": 0.1}, accum_steps=2,
                            mesh=one_device())
    x, y = np.ones((4, 8), np.float32), np.ones((4, 4), np.float32)
    txt = tr.lower_step(x, y).compile().as_text()
    assert "/microbatch/" in txt and "/optimizer/" in txt


def test_eager_block_call_enters_no_named_scope(monkeypatch):
    entered = []
    real = jax.named_scope

    def spy(name):
        entered.append(name)
        return real(name)
    monkeypatch.setattr(jax, "named_scope", spy)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(8, in_units=4), nn.Dense(2, in_units=8))
    net.initialize()
    x = mx.nd.ones((3, 4))
    net(x).wait_to_read()
    assert entered == []                    # eager: not one scope
    net.hybridize()
    net(x).wait_to_read()                   # the CachedOp trace: all three
    assert len(entered) == 3
    assert entered[0].startswith("hybrid_sequential")
    assert entered[1:] == ["dense0", "dense1"]      # the parent's prefix cut


# -- the trainer's spans and compile counters ------------------------------------

def test_trainer_spans_count_every_step_and_set_compiling_calls_apart(
        trainer_metrics):
    reg = trainer_metrics
    tr, (x, y) = tiny_bert()
    n = 5
    for _ in range(n):
        tr.step(x, y, batch_size=1)
    assert reg.get("trainer.h2d_us").count == n
    assert reg.get("trainer.to_vals_us").count == n
    calls, jit = reg.get("trainer.compile_calls").n, \
        reg.get("trainer.jit_call_us").count
    assert calls >= 1 and calls + jit == n
    assert reg.get("trainer.compile_call_s").n > 0
    assert 0 < reg.get("trainer.trace_lower_s").n <= \
        reg.get("trainer.compile_call_s").n
    # a new batch shape forces a re-trace: that step's number is kept
    x2, y2 = bert_batch(8, 32)
    tr.step(x2, y2, batch_size=1)
    assert reg.get("trainer.compile_calls").n == calls + 1
    assert reg.get("trainer.compile_step").read() == n + 1 == tr.num_update
    assert reg.get("trainer.jit_call_us").count == jit
    tr.step(x2, y2, batch_size=1)           # and the next one is plain again
    assert reg.get("trainer.compile_calls").n == calls + 1
    assert reg.get("trainer.jit_call_us").count == jit + 1
    # forward() goes through the same three
    tr.forward(x)
    assert reg.get("trainer.h2d_us").count == n + 3


def test_trainer_step_is_a_numbered_step_in_the_xplane(tmp_path):
    tr, (x, y) = tiny_bert()
    tr.step(x, y, batch_size=1)
    with jax_trace(tmp_path) as events:
        for _ in range(2):
            tr.step(x, y, batch_size=1)
    steps = find(events, "mx.train")
    assert [s[3]["step_num"] for s in steps] == [2, 3]
    for name in ("mx.trainer.to_vals_us", "mx.trainer.h2d_us",
                 "mx.trainer.jit_call_us"):
        inner = find(events, name)
        assert len(inner) == 2
        for s, e in zip(steps, inner):
            assert s[1] <= e[1] and e[2] <= s[2], name
    assert [e[3]["step_num"] for e in
            find(events, "mx.trainer.jit_call_us")] == [2, 3]


def test_watch_compiles_counts_the_outermost_region_once():
    counters = watch_compiles()
    assert watch_compiles() is counters             # installed once

    @jax.jit
    def inner(v):
        return jax.numpy.tanh(v) * 2

    @jax.jit
    def outer(v):
        for _ in range(5):
            v = inner(v) + 1
        return v
    v = jax.numpy.ones((3, 5))      # a program of its own, counted before
    before = {k: (s.n, n.n) for k, (s, n) in counters.items()}
    outer(v).block_until_ready()
    grew = {k: (counters[k][0].n - before[k][0],
                counters[k][1].n - before[k][1]) for k in counters}
    # jit(inner) is traced inside jit(outer)'s trace: one region, not two
    assert grew["trace"][1] == grew["lower"][1] == grew["backend"][1] == 1
    assert all(grew[k][0] > 0 for k in ("trace", "lower", "backend"))
    assert set(counters) == {"trace", "lower", "backend", "cache_read"}
    for phase in counters:
        assert registry().get(f"compile.{phase}_s") is counters[phase][0]


# -- mx.profiler reads a device trace ----------------------------------------------

def test_mx_profiler_round_trip_on_cpu_gives_the_span_table(tmp_path):
    tr, (x, y) = tiny_bert()
    tr.step(x, y, batch_size=1)
    p = profiler.Profiler.get()
    p.reset()
    profiler.set_config(profile_all=True, filename=str(tmp_path / "p.json"),
                        trace_dir=str(tmp_path / "xla"))
    profiler.set_state("run")
    try:
        for _ in range(3):
            with trace.span("t.prof_step_us"):
                tr.step(x, y, batch_size=1).asnumpy()
    finally:
        profiler.set_state("stop")
        profiler.set_config(profile_all=False, trace_dir=None)
    red = profiler.reduce_trace(profiler.load_xplane(p._xplane))
    calls, total, self_s = red["spans"]["mx.t.prof_step_us"]
    assert calls == 3 and 0 < self_s < total
    assert red["spans"]["mx.train"][0] == 3
    # a parent's self time is its duration less its children's cover
    kids = sum(red["spans"][k][1] for k in (
        "mx.trainer.to_vals_us", "mx.trainer.h2d_us",
        "mx.trainer.jit_call_us"))
    assert red["spans"]["mx.train"][2] == pytest.approx(
        red["spans"]["mx.train"][1] - kids, rel=1e-6)
    text = profiler.dumps(reset=True)
    assert "mx.t.prof_step_us" in text and "host spans" in text
    assert "carry no op_name" in text          # the CPU backend names none
    assert "host dispatch time" not in text    # not beside a device trace
    # without a device trace: the table of host dispatch times, as before
    assert "host dispatch time (no device trace was taken)" in \
        profiler.dumps()


@pytest.mark.parametrize("op_name,depth,want", [
    ("jit(step_fn)/jvp(bertmodel0)/enc/layers_transformer_encoder_cell3/"
     "attn/qkv/jit(fn)/dot_general", 4,
     ("bertmodel*/enc/layers_transformer_encoder_cell*/attn", "fwd")),
    ("jit(step_fn)/transpose(jvp(bertmodel0))/enc/"
     "layers_transformer_encoder_cell11/attn/flash_attention_bwd/"
     "transpose(jvp())/while/body/closed_call/mul", 6,
     ("bertmodel*/enc/layers_transformer_encoder_cell*/attn/"
      "flash_attention_bwd", "bwd")),
    ("jit(step_fn)/jvp(bertmodel0)/enc/layers_transformer_encoder_cell0/"
     "attn/flash_attention_fwd/pallas_call", 9,
     ("bertmodel*/enc/layers_transformer_encoder_cell*/attn/"
      "flash_attention_fwd", "fwd")),
    ("jit(step_fn)/optimizer/mul", 4, ("optimizer", "fwd")),
    ("jit(step_fn)/transpose(jvp(loss))/jit(fn)/jit(log_softmax)/div", 4,
     ("loss", "bwd")),
    ("jit(step_fn)/jit(_where)/select_n", 4, ("", "fwd")),
    ("", 4, ("", "fwd")),
])
def test_scope_of_an_op_name(op_name, depth, want):
    assert profiler.scope_of(op_name, depth) == want


def test_reduce_trace_small_cases():
    dev = [["%while.1 = ...", "jit(f)/optimizer/while", 0.0, 100e3],
           ["%fusion.2 = ...", "jit(f)/optimizer/while/body/add", 10e3, 30e3],
           ["%copy.3 = ...", "", 150e3, 50e3],
           ["%fusion.9 = ...", "jit(f)/transpose(jvp(net0))/dense1/mul",
            300e3, 100e3]]
    host = [["mx.outer", "main", 90e3, 300e3],
            ["mx.inner", "main", 95e3, 60e3],
            ["mx.other_thread", "worker", 0.0, 1e6]]
    red = profiler.reduce_trace({"device": {"/device:TPU:0": dev},
                                 "host": host}, depth=2)
    assert red["busy_s"] == pytest.approx(250e-6)
    assert red["scopes"]["optimizer"]["fwd"] == pytest.approx(100e-6)
    assert red["scopes"]["net*/dense*"]["bwd"] == pytest.approx(100e-6)
    assert red["scopes"][""]["fwd"] == pytest.approx(50e-6)
    assert red["unscoped"] == {"copy": pytest.approx(50e-6)}
    # gap 100-150 us: mx.inner covers all of it and is the shortest of the
    # three that do; gap 200-300 us: mx.outer and the worker's span cover
    # it whole, the shorter one owns it
    assert red["gaps"] == {"mx.inner": pytest.approx(50e-6),
                           "mx.outer": pytest.approx(100e-6)}
    assert red["spans"]["mx.outer"] == [1, pytest.approx(300e-6),
                                        pytest.approx(240e-6)]
    assert red["spans"]["mx.other_thread"][2] == pytest.approx(1e-3)


def test_by_scope_and_idle_owner_tables_on_two_steps_from_the_chip():
    with open(FIXTURE) as f:
        fx = json.load(f)
    trace_ = {"device": {p: [[h, fx["scopes"][i], s, d]
                             for h, i, s, d in rows]
                         for p, rows in fx["device"].items()},
              "host": fx["host"]}
    red = profiler.reduce_trace(trace_, depth=6)
    want = fx["expect"]
    assert red["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert red["busy_s"] <= fx["window_ns"] / 1e9
    for scope, t in want["scopes"].items():
        assert red["scopes"][scope] == pytest.approx(t, rel=1e-9), scope
    assert red["gaps"] == pytest.approx(want["gaps"], rel=1e-9)
    named = sum(t["fwd"] + t["bwd"] for s, t in red["scopes"].items() if s)
    total = named + sum(red["scopes"].get("", {}).values())
    assert total == pytest.approx(red["busy_s"], rel=0.02)
    assert named >= 0.9 * red["busy_s"]     # the program names its time
    bwd = red["scopes"]["bertmodel*/enc/layers_transformer_encoder_cell*/"
                        "attn/flash_attention_bwd"]
    assert bwd["fwd"] == 0.0 and bwd["bwd"] > 0.0
    assert red["scopes"]["optimizer"]["fwd"] > 0.0
    assert any(k.startswith("mx.") for k in red["gaps"])
    # a shallower cut folds the kernel's scopes into the attention block
    d4 = profiler.reduce_trace(trace_, depth=4)["scopes"]
    attn = d4["bertmodel*/enc/layers_transformer_encoder_cell*/attn"]
    assert attn["bwd"] >= bwd["bwd"]
    text = profiler.format_tables(red)
    assert "flash_attention_bwd" in text and "% busy" in text
