"""The trainer owns its executable and the program answers for it.

``mx.profiler.step_scopes()``'s table from a module text recorded in the
TPU compiler's print (``tests/data/step_module.hlo.txt``: stated scopes, a
rematerialised block, a ``while`` body, and one instruction for each way an
unstated scope is inferred); ``ShardedTrainer._jit_call`` through the
``jax.stages.Compiled`` it keeps, against the ``jax.jit`` call it replaced;
what a build publishes (counters, spans, the memory gauge, the executable) and
what an untraced run never pays (``as_text()``); and ``mx.profiler``'s
tables with inferred time given to its scope and marked.
"""
import gc
import json
import os
import weakref

import jax
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import parallel as par
from mxnet_tpu import profiler
from mxnet_tpu.gluon import HybridBlock, nn
from mxnet_tpu.observability.registry import registry

from . import test_profiler_trace as tpt

HERE = os.path.dirname(os.path.abspath(__file__))
MODULE = os.path.join(HERE, "data", "step_module.hlo.txt")
TRACE = os.path.join(HERE, "data", "bert_step_v5e.scoped.json")
MOE = "lm*/layer*/remat/moe"
HELD = profiler.Profiler.get        # where the published step is kept


@pytest.fixture(scope="module")
def table():
    with open(MODULE) as f:
        return profiler.scopes_from_hlo(f.read())


# -- the table from a module's text ------------------------------------------

@pytest.mark.parametrize("name,want", [
    # stated: forward, the forward run again, backward, a while and its body
    ("relu_fusion.3", (MOE + "/experts", "fwd", False)),
    ("recomputed_fusion.5", (MOE + "/experts", "recompute", False)),
    ("flash_attention_bwd_dq.2",
     ("lm*/layer*/remat/attn/flash_attention_bwd", "bwd", False)),
    ("adam_fusion.3", ("optimizer", "fwd", False)),
    ("while.30", (MOE + "/dispatch", "fwd", False)),
    ("gather_fusion.8", (MOE + "/dispatch", "fwd", False)),
    ("lt.9", (MOE + "/dispatch", "fwd", False)),
    # users: a layout copy serves the one that reads it
    ("copy.44", ("loss", "fwd", True)),
    # users, through a tuple: the zero-fill a while's carry starts from
    ("broadcast.9.clone", (MOE + "/dispatch", "fwd", True)),
    ("get-tuple-element.596", (MOE + "/dispatch", "fwd", True)),
    # operands, for want of users with a scope, through -start / -done
    ("slice-done.4", ("optimizer", "fwd", True)),
    ("slice-start.4", ("optimizer", "fwd", True)),
    # an instruction that computes asks its operands first: XLA's own
    # op_name on the grouped matmul (through a get-tuple-element) ...
    ("ragged-dot-none.12", (MOE + "/dispatch", "fwd", True)),
    # ... the common prefix where they disagree, and the last of their
    # ways: the weights' gradient is the expert layer's, not the optimizer's
    ("ragged-dot-none.2", (MOE, "bwd", True)),
    # ... the user's scope where it lies inside the operands' prefix (one
    # operand is the checkpoint's own copy of a weight: ``lm*/layer*/remat``)
    ("ragged-dot-none.26", (MOE + "/experts", "bwd", True)),
    # ... and a primitive at the step's top level
    ("top.1", ("loss", "fwd", True)),
    # no common prefix: a prefetch that a block and the optimizer read,
    # through its -start / -done pair; the outputs' tuple
    ("copy-done.1", ("", "fwd", False)),
    ("copy-start.1", ("", "fwd", False)),
    ("tuple.130", ("", "fwd", False)),
    # parameters say nothing, of the entry or of a body
    ("pvals_0_.1", ("", "fwd", False)),
    ("arg_tuple.3", ("", "fwd", False)),
])
def test_table_entry_from_a_recorded_module(table, name, want):
    assert table[name] == want


def test_table_skips_fused_computations_and_keeps_the_rest(table):
    assert "inside.1" not in table and "param_0.11" not in table
    assert {"add.166", "tuple.123", "constant.300", "main.21"} - set(table) \
        == {"main.21"}                      # computations are no instructions
    assert len(table) == 41


@pytest.mark.parametrize("op_name,want", [
    ("jit(step_fn)/transpose(jvp(lm0))/layer1/remat/jvp(lm0)/layer1/remat/"
     "checkpoint/rematted_computation/moe/dispatch/while/body/mul",
     ("lm*/layer*/remat/moe/dispatch", "recompute")),
    ("jit(step_fn)/transpose(jvp(lm0))/mtp/remat/jvp(lm0)/mtp/remat/"
     "checkpoint/rematted_computation/cell/moe/router/dot_general",
     ("lm*/mtp/remat/cell/moe/router", "recompute")),
    ("jit(step_fn)/transpose(jvp(lm0))/layer1/remat/jvp(lm0)/layer1/remat/"
     "checkpoint/moe/combine/mul", ("lm*/layer*/remat/moe/combine", "bwd")),
    ("jit(step_fn)/jvp(lm0)/layer0/remat/moe/experts/jit(relu)/max",
     ("lm*/layer*/remat/moe/experts", "fwd")),
    ("ragged-dot-none", ("", "fwd")),
    ("", ("", "fwd")),
])
def test_scope_way_takes_recompute_out_of_the_path(op_name, want):
    assert profiler.scope_way(op_name) == want


# -- the trainer through the executable it owns -------------------------------

class Net(HybridBlock):
    def __init__(self, dropout=False, **kw):
        super().__init__(**kw)
        with self.name_scope():
            self.blocks = nn.HybridSequential()
            for _ in range(2):
                self.blocks.add(nn.Dense(16, in_units=16, activation="relu"))
            if dropout:                     # the step reads its RNG key
                self.blocks.add(nn.Dropout(0.25))
            self.out = nn.Dense(4, in_units=16)

    def hybrid_forward(self, F, x):
        return self.out(self.blocks(x))


def build(guard=False, remat=False, mesh=None, seed=5, dropout=False):
    mx.random.seed(seed)
    net = Net(dropout)
    net.initialize(mx.init.Xavier(rnd_type="gaussian"))
    return par.ShardedTrainer(
        net, mx.gluon.loss.SoftmaxCrossEntropyLoss(), "adam",
        {"learning_rate": 1e-2}, guard_nonfinite=guard,
        remat=list(net.blocks) if remat else (),
        mesh=mesh or par.make_mesh({"dp": 1}, devices=jax.devices()[:1]))


def batch(n=8, seed=0):
    rng = np.random.RandomState(seed)
    return rng.randn(n, 16).astype(np.float32), rng.randint(0, 4, (n,))


def through_jit(tr):
    """``tr`` with the call this PR replaced: the jitted function itself."""
    tr._jit_call = lambda fn, *args, batch: fn(*args)
    return tr


@pytest.fixture
def trainer_metrics():
    registry().reset("trainer.")
    yield registry()
    registry().reset("trainer.")


@pytest.mark.parametrize("guard,remat", [(False, False), (True, False),
                                         (False, True)],
                         ids=["plain", "guard", "remat"])
def test_owned_executable_steps_bit_equal_to_the_jit_call(guard, remat):
    own, jit = build(guard, remat), through_jit(build(guard, remat))
    for i in range(3):
        x, y = batch(seed=i)
        a, b = own.step(x, y), jit.step(x, y)
        assert a.asnumpy() == b.asnumpy()
    assert len(own._compiled) == 1 and not jit._compiled
    for a, b in zip(jax.tree.leaves((own._pvals, own._avals, own._state)),
                    jax.tree.leaves((jit._pvals, jit._avals, jit._state))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    if guard:
        assert bool(own.last_step_finite) and bool(jit.last_step_finite)
    x, _ = batch()
    np.testing.assert_array_equal(own.forward(x).asnumpy(),
                                  jit.forward(x).asnumpy())


def test_a_second_batch_shape_is_a_second_entry_and_both_keep_working(
        trainer_metrics):
    reg, tr = trainer_metrics, build()
    small, large = batch(8), batch(16)
    for b in (small, small, large, large, small, large):
        assert np.isfinite(tr.step(*b).asnumpy())
    assert len(tr._compiled) == 2
    assert reg.get("trainer.compile_calls").n == 2
    assert reg.get("trainer.jit_call_us").count == 4
    assert reg.get("trainer.compile_step").read() == 3
    # another dtype of the batch is another program too
    tr.step(small[0].astype(np.float16), small[1])
    assert len(tr._compiled) == 3


def test_whatever_rebuilds_the_jits_empties_the_table(trainer_metrics):
    tr = build(mesh=par.make_mesh({"dp": 2}, devices=jax.devices()[:2]))
    x, y = batch()
    tr.step(x, y)
    tr.forward(x)
    assert len(tr._compiled) == 2
    tr.reshard(par.make_mesh({"dp": 4}, devices=jax.devices()[:4]))
    assert tr._compiled == {}
    assert np.isfinite(tr.step(x, y).asnumpy())
    assert [fn for fn, _ in tr._compiled] == [tr._jit_step]
    assert trainer_metrics.get("trainer.compile_calls").n == 3
    tr.set_comm_bucket_mb(1e-4)             # another partition: rebuilt
    assert tr.grad_buckets is not None and tr._compiled == {}


@pytest.mark.parametrize("route", ["set_state", "rollback_step", "forward"])
def test_a_key_committed_elsewhere_is_placed_as_the_step_was_compiled(route):
    """A ``Compiled`` refuses an argument committed to other devices than
    it was built for, where ``jax.jit`` moved it: ``step()`` and
    ``forward()`` place the key over the mesh themselves."""
    def mesh():
        return par.make_mesh({"dp": 4}, devices=jax.devices()[:4])
    tr = build(mesh=mesh(), dropout=True)
    ref = through_jit(build(mesh=mesh(), dropout=True))
    x, y = batch()
    for t in (tr, ref):                     # one process-wide stream
        mx.random.seed(3)
        t.step(x, y), t.forward(x)
    elsewhere = jax.device_put(jax.random.PRNGKey(11), jax.devices()[5])
    assert elsewhere.committed
    for t in (tr, ref):
        if route == "rollback_step":
            t.rollback_step((t.num_update, elsewhere))
        else:
            mx.random.set_state(elsewhere)
        got = t.forward(x) if route == "forward" else t.step(x, y)
        if t is tr:
            mine = got.asnumpy()
    np.testing.assert_array_equal(mine, got.asnumpy())
    assert len(tr._compiled) == 2           # nothing was built again


def test_a_build_counts_its_phases_and_later_calls_leave_them_still(
        trainer_metrics, tmp_path):
    reg, tr = trainer_metrics, build()
    x, y = batch()
    tr.step(x, y)
    first = {k: reg.get(f"trainer.{k}").n for k in (
        "compile_calls", "compile_call_s", "trace_lower_s")}
    assert first["compile_calls"] == 1
    assert reg.get("trainer.compile_step").read() == 1
    assert 0 < first["trace_lower_s"] < first["compile_call_s"]
    assert reg.get("trainer.jit_call_us").count == 0
    for _ in range(3):
        tr.step(x, y)
    assert {k: reg.get(f"trainer.{k}").n for k in first} == first
    assert reg.get("trainer.compile_step").read() == 1
    assert reg.get("trainer.jit_call_us").count == 3
    # the hot path has nothing of jax's compile counters to read
    assert not hasattr(tr._dispatch_metrics, "jax_phases_n")
    assert not hasattr(tr._dispatch_metrics, "jax_trace_lower_s")


def test_a_build_inside_a_trace_is_three_spans_with_the_steps_number(
        tmp_path):
    tr = build()
    x, y = batch()
    tr.step(x, y)
    with tpt.jax_trace(tmp_path) as events:
        tr.step(*batch(16))                 # a new shape: built in the trace
        tr.step(*batch(16))
    (whole, plain) = tpt.find(events, "mx.trainer.jit_call_us")
    for name in ("step_trace", "step_lower", "step_compile"):
        (ev,) = tpt.find(events, f"mx.trainer.{name}")
        assert whole[1] <= ev[1] and ev[2] <= whole[2], name
        assert ev[3]["step_num"] == 2
    assert plain[3]["step_num"] == 3


def test_the_compile_publishes_what_the_step_holds(trainer_metrics):
    tr = build()
    x, y = batch()
    tr.step(x, y)
    (compiled,) = tr._compiled.values()
    assert trainer_metrics.get("trainer.step_temp_bytes").value == \
        compiled.memory_analysis().temp_size_in_bytes
    assert HELD()._step_compiled is compiled
    tr.forward(x)                           # not the train step: not published
    assert HELD()._step_compiled is compiled


@pytest.fixture
def text_reads(monkeypatch):
    calls = []
    real = jax.stages.Compiled.as_text

    def spy(self, *a, **kw):
        calls.append(self)
        return real(self, *a, **kw)
    monkeypatch.setattr(jax.stages.Compiled, "as_text", spy)
    return calls


def test_dropping_the_trainer_frees_arrays_and_executable_the_table_answers(
        text_reads):
    tr = build()
    x, y = batch()
    tr.step(x, y)
    arrays = [weakref.ref(a) for a in
              jax.tree.leaves((tr._pvals, tr._state))]
    owner, step_fn = weakref.ref(tr), weakref.ref(tr._jit_step)
    (compiled,) = tr._compiled.values()
    assert HELD()._step_compiled is compiled and text_reads == []
    del tr
    gc.collect()
    assert owner() is None and step_fn() is None
    assert [a for a in arrays if a() is not None] == []
    # a loaded executable keeps its temporaries reserved on a chip: the
    # profiler took its text as the trainer went and let it go
    assert text_reads == [compiled]
    assert HELD()._step_compiled is None and HELD()._step_text
    del compiled
    table = profiler.step_scopes()
    assert table and all(len(v) == 3 for v in table.values())
    assert HELD()._step_text is None and len(text_reads) == 1
    assert profiler.step_scopes() is table


def test_no_step_reads_the_modules_text(text_reads):
    tr = build()
    x, y = batch()
    for _ in range(3):
        tr.step(x, y)
    tr.forward(x)
    assert text_reads == []
    assert profiler.step_scopes() is profiler.step_scopes()
    assert len(text_reads) == 1
    assert HELD()._step_compiled is None            # read once, let go
    tr.step(*batch(16))                     # a new step: a new table, unread
    assert len(text_reads) == 1 and HELD()._step_table is None
    older = build()                         # a trainer whose step was replaced
    older.step(x, y)
    tr.step(*batch(24))
    del older
    gc.collect()
    assert len(text_reads) == 1             # is let go without a read


def test_a_newer_step_drops_the_text_kept_of_an_older_one(text_reads):
    gone = build()
    gone.step(*batch())
    del gone
    gc.collect()
    assert len(text_reads) == 1 and HELD()._step_text   # kept for the asking
    tr = build()
    tr.step(*batch())
    assert HELD()._step_text is None and HELD()._step_table is None
    assert len(text_reads) == 1


def test_step_scopes_states_the_cpu_steps_scopes():
    tr = build(remat=True)
    tr.step(*batch())
    table = profiler.step_scopes()
    scopes = {scope for scope, _, _ in table.values()}
    assert "optimizer" in scopes
    assert any(s.split("/")[0] == "loss" for s in scopes)
    assert any(s.endswith("/remat") or "/remat/" in s for s in scopes)
    # (the CPU compiler folds this small block's second forward away)
    assert {way for _, way, _ in table.values()} >= {"fwd", "bwd"}


# -- mx.profiler's tables with the step's table ------------------------------

def recorded_trace():
    with open(TRACE) as f:
        fx = json.load(f)
    return {"device": {p: [[h, fx["scopes"][i], s, d] for h, i, s, d in rows]
                       for p, rows in fx["device"].items()},
            "host": fx["host"]}


def test_inferred_time_goes_to_its_scope_and_is_marked():
    trace_ = recorded_trace()
    plain = profiler.reduce_trace(trace_, depth=6)
    assert plain["inferred"] == {} and plain["scopes"][""]["fwd"] > 0
    names = {}
    for rows in trace_["device"].values():
        for hlo, op_name, _, _ in rows:
            if not profiler.scope_of(op_name, 6)[0]:
                names[hlo.split(" = ", 1)[0].lstrip("%")] = hlo
    done = sorted(n for n in names if n.startswith("copy-done"))
    assert done, "the recorded step has prefetches without a scope"
    ffn = "bertmodel*/enc/layers_transformer_encoder_cell*/ffn"
    table = {n: (ffn, "fwd", True) for n in done[:3]}
    table[done[-1]] = ("lm*/layer*/remat/moe", "recompute", True)
    table["fusion.nowhere"] = (ffn, "fwd", True)    # not in the trace: nothing
    red = profiler.reduce_trace(trace_, depth=6, table=table)
    moved = red["inferred"][ffn]
    assert moved > 0
    assert red["scopes"][ffn]["fwd"] == pytest.approx(
        plain["scopes"].get(ffn, {"fwd": 0.0})["fwd"] + moved)
    # the forward run again is a row of its own, as scope_of has it
    again = "lm*/layer*/remat/recompute/moe"
    assert red["scopes"][again]["bwd"] == red["inferred"][again] > 0
    assert red["scopes"][""]["fwd"] == pytest.approx(
        plain["scopes"][""]["fwd"] - moved - red["inferred"][again])
    assert red["busy_s"] == plain["busy_s"]
    text = profiler.format_tables(red)
    row = next(l for l in text.splitlines() if l.startswith(ffn + " "))
    assert "~" in row and f"{moved:.6f}" in row
    assert "~inferred" in text.splitlines()[1]
    stated = next(l for l in text.splitlines() if l.startswith("optimizer"))
    assert "~" not in stated
    # an entry the table states (not inferred) for an event whose own
    # op_name is empty gets its scope and is not counted as inferred
    table[done[0]] = (ffn, "fwd", False)
    less = profiler.reduce_trace(trace_, depth=6, table=table)
    assert less["scopes"][ffn] == red["scopes"][ffn]
    assert 0 < less["inferred"][ffn] < moved


def test_dumps_reads_the_published_table(monkeypatch):
    trace_ = recorded_trace()
    plain = profiler.reduce_trace(trace_, depth=6)
    name = next(hlo.split(" = ", 1)[0].lstrip("%")
                for rows in trace_["device"].values()
                for hlo, op_name, _, _ in rows
                if not op_name and hlo.startswith("%copy-done"))
    monkeypatch.setattr(profiler, "load_xplane", lambda path: trace_)
    p = HELD()
    monkeypatch.setattr(p, "_step_compiled", None)
    monkeypatch.setattr(p, "_step_table", {name: ("optimizer", "fwd", True)})
    monkeypatch.setattr(p, "_xplane", "recorded")
    text = mx.profiler.dumps(depth=6)
    row = next(l for l in text.splitlines() if l.startswith("optimizer"))
    assert "~" in row
    want = profiler.reduce_trace(trace_, 6, profiler.step_scopes())
    assert want["scopes"]["optimizer"]["fwd"] > \
        plain["scopes"]["optimizer"]["fwd"]
    assert f"{want['scopes']['optimizer']['fwd']:.6f}" in row
