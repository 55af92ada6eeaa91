"""Transformer family: BERT + NMT forward/backward, masking semantics,
weight tying, and tensor/sequence-parallel training over the mesh."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, parallel as par
from mxnet_tpu.gluon import loss as gloss
from mxnet_tpu.gluon.model_zoo.transformer import (
    MultiHeadAttention, TransformerNMT, TP_RULES, bert_small)


def test_attention_masking():
    """Masked-out keys must not affect attention output: compare a padded
    sequence vs the same sequence with garbage in the padded slots."""
    np.random.seed(0)
    att = MultiHeadAttention(16, 4, prefix="att_")
    att.initialize()
    x1 = np.random.randn(2, 6, 16).astype(np.float32)
    x2 = x1.copy()
    x2[:, 4:, :] = 99.0  # garbage in padded positions
    mask = np.zeros((8, 6, 6), np.float32)  # B*H=8
    mask[:, :, :4] = 1.0
    o1 = att(nd.array(x1), nd.array(mask)).asnumpy()
    o2 = att(nd.array(x2), nd.array(mask)).asnumpy()
    np.testing.assert_allclose(o1[:, :4], o2[:, :4], rtol=1e-5, atol=1e-5)


def test_bert_shapes_and_backward():
    net = bert_small(vocab_size=100)
    net.initialize()
    tokens = nd.array(np.random.randint(0, 100, (2, 12)), dtype="int32")
    types = nd.array(np.zeros((2, 12)), dtype="int32")
    valid = nd.array(np.ones((2, 12), np.float32))
    with mx.autograd.record():
        mlm, nsp = net(tokens, types, valid)
        l = mlm.sum() + nsp.sum()
    l.backward()
    assert mlm.shape == (2, 12, 100)
    assert nsp.shape == (2, 2)
    # by the net's own prefix: its number says how many were built before
    # it on this worker, which the order of the files decides
    g = net.collect_params()[net.prefix + "word_embed_weight"].data().grad
    assert float(abs(g).sum().asnumpy()) > 0


def test_nmt_weight_tying():
    net = TransformerNMT(vocab_size=50, num_layers=1, units=16,
                         hidden_size=32, num_heads=2, max_length=16,
                         prefix="nmt_")
    net.initialize()
    params = net.collect_params()
    assert not any(n.endswith("out_weight") for n in params), \
        "tied output projection must not own a weight"
    src = nd.array(np.random.randint(0, 50, (2, 5)), dtype="int32")
    tgt = nd.array(np.random.randint(0, 50, (2, 7)), dtype="int32")
    out = net(src, tgt)
    assert out.shape == (2, 7, 50)


def test_nmt_causal_mask():
    """Decoder position t must not depend on target positions > t."""
    net = TransformerNMT(vocab_size=30, num_layers=1, units=16,
                         hidden_size=32, num_heads=2, max_length=16,
                         dropout=0.0, prefix="causal_")
    net.initialize()
    src = nd.array(np.random.randint(0, 30, (1, 4)), dtype="int32")
    t1 = np.random.randint(0, 30, (1, 6))
    t2 = t1.copy()
    t2[0, 4:] = (t2[0, 4:] + 7) % 30   # perturb the future
    o1 = net(src, nd.array(t1, dtype="int32")).asnumpy()
    o2 = net(src, nd.array(t2, dtype="int32")).asnumpy()
    np.testing.assert_allclose(o1[0, :4], o2[0, :4], rtol=1e-5, atol=1e-5)


def test_bert_tp_sp_training():
    """BERT on a dp×tp×sp mesh: loss decreases with megatron-style weight
    sharding and sequence-sharded activations."""
    np.random.seed(1)
    mesh = par.make_mesh({"dp": 2, "tp": 2, "sp": 2})
    net = bert_small(vocab_size=64, dropout=0.0)
    net.initialize()

    class MLMLoss:
        def __call__(self, outs, y):
            mlm, _ = outs
            sce = gloss.SoftmaxCrossEntropyLoss()
            return sce(mlm.reshape((-1, 64)), y.reshape((-1,)))

    tr = par.ShardedTrainer(
        net, MLMLoss(), "adam", {"learning_rate": 3e-3}, mesh=mesh,
        rules=par.ShardingRules(TP_RULES), data_spec=("dp", "sp"),
        label_spec=("dp", "sp"))
    toks = np.random.randint(0, 64, (8, 16)).astype(np.int32)
    types = np.zeros((8, 16), np.int32)
    valid = np.ones((8, 16), np.float32)
    labels = toks.copy()
    losses = []
    for _ in range(6):
        losses.append(
            float(tr.step((toks, types, valid), labels).asnumpy()))
    assert losses[-1] < losses[0], losses


def test_nmt_translate_greedy_and_beam():
    """translate() (the Sockeye workflow, config #4): a copy-task model
    must reproduce source tokens through greedy and beam decoding."""
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon, nd
    from mxnet_tpu.gluon.model_zoo.transformer import TransformerNMT

    V, BOS, EOS, L = 8, 1, 2, 4
    rs = np.random.RandomState(0)
    net = TransformerNMT(vocab_size=V, num_layers=1, units=32,
                         hidden_size=64, num_heads=4, max_length=16,
                         dropout=0.0)
    net.initialize(mx.init.Xavier())
    tr = gluon.Trainer(net.collect_params(), "adam",
                       {"learning_rate": 5e-3})
    lf = gluon.loss.SoftmaxCrossEntropyLoss()

    def make_batch(n=32):
        src = rs.randint(3, V, (n, L))
        tgt_in = np.concatenate([np.full((n, 1), BOS), src], 1)
        tgt_out = np.concatenate([src, np.full((n, 1), EOS)], 1)
        return nd.array(src), nd.array(tgt_in), nd.array(tgt_out)

    for _ in range(260):
        src, ti, to = make_batch()
        with autograd.record():
            logits = net(src, ti)
            loss = nd.mean(lf(nd.reshape(logits, shape=(-1, V)),
                              nd.reshape(to, shape=(-1,))))
        loss.backward()
        tr.step(32)
    assert float(loss.asnumpy()) < 0.2, float(loss.asnumpy())

    src, _, _ = make_batch(4)
    srcl = src.asnumpy().astype(int).tolist()

    def token_acc(outs):
        hits = total = 0
        for o, s in zip(outs, srcl):
            for i, t in enumerate(s):
                hits += (i < len(o) and o[i] == t)
                total += 1
        return hits / total

    greedy, _ = net.translate(src, bos=BOS, eos=EOS, max_len=8)
    assert token_acc(greedy) >= 0.8, (greedy, srcl)
    beam, scores = net.translate(src, bos=BOS, eos=EOS, max_len=8,
                                 beam_size=3)
    assert token_acc(beam) >= 0.8, (beam, srcl)
    assert len(scores) == 4 and all(s <= 0 for s in scores)


def test_translate_scores_and_edge_cases():
    """Greedy scores are real GNMT-normalized log-probs (comparable to
    beam); beam with max_len=0 returns empty rows, not a crash; MC-
    dropout (train_mode inference) keeps the stochastic XLA attention
    path even with the flash flag set (review regressions)."""
    import os
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, nd
    from mxnet_tpu.gluon.model_zoo.transformer import (
        MultiHeadAttention, TransformerNMT)

    net = TransformerNMT(vocab_size=8, num_layers=1, units=16,
                         hidden_size=32, num_heads=2, max_length=16)
    net.initialize()
    src = nd.array(np.random.RandomState(0).randint(3, 8, (2, 4)))
    out, gs = net.translate(src, bos=1, eos=2, max_len=5)
    assert len(gs) == 2 and all(s <= 0 for s in gs)
    assert any(s < 0 for s in gs)
    outb, bs = net.translate(src, bos=1, eos=2, max_len=0, beam_size=2)
    assert outb == [[], []]

    att = MultiHeadAttention(units=16, num_heads=2, dropout=0.5)
    att.initialize()
    x = nd.array(np.random.RandomState(1).randn(1, 6, 16)
                 .astype(np.float32))
    os.environ["MXNET_ATTENTION_KERNEL"] = "flash"
    try:
        with autograd.train_mode():
            a = att(x).asnumpy()
            b = att(x).asnumpy()
    finally:
        del os.environ["MXNET_ATTENTION_KERNEL"]
    assert not np.allclose(a, b)
