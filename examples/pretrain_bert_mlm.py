"""BERT pretraining example: the real masked-LM + NSP objective through
the sharded trainer, with optional flash attention.

BASELINE config #3 as a user-facing recipe:
  - 15% of (valid) tokens masked; labels are the original ids; the loss
    is CE over masked positions plus the NSP head's CE
  - padding arrives as (B,) valid LENGTHS (the GluonNLP valid_length
    idiom) so the Pallas flash kernel can mask per row even under jit
  - the whole train step is ONE jitted computation (ShardedTrainer);
    on a multi-chip mesh the same script shards dp x tp x sp

Run (synthetic data, tiny model):
  python examples/pretrain_bert_mlm.py --steps 20
  MXNET_ATTENTION_KERNEL=flash python examples/pretrain_bert_mlm.py
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu import parallel as par
from mxnet_tpu.gluon.model_zoo.transformer import bert_base, bert_small

MASK_ID = 3


def mlm_nsp_loss(out, ys):
    mlm, nsp = out
    labels, weights, nsp_y = ys
    logp = nd.log_softmax(mlm, axis=-1)
    ce = -nd.pick(logp, labels, axis=-1)
    mlm_l = nd.sum(ce * weights) / nd.sum(weights)
    nsp_logp = nd.log_softmax(nsp, axis=-1)
    return mlm_l - nd.mean(nd.pick(nsp_logp, nsp_y, axis=-1))


def synthetic_batch(rng, batch, seq, vocab):
    tokens = rng.integers(4, vocab, (batch, seq))
    valid_lens = rng.integers(seq // 2, seq + 1, (batch,))
    valid = np.arange(seq)[None, :] < valid_lens[:, None]
    mask_pos = (rng.random((batch, seq)) < 0.15) & valid
    mask_pos[:, 1] = True
    inputs = np.where(mask_pos, MASK_ID, tokens)
    segs = np.zeros((batch, seq), np.int64)
    nsp_y = rng.integers(0, 2, (batch,))
    x = (inputs, segs, valid_lens.astype(np.float32))
    y = (tokens, mask_pos.astype(np.float32), nsp_y)
    return x, y


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--full-size", action="store_true",
                    help="bert_base instead of the tiny test config")
    args = ap.parse_args()

    import jax
    n_dev = len(jax.devices())
    batch = max(args.batch, n_dev) // n_dev * n_dev   # dp-shardable
    vocab = 30522 if args.full_size else 1000
    net = (bert_base if args.full_size else bert_small)(dropout=0.0)
    net.initialize()
    tr = par.ShardedTrainer(net, mlm_nsp_loss, "adam",
                            {"learning_rate": 3e-3})
    rng = np.random.default_rng(0)
    x, y = synthetic_batch(rng, batch, args.seq, vocab)
    for step in range(args.steps):
        loss = tr.step(x, y, batch_size=1)
        if step % 5 == 0 or step == args.steps - 1:
            print(f"step {step}: mlm+nsp loss {float(loss.asnumpy()):.4f}",
                  flush=True)


if __name__ == "__main__":
    main()
