"""Imperative Gluon MLP training (BASELINE config #1; reference:
example/image-classification/train_mnist.py).

Runs on real handwritten-digit data (sklearn's bundled digits scans —
no download needed) or synthetic MNIST-shaped data with --synthetic.

    python examples/train_mnist_mlp.py --epochs 10
"""
import argparse
import sys
import os

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon


def load_data(synthetic: bool):
    if synthetic:
        rng = np.random.RandomState(7)
        temp = rng.rand(10, 64).astype(np.float32)
        y = rng.randint(0, 10, 2000)
        X = temp[y] + 0.1 * rng.randn(2000, 64).astype(np.float32)
    else:
        from sklearn.datasets import load_digits
        X, y = load_digits(return_X_y=True)
        X = X.astype(np.float32) / 16.0
    X -= 0.5
    n = int(len(X) * 0.85)
    return (X[:n], y[:n]), (X[n:], y[n:])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=10)
    ap.add_argument("--batch-size", type=int, default=100)
    ap.add_argument("--lr", type=float, default=2e-3)
    ap.add_argument("--synthetic", action="store_true")
    ap.add_argument("--hybridize", action="store_true")
    ap.add_argument("--ctx", default="tpu(0)",
                    help="context to place the model and data on — the "
                         "default context is the host, so a run meant "
                         "for the chip names it (cpu(0) for a host run)")
    args = ap.parse_args()
    ctx = mx.Context.from_str(args.ctx)
    print(f"running on {ctx}: {ctx.device.platform} "
          f"{ctx.device.device_kind}")

    (Xtr, ytr), (Xte, yte) = load_data(args.synthetic)
    net = gluon.nn.HybridSequential()
    with net.name_scope():
        net.add(gluon.nn.Dense(128, activation="relu"),
                gluon.nn.Dense(64, activation="relu"),
                gluon.nn.Dense(10))
    net.initialize(mx.init.Xavier(), ctx=ctx)
    if args.hybridize:
        net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": args.lr})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    metric = mx.metric.Accuracy()

    for epoch in range(args.epochs):
        metric.reset()
        for i in range(0, len(Xtr), args.batch_size):
            x = mx.nd.array(Xtr[i:i + args.batch_size], ctx=ctx)
            y = mx.nd.array(ytr[i:i + args.batch_size], ctx=ctx)
            with autograd.record():
                out = net(x)
                L = loss_fn(out, y)
            L.backward()
            trainer.step(x.shape[0])
            metric.update([y], [out])
        test_acc = float(np.mean(np.argmax(
            net(mx.nd.array(Xte, ctx=ctx)).asnumpy(), 1) == yte))
        print(f"epoch {epoch}: train {metric.get()[1]:.4f} "
              f"test {test_acc:.4f}")


if __name__ == "__main__":
    main()
