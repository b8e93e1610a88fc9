"""Trainer-integration tier: small end-to-end trainings asserting a final
accuracy, the role of the reference's tests/python/train/{test_mlp.py,
test_conv.py} (SURVEY.md §4 tier 'Trainer integration').

The reference trains on downloaded MNIST and asserts >0.97; this image has
zero egress, so the datasets are sklearn's bundled handwritten digits
(1797 real 8x8 digit scans — load_digits) at native resolution for the
MLP and kron-upsampled to 32x32 for LeNet. A failing accuracy FAILS the
suite — these are convergence proofs, not smoke tests.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon
from mxnet_tpu.gluon import nn

ACC_TARGET = 0.97


def _digits(upsample=False, seed=7):
    from sklearn.datasets import load_digits
    d = load_digits()
    x = (d.data.astype(np.float32) / 16.0)
    y = d.target.astype(np.float32)
    rng = np.random.RandomState(seed)
    idx = rng.permutation(len(y))
    x, y = x[idx], y[idx]
    if upsample:
        img = x.reshape(-1, 8, 8)
        img = np.kron(img, np.ones((1, 4, 4), np.float32))  # 8x8 -> 32x32
        x = img[:, None, :, :]
    n_train = 1437
    return (x[:n_train], y[:n_train]), (x[n_train:], y[n_train:])


def _mlp_symbol():
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=128, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=64, name="fc2")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=10, name="fc3")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _lenet_symbol():
    data = mx.sym.Variable("data")
    net = mx.sym.Convolution(data, kernel=(5, 5), num_filter=20, name="c1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.Pooling(net, pool_type="max", kernel=(2, 2), stride=(2, 2))
    net = mx.sym.Convolution(net, kernel=(5, 5), num_filter=50, name="c2")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.Pooling(net, pool_type="max", kernel=(2, 2), stride=(2, 2))
    net = mx.sym.FullyConnected(mx.sym.Flatten(net), num_hidden=256,
                                name="f1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=10, name="f2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _fit_and_score(sym, train, val, batch=64, epochs=20, lr=0.1):
    # seeded: unseeded, the shuffle order and Xavier draws inherit whatever
    # state the tests before this one in the same worker left, and an
    # unlucky draw lands a hair under the bar (0.9635 seen)
    np.random.seed(0)
    mx.random.seed(0)
    (xt, yt), (xv, yv) = train, val
    it = mx.io.NDArrayIter(xt, yt, batch_size=batch, shuffle=True,
                           label_name="softmax_label")
    vit = mx.io.NDArrayIter(xv, yv, batch_size=batch,
                            label_name="softmax_label")
    mod = mx.mod.Module(sym, context=mx.cpu())
    mod.fit(it, num_epoch=epochs, optimizer="sgd",
            optimizer_params={"learning_rate": lr, "momentum": 0.9},
            initializer=mx.init.Xavier())
    vit.reset()
    return dict(mod.score(vit, mx.metric.Accuracy()))["accuracy"]


def test_mlp_module_fit_reaches_97():
    """Reference: tests/python/train/test_mlp.py — MLP via Module.fit."""
    train, val = _digits(upsample=False)
    acc = _fit_and_score(_mlp_symbol(), train, val, epochs=25, lr=0.1)
    assert acc > ACC_TARGET, f"MLP val accuracy {acc:.4f} <= {ACC_TARGET}"


def test_lenet_module_fit_reaches_97():
    """Reference: tests/python/train/test_conv.py — LeNet via Module.fit."""
    train, val = _digits(upsample=True)
    acc = _fit_and_score(_lenet_symbol(), train, val, epochs=12, lr=0.05)
    assert acc > ACC_TARGET, f"LeNet val accuracy {acc:.4f} <= {ACC_TARGET}"


def test_mlp_gluon_trainer_reaches_97():
    """Same convergence bar through the imperative Gluon path:
    HybridBlock + autograd + gluon.Trainer (reference gluon/mnist.py)."""
    (xt, yt), (xv, yv) = _digits(upsample=False)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(128, activation="relu"),
                nn.Dense(64, activation="relu"),
                nn.Dense(10))
    mx.random.seed(0)
    net.initialize(mx.init.Xavier())
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1, "momentum": 0.9})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    batch = 64
    from mxnet_tpu import autograd
    for epoch in range(25):
        perm = np.random.RandomState(epoch).permutation(len(yt))
        for i in range(0, len(yt) - batch + 1, batch):
            sel = perm[i:i + batch]
            x = mx.nd.array(xt[sel])
            y = mx.nd.array(yt[sel])
            with autograd.record():
                out = net(x)
                loss = loss_fn(out, y)
            loss.backward()
            trainer.step(batch)
    pred = net(mx.nd.array(xv)).asnumpy().argmax(axis=1)
    acc = float((pred == yv).mean())
    assert acc > ACC_TARGET, f"gluon MLP val accuracy {acc:.4f} <= 0.97"
