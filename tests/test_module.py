"""Module API tests (reference: tests/python/unittest/test_module.py +
tests/python/train/test_mlp.py, test_conv.py — tiny-train convergence)."""
import os

import numpy as np
import pytest

import mxnet_tpu as mx


def _mlp_sym(num_hidden=32, num_classes=4):
    data = mx.sym.Variable("data")
    fc1 = mx.sym.FullyConnected(data, name="fc1", num_hidden=num_hidden)
    act1 = mx.sym.Activation(fc1, name="relu1", act_type="relu")
    fc2 = mx.sym.FullyConnected(act1, name="fc2", num_hidden=num_classes)
    return mx.sym.SoftmaxOutput(fc2, name="softmax")


def _blob_data(n=400, dim=8, classes=4, seed=0):
    rng = np.random.RandomState(seed)
    centers = rng.uniform(-3, 3, size=(classes, dim))
    y = rng.randint(0, classes, size=n)
    x = centers[y] + rng.normal(0, 0.4, size=(n, dim))
    return x.astype(np.float32), y.astype(np.float32)


def test_module_basic_bind_forward():
    sym = _mlp_sym()
    mod = mx.mod.Module(sym, context=mx.cpu())
    mod.bind(data_shapes=[("data", (10, 8))],
             label_shapes=[("softmax_label", (10,))])
    mod.init_params()
    assert mod.binded and mod.params_initialized
    batch = mx.io.DataBatch(data=[mx.nd.ones((10, 8))],
                            label=[mx.nd.zeros((10,))])
    mod.forward(batch, is_train=False)
    out = mod.get_outputs()[0]
    assert out.shape == (10, 4)
    np.testing.assert_allclose(out.asnumpy().sum(axis=1), 1.0, rtol=1e-5)


def test_module_input_names_validation():
    sym = _mlp_sym()
    with pytest.raises(ValueError):
        mx.mod.Module(sym, data_names=("wrong_name",))


def test_module_fit_mlp_converges():
    X, Y = _blob_data()
    train = mx.io.NDArrayIter(X, Y, batch_size=40, shuffle=True)
    val = mx.io.NDArrayIter(X, Y, batch_size=40)
    mod = mx.mod.Module(_mlp_sym(), context=mx.cpu())
    mod.fit(train, eval_data=val, optimizer="sgd",
            optimizer_params={"learning_rate": 0.5, "momentum": 0.9},
            num_epoch=8, eval_metric="acc")
    score = mod.score(val, "acc")
    assert score[0][1] > 0.95, score


def test_module_predict_and_input_grads():
    X, Y = _blob_data(n=100)
    it = mx.io.NDArrayIter(X, Y, batch_size=20)
    mod = mx.mod.Module(_mlp_sym(), context=mx.cpu())
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label,
             inputs_need_grad=True)
    mod.init_params()
    pred = mod.predict(it)
    assert pred.shape == (100, 4)
    # input grads flow
    it.reset()
    batch = next(iter(it))
    mod.forward(batch, is_train=True)
    mod.backward()
    (dgrad,) = mod.get_input_grads()
    assert dgrad.shape == (20, 8)
    assert float(dgrad.abs().sum().asscalar()) > 0


def test_module_initializes_gluon_symbol_params():
    """A gluon block traced into a Symbol leaves the plain initializer
    names of its Parameters (init="ones") on the variables; Module's
    initializer must honour them like `Initializer.dumps()` JSON."""
    net = mx.gluon.nn.HybridSequential()
    net.add(mx.gluon.nn.Dense(4), mx.gluon.nn.BatchNorm())
    sym = mx.sym.SoftmaxOutput(net(mx.sym.Variable("data")), name="softmax")
    mod = mx.mod.Module(sym, context=mx.cpu())
    mod.bind(data_shapes=[("data", (2, 3))],
             label_shapes=[("softmax_label", (2,))])
    mod.init_params(mx.init.Xavier())
    args, aux = mod.get_params()
    by_suffix = {n.rsplit("_", 1)[-1]: v.asnumpy()
                 for n, v in list(args.items()) + list(aux.items())}
    assert (by_suffix["gamma"] == 1).all() and (by_suffix["beta"] == 0).all()
    assert (by_suffix["var"] == 1).all() and (by_suffix["mean"] == 0).all()
    assert np.abs(by_suffix["weight"]).sum() > 0


def test_module_get_set_params_roundtrip():
    mod = mx.mod.Module(_mlp_sym(), context=mx.cpu())
    mod.bind(data_shapes=[("data", (4, 8))],
             label_shapes=[("softmax_label", (4,))])
    mod.init_params(initializer=mx.init.Uniform(0.1))
    args, auxs = mod.get_params()
    assert set(args) == {"fc1_weight", "fc1_bias", "fc2_weight", "fc2_bias"}
    mod2 = mx.mod.Module(_mlp_sym(), context=mx.cpu())
    mod2.bind(data_shapes=[("data", (4, 8))],
              label_shapes=[("softmax_label", (4,))])
    mod2.init_params(arg_params=args, aux_params=auxs)
    a2, _ = mod2.get_params()
    for k in args:
        np.testing.assert_allclose(args[k].asnumpy(), a2[k].asnumpy())


def test_module_checkpoint_roundtrip(tmp_path):
    prefix = str(tmp_path / "mlp")
    mod = mx.mod.Module(_mlp_sym(), context=mx.cpu())
    mod.bind(data_shapes=[("data", (4, 8))],
             label_shapes=[("softmax_label", (4,))])
    mod.init_params()
    mod.save_checkpoint(prefix, 3)
    assert os.path.exists(f"{prefix}-symbol.json")
    assert os.path.exists(f"{prefix}-0003.params")
    mod2 = mx.mod.Module.load(prefix, 3, context=mx.cpu())
    mod2.bind(data_shapes=[("data", (4, 8))],
              label_shapes=[("softmax_label", (4,))])
    mod2.init_params(arg_params=mod2._arg_params, aux_params=mod2._aux_params,
                     force_init=True)
    a1, _ = mod.get_params()
    a2, _ = mod2.get_params()
    for k in a1:
        np.testing.assert_allclose(a1[k].asnumpy(), a2[k].asnumpy())


def test_module_fixed_params():
    mod = mx.mod.Module(_mlp_sym(), context=mx.cpu(),
                        fixed_param_names=["fc1_weight", "fc1_bias"])
    mod.bind(data_shapes=[("data", (8, 8))],
             label_shapes=[("softmax_label", (8,))])
    mod.init_params()
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 1.0})
    before, _ = mod.get_params()
    batch = mx.io.DataBatch(data=[mx.nd.ones((8, 8))],
                            label=[mx.nd.zeros((8,))])
    mod.forward_backward(batch)
    mod.update()
    after, _ = mod.get_params()
    np.testing.assert_allclose(before["fc1_weight"].asnumpy(),
                               after["fc1_weight"].asnumpy())
    assert not np.allclose(before["fc2_weight"].asnumpy(),
                           after["fc2_weight"].asnumpy())


def test_module_update_on_kvstore_device():
    """kvstore='device' path: optimizer runs inside the store."""
    X, Y = _blob_data(n=120)
    train = mx.io.NDArrayIter(X, Y, batch_size=30, shuffle=True)
    mod = mx.mod.Module(_mlp_sym(), context=mx.cpu())
    mod.fit(train, optimizer="sgd",
            optimizer_params={"learning_rate": 0.5, "momentum": 0.9},
            kvstore="device", num_epoch=6, eval_metric="acc")
    score = mod.score(mx.io.NDArrayIter(X, Y, batch_size=30), "acc")
    assert score[0][1] > 0.9, score


def test_lenet_mnist_e2e():
    """SURVEY.md §7 stage-5 milestone: LeNet on (synthetic) MNIST via
    Module.fit (BASELINE config 1)."""
    data = mx.sym.Variable("data")
    c1 = mx.sym.Convolution(data, name="conv1", kernel=(5, 5), num_filter=8)
    a1 = mx.sym.Activation(c1, act_type="tanh")
    p1 = mx.sym.Pooling(a1, pool_type="max", kernel=(2, 2), stride=(2, 2))
    c2 = mx.sym.Convolution(p1, name="conv2", kernel=(5, 5), num_filter=16)
    a2 = mx.sym.Activation(c2, act_type="tanh")
    p2 = mx.sym.Pooling(a2, pool_type="max", kernel=(2, 2), stride=(2, 2))
    fl = mx.sym.Flatten(p2)
    f1 = mx.sym.FullyConnected(fl, name="fc1", num_hidden=64)
    a3 = mx.sym.Activation(f1, act_type="tanh")
    f2 = mx.sym.FullyConnected(a3, name="fc2", num_hidden=10)
    lenet = mx.sym.SoftmaxOutput(f2, name="softmax")

    train = mx.io.MNISTIter(image="/nonexistent", batch_size=64, silent=True,
                            synthetic_size=512, seed=7)
    mod = mx.mod.Module(lenet, context=mx.cpu())
    mod.fit(train, optimizer="sgd", initializer=mx.init.Xavier(),
            optimizer_params={"learning_rate": 0.3, "momentum": 0.9},
            num_epoch=12, eval_metric="acc",
            batch_end_callback=mx.callback.Speedometer(64, 4))
    score = mod.score(mx.io.MNISTIter(image="/nonexistent", batch_size=64,
                                      silent=True, synthetic_size=512,
                                      seed=7), "acc")
    assert score[0][1] > 0.9, score
