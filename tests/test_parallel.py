"""Data-parallel mesh tests on the virtual 8-device CPU mesh
(role of tests/python/gpu/test_nccl.py + multi_lenet.py parity checks)."""
import os

import numpy as np
import pytest

import mxnet_tpu as mx

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
from mxnet_tpu.parallel import build_mesh, data_parallel_mesh, \
    DataParallelTrainer


def _mlp():
    data = mx.sym.Variable("data")
    f1 = mx.sym.FullyConnected(data, name="fc1", num_hidden=16)
    a1 = mx.sym.Activation(f1, act_type="relu")
    f2 = mx.sym.FullyConnected(a1, name="fc2", num_hidden=3)
    return mx.sym.SoftmaxOutput(f2, name="softmax")


def test_build_mesh():
    import jax
    assert len(jax.devices()) == 8, "conftest must force 8 cpu devices"
    mesh = build_mesh({"data": 4, "model": 2})
    assert mesh.axis_names == ("data", "model")
    assert mesh.devices.shape == (4, 2)
    with pytest.raises(ValueError):
        build_mesh({"data": 64})


def test_dp_trainer_runs_and_learns():
    mesh = data_parallel_mesh(8)
    sym = _mlp()
    batch = 64
    trainer = DataParallelTrainer(sym, mesh, learning_rate=0.1, momentum=0.9,
                                  rescale_grad=1.0 / batch)
    assert trainer.param_names == ["fc1_weight", "fc1_bias", "fc2_weight",
                                   "fc2_bias"]
    params, momenta, aux = trainer.init_state(
        {"data": (batch, 8), "softmax_label": (batch,)},
        initializer=mx.init.Xavier())

    rng = np.random.RandomState(0)
    centers = rng.uniform(-2, 2, size=(3, 8)).astype(np.float32)
    losses = []
    for i in range(30):
        y = rng.randint(0, 3, size=batch)
        x = centers[y] + rng.normal(0, 0.3, size=(batch, 8)).astype(np.float32)
        inputs = trainer.shard_inputs([x.astype(np.float32),
                                       y.astype(np.float32)])
        params, momenta, aux, loss, outputs = trainer.step(
            params, momenta, aux, inputs)
        losses.append(float(loss))
    # outputs of SoftmaxOutput head are probs; check final accuracy
    probs = np.asarray(outputs[0])
    assert probs.shape == (batch, 3)
    acc = (probs.argmax(1) == y).mean()
    assert acc > 0.9, (acc, losses[:3], losses[-3:])


def test_dp_matches_single_device():
    """DP over 8 shards must produce the same params as 1-device training
    (the reference's multi_lenet.py parity invariant)."""
    sym = _mlp()
    batch = 32
    rng = np.random.RandomState(1)
    x = rng.normal(size=(batch, 8)).astype(np.float32)
    y = rng.randint(0, 3, size=batch).astype(np.float32)

    results = []
    for ndev in (1, 8):
        mesh = data_parallel_mesh(ndev)
        trainer = DataParallelTrainer(sym, mesh, learning_rate=0.05,
                                      momentum=0.9, rescale_grad=1.0 / batch)
        params, momenta, aux = trainer.init_state(
            {"data": (batch, 8), "softmax_label": (batch,)})
        inputs = trainer.shard_inputs([x, y])
        for _ in range(3):
            params, momenta, aux, loss, _ = trainer.step(
                params, momenta, aux, inputs)
        results.append([np.asarray(p) for p in params])
    for p1, p8 in zip(*results):
        np.testing.assert_allclose(p1, p8, rtol=2e-4, atol=1e-5)


def test_module_multi_context_parity():
    """Module(context=[8 devices]).fit must match single-device training
    (reference invariant: tests/nightly/multi_lenet.py; round-1 defect:
    module.py used context[0] only)."""
    sym = _mlp()
    batch = 32
    rng = np.random.RandomState(3)
    X = rng.normal(size=(128, 8)).astype(np.float32)
    Y = rng.randint(0, 3, size=128).astype(np.float32)

    # common starting params
    mod0 = mx.mod.Module(sym, context=mx.cpu(0))
    it = mx.io.NDArrayIter(X, Y, batch_size=batch)
    mod0.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod0.init_params(initializer=mx.init.Xavier())
    arg0, aux0 = mod0.get_params()

    results = []
    for ctxs in ([mx.cpu(0)], [mx.cpu(i) for i in range(8)]):
        it = mx.io.NDArrayIter(X, Y, batch_size=batch)
        mod = mx.mod.Module(sym, context=ctxs)
        mod.fit(it, num_epoch=3, arg_params=arg0, aux_params=aux0,
                optimizer="sgd",
                optimizer_params={"learning_rate": 0.1, "momentum": 0.9})
        arg, _ = mod.get_params()
        results.append({k: v.asnumpy() for k, v in arg.items()})
    for k in results[0]:
        np.testing.assert_allclose(results[0][k], results[1][k],
                                   rtol=2e-4, atol=1e-5)


def test_module_multi_context_batch_divisibility():
    sym = _mlp()
    mod = mx.mod.Module(sym, context=[mx.cpu(i) for i in range(8)])
    with pytest.raises(mx.base.MXNetError):
        mod.bind(data_shapes=[("data", (12, 8))],
                 label_shapes=[("softmax_label", (12,))])


def test_gluon_trainer_mesh_parity():
    """gluon: initialize(ctx=[...8]) + split_and_load trains identically to
    single-device (params mesh-replicated, batch sharded, psum fused)."""
    from mxnet_tpu import gluon, autograd

    batch = 32
    rng = np.random.RandomState(5)
    X = rng.normal(size=(batch, 10)).astype(np.float32)
    Y = rng.randint(0, 3, size=batch).astype(np.float32)

    results = []
    for ctxs in ([mx.cpu(0)], [mx.cpu(i) for i in range(8)]):
        net = gluon.nn.HybridSequential()
        net.add(gluon.nn.Dense(16, activation="relu"), gluon.nn.Dense(3))
        net.initialize(mx.init.Xavier(rnd_type="gaussian"), ctx=ctxs)
        net.hybridize()
        net(gluon.utils.split_and_load(X, ctxs)[0])  # finish deferred init
        # deterministic start
        for i, (_, p) in enumerate(net.collect_params().items()):
            prng = np.random.RandomState(100 + i)
            p.set_data(mx.nd.array(
                prng.normal(0, 0.1, size=p.shape).astype(np.float32)))
        trainer = gluon.Trainer(net.collect_params(), "sgd",
                                {"learning_rate": 0.1, "momentum": 0.9})
        loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
        for _ in range(3):
            for x, y in zip(gluon.utils.split_and_load(X, ctxs),
                            gluon.utils.split_and_load(Y, ctxs)):
                with autograd.record():
                    loss = loss_fn(net(x), y)
                loss.backward()
            trainer.step(batch)
        results.append([p.data(ctxs[0]).asnumpy()
                        for _, p in net.collect_params().items()])
    for p1, p8 in zip(*results):  # auto-prefixes differ; order is stable
        np.testing.assert_allclose(p1, p8, rtol=2e-4, atol=1e-5)


def test_dp_trainer_adam_converges():
    """Generalized fused optimizer: adam in the sharded step."""
    mesh = data_parallel_mesh(8)
    sym = _mlp()
    batch = 64
    trainer = DataParallelTrainer(sym, mesh, optimizer="adam",
                                  learning_rate=0.01,
                                  rescale_grad=1.0 / batch)
    params, states, aux = trainer.init_state(
        {"data": (batch, 8), "softmax_label": (batch,)},
        initializer=mx.init.Xavier())
    assert all(len(st) == 2 for st in states)  # mean, var
    rng = np.random.RandomState(0)
    centers = rng.uniform(-2, 2, size=(3, 8)).astype(np.float32)
    for i in range(40):
        y = rng.randint(0, 3, size=batch)
        x = (centers[y] + rng.normal(0, 0.3, size=(batch, 8))
             ).astype(np.float32)
        inputs = trainer.shard_inputs([x, y.astype(np.float32)])
        params, states, aux, loss, outputs = trainer.step(
            params, states, aux, inputs)
    probs = np.asarray(outputs[0])
    acc = (probs.argmax(1) == y).mean()
    assert acc > 0.9, acc


def test_dryrun_multichip_hook():
    import sys
    sys.path.insert(0, _REPO)
    import __graft_entry__ as ge
    ge.dryrun_multichip(8)


def test_dryrun_multichip_driver_env():
    """Run the hook in a FRESH interpreter without the conftest's
    settings and without JAX_PLATFORMS: the hook itself must select its
    virtual CPU mesh before anything starts a backend."""
    import os
    import subprocess
    import sys
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "JAX_NUM_CPU_DEVICES", "XLA_FLAGS")}
    proc = subprocess.run(
        [sys.executable, "-c",
         "import __graft_entry__ as ge; ge.dryrun_multichip(8); print('OK')"],
        cwd=_REPO, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "OK" in proc.stdout


def test_entry_hook_compiles():
    import sys
    sys.path.insert(0, _REPO)
    import jax
    import __graft_entry__ as ge
    fn, example_args = ge.entry()
    out = jax.jit(fn)(*example_args)
    assert out.shape == (32, 1000)  # flagship: ResNet-50 inference b32
    np.testing.assert_allclose(np.asarray(out).sum(axis=1), 1.0, rtol=1e-3)


def test_dp_trainer_bf16_multiprecision():
    """bf16 compute with fp32 master params converges like fp32
    (reference multi_precision role, optimizer.py:201)."""
    import numpy as np
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu.parallel import data_parallel_mesh, DataParallelTrainer

    sym = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
        mx.sym.Variable("data"), num_hidden=4, name="fc"), name="softmax")
    mesh = data_parallel_mesh(4, jax.devices()[:4])
    tr = DataParallelTrainer(sym, mesh, optimizer="sgd", learning_rate=0.1,
                             momentum=0.9, dtype="bfloat16",
                             rescale_grad=1.0 / 16)
    rng = np.random.RandomState(0)
    x = rng.normal(size=(16, 8)).astype(np.float32)
    w = rng.normal(size=(4, 8)).astype(np.float32)
    y = (x @ w.T).argmax(1).astype(np.float32)
    params, states, aux = tr.init_state({"data": (16, 8),
                                         "softmax_label": (16,)})
    inputs = tr.shard_inputs([x, y])
    for _ in range(40):
        params, states, aux, loss, outs = tr.step(params, states, aux,
                                                  inputs)
    assert str(params[0].dtype) == "float32"      # fp32 masters
    acc = (np.asarray(outs[0]).argmax(1) == y).mean()
    assert acc >= 0.9
