"""steps_per_dispatch (K fused steps per dispatch) tests.

The K-step scan driver (parallel.dp.DataParallelTrainer.step_k,
Module.fit(steps_per_dispatch=K), gluon.trainer.fused_fit) must be
bit-compatible with K python-dispatched steps on the same batches — the
feature amortizes host dispatch, it must not change the math.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.parallel import data_parallel_mesh, DataParallelTrainer


def _mlp():
    data = mx.sym.Variable("data")
    f1 = mx.sym.FullyConnected(data, name="fc1", num_hidden=16)
    a1 = mx.sym.Activation(f1, act_type="relu")
    f2 = mx.sym.FullyConnected(a1, name="fc2", num_hidden=3)
    return mx.sym.SoftmaxOutput(f2, name="softmax")


def _batches(n, batch, seed=0):
    rng = np.random.RandomState(seed)
    centers = rng.uniform(-2, 2, size=(3, 8)).astype(np.float32)
    out = []
    for _ in range(n):
        y = rng.randint(0, 3, size=batch)
        x = centers[y] + rng.normal(0, 0.3, (batch, 8)).astype(np.float32)
        out.append((x.astype(np.float32), y.astype(np.float32)))
    return out


@pytest.mark.parametrize("ndev", [1, 8])
@pytest.mark.parametrize("optimizer,kw", [
    ("sgd", {"momentum": 0.9}), ("adam", {})])
def test_step_k_matches_sequential(ndev, optimizer, kw):
    """One step_k(K) dispatch == K step() dispatches from the same rng key:
    identical params, identical per-step losses."""
    sym = _mlp()
    batch, k = 32, 4
    batches = _batches(k, batch)
    import jax
    key = jax.random.PRNGKey(7)

    def make():
        mesh = data_parallel_mesh(ndev)
        t = DataParallelTrainer(sym, mesh, optimizer=optimizer,
                                learning_rate=0.05,
                                rescale_grad=1.0 / batch, **kw)
        return t, t.init_state({"data": (batch, 8),
                                "softmax_label": (batch,)})

    t1, (p1, s1, a1) = make()
    seq_losses = []
    for i, (x, y) in enumerate(batches):
        inputs = t1.shard_inputs([x, y])
        p1, s1, a1, loss, _ = t1.step(p1, s1, a1, inputs,
                                      rng=key if i == 0 else None)
        seq_losses.append(float(loss))

    t2, (p2, s2, a2) = make()
    xs = np.stack([b[0] for b in batches])
    ys = np.stack([b[1] for b in batches])
    stacked = t2.shard_inputs([xs, ys], stacked=True)
    p2, s2, a2, losses, outs = t2.step_k(p2, s2, a2, stacked, rng=key)
    assert outs == ()
    np.testing.assert_allclose(np.asarray(losses), seq_losses, rtol=1e-5)
    for a, b in zip(p1, p2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-6)
    # step counter advanced by K (adam bias correction depends on it)
    assert float(np.asarray(t2._t_dev)) == k


def test_step_k_outputs_all():
    """outputs_mode='all' stacks every step's symbol outputs on a leading
    K axis (what Module's fused fit feeds the training metric)."""
    sym = _mlp()
    batch, k = 16, 3
    mesh = data_parallel_mesh(8)
    t = DataParallelTrainer(sym, mesh, learning_rate=0.05,
                            rescale_grad=1.0 / batch)
    p, s, a = t.init_state({"data": (batch, 8), "softmax_label": (batch,)})
    batches = _batches(k, batch)
    stacked = t.shard_inputs([np.stack([b[0] for b in batches]),
                              np.stack([b[1] for b in batches])],
                             stacked=True)
    p, s, a, losses, outs = t.step_k(p, s, a, stacked, outputs_mode="all")
    assert losses.shape == (k,)
    assert len(outs) == 1 and outs[0].shape == (k, batch, 3)
    probs = np.asarray(outs[0])
    np.testing.assert_allclose(probs.sum(-1), np.ones((k, batch)),
                               rtol=1e-4)


def _digits_iter(batch=32, n=256):
    rng = np.random.RandomState(3)
    centers = rng.uniform(-2, 2, size=(3, 8)).astype(np.float32)
    y = rng.randint(0, 3, size=n)
    x = centers[y] + rng.normal(0, 0.3, (n, 8)).astype(np.float32)
    return mx.io.NDArrayIter(x, y.astype(np.float32), batch_size=batch,
                             label_name="softmax_label")


def test_module_fit_fused_matches_k1():
    """Module.fit(steps_per_dispatch=4) reaches the same params as the
    per-batch loop (same seed, same batches): the fused path changes
    dispatch granularity, not training math."""
    finals = []
    for k in (1, 4):
        mx.random.seed(0)
        np.random.seed(0)
        it = _digits_iter()
        mod = mx.mod.Module(_mlp(), context=mx.cpu(0))
        mod.fit(it, num_epoch=2, optimizer="sgd",
                optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
                initializer=mx.init.Xavier(), steps_per_dispatch=k)
        args, _ = mod.get_params()
        finals.append({n: a.asnumpy() for n, a in args.items()})
    assert set(finals[0]) == set(finals[1])
    for n in finals[0]:
        np.testing.assert_allclose(finals[0][n], finals[1][n], rtol=1e-3,
                                   atol=1e-5)


def test_module_fit_fused_metric_and_callbacks():
    """Per-K-block semantics: the train metric covers every sample, batch
    callbacks fire once per block with nbatch advanced by K."""
    it = _digits_iter(batch=32, n=224)   # 7 batches -> blocks of 4 + 3
    seen = []
    metric = mx.metric.Accuracy()
    mod = mx.mod.Module(_mlp(), context=mx.cpu(0))
    mod.fit(it, num_epoch=1, optimizer="sgd", eval_metric=metric,
            optimizer_params={"learning_rate": 0.1},
            initializer=mx.init.Xavier(),
            batch_end_callback=lambda p: seen.append(p.nbatch),
            steps_per_dispatch=4)
    assert seen == [3, 6]    # one per block, nbatch = consumed - 1
    # metric saw all 7 batches' samples
    assert metric.num_inst == 224
    assert mod.score(_digits_iter(), mx.metric.Accuracy())


@pytest.mark.parametrize("ndev", [1, 4])
def test_module_fit_fused_module_whole_at_every_moment(ndev):
    """The fused fit keeps the module's arrays on the host while the
    trainer's state trains: a callback that reads the module mid-epoch
    finds parameters (the draw, until an epoch wrote back), and an
    exception mid-epoch leaves the module bound, on its devices, with the
    executor it had: forward runs and retraces nothing of its own."""
    ctx = [mx.cpu(i) for i in range(ndev)]
    mod = mx.mod.Module(_mlp(), context=ctx if ndev > 1 else ctx[0])
    it = _digits_iter()
    mod.bind(it.provide_data, it.provide_label)
    mod.init_params(mx.init.Xavier())
    draw = {n: a.asnumpy() for n, a in mod.get_params()[0].items()}
    exe = mod._exec
    homes = {n: a._data.sharding for n, a in exe.arg_dict.items()}
    seen = []

    def interrupt(param):
        args, aux = mod.get_params()
        seen.append({n: a.asnumpy() for n, a in args.items()})
        raise KeyboardInterrupt

    with pytest.raises(KeyboardInterrupt):
        mod.fit(it, num_epoch=1, optimizer="sgd",
                optimizer_params={"learning_rate": 0.1},
                batch_end_callback=interrupt, steps_per_dispatch=4)
    assert mod.binded and mod._exec is exe
    for got in seen + [{n: a.asnumpy()
                        for n, a in mod.get_params()[0].items()}]:
        assert set(got) == set(draw)
        for n in draw:
            np.testing.assert_array_equal(got[n], draw[n])
    for n, a in exe.arg_dict.items():
        assert a._data.sharding == homes[n], n
    for n, g in exe.grad_dict.items():
        assert g is None or g._data.sharding == homes[n], n
    assert mod.score(_digits_iter(), mx.metric.Accuracy())
    # and a fit that runs to its end trains the same executor's arrays
    # (a forward over several devices has spread them over the mesh)
    homes = {n: a._data.sharding for n, a in exe.arg_dict.items()}
    it.reset()
    mod.fit(it, num_epoch=1, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1}, steps_per_dispatch=4)
    assert mod._exec is exe
    trained = {n: a.asnumpy() for n, a in mod.get_params()[0].items()}
    assert any(np.abs(trained[n] - draw[n]).max() > 1e-4 for n in draw)
    for n in draw:
        np.testing.assert_array_equal(exe.arg_dict[n].asnumpy(), trained[n])
        assert exe.arg_dict[n]._data.sharding == homes[n]


def test_module_fit_fused_fallback_warns():
    """An optimizer without a fused update op falls back to per-batch
    dispatch with a warning — and still trains."""
    it = _digits_iter(batch=32, n=64)
    mod = mx.mod.Module(_mlp(), context=mx.cpu(0))
    with _capture_warnings() as records:
        mod.fit(it, num_epoch=1, optimizer="adagrad",
                optimizer_params={"learning_rate": 0.1},
                initializer=mx.init.Xavier(), steps_per_dispatch=4)
    assert any("falling back to per-batch" in r for r in records), records
    assert mod.binded and mod.params_initialized


class _capture_warnings:
    """Capture logging warnings emitted through the module logger."""
    def __enter__(self):
        import logging

        class H(logging.Handler):
            def __init__(self):
                super().__init__()
                self.records = []

            def emit(self, record):
                self.records.append(record.getMessage())
        self._h = H()
        logging.getLogger().addHandler(self._h)
        return self._h.records

    def __exit__(self, *exc):
        import logging
        logging.getLogger().removeHandler(self._h)
        return False


def test_gluon_fused_fit_learns():
    """gluon fused_fit: trace net+loss, K-step scan, params written back."""
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn

    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu"))
        net.add(nn.Dense(3))
    net.initialize(mx.init.Xavier())
    loss = gluon.loss.SoftmaxCrossEntropyLoss()

    batches = _batches(12, 32, seed=5)
    data = [(mx.nd.array(x), mx.nd.array(y)) for x, y in batches]
    losses = gluon.trainer.fused_fit(
        net, loss, data, num_epoch=3, optimizer="sgd",
        optimizer_params={"learning_rate": 0.2, "momentum": 0.9},
        steps_per_dispatch=4)
    assert len(losses) == 3
    assert losses[-1] < losses[0] * 0.7, losses
    # written-back params serve eager inference
    x, y = batches[0]
    pred = net(mx.nd.array(x)).asnumpy().argmax(1)
    assert (pred == y).mean() > 0.8


def test_module_fit_fused_fallback_unknown_hyperparam():
    """Optimizer hyperparams the fused op schema can't take (e.g.
    begin_num_update) fall back to K=1 instead of raising, while
    multi_precision is HANDLED by the fused path (fp32 masters are
    always on there — mxnet_tpu.amp) and must not force a fallback."""
    it = _digits_iter(batch=32, n=64)
    mod = mx.mod.Module(_mlp(), context=mx.cpu(0))
    with _capture_warnings() as records:
        mod.fit(it, num_epoch=1, optimizer="sgd",
                optimizer_params={"learning_rate": 0.1,
                                  "begin_num_update": 0},
                initializer=mx.init.Xavier(), steps_per_dispatch=4)
    assert any("falling back to per-batch" in r for r in records), records

    it2 = _digits_iter(batch=32, n=64)
    mod2 = mx.mod.Module(_mlp(), context=mx.cpu(0))
    with _capture_warnings() as records2:
        mod2.fit(it2, num_epoch=1, optimizer="sgd",
                 optimizer_params={"learning_rate": 0.1,
                                   "multi_precision": True},
                 initializer=mx.init.Xavier(), steps_per_dispatch=4)
    assert not any("falling back to per-batch" in r for r in records2), \
        records2


def test_gluon_fused_fit_rejects_exhausted_generator():
    """A single-pass generator must fail loudly on epoch 1, not record
    0.0-loss epochs that trained nothing."""
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(3))
    net.initialize(mx.init.Xavier())
    loss = gluon.loss.SoftmaxCrossEntropyLoss()
    gen = ((mx.nd.array(x), mx.nd.array(y)) for x, y in _batches(4, 16))
    with pytest.raises(mx.MXNetError, match="no batches"):
        gluon.trainer.fused_fit(net, loss, gen, num_epoch=2,
                                steps_per_dispatch=2)


@pytest.mark.parametrize("front", ["module", "gluon"])
def test_fused_loop_view_contract_and_release(front, monkeypatch):
    """What a batch_end_callback finds in `param.locals` under
    Module.fit(steps_per_dispatch=K) is parallel.fused_loop's view: the
    names the benchmark's runners and chip_smoke index, with their shapes.
    And for either front end: once the fit has returned and a callback's
    own view is dropped, nothing holds the trainer's state."""
    import gc
    import weakref
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn
    k, batch = 2, 32
    last = []          # a weak reference to a parameter of the last state
    real_step_k = DataParallelTrainer.step_k

    def step_k(self, params, states, aux, inputs, **kwargs):
        out = real_step_k(self, params, states, aux, inputs, **kwargs)
        last[:] = [weakref.ref(out[0][0])]
        return out
    monkeypatch.setattr(DataParallelTrainer, "step_k", step_k)

    views = []
    if front == "module":
        mod = mx.mod.Module(_mlp(), context=mx.cpu(0))
        mod.fit(_digits_iter(batch=batch, n=4 * batch), num_epoch=1,
                optimizer="sgd", optimizer_params={"learning_rate": 0.1},
                initializer=mx.init.Xavier(), steps_per_dispatch=k,
                batch_end_callback=lambda p: views.append(p.locals))
        assert len(views) == 2
        view = views[-1]
        assert {"trainer", "params", "states", "aux", "inputs", "outputs",
                "losses", "epoch", "nbatch", "n_blk"} <= set(view)
        trainer = view["trainer"]
        assert isinstance(trainer, DataParallelTrainer)
        assert [tuple(x.shape) for x in view["inputs"]] == \
            [(k, batch, 8), (k, batch)]
        assert [tuple(o.shape) for o in view["outputs"]] == [(k, batch, 3)]
        assert tuple(view["losses"].shape) == (k,)
        assert (view["epoch"], view["nbatch"], view["n_blk"]) == (0, 4, k)
        assert len(view["params"]) == len(view["states"]) == \
            len(trainer.param_names)
        host = trainer.host_params(view["params"])
        trained = mod.get_params()[0]
        assert set(host) == set(trained)
        for n in host:       # the last view's state is what was written back
            np.testing.assert_array_equal(host[n], trained[n].asnumpy())
        assert view["params"][0] is last[0]()
        del trainer, host, trained
    else:
        net = nn.HybridSequential()
        with net.name_scope():
            net.add(nn.Dense(3))
        net.initialize(mx.init.Xavier())
        data = [(mx.nd.array(x), mx.nd.array(y))
                for x, y in _batches(4, batch)]
        gluon.trainer.fused_fit(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                                data, num_epoch=1, steps_per_dispatch=k)
    view = None
    views.clear()
    gc.collect()
    assert last and last[0]() is None
