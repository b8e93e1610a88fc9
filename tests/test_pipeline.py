"""mxnet_tpu.pipeline — async device-feed prefetcher (ISSUE 3).

Contracts under test on the CPU backend (8 virtual devices, conftest):
  - DeviceFeed preserves order/values, re-raises feeder exceptions in
    the consumer thread, and close() never leaks the feeder thread;
  - training results are BIT-identical with the feed on vs off, for
    both Module.fit and gluon fused_fit (the feed only moves device_put
    to another thread — same math, same RNG stream);
  - module_stage commits batches to the executor's sharding under a
    multi-device mesh, so forward's own device_put is a no-op;
  - the aggregate counters ride profiler.export_counters();
  - config.enable_compile_cache wires JAX's persistent cache so
    compiled programs land on disk and survive jax.clear_caches().
"""
import os
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import pipeline as pl
from mxnet_tpu.pipeline import DeviceFeed, module_stage


def _mlp_sym(num_classes=4):
    data = mx.sym.Variable("data")
    fc1 = mx.sym.FullyConnected(data, name="fc1", num_hidden=16)
    act1 = mx.sym.Activation(fc1, act_type="relu")
    fc2 = mx.sym.FullyConnected(act1, name="fc2", num_hidden=num_classes)
    return mx.sym.SoftmaxOutput(fc2, name="softmax")


def _blob_data(n=160, dim=8, classes=4, seed=0):
    rng = np.random.RandomState(seed)
    centers = rng.uniform(-3, 3, size=(classes, dim))
    y = rng.randint(0, classes, size=n)
    x = centers[y] + rng.normal(0, 0.4, size=(n, dim))
    return x.astype(np.float32), y.astype(np.float32)


# -- DeviceFeed core ---------------------------------------------------------

def test_feed_order_values_and_shutdown():
    items = [np.full((4,), i, np.float32) for i in range(20)]
    feed = DeviceFeed(iter(items), stage=lambda a: a * 2)
    out = list(feed)
    assert len(out) == 20
    for i, a in enumerate(out):
        np.testing.assert_array_equal(a, np.full((4,), 2 * i, np.float32))
    feed.close()
    assert not feed._thread.is_alive()


def test_feed_exception_propagates_to_consumer():
    def source():
        yield 1
        yield 2
        raise RuntimeError("decode failed")

    feed = DeviceFeed(source(), stage=lambda x: x)
    assert next(feed) == 1
    assert next(feed) == 2
    with pytest.raises(RuntimeError, match="decode failed"):
        next(feed)
    # the error path closes the feed: thread joined, iteration over
    assert not feed._thread.is_alive()
    with pytest.raises(StopIteration):
        next(feed)


def test_feed_stage_exception_propagates():
    def bad_stage(x):
        if x == 3:
            raise ValueError("bad batch 3")
        return x

    feed = DeviceFeed(iter(range(6)), stage=bad_stage)
    assert list(itertools_take(feed, 3)) == [0, 1, 2]
    with pytest.raises(ValueError, match="bad batch 3"):
        next(feed)
    assert not feed._thread.is_alive()


def itertools_take(it, n):
    out = []
    for _ in range(n):
        out.append(next(it))
    return out


def test_close_midstream_no_leaked_threads():
    """Abandoning a feed mid-epoch (early stop) must not leak the feeder
    even when it is blocked in put() on a full ring."""
    def slow_source():
        for i in range(1000):
            yield i

    before = threading.active_count()
    with DeviceFeed(slow_source(), stage=lambda x: x, depth=2) as feed:
        assert next(feed) == 0
        thread = feed._thread
    # context exit closed it; feeder must wake from the full queue and die
    thread.join(timeout=5)
    assert not thread.is_alive()
    deadline = time.time() + 5
    while threading.active_count() > before and time.time() < deadline:
        time.sleep(0.01)
    assert threading.active_count() <= before


def test_close_is_idempotent():
    feed = DeviceFeed(iter(range(3)), stage=lambda x: x)
    list(feed)
    feed.close()
    feed.close()
    assert not feed._thread.is_alive()


def test_feed_or_inline_off_is_plain_map(monkeypatch):
    monkeypatch.setenv("MXNET_DEVICE_FEED", "0")
    src = iter([1, 2, 3])
    feed = pl.feed_or_inline(src, lambda x: x + 1)
    assert not isinstance(feed, DeviceFeed)
    assert list(feed) == [2, 3, 4]
    pl.close_feed(feed)     # no-op, must not raise


# -- bit-identity: feed on == feed off ---------------------------------------

def _fit_params(feed_flag):
    os.environ["MXNET_DEVICE_FEED"] = feed_flag
    try:
        mx.random.seed(7)
        np.random.seed(7)
        X, Y = _blob_data()
        it = mx.io.NDArrayIter(X, Y, batch_size=40, shuffle=False)
        mod = mx.mod.Module(_mlp_sym(), context=mx.cpu(0))
        mod.fit(it, num_epoch=3, optimizer="sgd",
                optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
                initializer=mx.init.Xavier())
        args, _ = mod.get_params()
        return {n: a.asnumpy() for n, a in args.items()}
    finally:
        os.environ.pop("MXNET_DEVICE_FEED", None)


def test_module_fit_bit_identical_with_feed():
    """The acceptance contract: Module.fit params with the device feed
    are bit-identical to the synchronous path — not allclose, equal."""
    on = _fit_params("1")
    off = _fit_params("0")
    assert set(on) == set(off)
    for n in on:
        np.testing.assert_array_equal(on[n], off[n], err_msg=n)


def _fused_fit_params(feed_flag):
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn
    os.environ["MXNET_DEVICE_FEED"] = feed_flag
    try:
        mx.random.seed(11)
        np.random.seed(11)
        X, Y = _blob_data(n=128)
        data = [(mx.nd.array(X[i:i + 32]), mx.nd.array(Y[i:i + 32]))
                for i in range(0, 128, 32)]
        net = nn.HybridSequential(prefix="bitid_")
        with net.name_scope():
            net.add(nn.Dense(16, activation="relu"))
            net.add(nn.Dense(4))
        net.initialize(mx.init.Xavier())
        loss = gluon.loss.SoftmaxCrossEntropyLoss()
        gluon.trainer.fused_fit(
            net, loss, data, num_epoch=2, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
            steps_per_dispatch=2)
        return {n: p.data().asnumpy()
                for n, p in net.collect_params().items()}
    finally:
        os.environ.pop("MXNET_DEVICE_FEED", None)


def test_gluon_fused_fit_bit_identical_with_feed():
    on = _fused_fit_params("1")
    off = _fused_fit_params("0")
    assert set(on) == set(off)
    for n in on:
        np.testing.assert_array_equal(on[n], off[n], err_msg=n)


# -- sharded staging under a multi-device mesh -------------------------------

def test_module_stage_commits_to_executor_sharding():
    """Under a 2-context mesh, the staged data array must already carry
    the executor's batch sharding (so forward's device_put no-ops), and
    fit must still converge to the same params as the sync path."""
    import jax
    sym = _mlp_sym()
    mod = mx.mod.Module(sym, context=[mx.cpu(0), mx.cpu(1)])
    mod.bind(data_shapes=[("data", (8, 8))],
             label_shapes=[("softmax_label", (8,))])
    mod.init_params(mx.init.Xavier())
    stage = module_stage(mod)
    batch = mx.io.DataBatch(data=[mx.nd.array(np.ones((8, 8), np.float32))],
                            label=[mx.nd.zeros((8,))])
    staged = stage(batch)
    arr = staged.data[0]._data
    assert isinstance(arr, jax.Array)
    ex = mod._exec
    assert arr.sharding.is_equivalent_to(ex._arg_sharding("data"), arr.ndim)
    # staged batch runs through forward unchanged
    mod.forward(staged, is_train=False)
    out = mod.get_outputs()[0].asnumpy()
    assert out.shape == (8, 4)
    np.testing.assert_allclose(out.sum(axis=1), 1.0, rtol=1e-5)


def test_module_stage_passes_indivisible_batch_through():
    """A batch whose leading axis doesn't divide the mesh must NOT be
    staged on the feeder (forward owns the divisibility error)."""
    sym = _mlp_sym()
    mod = mx.mod.Module(sym, context=[mx.cpu(0), mx.cpu(1)])
    mod.bind(data_shapes=[("data", (8, 8))],
             label_shapes=[("softmax_label", (8,))])
    mod.init_params(mx.init.Xavier())
    stage = module_stage(mod)
    odd = mx.io.DataBatch(data=[mx.nd.array(np.ones((7, 8), np.float32))],
                          label=[mx.nd.zeros((7,))])
    staged = stage(odd)     # must not raise on the "feeder" side
    assert staged.data[0] is odd.data[0]


# -- counters + profiler export ----------------------------------------------

def test_counters_ride_profiler_export():
    from mxnet_tpu import profiler
    pl.reset_stats()
    feed = DeviceFeed(iter(range(5)), stage=lambda x: x)
    list(feed)
    feed.close()
    counters = profiler.export_counters()
    assert "device_feed" in counters
    snap = counters["device_feed"]
    assert snap["feed_batches"] >= 5
    assert snap["feeds_opened"] >= 1
    assert snap["feeds_closed"] >= 1
    assert "overlap_frac" in snap and "feed_wait_us" in snap


def test_overlap_frac_bounds():
    pl.reset_stats()
    def source():
        for i in range(8):
            time.sleep(0.002)
            yield i
    feed = DeviceFeed(source(), stage=lambda x: x)
    for _ in feed:
        time.sleep(0.002)
    feed.close()
    s = pl.stats()
    assert 0.0 <= s["overlap_frac"] <= 1.0
    assert s["feed_stage_us"] > 0


# -- persistent compile cache ------------------------------------------------

def _detach_compile_cache():
    """Leave the process as the test found it: later tests must not write
    cache entries into a tmp_path that pytest is about to delete."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_compilation_cache_dir", None)
    compilation_cache.reset_cache()


def test_enable_compile_cache_writes_entries(tmp_path, monkeypatch):
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.config import enable_compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    cache_dir = str(tmp_path / "xla_cache")
    assert enable_compile_cache(cache_dir) == cache_dir
    try:
        @jax.jit
        def fn(x):
            return jnp.tanh(x) @ x.T
        np.asarray(fn(np.ones((32, 32), np.float32)))
        entries = os.listdir(cache_dir)
        assert entries, "no cache entries written"
        # warm path: in-process executables dropped, disk cache survives
        jax.clear_caches()
        np.asarray(fn(np.ones((32, 32), np.float32)))
        assert len(os.listdir(cache_dir)) >= len(entries)
    finally:
        _detach_compile_cache()


def test_compile_cache_placed_from_outside_is_not_moved(tmp_path,
                                                        monkeypatch):
    """Where JAX_COMPILATION_CACHE_DIR is set, JAX's own handling of it
    is the only thing that places the cache: enable_compile_cache sets
    no directory in code and reports the one in use."""
    import jax
    from mxnet_tpu.config import enable_compile_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/from/outside")
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache(str(tmp_path / "ours")) == before
    assert jax.config.jax_compilation_cache_dir == before
    assert not (tmp_path / "ours").exists()
