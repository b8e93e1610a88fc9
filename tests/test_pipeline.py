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
  - BlockStager (the fused drivers' stack-and-commit step) gives the
    bytes np.stack gave, out of host buffers it reuses, and never
    refills one that a transfer may still read or that the staged
    array aliases;
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


# -- BlockStager: the (K, batch, ...) block out of reused host buffers --------

class _Late:
    """What a `put` that copies late returns: it reads its host array
    only when somebody waits for it (`jax.block_until_ready`) or fetches
    it, as a transfer in flight would at the last moment it may."""
    addressable_shards = ()     # no host-backed shard: nothing to alias

    def __init__(self, host, n, log):
        self.host, self.n, self.log, self.value = host, n, log, None

    def _land(self):
        if self.value is None:
            self.value, self.host = self.host.copy(), None

    def block_until_ready(self):
        self.log.append(("ready", self.n))
        self._land()
        return self

    def fetch(self):
        self._land()
        return self.value


def _late_put(log):
    count = iter(range(10**6))

    def put(arrays, **kwargs):
        n = next(count)
        log.append(("put", n))
        return tuple(_Late(a, n, log) for a in arrays)
    return put


def _true_blocks(shapes, k=4, dtype=np.float32, label_dtype=np.float32):
    """Per block an (x, y) pair of (rows, ...) arrays; `shapes` gives
    (rows, batch, dim) per block."""
    rng = np.random.RandomState(25)
    return [(rng.normal(size=(rows, batch, dim)).astype(dtype),
             rng.randint(0, 9, size=(rows, batch)).astype(label_dtype))
            for rows, batch, dim in shapes]


def _reusing_source(blocks):
    """Hands out every block as rows of ONE scratch array per column
    (the legacy iterators' habit) and overwrites it at the next pull —
    and once more after the last."""
    def scratch_of(arrays):
        size = max(a.nbytes for a in arrays)
        return np.empty(size, np.uint8)
    sx = scratch_of([b[0] for b in blocks])
    sy = scratch_of([b[1] for b in blocks])
    for x, y in blocks:
        sx.fill(0xFF)
        sy.fill(0xFF)
        vx = sx[:x.nbytes].view(x.dtype).reshape(x.shape)
        vy = sy[:y.nbytes].view(y.dtype).reshape(y.shape)
        vx[...], vy[...] = x, y
        yield list(vx), list(vy)
    sx.fill(0xFF)
    sy.fill(0xFF)


SIX_AND_A_TAIL = [(4, 8, 6)] * 6 + [(2, 8, 6)]


def _assert_blocks_equal(staged, blocks, fetch):
    assert len(staged) == len(blocks)
    for n, ((gx, gy), (x, y)) in enumerate(zip(staged, blocks)):
        gx, gy = fetch(gx), fetch(gy)
        assert gx.dtype == x.dtype and gy.dtype == y.dtype
        np.testing.assert_array_equal(gx, x, err_msg=f"block {n} data")
        np.testing.assert_array_equal(gy, y, err_msg=f"block {n} label")


@pytest.mark.parametrize("through", ["feed", "inline"])
def test_block_stager_reusing_source_late_put(through, monkeypatch):
    """(a) six blocks and a short tail from a source that overwrites its
    one array after every pull, through a put that copies late, fetched
    only once everything was staged: np.stack of the true batches."""
    monkeypatch.setenv("MXNET_DEVICE_FEED", "1" if through == "feed" else "0")
    blocks = _true_blocks(SIX_AND_A_TAIL)
    stager = pl.BlockStager(_late_put([]))
    feed = pl.feed_or_inline(_reusing_source(blocks),
                             lambda cols: stager(cols, stacked=True))
    assert isinstance(feed, DeviceFeed) == (through == "feed")
    staged = list(feed)
    pl.close_feed(feed)
    _assert_blocks_equal(staged, blocks, _Late.fetch)


@pytest.mark.parametrize("n_blocks", [3, 7])
def test_block_stager_waits_before_it_refills(n_blocks):
    """(b) what was staged from a buffer is waited for before the buffer
    is written again, and only then: block n's put comes after the wait
    for block n-2 and with block n-1 still unwaited."""
    log = []
    blocks = _true_blocks([(4, 8, 6)] * n_blocks)
    stager = pl.BlockStager(_late_put(log))
    staged = [stager(cols) for cols in _reusing_source(blocks)]
    want = []
    for n in range(n_blocks):
        if n >= 2:
            want += [("ready", n - 2)] * 2      # two columns, two arrays
        want.append(("put", n))
    assert log == want
    _assert_blocks_equal(staged, blocks, _Late.fetch)


@pytest.mark.parametrize("shapes,allocs", [
    # another batch shape mid-stream (reshape, bucketing), and back
    ([(4, 8, 6)] * 3 + [(4, 16, 6)] * 3 + [(4, 8, 6)] * 2, 6),
    # a short first block: the full one after it needs a larger buffer
    ([(2, 8, 6)] + [(4, 8, 6)] * 4 + [(1, 8, 6)], 3),
    # fewer rows never need a new one
    ([(4, 8, 6), (3, 8, 6), (2, 8, 6), (1, 8, 6), (4, 8, 6)], 2),
], ids=["reshape", "short_first", "shrinking"])
def test_block_stager_change_of_shape(shapes, allocs):
    """(c) the block's shape decides: same shape and no more rows reuse,
    anything else gets a new buffer."""
    pl.reset_stats()
    blocks = _true_blocks(shapes)
    stager = pl.BlockStager(_late_put([]))
    staged = [stager(cols) for cols in _reusing_source(blocks)]
    _assert_blocks_equal(staged, blocks, _Late.fetch)
    s = pl.stats()
    assert s["feed_stack_allocs"] == 2 * allocs         # two columns
    assert s["feed_stack_reuses"] == 2 * (len(shapes) - allocs)


def test_block_stager_change_of_dtype():
    """(c) a column whose dtype changes gets new buffers; the other
    column keeps its own."""
    pl.reset_stats()
    blocks = _true_blocks([(4, 8, 6)] * 3) + \
        _true_blocks([(4, 8, 6)] * 3, dtype=np.float64)
    stager = pl.BlockStager(_late_put([]))
    staged = [stager(cols) for cols in _reusing_source(blocks)]
    _assert_blocks_equal(staged, blocks, _Late.fetch)
    s = pl.stats()
    assert (s["feed_stack_allocs"], s["feed_stack_reuses"]) == (6, 6)


def test_block_stager_mixed_dtypes_promote_like_np_stack():
    cols = [[np.ones((2, 3), np.float32), np.ones((2, 3), np.float64)]]
    (got,), = [pl.BlockStager(lambda arrays: tuple(arrays))(cols)]
    want = np.stack(cols[0])
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(got, want)
    with pytest.raises(ValueError):
        pl.BlockStager(lambda arrays: tuple(arrays))(
            [[np.ones((2, 3)), np.ones((3, 2))]])


@pytest.mark.parametrize("n_columns,n_blocks", [(1, 5), (2, 9), (3, 4)])
def test_block_stager_stats(n_columns, n_blocks):
    """(d) two buffers a column however many blocks; the rest reuse;
    the guard's cost and the bytes are counted and exported."""
    from mxnet_tpu import profiler
    pl.reset_stats()
    rng = np.random.RandomState(3)
    stager = pl.BlockStager(_late_put([]))
    nbytes = 0
    for _ in range(n_blocks):
        cols = [[rng.normal(size=(8, 5)).astype(np.float32)
                 for _ in range(4)] for _ in range(n_columns)]
        nbytes += sum(a.nbytes for col in cols for a in col)
        stager(cols)
    s = profiler.export_counters()["device_feed"]
    assert s["feed_stack_allocs"] == 2 * n_columns
    assert s["feed_stack_reuses"] == (n_blocks - 2) * n_columns
    assert s["feed_staged_bytes"] == nbytes
    assert isinstance(s["feed_reuse_wait_us"], int)
    assert s["feed_reuse_wait_us"] >= 0


def _aligned_like(a, align=64):
    raw = np.empty(a.nbytes + align, np.uint8)
    off = (-raw.ctypes.data) % align
    out = raw[off:off + a.nbytes].view(a.dtype).reshape(a.shape)
    out[...] = a
    return out


class _FakeShard:
    """A shard that says where it lives, as jax's do."""
    def __init__(self, platform, pointer):
        self.device = type("D", (), {"platform": platform})()
        self.data = type("A", (), {
            "unsafe_buffer_pointer": staticmethod(lambda: pointer)})()


def _alias_case(name):
    import jax
    buf = _aligned_like(np.arange(4096, dtype=np.float32).reshape(4, 1024))
    if name == "numpy_view":
        return buf[1:3], buf, True
    if name == "numpy_copy":
        return buf.copy(), buf, False
    if name == "cpu_zero_copy":
        # a 64-byte aligned numpy buffer becomes the CPU array's memory
        arr = jax.block_until_ready(jax.device_put(buf, jax.devices()[0]))
        assert arr.addressable_shards[0].data.unsafe_buffer_pointer() \
            == buf.ctypes.data, "this JAX copies: drop the case"
        return arr, buf, True
    if name == "cpu_array_of_its_own":
        return jax.numpy.zeros((4, 1024)) + 1, buf, False
    inside = buf.ctypes.data + 128
    if name == "cpu_pointer_inside":
        arr = type("Arr", (), {
            "addressable_shards": [_FakeShard("cpu", inside)]})()
        return arr, buf, True
    if name == "tpu_pointer_is_device_memory":
        arr = type("Arr", (), {
            "addressable_shards": [_FakeShard("tpu", inside)]})()
        return arr, buf, False
    assert name == "unknown_object"
    return object(), buf, True


@pytest.mark.parametrize("name", [
    "numpy_view", "numpy_copy", "cpu_zero_copy", "cpu_array_of_its_own",
    "cpu_pointer_inside", "tpu_pointer_is_device_memory", "unknown_object"])
def test_may_alias_reads_where_the_staged_array_lives(name):
    staged, buf, want = _alias_case(name)
    assert pl._may_alias((staged,), buf) is want


@pytest.mark.parametrize("put_name", ["identity", "cpu_device_put",
                                      "cpu_device_put_aligned"])
def test_block_stager_never_reuses_an_aliased_buffer(put_name, monkeypatch):
    """(e) where what `put` returns may share memory with the stager's
    buffer, the buffer is not written again: every block, read only at
    the end, still holds its own bytes."""
    import jax
    pl.reset_stats()
    if put_name == "identity":
        put, fetch = (lambda arrays: tuple(arrays)), np.asarray
    else:
        dev = jax.devices()[0]
        put = lambda arrays: tuple(jax.device_put(a, dev) for a in arrays)
        fetch = np.asarray
        if put_name.endswith("aligned"):
            real = np.empty
            monkeypatch.setattr(
                pl, "_new_buffer",
                lambda shape, dtype: _aligned_like(real(shape, dtype)))
    # 2 MB a block: large enough for the CPU backend to copy it late
    blocks = _true_blocks([(4, 64, 2048)] * 6 + [(2, 64, 2048)])
    stager = pl.BlockStager(put)
    staged = [stager(cols) for cols in _reusing_source(blocks)]
    _assert_blocks_equal(staged, blocks, fetch)
    s = pl.stats()
    assert s["feed_stack_allocs"] + s["feed_stack_reuses"] == 2 * len(blocks)
    if put_name != "cpu_device_put":    # there numpy's alignment decides
        assert s["feed_stack_reuses"] == 0


# -- the fused drivers through BlockStager == through the old np.stack --------

class _OldStager:
    """The body both `_stage_block`s had before BlockStager, kept as the
    reference: a fresh np.stack per column and block."""

    def __init__(self, put):
        self._put = put

    def __call__(self, columns, **kwargs):
        from mxnet_tpu.telemetry import tracing
        with tracing.span("feed.stack"):
            stacked = [np.stack(col) for col in columns]
        return pl.staged_put(self._put, stacked, **kwargs)


def _module_fused_run():
    mx.random.seed(7)
    np.random.seed(7)
    X, Y = _blob_data(n=360)
    it = mx.io.NDArrayIter(X, Y, batch_size=40, shuffle=False)   # 9 batches
    mod = mx.mod.Module(_mlp_sym(), context=mx.cpu(0))
    losses = []

    def on_dispatch(param):
        losses.append(param.eval_metric.get()[1])
    mod.fit(it, num_epoch=3, optimizer="sgd", eval_metric="ce",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
            initializer=mx.init.Xavier(), steps_per_dispatch=4,
            batch_end_callback=on_dispatch)
    args, _ = mod.get_params()
    assert len(losses) == 9       # 4 + 4 + a tail of 1, three epochs
    return losses, {n: a.asnumpy() for n, a in args.items()}


def _gluon_fused_run():
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn
    mx.random.seed(11)
    np.random.seed(11)
    X, Y = _blob_data(n=160)
    data = [(mx.nd.array(X[i:i + 32]), mx.nd.array(Y[i:i + 32]))
            for i in range(0, 160, 32)]                           # 5 batches
    net = nn.HybridSequential(prefix="stager_")
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu"))
        net.add(nn.Dense(4))
    net.initialize(mx.init.Xavier())
    losses = gluon.trainer.fused_fit(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), data, num_epoch=3,
        optimizer="sgd",
        optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
        steps_per_dispatch=2)
    return losses, {n: p.data().asnumpy()
                    for n, p in net.collect_params().items()}


@pytest.mark.parametrize("feed_flag", ["1", "0"])
@pytest.mark.parametrize("run", [_module_fused_run, _gluon_fused_run],
                         ids=["module_fit", "gluon_fused_fit"])
def test_fused_fit_bit_identical_to_old_stage_block(run, feed_flag,
                                                    monkeypatch):
    """Losses and parameters through BlockStager equal, bit for bit,
    those through the old stacking code, feed on and off."""
    monkeypatch.setenv("MXNET_DEVICE_FEED", feed_flag)
    pl.reset_stats()
    new_losses, new_params = run()
    engaged = pl.stats()
    assert engaged["feed_stack_allocs"] + engaged["feed_stack_reuses"] > 0
    monkeypatch.setattr(pl, "BlockStager", _OldStager)
    old_losses, old_params = run()
    assert new_losses == old_losses
    assert set(new_params) == set(old_params)
    for n in new_params:
        np.testing.assert_array_equal(new_params[n], old_params[n],
                                      err_msg=n)


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


def test_compile_cache_placed_here_has_no_size_limit(tmp_path, monkeypatch):
    """A limit that the machine sets for the cache IT placed
    (JAX_COMPILATION_CACHE_MAX_SIZE) does not evict from a cache that
    `enable_compile_cache` places: a second large entry would push out
    the first, and the next process would compile everything again."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.config import enable_compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = jax.config.jax_compilation_cache_max_size
    jax.config.update("jax_compilation_cache_max_size", 1)   # one byte
    cache_dir = str(tmp_path / "xla_cache")
    try:
        enable_compile_cache(cache_dir)
        for n in (8, 16):
            np.asarray(jax.jit(lambda x: jnp.tanh(x) @ x.T)(
                np.ones((n, n), np.float32)))
        kept = [e for e in os.listdir(cache_dir) if e.endswith("-cache")]
        assert len(kept) >= 2, kept
    finally:
        jax.config.update("jax_compilation_cache_max_size", was)
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
