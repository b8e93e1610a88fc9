"""mxnet_tpu.serving — dynamic-batching inference runtime (ISSUE 2).

Covers the four serving contracts on the CPU backend:
  - ServingEngine bucketed pad-and-slice correctness vs the raw
    Predictor (same XLA program, so results must match);
  - DynamicBatcher coalescing under concurrent clients, with results
    routed back to the right caller;
  - the overload protocol: deadline timeouts and queue-full shedding
    (driven through a fake engine for determinism);
  - ServingMetrics counters + the profiler counter-export hook.
"""
import json
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import profiler
from mxnet_tpu.contrib.export import export_model, serving_buckets
from mxnet_tpu.predictor import Predictor
from mxnet_tpu.serving import (DynamicBatcher, RequestTimeout,
                               ServingEngine, ServingMetrics,
                               ServingQueueFull)

BATCH = 8
SHAPE = (BATCH, 3, 16, 16)


def _convnet():
    data = mx.sym.Variable("data")
    net = mx.sym.Convolution(data, kernel=(3, 3), num_filter=8, name="c1")
    net = mx.sym.BatchNorm(net, name="bn1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(mx.sym.Flatten(net), num_hidden=10,
                                name="fc")
    return mx.sym.SoftmaxOutput(net, name="softmax")


@pytest.fixture(scope="module")
def artifact(tmp_path_factory):
    sym = _convnet()
    mod = mx.mod.Module(sym, context=mx.cpu(0))
    mod.bind(data_shapes=[("data", SHAPE)],
             label_shapes=[("softmax_label", (BATCH,))])
    mod.init_params(mx.init.Xavier())
    args, auxs = mod.get_params()
    path = str(tmp_path_factory.mktemp("serving") / "model.mxa")
    export_model(path, sym, args, auxs, {"data": SHAPE})
    return path


@pytest.fixture(scope="module")
def engine(artifact):
    return ServingEngine(artifact)


class FakeEngine:
    """Duck-typed engine for deterministic batcher scheduling tests:
    identity over the batch, optionally slow or gated on an event."""

    def __init__(self, max_batch=8, delay_s=0.0, gate=None):
        self.max_batch = max_batch
        self.input_names = ["data"]
        self.delay_s = delay_s
        self.gate = gate
        self.calls = 0

    def infer(self, x):
        self.calls += 1
        if self.gate is not None:
            self.gate.wait(timeout=10)
        if self.delay_s:
            time.sleep(self.delay_s)
        return [np.asarray(x)]


@pytest.mark.quick
def test_manifest_serving_metadata(artifact):
    pred = Predictor(artifact)
    meta = pred.manifest["serving"]
    assert meta == {"batch_axis": 0, "max_batch": BATCH,
                    "buckets": [1, 2, 4, 8], "amp_dtype": "float32",
                    "model": "model"}
    assert pred.export_batch == BATCH
    assert serving_buckets(6) == [1, 2, 4, 6]
    assert serving_buckets(1) == [1]


@pytest.mark.quick
def test_predictor_small_batch_pad_and_slice(artifact):
    """Satellite: request batches < export batch are zero-padded in and
    sliced out; real rows bitwise-match the full-batch run."""
    pred = Predictor(artifact)
    x = np.random.RandomState(0).uniform(0, 1, SHAPE).astype(np.float32)
    full = pred.forward(x)[0]
    for n in (1, 3, BATCH - 1):
        out = pred.forward(x[:n])
        assert out[0].shape == (n, 10)
        np.testing.assert_array_equal(out[0], full[:n])
    # larger than the export batch still refuses (fixed-shape contract)
    with pytest.raises(ValueError, match="exported shape"):
        pred.forward(np.zeros((BATCH + 1, 3, 16, 16), np.float32))
    # rank / trailing-dim mismatches are never padded
    with pytest.raises(ValueError, match="exported shape"):
        pred.forward(np.zeros((2, 3, 8, 16), np.float32))


@pytest.mark.quick
def test_engine_buckets_match_predictor(artifact, engine):
    pred = Predictor(artifact)
    x = np.random.RandomState(1).uniform(0, 1, SHAPE).astype(np.float32)
    full = pred.forward(x)[0]
    assert engine.buckets == [1, 2, 4, 8]
    assert engine.plan_compiles == 4          # warmup compiled every bucket
    for n in (1, 2, 3, 5, 8):
        out = engine.infer(x[:n])
        assert out[0].shape == (n, 10)
        np.testing.assert_allclose(out[0], full[:n], rtol=1e-5,
                                   atol=1e-6)
    assert engine.plan_compiles == 4          # cache hits only, no recompiles
    assert engine.bucket_for(3) == 4 and engine.bucket_for(8) == 8
    with pytest.raises(ValueError):
        engine.bucket_for(9)


@pytest.mark.quick
def test_batcher_concurrent_clients(engine):
    """8 concurrent single-row clients coalesce into fewer engine
    executions, and every client gets ITS row's output back."""
    x = np.random.RandomState(2).uniform(0, 1, SHAPE).astype(np.float32)
    full = engine.infer(x)[0]
    execs_before = engine.executions
    results = [None] * BATCH
    start = threading.Barrier(BATCH)

    with DynamicBatcher(engine, max_wait_us=20000,
                        queue_depth=32) as bat:
        def client(i):
            start.wait()
            results[i] = bat.infer(x[i:i + 1], timeout_ms=10000)[0]

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(BATCH)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        snap = bat.metrics.snapshot()
    got = np.concatenate(results, axis=0)
    np.testing.assert_allclose(got, full, rtol=1e-5, atol=1e-6)
    batches = engine.executions - execs_before
    assert batches < BATCH                    # coalescing happened
    assert snap["requests"] == BATCH
    assert snap["completed"] == BATCH
    assert snap["batches"] == batches
    assert snap["batched_rows"] == BATCH
    assert sum(int(k) * v for k, v in snap["batch_hist"].items()) == BATCH
    assert snap["shed"] == 0 and snap["timeouts"] == 0
    assert snap["p50_ms"] is not None and snap["p99_ms"] >= snap["p50_ms"]


@pytest.mark.quick
def test_batcher_multirow_requests(engine):
    """Requests carrying several rows coalesce too; a request that
    doesn't fit the current batch waits for the next one."""
    x = np.random.RandomState(3).uniform(0, 1, SHAPE).astype(np.float32)
    full = engine.infer(x)[0]
    with DynamicBatcher(engine, max_wait_us=20000) as bat:
        f1 = bat.submit(x[:3])
        f2 = bat.submit(x[3:6])
        f3 = bat.submit(x[6:8])
        np.testing.assert_allclose(f1.result(10)[0], full[:3],
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(f2.result(10)[0], full[3:6],
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(f3.result(10)[0], full[6:8],
                                   rtol=1e-5, atol=1e-6)
        with pytest.raises(ValueError):
            bat.submit(np.zeros((9, 3, 16, 16), np.float32))


@pytest.mark.quick
def test_batcher_deadline_timeout():
    """A request whose deadline expires while the worker is busy fails
    with RequestTimeout and never reaches the engine."""
    eng = FakeEngine(delay_s=0.25)
    with DynamicBatcher(eng, max_wait_us=0, queue_depth=8) as bat:
        slow = bat.submit(np.zeros((1, 4), np.float32))   # occupies worker
        time.sleep(0.05)                                  # worker now busy
        doomed = bat.submit(np.zeros((1, 4), np.float32), timeout_ms=50)
        assert slow.result(5)[0].shape == (1, 4)
        with pytest.raises(RequestTimeout):
            doomed.result(5)
        snap = bat.metrics.snapshot()
    assert snap["timeouts"] == 1
    assert snap["completed"] == 1
    assert eng.calls == 1                     # the doomed one never ran


@pytest.mark.quick
def test_batcher_queue_full_sheds():
    """Bounded queue: submits past queue_depth raise ServingQueueFull
    (load shedding) and are counted; accepted requests still complete."""
    gate = threading.Event()
    eng = FakeEngine(gate=gate)
    depth = 4
    with DynamicBatcher(eng, max_wait_us=0, queue_depth=depth,
                        max_batch=1) as bat:
        first = bat.submit(np.zeros((1, 4), np.float32))  # worker blocks
        time.sleep(0.05)
        futures = [bat.submit(np.zeros((1, 4), np.float32))
                   for _ in range(depth)]                  # fills the queue
        with pytest.raises(ServingQueueFull):
            bat.submit(np.zeros((1, 4), np.float32))
        snap_mid = bat.metrics.snapshot()
        assert snap_mid["shed"] == 1
        assert snap_mid["queue_depth"] == depth
        gate.set()                                         # drain
        assert first.result(5)[0].shape == (1, 4)
        for f in futures:
            assert f.result(5)[0].shape == (1, 4)
        snap = bat.metrics.snapshot()
    assert snap["completed"] == depth + 1
    assert snap["requests"] == depth + 1      # shed submits aren't accepted


@pytest.mark.quick
def test_metrics_profiler_export_hook():
    """Every ServingMetrics is reachable through the profiler's counter
    export: mx.profiler.export_counters() carries the live snapshot."""
    m = ServingMetrics(name="serving-test")
    try:
        m.record_submit()
        m.record_batch(4)
        m.record_done(0.002)
        exported = profiler.export_counters()
        assert m.name in exported
        assert exported[m.name]["requests"] == 1
        assert exported[m.name]["batch_hist"] == {"4": 1}
        as_json = json.loads(profiler.export_counters(format="json"))
        assert as_json[m.name]["completed"] == 1
    finally:
        m.close()
    assert m.name not in profiler.export_counters()


def test_selftest_speedup_and_paths(artifact):
    """Acceptance: the closed-loop selftest at concurrency 8 serves every
    request, none shed or timed out, and coalesces requests into batches.
    Counts only: the ratio to the sequential Predictor loop is a CPU's,
    shared with the other test workers, and is not asserted."""
    from mxnet_tpu.serving.__main__ import selftest
    res = selftest(artifact, requests=96, concurrency=8,
                   max_wait_us=2000, min_speedup=0)
    assert res["ok"], res
    assert res["shed"] == 0 and res["timeouts"] == 0
    assert sum(int(k) * v for k, v in res["batch_hist"].items()) == 96
    assert any(int(k) > 1 for k in res["batch_hist"])
