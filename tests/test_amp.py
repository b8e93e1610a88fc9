"""mxnet_tpu.amp — framework-wide mixed precision (ISSUE 4).

Covers the five amp contracts on the CPU mesh:
  - MXNET_AMP=0 / amp.init("float32") is a bit-identical no-op;
  - bf16 autocast training converges with fp32 master weights
    (convergence is measured as HOST cross-entropy from the output
    probabilities: SoftmaxOutput's forward output is the softmax, whose
    sum is the batch size — its custom vjp supplies the CE gradient);
  - fp16 + DynamicLossScaler skips the step on non-finite grads (params
    bit-unchanged), halves the scale, and keeps training after;
  - the scaler state rides the fused k>1 scan carry (step_k);
  - the gradient all-reduce is half-width ON THE WIRE: asserted from
    the post-SPMD-partitioning HLO in a fresh subprocess, because the
    dump flags are read once at backend init and XLA:CPU's later
    float-normalization pass re-widens bf16 collectives in the FINAL
    optimized HLO (backend legalization, not a program property);
  - bf16 export/serving round-trip: fp32 request/response I/O with the
    compute casts baked into the artifact, amp_dtype in the manifest.
"""
import json
import logging
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import amp
from mxnet_tpu.amp import DynamicLossScaler


@pytest.fixture(autouse=True)
def _amp_reset():
    yield
    amp._reset_for_tests()


def _mlp_sym():
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=16,
                                name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _trainer(dtype, n_dev=2, **kw):
    import jax
    from mxnet_tpu.parallel import DataParallelTrainer, data_parallel_mesh
    mesh = data_parallel_mesh(n_dev, jax.devices()[:n_dev])
    if dtype == "float16" and "loss_scaler" not in kw:
        # the default 2^15 init scale genuinely overflows this tiny
        # MLP's batch-summed fp16 grads on step one (a correct backoff,
        # but it offsets the exact skip counts asserted below) — pin a
        # scale that only the injected-inf batches can trip
        kw["loss_scaler"] = DynamicLossScaler(init_scale=1024.0)
    return DataParallelTrainer(_mlp_sym(), mesh, optimizer="sgd",
                               learning_rate=0.1, momentum=0.9,
                               dtype=dtype, rescale_grad=1.0 / 16, **kw)


def _data():
    rng = np.random.RandomState(0)
    x = rng.normal(size=(16, 8)).astype(np.float32)
    y = rng.randint(0, 4, size=(16,)).astype(np.float32)
    return x, y


def _host_ce(outs, y):
    p = np.asarray(outs[0], np.float32)
    return float(-np.log(p[np.arange(len(y)), y.astype(int)] + 1e-8).mean())


def test_amp_init_float32_is_bit_identical_noop():
    x, y = _data()

    def _forward():
        mod = mx.mod.Module(_mlp_sym(), context=mx.cpu(0))
        mod.bind(data_shapes=[("data", (16, 8))],
                 label_shapes=[("softmax_label", (16,))])
        mod.init_params(mx.init.Xavier(rnd_type="gaussian"))
        return mod

    base_mod = _forward()
    base_mod.forward(mx.io.DataBatch(data=[mx.nd.array(x)],
                                     label=[mx.nd.array(y)]),
                     is_train=False)
    base = base_mod.get_outputs()[0].asnumpy()

    amp.init("float32")              # the MXNET_AMP=0 contract: identity
    assert not amp.is_enabled()
    mod2 = _forward()
    arg_p, aux_p = base_mod.get_params()
    mod2.set_params(arg_p, aux_p)
    mod2.forward(mx.io.DataBatch(data=[mx.nd.array(x)],
                                 label=[mx.nd.array(y)]), is_train=False)
    assert (mod2.get_outputs()[0].asnumpy() == base).all()


def test_amp_bf16_mlp_converges_with_f32_masters():
    x, y = _data()
    tr = _trainer("bfloat16")
    params, states, aux = tr.init_state({"data": (16, 8),
                                         "softmax_label": (16,)})
    inputs = tr.shard_inputs([x, y])
    ces = []
    for _ in range(30):
        params, states, aux, _, outs = tr.step(params, states, aux, inputs)
        ces.append(_host_ce(outs, y))
    assert ces[-1] < ces[0]
    assert all(str(p.dtype) == "float32" for p in params)
    assert all(str(s.dtype) == "float32" for st in states for s in st)


def test_fp16_scaler_skips_step_and_halves_scale():
    x, y = _data()
    tr = _trainer("float16")
    params, states, aux = tr.init_state({"data": (16, 8),
                                         "softmax_label": (16,)})
    inputs = tr.shard_inputs([x, y])
    params, states, aux, _, _ = tr.step(params, states, aux, inputs)
    before = [np.asarray(p).copy() for p in params]
    scale0 = tr.loss_scale

    bad = x.copy()
    bad[0, 0] = np.inf
    params, states, aux, _, _ = tr.step(params, states, aux,
                                        tr.shard_inputs([bad, y]))
    assert all((np.asarray(p) == b).all() for p, b in zip(params, before))
    assert tr.loss_scale == scale0 * 0.5
    assert tr.skipped_steps == 1

    ces = []
    for _ in range(20):
        params, states, aux, _, outs = tr.step(params, states, aux, inputs)
        ces.append(_host_ce(outs, y))
    assert np.isfinite(ces).all() and ces[-1] < ces[0]
    assert tr.skipped_steps == 1          # only the injected batch skipped


def test_fp16_step_k_carries_scale_in_scan():
    x, y = _data()
    tr = _trainer("float16")
    params, states, aux = tr.init_state({"data": (16, 8),
                                         "softmax_label": (16,)})
    k = 3
    xs = np.stack([x, x, x])
    xs[1, 0, 0] = np.inf                  # middle step overflows
    ys = np.stack([y, y, y])
    inputs_k = tr.shard_inputs([xs, ys], stacked=True)
    params, states, aux, losses, _ = tr.step_k(params, states, aux,
                                               inputs_k)
    assert np.asarray(losses).shape[0] == k
    # the carry threaded the scaler through the scan: exactly one skip,
    # one backoff, and the finite steps still applied
    assert tr.skipped_steps == 1
    assert tr.loss_scale == 1024.0 * 0.5
    assert all(np.isfinite(np.asarray(p)).all() for p in params)
    # fused result must match sequential stepping over the same batches
    tr2 = _trainer("float16")
    p2, s2, a2 = tr2.init_state({"data": (16, 8), "softmax_label": (16,)})
    for i in range(k):
        p2, s2, a2, _, _ = tr2.step(p2, s2, a2,
                                    tr2.shard_inputs([xs[i], ys[i]]))
    for a, b in zip(params, p2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert tr2.loss_scale == tr.loss_scale
    assert tr2.skipped_steps == tr.skipped_steps


def test_dynamic_loss_scaler_host_semantics():
    s = DynamicLossScaler(init_scale=8.0, growth_interval=2)
    assert s.update(overflow=True) is False      # skip the step
    assert s.scale == 4.0
    assert s.update(overflow=False) is True
    assert s.update(overflow=False) is True      # hits the interval
    assert s.scale == 8.0                        # grew back
    assert s.skipped_steps == 1


def test_hlo_bf16_allreduce_wire_dtype():
    """The tentpole acceptance check: all gradient all-reduce operands
    in the partitioned train step are bf16 while masters stay f32."""
    proc = subprocess.run(
        [sys.executable, "-m", "mxnet_tpu.amp", "--hlo-check",
         "--dtype", "bfloat16"],
        capture_output=True, text=True, timeout=300, cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert proc.returncode == 0, proc.stderr[-2000:]
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    assert rec["metric"] == "amp_hlo_check" and rec["ok"]
    assert rec["grad_allreduce"]
    assert all(dt == "bf16" for dt, _ in rec["grad_allreduce"])
    assert rec["master_f32"]


def test_serving_bf16_roundtrip(tmp_path):
    """bf16 .mxa artifact: fp32 I/O, amp_dtype recorded, outputs close
    to the fp32 artifact of the same params."""
    from mxnet_tpu.contrib.export import export_model
    from mxnet_tpu.serving import ServingEngine

    sym = _mlp_sym()
    mod = mx.mod.Module(sym, context=mx.cpu(0))
    mod.bind(data_shapes=[("data", (8, 8))],
             label_shapes=[("softmax_label", (8,))])
    mod.init_params(mx.init.Xavier())
    args, auxs = mod.get_params()

    p32 = str(tmp_path / "m32.mxa")
    p16 = str(tmp_path / "m16.mxa")
    export_model(p32, sym, args, auxs, {"data": (8, 8)})
    export_model(p16, sym, args, auxs, {"data": (8, 8)},
                 dtype="bfloat16")

    from mxnet_tpu.predictor import Predictor
    man = Predictor(p16).manifest
    assert man["serving"]["amp_dtype"] == "bfloat16"
    assert all(i["dtype"] == "float32" for i in man["inputs"])

    eng32 = ServingEngine(p32, warmup=False)
    eng16 = ServingEngine(p16, warmup=False)
    assert eng16.amp_dtype == "bfloat16"
    assert eng16.stats()["amp_dtype"] == "bfloat16"

    x = np.random.RandomState(0).normal(size=(5, 8)).astype(np.float32)
    out32 = eng32.infer(x)
    out16 = eng16.infer(x)
    for a, b in zip(out32, out16):
        assert a.dtype == np.float32 and b.dtype == np.float32
        np.testing.assert_allclose(a, b, atol=0.05)


def test_optimizer_bf16_multi_precision(caplog):
    """Satellite: create_state_multi_precision/update_multi_precision
    generalized from fp16-only to bf16 — bf16 weights get fp32 masters
    and track an fp32 reference run."""
    import jax.numpy as jnp
    rng = np.random.RandomState(3)
    w = rng.uniform(-1, 1, (4, 4)).astype(np.float32)
    g = rng.uniform(-1, 1, (4, 4)).astype(np.float32)

    opt16 = mx.optimizer.create("sgd", learning_rate=0.1, momentum=0.9,
                                multi_precision=True)
    w16 = mx.nd.array(np.asarray(jnp.asarray(w, jnp.bfloat16)))
    state = opt16.create_state_multi_precision(0, w16)
    assert state[1].dtype == np.float32        # fp32 master
    opt16.update_multi_precision(0, w16, mx.nd.array(g), state)

    opt32 = mx.optimizer.create("sgd", learning_rate=0.1, momentum=0.9)
    w32 = mx.nd.array(w)
    st32 = opt32.create_state(0, w32)
    opt32.update(0, w32, mx.nd.array(g), st32)
    # the fp32 MASTER matches the fp32 run exactly up to the initial
    # bf16 rounding of the weight
    np.testing.assert_allclose(state[1].asnumpy(), w32.asnumpy(),
                               atol=0.02)

    # the actionable warning fires for bf16 without multi_precision
    # (reference contract: create_state_multi_precision logs it; plain
    # create_state stays silent)
    with caplog.at_level(logging.WARNING):
        mx.optimizer.create("sgd", learning_rate=0.1) \
            .create_state_multi_precision(
                1, mx.nd.array(np.asarray(jnp.asarray(w, jnp.bfloat16))))
    assert any("multi_precision" in r.getMessage() for r in caplog.records)


def test_amp_profiler_counters():
    amp.init("bfloat16")
    c = amp.counters()
    assert c["enabled"] and c["dtype"] == "bfloat16"
    from mxnet_tpu import profiler
    exported = profiler.export_counters()
    assert exported["amp"]["dtype"] == "bfloat16"
    # a plain fp32 module forward traced under amp: the executor hook
    # downcasts the matmul inputs, which the byte counter accounts
    x, y = _data()
    mod = mx.mod.Module(_mlp_sym(), context=mx.cpu(0))
    mod.bind(data_shapes=[("data", (16, 8))],
             label_shapes=[("softmax_label", (16,))])
    mod.init_params(mx.init.Xavier())
    mod.forward(mx.io.DataBatch(data=[mx.nd.array(x)],
                                label=[mx.nd.array(y)]), is_train=False)
    mod.get_outputs()[0].asnumpy()
    assert amp.counters()["amp_cast_bytes_saved"] > 0
    tr = _trainer("float16")
    params, states, aux = tr.init_state({"data": (16, 8),
                                         "softmax_label": (16,)})
    tr.step(params, states, aux, tr.shard_inputs([x, y]))
    c = amp.counters()
    assert c["amp_scale"] == 1024.0
    assert c["amp_skipped_steps"] == 0
