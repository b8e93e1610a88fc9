"""CPU-jax vs TPU-jax backend parity (role of
tests/python/gpu/test_operator_gpu.py + check_consistency,
python/mxnet/test_utils.py:1207). Tolerances account for the TPU MXU's
bf16 matmul passes (XLA DEFAULT precision)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.test_utils import check_consistency, assert_almost_equal


def _pair(shapes):
    return [dict(ctx=mx.cpu(0), **shapes), dict(ctx=mx.tpu(0), **shapes)]


ELEMWISE_RTOL = 1e-4
MXU_RTOL = 5e-3   # matmul/conv run as bf16 MXU passes
MXU_ATOL = 5e-2


def test_elementwise_consistency():
    d = mx.sym.Variable("data")
    sym = mx.sym.tanh(mx.sym.exp(d * 0.3) + mx.sym.sigmoid(d))
    check_consistency(sym, _pair({"data": (4, 5)}), rtol=ELEMWISE_RTOL,
                      atol=1e-4)


def test_fc_consistency():
    sym = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=8,
                                name="fc")
    check_consistency(sym, _pair({"data": (4, 6)}), rtol=MXU_RTOL,
                      atol=MXU_ATOL)


def test_conv_bn_pool_consistency():
    d = mx.sym.Variable("data")
    c = mx.sym.Convolution(d, kernel=(3, 3), pad=(1, 1), num_filter=4,
                           name="conv")
    b = mx.sym.BatchNorm(c, name="bn", fix_gamma=False)
    p = mx.sym.Pooling(b, pool_type="max", kernel=(2, 2), stride=(2, 2))
    check_consistency(p, _pair({"data": (2, 3, 8, 8)}), rtol=MXU_RTOL,
                      atol=MXU_ATOL)


def test_softmax_reduce_consistency():
    d = mx.sym.Variable("data")
    sym = mx.sym.sum(mx.sym.log_softmax(d, axis=1), axis=0)
    check_consistency(sym, _pair({"data": (4, 7)}), rtol=1e-4, atol=1e-4)


def test_training_step_parity():
    """3 SGD steps on TPU track CPU within bf16-matmul tolerance."""
    rng = np.random.RandomState(0)
    X = rng.normal(size=(64, 8)).astype(np.float32)
    Y = rng.randint(0, 3, size=64).astype(np.float32)
    sym = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=3,
                              name="fc"), name="softmax")
    results = []
    for ctx in (mx.cpu(0), mx.tpu(0)):
        it = mx.io.NDArrayIter(X, Y, batch_size=32)
        mod = mx.mod.Module(sym, context=ctx)
        mod.bind(data_shapes=it.provide_data,
                 label_shapes=it.provide_label)
        mod.init_params(mx.init.Constant(0.05))
        mod.init_optimizer(optimizer="sgd",
                           optimizer_params={"learning_rate": 0.1})
        for _ in range(3):
            it.reset()
            for batch in it:
                mod.forward_backward(batch)
                mod.update()
        results.append(mod.get_params()[0]["fc_weight"].asnumpy())
    assert_almost_equal(results[1], results[0], rtol=5e-3, atol=5e-3,
                        names=("tpu", "cpu"))


def test_rng_ops_run_on_tpu():
    x = mx.nd.random.uniform(0, 1, shape=(64, 64), ctx=mx.tpu(0))
    assert x.context.device_type in ("tpu", "gpu")
    m = float(x.asnumpy().mean())
    assert 0.4 < m < 0.6


def test_detection_ops_consistency():
    """Contrib detection ops agree across backends (fori-loop NMS and
    argsort compaction must not diverge between CPU and TPU lowering)."""
    d = mx.sym.Variable("data")
    anchors = mx.sym.contrib.MultiBoxPrior(d, sizes=(0.3, 0.5),
                                           ratios=(1.0, 2.0), clip=True)
    check_consistency(anchors, _pair({"data": (1, 3, 4, 4)}),
                      rtol=1e-5, atol=1e-6, grad_req="null")

    rng = np.random.RandomState(5)
    rows = np.concatenate([
        rng.randint(0, 2, (12, 1)).astype(np.float32),
        rng.uniform(0.1, 1.0, (12, 1)).astype(np.float32),
        rng.uniform(0, 0.8, (12, 2)).astype(np.float32),
        rng.uniform(0.1, 0.3, (12, 2)).astype(np.float32)], axis=1)
    rows[:, 4:] += rows[:, 2:4]
    outs = []
    for ctx in (mx.cpu(0), mx.tpu(0)):
        with mx.Context(ctx):
            nd_rows = mx.nd.array(rows, ctx=ctx)
            outs.append(mx.nd.contrib.box_nms(
                nd_rows, overlap_thresh=0.5, coord_start=2, score_index=1,
                id_index=0).asnumpy())
    assert_almost_equal(outs[0], outs[1], rtol=1e-5, atol=1e-5)


def test_quantized_ops_consistency():
    """int8 conv/FC on the MXU lane give the same int32 accumulators as
    the CPU backend (integer math must be bit-exact)."""
    rng = np.random.RandomState(6)
    qx = rng.randint(-127, 128, (2, 3, 6, 6)).astype(np.int8)
    qw = rng.randint(-127, 128, (4, 3, 3, 3)).astype(np.int8)
    outs = []
    for ctx in (mx.cpu(0), mx.tpu(0)):
        x = mx.nd.array(qx, ctx=ctx, dtype="int8")
        w = mx.nd.array(qw, ctx=ctx, dtype="int8")
        o, _, _ = mx.nd.contrib.quantized_conv(
            x, w, mx.nd.array([-1.0], ctx=ctx), mx.nd.array([1.0], ctx=ctx),
            mx.nd.array([-1.0], ctx=ctx), mx.nd.array([1.0], ctx=ctx),
            kernel=(3, 3), num_filter=4, no_bias=True)
        outs.append(o.asnumpy())
    np.testing.assert_array_equal(outs[0], outs[1])


def test_extra_ops_consistency():
    rng = np.random.RandomState(7)
    img = rng.randint(0, 255, (5, 6, 3)).astype(np.uint8)
    outs = []
    for ctx in (mx.cpu(0), mx.tpu(0)):
        outs.append(mx.nd._image_to_tensor(
            mx.nd.array(img, ctx=ctx, dtype="uint8")).asnumpy())
    assert_almost_equal(outs[0], outs[1], rtol=1e-6, atol=1e-6)
    # CTC loss parity
    acts = rng.normal(size=(5, 2, 4)).astype(np.float32)
    labels = np.array([[1, 2], [3, 0]], np.float32)
    louts = []
    for ctx in (mx.cpu(0), mx.tpu(0)):
        louts.append(mx.nd.contrib.ctc_loss(
            mx.nd.array(acts, ctx=ctx),
            mx.nd.array(labels, ctx=ctx)).asnumpy())
    assert_almost_equal(louts[0], louts[1], rtol=1e-4, atol=1e-4)


def _v(name="data"):
    return mx.sym.Variable(name)


# broad per-family sweep (role of test_operator_gpu re-running the op suite
# under the accelerator): each case is (id, symbol builder, shapes, rtol,
# atol). Shapes stay small — every case compiles fwd+bwd on both backends.
_SWEEP = [
    ("unary_chain",
     lambda: mx.sym.arctan(mx.sym.softsign(_v()) + mx.sym.erf(_v() * 0.5)),
     {"data": (3, 7)}, ELEMWISE_RTOL, 1e-4),
    ("unary_log_exp",
     lambda: mx.sym.log1p(mx.sym.exp(_v() * 0.3)) + mx.sym.expm1(_v() * 0.1),
     {"data": (4, 5)}, ELEMWISE_RTOL, 1e-4),
    ("binary_broadcast",
     lambda: mx.sym.broadcast_maximum(
         mx.sym.broadcast_add(_v(), mx.sym.Variable("b")),
         mx.sym.broadcast_mul(_v(), mx.sym.Variable("b"))),
     {"data": (3, 1, 4), "b": (1, 2, 4)}, ELEMWISE_RTOL, 1e-4),
    ("reductions",
     lambda: mx.sym.sum(_v(), axis=1) + mx.sym.mean(_v(), axis=1) +
     mx.sym.max(_v(), axis=1) + mx.sym.min(_v(), axis=1),
     {"data": (5, 6)}, 1e-4, 1e-4),
    ("dot_transpose",
     lambda: mx.sym.dot(_v(), mx.sym.transpose(mx.sym.Variable("b"))),
     {"data": (4, 6), "b": (5, 6)}, MXU_RTOL, MXU_ATOL),
    ("batch_dot",
     lambda: mx.sym.batch_dot(_v(), mx.sym.Variable("b")),
     {"data": (2, 3, 4), "b": (2, 4, 5)}, MXU_RTOL, MXU_ATOL),
    ("matrix_ops",
     lambda: mx.sym.reverse(mx.sym.tile(mx.sym.slice(
         _v(), begin=(0, 1), end=(3, 4)), reps=(1, 2)), axis=1),
     {"data": (3, 5)}, ELEMWISE_RTOL, 1e-5),
    ("indexing_take",
     lambda: mx.sym.take(_v(), mx.sym.floor(
         mx.sym.abs(mx.sym.Variable("idx")) * 2), axis=0),
     {"data": (5, 3), "idx": (4,)}, ELEMWISE_RTOL, 1e-4),
    ("one_hot_embed",
     lambda: mx.sym.Embedding(mx.sym.abs(mx.sym.round(
         mx.sym.Variable("idx") * 2)), input_dim=6, output_dim=4,
         name="emb"),
     {"idx": (3, 2)}, ELEMWISE_RTOL, 1e-4),
    ("ordering_topk",
     lambda: mx.sym.topk(_v(), k=3, ret_typ="value", axis=1),
     {"data": (4, 8)}, ELEMWISE_RTOL, 1e-5),
    ("argsort_argmax",
     lambda: mx.sym.argsort(_v(), axis=1) + mx.sym.argmax(
         _v(), axis=1, keepdims=True),
     {"data": (3, 6)}, 1e-6, 1e-6),
    ("linalg_gemm2_potrf",
     lambda: mx.sym._linalg_gemm2(_v(), _v(), transpose_b=True),
     {"data": (3, 4)}, MXU_RTOL, MXU_ATOL),
    ("layernorm",
     lambda: mx.sym.LayerNorm(_v(), mx.sym.Variable("g"),
                              mx.sym.Variable("be"), axis=-1),
     {"data": (4, 6), "g": (6,), "be": (6,)}, 1e-3, 1e-3),
    ("instancenorm_l2norm",
     lambda: mx.sym.L2Normalization(mx.sym.InstanceNorm(
         _v(), mx.sym.Variable("g"), mx.sym.Variable("be"))),
     {"data": (2, 3, 4, 4), "g": (3,), "be": (3,)}, 1e-3, 1e-3),
    ("lrn",
     lambda: mx.sym.LRN(_v(), nsize=3),
     {"data": (2, 5, 4, 4)}, 1e-3, 1e-3),
    ("deconv",
     lambda: mx.sym.Deconvolution(_v(), kernel=(3, 3), num_filter=2,
                                  name="dc"),
     {"data": (1, 3, 5, 5)}, MXU_RTOL, MXU_ATOL),
    ("depthwise_conv",
     lambda: mx.sym.Convolution(_v(), kernel=(3, 3), num_filter=4,
                                num_group=4, pad=(1, 1), name="dw"),
     {"data": (1, 4, 6, 6)}, MXU_RTOL, MXU_ATOL),
    ("conv1d_3d",
     lambda: mx.sym.Convolution(_v(), kernel=(3,), num_filter=2,
                                name="c1"),
     {"data": (2, 3, 8)}, MXU_RTOL, MXU_ATOL),
    ("upsampling_pad",
     lambda: mx.sym.Pad(mx.sym.UpSampling(
         _v(), scale=2, sample_type="nearest"), mode="edge",
         pad_width=(0, 0, 0, 0, 1, 1, 1, 1)),
     {"data": (1, 2, 3, 3)}, ELEMWISE_RTOL, 1e-5),
    ("leaky_prelu",
     lambda: mx.sym.LeakyReLU(_v(), act_type="prelu",
                              gamma=mx.sym.Variable("g"), name="pr"),
     {"data": (3, 4), "g": (4,)}, ELEMWISE_RTOL, 1e-5),
    ("elu_selu_gelu",
     lambda: mx.sym.LeakyReLU(_v(), act_type="elu") +
     mx.sym.Activation(_v(), act_type="softrelu"),
     {"data": (3, 5)}, 1e-4, 1e-4),
    ("sequence_ops",
     lambda: mx.sym.SequenceReverse(mx.sym.SequenceMask(
         _v(), use_sequence_length=False)),
     {"data": (4, 2, 3)}, ELEMWISE_RTOL, 1e-6),
    ("roipooling",
     lambda: mx.sym.ROIPooling(_v(), mx.sym.Variable("rois"),
                               pooled_size=(2, 2), spatial_scale=1.0),
     {"data": (1, 2, 6, 6), "rois": (2, 5)}, 1e-4, 1e-4),
    ("bilinear_resize",
     lambda: mx.sym.contrib.BilinearResize2D(_v(), height=6, width=6),
     {"data": (1, 2, 4, 4)}, 1e-4, 1e-4),
    ("adaptive_avg_pool",
     lambda: mx.sym.contrib.AdaptiveAvgPooling2D(_v(), output_size=(2, 2)),
     {"data": (1, 3, 6, 6)}, MXU_RTOL, MXU_ATOL),
    ("grid_bilinear_sampler",
     lambda: mx.sym.BilinearSampler(_v(), mx.sym.GridGenerator(
         mx.sym.Variable("aff"), transform_type="affine",
         target_shape=(4, 4))),
     {"data": (1, 2, 4, 4), "aff": (1, 6)}, 5e-2, 5e-2),
    ("swapaxis_flip_clip",
     lambda: mx.sym.clip(mx.sym.SwapAxis(_v(), dim1=1, dim2=2), -0.5, 0.5),
     {"data": (2, 3, 4)}, ELEMWISE_RTOL, 1e-6),
    ("where_mask",
     lambda: mx.sym.where(mx.sym.broadcast_greater(
         _v(), mx.sym.zeros(shape=(3, 4))), _v(), _v() * 0.1),
     {"data": (3, 4)}, ELEMWISE_RTOL, 1e-6),
    ("gather_scatter_nd",
     lambda: mx.sym.gather_nd(_v(), mx.sym.abs(mx.sym.round(
         mx.sym.Variable("idx")))),
     {"data": (4, 3), "idx": (1, 2)}, ELEMWISE_RTOL, 1e-5),
    ("fused_rnn_lstm",
     lambda: mx.sym.RNN(_v(), mx.sym.Variable("p"), mx.sym.Variable("s0"),
                        mx.sym.Variable("s1"), state_size=4, num_layers=1,
                        mode="lstm", name="rnn"),
     {"data": (3, 2, 5), "p": (4 * 4 * (5 + 4 + 2),), "s0": (1, 2, 4),
      "s1": (1, 2, 4)}, MXU_RTOL, MXU_ATOL),
    ("flash_attention_op",
     lambda: mx.sym.contrib.flash_attention(
         _v("q"), _v("k"), _v("v"), causal=True),
     {"q": (1, 2, 128, 128), "k": (1, 2, 128, 128),
      "v": (1, 2, 128, 128)}, 5e-3, 5e-2),
]


@pytest.mark.parametrize("case", _SWEEP, ids=[c[0] for c in _SWEEP])
def test_family_sweep_consistency(case):
    _, builder, shapes, rtol, atol = case
    check_consistency(builder(), _pair(shapes), rtol=rtol, atol=atol)


def test_rtc_kernel_output_stays_on_device():
    rng = np.random.RandomState(0)
    mod = mx.rtc.PallasModule(
        "def mul2(x_ref, o_ref):\n    o_ref[:] = x_ref[:] * 2.0\n")
    k = mod.get_kernel("mul2", num_inputs=1)
    a = mx.nd.array(rng.normal(size=(2, 128)).astype(np.float32),
                    ctx=mx.tpu(0))
    out = k.launch(a)
    assert "cpu" not in str(out.context).lower()
    assert_almost_equal(out.asnumpy(), a.asnumpy() * 2.0, rtol=1e-6)
    # cpu-context arrays run under the interpreter and stay on cpu
    b = mx.nd.array(rng.normal(size=(2, 128)).astype(np.float32),
                    ctx=mx.cpu())
    out_cpu = k.launch(b)
    assert "cpu" in str(out_cpu.context).lower()


def test_native_iter_feeds_module_on_chip(tmp_path):
    """Regression lane for the pipeline deadlock: the native C++ iterator
    feeding Module.fit on the real chip (a slow first step exposed the
    claim-before-buffer worker deadlock)."""
    from mxnet_tpu import recordio
    from mxnet_tpu.image.io import ImageRecordIter, _NativeImageRecordIter
    from mxnet_tpu import _native
    if not _native.has_jpeg():
        pytest.skip("native lib built without libjpeg")
    rng = np.random.RandomState(0)
    rec = recordio.MXIndexedRecordIO(str(tmp_path / "c.idx"),
                                     str(tmp_path / "c.rec"), "w")
    for i in range(32):
        base = 40 if i % 2 == 0 else 180
        img = (base + rng.randint(0, 20, (32, 32, 3))).clip(
            0, 255).astype(np.uint8)
        rec.write_idx(i, recordio.pack_img(
            recordio.IRHeader(0, float(i % 2), i, 0), img))
    rec.close()
    it = ImageRecordIter(str(tmp_path / "c.rec"), (3, 28, 28), 8,
                         shuffle=True, rand_crop=True, mean=128.0, std=64.0,
                         preprocess_threads=2, seed=3)
    assert isinstance(it, _NativeImageRecordIter)
    net = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
        mx.sym.Flatten(mx.sym.Variable("data")), num_hidden=2, name="fc"),
        name="softmax")
    mod = mx.mod.Module(net, context=mx.tpu(0))
    mod.fit(it, num_epoch=3, optimizer="sgd",
            optimizer_params={"learning_rate": 0.05, "momentum": 0.9},
            initializer=mx.init.Xavier())
    it.reset()
    acc = dict(mod.score(it, mx.metric.Accuracy()))["accuracy"]
    assert acc > 0.9
    it.close()


@pytest.mark.parametrize("dtype", ["bfloat16", "float16"])
def test_dtype_variant_consistency(dtype):
    """Reference check_consistency sweeps dtypes (fp16/32/64 ctx configs,
    test_utils.py:1207); here the TPU-relevant reduced precisions."""
    d = mx.sym.Variable("data")
    sym = mx.sym.FullyConnected(mx.sym.Activation(d, act_type="tanh"),
                                num_hidden=8, name="fc")
    shapes = {"data": (4, 6)}
    ctx_list = [dict(ctx=mx.cpu(0), type_dict={"data": dtype}, **shapes),
                dict(ctx=mx.tpu(0), type_dict={"data": dtype}, **shapes)]
    # reduced-precision storage: wide tolerances, but both backends must
    # agree to within a few representable steps
    check_consistency(sym, ctx_list, rtol=5e-2, atol=5e-2)


def test_profiler_chrome_trace_on_chip(tmp_path):
    """mx.profiler captures per-op events from a real-chip Module.fit and
    dumps a chrome://tracing-loadable JSON (profiler.h:87 role)."""
    import json
    out = str(tmp_path / "trace.json")
    mx.profiler.set_config(profile_all=True, filename=out)
    try:
        mx.profiler.set_state("run")
        rng = np.random.RandomState(0)
        X = rng.normal(size=(64, 16)).astype(np.float32)
        y = (X.sum(1) > 0).astype(np.float32)
        it = mx.io.NDArrayIter({"data": X}, {"softmax_label": y},
                               batch_size=32)
        net = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
            mx.sym.Variable("data"), num_hidden=2, name="fc"),
            name="softmax")
        mod = mx.mod.Module(net, context=mx.tpu(0))
        mod.fit(it, num_epoch=1, optimizer="sgd",
                optimizer_params={"learning_rate": 0.1},
                initializer=mx.init.Xavier())
        mx.profiler.set_state("stop")
        mx.profiler.dump()
    finally:
        # never leak run-state/profile_all into the rest of the lane
        mx.profiler.set_state("stop")
        mx.profiler.set_config(profile_all=False, filename=None)
    tr = json.load(open(out))
    events = tr["traceEvents"] if isinstance(tr, dict) else tr
    names = {e.get("name") for e in events if isinstance(e, dict)}
    assert len(events) > 5
    assert any("Forward" in (n or "") for n in names)
    assert "sgd_update" in names


# -- host-callback ops on the chip (jax.pure_callback) -----------------------

def test_custom_op_on_chip():
    """mx.operator.CustomOp forward/backward on the TPU (custom-inl.h
    escape-hatch role, SURVEY §2.2)."""
    import mxnet_tpu.operator as op

    class Square(op.CustomOp):
        def forward(self, is_train, req, in_data, out_data, aux):
            self.assign(out_data[0], req[0], in_data[0] * in_data[0])

        def backward(self, req, out_grad, in_data, out_data, in_grad, aux):
            self.assign(in_grad[0], req[0],
                        2 * in_data[0] * out_grad[0])

    @op.register("square_tpu")
    class SquareProp(op.CustomOpProp):
        def list_arguments(self):
            return ["data"]

        def infer_shape(self, in_shape):
            return in_shape, [in_shape[0]], []

        def create_operator(self, ctx, shapes, dtypes):
            return Square()

    x = mx.nd.array(np.arange(6).reshape(2, 3), ctx=mx.tpu(0))
    x.attach_grad()
    from mxnet_tpu import autograd
    with autograd.record():
        y = mx.nd.Custom(x, op_type="square_tpu")
    y.backward(mx.nd.ones_like(y))
    assert_almost_equal(y.asnumpy(), (np.arange(6).reshape(2, 3)) ** 2)


def test_autograd_function_on_chip():
    """mx.autograd.Function custom-vjp path on the TPU (reference
    autograd.py:383)."""
    from mxnet_tpu import autograd

    class Sigmoid(autograd.Function):
        def forward(self, x):
            y = 1.0 / (1.0 + mx.nd.exp(-x))
            self.save_for_backward(y)
            return y

        def backward(self, dy):
            (y,) = self.saved_tensors
            return dy * y * (1 - y)

    x = mx.nd.array([0.5, -1.0, 2.0], ctx=mx.tpu(0))
    x.attach_grad()
    with autograd.record():
        y = Sigmoid()(x)
    y.backward(mx.nd.ones_like(y))
    sig = 1 / (1 + np.exp(-x.asnumpy()))
    assert_almost_equal(x.grad.asnumpy(), sig * (1 - sig), atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_backward_parity_on_chip(causal):
    """Compiled Pallas flash backward (dq/dk/dv from the one recompute
    kernel, ops/attention.py:_flash_pallas_bwd) vs the dense-XLA vjp on
    the real chip."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import attention as at

    rng = np.random.RandomState(13)
    shape = (1, 2, 512, 128)
    q, k, v, g = (jnp.asarray(rng.normal(scale=0.5, size=shape)
                              .astype(np.float32)) for _ in range(4))
    with jax.default_matmul_precision("highest"):
        _, vjp_f = jax.vjp(lambda a, b, c: at.flash_attention(
            a, b, c, causal=causal, force="pallas"), q, k, v)
        got = vjp_f(g)
        _, vjp_d = jax.vjp(lambda a, b, c: at.reference_attention(
            a, b, c, causal=causal), q, k, v)
        want = vjp_d(g)
    for name, a, b in zip("qkv", got, want):
        assert_almost_equal(np.asarray(a), np.asarray(b), rtol=2e-2,
                            atol=2e-3, names=(f"flash_d{name}",
                                              f"dense_d{name}"))


def test_ring_attention_flash_on_chip():
    """Compiled ring-flash path on a 1-device TPU mesh: auto impl picks
    'flash' (mesh platform), the unrolled ring runs the Pallas kernels +
    logsumexp merge, and fwd/grads match the dense oracle. Scope notes:
    multi-device block merging is covered on the CPU mesh in
    tests/test_sp.py, and with n=1 the merge weight is constant so the
    lse cotangent here is identically zero — the NONZERO-glse compiled
    backward is covered by test_flash_lse_cotangent_on_chip below."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from mxnet_tpu.parallel import sp

    dev = [d for d in jax.devices() if d.platform != "cpu"][0]
    mesh = Mesh(np.array([dev]), ("sp",))
    rng = np.random.RandomState(17)
    q, k, v = (jnp.asarray(rng.normal(scale=0.5, size=(1, 2, 256, 128))
                           .astype(np.float32)) for _ in range(3))
    with jax.default_matmul_precision("highest"):
        got = sp.ring_attention(q, k, v, mesh, causal=True)
        want = sp.attention_reference(q, k, v, causal=True)
        assert_almost_equal(np.asarray(got), np.asarray(want),
                            rtol=2e-2, atol=2e-3,
                            names=("ring_flash", "dense"))

        def loss_ring(q, k, v):
            return jnp.sum(sp.ring_attention(q, k, v, mesh, causal=True)
                           ** 2)

        def loss_dense(q, k, v):
            return jnp.sum(sp.attention_reference(q, k, v, causal=True)
                           ** 2)

        g_r = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
        g_d = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", g_r, g_d):
        assert_almost_equal(np.asarray(a), np.asarray(b), rtol=2e-2,
                            atol=2e-2, names=(f"ring_d{name}",
                                              f"dense_d{name}"))


def test_flash_lse_cotangent_on_chip():
    """Compiled kernels with a NONZERO lse cotangent (the glse term the
    ring merge produces with >1 blocks): loss mixes out and lse; oracle
    is autodiff through the dense (out, lse) formulation."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import attention as at

    rng = np.random.RandomState(23)
    q, k, v = (jnp.asarray(rng.normal(scale=0.5, size=(1, 2, 256, 128))
                           .astype(np.float32)) for _ in range(3))

    def loss_flash(q, k, v):
        out, lse = at.flash_attention_with_lse(q, k, v, causal=True,
                                               force="pallas")
        return jnp.sum(out ** 2) + jnp.sum(jnp.sin(lse))

    def loss_dense(q, k, v):
        out, lse = at.reference_attention_with_lse(q, k, v, causal=True)
        return jnp.sum(out ** 2) + jnp.sum(jnp.sin(lse))

    with jax.default_matmul_precision("highest"):
        got = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", got, want):
        assert_almost_equal(np.asarray(a), np.asarray(b), rtol=2e-2,
                            atol=2e-2, names=(f"flash_d{name}",
                                              f"dense_d{name}"))


@pytest.mark.parametrize("h_kv", [2, 1])
def test_flash_gqa_parity_on_chip(h_kv):
    """Compiled GQA kernels (shared-KV index maps, r5) vs the dense
    oracle on the real chip — fwd + all three grads."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import attention as at

    rng = np.random.RandomState(14)
    q = jnp.asarray(rng.normal(scale=0.5, size=(1, 4, 512, 128))
                    .astype(np.float32))
    k, v = (jnp.asarray(rng.normal(scale=0.5, size=(1, h_kv, 512, 128))
                        .astype(np.float32)) for _ in range(2))
    g = jnp.asarray(rng.normal(scale=0.5, size=(1, 4, 512, 128))
                    .astype(np.float32))
    with jax.default_matmul_precision("highest"):
        out_f, vjp_f = jax.vjp(lambda a, b, c: at.flash_attention(
            a, b, c, causal=True, force="pallas"), q, k, v)
        got = vjp_f(g)
        out_d, vjp_d = jax.vjp(lambda a, b, c: at.reference_attention(
            a, b, c, causal=True), q, k, v)
        want = vjp_d(g)
    assert_almost_equal(np.asarray(out_f), np.asarray(out_d), rtol=2e-2,
                        atol=2e-3)
    for name, a, b in zip("qkv", got, want):
        assert a.shape == b.shape
        assert_almost_equal(np.asarray(a), np.asarray(b), rtol=2e-2,
                            atol=2e-3, names=(f"gqa_d{name}",
                                              f"dense_d{name}"))


@pytest.mark.parametrize("h,h_kv,d,dv", [(4, 4, 192, 128), (8, 2, 64, 64)],
                         ids=["4_heads_192_128", "8_over_2_heads_64"])
def test_flash_kernels_at_the_cells_shapes_on_chip(h, h_kv, d, dv):
    """The forward and the one backward kernel at the shapes the language-
    model cells run (8,192 tokens, bf16, causal, the default blocks of 512:
    16 q-blocks meeting unmasked, straddling and skipped k-blocks; heads of
    192 / 128, and a group of four query heads adding into one resident
    dK / dV at heads of 64), against the dense oracle in float32 at
    HIGHEST on the same bf16 values: within 2e-2 of the largest entry."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import attention as at

    s = 8192
    ks = jax.random.split(jax.random.PRNGKey(33), 4)
    q, k, v, g = (jax.random.normal(key, shape, jnp.float32)
                  .astype(jnp.bfloat16) for key, shape in zip(ks, (
                      (1, h, s, d), (1, h_kv, s, d), (1, h_kv, s, dv),
                      (1, h, s, dv))))
    out, vjp = jax.vjp(lambda *a: at.flash_attention(
        *a, causal=True, scale=d ** -0.5, force="pallas"), q, k, v)
    got = [np.asarray(a, np.float32) for a in (out,) + vjp(g)]
    del out, vjp
    # the oracle one k/v head and its group at a time: four heads' dense
    # (S, S) float32 scores and their cotangents are what the chip holds
    group = h // h_kv
    want = [[], [], [], []]
    for j in range(h_kv):
        heads = slice(j * group, (j + 1) * group)
        with jax.default_matmul_precision("highest"):
            want_out, vjp = jax.vjp(
                lambda *a: at.reference_attention(*a, causal=True,
                                                  scale=d ** -0.5),
                *(a.astype(jnp.float32) for a in (
                    q[:, heads], k[:, j:j + 1], v[:, j:j + 1])))
            parts = (want_out,) + vjp(g[:, heads].astype(jnp.float32))
        for acc, part in zip(want, parts):
            acc.append(np.asarray(part, np.float32))
        del want_out, vjp, parts
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        b = np.concatenate(b, axis=1)
        assert a.shape == b.shape
        err = np.max(np.abs(a - b)) / np.max(np.abs(b))
        print(f"flash {h}/{h_kv} x {d}/{dv} {name}: {err:.3e}")
        assert err <= 2e-2, (name, err)


def test_step_k_parity_on_chip():
    """One compiled step_k(4) dispatch == 4 step() dispatches on the
    real chip (the steps_per_dispatch driver, r5)."""
    import jax
    from mxnet_tpu.parallel import data_parallel_mesh, DataParallelTrainer

    data = mx.sym.Variable("data")
    f1 = mx.sym.FullyConnected(data, name="fc1", num_hidden=32)
    a1 = mx.sym.Activation(f1, act_type="relu")
    sym = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(a1, name="fc2", num_hidden=5),
        name="softmax")
    mesh = data_parallel_mesh(1, jax.devices())
    rng = np.random.RandomState(0)
    batches = [(rng.normal(size=(16, 12)).astype(np.float32),
                rng.randint(0, 5, 16).astype(np.float32))
               for _ in range(4)]
    key = jax.random.PRNGKey(11)

    def make():
        t = DataParallelTrainer(sym, mesh, learning_rate=0.1,
                                momentum=0.9, rescale_grad=1.0 / 16)
        return t, t.init_state({"data": (16, 12),
                                "softmax_label": (16,)})

    t1, (p1, s1, a1_) = make()
    for i, (x, y) in enumerate(batches):
        p1, s1, a1_, loss, _ = t1.step(p1, s1, a1_, t1.shard_inputs([x, y]),
                                       rng=key if i == 0 else None)
    t2, (p2, s2, a2_) = make()
    stacked = t2.shard_inputs([np.stack([b[0] for b in batches]),
                               np.stack([b[1] for b in batches])],
                              stacked=True)
    p2, s2, a2_, losses, _ = t2.step_k(p2, s2, a2_, stacked, rng=key)
    for a, b in zip(p1, p2):
        assert_almost_equal(np.asarray(a), np.asarray(b), rtol=2e-4,
                            atol=1e-5)
