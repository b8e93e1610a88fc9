"""Registry-driven whole-surface TPU sweep.

Role of the reference's tests/python/gpu/test_operator_gpu.py:1, which
re-runs the ENTIRE CPU unit suite on the accelerator: here, every schema in
`ops/registry.py` is executed on BOTH backends (CPU jax vs TPU jax) through
the real imperative layer with auto-synthesized inputs, and the outputs are
cross-checked. Ops that cannot run in this generic harness MUST carry a
written reason in `SKIP` — the parametrization covers every canonical
schema, so an op that is neither executable nor excused fails the lane.

Gradient parity: for each case, d(sum(out0))/d(input0) is also compared
whenever jax can differentiate the op (integer/bool ops and
non-differentiable kernels are detected per-op and recorded, not failed —
forward parity is the contract for those).
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import imperative
from mxnet_tpu.ndarray.ndarray import NDArray
from mxnet_tpu.ops.registry import canonical_names

RTOL, ATOL = 2e-2, 2e-3          # bf16-ish MXU headroom on conv/dot paths
CPU, TPU = mx.cpu(0), mx.tpu(0)

# ---------------------------------------------------------------------------
# Ops excluded from the generic harness — every entry carries its reason.
# "covered by <test>" means the op executes on the TPU in that dedicated
# test; "host-only" ops never touch the accelerator by design.
# ---------------------------------------------------------------------------
SKIP = {
    # -- covered by dedicated TPU-lane tests (structured inputs) ----------
    "_contrib_MultiBoxPrior": "covered by test_detection_ops_consistency",
    "_contrib_MultiBoxTarget": "covered by test_detection_ops_consistency",
    "_contrib_MultiBoxDetection": "covered by test_detection_ops_consistency",
    "_contrib_box_nms": "covered by test_detection_ops_consistency",
    "_contrib_box_iou": "covered by test_detection_ops_consistency",
    "_contrib_bipartite_matching":
        "covered by test_detection_ops_consistency",
    "_contrib_Proposal": "anchor/score/im_info triplet; covered by "
                         "tests/test_contrib.py::test_proposal (CPU) — "
                         "runs the same jax kernel XLA compiles for TPU",
    "_contrib_MultiProposal": "same kernel family as _contrib_Proposal",
    "CTCLoss": "label/length-coupled inputs; covered by "
               "test_extra_ops_consistency (ctc parity on chip)",
    "_contrib_DeformableConvolution":
        "offset-shaped inputs; covered by tests/test_contrib.py deformable "
        "cases (CPU) over the same jax kernel",
    "_contrib_DeformablePSROIPooling":
        "roi+trans inputs; covered by tests/test_contrib.py",
    "_contrib_PSROIPooling": "roi inputs; covered by tests/test_contrib.py",
    "_contrib_count_sketch": "hash-table h/s inputs; tests/test_contrib.py",
    "_contrib_flash_attention": "covered by test_family_sweep_consistency"
                                " ('flash_attention_op' case)",
    "_contrib_kda": "ten coupled operands at head widths the kernels "
                    "take; covered by test_kda_kernels.py and "
                    "test_kda_prepare.py",
    "_contrib_moe_experts": "router, ids and grouped weights coupled; "
                            "covered on the chip by check (b) of the "
                            "benchmark's language-model cells",
    "_contrib_lm_head_ce": "integer labels coupled to the head's rows; "
                           "covered on the chip by check (b) of the "
                           "benchmark's language-model cells",
    "RNN": "packed-parameter layout; covered by test_family_sweep_"
           "consistency ('fused_rnn_lstm') and tests/test_rnn.py",
    "ROIPooling": "covered by test_family_sweep_consistency ('roipooling')",
    "BilinearSampler": "grid input range-coupled to data; covered by "
                       "test_family_sweep_consistency "
                       "('grid_bilinear_sampler')",
    "Correlation": "two coupled feature maps; tests/test_contrib_python.py",
    "Crop": "legacy multi-input crop; tests/test_operator.py (CPU) — "
            "pure lax.slice lowering",
    "SVMOutput": "margin-label coupling; tests/test_operator.py (CPU), "
                 "pure elementwise lowering",
    "IdentityAttachKLSparseReg": "sparsity-regularizer aux contract; "
                                 "tests/test_operator.py (CPU)",
    # -- quantization: int8 lane has its own consistency tests ------------
    "_contrib_quantize": "covered by test_quantized_ops_consistency",
    "_contrib_dequantize": "covered by test_quantized_ops_consistency",
    "_contrib_requantize": "covered by test_quantized_ops_consistency",
    "_contrib_quantized_conv": "covered by test_quantized_ops_consistency",
    "_contrib_quantized_fully_connected":
        "covered by test_quantized_ops_consistency",
    "_contrib_quantized_pooling": "covered by test_quantized_ops_"
                                  "consistency",
    "_contrib_quantized_flatten": "covered by test_quantized_ops_"
                                  "consistency",
    # -- host-only by design ----------------------------------------------
    "Custom": "frontend callback op, needs a registered python op_type; "
              "covered by test_custom_op_on_chip",
    "_image_to_tensor": "uint8 host decode helper; covered by "
                        "test_extra_ops_consistency",
}

# required-attr defaults by param name (generic), then per-op overrides
GENERIC_ATTRS = {
    "scalar": 2.0, "dtype": "float32", "shape": (2, 3), "axis": 0,
    "size": 2, "nsize": 3, "lr": 0.1, "block_size": 2, "value": 2.0,
    "N": 3, "num": 1, "dim": 4, "stype": "default", "t": 1,
}

# per-op: attrs / input shapes / integer-input indices / positive inputs
CASES = {
    "Convolution": dict(attrs={"kernel": (3, 3), "num_filter": 4},
                        shapes=[(2, 3, 6, 6), None, None]),
    "Deconvolution": dict(attrs={"kernel": (3, 3), "num_filter": 4},
                          shapes=[(2, 3, 6, 6), None, None]),
    "FullyConnected": dict(attrs={"num_hidden": 4},
                           shapes=[(2, 6), None, None]),
    "Pooling": dict(attrs={"kernel": (2, 2), "pool_type": "max"},
                    shapes=[(2, 3, 6, 6)]),
    "Pooling_v1": dict(attrs={"kernel": (2, 2), "pool_type": "avg"},
                       shapes=[(2, 3, 6, 6)]),
    "Activation": dict(attrs={"act_type": "relu"}),
    "LeakyReLU": dict(attrs={"act_type": "leaky"}),
    "Dropout": dict(attrs={"p": 0.5}),
    "BatchNorm": dict(shapes=[(2, 3, 4, 4), (3,), (3,), (3,), (3,)],
                      positive={3: False, 4: True}),
    "LayerNorm": dict(shapes=[(2, 6), (6,), (6,)]),
    "InstanceNorm": dict(shapes=[(2, 3, 4, 4), (3,), (3,)]),
    "L2Normalization": dict(shapes=[(2, 3, 4)]),
    "LRN": dict(attrs={"nsize": 3}, shapes=[(2, 5, 4, 4)]),
    "SoftmaxOutput": dict(shapes=[(4, 5), (4,)], int_inputs={1}),
    "SoftmaxActivation": dict(shapes=[(4, 5)]),
    "LinearRegressionOutput": dict(shapes=[(4, 3), (4, 3)]),
    "MAERegressionOutput": dict(shapes=[(4, 3), (4, 3)]),
    "LogisticRegressionOutput": dict(shapes=[(4, 3), (4, 3)]),
    "MakeLoss": dict(shapes=[(4, 3)]),
    "Embedding": dict(attrs={"input_dim": 6, "output_dim": 4},
                      shapes=[(3, 2), (6, 4)], int_inputs={0}),
    "UpSampling": dict(attrs={"scale": 2, "sample_type": "nearest",
                              "num_args": 1}, shapes=[(1, 2, 3, 3)]),
    "Pad": dict(attrs={"mode": "edge",
                       "pad_width": (0, 0, 0, 0, 1, 1, 1, 1)},
                shapes=[(1, 2, 3, 3)]),
    "GridGenerator": dict(attrs={"transform_type": "affine",
                                 "target_shape": (4, 4)},
                          shapes=[(1, 6)], rtol=5e-2, atol=1e-2),
    "SpatialTransformer": dict(
        attrs={"transform_type": "affine", "sampler_type": "bilinear",
               "target_shape": (4, 4)}, shapes=[(1, 2, 4, 4), (1, 6)]),
    "SequenceMask": dict(attrs={"use_sequence_length": False},
                         shapes=[(4, 2, 3)]),
    "SequenceLast": dict(attrs={"use_sequence_length": False},
                         shapes=[(4, 2, 3)]),
    "SequenceReverse": dict(attrs={"use_sequence_length": False},
                            shapes=[(4, 2, 3)]),
    "SliceChannel": dict(attrs={"num_outputs": 2}, shapes=[(2, 4, 3)]),
    "SwapAxis": dict(attrs={"dim1": 0, "dim2": 1}),
    "Cast": dict(attrs={"dtype": "float32"}),
    "_contrib_div_sqrt_dim": dict(shapes=[(2, 8)]),
    "_contrib_rope": dict(attrs={"rotary_dim": 4, "offset": 2},
                          shapes=[(2, 3, 8)]),
    "_contrib_gated_short_conv": dict(shapes=[(2, 5, 12), (4, 3)]),
    "RMSNorm": dict(shapes=[(2, 6), (6,)]),
    "_contrib_AdaptiveAvgPooling2D": dict(attrs={"output_size": (2, 2)},
                                          shapes=[(1, 3, 6, 6)]),
    "_contrib_BilinearResize2D": dict(attrs={"height": 6, "width": 6},
                                      shapes=[(1, 2, 4, 4)]),
    "_contrib_fft": dict(shapes=[(2, 8)]),
    "_contrib_ifft": dict(shapes=[(2, 16)]),
    "_contrib_krprod": dict(attrs={"num_args": 2}, shapes=[(3, 4), (5, 4)]),
    "khatri_rao": dict(attrs={"num_args": 2}, shapes=[(3, 4), (5, 4)]),
    "_contrib_quadratic": dict(attrs={"a": 1.0, "b": 2.0, "c": 3.0}),
    "Concat": dict(attrs={"num_args": 2}, shapes=[(2, 3), (2, 3)]),
    "add_n": dict(attrs={"num_args": 2}, shapes=[(2, 3), (2, 3)]),
    "stack": dict(attrs={"num_args": 2}, shapes=[(2, 3), (2, 3)]),
    "dot": dict(shapes=[(3, 4), (4, 5)]),
    "batch_dot": dict(shapes=[(2, 3, 4), (2, 4, 5)]),
    "take": dict(shapes=[(5, 3), (4,)], int_inputs={1}),
    "pick": dict(shapes=[(4, 5), (4,)], int_inputs={1}),
    "gather_nd": dict(shapes=[(4, 3), (1, 2)], int_inputs={1}),
    "scatter_nd": dict(attrs={"shape": (4, 3)}, shapes=[(2, 3), (1, 2)],
                       int_inputs={1}),
    "_scatter_set_nd": dict(attrs={"shape": (4, 3)},
                            shapes=[(4, 3), (2, 3), (1, 2)],
                            int_inputs={2}),
    "batch_take": dict(shapes=[(4, 3), (4,)], int_inputs={1}),
    "_slice_assign": dict(attrs={"begin": (0, 0), "end": (2, 2)},
                          shapes=[(3, 4), (2, 2)]),
    "_slice_assign_scalar": dict(attrs={"begin": (0, 0), "end": (2, 2),
                                        "scalar": 1.5}, shapes=[(3, 4)]),
    "depth_to_space": dict(attrs={"block_size": 2}, shapes=[(1, 8, 2, 3)]),
    "space_to_depth": dict(attrs={"block_size": 2}, shapes=[(1, 2, 4, 6)]),
    "one_hot": dict(attrs={"depth": 5}, shapes=[(4,)], int_inputs={0}),
    "reshape": dict(attrs={"shape": (3, 2)}, shapes=[(2, 3)]),
    "Reshape": dict(attrs={"shape": (3, 2)}, shapes=[(2, 3)]),
    "reshape_like": dict(shapes=[(2, 3), (3, 2)]),
    "broadcast_to": dict(attrs={"shape": (4, 3)}, shapes=[(1, 3)]),
    "broadcast_like": dict(shapes=[(1, 3), (4, 3)]),
    "broadcast_axis": dict(attrs={"axis": 0, "size": 4}, shapes=[(1, 3)]),
    "tile": dict(attrs={"reps": (2, 1)}, shapes=[(2, 3)]),
    "repeat": dict(attrs={"repeats": 2}),
    "pad": dict(attrs={"mode": "constant",
                       "pad_width": (0, 0, 0, 0, 1, 1, 1, 1)},
                shapes=[(1, 2, 3, 3)]),
    "expand_dims": dict(attrs={"axis": 0}),
    "slice": dict(attrs={"begin": (0, 1), "end": (2, 3)}, shapes=[(3, 4)]),
    "slice_axis": dict(attrs={"axis": 1, "begin": 0, "end": 2},
                       shapes=[(3, 4)]),
    "slice_like": dict(shapes=[(4, 5), (2, 3)]),
    "clip": dict(attrs={"a_min": -0.5, "a_max": 0.5}),
    "topk": dict(attrs={"k": 2, "axis": 1}, shapes=[(3, 5)]),
    "sort": dict(attrs={"axis": 1}, shapes=[(3, 5)]),
    "argsort": dict(attrs={"axis": 1}, shapes=[(3, 5)]),
    "argmax": dict(attrs={"axis": 1}, shapes=[(3, 5)]),
    "argmin": dict(attrs={"axis": 1}, shapes=[(3, 5)]),
    "argmax_channel": dict(shapes=[(3, 5)]),
    "where": dict(shapes=[(3, 4), (3, 4), (3, 4)], int_inputs={0}),
    "transpose": dict(shapes=[(2, 3)]),
    "flip": dict(attrs={"axis": 0}),
    "reverse": dict(attrs={"axis": 0}),
    "square_sum": dict(attrs={"axis": 1}, shapes=[(3, 4)]),
    "norm": dict(shapes=[(3, 4)]),
    "_linalg_gemm": dict(shapes=[(3, 4), (4, 5), (3, 5)]),
    "_linalg_gemm2": dict(shapes=[(3, 4), (4, 5)]),
    "_linalg_potrf": dict(spd=True, shapes=[(3, 3)]),
    "_linalg_potri": dict(spd=True, shapes=[(3, 3)]),
    "_linalg_trsm": dict(spd=True, shapes=[(3, 3), (3, 2)]),
    "_linalg_trmm": dict(spd=True, shapes=[(3, 3), (3, 2)]),
    "_linalg_sumlogdiag": dict(spd=True, shapes=[(3, 3)]),
    "_linalg_syrk": dict(shapes=[(3, 4)]),
    "_linalg_gelqf": dict(shapes=[(3, 4)]),
    # eigenvectors are unique only up to per-column sign: compare |U|
    "_linalg_syevd": dict(spd=True, shapes=[(3, 3)], abs_compare=True),
    "_linalg_makediag": dict(shapes=[(3,)]),
    "_linalg_extractdiag": dict(shapes=[(3, 3)]),
    "_linalg_maketrian": dict(shapes=[(6,)]),
    "_linalg_extracttrian": dict(shapes=[(3, 3)]),
    "_linalg_inverse": dict(spd=True, shapes=[(3, 3)]),
    "_linalg_det": dict(shapes=[(3, 3)]),
    "_linalg_slogdet": dict(spd=True, shapes=[(3, 3)]),
}

_ATTR_CACHE = {}


def _case_for(name, schema):
    case = dict(CASES.get(name, {}))
    attrs = dict(case.get("attrs", {}))
    for pname, p in schema.params.items():
        if p.required and pname not in attrs:
            if pname in GENERIC_ATTRS:
                attrs[pname] = GENERIC_ATTRS[pname]
            else:
                raise AssertionError(
                    f"op {name}: no default for required param {pname!r}; "
                    "add a CASES entry or a SKIP reason")
    case["attrs"] = attrs
    return case


def _synth_inputs(name, schema, case, rng):
    attrs = schema.parse_attrs(case["attrs"])
    n_in = schema.num_inputs(attrs)
    shapes = case.get("shapes")
    candidates = [shapes] if shapes else [[(2, 3)] * n_in, [(2, 3, 4)] * n_in,
                                          [(2, 3, 4, 4)] * n_in, [(4,)] * n_in]
    int_inputs = case.get("int_inputs", set())
    last_err = None
    for cand in candidates:
        cand = list(cand) + [None] * (n_in - len(cand))
        if schema.infer_shape is not None:
            try:
                cand, _ = schema.infer_shape(attrs, list(cand))
            except Exception as e:           # infer may reject the guess
                last_err = e
                continue
        if any(s is None for s in cand):
            last_err = AssertionError(f"unresolved input shapes {cand}")
            continue
        vals = []
        for i, s in enumerate(cand):
            if i in int_inputs:
                v = rng.randint(0, 2, size=s).astype(np.float32)
            elif case.get("spd"):
                a = rng.normal(0, 1, size=s).astype(np.float32)
                v = (a @ a.T + np.eye(s[0], dtype=np.float32) * s[0]) \
                    if len(s) == 2 and s[0] == s[-1] else np.abs(a) + 0.5
            elif case.get("positive", {}).get(i, True):
                v = rng.uniform(0.3, 1.2, size=s).astype(np.float32)
            else:
                v = rng.normal(0, 1, size=s).astype(np.float32)
            vals.append(v)
        # probe on CPU: does this input set actually execute?
        try:
            _run(schema, vals, case["attrs"], CPU)
            return vals
        except Exception as e:
            last_err = e
            continue
    raise AssertionError(
        f"op {name}: could not synthesize executable inputs "
        f"({type(last_err).__name__}: {last_err}); add a CASES entry or a "
        "SKIP reason")


def _run(schema, vals, attrs, ctx):
    mx.random.seed(1234)   # rng ops: same key stream on both backends
    nds = [mx.nd.array(v, ctx=ctx) for v in vals]
    out = imperative.invoke(schema, nds, dict(attrs))
    if isinstance(out, NDArray):
        out = [out]
    return [o.asnumpy() for o in out]


def _grad_parity(schema, vals, attrs, rtol, atol):
    """d(sum(out0))/d(input0) on both backends, when differentiable."""
    import jax
    import jax.numpy as jnp
    parsed = schema.parse_attrs(dict(attrs))
    from mxnet_tpu.ops.registry import OpCtx

    def f(x0, rest, platform):
        key = jax.random.PRNGKey(7)
        octx = OpCtx(is_train=True, rng=key, platform=platform)
        res = schema.fcompute(parsed, octx, x0, *rest)
        out0 = res[0] if isinstance(res, tuple) else res
        if not jnp.issubdtype(out0.dtype, jnp.floating):
            raise TypeError("integer output")
        return jnp.sum(out0)

    grads = []
    for dev_str in ("cpu", None):
        dev = jax.devices("cpu")[0] if dev_str == "cpu" else \
            TPU.jax_device()
        x0 = jax.device_put(vals[0], dev)
        rest = [jax.device_put(v, dev) for v in vals[1:]]
        try:
            g = jax.grad(lambda x: f(x, rest, dev.platform))(x0)
        except (TypeError, ValueError):
            return None  # not differentiable — forward parity is the bar
        grads.append(np.asarray(jax.device_get(g)))
    np.testing.assert_allclose(grads[0], grads[1], rtol=rtol, atol=atol,
                               equal_nan=True,
                               err_msg=f"{schema.name}: grad mismatch")
    return True


_ALL = sorted(canonical_names().items())


@pytest.mark.parametrize("name,schema", _ALL, ids=[n for n, _ in _ALL])
def test_registry_op_tpu_consistency(name, schema):
    if name in SKIP:
        pytest.skip(SKIP[name])
    if len(schema.input_names) == 0:
        # creation ops (zeros/ones/arange...): execute on TPU, compare
        case = _case_for(name, schema)
        out_c = _run(schema, [], case["attrs"], CPU)
        out_t = _run(schema, [], case["attrs"], TPU)
        for a, b in zip(out_c, out_t):
            np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6,
                                       equal_nan=True)
        return
    rng = np.random.RandomState(99)
    case = _case_for(name, schema)
    vals = _synth_inputs(name, schema, case, rng)
    out_c = _run(schema, vals, case["attrs"], CPU)
    out_t = _run(schema, vals, case["attrs"], TPU)
    assert len(out_c) == len(out_t)
    rtol = case.get("rtol", RTOL)
    atol = case.get("atol", ATOL)
    if case.get("abs_compare"):
        out_c = [np.abs(a) for a in out_c]
        out_t = [np.abs(b) for b in out_t]
    for i, (a, b) in enumerate(zip(out_c, out_t)):
        if a.dtype.kind in "iub":
            np.testing.assert_array_equal(a, b,
                                          err_msg=f"{name} out[{i}]")
        else:
            np.testing.assert_allclose(a, b, rtol=rtol, atol=atol,
                                       equal_nan=True,
                                       err_msg=f"{name} out[{i}]")
    if not case.get("abs_compare"):   # sign-ambiguous outputs: fwd-only
        _grad_parity(schema, vals, case["attrs"], rtol=5e-2, atol=5e-3)


def test_registry_sweep_covers_every_schema():
    """The executes-or-documented contract: every canonical schema is either
    parametrized above (and must pass) or carries a written SKIP reason."""
    names = set(canonical_names())
    unknown_skips = set(SKIP) - names
    assert not unknown_skips, f"SKIP entries for unknown ops: {unknown_skips}"
    assert all(r.strip() for r in SKIP.values())
