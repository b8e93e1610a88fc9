"""INT8 quantization operators.

Parity target: src/operator/quantization/ (SURVEY.md §2.2 — quantize/
dequantize/requantize, quantized_conv, quantized_fully_connected,
quantized_pooling, quantized_flatten; range math in quantization_utils.h).

TPU-first notes. int8 is the MXU-native low-precision integer path: XLA
lowers int8 x int8 -> int32 `dot_general`/`conv_general_dilated`
(preferred_element_type=int32) straight onto the MXU, so the quantized ops
here are plain jax calls — no assembly kernels, no per-backend variants.
Symmetric (zero-offset) int8 is the default lane, matching the reference's
int8 calibration flow; uint8 in/out is supported in quantize/dequantize for
API parity. Ranges ride through the graph as (min, max) scalar arrays
exactly like the reference's extra op outputs, so the quantized graph stays
a pure dataflow program that XLA fuses.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as _np

from ..base import MXNetError
from .registry import Param, register

_INT32_MAX = float(2 ** 31 - 1)


def _t(*outs):
    return tuple(outs)


def _qrange(dtype_str):
    if dtype_str == "int8":
        return 127.0
    if dtype_str == "uint8":
        return 255.0
    if dtype_str == "int32":
        return _INT32_MAX
    raise MXNetError(f"unsupported quantized dtype {dtype_str!r}")


def _float_to_quantized(x, real_range, qrange):
    """Symmetric quantization (quantization_utils.h FloatToQuantized :78):
    sign(x) * min(|x| * scale + 0.5, qrange)."""
    scale = qrange / real_range
    return jnp.sign(x) * jnp.minimum(jnp.abs(x) * scale + 0.5, qrange)


def _quantize(attrs, octx, data, min_range, max_range):
    ot = attrs["out_type"]
    mn = jnp.reshape(min_range, ())
    mx = jnp.reshape(max_range, ())
    if ot == "int8":
        real = jnp.maximum(jnp.abs(mn), jnp.abs(mx))
        q = jnp.trunc(_float_to_quantized(data, real, 127.0))
        return _t(q.astype(jnp.int8), -real, real)
    elif ot == "uint8":
        # affine uint8 (quantize-inl.h uint8 lane)
        scale = 255.0 / (mx - mn)
        q = jnp.clip((data - mn) * scale + 0.5, 0.0, 255.0)
        return _t(jnp.trunc(q).astype(jnp.uint8), mn, mx)
    raise MXNetError(f"quantize: unsupported out_type {ot!r}")


def _quantize_infer(attrs, in_shapes):
    ds = in_shapes[0]
    in_shapes = [ds, (1,), (1,)]
    return in_shapes, [ds, (1,), (1,)]


register("_contrib_quantize", _quantize,
         params={"out_type": Param("str", "int8")},
         inputs=("data", "min_range", "max_range"), num_outputs=3,
         infer_shape=_quantize_infer)


def _dequantize(attrs, octx, data, min_range, max_range):
    mn = jnp.reshape(min_range, ())
    mx = jnp.reshape(max_range, ())
    real = jnp.maximum(jnp.abs(mn), jnp.abs(mx))
    if data.dtype == jnp.uint8:
        scale = (mx - mn) / 255.0
        return _t(data.astype(jnp.float32) * scale + mn)
    qrange = 127.0 if data.dtype == jnp.int8 else _INT32_MAX
    return _t(data.astype(jnp.float32) * (real / qrange))


def _dequantize_infer(attrs, in_shapes):
    ds = in_shapes[0]
    return [ds, (1,), (1,)], [ds]


register("_contrib_dequantize", _dequantize,
         params={"out_type": Param("str", "float32")},
         inputs=("data", "min_range", "max_range"),
         infer_shape=_dequantize_infer,
         infer_type=lambda attrs, in_types: ["float32"])


def _requantize(attrs, octx, data, min_range, max_range):
    """int32 -> int8. With calib ranges: fixed rescale. Without: the output
    range is the actual min/max of the data (requantize-inl.h online mode)."""
    mn = jnp.reshape(min_range, ())
    mx = jnp.reshape(max_range, ())
    in_real = jnp.maximum(jnp.abs(mn), jnp.abs(mx))
    f = data.astype(jnp.float32) * (in_real / _INT32_MAX)
    if attrs["min_calib_range"] is not None and \
            attrs["max_calib_range"] is not None:
        out_real = max(abs(attrs["min_calib_range"]),
                       abs(attrs["max_calib_range"]))
        out_real = jnp.asarray(out_real, jnp.float32)
    else:
        out_real = jnp.maximum(jnp.max(jnp.abs(f)), 1e-20)
    q = jnp.trunc(_float_to_quantized(f, out_real, 127.0))
    return _t(q.astype(jnp.int8), -out_real, out_real)


def _requantize_infer(attrs, in_shapes):
    ds = in_shapes[0]
    return [ds, (1,), (1,)], [ds, (1,), (1,)]


register("_contrib_requantize", _requantize,
         params={"min_calib_range": Param("float", None),
                 "max_calib_range": Param("float", None)},
         inputs=("data", "min_range", "max_range"), num_outputs=3,
         infer_shape=_requantize_infer,
         infer_type=lambda attrs, in_types: ["int8", "float32", "float32"])


def _mult_range(min_a, max_a, min_b, max_b, qa=127.0, qb=127.0):
    """Output range of int8 x int8 -> int32
    (QuantizationRangeForMultiplication, quantization_utils.h:138)."""
    a_level = jnp.maximum(jnp.abs(min_a), jnp.abs(max_a)) / qa
    b_level = jnp.maximum(jnp.abs(min_b), jnp.abs(max_b)) / qb
    c_level = a_level * b_level
    return -c_level * _INT32_MAX, c_level * _INT32_MAX


def _bias_to_int32(bias, min_bias, max_bias, out_level):
    """Fold an int8 bias into the int32 accumulator scale."""
    b_real = jnp.maximum(jnp.abs(jnp.reshape(min_bias, ())),
                         jnp.abs(jnp.reshape(max_bias, ())))
    f = bias.astype(jnp.float32) * (b_real / 127.0)
    return jnp.round(f / out_level).astype(jnp.int32)


def _quantized_conv(attrs, octx, data, weight, *rest):
    no_bias = attrs["no_bias"]
    if no_bias:
        bias = None
        min_d, max_d, min_w, max_w = rest
    else:
        bias, min_d, max_d, min_w, max_w, min_b, max_b = rest
    ns = len(attrs["kernel"])
    stride = tuple(attrs["stride"] or (1,) * ns)
    dilate = tuple(attrs["dilate"] or (1,) * ns)
    pad = tuple(attrs["pad"] or (0,) * ns)
    specs = {1: ("NCW", "OIW", "NCW"), 2: ("NCHW", "OIHW", "NCHW"),
             3: ("NCDHW", "OIDHW", "NCDHW")}
    out = jax.lax.conv_general_dilated(
        data.astype(jnp.int8), weight.astype(jnp.int8),
        window_strides=stride, padding=[(p, p) for p in pad],
        rhs_dilation=dilate, dimension_numbers=specs[ns],
        feature_group_count=attrs["num_group"],
        preferred_element_type=jnp.int32)
    mn_d = jnp.reshape(min_d, ())
    mx_d = jnp.reshape(max_d, ())
    mn_w = jnp.reshape(min_w, ())
    mx_w = jnp.reshape(max_w, ())
    min_o, max_o = _mult_range(mn_d, mx_d, mn_w, mx_w)
    if bias is not None:
        out_level = max_o / _INT32_MAX
        b32 = _bias_to_int32(bias, min_b, max_b, out_level)
        out = out + b32.reshape((1, -1) + (1,) * ns)
    return _t(out, min_o, max_o)


def _qlinear_inputs(attrs):
    """Input names shared by quantized conv and FC (quantized_conv.cc:120,
    quantized_fully_connected.cc:95): data/weight[/bias] + their ranges."""
    if attrs["no_bias"]:
        return ["data", "weight", "min_data", "max_data", "min_weight",
                "max_weight"]
    return ["data", "weight", "bias", "min_data", "max_data", "min_weight",
            "max_weight", "min_bias", "max_bias"]


_qconv_inputs = _qlinear_inputs


def _qconv_infer(attrs, in_shapes):
    from .nn import _conv_out_dim
    ds = in_shapes[0]
    if ds is None:
        return in_shapes, [None, (1,), (1,)]
    nf = attrs["num_filter"]
    k = attrs["kernel"]
    ns = len(k)
    stride = tuple(attrs["stride"] or (1,) * ns)
    dilate = tuple(attrs["dilate"] or (1,) * ns)
    pad = tuple(attrs["pad"] or (0,) * ns)
    in_shapes = list(in_shapes)
    if in_shapes[1] is None:
        in_shapes[1] = (nf, ds[1] // attrs["num_group"]) + tuple(k)
    names = _qconv_inputs(attrs)
    for i, nm in enumerate(names):
        if i >= 2 and in_shapes[i] is None:
            in_shapes[i] = (nf,) if nm == "bias" else (1,)
    spatial = tuple(_conv_out_dim(d, kk, s, p, dl) for d, kk, s, p, dl in
                    zip(ds[2:], k, stride, pad, dilate))
    return in_shapes, [(ds[0], nf) + spatial, (1,), (1,)]


_qconv_schema = register(
    "_contrib_quantized_conv", _quantized_conv,
    params={"kernel": Param("shape", None, True),
            "stride": Param("shape", None),
            "dilate": Param("shape", None),
            "pad": Param("shape", None),
            "num_filter": Param("int", None, True),
            "num_group": Param("int", 1),
            "no_bias": Param("bool", False),
            "workspace": Param("int", 1024),
            "cudnn_tune": Param("str", None),
            "cudnn_off": Param("bool", False),
            "layout": Param("str", None)},
    inputs=("data", "weight", "bias", "min_data", "max_data", "min_weight",
            "max_weight", "min_bias", "max_bias"),
    num_outputs=3, infer_shape=_qconv_infer,
    infer_type=lambda attrs, in_types: ["int32", "float32", "float32"])
_qconv_schema.list_inputs = _qconv_inputs  # type: ignore
_qconv_schema.num_inputs = lambda attrs: len(_qconv_inputs(attrs))  # type: ignore


def _quantized_fc(attrs, octx, data, weight, *rest):
    no_bias = attrs["no_bias"]
    if no_bias:
        bias = None
        min_d, max_d, min_w, max_w = rest
    else:
        bias, min_d, max_d, min_w, max_w, min_b, max_b = rest
    x = data.reshape(data.shape[0], -1) if attrs["flatten"] else data
    out = jax.lax.dot_general(
        x.astype(jnp.int8), weight.astype(jnp.int8),
        (((x.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32)
    min_o, max_o = _mult_range(jnp.reshape(min_d, ()), jnp.reshape(max_d, ()),
                               jnp.reshape(min_w, ()), jnp.reshape(max_w, ()))
    if bias is not None:
        out_level = max_o / _INT32_MAX
        b32 = _bias_to_int32(bias, min_b, max_b, out_level)
        out = out + b32
    return _t(out, min_o, max_o)


_qfc_inputs = _qlinear_inputs


def _qfc_infer(attrs, in_shapes):
    ds = in_shapes[0]
    nh = attrs["num_hidden"]
    if ds is None:
        return in_shapes, [None, (1,), (1,)]
    in_shapes = list(in_shapes)
    if in_shapes[1] is None:
        in_dim = 1
        for d in ds[1:]:
            in_dim *= d
        in_shapes[1] = (nh, in_dim if attrs["flatten"] else ds[-1])
    names = _qfc_inputs(attrs)
    for i, nm in enumerate(names):
        if i >= 2 and in_shapes[i] is None:
            in_shapes[i] = (nh,) if nm == "bias" else (1,)
    out = (ds[0], nh) if attrs["flatten"] else tuple(ds[:-1]) + (nh,)
    return in_shapes, [out, (1,), (1,)]


_qfc_schema = register(
    "_contrib_quantized_fully_connected", _quantized_fc,
    params={"num_hidden": Param("int", None, True),
            "no_bias": Param("bool", False),
            "flatten": Param("bool", True)},
    inputs=("data", "weight", "bias", "min_data", "max_data", "min_weight",
            "max_weight", "min_bias", "max_bias"),
    num_outputs=3, infer_shape=_qfc_infer,
    infer_type=lambda attrs, in_types: ["int32", "float32", "float32"])
_qfc_schema.list_inputs = _qfc_inputs  # type: ignore
_qfc_schema.num_inputs = lambda attrs: len(_qfc_inputs(attrs))  # type: ignore


def _quantized_pooling(attrs, octx, data, min_data, max_data):
    from .nn import _pooling
    # pool in int32, return to int8: max-pool is exact; avg-pool rounds
    f = _pooling(attrs, octx, data.astype(jnp.float32))[0]
    if attrs["pool_type"] == "avg":
        f = jnp.round(f)
    q = jnp.clip(f, -127, 127).astype(jnp.int8)
    return _t(q, jnp.reshape(min_data, ()), jnp.reshape(max_data, ()))


def _qpool_infer(attrs, in_shapes):
    from .nn import _pool_infer
    ds = in_shapes[0]
    if ds is None:
        return in_shapes, [None, (1,), (1,)]
    _, outs = _pool_infer(attrs, [ds])
    return [ds, (1,), (1,)], [outs[0], (1,), (1,)]


register("_contrib_quantized_pooling", _quantized_pooling,
         params={"kernel": Param("shape", ()),
                 "pool_type": Param("str", "max"),
                 "global_pool": Param("bool", False),
                 "stride": Param("shape", None),
                 "pad": Param("shape", None),
                 "pooling_convention": Param("str", "valid"),
                 "count_include_pad": Param("bool", True),
                 "cudnn_off": Param("bool", False)},
         inputs=("data", "min_data", "max_data"), num_outputs=3,
         infer_shape=_qpool_infer,
         infer_type=lambda attrs, in_types: ["int8", "float32", "float32"])


def _quantized_flatten(attrs, octx, data, min_data, max_data):
    return _t(data.reshape(data.shape[0], -1), jnp.reshape(min_data, ()),
              jnp.reshape(max_data, ()))


def _qflatten_infer(attrs, in_shapes):
    ds = in_shapes[0]
    if ds is None:
        return in_shapes, [None, (1,), (1,)]
    flat = 1
    for d in ds[1:]:
        flat *= d
    return [ds, (1,), (1,)], [(ds[0], flat), (1,), (1,)]


register("_contrib_quantized_flatten", _quantized_flatten,
         inputs=("data", "min_data", "max_data"), num_outputs=3,
         infer_shape=_qflatten_infer,
         infer_type=lambda attrs, in_types: [in_types[0], "float32",
                                             "float32"])


# -- weight-only quantization (decode/serving bandwidth path) ---------------
#
# The ops above mirror the reference's activation+weight int8 graph rewrite
# (int8 x int8 -> int32 on the MXU). Decode serving wants something simpler
# and strictly bandwidth-motivated: weights stored narrow (int8 / fp8
# e4m3), activations left in bf16/fp32, dequant fused INTO the matmul so
# the wide weight tensor never exists in HBM. Per-OUTPUT-channel symmetric
# scales keep the error per channel; because the scale is constant along
# the contraction axis it factors out of the dot —
#     x @ (q * s[None, :]) == (x @ q_wide) * s
# — which is exactly the algebra both consumers below rely on.

_WEIGHT_QDTYPES = ("int8", "fp8")


def _fp8_dtype():
    """float8_e4m3fn when this jax build has it (e4m3: decode wants the
    mantissa, matching parallel/zero.py's wire-dtype choice); None
    disables the fp8 lane rather than silently aliasing to bf16 — a
    "quantized" artifact must actually be narrow."""
    return getattr(jnp, "float8_e4m3fn", None)


def quantize_rows(w, dtype="int8"):
    """Per-output-channel symmetric weight quantization.

    w: (..., K, N) float array; the LAST axis is the output-feature axis.
    Returns (q, scale): q is int8 (or fp8 e4m3) with the same shape,
    scale is (N,) float32 with w ~= q.astype(f32) * scale. Channels that
    are entirely zero get scale 1.0 (q is zero there either way).
    """
    w = _np.asarray(w, _np.float32)
    if w.ndim < 2:
        raise MXNetError("quantize_rows: need a matrix (ndim >= 2), got "
                         f"shape {w.shape}")
    amax = _np.max(_np.abs(w), axis=tuple(range(w.ndim - 1)))
    if dtype == "int8":
        scale = _np.where(amax > 0, amax / 127.0, 1.0).astype(_np.float32)
        q = _np.clip(_np.rint(w / scale), -127, 127).astype(_np.int8)
    elif dtype == "fp8":
        f8 = _fp8_dtype()
        if f8 is None:
            raise MXNetError("quantize_rows: this jax build has no "
                             "float8_e4m3fn — use dtype='int8'")
        # e4m3fn max finite value is 448
        scale = _np.where(amax > 0, amax / 448.0, 1.0).astype(_np.float32)
        q = _np.asarray(jnp.asarray(w / scale).astype(f8))
    else:
        raise MXNetError(f"quantize_rows: dtype must be one of "
                         f"{_WEIGHT_QDTYPES}, got {dtype!r}")
    return q, scale


def dequantize_rows(q, scale):
    """Inverse of quantize_rows (the oracle the fused matmul is tested
    against): wide float32 weights."""
    return _np.asarray(q, _np.float32) * _np.asarray(scale, _np.float32)


def _qmm_kernel(x_ref, q_ref, s_ref, o_ref, *, block_k, k_dim):
    """One (m-block, n-block) grid cell of the fused quantized matmul:
    stream K-blocks of the NARROW weight, widen in VMEM, MXU dot with
    fp32 accumulation, one per-channel scale multiply at the end (the
    scale factors out of the contraction)."""
    acc0 = jnp.zeros((x_ref.shape[0], o_ref.shape[1]), jnp.float32)
    n_blocks = k_dim // block_k

    def body(i, acc):
        import jax.experimental.pallas as pl
        xk = x_ref[:, pl.dslice(i * block_k, block_k)]
        qk = q_ref[pl.dslice(i * block_k, block_k), :]
        return acc + jax.lax.dot_general(
            xk, qk.astype(xk.dtype), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    acc = jax.lax.fori_loop(0, n_blocks, body, acc0)
    o_ref[:] = (acc * s_ref[:].astype(jnp.float32)).astype(o_ref.dtype)


def _qmm_block(dim, prefs=(256, 128, 8)):
    for blk in prefs:
        if dim % blk == 0:
            return blk
    return dim


def _qmm_pallas(x, q, scale, interpret=False):
    import functools
    import jax.experimental.pallas as pl
    m, k = x.shape
    n = q.shape[1]
    block_m = _qmm_block(m)
    block_n = _qmm_block(n, (512, 256, 128))
    block_k = _qmm_block(k, (512, 256, 128))
    kernel = functools.partial(_qmm_kernel, block_k=block_k, k_dim=k)
    return pl.pallas_call(
        kernel,
        grid=(m // block_m, n // block_n),
        in_specs=[
            pl.BlockSpec((block_m, k), lambda mi, ni: (mi, 0)),
            pl.BlockSpec((k, block_n), lambda mi, ni: (0, ni)),
            pl.BlockSpec((1, block_n), lambda mi, ni: (0, ni)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n),
                               lambda mi, ni: (mi, ni)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        interpret=interpret,
    )(x, q, scale.reshape(1, n))


def _qmm_eligible(x, q, platform=None):
    if x.ndim != 2 or q.ndim != 2:
        return False
    m, k = x.shape
    n = q.shape[1]
    if k % 128 or n % 128:
        return False
    if platform is not None:
        return platform == "tpu"
    return jax.default_backend() == "tpu"


def quantized_matmul(x, q, scale, force=None, platform=None):
    """x @ dequant(q, scale) without materializing the wide weight.

    x: (..., K) activations (bf16/f32); q: (K, N) int8 or fp8 weights;
    scale: (N,) per-output-channel float32. On TPU (tile-friendly K/N)
    a Pallas kernel widens weight blocks in VMEM and fuses the scale
    into the epilogue; elsewhere the XLA spelling
    ``dot(x, q.astype(x.dtype)) * scale`` is used — XLA fuses the
    narrow->wide convert into the dot fusion, so the HLO still reads the
    s8/f8 buffer (hloaudit's fit_decode audit pins this).

    force: None (auto) | 'pallas' | 'xla' | 'interpret'.
    """
    if q.ndim != 2 or x.shape[-1] != q.shape[0]:
        raise MXNetError(f"quantized_matmul: x {x.shape} @ q {q.shape}")
    lead = x.shape[:-1]
    x2 = x.reshape((-1, x.shape[-1]))
    use_pallas = (force in ("pallas", "interpret") or
                  (force is None and _qmm_eligible(x2, q, platform)))
    if use_pallas:
        out = _qmm_pallas(x2, q, scale, interpret=force == "interpret")
    else:
        out = jax.lax.dot_general(
            x2, q.astype(x.dtype), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        out = (out * scale.astype(jnp.float32)).astype(x.dtype)
    return out.reshape(lead + (q.shape[1],))
