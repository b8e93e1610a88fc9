"""Neural-network operators.

Parity target: src/operator/nn/ + legacy top-level ops (SURVEY.md §2.2 —
Convolution, Deconvolution, FullyConnected, BatchNorm, LayerNorm, LRN, Pooling,
Activation, softmax, Dropout, Embedding, UpSampling, SoftmaxOutput,
*RegressionOutput, MakeLoss, SequenceMask/Last/Reverse, InstanceNorm,
L2Normalization, LeakyReLU). All map onto XLA HLO (conv_general_dilated,
reduce_window, dot_general) so the MXU does the FLOPs; no cuDNN/mkldnn-style
per-backend kernels are needed. Ops whose reference *backward* differs from
the mathematical vjp of their forward (SoftmaxOutput & friends — their grad is
defined through the implied loss) use jax.custom_vjp.

Shape inference fills unknown weight shapes from data shapes, reproducing
FInferShape's bidirectional contract that `simple_bind` relies on.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as _np

from ..base import MXNetError
from .registry import Param, register


def _t(*outs):
    return tuple(outs)


def _prod(xs):
    n = 1
    for x in xs:
        n *= x
    return n


# ---------------------------------------------------------------------------
# FullyConnected (src/operator/nn/fully_connected.cc)
# ---------------------------------------------------------------------------

@jax.custom_vjp
def _bias_add_dead_grad(y, b):
    """y + b where d(b) is a structural zero.

    Applied by the executor's dead-bias pass (executor.py:_dead_bias_convs)
    when the op's only consumer is a batch-stats BatchNorm: the BN output is
    invariant to a per-channel shift, so the true bias gradient is exactly
    zero — this just stops XLA from spending a full pass over dy to compute
    that zero. Forward is bit-identical to a plain add.
    """
    return y + b


def _bias_add_dead_fwd(y, b):
    return y + b, b  # b is a (C,)-sized vector; kept only for zeros_like


def _bias_add_dead_bwd(b, dy):
    return dy, jnp.zeros_like(b)


_bias_add_dead_grad.defvjp(_bias_add_dead_fwd, _bias_add_dead_bwd)


def _add_bias(attrs, y, bias):
    if attrs.get("__bias_grad_dead__"):
        return _bias_add_dead_grad(y, bias.astype(y.dtype))
    return y + bias.astype(y.dtype)


def _fc(attrs, octx, data, weight, bias=None):
    x = data.reshape(data.shape[0], -1) if attrs["flatten"] else data
    y = jnp.matmul(x, weight.T)  # weight: (num_hidden, in_dim) — MXNet layout
    if not attrs["no_bias"]:
        y = _add_bias(attrs, y, bias)
    return _t(y)


def _fc_infer(attrs, in_shapes):
    ds = in_shapes[0]
    nh = attrs["num_hidden"]
    if ds is not None:
        in_dim = _prod(ds[1:]) if attrs["flatten"] else ds[-1]
        if in_shapes[1] is None:
            in_shapes = list(in_shapes)
            in_shapes[1] = (nh, in_dim)
    if not attrs["no_bias"] and len(in_shapes) > 2 and in_shapes[2] is None:
        in_shapes = list(in_shapes)
        in_shapes[2] = (nh,)
    if ds is None:
        return in_shapes, [None]
    out = (ds[0], nh) if attrs["flatten"] else tuple(ds[:-1]) + (nh,)
    return in_shapes, [out]


def _fc_inputs(attrs):
    return ["data", "weight"] if attrs["no_bias"] else ["data", "weight", "bias"]


_fc_schema = register(
    "FullyConnected", _fc,
    params={"num_hidden": Param("int", None, True),
            "no_bias": Param("bool", False),
            "flatten": Param("bool", True)},
    inputs=("data", "weight", "bias"), infer_shape=_fc_infer)
_fc_schema.list_inputs = _fc_inputs  # type: ignore[method-assign]
_fc_schema.num_inputs = lambda attrs: 2 if attrs["no_bias"] else 3  # type: ignore

# ---------------------------------------------------------------------------
# Convolution / Deconvolution (src/operator/nn/convolution.cc)
# ---------------------------------------------------------------------------

_CONV_SPECS = {1: ("NCW", "OIW", "NCW"),
               2: ("NCHW", "OIHW", "NCHW"),
               3: ("NCDHW", "OIDHW", "NCDHW")}


def _conv_attrs(attrs, nspatial):
    k = attrs["kernel"]
    stride = attrs["stride"] or (1,) * nspatial
    dilate = attrs["dilate"] or (1,) * nspatial
    pad = attrs["pad"] or (0,) * nspatial
    return k, tuple(stride), tuple(dilate), tuple(pad)


def _conv(attrs, octx, data, weight, bias=None):
    ns = len(attrs["kernel"])
    k, stride, dilate, pad = _conv_attrs(attrs, ns)
    # NOTE: no preferred_element_type=f32 for bf16 inputs — the MXU already
    # accumulates in fp32 internally, and a widened output dtype breaks the
    # conv transpose rule under reverse-mode (f32 cotangent x bf16 weight)
    y = jax.lax.conv_general_dilated(
        data, weight, window_strides=stride,
        padding=[(p, p) for p in pad], rhs_dilation=dilate,
        dimension_numbers=_CONV_SPECS[ns],
        feature_group_count=attrs["num_group"])
    if y.dtype != data.dtype:
        y = y.astype(data.dtype)
    if not attrs["no_bias"]:
        # bias cast at the use site: a fp32 bias must not promote bf16
        # activations (mixed-precision discipline, same as _batch_norm)
        y = _add_bias(attrs, y, bias.reshape((1, -1) + (1,) * ns))
    return _t(y)


def _conv_out_dim(d, k, s, p, dil):
    return (d + 2 * p - (dil * (k - 1) + 1)) // s + 1


def _conv_infer(attrs, in_shapes):
    ds = in_shapes[0]
    nf = attrs["num_filter"]
    ns = len(attrs["kernel"])
    k, stride, dilate, pad = _conv_attrs(attrs, ns)
    in_shapes = list(in_shapes)
    if ds is not None and in_shapes[1] is None:
        in_shapes[1] = (nf, ds[1] // attrs["num_group"]) + tuple(k)
    if not attrs["no_bias"] and len(in_shapes) > 2 and in_shapes[2] is None:
        in_shapes[2] = (nf,)
    if ds is None:
        return in_shapes, [None]
    spatial = tuple(_conv_out_dim(ds[2 + i], k[i], stride[i], pad[i], dilate[i])
                    for i in range(ns))
    return in_shapes, [(ds[0], nf) + spatial]


_conv_params = {"kernel": Param("shape", None, True),
                "stride": Param("shape", None),
                "dilate": Param("shape", None),
                "pad": Param("shape", None),
                "num_filter": Param("int", None, True),
                "num_group": Param("int", 1),
                "no_bias": Param("bool", False),
                "workspace": Param("int", 1024),
                "cudnn_tune": Param("str", None),
                "cudnn_off": Param("bool", False),
                "layout": Param("str", None)}

_conv_schema = register("Convolution", _conv, params=dict(_conv_params),
                        inputs=("data", "weight", "bias"),
                        infer_shape=_conv_infer)
_conv_schema.list_inputs = _fc_inputs  # type: ignore
_conv_schema.num_inputs = lambda attrs: 2 if attrs["no_bias"] else 3  # type: ignore


def _deconv(attrs, octx, data, weight, bias=None):
    ns = len(attrs["kernel"])
    k, stride, dilate, pad = _conv_attrs(attrs, ns)
    adj = attrs["adj"] or (0,) * ns
    # Deconvolution == gradient of Convolution w.r.t. its input. Weight layout
    # is (in_channels, num_filter/num_group, *kernel) (deconvolution-inl.h).
    g = attrs["num_group"]
    # transposed conv via lhs dilation
    pads = []
    for i in range(ns):
        eff_k = dilate[i] * (k[i] - 1) + 1
        lo = eff_k - 1 - pad[i]
        hi = eff_k - 1 - pad[i] + adj[i]
        pads.append((lo, hi))
    # weight (Cin, Cout/g, *k) -> flip spatial, swap to (Cout, Cin/g, *k)
    w = jnp.flip(weight, axis=tuple(range(2, 2 + ns)))
    if g == 1:
        w = jnp.swapaxes(w, 0, 1)
    else:
        cin = weight.shape[0]
        w = w.reshape((g, cin // g) + w.shape[1:])
        w = jnp.swapaxes(w, 1, 2)
        w = w.reshape((-1, cin // g) + tuple(k))
    y = jax.lax.conv_general_dilated(
        data, w, window_strides=(1,) * ns, padding=pads,
        lhs_dilation=stride, rhs_dilation=dilate,
        dimension_numbers=_CONV_SPECS[ns], feature_group_count=g)
    if not attrs["no_bias"]:
        y = y + bias.reshape((1, -1) + (1,) * ns).astype(y.dtype)
    return _t(y)


def _deconv_infer(attrs, in_shapes):
    ds = in_shapes[0]
    nf = attrs["num_filter"]
    ns = len(attrs["kernel"])
    k, stride, dilate, pad = _conv_attrs(attrs, ns)
    adj = attrs["adj"] or (0,) * ns
    in_shapes = list(in_shapes)
    if ds is not None and in_shapes[1] is None:
        in_shapes[1] = (ds[1], nf // attrs["num_group"]) + tuple(k)
    if not attrs["no_bias"] and len(in_shapes) > 2 and in_shapes[2] is None:
        in_shapes[2] = (nf,)
    if ds is None:
        return in_shapes, [None]
    spatial = tuple(
        stride[i] * (ds[2 + i] - 1) + dilate[i] * (k[i] - 1) + 1
        - 2 * pad[i] + adj[i]
        for i in range(ns))
    return in_shapes, [(ds[0], nf) + spatial]


_deconv_params = dict(_conv_params)
_deconv_params["adj"] = Param("shape", None)
_deconv_params["target_shape"] = Param("shape", None)
_deconv_schema = register("Deconvolution", _deconv, params=_deconv_params,
                          inputs=("data", "weight", "bias"),
                          infer_shape=_deconv_infer)
_deconv_schema.list_inputs = _fc_inputs  # type: ignore
_deconv_schema.num_inputs = lambda attrs: 2 if attrs["no_bias"] else 3  # type: ignore

# ---------------------------------------------------------------------------
# Pooling (src/operator/nn/pooling.cc)
# ---------------------------------------------------------------------------

def _pooling(attrs, octx, data):
    ptype = attrs["pool_type"]
    ns = data.ndim - 2
    if attrs["global_pool"]:
        axes = tuple(range(2, data.ndim))
        red = {"max": jnp.max, "avg": jnp.mean, "sum": jnp.sum}[ptype]
        y = red(data, axis=axes, keepdims=True)
        return _t(y)
    k = attrs["kernel"]
    stride = tuple(attrs["stride"] or (1,) * ns)
    pad = tuple(attrs["pad"] or (0,) * ns)
    window = (1, 1) + tuple(k)
    strides = (1, 1) + stride
    pads = [(0, 0), (0, 0)] + [(p, p) for p in pad]
    if attrs["pooling_convention"] == "full":
        # ceil-mode output: widen right pad so the last partial window counts
        for i in range(ns):
            d = data.shape[2 + i]
            out_full = -(-(d + 2 * pad[i] - k[i]) // stride[i]) + 1
            span = (out_full - 1) * stride[i] + k[i]
            extra = max(0, span - (d + 2 * pad[i]))
            pads[2 + i] = (pad[i], pad[i] + extra)
    if ptype == "max":
        # init must stay a python scalar: a traced-array init defeats jax's
        # reduce_window monoid recognition and kills reverse-mode autodiff
        init = -jnp.inf if jnp.issubdtype(data.dtype, jnp.floating) else \
            int(jnp.iinfo(data.dtype).min)
        y = jax.lax.reduce_window(data, init,
                                  jax.lax.max, window, strides, pads)
    else:
        zero = 0.0 if jnp.issubdtype(data.dtype, jnp.floating) else 0
        y = jax.lax.reduce_window(data, zero,
                                  jax.lax.add, window, strides, pads)
        if ptype == "avg":
            if attrs["count_include_pad"]:
                y = y / _prod(k)
            else:
                ones = jnp.ones(data.shape, dtype=data.dtype)
                cnt = jax.lax.reduce_window(ones, zero,
                                            jax.lax.add, window, strides, pads)
                y = y / cnt
    return _t(y)


def _pool_infer(attrs, in_shapes):
    ds = in_shapes[0]
    if ds is None:
        return in_shapes, [None]
    if attrs["global_pool"]:
        return in_shapes, [tuple(ds[:2]) + (1,) * (len(ds) - 2)]
    ns = len(ds) - 2
    k = attrs["kernel"]
    stride = tuple(attrs["stride"] or (1,) * ns)
    pad = tuple(attrs["pad"] or (0,) * ns)
    out = []
    for i in range(ns):
        if attrs["pooling_convention"] == "full":
            out.append(-(-(ds[2 + i] + 2 * pad[i] - k[i]) // stride[i]) + 1)
        else:
            out.append((ds[2 + i] + 2 * pad[i] - k[i]) // stride[i] + 1)
    return in_shapes, [tuple(ds[:2]) + tuple(out)]


register("Pooling", _pooling,
         params={"kernel": Param("shape", ()),
                 "pool_type": Param("str", "max"),
                 "global_pool": Param("bool", False),
                 "stride": Param("shape", None),
                 "pad": Param("shape", None),
                 "pooling_convention": Param("str", "valid"),
                 "count_include_pad": Param("bool", True),
                 "cudnn_off": Param("bool", False)},
         infer_shape=_pool_infer)

# ---------------------------------------------------------------------------
# Activations
# ---------------------------------------------------------------------------

def _activation(attrs, octx, x):
    t = attrs["act_type"]
    if t == "relu":
        return _t(jnp.maximum(x, 0))
    if t == "sigmoid":
        return _t(jax.nn.sigmoid(x))
    if t == "tanh":
        return _t(jnp.tanh(x))
    if t == "softrelu":
        return _t(jax.nn.softplus(x))
    if t == "softsign":
        return _t(x / (1 + jnp.abs(x)))
    raise MXNetError(f"Activation: unknown act_type {t}")


def _same1(attrs, in_shapes):
    return in_shapes, [in_shapes[0]]

register("Activation", _activation,
         params={"act_type": Param("str", None, True)}, infer_shape=_same1)


def _leaky_relu(attrs, octx, *inputs):
    t = attrs["act_type"]
    x = inputs[0]
    slope = attrs["slope"]
    if t == "leaky":
        return _t(jnp.where(x > 0, x, slope * x))
    if t == "elu":
        return _t(jnp.where(x > 0, x, slope * (jnp.exp(x) - 1)))
    if t == "prelu":
        gamma = inputs[1]
        g = gamma.reshape((1, -1) + (1,) * (x.ndim - 2)) if x.ndim > 1 else gamma
        return _t(jnp.where(x > 0, x, g * x))
    if t == "rrelu":
        lo, hi = attrs["lower_bound"], attrs["upper_bound"]
        if octx.is_train and octx.rng is not None:
            a = jax.random.uniform(octx.rng, x.shape, dtype=x.dtype,
                                   minval=lo, maxval=hi)
        else:
            a = (lo + hi) / 2.0
        return _t(jnp.where(x > 0, x, a * x))
    if t == "gelu":
        return _t(jax.nn.gelu(x))
    raise MXNetError(f"LeakyReLU: unknown act_type {t}")


def _lrelu_infer(attrs, in_shapes):
    in_shapes = list(in_shapes)
    if attrs["act_type"] == "prelu" and len(in_shapes) > 1 and \
            in_shapes[1] is None and in_shapes[0] is not None:
        in_shapes[1] = (in_shapes[0][1],)
    return in_shapes, [in_shapes[0]]


_lrelu_schema = register(
    "LeakyReLU", _leaky_relu,
    params={"act_type": Param("str", "leaky"),
            "slope": Param("float", 0.25),
            "lower_bound": Param("float", 0.125),
            "upper_bound": Param("float", 0.334)},
    inputs=("data", "gamma"), needs_rng=True, infer_shape=_lrelu_infer)
_lrelu_schema.num_inputs = lambda a: 2 if a["act_type"] == "prelu" else 1  # type: ignore
_lrelu_schema.list_inputs = lambda a: (["data", "gamma"]  # type: ignore
                                       if a["act_type"] == "prelu" else ["data"])

# ---------------------------------------------------------------------------
# softmax family (src/operator/nn/softmax.cc)
# ---------------------------------------------------------------------------

def _softmax(attrs, octx, x):
    z = x / attrs["temperature"] if attrs["temperature"] != 1.0 else x
    return _t(jax.nn.softmax(z, axis=attrs["axis"]))

register("softmax", _softmax,
         params={"axis": Param("int", -1), "temperature": Param("float", 1.0)},
         infer_shape=_same1)


def _log_softmax(attrs, octx, x):
    z = x / attrs["temperature"] if attrs["temperature"] != 1.0 else x
    return _t(jax.nn.log_softmax(z, axis=attrs["axis"]))

register("log_softmax", _log_softmax,
         params={"axis": Param("int", -1), "temperature": Param("float", 1.0)},
         infer_shape=_same1)


def _softmax_activation(attrs, octx, x):
    if attrs["mode"] == "channel":
        return _t(jax.nn.softmax(x, axis=1))
    return _t(jax.nn.softmax(x.reshape(x.shape[0], -1), axis=-1).reshape(x.shape))

register("SoftmaxActivation", _softmax_activation,
         params={"mode": Param("str", "instance")}, infer_shape=_same1)


# SoftmaxOutput: forward=softmax, backward=(p - onehot(label)) scaled — the
# reference defines the grad through the implied CE loss
# (src/operator/softmax_output-inl.h). custom_vjp reproduces that contract.

def _softmax_output(attrs, octx, data, label):
    grad_scale = attrs["grad_scale"]
    ignore_label = attrs["ignore_label"]
    use_ignore = attrs["use_ignore"]
    multi_output = attrs["multi_output"]
    preserve_shape = attrs["preserve_shape"]
    normalization = attrs["normalization"]
    smooth_alpha = attrs["smooth_alpha"]

    axis = 1 if multi_output else -1
    if not multi_output and not preserve_shape and data.ndim > 2:
        pass  # softmax over trailing axis of flattened rows == last axis

    @jax.custom_vjp
    def _fn(d, lbl):
        return jax.nn.softmax(d, axis=axis)

    def _fwd(d, lbl):
        out = jax.nn.softmax(d, axis=axis)
        return out, (out, lbl)

    def _bwd(res, g):
        out, lbl = res
        nclass = out.shape[axis]
        if lbl.shape == out.shape:
            tgt = lbl
            valid = jnp.ones(lbl.shape[:1], dtype=out.dtype)
        else:
            li = lbl.astype(jnp.int32)
            oh = jax.nn.one_hot(li, nclass, dtype=out.dtype)
            if multi_output:
                # label (n, d...) -> one_hot gives (n, d..., c); move c to axis 1
                oh = jnp.moveaxis(oh, -1, 1)
            tgt = oh
            if smooth_alpha:
                tgt = tgt * (1 - smooth_alpha) + smooth_alpha / (nclass - 1) * (1 - tgt)
            valid = jnp.ones(li.shape, dtype=out.dtype)
            if use_ignore:
                mask = (li != int(ignore_label)).astype(out.dtype)
                valid = mask
                if multi_output:
                    tgt = tgt * jnp.expand_dims(mask, 1)
                    out_m = out * jnp.expand_dims(mask, 1)
                else:
                    tgt = tgt * mask[..., None]
                    out_m = out * mask[..., None]
            else:
                out_m = out
        if not use_ignore:
            out_m = out
        grad = out_m - tgt
        if normalization == "batch":
            grad = grad / out.shape[0]
        elif normalization == "valid":
            grad = grad / jnp.maximum(jnp.sum(valid), 1.0)
        grad = grad * grad_scale
        return grad.astype(out.dtype), jnp.zeros_like(lbl)

    _fn.defvjp(_fwd, _bwd)
    return _t(_fn(data, label))


def _softmax_output_infer(attrs, in_shapes):
    ds = in_shapes[0]
    in_shapes = list(in_shapes)
    if ds is not None and in_shapes[1] is None:
        if attrs["multi_output"]:
            in_shapes[1] = (ds[0],) + tuple(ds[2:])
        else:
            in_shapes[1] = tuple(ds[:-1])
    return in_shapes, [ds]


register("SoftmaxOutput", _softmax_output,
         params={"grad_scale": Param("float", 1.0),
                 "ignore_label": Param("float", -1.0),
                 "use_ignore": Param("bool", False),
                 "multi_output": Param("bool", False),
                 "preserve_shape": Param("bool", False),
                 "normalization": Param("str", "null"),
                 "out_grad": Param("bool", False),
                 "smooth_alpha": Param("float", 0.0)},
         inputs=("data", "label"), aliases=("Softmax",),
         infer_shape=_softmax_output_infer)

# ---------------------------------------------------------------------------
# Normalization layers
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _bn_train(data, gamma, beta, axis, eps, fix_gamma, relu):
    """Training-mode BN core: returns (out, batch_mean, batch_var).

    Hand-written vjp to save HBM passes:
    the backward does the minimal two passes (one for the dgamma/dbeta
    sums, one for dx) instead of autodiff's mean->var dependency chain.
    Stats accumulate in fp32 regardless of the activation dtype (stable
    two-pass variance — see _bn_stats). `relu` folds a following
    Activation('relu') node into the kernel (executor BN+ReLU fusion pass):
    the backward masks dy inline instead of paying a separate full
    read+write pass over the activation tensor.
    """
    return _bn_train_fwd(data, gamma, beta, axis, eps, fix_gamma, relu)[0]


def _bn_stats(data, red_axes):
    # two-pass variance (mean first, then E[(x-mean)^2]) — the one-pass
    # E[x^2]-mean^2 form cancels catastrophically when |mean| >> std
    # (measured: fp32 data with mean 1e3/std 1e-2 yields var=-0.19 -> NaN
    # through rsqrt; the reference's CPU BN is two-pass for the same
    # reason). Costs ~4% ResNet-50 step time vs one-pass; correctness wins.
    m = jnp.mean(data, axis=red_axes, dtype=jnp.float32)
    bshape = tuple(1 if i in red_axes else s
                   for i, s in enumerate(data.shape))
    d = data.astype(jnp.float32) - m.reshape(bshape)
    return m, jnp.mean(jax.lax.square(d), axis=red_axes)


def _bn_train_fwd(data, gamma, beta, axis, eps, fix_gamma, relu):
    red_axes = tuple(i for i in range(data.ndim) if i != axis)
    bshape = tuple(data.shape[axis] if i == axis else 1
                   for i in range(data.ndim))
    mean, var = _bn_stats(data, red_axes)
    rstd = jax.lax.rsqrt(var + eps)
    g = jnp.ones_like(gamma) if fix_gamma else gamma
    scale = (g.astype(jnp.float32) * rstd).astype(data.dtype)
    shift = (beta.astype(jnp.float32)
             - mean * g.astype(jnp.float32) * rstd).astype(data.dtype)
    out = data * scale.reshape(bshape) + shift.reshape(bshape)
    if relu:
        out = jnp.maximum(out, 0)
    return (out, mean, var), (data, gamma, beta, mean, rstd)


def _bn_train_bwd(axis, eps, fix_gamma, relu, res, cts):
    # cotangents for the mean/var outputs are ignored: callers feed them
    # only into the stop-gradient EMA update, so they are exact zeros
    data, gamma, beta, mean, rstd = res
    dy = cts[0]
    red_axes = tuple(i for i in range(data.ndim) if i != axis)
    bshape = tuple(data.shape[axis] if i == axis else 1
                   for i in range(data.ndim))
    n = _prod(data.shape[i] for i in red_axes)
    xhat = (data - mean.reshape(bshape).astype(data.dtype)) \
        * rstd.reshape(bshape).astype(data.dtype)
    if relu:
        # recompute the relu mask from xhat (cheaper than saving `out`:
        # out > 0 <=> g*xhat + beta > 0, all in-registers here).
        # Accepted tradeoff: g*xhat + beta is a different bf16 evaluation
        # order than the forward's data*scale + shift, so an element
        # landing EXACTLY on the relu boundary can round to a different
        # side and flip its mask bit — bounded by one ulp of gradient
        # noise on measure-zero inputs, in exchange for not saving `out`
        g_b = (jnp.ones_like(gamma) if fix_gamma else gamma) \
            .reshape(bshape).astype(data.dtype)
        pre = xhat * g_b + beta.reshape(bshape).astype(data.dtype)
        dy = jnp.where(pre > 0, dy, jnp.zeros((), dy.dtype))
    # pass 1: both channel reductions stream (dy, data) once
    dbeta = jnp.sum(dy, axis=red_axes, dtype=jnp.float32)
    dgamma = jnp.sum(dy * xhat, axis=red_axes, dtype=jnp.float32)
    g = jnp.ones_like(gamma) if fix_gamma else gamma
    coef = (g.astype(jnp.float32) * rstd).reshape(bshape).astype(data.dtype)
    # pass 2: dx from dy, data and the reduced sums
    dx = coef * (dy
                 - (dbeta / n).reshape(bshape).astype(data.dtype)
                 - xhat * (dgamma / n).reshape(bshape).astype(data.dtype))
    dgamma_out = jnp.zeros_like(gamma) if fix_gamma \
        else dgamma.astype(gamma.dtype)
    return dx, dgamma_out, dbeta.astype(gamma.dtype)


_bn_train.defvjp(_bn_train_fwd, _bn_train_bwd)


def _batch_norm(attrs, octx, data, gamma, beta, moving_mean, moving_var):
    eps = attrs["eps"]
    momentum = attrs["momentum"]
    axis = attrs["axis"] % data.ndim
    bshape = tuple(data.shape[axis] if i == axis else 1
                   for i in range(data.ndim))

    fuse_relu = bool(attrs.get("__fuse_relu__", False))
    use_batch = octx.is_train and not attrs["use_global_stats"]
    if use_batch:
        out, mean, var = _bn_train(data, gamma, beta, axis, eps,
                                   bool(attrs["fix_gamma"]), fuse_relu)
        mean = jax.lax.stop_gradient(mean).astype(moving_mean.dtype)
        var = jax.lax.stop_gradient(var).astype(moving_var.dtype)
        new_mean = momentum * moving_mean + (1 - momentum) * mean
        new_var = momentum * moving_var + (1 - momentum) * var
        return (out, new_mean, new_var)
    g = jnp.ones_like(gamma) if attrs["fix_gamma"] else gamma
    mean, var = moving_mean, moving_var
    inv = jax.lax.rsqrt(var.astype(jnp.float32) + eps).astype(data.dtype)
    out = (data - mean.reshape(bshape).astype(data.dtype)) * \
        inv.reshape(bshape) * g.reshape(bshape).astype(data.dtype) + \
        beta.reshape(bshape).astype(data.dtype)
    if fuse_relu:
        out = jnp.maximum(out, 0)
    return (out, mean, var)


def _bn_infer(attrs, in_shapes):
    ds = in_shapes[0]
    in_shapes = list(in_shapes)
    if ds is not None:
        c = (ds[attrs["axis"] % len(ds)],)
        for i in range(1, 5):
            if in_shapes[i] is None:
                in_shapes[i] = c
    return in_shapes, [ds]


register("BatchNorm", _batch_norm,
         params={"eps": Param("float", 1e-3),
                 "momentum": Param("float", 0.9),
                 "fix_gamma": Param("bool", True),
                 "use_global_stats": Param("bool", False),
                 "output_mean_var": Param("bool", False),
                 "axis": Param("int", 1),
                 "cudnn_off": Param("bool", False)},
         inputs=("data", "gamma", "beta", "moving_mean", "moving_var"),
         aux=("moving_mean", "moving_var"), mutates_aux=True,
         infer_shape=_bn_infer, aliases=("BatchNorm_v1",))


def _layer_norm(attrs, octx, data, gamma, beta):
    axis = attrs["axis"] % data.ndim
    mean = jnp.mean(data, axis=axis, keepdims=True)
    var = jnp.var(data, axis=axis, keepdims=True)
    out = (data - mean) * jax.lax.rsqrt(var + attrs["eps"])
    bshape = tuple(data.shape[axis] if i == axis else 1
                   for i in range(data.ndim))
    return _t(out * gamma.reshape(bshape) + beta.reshape(bshape))


def _ln_infer(attrs, in_shapes):
    ds = in_shapes[0]
    in_shapes = list(in_shapes)
    if ds is not None:
        c = (ds[attrs["axis"] % len(ds)],)
        for i in (1, 2):
            if in_shapes[i] is None:
                in_shapes[i] = c
    return in_shapes, [ds]


register("LayerNorm", _layer_norm,
         params={"axis": Param("int", -1), "eps": Param("float", 1e-5),
                 "output_mean_var": Param("bool", False)},
         inputs=("data", "gamma", "beta"), infer_shape=_ln_infer)


def _instance_norm(attrs, octx, data, gamma, beta):
    axes = tuple(range(2, data.ndim))
    mean = jnp.mean(data, axis=axes, keepdims=True)
    var = jnp.var(data, axis=axes, keepdims=True)
    out = (data - mean) * jax.lax.rsqrt(var + attrs["eps"])
    bshape = (1, data.shape[1]) + (1,) * (data.ndim - 2)
    return _t(out * gamma.reshape(bshape) + beta.reshape(bshape))


def _in_infer(attrs, in_shapes):
    ds = in_shapes[0]
    in_shapes = list(in_shapes)
    if ds is not None:
        for i in (1, 2):
            if in_shapes[i] is None:
                in_shapes[i] = (ds[1],)
    return in_shapes, [ds]


register("InstanceNorm", _instance_norm,
         params={"eps": Param("float", 1e-3)},
         inputs=("data", "gamma", "beta"), infer_shape=_in_infer)


def _l2_normalization(attrs, octx, data):
    eps = attrs["eps"]
    mode = attrs["mode"]
    if mode == "instance":
        axes = tuple(range(1, data.ndim))
    elif mode == "channel":
        axes = (1,)
    elif mode == "spatial":
        axes = tuple(range(2, data.ndim))
    else:
        raise MXNetError(f"L2Normalization: unknown mode {mode}")
    norm = jnp.sqrt(jnp.sum(jnp.square(data), axis=axes, keepdims=True) + eps)
    return _t(data / norm)

register("L2Normalization", _l2_normalization,
         params={"eps": Param("float", 1e-10),
                 "mode": Param("str", "instance")}, infer_shape=_same1)


def _lrn(attrs, octx, data):
    n = attrs["nsize"]
    alpha, beta, knorm = attrs["alpha"], attrs["beta"], attrs["knorm"]
    sq = jnp.square(data)
    half = n // 2
    pads = [(0, 0), (half, half)] + [(0, 0)] * (data.ndim - 2)
    acc = jax.lax.reduce_window(sq, 0.0, jax.lax.add,
                                (1, n) + (1,) * (data.ndim - 2),
                                (1,) * data.ndim, pads)
    return _t(data / jnp.power(knorm + (alpha / n) * acc, beta))

register("LRN", _lrn,
         params={"alpha": Param("float", 1e-4), "beta": Param("float", 0.75),
                 "knorm": Param("float", 2.0), "nsize": Param("int", None, True)},
         infer_shape=_same1)

# ---------------------------------------------------------------------------
# Dropout / Embedding / UpSampling
# ---------------------------------------------------------------------------

def _dropout(attrs, octx, x):
    p = attrs["p"]
    mode = attrs["mode"]
    apply_drop = (octx.is_train or mode == "always") and p > 0
    if not apply_drop or octx.rng is None:
        return _t(x)
    keep = 1.0 - p
    mask = jax.random.bernoulli(octx.rng, keep, x.shape)
    return _t(jnp.where(mask, x / keep, 0).astype(x.dtype))

register("Dropout", _dropout,
         params={"p": Param("float", 0.5), "mode": Param("str", "training"),
                 "axes": Param("shape", None)},
         needs_rng=True, infer_shape=_same1)


def _embedding(attrs, octx, data, weight):
    idx = jnp.clip(data.astype(jnp.int32), 0, attrs["input_dim"] - 1)
    return _t(jnp.take(weight, idx, axis=0))


def _embedding_infer(attrs, in_shapes):
    in_shapes = list(in_shapes)
    if in_shapes[1] is None:
        in_shapes[1] = (attrs["input_dim"], attrs["output_dim"])
    ds = in_shapes[0]
    if ds is None:
        return in_shapes, [None]
    return in_shapes, [tuple(ds) + (attrs["output_dim"],)]


register("Embedding", _embedding,
         params={"input_dim": Param("int", None, True),
                 "output_dim": Param("int", None, True),
                 "dtype": Param("dtype", "float32"),
                 "sparse_grad": Param("bool", False)},
         inputs=("data", "weight"), infer_shape=_embedding_infer)


def _upsampling(attrs, octx, *inputs):
    scale = attrs["scale"]
    st = attrs["sample_type"]
    x = inputs[0]
    if st == "nearest":
        y = jnp.repeat(jnp.repeat(x, scale, axis=2), scale, axis=3)
        return _t(y)
    if st == "bilinear":
        n, c, h, w = x.shape
        y = jax.image.resize(x, (n, c, h * scale, w * scale), method="bilinear")
        return _t(y)
    raise MXNetError(f"UpSampling: unknown sample_type {st}")


def _upsampling_infer(attrs, in_shapes):
    ds = in_shapes[0]
    if ds is None:
        return in_shapes, [None]
    s = attrs["scale"]
    return in_shapes, [(ds[0], ds[1], ds[2] * s, ds[3] * s)]


_ups_schema = register("UpSampling", _upsampling,
                       params={"scale": Param("int", None, True),
                               "sample_type": Param("str", None, True),
                               "num_filter": Param("int", 0),
                               "multi_input_mode": Param("str", "concat"),
                               "num_args": Param("int", 1),
                               "workspace": Param("int", 512)},
                       inputs=("data",), key_var_num_args="num_args",
                       infer_shape=_upsampling_infer)

# ---------------------------------------------------------------------------
# loss-layer ops (legacy top-level): custom backward through implied loss
# ---------------------------------------------------------------------------

def _regression_output(name, fwd_fn, grad_fn):
    def fcompute(attrs, octx, data, label):
        gs = attrs["grad_scale"]

        @jax.custom_vjp
        def _fn(d, lbl):
            return fwd_fn(d)

        def _f(d, lbl):
            return fwd_fn(d), (fwd_fn(d), lbl)

        def _b(res, g):
            out, lbl = res
            n = _prod(out.shape[1:])  # reference normalizes by num outputs
            grad = grad_fn(out, lbl) * (gs / n)
            return grad.astype(out.dtype), jnp.zeros_like(lbl)

        _fn.defvjp(_f, _b)
        return _t(_fn(data, label))

    def infer(attrs, in_shapes):
        ds = in_shapes[0]
        in_shapes = list(in_shapes)
        if ds is not None and in_shapes[1] is None:
            in_shapes[1] = ds
        return in_shapes, [ds]

    register(name, fcompute, params={"grad_scale": Param("float", 1.0)},
             inputs=("data", "label"), infer_shape=infer)


_regression_output("LinearRegressionOutput",
                   lambda d: d, lambda o, l: o - l)
_regression_output("LogisticRegressionOutput",
                   jax.nn.sigmoid, lambda o, l: o - l)
_regression_output("MAERegressionOutput",
                   lambda d: d, lambda o, l: jnp.sign(o - l))


def _make_loss_op(attrs, octx, data):
    gs = attrs["grad_scale"]
    norm = attrs["normalization"]
    vt = attrs["valid_thresh"]

    @jax.custom_vjp
    def _fn(d):
        return d

    def _f(d):
        return d, d

    def _b(d, g):
        grad = jnp.full_like(d, gs)
        if norm == "batch":
            grad = grad / d.shape[0]
        elif norm == "valid":
            nv = jnp.maximum(jnp.sum((d > vt).astype(d.dtype)), 1.0)
            grad = grad / nv
        return (grad,)

    _fn.defvjp(_f, _b)
    return _t(_fn(data))

register("MakeLoss", _make_loss_op,
         params={"grad_scale": Param("float", 1.0),
                 "valid_thresh": Param("float", 0.0),
                 "normalization": Param("str", "null")},
         infer_shape=_same1)


def _svm_output(attrs, octx, data, label):
    margin = attrs["margin"]
    coef = attrs["regularization_coefficient"]
    use_linear = attrs["use_linear"]

    @jax.custom_vjp
    def _fn(d, lbl):
        return d

    def _f(d, lbl):
        return d, (d, lbl)

    def _b(res, g):
        d, lbl = res
        oh = jax.nn.one_hot(lbl.astype(jnp.int32), d.shape[-1], dtype=d.dtype)
        # hinge: grad = -coef*label_sign where margin violated
        score_y = jnp.sum(d * oh, axis=-1, keepdims=True)
        if use_linear:
            viol = ((d - score_y + margin) > 0).astype(d.dtype) * (1 - oh)
            grad = coef * (viol - oh * jnp.sum(viol, axis=-1, keepdims=True))
        else:
            viol = jnp.maximum(0.0, d - score_y + margin) * (1 - oh)
            grad = 2 * coef * (viol - oh * jnp.sum(viol, axis=-1, keepdims=True))
        return grad, jnp.zeros_like(lbl)

    _fn.defvjp(_f, _b)
    return _t(_fn(data, label))

register("SVMOutput", _svm_output,
         params={"margin": Param("float", 1.0),
                 "regularization_coefficient": Param("float", 1.0),
                 "use_linear": Param("bool", False)},
         inputs=("data", "label"),
         infer_shape=lambda a, s: (([s[0], (s[0][0],) if s[1] is None and
                                     s[0] is not None else s[1]]), [s[0]]))

# ---------------------------------------------------------------------------
# sequence ops (src/operator/sequence_*.cc)
# ---------------------------------------------------------------------------

def _seq_axes(x):
    # layout: (seq_len, batch, ...) — MXNet sequence ops' default
    return 0, 1


def _sequence_mask(attrs, octx, data, seq_len=None):
    if not attrs["use_sequence_length"] or seq_len is None:
        return _t(data)
    t = data.shape[0]
    steps = jnp.arange(t).reshape((t,) + (1,) * (data.ndim - 1))
    sl = seq_len.reshape((1, -1) + (1,) * (data.ndim - 2))
    mask = steps < sl
    return _t(jnp.where(mask, data, attrs["value"]).astype(data.dtype))


_seqmask_schema = register(
    "SequenceMask", _sequence_mask,
    params={"use_sequence_length": Param("bool", False),
            "value": Param("float", 0.0), "axis": Param("int", 0)},
    inputs=("data", "sequence_length"))
_seqmask_schema.num_inputs = lambda a: 2 if a["use_sequence_length"] else 1  # type: ignore
_seqmask_schema.list_inputs = lambda a: (["data", "sequence_length"]  # type: ignore
                                         if a["use_sequence_length"] else ["data"])


def _sequence_last(attrs, octx, data, seq_len=None):
    if not attrs["use_sequence_length"] or seq_len is None:
        return _t(data[-1])
    idx = (seq_len.astype(jnp.int32) - 1)
    batch = jnp.arange(data.shape[1])
    return _t(data[idx, batch])


_seqlast_schema = register(
    "SequenceLast", _sequence_last,
    params={"use_sequence_length": Param("bool", False),
            "axis": Param("int", 0)},
    inputs=("data", "sequence_length"))
_seqlast_schema.num_inputs = lambda a: 2 if a["use_sequence_length"] else 1  # type: ignore
_seqlast_schema.list_inputs = _seqmask_schema.list_inputs  # type: ignore


def _sequence_reverse(attrs, octx, data, seq_len=None):
    if not attrs["use_sequence_length"] or seq_len is None:
        return _t(jnp.flip(data, axis=0))
    t = data.shape[0]
    steps = jnp.arange(t)[:, None]
    sl = seq_len.astype(jnp.int32)[None, :]
    src = jnp.where(steps < sl, sl - 1 - steps, steps)
    batch = jnp.arange(data.shape[1])[None, :]
    return _t(data[src, batch])


_seqrev_schema = register(
    "SequenceReverse", _sequence_reverse,
    params={"use_sequence_length": Param("bool", False),
            "axis": Param("int", 0)},
    inputs=("data", "sequence_length"))
_seqrev_schema.num_inputs = lambda a: 2 if a["use_sequence_length"] else 1  # type: ignore
_seqrev_schema.list_inputs = _seqmask_schema.list_inputs  # type: ignore
