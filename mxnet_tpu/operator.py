"""Custom operator escape hatch — mx.operator.CustomOp/CustomOpProp.

Parity target: python/mxnet/operator.py (1101 LoC) + the C++ marshalling in
src/operator/custom/custom.cc:103. The reference routes custom-op calls to
frontend python through a dedicated async engine lane (ExecType::kAsync);
here the host round-trip is `jax.pure_callback` — the op traces into any
jitted graph (imperative, CachedOp, Executor) as a host call, and its
backward is wired in with `jax.custom_vjp` calling the user's
`CustomOp.backward` through a second callback. Shapes/dtypes stay static:
`CustomOpProp.infer_shape/infer_type` supply the callback result avals.

Device note: host callbacks need the runtime's host send/recv support;
`tests_tpu/test_tpu_consistency.py::test_custom_op_on_chip` is the check
on the chip.
"""
from __future__ import annotations

import functools

import numpy as _np

from .base import MXNetError
from .ops.registry import Param, register as _register_op

__all__ = ["CustomOp", "CustomOpProp", "register", "get_all_registered"]


class CustomOp:
    """Base class for user forward/backward (operator.py CustomOp)."""

    def forward(self, is_train, req, in_data, out_data, aux):
        raise NotImplementedError

    def backward(self, req, out_grad, in_data, out_data, in_grad, aux):
        raise NotImplementedError

    def assign(self, dst, req, src):
        """Write `src` into `dst` honoring the grad_req."""
        if req in ("null", None):
            return
        if req == "add":
            dst[:] = dst[:] + src if hasattr(dst, "__getitem__") else dst + src
        else:  # write / inplace
            dst[:] = src


class CustomOpProp:
    """Op metadata provider (operator.py CustomOpProp)."""

    def __init__(self, need_top_grad=True):
        self.need_top_grad_ = need_top_grad

    def list_arguments(self):
        return ["data"]

    def list_outputs(self):
        return ["output"]

    def list_auxiliary_states(self):
        return []

    def infer_shape(self, in_shape):
        return in_shape, [in_shape[0]], []

    def infer_type(self, in_type):
        return in_type, [in_type[0]] * len(self.list_outputs()), []

    def declare_backward_dependency(self, out_grad, in_data, out_data):
        deps = []
        if self.need_top_grad_:
            deps.extend(out_grad)
        deps.extend(in_data)
        deps.extend(out_data)
        return deps

    def create_operator(self, ctx, in_shapes, in_dtypes):
        raise NotImplementedError


_PROP_REGISTRY = {}


def register(reg_name):
    """Decorator: mx.operator.register("myop")(MyProp) — afterwards
    `mx.nd.Custom(..., op_type="myop")` and `mx.sym.Custom(...)` work."""

    def do_register(prop_cls):
        if not issubclass(prop_cls, CustomOpProp):
            raise MXNetError("register expects a CustomOpProp subclass")
        _PROP_REGISTRY[reg_name] = prop_cls
        return prop_cls

    return do_register


def get_all_registered():
    return dict(_PROP_REGISTRY)


@functools.lru_cache(maxsize=None)
def _prop_for(op_type, frozen_extra):
    cls = _PROP_REGISTRY.get(op_type)
    if cls is None:
        raise MXNetError(f"Custom op_type {op_type!r} is not registered")
    return cls(**dict(frozen_extra))


def _custom_fcompute(attrs, octx, *inputs):
    import jax
    import jax.numpy as jnp

    op_type = attrs["op_type"]
    extra = tuple(sorted((k, v) for k, v in (attrs.get("_extra") or {})
                         .items()))
    prop = _prop_for(op_type, extra)
    n_args = len(prop.list_arguments())
    n_out = len(prop.list_outputs())
    if prop.list_auxiliary_states():
        raise MXNetError("Custom: auxiliary states are not supported")
    if len(inputs) != n_args:
        raise MXNetError(f"Custom({op_type}): expected {n_args} inputs, "
                         f"got {len(inputs)}")

    in_shapes = [tuple(x.shape) for x in inputs]
    in_dtypes = [_np.dtype(x.dtype) for x in inputs]
    _, out_shapes, _ = prop.infer_shape([list(s) for s in in_shapes])
    _, out_dtypes, _ = prop.infer_type(list(in_dtypes))
    out_avals = tuple(jax.ShapeDtypeStruct(tuple(s), _np.dtype(t))
                      for s, t in zip(out_shapes, out_dtypes))
    is_train = bool(octx.is_train)

    def host_forward(*arrs):
        op = prop.create_operator(None, in_shapes, in_dtypes)
        in_data = [_np.asarray(a) for a in arrs]
        out_data = [_np.zeros(s, t) for s, t in zip(out_shapes, out_dtypes)]
        op.forward(is_train, ["write"] * n_out, in_data, out_data, [])
        return tuple(out_data)

    def host_backward(*arrs):
        # residuals: inputs + the SAME forward outputs produced in fwd (no
        # host re-run; matters for stochastic/stateful user forwards)
        ins = [_np.asarray(a) for a in arrs[:n_args]]
        outs = [_np.asarray(a) for a in arrs[n_args:n_args + n_out]]
        cts = [_np.asarray(a) for a in arrs[n_args + n_out:]]
        op = prop.create_operator(None, in_shapes, in_dtypes)
        in_grad = [_np.zeros(s, t) for s, t in zip(in_shapes, in_dtypes)]
        op.backward(["write"] * n_args, cts, ins, outs, in_grad, [])
        return tuple(in_grad)

    in_avals = tuple(jax.ShapeDtypeStruct(s, t)
                     for s, t in zip(in_shapes, in_dtypes))

    @jax.custom_vjp
    def run(*ins):
        return jax.pure_callback(host_forward, out_avals, *ins)

    def fwd(*ins):
        outs = run(*ins)
        return outs, (ins, outs)

    def bwd(saved, cts):
        ins, outs = saved
        grads = jax.pure_callback(host_backward, in_avals, *ins, *outs,
                                  *cts)
        return tuple(grads)

    run.defvjp(fwd, bwd)
    return tuple(run(*inputs))


def _custom_infer_shape(attrs, in_shapes):
    prop = _prop_for(attrs["op_type"],
                     tuple(sorted((k, v) for k, v in
                                  (attrs.get("_extra") or {}).items())))
    if any(s is None for s in in_shapes):
        return in_shapes, [None] * len(prop.list_outputs())
    ins, outs, _ = prop.infer_shape([list(s) for s in in_shapes])
    return [tuple(s) for s in ins], [tuple(s) for s in outs]


def _custom_list_inputs(attrs):
    prop = _prop_for(attrs["op_type"],
                     tuple(sorted((k, v) for k, v in
                                  (attrs.get("_extra") or {}).items())))
    return list(prop.list_arguments())


def _custom_num_outputs(attrs):
    prop = _prop_for(attrs["op_type"],
                     tuple(sorted((k, v) for k, v in
                                  (attrs.get("_extra") or {}).items())))
    return len(prop.list_outputs())


_custom_schema = _register_op(
    "Custom", _custom_fcompute,
    params={"op_type": Param("str", None, True),
            "_extra": Param("any", None)},
    inputs=("data",), infer_shape=_custom_infer_shape)
_custom_schema.list_inputs = _custom_list_inputs  # type: ignore
_custom_schema.num_inputs = lambda attrs: len(_custom_list_inputs(attrs))  # type: ignore
_custom_schema.num_outputs = _custom_num_outputs  # type: ignore


def _custom_parse_attrs(kwargs):
    """Custom accepts arbitrary user kwargs, forwarded (as the reference
    does via string marshalling, custom-inl.h) to the Prop constructor."""
    from .ops.registry import AttrDict
    if "op_type" not in kwargs or kwargs["op_type"] is None:
        raise MXNetError("Custom: required param 'op_type' missing")
    skip = {"op_type", "name", "attr", "out", "dtype_hint", "__layout__"}
    out = AttrDict()
    out["op_type"] = str(kwargs["op_type"])
    extra = {k: v for k, v in kwargs.items()
             if k not in skip and v is not None}
    out["_extra"] = extra or None
    return out


_custom_schema.parse_attrs = _custom_parse_attrs  # type: ignore
