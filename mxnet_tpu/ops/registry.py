"""Operator registry — the TPU-native analog of the NNVM op registry.

Reference model (SURVEY.md §2.2): every op registers FInferShape/FInferType/
FCompute<cpu|gpu> attributes (include/mxnet/op_attr_types.h:183-268) and is
dispatched through the dependency engine. Here an op is a *pure jax-traceable
function* plus typed parameter schema and (optional) backward shape inference:

  - `fcompute(attrs, octx, *inputs) -> tuple of jnp arrays` is traced by XLA;
    gradients come from jax.vjp — no hand-written _backward_* ops, except where
    the reference defines a *semantically different* backward (SoftmaxOutput,
    MakeLoss), which use jax.custom_vjp inside fcompute.
  - `infer_shape(attrs, in_shapes) -> (in_shapes, out_shapes)` fills unknown
    input shapes (None entries) so `simple_bind` can derive weight shapes from
    the data shape, exactly like FInferShape's bidirectional contract. Ops
    without one fall back to jax.eval_shape (forward-only inference).

Parsed attrs are *static* arguments: each (op, attrs, is_train) triple maps to
one jit-compiled XLA executable, cached by jax on input avals.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Sequence

import numpy as _np

from ..base import (MXNetError, parse_bool, parse_float, parse_int,
                    parse_shape)

__all__ = ["Param", "OpSchema", "OpCtx", "register", "register_alias",
           "get_op", "list_ops", "AttrDict", "kept_residual",
           "kept_residuals"]


def _parse_floats(v):
    """Tuple-of-float attr ((1.0, 2.0), "[1,2]", 0.5 -> tuple of float) —
    role of nnvm::Tuple<float> params (sizes/ratios/variances)."""
    if isinstance(v, (int, float, _np.floating, _np.integer)):
        return (float(v),)
    if isinstance(v, str):
        import ast
        v = ast.literal_eval(v.strip())
        if not isinstance(v, (tuple, list)):
            return (float(v),)
    return tuple(float(x) for x in v)


_PARSERS = {
    "int": parse_int,
    "float": parse_float,
    "bool": parse_bool,
    "str": lambda v: str(v),
    "shape": parse_shape,
    "floats": _parse_floats,
    "dtype": lambda v: v if isinstance(v, str) else _np.dtype(v).name,
    "any": lambda v: v,
}


@dataclasses.dataclass
class Param:
    """Typed op parameter (role of a dmlc::Parameter field)."""
    type: str = "any"
    default: object = None
    required: bool = False

    def parse(self, v):
        if v is None:
            return None
        return _PARSERS[self.type](v)


class AttrDict(dict):
    """Parsed-attr dict, attribute access + hashable freeze for jit cache keys."""

    def __getattr__(self, k):
        try:
            return self[k]
        except KeyError:
            raise AttributeError(k)

    def frozen(self):
        return tuple(sorted((k, _freeze(v)) for k, v in self.items()))


def _freeze(v):
    if isinstance(v, (list, tuple)):
        return tuple(_freeze(x) for x in v)
    if isinstance(v, dict):
        return tuple(sorted((k, _freeze(x)) for k, x in v.items()))
    return v


@dataclasses.dataclass
class OpCtx:
    """Per-invocation execution context handed to fcompute.

    `is_train` is static (affects tracing: dropout/BN branches); `rng` is a
    traced jax PRNG key array for ops with needs_rng=True. This is the analog
    of OpContext (include/mxnet/op_attr_types.h:64-85) minus streams, which
    XLA owns.
    """
    is_train: bool = False
    rng: object = None
    # target platform ("cpu"/"tpu") when the caller compiles for a specific
    # device — backend-specialized ops (pallas kernels) must not key off
    # jax.default_backend(), which may differ from the jit target
    platform: str = None


@dataclasses.dataclass
class OpSchema:
    name: str
    fcompute: Callable
    params: dict
    # input names in order; auxiliary-state inputs (e.g. BN moving stats) are
    # listed too and flagged by aux_indices (MXNet ListAuxiliaryStates model)
    input_names: Sequence[str]
    num_outputs: int = 1
    aux_indices: Sequence[int] = ()
    # if True, fcompute returns num_outputs + len(aux_indices) arrays; the
    # trailing ones are updated aux values written back by the caller
    mutates_aux: bool = False
    # aux writeback normally happens only under is_train (BatchNorm moving
    # stats); optimizer update ops mutate their state inputs unconditionally
    # (reference marks them TakeParamAsInput/mutable, optimizer_op.cc)
    aux_always: bool = False
    needs_rng: bool = False
    # variadic ops (Concat, add_n): attr naming the input count
    key_var_num_args: Optional[str] = None
    infer_shape: Optional[Callable] = None
    # dtype of outputs when not simply inputs' common dtype
    infer_type: Optional[Callable] = None
    # aliases under which this op is also exposed (e.g. snake_case)
    aliases: Sequence[str] = ()

    def parse_attrs(self, kwargs) -> AttrDict:
        out = AttrDict()
        for k, p in self.params.items():
            if k in kwargs and kwargs[k] is not None:
                out[k] = p.parse(kwargs[k])
            elif p.required:
                raise MXNetError(f"op {self.name}: required param {k!r} missing")
            else:
                out[k] = p.default
        unknown = set(kwargs) - set(self.params)
        # MXNet tolerates and round-trips unknown attrs on symbols; we keep
        # string extras out of the static attr set but don't hard error on
        # the conventional ones.
        unknown -= {"name", "attr", "out", "dtype_hint", "__layout__"}
        if unknown:
            raise MXNetError(f"op {self.name}: unknown params {sorted(unknown)}")
        return out

    def num_inputs(self, attrs) -> int:
        if self.key_var_num_args:
            return int(attrs[self.key_var_num_args])
        return len(self.input_names)

    def list_inputs(self, attrs):
        if self.key_var_num_args:
            n = int(attrs[self.key_var_num_args])
            base = self.input_names[0] if self.input_names else "arg"
            return [f"{base}{i}" for i in range(n)]
        return list(self.input_names)


_REGISTRY: dict = {}


def register(name, fcompute, *, params=None, inputs=("data",), num_outputs=1,
             aux=(), mutates_aux=False, aux_always=False, needs_rng=False,
             key_var_num_args=None, infer_shape=None, infer_type=None,
             aliases=()):
    """Register an operator. `aux` is a list of input names that are auxiliary
    states. Returns the OpSchema."""
    params = {k: (v if isinstance(v, Param) else Param(*v) if isinstance(v, tuple)
                  else Param(default=v)) for k, v in (params or {}).items()}
    inputs = list(inputs)
    aux_idx = tuple(inputs.index(a) for a in aux)
    schema = OpSchema(name=name, fcompute=fcompute, params=params,
                      input_names=inputs, num_outputs=num_outputs,
                      aux_indices=aux_idx, mutates_aux=mutates_aux,
                      aux_always=aux_always,
                      needs_rng=needs_rng, key_var_num_args=key_var_num_args,
                      infer_shape=infer_shape, infer_type=infer_type,
                      aliases=tuple(aliases))
    for n in (name, *aliases):
        if n in _REGISTRY:
            raise MXNetError(f"op {n!r} already registered")
        _REGISTRY[n] = schema
    return schema


def register_alias(alias, name):
    """Expose an already-registered op under an additional public name
    (role of nnvm ``.add_alias``; e.g. legacy CamelCase / sparse names).
    Unknown targets and clashes with a DIFFERENT op raise; re-aliasing to
    the same op is a no-op."""
    schema = get_op(name)
    existing = _REGISTRY.get(alias)
    if existing is not None:
        if existing is schema:
            return schema
        raise MXNetError(f"op {alias!r} already registered to "
                         f"{existing.name!r}")
    _REGISTRY[alias] = schema
    return schema


def get_op(name) -> OpSchema:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise MXNetError(f"operator {name!r} not registered") from None


def list_ops():
    return sorted(set(s.name for s in _REGISTRY.values()))


_KEPT_RESIDUALS: set = set()


def kept_residual(name):
    """Declare `name`, and return it, as a name under which an op tags
    (`jax.ad_checkpoint.checkpoint_name`, in the `fwd` of its custom VJP)
    a value that its backward needs and that the rerun of a rematerialised
    stage could only get from a kernel of the op's own: the executor's
    stages (MXNET_BACKWARD_DO_MIRROR) keep the values so tagged and
    recompute everything else. Outside a checkpoint the tag is the
    identity."""
    _KEPT_RESIDUALS.add(name)
    return name


def kept_residuals():
    """The names declared so far, every op's."""
    return frozenset(_KEPT_RESIDUALS)


def canonical_names():
    """name -> schema for primary names only (no aliases)."""
    return {s.name: s for s in _REGISTRY.values()}
