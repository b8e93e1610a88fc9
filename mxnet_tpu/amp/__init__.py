"""mxnet_tpu.amp — automatic mixed precision for the whole stack.

Role of the reference's `mxnet.contrib.amp` (amp.init patches the op
namespace with casts; LossScaler guards fp16), rebuilt for the XLA
lowering: instead of rewriting symbols, the policy hooks the ONE place
every op call funnels through — `executor._build_runner`'s fcompute
dispatch — and casts op inputs at trace time per the ALLOW/WIDEN lists
(amp/policy.py). Since every execution route (Executor.bind, Module.fit,
gluon CachedOp, DataParallelTrainer, export) lowers through that runner,
one hook mixes precision everywhere, and `amp.init("float32")` (or
leaving amp off) is a literal no-op: the traced program is unchanged,
so fp32 results stay bit-identical.

    import mxnet_tpu as mx
    mx.amp.init("bfloat16")     # before bind/fit: jit caches by shape,
                                # not by amp state, so flip it first
    mod.fit(...)                # matmuls/convs in bf16, softmax/norm
                                # stats and the update in fp32

Master weights: parameters stay fp32 everywhere (NDArray args, the
DataParallelTrainer param pytree) — the policy casts them down at each
use site, XLA dedups the casts, and gradients flow back in the compute
dtype to be accumulated into the fp32 state. fp16 additionally needs
`DynamicLossScaler` (amp/scaler.py) — wired automatically into
DataParallelTrainer(dtype="float16").

Env wiring (config.py): MXNET_AMP=1 [MXNET_AMP_DTYPE=bfloat16|float16]
calls `init` at import. Counters (amp_scale, amp_skipped_steps,
amp_cast_bytes_saved) export through profiler.register_counter_export.
"""
from __future__ import annotations

import threading
import weakref

import numpy as _np

from .policy import (ALLOW, EXACT_INPUTS, KEEP_FP32, LOSS_HEADS, MIXED,
                     WIDEN)
from .scaler import DynamicLossScaler

__all__ = ["init", "disable", "is_enabled", "get_dtype", "compute_dtype",
           "reduce_dtype", "cast_op_inputs", "exact_variables", "counters",
           "DynamicLossScaler", "ALLOW", "LOSS_HEADS", "WIDEN", "MIXED",
           "KEEP_FP32", "EXACT_INPUTS"]

_DTYPES = ("float32", "bfloat16", "float16")

_lock = threading.Lock()
_state = {"enabled": False, "dtype": "float32"}
_cast_bytes_saved = [0]      # trace-time accounting, see cast_op_inputs
_scale_sources = []          # weakrefs to objects with _amp_counters()
_export_registered = [False]
_tls = threading.local()     # trace-scoped loss scale, see below
_inject_vjp = [None]         # lazily-built custom_vjp (needs jax)


def init(dtype="bfloat16"):
    """Enable autocast with the given compute dtype ("bfloat16" or
    "float16"); "float32" disables (explicit no-op policy). Call BEFORE
    binding/compiling: already-jitted programs do not retrace on amp
    state changes (jax caches by input avals). Returns the active dtype.
    """
    dtype = str(dtype)
    if dtype not in _DTYPES:
        raise ValueError(f"amp.init: dtype must be one of {_DTYPES}, "
                         f"got {dtype!r}")
    with _lock:
        _state["dtype"] = dtype
        _state["enabled"] = dtype != "float32"
    _ensure_counter_export()
    return dtype


def disable():
    with _lock:
        _state["enabled"] = False
        _state["dtype"] = "float32"


def is_enabled():
    return _state["enabled"]


def get_dtype():
    """Active compute dtype name ("float32" when disabled)."""
    return _state["dtype"]


def compute_dtype():
    """Active compute dtype as a jnp dtype, or None when disabled."""
    if not _state["enabled"]:
        return None
    import jax.numpy as jnp
    return jnp.bfloat16 if _state["dtype"] == "bfloat16" else jnp.float16


def reduce_dtype():
    """Wire dtype for cross-process gradient reduction (kvstore/dist
    push path): bf16 when amp is on — fp16 grads also reduce in bf16
    (same width, fp32-range exponent, so the sum cannot overflow where
    the addends did not) — else None (keep fp32)."""
    if not _state["enabled"]:
        return None
    from ..base import bfloat16 as _bf16
    return _bf16


def _set_trace_loss_scale(scale):
    """Trace-scoped fp16 loss scale (parallel/dp.py sets it around its
    value_and_grad trace, clears in a finally). While set, the executor
    funnel wraps each legacy loss head's data input in a cotangent
    multiplier — the ONLY way to scale gradients under heads whose
    custom VJP ignores the incoming cotangent (policy.LOSS_HEADS).
    Thread-local: concurrent trainers on other threads are unaffected."""
    _tls.loss_scale = scale


def _trace_loss_scale():
    return getattr(_tls, "loss_scale", None)


def _inject_grad_scale(x, scale):
    """Identity on the forward value; multiplies the backward cotangent
    by `scale` (in fp32, then back to the cotangent's dtype so fp16
    overflow stays detectable as inf downstream)."""
    if _inject_vjp[0] is None:
        import jax
        import jax.numpy as jnp

        @jax.custom_vjp
        def _inject(v, s):
            return v

        def _fwd(v, s):
            return v, s

        def _bwd(s, g):
            scaled = (g.astype(jnp.float32) * s).astype(g.dtype)
            return scaled, jnp.zeros_like(s)

        _inject.defvjp(_fwd, _bwd)
        _inject_vjp[0] = _inject
    return _inject_vjp[0](x, scale)


def cast_op_inputs(op_name, ins):
    """The executor hook: given an op's registry name and its input
    values (jax arrays at trace time), return the policy-cast inputs.
    Identity when amp is off, for NEUTRAL ops, and for every non-float
    input (ids/masks/aux ints are never cast). Independently of the
    policy, while a trace loss scale is set (fp16 training), loss-head
    data inputs get the gradient-scale injection — applied AFTER the
    policy casts so the cotangent multiply runs in the widened dtype."""
    scale = getattr(_tls, "loss_scale", None)
    if not _state["enabled"] and scale is None:
        return ins
    import jax.numpy as jnp
    out = list(ins)
    tgt = None
    if _state["enabled"]:
        if op_name in ALLOW:
            tgt = jnp.bfloat16 if _state["dtype"] == "bfloat16" \
                else jnp.float16
        elif op_name in WIDEN:
            tgt = jnp.float32
    if tgt is not None:
        tgt_np = _np.dtype(tgt)
        for i, x in enumerate(out):
            dt = getattr(x, "dtype", None)
            if dt is not None and jnp.issubdtype(dt, jnp.floating) \
                    and dt != tgt_np:
                saved = (_np.dtype(dt).itemsize - tgt_np.itemsize) \
                    * int(getattr(x, "size", 0))
                if saved > 0:
                    # counted once per TRACE (each compiled program), not
                    # per step: it measures bytes the cast removes from
                    # the program's activation traffic, via counters()
                    with _lock:
                        _cast_bytes_saved[0] += saved
                out[i] = x.astype(tgt)
    if scale is not None and op_name in LOSS_HEADS and out:
        out[0] = _inject_grad_scale(out[0], scale)
    return out


def exact_variables(symbol):
    """Names of the symbol's variables that a trainer must hand to the
    graph as they are, whatever its compute dtype: parameters that feed an
    input listed in policy.KEEP_FP32 and data that feeds one listed in
    policy.EXACT_INPUTS, directly."""
    keep = set()
    for node in symbol._topo():
        if node.op is None:
            continue
        names = KEEP_FP32.get(node.op.name, ()) + \
            EXACT_INPUTS.get(node.op.name, ())
        if not names:
            continue
        parsed = node.op.parse_attrs(node.attrs)
        for iname, (src, _) in zip(node.op.list_inputs(parsed), node.inputs):
            if iname in names and src.op is None:
                keep.add(src.name)
    return frozenset(keep)


# -- counters ---------------------------------------------------------------

def _register_scale_source(obj):
    """Trainers with a live loss scale register themselves (weakly);
    counters() polls whoever is still alive. `obj` must expose
    `_amp_counters() -> {"amp_scale": float, "amp_skipped_steps": int}`.
    """
    with _lock:
        _scale_sources.append(weakref.ref(obj))


def counters():
    """Snapshot for profiler.export_counters()/dump(): the three ISSUE
    counters plus the active policy."""
    out = {"enabled": _state["enabled"], "dtype": _state["dtype"],
           "amp_cast_bytes_saved": int(_cast_bytes_saved[0]),
           "amp_scale": None, "amp_skipped_steps": 0}
    with _lock:
        refs = list(_scale_sources)
    live = []
    for r in refs:
        src = r()
        if src is None:
            continue
        live.append(r)
        try:
            c = src._amp_counters()
        except Exception:
            continue
        if c.get("amp_scale") is not None:
            out["amp_scale"] = float(c["amp_scale"])
        out["amp_skipped_steps"] += int(c.get("amp_skipped_steps", 0))
    with _lock:
        _scale_sources[:] = live
    return out


def _ensure_counter_export():
    if _export_registered[0]:
        return
    from .. import profiler
    profiler.register_counter_export("amp", counters)
    _export_registered[0] = True


def _reset_for_tests():
    """Test hook: restore pristine module state (policy off, counters
    zeroed) so amp tests cannot leak into dtype-sensitive suites."""
    with _lock:
        _state["enabled"] = False
        _state["dtype"] = "float32"
        _cast_bytes_saved[0] = 0
        _scale_sources[:] = []
    _tls.loss_scale = None

# register the export hook at import, not just amp.init(): the telemetry
# registry absorbs every profiler hook at /metrics scrape time, and amp's
# enabled/dtype/cast-savings counters should be visible (zeroed) even on
# runs that never turn amp on
_ensure_counter_export()
