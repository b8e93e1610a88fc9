"""Autograd: imperative differentiation on a recorded tape.

Parity target: python/mxnet/autograd.py + src/imperative/imperative.cc
(RecordOp :182, Backward :358). The reference records an nnvm graph via
per-NDArray AGInfo and executes a gradient graph op-by-op. TPU-natively, the
tape records (jax-traceable fn, inputs, outputs); `backward()` stitches the
reachable subgraph into ONE pure function of the gradient-requiring variables
and calls jax.vjp on it — the entire backward pass compiles to a single XLA
module instead of a per-op interpreter loop.
"""
from __future__ import annotations

import threading

import jax
import jax.numpy as jnp
import numpy as _np

from .base import MXNetError

_state = threading.local()


def _st():
    if not hasattr(_state, "recording"):
        _state.recording = False
        _state.training = False
    return _state


def is_recording() -> bool:
    return _st().recording


def is_training() -> bool:
    return _st().training


def set_recording(is_record: bool) -> bool:
    s = _st()
    prev, s.recording = s.recording, is_record
    return prev


def set_training(train_mode: bool) -> bool:
    s = _st()
    prev, s.training = s.training, train_mode
    return prev


class _RecordingStateScope:
    def __init__(self, is_record, train_mode):
        self._is_record = is_record
        self._train = train_mode

    def __enter__(self):
        s = _st()
        self._prev = (s.recording, s.training)
        if self._is_record is not None:
            s.recording = self._is_record
        if self._train is not None:
            s.training = self._train
        return self

    def __exit__(self, *exc):
        s = _st()
        s.recording, s.training = self._prev


def record(train_mode=True):
    """Returns a scope that turns on recording (and train mode)."""
    return _RecordingStateScope(True, train_mode)


def pause(train_mode=False):
    return _RecordingStateScope(False, train_mode)


def train_mode():
    return _RecordingStateScope(None, True)


def predict_mode():
    return _RecordingStateScope(None, False)


# ---------------------------------------------------------------------------
# Tape
# ---------------------------------------------------------------------------

class AGNode:
    """One recorded op application (role of nnvm node + AGInfo,
    include/mxnet/imperative.h:59-95).

    `fn` must have a *stable identity* across steps (the per-(op, attrs,
    is_train) jitted callable from the imperative cache) — it is part of the
    backward-replay cache key. Per-step values (rng key, captured arrays)
    are stored separately and passed as arguments to the cached replay."""

    __slots__ = ("fn", "inputs", "input_values", "n_out", "rng")

    def __init__(self, fn, inputs, input_values, n_out, rng=None):
        self.fn = fn                  # fn(*arrays) -> tuple of arrays
        self.inputs = inputs          # list of AGEntry (node, idx) or var marker
        self.input_values = input_values  # jax arrays captured at record time
        self.n_out = n_out
        self.rng = rng                # PRNG key when fn is fn(rng, *arrays)


class AGVar:
    """A leaf variable (NDArray with attach_grad or any un-recorded input)."""

    __slots__ = ("nd", "value")

    def __init__(self, nd, value):
        self.nd = nd
        self.value = value


def _record(schema, attrs, rng, is_train, inputs, outputs, n_out,
            platform=None):
    from .imperative import jitted_for_schema
    # same platform as the forward dispatch: the replay must reuse the
    # forward's compiled executable (cache key includes platform) and
    # backend-specialized ops must not diverge between fwd and bwd
    base = jitted_for_schema(schema, attrs, is_train, platform=platform)
    _record_fn(base, inputs, outputs, n_out=n_out,
               rng=rng if schema.needs_rng else None)


def _record_fn(fn, inputs, outputs, n_out=None, rng=None):
    from .ndarray.ndarray import NDArray
    entries = []
    values = []
    for x in inputs:
        if isinstance(x, NDArray):
            entries.append(x._ag_node)  # (AGNode, idx) or AGVar or None
            values.append(x._data)
        else:
            entries.append(None)
            values.append(x)
    node = AGNode(fn, entries, values,
                  n_out if n_out is not None else len(outputs), rng)
    for i, o in enumerate(outputs[:node.n_out]):
        o._ag_node = (node, i)


def mark_variables(variables, gradients, grad_reqs="write"):
    """Parity: mx.autograd.mark_variables (autograd.py:216)."""
    if isinstance(grad_reqs, str):
        grad_reqs = [grad_reqs] * len(variables)
    for v, g, req in zip(variables, gradients, grad_reqs):
        v._grad = g
        v._grad_req = req
        v._ag_node = AGVar(v, v._data)


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------

def _collect(heads):
    """Topologically collect reachable AGNodes and leaf AGVars."""
    nodes = []       # topo order (inputs before users)
    seen = set()
    variables = []   # AGVar leaves with grad attached
    var_seen = set()

    def visit(entry):
        if entry is None:
            return
        if isinstance(entry, AGVar):
            if id(entry) not in var_seen:
                var_seen.add(id(entry))
                variables.append(entry)
            return
        node, _ = entry
        if id(node) in seen:
            return
        seen.add(id(node))
        for e in node.inputs:
            visit(e)
        nodes.append(node)

    for h in heads:
        visit(h)
    return nodes, variables


# Backward-replay executable cache: one jitted fwd+vjp program per tape
# *structure* (node fns + wiring + heads). A training loop records an
# identical structure every step, so step 2..N skip tracing entirely
# (re-vjp'ing the whole tape per backward() is what this replaces).
_REPLAY_CACHE: "dict" = {}
_REPLAY_CACHE_MAX = 64
_REPLAY_NONCE = 0


def _replay_executable(node_list, var_index, node_index, head_specs):
    """Return (jitted_fn, dyn_specs, rng_nodes) for this tape structure.

    jitted_fn(var_values, dyn_values, rng_values, head_grads) -> grads.
    Captured arrays (unmarked inputs — e.g. the data batch) and per-node rng
    keys are *arguments*, not baked constants, so the executable is reusable
    across steps."""
    dyn_specs = []    # (node_i, input_j) of captured jax.Array inputs
    rng_nodes = []    # node indices that take a leading rng key
    key_parts = []
    wirings = []
    for ni, node in enumerate(node_list):
        wiring = []
        for j, (e, captured) in enumerate(zip(node.inputs,
                                              node.input_values)):
            if isinstance(e, AGVar):
                wiring.append(("v", var_index[id(e)]))
            elif e is None:
                if isinstance(captured, (jax.Array, _np.ndarray)):
                    wiring.append(("d", len(dyn_specs)))
                    dyn_specs.append((ni, j))
                elif isinstance(captured, (int, float, bool, complex, str,
                                           bytes, type(None))):
                    # python scalar — injective repr, part of the structure
                    wiring.append(("c", ni, j, repr(captured)))
                else:
                    # unknown static: never share a cache entry for it
                    global _REPLAY_NONCE
                    _REPLAY_NONCE += 1
                    wiring.append(("c", ni, j, ("nonce", _REPLAY_NONCE)))
            else:
                n2, i2 = e
                wiring.append(("n", node_index[id(n2)], i2))
        if node.rng is not None:
            rng_nodes.append(ni)
        wirings.append(tuple(wiring))
        key_parts.append((node.fn, node.rng is not None, wirings[-1],
                          node.n_out))
    key = (tuple(key_parts), tuple(head_specs))

    hit = _REPLAY_CACHE.get(key)
    if hit is not None:
        return hit[0], dyn_specs, rng_nodes

    fns = [node.fn for node in node_list]
    consts = {}
    for w in wirings:
        for s in w:
            if s[0] == "c":
                consts[(s[1], s[2])] = node_list[s[1]].input_values[s[2]]
    rng_pos = {ni: i for i, ni in enumerate(rng_nodes)}

    def replay(var_values, dyn_values, rng_values):
        node_outs = [None] * len(fns)
        for ni, fn in enumerate(fns):
            args = []
            for spec in wirings[ni]:
                kind = spec[0]
                if kind == "v":
                    args.append(var_values[spec[1]])
                elif kind == "d":
                    args.append(dyn_values[spec[1]])
                elif kind == "c":
                    args.append(consts[(spec[1], spec[2])])
                else:
                    args.append(node_outs[spec[1]][spec[2]])
            res = fn(rng_values[rng_pos[ni]], *args) if ni in rng_pos \
                else fn(*args)
            if not isinstance(res, tuple):
                res = (res,)
            node_outs[ni] = res
        outs = []
        for spec in head_specs:
            if spec[0] == "var":
                outs.append(var_values[spec[1]])
            else:
                outs.append(node_outs[spec[1]][spec[2]])
        return tuple(outs)

    def vjp_replay(var_values, dyn_values, rng_values, head_grads):
        _, vjp_fn = jax.vjp(
            lambda *vs: replay(vs, dyn_values, rng_values), *var_values)
        return vjp_fn(tuple(head_grads))

    jitted = jax.jit(vjp_replay)
    # tapes containing per-call closures (autograd.Function) can never hit
    # the cache again (fn identity is the key): keep them out so they do
    # not evict the stable entries training loops rely on
    if not any(getattr(fn, "_mx_uncached_replay", False) for fn in fns):
        if len(_REPLAY_CACHE) >= _REPLAY_CACHE_MAX:
            _REPLAY_CACHE.pop(next(iter(_REPLAY_CACHE)))
        _REPLAY_CACHE[key] = (jitted,)
    return jitted, dyn_specs, rng_nodes


def backward(heads, head_grads=None, retain_graph=False, train_mode=True):
    """Compute gradients of heads w.r.t. all reachable marked variables.

    Replays the tape as ONE jitted fwd+vjp XLA program, cached on tape
    structure. The replay re-executes forward inside the compiled vjp —
    the standard functional trade (reference avoids it by storing every
    intermediate in HBM; XLA rematerializes cheaper than it stores).
    """
    from .ndarray.ndarray import NDArray

    if isinstance(heads, NDArray):
        heads = [heads]
    head_entries = []
    for h in heads:
        if h._ag_node is None:
            raise MXNetError("cannot differentiate: output not recorded "
                             "(is autograd.record() active?)")
        head_entries.append(h._ag_node)

    if head_grads is None:
        head_grads = [jnp.ones_like(h._data) for h in heads]
    else:
        head_grads = [g._data if isinstance(g, NDArray) else jnp.asarray(g)
                      for g in head_grads]

    nodes, variables = _collect(head_entries)
    if not variables:
        raise MXNetError("no variables with gradients reachable from heads")

    node_list = nodes
    var_index = {id(v): i for i, v in enumerate(variables)}
    node_index = {id(n): i for i, n in enumerate(node_list)}
    head_specs = []
    for e in head_entries:
        if isinstance(e, AGVar):
            head_specs.append(("var", var_index[id(e)]))
        else:
            node, idx = e
            head_specs.append(("node", node_index[id(node)], idx))

    jitted, dyn_specs, rng_nodes = _replay_executable(
        node_list, var_index, node_index, head_specs)
    var_values = tuple(v.value for v in variables)
    dyn_values = tuple(node_list[ni].input_values[j] for ni, j in dyn_specs)
    rng_values = tuple(node_list[ni].rng for ni in rng_nodes)
    grads = jitted(var_values, dyn_values, rng_values, tuple(head_grads))

    for v, g in zip(variables, grads):
        nd = v.nd
        req = getattr(nd, "_grad_req", "write")
        if req == "null" or nd._grad is None:
            continue
        if req == "add":
            nd._grad._data = nd._grad._data + g
        else:
            nd._grad._data = g

    if not retain_graph:
        for h in heads:
            pass  # tape nodes are GC'd once outputs drop references


def grad(heads, variables, head_grads=None, retain_graph=None,
         create_graph=False, train_mode=True):
    """Parity: mx.autograd.grad (autograd.py:270) — returns grads instead of
    writing .grad buffers. create_graph=True is not yet supported."""
    from .ndarray.ndarray import NDArray
    if isinstance(heads, NDArray):
        heads = [heads]
    if isinstance(variables, NDArray):
        variables = [variables]
    if create_graph:
        raise MXNetError("create_graph=True not supported yet")

    head_entries = [h._ag_node for h in heads]
    for e in head_entries:
        if e is None:
            raise MXNetError("output not recorded")
    nodes, all_vars = _collect(head_entries)
    # ensure requested variables are leaves
    want = []
    for v in variables:
        e = v._ag_node
        if not isinstance(e, AGVar):
            raise MXNetError("requested variable was not marked "
                             "(call attach_grad() before record)")
        want.append(e)

    saved = [(v.nd, getattr(v.nd, "_grad", None), getattr(v.nd, "_grad_req", "write"))
             for v in all_vars]
    tmp = []
    for v in variables:
        from .ndarray.ndarray import zeros_like as _zl
        g = _zl(v)
        v._grad = g
        v._grad_req = "write"
        tmp.append(g)
    backward(heads, head_grads, retain_graph=True, train_mode=train_mode)
    out = [v._grad for v in variables]
    for nd, g, req in saved:
        if nd not in variables:
            nd._grad, nd._grad_req = g, req
    return out


def get_symbol(x):
    raise MXNetError("autograd.get_symbol is not supported; use "
                     "Gluon HybridBlock tracing instead")


# ---------------------------------------------------------------------------
# Custom differentiable functions — mx.autograd.Function (autograd.py:383)
# ---------------------------------------------------------------------------

class Function:
    """User-defined differentiable NDArray function.

    Subclass and implement ``forward(self, *inputs)`` (NDArrays in,
    NDArray or tuple out) and ``backward(self, *output_grads)``
    (NDArrays of head gradients in, per-input gradient NDArrays out);
    call the instance. Both run as host callbacks (``jax.pure_callback``)
    inside the recorded graph, so the tape replay stays one compiled
    program. Same device note as mx.operator.CustomOp.

    Cost model: ``forward`` executes once eagerly at call time (to learn
    output shapes/dtypes) and again inside the replayed program when
    ``backward()`` runs, and each call records a fresh closure, so every
    backward over a Function-bearing tape re-traces — this is the slow
    escape-hatch path, like the reference's custom-op engine lane.

    Reference: python/mxnet/autograd.py:383 (Function over
    MXCustomFunctionRecord).
    """

    def forward(self, *inputs):
        raise NotImplementedError

    def backward(self, *output_grads):
        raise NotImplementedError

    def save_for_backward(self, *arrays):
        """Keep forward values for backward, read back as
        ``self.saved_tensors`` (reference autograd.py Function). Kept on
        the host: backward runs in a host callback, whose head gradients
        arrive on the CPU whatever device the forward ran on."""
        from .context import cpu
        self.saved_tensors = tuple(a.as_in_context(cpu()) for a in arrays)

    def __call__(self, *inputs):
        from .ndarray.ndarray import NDArray
        import jax

        vals = [x._data if isinstance(x, NDArray) else jnp.asarray(x)
                for x in inputs]
        in_avals = tuple(jax.ShapeDtypeStruct(v.shape, v.dtype)
                         for v in vals)
        fn_self = self

        # learn output avals by running forward once, eagerly (host)
        with pause():
            eager = fn_self.forward(*[NDArray(v) for v in vals])
        single = not isinstance(eager, (list, tuple))
        eager_list = [eager] if single else list(eager)
        out_avals = tuple(jax.ShapeDtypeStruct(o.shape, o._data.dtype)
                          for o in eager_list)

        if not is_recording():
            return eager if single else tuple(eager_list)

        def _host_fwd(*vs):
            with pause():
                res = fn_self.forward(*[NDArray(jnp.asarray(v))
                                        for v in vs])
            res = [res] if not isinstance(res, (list, tuple)) else res
            return tuple(_np.asarray(r.asnumpy(), dtype=a.dtype)
                         for r, a in zip(res, out_avals))

        def _host_bwd(*args):
            gs = args[len(in_avals):]
            with pause():
                grads = fn_self.backward(*[NDArray(jnp.asarray(g))
                                           for g in gs])
            grads = [grads] if not isinstance(grads, (list, tuple)) \
                else grads
            return tuple(_np.asarray(g.asnumpy(), dtype=a.dtype)
                         for g, a in zip(grads, in_avals))

        @jax.custom_vjp
        def f(*vs):
            return jax.pure_callback(_host_fwd, out_avals, *vs)

        def fwd(*vs):
            return f(*vs), vs

        def bwd(res_vs, gs):
            return jax.pure_callback(_host_bwd, in_avals, *res_vs, *gs)

        f.defvjp(fwd, bwd)
        # per-call closure: replay executables containing it are one-shot
        f._mx_uncached_replay = True
        _record_fn(f, list(inputs), eager_list, n_out=len(eager_list))
        return eager if single else tuple(eager_list)
