"""Executor — binds a Symbol to devices/arrays and runs it.

Parity target: src/executor/graph_executor.{h,cc} + python/mxnet/executor.py
(SURVEY.md §2.1, §3.4). The reference's Init pipeline (gradient graph, device
placement, shape/type inference, PlanMemory, AttachOpExecs, engine op
creation) collapses TPU-natively into: walk the Symbol once to emit a pure
jax function of (args, aux, rng) → (outputs, new_aux), then let XLA do
placement/memory-planning/fusion. `forward(is_train=True)` runs jax.vjp over
that function so `backward()` is the transposed XLA module — the whole
fwd+bwd is two compiled executables instead of per-op engine pushes.

grad_req: 'write' stores grads, 'add' accumulates into the bound grad arrays
(the reference's kAddTo), 'null' skips.
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
import numpy as _np

from .base import MXNetError
from .context import Context, current_context
from .ops.registry import OpCtx, kept_residuals

__all__ = ["Executor"]

MIRROR_KEPT_COUNTER = "mirror_kept_residuals_total"
MIRROR_KEPT_BYTES_COUNTER = "mirror_kept_bytes_total"


def _rematerialised(fn):
    """`fn` under the `jax.checkpoint` of everything that
    MXNET_BACKWARD_DO_MIRROR rematerialises: its backward keeps what enters
    `fn` and every value that an op inside tagged under a name it declared
    (`ops.registry.kept_residual`), and reruns the rest. Where no such
    value occurs the policy is `nothing_saveable`, a bare `jax.checkpoint`'s.
    A kept array and its bytes are counted once a trace in the telemetry
    registry."""
    declared = jax.checkpoint_policies.save_only_these_names(
        *kept_residuals())

    def keeps_declared(prim, *avals, **params):
        if not declared(prim, *avals, **params):
            return False
        from .telemetry import registry
        kept, = avals               # the tag's one operand
        registry.counter(
            MIRROR_KEPT_COUNTER,
            help="arrays that ops declared as residuals of their own "
                 "backward and rematerialised stages traced so far keep"
        ).inc()
        registry.counter(
            MIRROR_KEPT_BYTES_COUNTER,
            help="bytes of the arrays counted by " + MIRROR_KEPT_COUNTER
        ).inc(kept.size * kept.dtype.itemsize)
        return True

    return jax.checkpoint(fn, policy=keeps_declared)


def _node_group_dev(node, group2dev):
    """Device for a ctx_group-tagged node, or None (PlaceDevice role)."""
    if not group2dev:
        return None
    return group2dev.get(node.user_attrs.get("ctx_group"))


def _fuse_bn_relu(symbol, topo):
    """BN+ReLU fusion pass: find Activation('relu') nodes whose sole input
    is the data output of a BatchNorm that nothing else consumes. The BN
    kernel then applies the relu (and masks dy inline in its hand-written
    vjp, ops/nn.py:_bn_train_bwd) — saving one full read+write pass over
    the activation tensor per BN in the backward. Role of the reference's
    cuDNN fused BNForwardTraining+Activation path; here it is a graph pass
    feeding the XLA lowering.

    Returns (fused_bn: set of BN node ids, passthrough: {relu_id: bn_id}).
    """
    consumers, out_entries = _graph_consumers(symbol, topo)
    fused, passthrough = set(), {}
    for n in topo:
        if n.op is None or n.op.name != "Activation":
            continue
        if n.attrs.get("act_type") != "relu":
            continue
        src, i = n.inputs[0]
        if i != 0 or src.op is None or src.op.name != "BatchNorm":
            continue
        if len(consumers.get((id(src), 0), [])) != 1 or \
                (id(src), 0) in out_entries:
            continue
        if n.user_attrs.get("ctx_group") != src.user_attrs.get("ctx_group"):
            # model-parallel stage boundary: the relu's outputs belong to
            # a different device group — keep the nodes separate so the
            # PlaceDevice-role commit still happens
            continue
        fused.add(id(src))
        passthrough[id(n)] = id(src)
    return fused, passthrough


def _graph_consumers(symbol, topo):
    """(node-output -> consumer nodes) index + the symbol's output set."""
    consumers = {}
    for n in topo:
        if n.op is None:
            continue
        for (src, i) in n.inputs:
            consumers.setdefault((id(src), i), []).append(n)
    out_entries = {(id(n), i) for (n, i) in symbol._outputs}
    return consumers, out_entries


def _dead_bias_convs(symbol, topo):
    """Mark Convolution/FullyConnected nodes whose bias gradient is exactly
    zero: a training-mode BatchNorm (batch statistics) is invariant to a
    per-channel constant shift of its input — mean subtraction cancels the
    bias — so when the linear op's only consumer is such a BN on the same
    channel axis, d(bias) == 0 identically. XLA cannot see this (it
    faithfully reduces the BN-transformed cotangent to an exact zero, one
    full pass over dy per conv, ~12% of the ResNet-50 step); the op's
    bias-add instead uses a vjp that returns a structural zero
    (ops/nn.py:_bias_add_dead_grad). Forward is unchanged, so running-stat
    EMAs and checkpoints with nonzero biases are unaffected.
    """
    consumers, out_entries = _graph_consumers(symbol, topo)
    dead = set()
    for n in topo:
        if n.op is None or n.op.name not in ("Convolution",
                                             "FullyConnected"):
            continue
        if len(n.inputs) < 3:   # no_bias
            continue
        cons = consumers.get((id(n), 0), [])
        if len(cons) != 1 or (id(n), 0) in out_entries:
            continue
        bn = cons[0]
        if bn.op is None or bn.op.name != "BatchNorm":
            continue
        battrs = bn.op.parse_attrs(bn.attrs)
        if battrs["use_global_stats"]:
            continue
        if bn.inputs[0][0] is not n:
            continue
        # the bias must broadcast exactly on the BN's channel axis: NCHW
        # convs put channels on axis 1; FC puts the bias on the LAST output
        # axis — (N, nh) when flatten=True (axis 1 == -1), arbitrary-rank
        # (..., nh) when flatten=False, where only axis == -1 is the bias
        # axis (a BN on axis 1 of a rank-3 output reduces OVER the bias
        # axis and the shift is not per-channel constant)
        if n.op.name == "Convolution" and battrs["axis"] != 1:
            continue
        if n.op.name == "FullyConnected":
            fattrs = n.op.parse_attrs(n.attrs)
            if fattrs["flatten"]:
                if battrs["axis"] not in (1, -1):
                    continue
            elif battrs["axis"] != -1:
                continue
        dead.add(id(n))
    return dead


def _build_runner(symbol, is_train, platform=None):
    """Emit run(arg_values: tuple, aux_values: tuple, rng) ->
    (outputs tuple, new_aux tuple). Pure; jit-compiled by the caller.
    (group2ctx model parallelism does NOT come through here — it runs
    per-stage compiled segments, see _SegmentedRunner.)
    """
    topo = symbol._topo()
    args_n, aux_n = symbol._input_vars()
    arg_index = {id(n): i for i, n in enumerate(args_n)}
    aux_index = {id(n): i for i, n in enumerate(aux_n)}
    node_pos = {id(n): i for i, n in enumerate(topo)}
    out_entries = [(node_pos[id(n)], i) for (n, i) in symbol._outputs]

    # MXNET_BACKWARD_DO_MIRROR (docs/faq/env_var.md; graph_executor mirror
    # pass): trade FLOPs for HBM by rematerializing each op's internals in
    # the backward — jax.checkpoint per node keeps only op-boundary
    # activations live, the TPU-native realization of activation mirroring
    from . import config as _config
    do_mirror = is_train and bool(_config.get("MXNET_BACKWARD_DO_MIRROR"))

    # mxnet_tpu.amp autocast: every execution route (bind, Module.fit,
    # CachedOp, DataParallelTrainer, export) lowers through this runner,
    # so casting op inputs here per the ALLOW/WIDEN policy mixes
    # precision framework-wide. Identity when amp is off — the traced
    # program is unchanged, keeping fp32 results bit-identical. The amp
    # state is read at TRACE time: flip amp.init before binding.
    from . import amp as _amp

    # count rng consumers for key splitting
    rng_nodes = [id(n) for n in topo
                 if n.op is not None and n.op.needs_rng]
    rng_slot = {nid: i for i, nid in enumerate(rng_nodes)}
    fused_bn, bn_passthrough = _fuse_bn_relu(symbol, topo)
    dead_bias = _dead_bias_convs(symbol, topo) if is_train else set()

    # `mirror_stage` (AttrScope): under MXNET_BACKWARD_DO_MIRROR the nodes
    # that share a stage are rematerialised as ONE unit: the backward keeps
    # what enters the stage and what an op inside declared as the residuals
    # of its own backward (`_rematerialised`), and recomputes the rest (a
    # decoder layer keeps its residual stream and its flash kernel's out
    # and lse, not its mixer's activations)
    units = _mirror_units(topo, node_pos, out_entries) if do_mirror else None

    def run(arg_values, aux_values, rng):
        vals = [None] * len(topo)
        new_aux = list(aux_values)
        keys = jax.random.split(rng, max(1, len(rng_nodes))) \
            if rng_nodes else None
        if units:
            return _run_units(units, vals, new_aux, keys, arg_values)
        run_nodes(range(len(topo)), vals, new_aux, keys, arg_values,
                  do_mirror)
        outputs = tuple(vals[p][i] for (p, i) in out_entries)
        return outputs, tuple(new_aux)

    def _run_units(units, vals, new_aux, keys, arg_values):
        for variables, positions, ext, produced in units:
            run_nodes(variables, vals, new_aux, keys, arg_values, False)
            if ext is None:                 # a node outside every stage
                run_nodes(positions, vals, new_aux, keys, arg_values, True)
                continue

            def stage(ext_vals, aux_in, keys_in, _positions=positions,
                      _ext=ext, _produced=produced):
                local = [None] * len(topo)
                _fill(local, _ext, ext_vals)
                aux_out = list(aux_in)
                run_nodes(_positions, local, aux_out, keys_in, arg_values,
                          False)
                return [local[p][i] for (p, i) in _produced], aux_out

            outs, aux_out = _rematerialised(stage)(
                [vals[p][i] for (p, i) in ext], list(new_aux), keys)
            new_aux[:] = aux_out
            _fill(vals, produced, outs)
        outputs = tuple(vals[p][i] for (p, i) in out_entries)
        return outputs, tuple(new_aux)

    def run_nodes(positions, vals, new_aux, keys, arg_values, mirror_each):
        # `vals` and `new_aux` are the lists ONE call of `run` (or one
        # stage of it) made for itself: filled while that call is traced,
        # never kept between calls
        for pos in positions:
            node = topo[pos]
            if node.op is None:
                if id(node) in aux_index:
                    vals[pos] = (new_aux[aux_index[id(node)]],)  # analysis: allow=trace-state-mutation
                else:
                    vals[pos] = (arg_values[arg_index[id(node)]],)  # analysis: allow=trace-state-mutation
                continue
            if id(node) in bn_passthrough:
                # relu folded into the producing BatchNorm (fusion pass)
                src, _ = node.inputs[0]
                vals[pos] = vals[node_pos[id(src)]][:1]  # analysis: allow=trace-state-mutation
                continue
            parsed = node.op.parse_attrs(node.attrs)
            if id(node) in fused_bn:
                parsed["__fuse_relu__"] = True
            if id(node) in dead_bias:
                parsed["__bias_grad_dead__"] = True
            ins = [vals[node_pos[id(n2)]][i2] for (n2, i2) in node.inputs]
            # unconditional: besides the policy casts, this hook injects
            # the fp16 loss scale into loss-head cotangents whenever a
            # trace scale is set — which happens with amp globally off
            # too (DataParallelTrainer(dtype="float16") standalone)
            ins = _amp.cast_op_inputs(node.op.name, ins)
            key = keys[rng_slot[id(node)]] if id(node) in rng_slot else None
            octx = OpCtx(is_train=is_train, rng=key, platform=platform)
            # `profiler_scope` (AttrScope): the node's operations carry the
            # name in the profiler's metadata, forward and backward
            scope = node.user_attrs.get("profiler_scope")
            with jax.named_scope(scope) if scope else _NO_SCOPE:
                if mirror_each:
                    def _call(k, *a, _op=node.op, _p=parsed, _pf=platform):
                        return _op.fcompute(
                            _p, OpCtx(is_train=True, rng=k, platform=_pf),
                            *a)
                    res = _rematerialised(_call)(key, *ins)
                else:
                    res = node.op.fcompute(parsed, octx, *ins)
            if not isinstance(res, tuple):
                res = (res,)
            n_out = node.num_outputs()
            vals[pos] = res[:n_out]  # analysis: allow=trace-state-mutation
            if node.op.mutates_aux and (is_train or node.op.aux_always):
                for j, aux_i in enumerate(node.op.aux_indices):
                    n2, _ = node.inputs[aux_i]
                    if id(n2) in aux_index:
                        new_aux[aux_index[id(n2)]] = res[n_out + j]  # analysis: allow=trace-state-mutation

    return run


_NO_SCOPE = contextlib.nullcontext()


def _fill(vals, entries, values):
    """vals[pos][output] = value for each (pos, output) entry; a node's
    slot grows to the outputs that are set."""
    for (p, i), v in zip(entries, values):
        slot = list(vals[p] or ())
        slot += [None] * (i + 1 - len(slot))
        slot[i] = v
        vals[p] = slot  # analysis: allow=trace-state-mutation


def _mirror_units(topo, node_pos, out_entries):
    """Execution units for rematerialisation by stage, or None where no
    node carries `mirror_stage`: [(variables, positions, ext, produced)]
    in an order that respects the graph. `variables` are the variable nodes
    the unit reads, filled before it runs. A stage's unit has `ext`, the
    (pos, output) entries it reads from outside (variables among them),
    and `produced`, those of its own that are read outside or are outputs
    of the graph; a node outside every stage is a unit with ext None
    (checkpointed alone, as MXNET_BACKWARD_DO_MIRROR does without
    stages)."""
    stage_of = {}
    for pos, node in enumerate(topo):
        st = node.user_attrs.get("mirror_stage") if node.op is not None \
            else None
        if st is not None:
            stage_of[pos] = st
    if not stage_of:
        return None
    # units in the order of their last node: everything a stage reads from
    # outside comes before its last node in a topological order only if no
    # outside node depends on the stage and feeds it back, which would be a
    # cycle between units and is refused below
    members = {}
    for pos, st in stage_of.items():
        members.setdefault(st, []).append(pos)
    # who reads each (pos, output) entry, the graph's own outputs as -1
    readers = {e: {-1} for e in out_entries}
    for pos, node in enumerate(topo):
        for (n2, i2) in node.inputs:
            readers.setdefault((node_pos[id(n2)], i2), set()).add(pos)
    units, emitted = [], set()
    for pos, node in enumerate(topo):
        if node.op is None or pos in emitted:
            continue
        st = stage_of.get(pos)
        if st is None:
            var_ins = [node_pos[id(n2)] for (n2, _) in node.inputs
                       if n2.op is None]
            units.append((var_ins, [pos], None, None))
            emitted.add(pos)
            continue
        if pos != members[st][-1]:
            continue                      # emit the stage at its last node
        inside = set(members[st])
        variables, ext = [], []
        for p in members[st]:
            for (n2, i2) in topo[p].inputs:
                p2 = node_pos[id(n2)]
                if p2 in inside or (p2, i2) in ext:
                    continue
                if n2.op is None:
                    variables.append(p2)
                elif p2 not in emitted:
                    raise MXNetError(
                        f"mirror_stage {st!r}: node {topo[p].name!r} "
                        f"reads {n2.name!r}, which depends on the "
                        "stage's own nodes")
                ext.append((p2, i2))
        produced = sorted(e for e, by in readers.items()
                          if e[0] in inside and by - inside)
        units.append((variables, members[st], ext, produced))
        emitted.update(inside)
    return units


class _SegmentedRunner:
    """Per-stage compiled execution for group2ctx model parallelism.

    Role of the reference's PlaceDevice pass + per-device executor
    segments joined by _CrossDeviceCopy (graph_executor.cc:314,407): the
    topo order is partitioned into maximal runs of nodes on the same
    device; each run compiles ONCE into a jitted forward fn (and, for
    training, a jitted recompute-based backward fn), and the driver
    chains them with explicit `jax.device_put` transfers at stage
    boundaries. This replaces an eager per-op walk (python dispatch
    per node per step + a fresh jax.vjp retrace every step): per step the host now dispatches one call per stage, and
    nothing retraces after the first step.

    Within-jit `device_put` cannot express this (measured: XLA pins the
    whole program to one device and swallows interior placements), so
    the stage boundary must be a host-level dispatch boundary — which is
    exactly the reference's execution model for group2ctx.

    Notes vs the single-program path: the BN+ReLU fusion / dead-bias
    passes are not applied (XLA still fuses within each stage) and
    MXNET_BACKWARD_DO_MIRROR is ignored; aux reads see the step's
    original values (same as the fused path); backward recomputes each
    stage's forward inside its compiled backward (activation-recompute —
    one extra stage-forward of FLOPs, no retrace).
    """

    def __init__(self, symbol, is_train, group2dev, default_dev,
                 diff_arg_pos=()):
        self._is_train = is_train
        topo = symbol._topo()
        args_n, aux_n = symbol._input_vars()
        self._arg_index = {id(n): i for i, n in enumerate(args_n)}
        self._aux_index = {id(n): i for i, n in enumerate(aux_n)}
        self._n_args = len(args_n)
        node_pos = {id(n): i for i, n in enumerate(topo)}
        self._topo, self._node_pos = topo, node_pos
        self._out_entries = [(node_pos[id(n)], i)
                             for (n, i) in symbol._outputs]
        diff_arg_pos = frozenset(diff_arg_pos)
        rng_ids = [id(n) for n in topo if n.op is not None
                   and n.op.needs_rng]
        self._rng_slot = {nid: i for i, nid in enumerate(rng_ids)}
        self._n_rng = len(rng_ids)
        self._default_dev = default_dev

        # ---- segmentation: maximal same-device runs of op nodes -------
        runs = []
        for pos, node in enumerate(topo):
            if node.op is None:
                continue
            dev = _node_group_dev(node, group2dev) or default_dev
            if runs and runs[-1][0] == dev:
                runs[-1][1].append(pos)
            else:
                runs.append((dev, [pos]))

        # ---- per-segment IO analysis ----------------------------------
        consumed, produced = [], []
        for dev, poss in runs:
            pset = set(poss)
            c = []
            seen = set()
            for p in poss:
                for (n2, i2) in topo[p].inputs:
                    e = (node_pos[id(n2)], i2)
                    if e[0] not in pset and e not in seen:
                        seen.add(e)
                        c.append(e)
            consumed.append(c)
            produced.append({(p, i) for p in poss
                             for i in range(topo[p].num_outputs())})
        out_set = set(self._out_entries)
        self.segments = []
        for si, (dev, poss) in enumerate(runs):
            later = set().union(*consumed[si + 1:]) if si + 1 < len(runs) \
                else set()
            ext_out = sorted(produced[si] & (later | out_set))
            diff_in, nondiff_in = [], []
            for e in consumed[si]:
                n2 = topo[e[0]]
                if n2.op is None:
                    if id(n2) in self._aux_index:
                        nondiff_in.append(e)
                    elif self._arg_index[id(n2)] in diff_arg_pos:
                        diff_in.append(e)
                    else:
                        nondiff_in.append(e)
                else:
                    # cross-stage activation: always on the diff path
                    diff_in.append(e)
            aux_upd = []           # (aux leaf index, node pos, res slot j)
            if is_train or any(topo[p].op.aux_always for p in poss):
                for p in poss:
                    node = topo[p]
                    if node.op.mutates_aux and (is_train or
                                                node.op.aux_always):
                        for j, aux_i in enumerate(node.op.aux_indices):
                            n2, _ = node.inputs[aux_i]
                            if id(n2) in self._aux_index:
                                aux_upd.append(
                                    (self._aux_index[id(n2)], p, j))
            self.segments.append({
                "dev": dev, "pos": poss, "diff_in": diff_in,
                "nondiff_in": nondiff_in, "ext_out": ext_out,
                "aux_upd": aux_upd, "fwd": None, "bwd": None})
        self.trace_counts = [0] * len(self.segments)
        # producing device of each op position (cotangents accumulate on
        # the producer's device; the consumer-side transfer is explicit)
        self._dev_of_pos = {}
        for seg in self.segments:
            for p in seg["pos"]:
                self._dev_of_pos[p] = seg["dev"]

    # -- per-segment function construction ------------------------------
    def _seg_fn(self, si):
        seg = self.segments[si]
        topo, node_pos = self._topo, self._node_pos
        din = {e: i for i, e in enumerate(seg["diff_in"])}
        nin = {e: i for i, e in enumerate(seg["nondiff_in"])}
        platform = seg["dev"].platform
        is_train = self._is_train
        rng_slot = self._rng_slot

        def f(diff_ins, nondiff_ins, keys):
            self.trace_counts[si] += 1     # traces, not executions
            local = {}

            def val(e):
                if e in din:
                    return diff_ins[din[e]]
                if e in nin:
                    return nondiff_ins[nin[e]]
                return local[e]

            aux_news = {}
            for p in seg["pos"]:
                node = topo[p]
                parsed = node.op.parse_attrs(node.attrs)
                ins = [val((node_pos[id(n2)], i2))
                       for (n2, i2) in node.inputs]
                key = keys[rng_slot[id(node)]] \
                    if id(node) in rng_slot else None
                res = node.op.fcompute(
                    parsed, OpCtx(is_train=is_train, rng=key,
                                  platform=platform), *ins)
                if not isinstance(res, tuple):
                    res = (res,)
                for i in range(node.num_outputs()):
                    local[(p, i)] = res[i]
                for (aux_i, pp, j) in seg["aux_upd"]:
                    if pp == p:
                        aux_news[aux_i] = res[node.num_outputs() + j]
            return (tuple(local[e] for e in seg["ext_out"]),
                    tuple(aux_news[aux_i]
                          for (aux_i, _, _) in seg["aux_upd"]))
        return f

    def _fns(self, si):
        seg = self.segments[si]
        if seg["fwd"] is None:
            f = self._seg_fn(si)
            seg["fwd"] = jax.jit(f)

            def bwd(diff_ins, nondiff_ins, keys, cts):
                _, vjp_fn = jax.vjp(
                    lambda d: f(d, nondiff_ins, keys)[0], diff_ins)
                (g,) = vjp_fn(cts)
                return g
            seg["bwd"] = jax.jit(bwd)
        return seg["fwd"], seg["bwd"]

    # -- drivers ---------------------------------------------------------
    def _keys(self, rng):
        if not self._n_rng:
            return None
        return jax.random.split(rng, self._n_rng)

    def _gather(self, seg, entries, vals, arg_values, aux_values):
        out = []
        for e in entries:
            n2 = self._topo[e[0]]
            if n2.op is None:
                v = aux_values[self._aux_index[id(n2)]] \
                    if id(n2) in self._aux_index \
                    else arg_values[self._arg_index[id(n2)]]
            else:
                v = vals[e]
            out.append(jax.device_put(v, seg["dev"]))
        return tuple(out)

    def _run_forward(self, arg_values, aux_values, rng):
        """Returns (vals, new_aux, cache) — cache holds each segment's
        placed inputs for the backward drivers."""
        vals, cache = {}, []
        new_aux = list(aux_values)
        keys = self._keys(rng)
        for si, seg in enumerate(self.segments):
            fwd, _ = self._fns(si)
            d = self._gather(seg, seg["diff_in"], vals, arg_values,
                             aux_values)
            nd = self._gather(seg, seg["nondiff_in"], vals, arg_values,
                              aux_values)
            k = jax.device_put(keys, seg["dev"]) \
                if keys is not None else ()
            outs, aux_news = fwd(d, nd, k)
            for e, v in zip(seg["ext_out"], outs):
                vals[e] = v
            for (aux_i, _, _), v in zip(seg["aux_upd"], aux_news):
                new_aux[aux_i] = v
            cache.append((d, nd, k))
        return vals, tuple(new_aux), cache

    def _out_value(self, e, vals, arg_values, aux_values):
        """Resolve an output entry: op outputs from the segment vals,
        bare-Variable outputs (Group([Variable, ...])) straight from the
        leaf values — parity with _build_runner, which fills vals for
        null nodes too."""
        n2 = self._topo[e[0]]
        if n2.op is None:
            return aux_values[self._aux_index[id(n2)]] \
                if id(n2) in self._aux_index \
                else arg_values[self._arg_index[id(n2)]]
        return vals[e]

    def forward(self, arg_values, aux_values, rng):
        vals, new_aux, _ = self._run_forward(arg_values, aux_values, rng)
        return tuple(self._out_value(e, vals, arg_values, aux_values)
                     for e in self._out_entries), new_aux

    def forward_backward(self, arg_values, aux_values, rng, cts=None):
        """Returns (outputs, new_aux, arg_grads) with arg_grads a tuple
        over ALL symbol arguments (None where no gradient flowed)."""
        vals, new_aux, cache = self._run_forward(arg_values, aux_values,
                                                 rng)
        outputs = tuple(self._out_value(e, vals, arg_values, aux_values)
                        for e in self._out_entries)
        ct_map = {}
        arg_grads = [None] * self._n_args
        if cts is None:
            cts = tuple(jnp.ones_like(o) for o in outputs)
        for e, ct in zip(self._out_entries, cts):
            n2 = self._topo[e[0]]
            if n2.op is None:
                # bare-Variable output: its cotangent IS the arg grad
                if id(n2) in self._arg_index:
                    p = self._arg_index[id(n2)]
                    ct = jax.device_put(ct, self._default_dev)
                    arg_grads[p] = ct if arg_grads[p] is None \
                        else arg_grads[p] + ct
                continue
            ct = jax.device_put(ct, self._dev_of_pos[e[0]])
            ct_map[e] = ct_map[e] + ct if e in ct_map else ct
        for si in range(len(self.segments) - 1, -1, -1):
            seg = self.segments[si]
            if not seg["diff_in"]:
                continue
            _, bwd = self._fns(si)
            d, nd, k = cache[si]
            seg_cts = tuple(
                jax.device_put(ct_map[e], seg["dev"]) if e in ct_map
                else jnp.zeros_like(vals[e])
                for e in seg["ext_out"])
            grads = bwd(d, nd, k, seg_cts)
            for e, g in zip(seg["diff_in"], grads):
                if g is None or getattr(g, "dtype", None) == \
                        jax.dtypes.float0:
                    continue
                n2 = self._topo[e[0]]
                if n2.op is None:
                    p = self._arg_index[id(n2)]
                    g = jax.device_put(g, self._default_dev)
                    arg_grads[p] = g if arg_grads[p] is None \
                        else arg_grads[p] + g
                else:
                    g = jax.device_put(g, self._dev_of_pos[e[0]])
                    ct_map[e] = ct_map[e] + g if e in ct_map else g
        return outputs, new_aux, tuple(arg_grads)


class Executor:
    def __init__(self, symbol, ctx, arg_dict, grad_dict, grad_req_dict,
                 aux_dict, mesh=None, sharded_args=(), group2ctx=None):
        from .ndarray.ndarray import NDArray
        self._symbol = symbol
        self._ctx = ctx or current_context()
        # model-parallel ctx groups (simple_bind(group2ctx=...)): outputs of
        # tagged nodes are committed to their group's device in-program
        self._group2dev = None
        if group2ctx:
            if mesh is not None:
                raise MXNetError(
                    "group2ctx model parallelism cannot be combined with a "
                    "data-parallel mesh executor")
            self._group2dev = {g: c.jax_device()
                               for g, c in group2ctx.items()}
        # Multi-device data parallelism: ONE program sharded over `mesh`
        # (role of DataParallelExecutorGroup's per-device executor replicas,
        # executor_group.py:129). `sharded_args` (data/label names) are
        # batch-sharded on axis 0; params/aux replicated; XLA inserts the
        # gradient psum over ICI.
        self._mesh = mesh
        self._sharded_args = frozenset(sharded_args)
        if mesh is not None:
            from .parallel.mesh import replicated_sharding, batch_sharding
            self._repl_sharding = replicated_sharding(mesh)
            self._batch_sharding = batch_sharding(mesh)
        else:
            self._repl_sharding = self._batch_sharding = None
        self._arg_names = symbol.list_arguments()
        self._aux_names = symbol.list_auxiliary_states()
        self._output_names = symbol.list_outputs()
        self.arg_dict = arg_dict
        self.grad_dict = grad_dict
        self._grad_req = grad_req_dict
        self.aux_dict = aux_dict
        self.arg_arrays = [arg_dict[n] for n in self._arg_names]
        self.grad_arrays = [grad_dict.get(n) for n in self._arg_names]
        self.aux_arrays = [aux_dict[n] for n in self._aux_names]
        self.outputs = []
        self._monitor_callback = None
        self._monitor_all = False

        # graphs without rng consumers reuse one device-resident key per
        # executor: minting + uploading a key per forward() is a serial
        # host->device round-trip, pure overhead for the (common)
        # dropout-free eval path
        self._has_rng = any(n.op is not None and n.op.needs_rng
                            for n in symbol._topo())
        self._rng_const = None

        self._jit_eval = None
        self._jit_fwd_train = None     # train-mode forward only (no diff args)
        self._fused_ones = None        # fwd+bwd, ones cotangents, one XLA module
        self._fused_ct = None          # fwd+bwd with explicit out_grads
        self._diff_pos = None
        self._pending = None           # (diff_vals, other_vals, aux, rng)
        self._pending_grads = None     # grads from the fused ones-step

    # -- construction helpers ----------------------------------------------
    @staticmethod
    def _simple_bind(symbol, ctx, grad_req, type_dict, shape_kwargs,
                     mesh=None, sharded_args=(), group2ctx=None):
        from .ndarray import ndarray as ndmod
        arg_shapes, out_shapes, aux_shapes = symbol.infer_shape(**shape_kwargs)
        arg_names = symbol.list_arguments()
        aux_names = symbol.list_auxiliary_states()
        type_dict = type_dict or {}
        arg_dict, grad_dict, req_dict = {}, {}, {}
        if isinstance(grad_req, str):
            reqs = {n: grad_req for n in arg_names}
        elif isinstance(grad_req, dict):
            reqs = {n: grad_req.get(n, "null") for n in arg_names}
        else:
            reqs = {n: r for n, r in zip(arg_names, grad_req)}
        for n, s in zip(arg_names, arg_shapes):
            dt = type_dict.get(n, "float32")
            arg_dict[n] = ndmod.zeros(s, ctx=ctx, dtype=dt)
            if reqs[n] != "null":
                grad_dict[n] = ndmod.zeros(s, ctx=ctx, dtype=dt)
            req_dict[n] = reqs[n]
        aux_dict = {n: ndmod.zeros(s, ctx=ctx)
                    for n, s in zip(aux_names, aux_shapes)}
        return Executor(symbol, ctx, arg_dict, grad_dict, req_dict, aux_dict,
                        mesh=mesh, sharded_args=sharded_args,
                        group2ctx=group2ctx)

    @staticmethod
    def _bind(symbol, ctx, args, args_grad, grad_req, aux_states,
              group2ctx=None):
        from .ndarray.ndarray import NDArray
        from .ndarray import ndarray as ndmod
        arg_names = symbol.list_arguments()
        aux_names = symbol.list_auxiliary_states()
        if isinstance(args, (list, tuple)):
            arg_dict = dict(zip(arg_names, args))
        else:
            arg_dict = dict(args)
        missing = [n for n in arg_names if n not in arg_dict]
        if missing:
            raise MXNetError(f"bind: missing arguments {missing}")
        if args_grad is None:
            grad_dict = {}
        elif isinstance(args_grad, (list, tuple)):
            grad_dict = dict(zip(arg_names, args_grad))
        else:
            grad_dict = dict(args_grad)
        if isinstance(grad_req, str):
            req = {n: (grad_req if n in grad_dict or args_grad is None
                       else "null") for n in arg_names}
            if args_grad is None:
                req = {n: "null" for n in arg_names}
        elif isinstance(grad_req, dict):
            req = {n: grad_req.get(n, "null") for n in arg_names}
        else:
            req = dict(zip(arg_names, grad_req))
        if aux_states is None:
            aux_dict = {}
            if aux_names:
                _, _, aux_shapes = symbol.infer_shape(
                    **{n: a.shape for n, a in arg_dict.items()})
                aux_dict = {n: ndmod.zeros(s, ctx=ctx)
                            for n, s in zip(aux_names, aux_shapes)}
        elif isinstance(aux_states, (list, tuple)):
            aux_dict = dict(zip(aux_names, aux_states))
        else:
            aux_dict = dict(aux_states)
        return Executor(symbol, ctx, arg_dict, grad_dict, req, aux_dict,
                        group2ctx=group2ctx)

    # -- execution ----------------------------------------------------------
    def _arg_sharding(self, name):
        return self._batch_sharding if name in self._sharded_args \
            else self._repl_sharding

    def _arg_values(self):
        if self._mesh is None:
            return tuple(self.arg_dict[n]._data for n in self._arg_names)
        # re-commit to the mesh: no-op when already placed; heals arrays
        # rebound off-mesh (init_params, set_params, [:]=). Write the healed
        # array back so the broadcast happens once, not per batch.
        out = []
        for n in self._arg_names:
            nd = self.arg_dict[n]
            v = jax.device_put(nd._data, self._arg_sharding(n))
            nd._data = v
            out.append(v)
        return tuple(out)

    def _aux_values(self):
        if self._mesh is None:
            return tuple(self.aux_dict[n]._data for n in self._aux_names)
        out = []
        for n in self._aux_names:
            nd = self.aux_dict[n]
            v = jax.device_put(nd._data, self._repl_sharding)
            nd._data = v
            out.append(v)
        return tuple(out)

    def forward(self, is_train=False, **kwargs):
        from . import profiler
        if profiler.symbolic_enabled():
            return profiler.profile_op(
                f"Forward({self._symbol.name or 'graph'})",
                lambda: self._forward_impl(is_train, **kwargs))
        return self._forward_impl(is_train, **kwargs)

    def _forward_impl(self, is_train=False, **kwargs):
        from .ndarray.ndarray import NDArray
        from . import random as _random
        dev = self._ctx.jax_device()
        for k, v in kwargs.items():
            if k not in self.arg_dict:
                raise MXNetError(f"forward: unknown argument {k}")
            new = v._data if isinstance(v, NDArray) else v
            if not isinstance(new, jax.Array):
                new = _np.asarray(new)
            # incoming batch arrays may live on another device (host-side
            # iterators commit to cpu): the executor owns placement —
            # this is the reference's kCopyToGPU engine lane. Mesh mode
            # shards the batch axis across devices instead.
            if self._mesh is not None:
                if k in self._sharded_args and new.shape and \
                        new.shape[0] % self._mesh.devices.size != 0:
                    raise MXNetError(
                        f"forward: batch size {new.shape[0]} of '{k}' must "
                        f"be divisible by the {self._mesh.devices.size}-"
                        "device mesh (pad or drop the last batch, e.g. "
                        "NDArrayIter(..., last_batch_handle='discard'))")
                target = self._arg_sharding(k)
            else:
                target = dev
            self.arg_dict[k]._data = jax.device_put(new, target)

        if self._has_rng:
            rng = jax.device_put(
                _random.next_key(),
                self._repl_sharding if self._mesh is not None else dev)
        else:
            if self._rng_const is None:
                self._rng_const = jax.device_put(
                    jax.random.PRNGKey(0),
                    self._repl_sharding if self._mesh is not None else dev)
            rng = self._rng_const  # unused by the traced program
        if self._monitor_callback is not None:
            if not is_train:
                self._pending = self._pending_grads = None
                return self._forward_monitored(False, rng)
            # tap every node eagerly for the monitor, but keep the fused
            # backward available: stash the pre-forward values; backward()
            # re-runs the fused program from them (debug path, pays 2x)
            if self._fused_ones is None:
                self._build_train_fns()
            diff_vals, other_vals = self._split_argv(self._arg_values())
            self._pending = (diff_vals, other_vals, self._aux_values(), rng)
            self._pending_grads = None
            return self._forward_monitored(True, rng)
        if is_train:
            outputs, new_aux = self._forward_train(rng)
        else:
            if self._jit_eval is None:
                if self._group2dev:
                    # group2ctx: per-stage jitted segments (see
                    # _SegmentedRunner / _build_train_fns)
                    seg_eval = _SegmentedRunner(
                        self._symbol, False, self._group2dev,
                        self._ctx.jax_device())
                    self._segmented_eval = seg_eval
                    self._jit_eval = seg_eval.forward
                else:
                    run_eval = _build_runner(
                        self._symbol, False,
                        platform=self._ctx.jax_device().platform)
                    self._jit_eval = jax.jit(run_eval)
            outputs, new_aux = self._jit_eval(
                self._arg_values(), self._aux_values(), rng)
            self._pending = self._pending_grads = None
        for n, v in zip(self._aux_names, new_aux):
            self.aux_dict[n]._data = v
        self.outputs = [NDArray(o) for o in outputs]
        return self.outputs

    def _build_train_fns(self):
        """One fused fwd+bwd XLA executable per executor (jax re-keys on
        shapes). Built once: re-running jax.vjp per batch would
        re-trace the whole graph every step."""
        n_args = len(self._arg_names)
        diff_pos = [i for i, n in enumerate(self._arg_names)
                    if self._grad_req.get(n, "null") != "null"]
        other_pos = [i for i in range(n_args) if i not in set(diff_pos)]
        self._diff_pos = diff_pos

        def _assemble(diff_vals, other_vals):
            args = [None] * n_args
            for p, v in zip(diff_pos, diff_vals):
                args[p] = v
            for p, v in zip(other_pos, other_vals):
                args[p] = v
            return tuple(args)

        if self._group2dev:
            # model-parallel executors run per-STAGE jitted segments
            # (_SegmentedRunner): one compiled subprogram per contiguous
            # ctx_group, cached across steps, with explicit device_put
            # transfers between stages. (Whole-graph jit cannot express
            # this: XLA pins one device per program and swallows interior
            # device_puts — measured.) The fused single-program machinery
            # below is not built at all on this branch.
            seg = _SegmentedRunner(self._symbol, True, self._group2dev,
                                   self._ctx.jax_device(),
                                   diff_arg_pos=diff_pos)
            self._segmented_train = seg

            def seg_fwd_bwd(d, o, a, r, cts=None):
                args = _assemble(d, o)
                outputs, new_aux, arg_grads = seg.forward_backward(
                    args, a, r, cts)
                # disconnected-but-requested grads are zeros (vjp parity)
                return outputs, new_aux, tuple(
                    arg_grads[p] if arg_grads[p] is not None
                    else jnp.zeros_like(args[p]) for p in diff_pos)

            self._fused_ones = lambda d, o, a, r: seg_fwd_bwd(d, o, a, r)
            self._fused_ct = seg_fwd_bwd
            self._jit_fwd_train = \
                lambda d, o, a, r: seg.forward(_assemble(d, o), a, r)
            return

        run = _build_runner(self._symbol, True,
                            platform=self._ctx.jax_device().platform)

        def merged(diff_vals, other_vals, aux, rng):
            return run(_assemble(diff_vals, other_vals), aux, rng)

        repl = self._repl_sharding

        def fwd_bwd(diff_vals, other_vals, aux, rng, cts):
            outputs, vjp_fn, new_aux = jax.vjp(
                lambda d: merged(d, other_vals, aux, rng),
                diff_vals, has_aux=True)
            if cts is None:
                cts = tuple(jnp.ones_like(o) for o in outputs)
            (dgrads,) = vjp_fn(tuple(cts))
            if repl is not None:
                # pin grads/aux to replicated so the batch-reduction psum
                # happens inside this program, not lazily downstream
                dgrads = tuple(jax.lax.with_sharding_constraint(g, repl)
                               for g in dgrads)
                new_aux = tuple(jax.lax.with_sharding_constraint(a, repl)
                                for a in new_aux)
            return outputs, new_aux, dgrads

        self._fused_ones = jax.jit(
            lambda d, o, a, r: fwd_bwd(d, o, a, r, None))
        self._fused_ct = jax.jit(fwd_bwd)
        self._jit_fwd_train = jax.jit(merged)

    def _split_argv(self, argv):
        diff_set = set(self._diff_pos)
        return (tuple(argv[p] for p in self._diff_pos),
                tuple(v for p, v in enumerate(argv) if p not in diff_set))

    def _forward_train(self, rng):
        if self._fused_ones is None:
            self._build_train_fns()
        diff_vals, other_vals = self._split_argv(self._arg_values())
        aux = self._aux_values()
        if not diff_vals:
            # nothing differentiable: plain train-mode forward; backward()
            # after this is a no-op (not an error) — every grad_req is null
            outputs, new_aux = self._jit_fwd_train(
                diff_vals, other_vals, aux, rng)
            self._pending, self._pending_grads = None, ()
            return outputs, new_aux
        # the fused program computes fwd+bwd in one XLA module; grads are
        # stashed for backward() (async — nothing blocks here)
        outputs, new_aux, dgrads = self._fused_ones(
            diff_vals, other_vals, aux, rng)
        self._pending = (diff_vals, other_vals, aux, rng)
        self._pending_grads = dgrads
        return outputs, new_aux

    def _diff_names(self):
        return [self._arg_names[p] for p in self._diff_pos]

    def _forward_monitored(self, is_train, rng):
        """Un-fused eager execution calling the monitor per node (parity:
        executor monitor callback, graph_executor.cc:1451)."""
        from .ndarray.ndarray import NDArray
        symbol = self._symbol
        base_platform = self._ctx.jax_device().platform
        group2dev = self._group2dev
        topo = symbol._topo()
        args_n, aux_n = symbol._input_vars()
        arg_index = {id(n): i for i, n in enumerate(args_n)}
        aux_index = {id(n): i for i, n in enumerate(aux_n)}
        node_pos = {id(n): i for i, n in enumerate(topo)}
        vals = [None] * len(topo)
        argv, auxv = self._arg_values(), list(self._aux_values())
        # same key-splitting discipline as _build_runner so the monitored
        # forward and the fused backward see identical random draws
        rng_nodes = [id(n) for n in topo
                     if n.op is not None and n.op.needs_rng]
        rng_slot = {nid: i for i, nid in enumerate(rng_nodes)}
        keys = jax.random.split(rng, max(1, len(rng_nodes))) \
            if rng_nodes else None
        for pos, node in enumerate(topo):
            if node.op is None:
                vals[pos] = ((auxv[aux_index[id(node)]],)
                             if id(node) in aux_index
                             else (argv[arg_index[id(node)]],))
                continue
            parsed = node.op.parse_attrs(node.attrs)
            ins = [vals[node_pos[id(n2)]][i2] for (n2, i2) in node.inputs]
            if self._monitor_all:
                in_names = node.op.list_inputs(parsed)
                for i, v in enumerate(ins):
                    nm = in_names[i] if i < len(in_names) else str(i)
                    self._monitor_callback(f"{node.name}_{nm}", NDArray(v))
            key = keys[rng_slot[id(node)]] if id(node) in rng_slot else None
            grp_dev = _node_group_dev(node, group2dev)
            node_platform = grp_dev.platform if grp_dev is not None \
                else base_platform
            res = node.op.fcompute(
                parsed, OpCtx(is_train=is_train, rng=key,
                              platform=node_platform),
                *ins)
            if not isinstance(res, tuple):
                res = (res,)
            if grp_dev is not None:
                # commit outputs to the group's device (fused-path parity:
                # the monitored forward must place like _build_runner)
                res = tuple(jax.device_put(r, grp_dev) for r in res)
            n_out = node.num_outputs()
            vals[pos] = res[:n_out]
            for i in range(n_out):
                out_name = f"{node.name}_output{i if n_out > 1 else ''}" \
                    if n_out > 1 else f"{node.name}_output"
                self._monitor_callback(out_name, NDArray(res[i]))
            if node.op.mutates_aux and (is_train or node.op.aux_always):
                for j, aux_i in enumerate(node.op.aux_indices):
                    n2, _ = node.inputs[aux_i]
                    if id(n2) in aux_index:
                        auxv[aux_index[id(n2)]] = res[n_out + j]
        out_entries = [(node_pos[id(n)], i) for (n, i) in symbol._outputs]
        for n, v in zip(self._aux_names, auxv):
            self.aux_dict[n]._data = v
        self.outputs = [NDArray(vals[p][i]) for (p, i) in out_entries]
        return self.outputs

    def backward(self, out_grads=None, is_train=True):
        from . import profiler
        if profiler.symbolic_enabled():
            return profiler.profile_op(
                f"Backward({self._symbol.name or 'graph'})",
                lambda: self._backward_impl(out_grads, is_train))
        return self._backward_impl(out_grads, is_train)

    def _backward_impl(self, out_grads=None, is_train=True):
        # out_grads=None (the dominant path) reuses the grads computed by the
        # fused ones-cotangent step — zero extra work. Explicit out_grads
        # re-runs the fused program with the given cotangents: callers
        # chaining executors pay one extra fwd+bwd.
        from .ndarray.ndarray import NDArray
        if self._pending is None and self._pending_grads is None:
            raise MXNetError("backward called before forward(is_train=True)")
        if not self._diff_pos:
            return  # every grad_req is 'null'
        if out_grads is None:
            if self._pending_grads is not None:
                dgrads = self._pending_grads  # from the fused ones-step
            else:
                _, _, dgrads = self._fused_ones(*self._pending)
        else:
            if isinstance(out_grads, NDArray):
                out_grads = [out_grads]
            dev = self._ctx.jax_device()
            # cotangents may arrive on another device (e.g. default-ctx
            # NDArrays); the executor owns placement
            grads_in = tuple(jax.device_put(
                g._data if isinstance(g, NDArray) else jnp.asarray(g), dev)
                for g in out_grads)
            _, _, dgrads = self._fused_ct(*self._pending, grads_in)
        for n, g in zip(self._diff_names(), dgrads):
            req = self._grad_req.get(n, "null")
            if req == "null" or n not in self.grad_dict:
                continue
            if req == "add":
                self.grad_dict[n]._data = self.grad_dict[n]._data + g
            else:
                self.grad_dict[n]._data = g

    # -- parity helpers ------------------------------------------------------
    @property
    def output_dict(self):
        return dict(zip(self._output_names, self.outputs))

    def set_monitor_callback(self, callback, monitor_all=False):
        """Tap every node output (graph_executor.cc:1451 role). While a
        callback is installed the forward runs the UNFUSED graph eagerly
        (_forward_monitored), so monitored intermediates match the
        per-node semantics — BN outputs are pre-relu even though the
        normal path folds relu into BN (same discipline as cuDNN fusion
        being bypassed under debugging). Backward still runs the fused
        program from stashed inputs, paying ~2x forward cost.
        monitor_all additionally taps every node INPUT (named
        ``{node}_{input_name}``), the reference's monitor_all=True."""
        self._monitor_callback = callback
        self._monitor_all = bool(monitor_all)

    def copy_params_from(self, arg_params, aux_params=None,
                         allow_extra_params=False):
        for k, v in arg_params.items():
            if k in self.arg_dict:
                self.arg_dict[k]._data = v._data.astype(
                    self.arg_dict[k].dtype)
            elif not allow_extra_params:
                raise MXNetError(f"unknown parameter {k}")
        if aux_params:
            for k, v in aux_params.items():
                if k in self.aux_dict:
                    self.aux_dict[k]._data = v._data
                elif not allow_extra_params:
                    raise MXNetError(f"unknown aux state {k}")

    def reshape(self, partial_shaping=False, allow_up_sizing=False, **kwargs):
        from .ndarray import ndarray as ndmod
        arg_shapes, _, aux_shapes = self._symbol.infer_shape(**kwargs)
        arg_dict, grad_dict = {}, {}
        for n, s in zip(self._arg_names, arg_shapes):
            old = self.arg_dict[n]
            if tuple(old.shape) == tuple(s):
                arg_dict[n] = old
                if n in self.grad_dict:
                    grad_dict[n] = self.grad_dict[n]
            else:
                arg_dict[n] = ndmod.zeros(s, ctx=self._ctx,
                                          dtype=str(old.dtype))
                if n in self.grad_dict:
                    grad_dict[n] = ndmod.zeros(s, ctx=self._ctx)
        aux_dict = {n: (self.aux_dict[n]
                        if tuple(self.aux_dict[n].shape) == tuple(s)
                        else ndmod.zeros(s, ctx=self._ctx))
                    for n, s in zip(self._aux_names, aux_shapes)}
        return Executor(self._symbol, self._ctx, arg_dict, grad_dict,
                        dict(self._grad_req), aux_dict, mesh=self._mesh,
                        sharded_args=self._sharded_args)
