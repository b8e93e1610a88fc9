"""Data-parallel training: one compiled step over a device mesh.

Role of the reference stack {DataParallelExecutorGroup → kvstore device/NCCL
reduce → optimizer update ops} (SURVEY.md §2.3, §3.1-3.5), collapsed into a
single pjit-sharded XLA program: fwd + bwd + grad-psum + SGD/momentum update.
Gradient reduction is implicit — the loss sums over the batch axis that is
sharded across the mesh, so XLA emits the psum over ICI; no push/pull, no
per-device executor replicas, no host round-trips inside the step.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as _np

from ..base import MXNetError
from ..executor import _build_runner
from .mesh import data_axis as _mesh_data_axis


# optimizer name -> fused update op (ops/optimizer_ops.py). All state
# tensors are zeros-initialized; Adam gets the python-optimizer bias
# correction folded into a traced lr (optimizer.py Adam parity).
_OPT_OPS = {
    "sgd": lambda kw: ("sgd_mom_update" if kw.get("momentum")
                       else "sgd_update"),
    "adam": "adam_update",
    "rmsprop": "rmsprop_update",
    "rmspropalex": "rmspropalex_update",
    "ftrl": "ftrl_update",
    "signsgd": "signsgd_update",
    "signum": "signum_update",
    "ftml": "ftml_update",
}


class DataParallelTrainer:
    """Compile a full training step for a Symbol over a 1-D data mesh.

    Parameters are replicated; `data_names`/`label_names` inputs are sharded
    on axis 0 over the mesh's `data` axis. The optimizer update (any op in
    _OPT_OPS) is fused into the step; the learning rate and step count ride
    as traced scalars so schedules never retrace. This is the engine of
    the fused training loop (parallel/fused_loop.py) and of the
    dryrun_multichip driver hook.
    """

    def __new__(cls, *args, **kwargs):
        # MXNET_ZERO_STAGE (or an explicit zero_stage kwarg) reroutes
        # plain DataParallelTrainer construction to the ZeRO-sharded
        # engine (parallel/zero.py) — same constructor surface, same
        # step contract, sharded masters/optimizer state. Subclasses
        # dispatch themselves, so only direct construction reroutes.
        if cls is DataParallelTrainer:
            from .zero import resolve_stage, ZeroTrainer
            if resolve_stage(kwargs.get("zero_stage")) > 0:
                return object.__new__(ZeroTrainer)
        return object.__new__(cls)

    def __init__(self, symbol, mesh, data_names=("data",),
                 label_names=("softmax_label",), optimizer="sgd",
                 learning_rate=0.01, momentum=0.0, wd=0.0, rescale_grad=None,
                 clip_gradient=None, loss_index=0, dtype="float32",
                 input_preproc=None, loss_scaler=None, param_specs=None,
                 zero_stage=None, zero_bucket_mb=None, grad_compress=None,
                 **opt_kwargs):
        # zero_stage/zero_bucket_mb/grad_compress belong to the ZeRO
        # subclass; accepted (and ignored) here so a stage-0 run can keep
        # them in its construction kwargs
        from jax.sharding import NamedSharding, PartitionSpec as P
        from ..ops.registry import get_op, AttrDict, OpCtx

        self._symbol = symbol
        self._mesh = mesh
        self._data_axis = _mesh_data_axis(mesh)
        arg_names = symbol.list_arguments()
        self._arg_names = arg_names
        self._aux_names = symbol.list_auxiliary_states()
        input_names = list(data_names) + list(label_names)
        self._input_names = [n for n in arg_names if n in input_names]
        self._param_names = [n for n in arg_names if n not in input_names]
        self._param_pos = [arg_names.index(n) for n in self._param_names]
        self._input_pos = [arg_names.index(n) for n in self._input_names]
        self._lr = float(learning_rate)
        self._loss_index = loss_index
        self._t = 0
        # device-carried step state (see step()): rng key, lr, step count
        self._rng_dev = None
        self._lr_dev = None
        self._t_dev = None
        if dtype not in ("float32", "bfloat16", "float16"):
            raise MXNetError("DataParallelTrainer dtype must be float32, "
                             "bfloat16 or float16")
        # half precision = multi-precision training (reference optimizer
        # multi_precision, SURVEY §7 hard-part 5): fp32 master params/aux,
        # compute + activations + the gradient all-reduce in the half
        # dtype, grads upcast into the fused fp32 update. The half-width
        # all-reduce halves the wire bytes of the dp step (asserted by
        # `python -m mxnet_tpu.amp --hlo-check`); every cell of the
        # benchmark trains in bf16, so PERF_LEDGER.jsonl has no fp32 rate
        # to compare with.
        self._compute_bf16 = dtype == "bfloat16"
        self._dtype = dtype
        compute_dtype = {"float32": None, "bfloat16": jnp.bfloat16,
                         "float16": jnp.float16}[dtype]
        self._compute_dtype = compute_dtype
        # fp16's 5-bit exponent flushes small grads to zero and overflows
        # large activations: wire in dynamic loss scaling (amp/scaler.py)
        # with non-finite step skip. bf16 keeps fp32's exponent range and
        # needs none of this (docs/AMP.md).
        self._has_ls = dtype == "float16"
        if self._has_ls and loss_scaler is None:
            from ..amp.scaler import DynamicLossScaler
            loss_scaler = DynamicLossScaler()
        self._scaler = loss_scaler if self._has_ls else None
        self._ls_dev = None
        if self._has_ls:
            from .. import amp as _amp
            _amp._register_scale_source(self)

        hp = dict(opt_kwargs)
        if momentum:
            hp["momentum"] = momentum
        opt_op = _OPT_OPS.get(optimizer)
        if opt_op is None:
            raise MXNetError(
                f"DataParallelTrainer: fused optimizer {optimizer!r} not "
                f"supported ({sorted(_OPT_OPS)}); use Module+kvstore for "
                "host-updated optimizers")
        opname = opt_op(hp) if callable(opt_op) else opt_op
        schema = get_op(opname)
        self._opt_schema = schema
        # states = the op's aux inputs beyond (weight, grad)
        self._n_states = len(schema.input_names) - 2
        # built-in knobs are filtered to what the op takes; user opt_kwargs
        # go through UNfiltered so parse_attrs fails fast on typos
        attr_kwargs = {k: v for k, v in
                       {"lr": self._lr, "wd": wd,
                        "rescale_grad": 1.0 if rescale_grad is None
                        else rescale_grad,
                        "clip_gradient": clip_gradient,
                        "t": 1 if "t" in schema.params else None}.items()
                       if k in schema.params and v is not None}
        attr_kwargs.update(hp)
        attrs = schema.parse_attrs(attr_kwargs)

        run = _build_runner(symbol, is_train=True,
                            platform=mesh.devices.flat[0].platform)
        n_args = len(arg_names)
        param_pos = list(self._param_pos)
        input_pos = list(self._input_pos)
        loss_index = self._loss_index
        fcompute = schema.fcompute
        has_t = "t" in schema.params
        is_adam = optimizer == "adam"
        compute_dtype = self._compute_dtype
        has_ls = self._has_ls
        scaler = self._scaler
        data_name_set = frozenset(data_names)
        # what amp's policy says must reach its op as it is: fp32 decay
        # and router parameters, ids carried in float arrays
        from .. import amp as _amp
        exact = _amp.exact_variables(symbol) \
            if compute_dtype is not None else frozenset()
        cast_input = [arg_names[p] in data_name_set and
                      arg_names[p] not in exact for p in input_pos]
        cast_param = [n not in exact for n in self._param_names]
        # input_preproc(name, value) -> value runs INSIDE the compiled
        # step, before any bf16 cast — the device-side half of the
        # ship-uint8/normalize-on-chip input regime (pair with
        # ImageRecordIter(output_dtype="uint8")); XLA fuses it into the
        # first conv's input chain
        preproc_names = [arg_names[p] for p in input_pos]
        # the step-building surface, kept on self so subclasses
        # (parallel/zero.py) can assemble their own step program from the
        # same runner/optimizer-op plumbing
        self._run = run
        self._fcompute = fcompute
        self._attrs = attrs
        self._has_t = has_t
        self._is_adam = is_adam
        self._cast_input = cast_input
        self._preproc_names = preproc_names
        self._input_preproc = input_preproc

        def _step_impl(params, states, aux, inputs, rng, lr, t, ls):
            # rng and t are device-carried: split/increment INSIDE the
            # compiled step so the host never dispatches a per-step key
            # split or scalar transfer (each is a serializing host
            # round-trip)
            rng, next_rng = jax.random.split(rng)
            scale = ls[0] if has_ls else None

            # params are cast to the compute dtype OUTSIDE loss_fn and
            # differentiated AT the cast values: grad dtype == primal
            # dtype, so the batch-axis psum XLA inserts reduces
            # HALF-WIDTH words over ICI (the bf16 all-reduce). The fp32
            # upcast in the update below is the exact transpose of the
            # cast, so the update sees the same values as differentiating
            # the fp32 masters directly — only the all-reduce narrows.
            cparams = params if compute_dtype is None else tuple(
                jnp.asarray(v, compute_dtype) if cast else v
                for v, cast in zip(params, cast_param))

            def loss_fn(cparams):
                args = [None] * n_args
                for p, v in zip(param_pos, cparams):
                    args[p] = v
                for p, v, cast, nm in zip(input_pos, inputs, cast_input,
                                          preproc_names):
                    if input_preproc is not None:
                        v = input_preproc(nm, v)
                    # only FLOAT inputs cast: integer data (embedding token
                    # ids) would be corrupted by the half dtype's mantissa
                    args[p] = jnp.asarray(v, compute_dtype) \
                        if compute_dtype is not None and cast and \
                        jnp.issubdtype(v.dtype, jnp.floating) else v
                # aux (BN running stats) stays fp32: _batch_norm casts at
                # use sites, and the EMA update must accumulate in fp32 —
                # a half round-trip would quantize the running stats
                outputs, new_aux = run(tuple(args), aux, rng)
                # summing the (custom-vjp) head over the sharded batch is
                # what makes XLA insert the gradient psum over ICI
                loss = outputs[loss_index].sum().astype(jnp.float32)
                # fp16: backprop the SCALED loss so small-magnitude grads
                # stay representable; the unscaled loss rides has_aux.
                # NOTE this only reaches the gradients when the loss is an
                # ordinary differentiable value — the legacy loss heads
                # (SoftmaxOutput & co) IGNORE the incoming cotangent, so
                # for them the scale is injected below the head instead
                # (amp.LOSS_HEADS + the trace scale set around this trace)
                obj = loss * scale if has_ls else loss
                return obj, (new_aux, outputs, loss)

            if has_ls:
                from .. import amp as _amp
                _amp._set_trace_loss_scale(scale)
            try:
                (_, (new_aux, outputs, loss)), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(cparams)
            finally:
                if has_ls:
                    from .. import amp as _amp
                    _amp._set_trace_loss_scale(None)
            if has_ls:
                # overflow check on the SCALED half grads (post-psum):
                # any inf/nan skips the whole update and backs the scale
                # off (Micikevicius et al. 2018 §3.2)
                finite = jnp.asarray(True)
                for g in grads:
                    finite = jnp.logical_and(finite,
                                             jnp.all(jnp.isfinite(g)))
                # a skipped step is not an update: t (Adam bias
                # correction) advances only on applied steps
                t = t + jnp.where(finite, 1.0, 0.0)
                inv_scale = 1.0 / scale
            else:
                t = t + 1.0
            eff_lr = lr
            if is_adam:  # python Adam's bias correction (optimizer.py)
                b1, b2 = attrs["beta1"], attrs["beta2"]
                eff_lr = lr * jnp.sqrt(1.0 - b2 ** t) / (1.0 - b1 ** t)
            a2 = AttrDict(attrs)
            a2["lr"] = eff_lr
            if has_t:
                a2["t"] = t
            octx = OpCtx(is_train=True)
            new_params, new_states = [], []
            with jax.named_scope("mx.optimizer"):
                for w, g, st in zip(params, grads, states):
                    # upcast into the fused fp32 master update; fp16 also
                    # unscales — in fp32, so an overflowed grad stays inf
                    # (detectable above) instead of wrapping
                    if g.dtype != jnp.float32:
                        g = g.astype(jnp.float32)
                    if has_ls:
                        g = g * inv_scale
                    res = fcompute(a2, octx, w, g, *st)
                    if has_ls:
                        # skipped step: params/states stay bit-identical
                        new_params.append(jnp.where(finite, res[0], w))
                        new_states.append(tuple(
                            jnp.where(finite, s, s0)
                            for s, s0 in zip(res[1:], st)))
                    else:
                        new_params.append(res[0])
                        new_states.append(tuple(res[1:]))
            if has_ls:
                # an overflowed forward would poison BN running stats too
                new_aux = tuple(jnp.where(finite, a, a0)
                                for a, a0 in zip(new_aux, aux))
                new_ls = scaler.update_state(ls, finite)
                return (tuple(new_params), tuple(new_states), new_aux,
                        loss, outputs, next_rng, t, new_ls)
            return (tuple(new_params), tuple(new_states), new_aux, loss,
                    outputs, next_rng, t)

        # the loss-scaler state rides the step signature ONLY for fp16:
        # fp32/bf16 keep the 7-arg step so existing lower()/cost-analysis
        # call sites (__graft_entry__, chip_smoke.py) stay valid
        if has_ls:
            def step(params, states, aux, inputs, rng, lr, t, ls):
                return _step_impl(params, states, aux, inputs, rng, lr,
                                  t, ls)
        else:
            def step(params, states, aux, inputs, rng, lr, t):
                return _step_impl(params, states, aux, inputs, rng, lr,
                                  t, None)

        repl = NamedSharding(mesh, P())
        shard = NamedSharding(mesh, P(self._data_axis))
        # stacked (K, batch, ...) blocks for step_k: scan axis replicated,
        # batch axis (axis 1) sharded over the mesh
        self._block_shard = NamedSharding(mesh, P(None, self._data_axis))
        self._repl, self._shard = repl, shard
        # param_specs (name -> PartitionSpec) turns on GSPMD tensor
        # parallelism: the listed params (and their optimizer state) live
        # sharded over the named mesh axes and XLA's partitioner inserts
        # the megatron-style collectives around the matmuls. None keeps
        # today's replicated-params program BIT-identical (same jit, same
        # sharding tuple); unlisted params stay replicated.
        self._param_specs = None
        self._pshard = None
        if param_specs:
            self._param_specs = {str(k): v
                                 for k, v in dict(param_specs).items()}
            self._pshard = tuple(
                NamedSharding(mesh, self._param_specs.get(n, P()))
                for n in self._param_names)
        p_io = self._pshard if self._pshard is not None else repl
        self._step_py = step
        self._multi = {}   # (k, outputs_mode) -> jitted K-step scan
        ls_extra = (repl,) if has_ls else ()
        self._step = jax.jit(
            step,
            in_shardings=(p_io, p_io, repl, shard, repl, repl, repl)
            + ls_extra,
            out_shardings=(p_io, p_io, repl, repl, shard, repl, repl)
            + ls_extra,
            donate_argnums=(0, 1))

    def _multi_step_fn(self, k, outputs_mode):
        """K training steps fused into ONE compiled dispatch (a lax.scan
        over the single-step body). This is the op-bulking concern of the
        reference engine (graph_executor.cc:1343-1369) applied at step
        granularity: each python dispatch has a fixed host cost, which K
        fused steps pay once — what it is worth on this runtime is not
        measured yet (PERF.md).
        rng, the step counter and (fp16) the loss-scaler state are carried
        on-device across the scan, so K fused steps are bit-identical to K
        python-dispatched steps — including grow/backoff/skip decisions."""
        key = (int(k), outputs_mode)
        fn = self._multi.get(key)
        if fn is not None:
            return fn
        step = self._step_py

        if self._has_ls:
            def multi(params, states, aux, inputs, rng, lr, t, ls):
                def body(carry, xs):
                    params, states, aux, rng, t, ls = carry
                    (params, states, aux, loss, outputs, rng, t,
                     ls) = step(params, states, aux, xs, rng, lr, t, ls)
                    ys = (loss, outputs) if outputs_mode == "all" else loss
                    return (params, states, aux, rng, t, ls), ys

                (params, states, aux, rng, t, ls), ys = jax.lax.scan(
                    body, (params, states, aux, rng, t, ls), inputs,
                    length=key[0])
                if outputs_mode == "all":
                    losses, outputs = ys
                else:
                    losses, outputs = ys, ()
                return params, states, aux, losses, outputs, rng, t, ls
        else:
            def multi(params, states, aux, inputs, rng, lr, t):
                def body(carry, xs):
                    params, states, aux, rng, t = carry
                    params, states, aux, loss, outputs, rng, t = step(
                        params, states, aux, xs, rng, lr, t)
                    ys = (loss, outputs) if outputs_mode == "all" else loss
                    return (params, states, aux, rng, t), ys

                (params, states, aux, rng, t), ys = jax.lax.scan(
                    body, (params, states, aux, rng, t), inputs,
                    length=key[0])
                if outputs_mode == "all":
                    losses, outputs = ys
                else:
                    losses, outputs = ys, ()
                return params, states, aux, losses, outputs, rng, t

        repl, block = self._repl, self._block_shard
        p_io = self._pshard if self._pshard is not None else repl
        ls_extra = (repl,) if self._has_ls else ()
        fn = jax.jit(
            multi,
            in_shardings=(p_io, p_io, repl, block, repl, repl, repl)
            + ls_extra,
            out_shardings=(p_io, p_io, repl, repl,
                           block if outputs_mode == "all" else repl,
                           repl, repl) + ls_extra,
            donate_argnums=(0, 1))
        self._multi[key] = fn
        return fn

    def _param_sharding(self, i):
        """Placement of parameter i (and its optimizer state): its
        param_specs sharding under tensor parallelism, replicated
        otherwise."""
        return self._repl if self._pshard is None else self._pshard[i]

    @property
    def param_names(self):
        return list(self._param_names)

    @property
    def input_names(self):
        return list(self._input_names)

    @property
    def aux_names(self):
        return list(self._aux_names)

    def init_state(self, shape_kwargs, initializer=None, seed=0,
                   arg_params=None, aux_params=None):
        """Infer shapes from input shapes; return (params, states, aux)
        tuples of replicated jax arrays. `states` holds one tuple of
        optimizer-state arrays per parameter (momenta for sgd, mean/var for
        adam, ...). `arg_params`/`aux_params` (name -> NDArray/array)
        seed values directly — Module's fused fit hands over the params it
        already initialized so both fit paths start from the same draw."""
        arg_shapes, _, aux_shapes = self._symbol.infer_shape(**shape_kwargs)
        shapes = dict(zip(self._arg_names, arg_shapes))
        rng = _np.random.RandomState(seed)
        params = []
        for i, n in enumerate(self._param_names):
            s = shapes[n]
            if arg_params is not None and n in arg_params:
                a = arg_params[n]
                v = _np.asarray(getattr(a, "_data", a), _np.float32)
            elif initializer is not None:
                from ..ndarray.ndarray import zeros as nd_zeros
                arr = nd_zeros(s)
                from ..initializer import InitDesc
                initializer(InitDesc(n), arr)
                v = _np.asarray(arr._data)
            else:
                v = rng.normal(0, 0.01, size=s).astype(_np.float32)
            # host numpy straight onto the mesh (see shard_inputs)
            params.append(jax.device_put(v, self._param_sharding(i)))
        states = tuple(
            tuple(jax.device_put(_np.zeros(p.shape, p.dtype),
                                 self._param_sharding(i))
                  for _ in range(self._n_states))
            for i, p in enumerate(params))
        aux = tuple(jax.device_put(
            _np.asarray(getattr(aux_params[n], "_data", aux_params[n]),
                        _np.float32)
            if aux_params is not None and n in aux_params
            # moving/running variances start at 1 (MXNet BatchNorm parity)
            else _np.ones(s, _np.float32)
            if n.endswith(("moving_var", "running_var"))
            else _np.zeros(s, _np.float32), self._repl)
            for n, s in zip(self._aux_names, aux_shapes))
        return tuple(params), states, aux

    def shard_inputs(self, arrays, stacked=False):
        """Commit host batch arrays to the mesh, sharded on the batch axis.

        `stacked=False`: per-step (batch, ...) arrays, sharded on axis 0.
        `stacked=True`: (K, batch, ...) blocks for step_k — the scan axis
        stays replicated and axis 1 (batch) is sharded.

        Host numpy goes straight to the mesh sharding — never through
        `jnp.asarray`, which would commit to the *default* device first
        (wrong platform when the mesh is not on the default backend).
        """
        sharding = self._block_shard if stacked else self._shard
        out = []
        for a in arrays:
            a = getattr(a, "_data", a)
            if not isinstance(a, jax.Array):
                a = _np.asarray(a)
            out.append(jax.device_put(a, sharding))
        return tuple(out)

    @property
    def learning_rate(self):
        return self._lr

    def set_learning_rate(self, lr):
        """Schedules never retrace: lr is a traced input to the step."""
        self._lr = float(lr)
        self._lr_dev = None  # re-commit on next step

    def replicate_inputs(self, arrays):
        """Commit host arrays to the mesh, replicated (e.g. eval inputs)."""
        out = []
        for a in arrays:
            a = getattr(a, "_data", a)
            if not isinstance(a, jax.Array):
                a = _np.asarray(a)
            out.append(jax.device_put(a, self._repl))
        return tuple(out)

    def _ensure_dev_state(self, rng):
        if rng is not None:
            # explicit key (tests/reproducibility): commit it to the mesh —
            # it may have been minted on the default backend
            self._rng_dev = jax.device_put(rng, self._repl)
        elif self._rng_dev is None:
            from .. import random as _random
            self._rng_dev = jax.device_put(_random.next_key(), self._repl)
        if self._lr_dev is None:
            self._lr_dev = jax.device_put(_np.float32(self._lr), self._repl)
        if self._t_dev is None:
            self._t_dev = jax.device_put(_np.float32(self._t), self._repl)
        if self._has_ls and self._ls_dev is None:
            self._ls_dev = jax.device_put(self._scaler.state0(), self._repl)

    @property
    def loss_scale(self):
        """Live fp16 loss scale (None when loss scaling is inactive).
        Reads the device-carried scaler state, so it synchronizes."""
        if not self._has_ls:
            return None
        if self._ls_dev is None:
            return float(self._scaler.scale)
        return float(_np.asarray(self._ls_dev)[0])

    @property
    def skipped_steps(self):
        """Steps skipped on non-finite fp16 gradients so far."""
        if not self._has_ls:
            return 0
        if self._ls_dev is None:
            return int(self._scaler.skipped_steps)
        return int(_np.asarray(self._ls_dev)[2])

    def _amp_counters(self):
        """amp counter-export hook (amp.counters aggregates these)."""
        return {"amp_scale": self.loss_scale,
                "amp_skipped_steps": self.skipped_steps}

    # -- host views ---------------------------------------------------------

    def host_params(self, params):
        """name -> host numpy array for the trainer's params tuple. The
        generic spelling fused-fit loops must use for writeback: ZeRO
        subclasses carry flat sharded buckets instead of per-parameter
        replicas, and override this to unflatten them."""
        return {n: _np.asarray(p)
                for n, p in zip(self._param_names, params)}

    def host_aux(self, aux):
        """name -> host numpy array for the aux tuple (replicated on
        every trainer variant)."""
        return {n: _np.asarray(a) for n, a in zip(self._aux_names, aux)}

    # -- checkpoint round-trip ----------------------------------------------

    def _export_meta(self):
        """Scalar device-carried step state (t, rng chain position, fp16
        loss-scaler vector, exporting mesh) — shared by every trainer
        variant's export_training_state."""
        from .. import random as _random
        from .mesh import mesh_descriptor
        return {
            "t": float(self._t if self._t_dev is None
                       else _np.asarray(self._t_dev)),
            "rng": None if self._rng_dev is None
            else _random.key_data(self._rng_dev).ravel().tolist(),
            "loss_scaler": None if not (self._has_ls
                                        and self._ls_dev is not None)
            else [float(x) for x in _np.asarray(self._ls_dev)],
            # the exporting mesh, for the checkpoint TOPOLOGY record —
            # import_training_state ignores it (device_put onto the
            # CURRENT mesh is what reshards an elastic restore)
            "mesh": mesh_descriptor(self._mesh),
        }

    def _import_scalar_state(self, meta):
        """Inverse of _export_meta: restore t/rng/loss-scaler carries."""
        from .. import random as _random
        put = lambda v: jax.device_put(_np.asarray(v), self._repl)
        self._t = float(meta.get("t", 0.0))
        self._t_dev = put(_np.float32(self._t))
        if meta.get("rng") is not None:
            self._rng_dev = jax.device_put(_random.wrap_key(meta["rng"]),
                                           self._repl)
        ls = meta.get("loss_scaler")
        if ls is not None and self._has_ls:
            self._ls_dev = put(_np.asarray(ls, _np.float32))

    def export_training_state(self, params, states, aux):
        """Host snapshot of the full fused-loop training state: the
        (donated, device-carried) params/opt-states/aux tuples as numpy,
        plus the device-carried step counter, PRNG key chain position and
        fp16 loss-scaler vector. Everything mxnet_tpu.checkpoint needs for
        a bit-identical step_k continuation after restore. Must be called
        between dispatches (the tuples are invalidated by the next step's
        donation — copy now, serialize later)."""
        arrays = {}
        for n, p in zip(self._param_names, params):
            arrays[f"param:{n}"] = _np.asarray(p)
        for n, st in zip(self._param_names, states):
            for i, s in enumerate(st):
                arrays[f"opt:{n}:{i}"] = _np.asarray(s)
        for n, a in zip(self._aux_names, aux):
            arrays[f"aux:{n}"] = _np.asarray(a)
        return arrays, self._export_meta()

    def import_training_state(self, arrays, meta):
        """Inverse of export_training_state: re-commit a snapshot to the
        mesh. Returns (params, states, aux) replicated tuples ready for
        step/step_k; the internal t/rng/loss-scaler carries are restored
        so the continuation is bit-identical to the uninterrupted run."""
        put = lambda v: jax.device_put(_np.asarray(v), self._repl)
        pput = lambda v, i: jax.device_put(_np.asarray(v),
                                           self._param_sharding(i))
        params = tuple(pput(arrays[f"param:{n}"], i)
                       for i, n in enumerate(self._param_names))
        states = tuple(
            tuple(pput(arrays[f"opt:{n}:{j}"], i)
                  for j in range(self._n_states))
            for i, n in enumerate(self._param_names))
        aux = tuple(put(arrays[f"aux:{n}"]) for n in self._aux_names)
        self._import_scalar_state(meta)
        return params, states, aux

    def step(self, params, states, aux, inputs, rng=None):
        self._ensure_dev_state(rng)
        from ..telemetry import devstats
        if self._has_ls:
            args = (params, states, aux, inputs, self._rng_dev,
                    self._lr_dev, self._t_dev, self._ls_dev)
            devstats.on_dispatch("dp.step", self._step, args, steps=1)
            out = self._step(*args)
            self._ls_dev = out[7]
        else:
            args = (params, states, aux, inputs, self._rng_dev,
                    self._lr_dev, self._t_dev)
            devstats.on_dispatch("dp.step", self._step, args, steps=1)
            out = self._step(*args)
        # rng/t are device-carried (split/incremented inside the step): the
        # host never dispatches per-step key splits or scalar transfers
        self._rng_dev, self._t_dev = out[5], out[6]
        return out[:5]

    def step_k(self, params, states, aux, inputs, rng=None,
               outputs_mode="none"):
        """Run K fused training steps in ONE dispatch (steps_per_dispatch).

        `inputs` are (K, batch, ...) stacked blocks (shard_inputs with
        stacked=True); K is read off the leading axis and each distinct K
        compiles once (cached). Returns (params, states, aux, losses,
        outputs) where `losses` has shape (K,). `outputs_mode`:
          - "none" (default): outputs is () — nothing beyond the losses
            leaves the scan (an LSTM LM's stacked logits would be GBs).
          - "all": outputs are the symbol outputs of EVERY step, stacked
            on a leading K axis (Module's fused fit uses this to feed the
            training metric).
        Bit-identical to K step() calls from the same rng key: the scan
        body IS the single-step body and the key chain is the same splits.
        """
        self._ensure_dev_state(rng)
        k = int(inputs[0].shape[0])
        fn = self._multi_step_fn(k, outputs_mode)
        from ..telemetry import devstats
        if self._has_ls:
            args = (params, states, aux, inputs, self._rng_dev,
                    self._lr_dev, self._t_dev, self._ls_dev)
            devstats.on_dispatch("dp.step_k%d" % k, fn, args, steps=k)
            out = fn(*args)
            self._ls_dev = out[7]
        else:
            args = (params, states, aux, inputs, self._rng_dev,
                    self._lr_dev, self._t_dev)
            devstats.on_dispatch("dp.step_k%d" % k, fn, args, steps=k)
            out = fn(*args)
        self._rng_dev, self._t_dev = out[5], out[6]
        return out[:5]
