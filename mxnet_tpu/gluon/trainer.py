"""Gluon Trainer.

Parity target: python/mxnet/gluon/trainer.py (SURVEY.md §2.4, §3.2):
`_init_kvstore` (:112), `step` (:174), `_allreduce_grads` (:220),
`_update` (:261). Single-process: grads already live on the parameter's
context; multi-device DP rides the sharded step (mxnet_tpu.parallel), with
the kvstore facade kept for explicit push/pull training loops.

Similarity constraint note: the constructor signature, method names,
argument-validation messages and the step/allreduce/update decision flow
are pinned by the reference Trainer's public contract — downstream code
calls `trainer.step`, toggles `update_on_kvstore`, and relies on the
exact assertion wording. The update machinery underneath diverges from
the reference (which keeps one weight copy per device and reduces
through the kvstore): mesh-replicated parameters here expose ONE device
buffer through N ctx slots, so pushes/updates dedup on device-buffer
identity (`_buffer_key`/`_unique`) — machinery the reference does not
have or need.
"""
from __future__ import annotations

from ..base import MXNetError
from .. import optimizer as opt
from ..model import _create_kvstore
from .parameter import ParameterDict, Parameter

__all__ = ["Trainer", "fused_fit"]


def fused_fit(net, loss, train_data, num_epoch, optimizer="sgd",
              optimizer_params=None, steps_per_dispatch=None,
              contexts=None, dtype=None, epoch_callback=None,
              checkpoint_dir=None, checkpoint_period=None, resume=False):
    """K-steps-per-dispatch training driver for gluon nets
    (steps_per_dispatch, beyond-reference; Module.fit's equivalent knob).

    Traces `net` + `loss` (both HybridBlocks) into one symbol, compiles a
    fused fwd+bwd+update step over the contexts' mesh, and dispatches K
    consecutive steps per jitted lax.scan call — amortizing per-step host
    dispatch, which dominates for small-step models. The update math is the
    fused-op twin of the imperative Trainer loop on the same batches.

    `net` must be initialized (params created; a deferred-init net is
    finished against the first batch). `train_data` yields (data, label)
    pairs — a gluon DataLoader — with fixed shapes; a short tail block
    compiles its own k'-step scan (cached). Trained params are written
    back into `net` after the final epoch and at every epoch boundary, so
    `epoch_callback(epoch, net, mean_loss)` and ordinary gluon
    save/export see current values. Returns the per-epoch mean losses.

    Constraints (use the imperative Trainer loop where they bind): the
    optimizer must have a fused update op (parallel.dp._OPT_OPS), and the
    training metric is the loss itself — per-batch prediction metrics
    need Module.fit(steps_per_dispatch=K)'s outputs_mode="all" path.

    Fault tolerance (mxnet_tpu.checkpoint, docs/CHECKPOINT.md):
    `checkpoint_dir` commits an atomic full-state checkpoint (params,
    optimizer states, device t/rng/loss-scaler carries, cursor) at every
    epoch boundary — plus every `checkpoint_period` fused steps — and
    `resume=True` restores the newest committed step for a bit-identical
    continuation. SIGTERM takes one final checkpoint at the next block
    boundary and exits 143.
    """
    import itertools
    import numpy as np
    from .. import symbol as sym_mod
    from ..base import to_numpy as _np_of
    from ..context import current_context
    from ..ndarray.ndarray import NDArray, array as nd_array
    from ..parallel.dp import DataParallelTrainer
    from ..parallel.fused_loop import FusedLoop
    from ..parallel.mesh import mesh_for_contexts

    contexts = contexts or [current_context()]
    if not isinstance(contexts, (list, tuple)):
        contexts = [contexts]
    if dtype is None:
        # unspecified dtype follows the process-wide autocast policy
        # (amp.init / MXNET_AMP); an explicit dtype= always wins
        from .. import amp as _amp
        dtype = _amp.get_dtype() if _amp.is_enabled() else "float32"
    # default K comes from MXNET_FUSED_K (the planner auto-tunes it per
    # chosen plan, "auto unless set"); 0/unset keeps the historical 8
    if steps_per_dispatch is None:
        from .. import config
        steps_per_dispatch = int(config.get("MXNET_FUSED_K", 0)) or 8

    it = iter(train_data)
    try:
        first = next(it)
    except StopIteration:
        raise MXNetError("fused_fit: train_data is empty")
    x0, y0 = first[0], first[1]
    if not isinstance(x0, NDArray):
        x0, y0 = nd_array(np.asarray(x0)), nd_array(np.asarray(y0))
    # finish deferred init (shapes come from the first batch) before the
    # symbolic trace reads param shapes
    net(x0)

    data_v = sym_mod.Variable("data")
    label_v = sym_mod.Variable("fused_label")
    out_sym = net(data_v)
    if isinstance(out_sym, (list, tuple)):
        out_sym = out_sym[0]
    loss_sym = loss(out_sym, label_v)
    if isinstance(loss_sym, (list, tuple)):
        loss_sym = loss_sym[0]

    batch = int(x0.shape[0])
    opt_params = dict(optimizer_params or {})
    lr = float(opt_params.pop("learning_rate", 0.01))
    trainer = DataParallelTrainer(
        loss_sym, mesh_for_contexts(list(contexts)), data_names=("data",),
        label_names=("fused_label",), optimizer=optimizer,
        learning_rate=lr, momentum=float(opt_params.pop("momentum", 0.0)),
        wd=float(opt_params.pop("wd", 0.0)),
        rescale_grad=float(opt_params.pop("rescale_grad", 1.0 / batch)),
        clip_gradient=opt_params.pop("clip_gradient", None), dtype=dtype,
        **opt_params)
    pmap = {p.name: p for _, p in net.collect_params().items()}

    def write_back(arg_np, aux_np):
        for n, v in itertools.chain(arg_np.items(), aux_np.items()):
            if n in pmap:
                pmap[n].set_data(nd_array(v))

    loop = FusedLoop("gluon_fused_fit", "gluon_fused", checkpoint_dir,
                     checkpoint_period, resume)
    epoch_losses = []
    total = count = 0

    def batches(epoch):
        nonlocal total, count
        total, count = 0.0, 0
        return itertools.chain([first], it) if epoch == 0 \
            else iter(train_data)

    def columns(block):
        return [[_np_of(b[0]) for b in block],
                [_np_of(b[1]) for b in block]], None

    def sum_loss(losses, outputs, extra, n_blk):
        nonlocal total, count
        blk_loss = float(np.sum(np.asarray(losses)))
        total += blk_loss
        count += n_blk * batch
        return blk_loss

    def end_epoch(epoch, arg_np, aux_np):
        if count == 0:
            # a single-pass generator exhausts after epoch 0 — failing
            # loudly beats recording 0.0-loss "epochs" that trained nothing
            raise MXNetError(
                f"fused_fit: epoch {epoch} yielded no batches (is "
                "train_data a single-pass generator? pass a "
                "re-iterable like a DataLoader or list)")
        mean_loss = total / count
        epoch_losses.append(mean_loss)
        write_back(arg_np, aux_np)
        if epoch_callback is not None:
            epoch_callback(epoch, net, mean_loss)
        return mean_loss

    try:
        if loop.restored is not None:
            # the snapshot's parameters, whatever wrote it; its trainer
            # state too where the loop finds it to be this front end's own
            write_back(loop.restored.arg_params_nd(),
                       loop.restored.aux_params_nd())
        loop.hold(trainer, trainer.init_state(
            {"data": tuple(x0.shape), "fused_label": tuple(y0.shape)},
            arg_params={n: pmap[n].data() for n in trainer.param_names},
            aux_params={n: pmap[n].data() for n in trainer.aux_names
                        if n in pmap}))
        loop.run(int(steps_per_dispatch), batch, loop.resume_epoch(),
                 num_epoch, batches, columns, sum_loss, end_epoch,
                 optimizer=optimizer,
                 amp_dtype=dtype if dtype != "float32" else None)
    finally:
        loop.release()
        loop.close()
    return epoch_losses


class Trainer:
    def __init__(self, params, optimizer, optimizer_params=None,
                 kvstore="device", compression_params=None,
                 update_on_kvstore=None):
        if isinstance(params, (dict, ParameterDict)):
            params = list(params.values())
        if not isinstance(params, (list, tuple)):
            raise ValueError(
                "First argument must be a list or dict of Parameters, "
                f"got {type(params)}.")
        self._params = []
        for param in params:
            if not isinstance(param, Parameter):
                raise ValueError(
                    "First argument must be a list or dict of Parameters, "
                    f"got list of {type(param)}.")
            self._params.append(param)
        self._compression_params = compression_params
        optimizer_params = optimizer_params if optimizer_params else {}
        self._scale = float(optimizer_params.get("rescale_grad", 1.0))
        self._contexts = self._check_contexts()
        self._init_optimizer(optimizer, optimizer_params)
        self._kv_initialized = False
        self._kvstore = None
        self._update_on_kvstore = update_on_kvstore
        self._kvstore_kind = kvstore

    def _check_contexts(self):
        contexts = None
        for param in self._params:
            ctx = param.list_ctx()
            assert contexts is None or contexts == ctx, \
                (f"All Parameters must be initialized on the same set of "
                 f"contexts, but Parameter {param.name} is initialized on "
                 f"{ctx} while previous Parameters are initialized on "
                 f"{contexts}.")
            contexts = ctx
        return contexts

    def _init_optimizer(self, optimizer, optimizer_params):
        param_dict = {i: param for i, param in enumerate(self._params)}
        if isinstance(optimizer, opt.Optimizer):
            assert not optimizer_params, \
                "optimizer_params must be None if optimizer is an " \
                "Optimizer instance"
            self._optimizer = optimizer
            self._optimizer.param_dict = param_dict
        else:
            from .. import amp as _amp
            if _amp.is_enabled():
                # half-precision weights need fp32 masters; amp turns them
                # on by default (an explicit multi_precision=False wins)
                optimizer_params = dict(optimizer_params)
                optimizer_params.setdefault("multi_precision", True)
            self._optimizer = opt.create(optimizer, param_dict=param_dict,
                                         **optimizer_params)
        self._updaters = [opt.get_updater(self._optimizer)
                          for _ in self._contexts]

    def _init_kvstore(self):
        arg_arrays = {param.name: param.data(self._contexts[0])
                      for param in self._params}
        kvstore, update_on_kvstore = _create_kvstore(
            self._kvstore_kind, len(self._contexts), arg_arrays)
        if kvstore:
            if self._compression_params:
                kvstore.set_gradient_compression(self._compression_params)
            if self._update_on_kvstore is not None:
                update_on_kvstore = self._update_on_kvstore
            for i, param in enumerate(self._params):
                kvstore.init(i, param.data(self._contexts[0]))
            if update_on_kvstore:
                kvstore.set_optimizer(self._optimizer)
            self._kvstore = kvstore
            self._update_on_kvstore = update_on_kvstore
        else:
            self._kvstore = None
            self._update_on_kvstore = False
        self._kv_initialized = True

    @property
    def learning_rate(self):
        if not isinstance(self._optimizer, opt.Optimizer):
            raise UserWarning(
                "Optimizer has to be defined before its learning rate can "
                "be accessed.")
        return self._optimizer.learning_rate if hasattr(
            self._optimizer, "learning_rate") else self._optimizer.lr

    def set_learning_rate(self, lr):
        if not isinstance(self._optimizer, opt.Optimizer):
            raise UserWarning(
                "Optimizer has to be defined before its learning rate is "
                "mutated.")
        self._optimizer.lr = lr

    def step(self, batch_size, ignore_stale_grad=False):
        """Rescale by 1/batch_size, allreduce (facade), update."""
        if not self._kv_initialized:
            self._init_kvstore()
        self._optimizer.rescale_grad = self._scale / batch_size
        self._allreduce_grads()
        self._update(ignore_stale_grad)

    def allreduce_grads(self):
        if not self._kv_initialized:
            self._init_kvstore()
        assert not (self._kvstore and self._update_on_kvstore), \
            "allreduce_grads() when parameters are updated on kvstore is " \
            "not supported. Try setting `update_on_kvstore` to False when " \
            "creating trainer."
        self._allreduce_grads()

    @staticmethod
    def _buffer_key(a):
        """Identity of the underlying device buffer, not the python
        wrapper: a re-wrapped NDArray around the same jax array (or an
        aliasing single-device buffer) must dedup with the original, or
        the kvstore would sum the same gradient twice. id(wrapper) held
        that invariant only by convention."""
        data = a._data
        try:
            # single-device arrays: the actual device pointer catches
            # aliasing even across distinct jax.Array objects
            return data.unsafe_buffer_pointer()
        except Exception:
            # replicated/sharded mesh arrays: python identity of the
            # jax.Array (one replicated array per mesh param)
            return id(data)

    @classmethod
    def _alias_groups(cls, arrays):
        """Group wrappers by underlying buffer. group[0] is the
        representative handed to kvstore/updater; the rest are aliases
        that must be re-synced after the representative's _data is
        rebound (functional substrate: writes rebind, never mutate)."""
        groups = {}
        for a in arrays:
            groups.setdefault(cls._buffer_key(a), []).append(a)
        return list(groups.values())

    @classmethod
    def _unique(cls, arrays):
        # mesh-replicated params expose N references to ONE array; the
        # kvstore must see it once or it would sum the same grad N times
        return [g[0] for g in cls._alias_groups(arrays)]

    @staticmethod
    def _resync(groups):
        # propagate the representative's (possibly rebound) buffer to
        # aliased wrappers so no ctx slot is left holding a stale array;
        # _rebind (not raw _data assignment) keeps an autograd-marked
        # alias's captured leaf value fresh
        for g in groups:
            for alias in g[1:]:
                alias._rebind(g[0]._data)

    def _allreduce_grads(self):
        if self._kvstore and not self._update_on_kvstore:
            for i, param in enumerate(self._params):
                if param.grad_req != "null":
                    groups = self._alias_groups(param.list_grad())
                    reps = [g[0] for g in groups]
                    self._kvstore.push(i, reps, priority=-i)
                    self._kvstore.pull(i, reps, priority=-i)
                    self._resync(groups)

    def _update(self, ignore_stale_grad=False):
        if self._kvstore and self._update_on_kvstore:
            for i, param in enumerate(self._params):
                if param.grad_req != "null":
                    self._kvstore.push(i, self._unique(param.list_grad()),
                                       priority=-i)
                    data_groups = self._alias_groups(param.list_data())
                    self._kvstore.pull(i, [g[0] for g in data_groups],
                                       priority=-i)
                    self._resync(data_groups)
            return
        for i, param in enumerate(self._params):
            if param.grad_req == "null":
                continue
            # mesh-replicated params share ONE array across all ctx
            # slots — apply the update exactly once per device buffer,
            # then re-sync aliased wrappers to the rebound result
            groups = []   # [rep_arr, rep_grad, aliases...] per buffer
            by_key = {}
            for arr, grad in zip(param.list_data(), param.list_grad()):
                k = self._buffer_key(arr)
                if k in by_key:
                    by_key[k].append(arr)
                else:
                    by_key[k] = entry = [arr, grad]
                    groups.append(entry)
            for upd, (rep, grad, *aliases) in zip(self._updaters, groups):
                upd(i, grad, rep)
                for alias in aliases:
                    alias._rebind(rep._data)

    def update(self, batch_size, ignore_stale_grad=False):
        if not self._kv_initialized:
            self._init_kvstore()
        assert not (self._kvstore and self._update_on_kvstore), \
            "update() when parameters are updated on kvstore is not " \
            "supported. Try setting `update_on_kvstore` to False when " \
            "creating trainer."
        self._optimizer.rescale_grad = self._scale / batch_size
        self._update(ignore_stale_grad)

    def save_states(self, fname):
        assert self._optimizer is not None
        if not self._kv_initialized:
            self._init_kvstore()
        if self._update_on_kvstore:
            self._kvstore.save_optimizer_states(fname, dump_optimizer=True)
        else:
            from ..base import atomic_write
            atomic_write(fname, self._updaters[0].get_states(dump_optimizer=True))

    def load_states(self, fname):
        if not self._kv_initialized:
            self._init_kvstore()
        if self._update_on_kvstore:
            self._kvstore.load_optimizer_states(fname)
            self._optimizer = self._kvstore._updater.optimizer
        else:
            with open(fname, "rb") as f:
                states = f.read()
            for updater in self._updaters:
                updater.set_states(states)
                updater.optimizer = self._updaters[0].optimizer
            self._optimizer = self._updaters[0].optimizer
        param_dict = {i: param for i, param in enumerate(self._params)}
        self._optimizer.param_dict = param_dict

    # -- fault-tolerant checkpoints (mxnet_tpu.checkpoint) -------------------

    def save_checkpoint(self, directory, step, metric=None):
        """Commit a FULL-state checkpoint (params + optimizer states incl.
        fp32 masters + RNG) through the atomic CheckpointManager.
        `directory` is a checkpoint root or an existing manager; returns
        the manager (reuse it across steps to keep retention state)."""
        from ..checkpoint import CheckpointManager
        from ..checkpoint.state import capture_trainer_state
        mgr = directory if hasattr(directory, "save") \
            else CheckpointManager(directory)
        mgr.save(capture_trainer_state(self, step=step), step=step,
                 metric=metric, blocking=True)
        return mgr

    def restore_checkpoint(self, directory, step=None):
        """Auto-restore the newest committed checkpoint (or exactly
        `step`) into this Trainer's Parameters and optimizer. Returns the
        restored step number, or None when nothing restorable exists."""
        from ..checkpoint import CheckpointManager
        from ..checkpoint.state import restore_trainer_state
        mgr = directory if hasattr(directory, "restore") \
            else CheckpointManager(directory)
        state = mgr.restore(step)
        if state is None:
            return None
        restore_trainer_state(self, state)
        return state.step
