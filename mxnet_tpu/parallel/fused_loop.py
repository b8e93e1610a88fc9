"""The fused training loop: K steps a dispatch, written once.

`Module.fit(steps_per_dispatch=K)` (module/module.py::_fit_fused) and
`gluon.trainer.fused_fit` are its two front ends. Each sets up its own
trainer (the module binds and draws parameters as K=1 would; gluon traces
net + loss) and hands the loop what differs between them:

  batches(epoch)       the epoch's iterator of batches; whatever a front
                       end resets per epoch it resets here
  columns(block)       -> (one list of K host arrays per trainer input,
                       extra): `extra` rides beside the staged inputs to
                       `consume`, opaque to the loop (the module's labels)
  consume(losses, outputs, extra, n_blk)
                       under `step.metric_update`: syncs on the dispatch's
                       results; returns the block's loss sum for the step
                       log, or None
  after_block(view)    under `step.callbacks`, or None for no callbacks;
                       `view` is `FusedLoop._view`'s dict
  end_epoch(epoch, arg_params, aux_params)
                       host copies of the trained parameters to write back;
                       returns the metric the epoch-end checkpoint records

Everything else is the loop's: the checkpoint manager's life, the
trainer's state `(params, states, aux)` as its single holder, blocking and
staging, the span tree, the `step_k` call, the step log, the cursor and
the saves. Layering: module/ and gluon/ -> parallel.fused_loop ->
parallel.dp / zero, pipeline, checkpoint, telemetry.
"""
from __future__ import annotations

import itertools
import logging
import types

from .. import pipeline
from .. import random as _random
from ..telemetry import devstats, maybe_step_logger, tracing


def _call(fn, *args, **kwargs):
    return fn(*args, **kwargs)


# CPython keeps interpreter frames in 16 KB chunks and unmaps a chunk as soon
# as the frame at its base returns: a hot call site that happens to straddle a
# chunk's end pays an mmap + munmap per call, each a TLB shoot-down across the
# runtime's threads. Tracing the fused step made 64,000 to 164,000 such calls
# by how many locals the frames above it held (PERF.md §6, PR 28). A frame
# larger than a chunk gets a chunk of its own, twice its size, and whatever is
# called from it runs in the free half: no boundary, whatever lies above.
_in_roomy_frame = types.FunctionType(
    _call.__code__.replace(co_stacksize=1 << 15), globals(), "_in_roomy_frame")


def _blocks(stream, k):
    while True:
        block = list(itertools.islice(stream, k))
        if not block:
            return
        yield block


class FusedLoop:
    """One fused fit. `name` is the step log's phase and the feed's name,
    `kind` the string its checkpoints carry (a snapshot of another kind
    restores parameters only). Construction opens the checkpoint directory
    and, under `resume`, reads the newest committed step into `restored`
    (a front end takes its initial parameters and `resume_epoch()` from
    it); `hold` takes the trainer and its state, `run` trains, `release`
    lets the state go, `close` ends the step log and the manager. A front
    end calls `release` and `close` in a `finally`, however the fit ends."""

    def __init__(self, name, kind, checkpoint_dir=None,
                 checkpoint_period=None, resume=False, logger=None):
        self.name, self.kind = name, kind
        self.logger = logger or logging.getLogger("mxnet_tpu.checkpoint")
        self.trainer = self.params = self.states = self.aux = None
        self.restored = None
        self._period = int(checkpoint_period or 0)
        self._mgr = self._slog = None
        if checkpoint_dir is not None:
            from ..checkpoint import CheckpointManager
            self._mgr = CheckpointManager(checkpoint_dir, logger=logger)
            if resume:
                self.restored = self._mgr.restore()

    def resume_epoch(self, default=0):
        if self.restored is None:
            return default
        return int(self.restored.meta.get("epoch", default))

    def hold(self, trainer, state):
        self.trainer = trainer
        self.params, self.states, self.aux = state

    def release(self):
        """Drop the trainer's state: after this only a callback that kept
        its `view` holds any of it."""
        self.params = self.states = self.aux = None

    def close(self):
        # run_end carries the step program's XLA cost digest (which
        # program the per-step MFU was measured against, its FLOPs/bytes
        # per step, the peak table in force)
        if self._slog is not None:
            try:
                self._slog.close(**devstats.fit_summary())
            except Exception:
                self._slog.close()
        if self._mgr is not None:
            self._mgr.remove_sigterm_hook()
            self._mgr.close()

    def _resume(self, batch_size):
        """Continue from `restored`: the trainer's full state where the
        snapshot is this front end's own, the RNG, the global step; returns
        the batches of the first epoch that are already trained."""
        meta = self.restored.meta
        if meta.get("kind") == self.kind and meta.get("trainer") is not None:
            # opt-state arrays + device t/rng/loss-scaler carries: the
            # continuation is bit-identical. import device_puts the
            # reassembled host arrays onto THIS run's mesh, so an elastic
            # restore at a different device count reshards here
            self.hold(self.trainer, self.trainer.import_training_state(
                self.restored.arrays, meta["trainer"]))
        else:
            self.logger.warning(
                "checkpoint: snapshot kind=%r has no fused-trainer state; "
                "params restored, optimizer state starts fresh",
                meta.get("kind"))
        if meta.get("rng") is not None:
            _random.set_state(meta["rng"])
        saved = (meta.get("topology") or {}).get("device_count")
        if saved is not None:
            import jax
            if int(saved) != jax.device_count():
                self.logger.info(
                    "checkpoint: topology changed since save (%s -> %d "
                    "devices); state resharded onto the current mesh",
                    saved, jax.device_count())
        from ..checkpoint.state import rescale_cursor
        return int(meta.get("step", 0)), rescale_cursor(meta, batch_size)

    def _view(self, epoch, nbatch, n_blk, inputs, outputs, losses):
        """What a batch-end callback sees of the loop, as
        `BatchEndParam.locals`: the objects themselves, no copies, and
        nothing the loop keeps (a callback that stores the view holds
        these arrays until the next dispatch's view replaces it).

          trainer  the DataParallelTrainer / ZeroTrainer (benchmarks'
                   runners and chip_smoke: `"trainer" in locals` says the
                   fused loop ran; `trainer.host_params(params)`)
          params, states, aux
                   the trainer's state after this dispatch (runners'
                   `spread`, chip_smoke's placement and re-lowering)
          inputs   the staged block, one (K', batch, ...) array per
                   trainer input (`spread`: `inputs[0]`'s shards)
          outputs  one (K', batch, ...) array per symbol output, or ()
                   under outputs_mode="none" (callback.ExpertLoadCounters,
                   train_fit_tokens' FirstDispatch and CounterLog)
          losses   the (K',) loss of each step
          epoch, nbatch
                   the epoch, and the batches it has consumed so far
          n_blk    K', the steps of this dispatch (chip_smoke re-lowers
                   the K'-step program)
        """
        return {"trainer": self.trainer, "params": self.params,
                "states": self.states, "aux": self.aux, "inputs": inputs,
                "outputs": outputs, "losses": losses, "epoch": epoch,
                "nbatch": nbatch, "n_blk": n_blk}

    def run(self, *args, **kwargs):
        """`_run`, called from a frame with room beneath it for the frames
        of the first dispatch's tracing (`_in_roomy_frame`)."""
        return _in_roomy_frame(self._run, *args, **kwargs)

    def _run(self, k, batch_size, begin_epoch, num_epoch, batches, columns,
             consume, end_epoch, after_block=None, outputs_mode="none",
             optimizer=None, amp_dtype=None):
        """Train epochs [begin_epoch, num_epoch) on the held state, K
        batches a dispatch (a short tail block compiles its own, cached,
        k'-step scan). `optimizer` and `amp_dtype` (None for float32) go
        into the records only."""
        trainer, mgr = self.trainer, self._mgr
        gstep = skip = 0
        if self.restored is not None:
            gstep, skip = self._resume(batch_size)
        if mgr is not None:
            mgr.install_sigterm_hook()
        slog = self._slog = maybe_step_logger(self.name, meta={
            "optimizer": optimizer, "steps_per_dispatch": int(k),
            "batch_size": int(batch_size), "begin_epoch": begin_epoch,
            "num_epoch": num_epoch, "amp_dtype": amp_dtype})
        stager = pipeline.BlockStager(trainer.shard_inputs)

        def stage(block):
            # host stack + device commit run on the feeder thread: block
            # N+1 is staged while block N's fused scan executes. The stager
            # copies into host buffers of its own before it returns, so
            # iterator buffer reuse is safe
            cols, extra = columns(block)
            return stager(cols, stacked=True), extra, len(block)

        def save(next_epoch, next_batch, **kwargs):
            # synchronous snapshot of the (donated) device tuples: must
            # happen between dispatches; the atomic write itself still
            # overlaps the following steps on the saver thread
            from ..checkpoint.state import TrainingState
            arrays, tmeta = trainer.export_training_state(
                self.params, self.states, self.aux)
            mgr.save(TrainingState(arrays=arrays, meta={
                "kind": self.kind, "epoch": int(next_epoch),
                "batch": int(next_batch), "step": int(gstep),
                "batch_size": int(batch_size), "trainer": tmeta,
                "rng": _random.get_state(), "amp_dtype": amp_dtype}),
                step=gstep, **kwargs)

        for epoch in range(begin_epoch, num_epoch):
            stream = batches(epoch)
            if skip:
                self.logger.info("checkpoint: fast-forwarding %d batches "
                                 "to the saved cursor", skip)
                for _ in itertools.islice(stream, skip):
                    pass
            nbatch, skip = skip, 0
            last_ckpt = gstep
            feed = pipeline.feed_or_inline(_blocks(stream, k), stage,
                                           name=self.name)
            try:
                for seq, (inputs, extra, n_blk) in enumerate(feed):
                    # "compute" span: the fused dispatch plus the consumer
                    # that syncs on its results, i.e. the device-bound
                    # slice of the loop body. Its two halves have spans of
                    # their own (no phase: the parent's time is the
                    # phase's)
                    with tracing.span("step.fused_dispatch",
                                      phase="compute", k=n_blk, seq=seq):
                        # returns once the scan is enqueued, before the
                        # device ends
                        with tracing.span("step.enqueue"):
                            (self.params, self.states, self.aux, losses,
                             outputs) = trainer.step_k(
                                self.params, self.states, self.aux, inputs,
                                outputs_mode=outputs_mode)
                        with tracing.span("step.metric_update"):
                            loss = consume(losses, outputs, extra, n_blk)
                    # one record per fused dispatch (K steps); the consumer
                    # above already synced on the results, so the wall time
                    # covers real device work
                    samples = n_blk * batch_size
                    with tracing.span("step.log", seq=seq):
                        slog.step(samples=samples, steps=n_blk,
                                  loss=None if loss is None
                                  else loss / max(samples, 1),
                                  extra={"epoch": epoch})
                    nbatch += n_blk
                    gstep += n_blk
                    if after_block is not None:
                        with tracing.span("step.callbacks", seq=seq):
                            after_block(self._view(epoch, nbatch, n_blk,
                                                   inputs, outputs, losses))
                    if mgr is not None:
                        if self._period and gstep - last_ckpt >= self._period:
                            with tracing.span("step.checkpoint", seq=seq):
                                save(epoch, nbatch)
                            last_ckpt = gstep
                        if mgr.preempted:
                            with tracing.span("step.checkpoint", seq=seq):
                                save(epoch, nbatch, blocking=True)
                            raise SystemExit(143)
            finally:
                pipeline.close_feed(feed)
                # an exception's traceback keeps this frame: it must not
                # keep the last staged block and its results with it
                inputs = outputs = losses = extra = None

            # COPIES (np.asarray), not the live buffers: step_k donates
            # its params, so whatever a front end aliased them into would
            # hold deleted arrays after the next epoch's first dispatch
            metric = end_epoch(epoch, trainer.host_params(self.params),
                               trainer.host_aux(self.aux))
            if mgr is not None:
                save(epoch + 1, 0, metric=metric)
                if mgr.preempted:
                    mgr.wait()
                    raise SystemExit(143)
