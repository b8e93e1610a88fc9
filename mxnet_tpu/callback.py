"""Training callbacks.

Parity target: python/mxnet/callback.py (SURVEY.md §2.4) — `do_checkpoint`
epoch callback, `module_checkpoint` (incl. optimizer states), `Speedometer`
throughput logger, `ProgressBar`, `log_train_metric`,
`LogValidationMetricsCallback`.

NOTE on similarity to the reference: callbacks are thin glue whose whole
contract is observable behavior — closure signatures
(`_callback(iter_no, sym, arg, aux)` / `BatchEndParam` fields), checkpoint
file naming (`%s-%04d.params`), and the exact log-line formats that
downstream log parsers (and the reference's own tests) match against.
Matching those strings and signatures is the point; there is no
algorithmic freedom to exercise underneath them.
"""
from __future__ import annotations

import logging
import math
import sys
import time

__all__ = ["module_checkpoint", "do_checkpoint", "log_train_metric",
           "ExpertLoadCounters",
           "Speedometer", "ProgressBar", "LogValidationMetricsCallback"]


def module_checkpoint(mod, prefix, period=1, save_optimizer_states=False,
                      manager=None):
    """Checkpoint the Module (and optionally optimizer states) every
    `period` epochs (callback.py:27).

    With `manager` (a `checkpoint.CheckpointManager`, or a directory
    string one is created for), every save routes through the
    fault-tolerant manager instead of the legacy `prefix-NNNN.params`
    files: atomic commit, async write, retention, and — regardless of
    `save_optimizer_states` — the FULL training state (optimizer states
    incl. fp32 masters, RNG, cursor), restorable with
    `fit(checkpoint_dir=..., resume=True)` or `manager.restore()`."""
    period = int(max(1, period))
    if manager is not None and not hasattr(manager, "save"):
        import atexit
        from .checkpoint import CheckpointManager
        manager = CheckpointManager(manager)
        # nobody else owns this manager: drain its saver thread at
        # interpreter exit so a trailing async commit can't be torn off
        atexit.register(manager.close)

    def _callback(iter_no, sym=None, arg=None, aux=None):
        if (iter_no + 1) % period != 0:
            return
        if manager is not None:
            from .checkpoint import capture_module_state
            manager.save(capture_module_state(mod, epoch=iter_no + 1),
                         step=iter_no + 1)
            return
        mod.save_checkpoint(prefix, iter_no + 1, save_optimizer_states)
    return _callback


def do_checkpoint(prefix, period=1):
    """Checkpoint params every `period` epochs (callback.py:55)."""
    from .model import save_checkpoint
    period = int(max(1, period))

    def _callback(iter_no, sym, arg, aux):
        if (iter_no + 1) % period == 0:
            save_checkpoint(prefix, iter_no + 1, sym, arg, aux)
    return _callback


def log_train_metric(period, auto_reset=False):
    def _callback(param):
        if param.nbatch % period == 0 and param.eval_metric is not None:
            name_value = param.eval_metric.get_name_value()
            for name, value in name_value:
                logging.info("Iter[%d] Batch[%d] Train-%s=%f",
                             param.epoch, param.nbatch, name, value)
            if auto_reset:
                param.eval_metric.reset()
    return _callback


class Speedometer:
    """Logs samples/sec and metrics every `frequent` batches
    (callback.py:120)."""

    def __init__(self, batch_size, frequent=50, auto_reset=True):
        self.batch_size = batch_size
        self.frequent = frequent
        self.init = False
        self.tic = 0
        self.last_count = 0
        self.auto_reset = auto_reset

    def __call__(self, param):
        count = param.nbatch
        if self.last_count > count:
            self.init = False
        self.last_count = count

        if self.init:
            if count % self.frequent == 0:
                speed = self.frequent * self.batch_size / \
                    (time.time() - self.tic)
                if param.eval_metric is not None:
                    name_value = param.eval_metric.get_name_value()
                    if self.auto_reset:
                        param.eval_metric.reset()
                    msg = "Epoch[%d] Batch [%d]\tSpeed: %.2f samples/sec"
                    msg += "\t%s=%f" * len(name_value)
                    logging.info(msg, param.epoch, count, speed,
                                 *sum(name_value, ()))
                else:
                    logging.info("Iter[%d] Batch [%d]\tSpeed: %.2f samples/sec",
                                 param.epoch, count, speed)
                self.tic = time.time()
        else:
            self.init = True
            self.tic = time.time()


class ProgressBar:
    """ASCII progress bar over total batch count."""

    def __init__(self, total, length=80):
        self.bar_len = length
        self.total = total

    def __call__(self, param):
        count = param.nbatch
        filled_len = int(round(self.bar_len * count / float(self.total)))
        percents = math.ceil(100.0 * count / float(self.total))
        prog_bar = "=" * filled_len + "-" * (self.bar_len - filled_len)
        sys.stdout.write(f"[{prog_bar}] {percents}%\r")


class LogValidationMetricsCallback:
    def __call__(self, param):
        if not param.eval_metric:
            return
        name_value = param.eval_metric.get_name_value()
        for name, value in name_value:
            logging.info("Epoch[%d] Validation-%s=%f", param.epoch, name,
                         value)


class ExpertLoadCounters:
    """batch_end_callback for graphs with mixture-of-experts layers: reads
    the small statistics output the layers hand out of each step
    (`ops/lm.py::moe_experts`: tokens per held expert, token-expert pairs
    on held experts, pairs computed, dense fall-backs; stacked over layers
    and, in the fused fit, over the K steps of a dispatch) and keeps the
    telemetry registry's counters of them:

      moe_tokens_routed_total     pairs that fell on experts held here
      moe_tokens_dropped_total    pairs routed but not computed (always 0
                                  with `moe_experts`, which drops none)
      moe_dense_fallback_total    layer-steps that took the dense path
      moe_expert_load_max / _mean largest and mean tokens of a held expert
                                  in one layer-step of the last dispatch

    `output` is the statistics output's index among the symbol's outputs.
    """

    def __init__(self, output=1):
        from .telemetry import registry
        self._output = output
        self._routed = registry.counter(
            "moe_tokens_routed_total",
            help="token-expert pairs routed to experts held here")
        self._dropped = registry.counter(
            "moe_tokens_dropped_total",
            help="token-expert pairs routed to held experts, not computed")
        self._dense = registry.counter(
            "moe_dense_fallback_total",
            help="mixture layer-steps computed densely (over capacity)")
        self._max = registry.gauge(
            "moe_expert_load_max",
            help="largest token count of a held expert, last dispatch")
        self._mean = registry.gauge(
            "moe_expert_load_mean",
            help="mean token count of a held expert, last dispatch")

    def __call__(self, param):
        import numpy as np
        outputs = (param.locals or {}).get("outputs")
        if outputs is None or len(outputs) <= self._output:
            return
        stats = np.asarray(outputs[self._output]).astype(np.int64)
        stats = stats.reshape(-1, stats.shape[-1])    # (steps*layers, E+3)
        load, pairs, computed, dense = (stats[:, :-3], stats[:, -3],
                                        stats[:, -2], stats[:, -1])
        self._routed.inc(int(pairs.sum()))
        self._dropped.inc(int((pairs - computed).sum()))
        self._dense.inc(int(dense.sum()))
        self._max.set(float(load.max()))
        self._mean.set(float(load.mean()))
