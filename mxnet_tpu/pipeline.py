"""Async device-feed pipeline — hide host-side input cost behind compute.

The reference hides input cost behind compute twice over: iter_prefetcher.h
double-buffers batches on the host and the dependency engine overlaps the
host->device copy lane (kCopyToGPU) with kernels (SURVEY §1 rows 2/7).
io.PrefetchingIter reproduces the first half; this module is the device
boundary's half: `DeviceFeed` runs a background feeder thread that pulls
batch N+1 from the source iterator and *stages* it — commits it to the
device (jax.device_put with the consumer's sharding, parallel/mesh.py) —
while step N executes on the device. The consumer loop then finds its
next batch already resident and its per-step device_put collapses to a
no-op (device_put on a committed array with the same sharding returns it
unchanged, so results are bit-identical to the synchronous path).

Mechanics:
  - bounded ring (depth 2 by default, MXNET_DEVICE_FEED_DEPTH): the
    feeder stays at most `depth` batches ahead, so device memory holds a
    bounded number of staged batches no matter how fast the source is;
  - the stage function runs ON THE FEEDER THREAD and must copy out of
    the source item before it returns (device_put does; so does
    BlockStager's stack into a host buffer of its own), which is what
    makes prefetching safe over legacy buffer-reusing iterators — the
    very reason BaseModule.fit's fetch-after-update discipline exists;
  - the fused K-step drivers stage through `BlockStager`: the (K, batch,
    ...) block of every input column is stacked into one of two host
    buffers the stager owns and refills, block after block (a fresh
    np.stack result of that size is a fresh mmap whose every page faults
    on first touch: 616 MB a dispatch for ResNet-50 at batch 256, which
    set that fit's pace; PERF.md §6, PR 25). device_put returns before
    the runtime has read the host array, so a buffer is refilled only
    after the device arrays last staged from it are ready
    (`feed.reuse_wait`), and never where they alias it;
  - feeder exceptions are re-raised in the consumer thread at the next
    __next__; close() drains and joins the thread (no leaked threads);
  - counters (`feed_wait_us`, `feed_stage_us`, `feed_staged_bytes`,
    `feed_stack_reuses`, `feed_stack_allocs`, `feed_reuse_wait_us`,
    `overlap_frac`, ...) are exported through
    profiler.register_counter_export under the "device_feed" key, so
    profiler.dump() traces carry them;
  - spans (telemetry/tracing.py; `mx.<name>` in a profiler trace), every
    one carrying `seq`, the block's number since the feed opened: on the
    feeder thread `feed.stage` over `feed.pull` (the source), whatever
    the stage function opens (`feed.reuse_wait`, `feed.stack`,
    `feed.put`), then `feed.enqueue` (blocked on a full ring); on the
    consumer's thread
    `feed.wait`. Each counter is fed from its span's own clock reads.

The loops threaded through it: Module/BaseModule.fit, the fused K-step
loop (parallel/fused_loop.py, behind Module._fit_fused and
gluon.trainer.fused_fit), BaseModule.score / predict, and
ServingEngine.warmup. `MXNET_DEVICE_FEED=0` restores the fully synchronous
path everywhere.
"""
from __future__ import annotations

import itertools
import queue
import threading
import time

import numpy as np

from .telemetry import tracing as _tracing

__all__ = ["DeviceFeed", "BlockStager", "module_stage", "staged_put",
           "enabled", "default_depth", "stats", "reset_stats"]

# -- aggregate counters (exported via profiler.register_counter_export) -----

_STATS_LOCK = threading.Lock()
_TOTALS = {"feed_wait_us": 0, "feed_stage_us": 0, "feed_batches": 0,
           "feed_staged_bytes": 0, "feed_stack_reuses": 0,
           "feed_stack_allocs": 0, "feed_reuse_wait_us": 0,
           "feeds_opened": 0, "feeds_closed": 0}


def _bump(key, val):
    with _STATS_LOCK:
        _TOTALS[key] += val


def stats():
    """Snapshot of the aggregate device-feed counters. `overlap_frac` is
    the fraction of staging time hidden behind compute: 1 when consumers
    never blocked on the feed, 0 when every staged microsecond was waited
    for (fully serial)."""
    with _STATS_LOCK:
        out = dict(_TOTALS)
    stage = out["feed_stage_us"]
    out["overlap_frac"] = round(
        max(0.0, 1.0 - out["feed_wait_us"] / stage), 4) if stage else 0.0
    out["feeds_active"] = out["feeds_opened"] - out["feeds_closed"]
    return out


def reset_stats():
    with _STATS_LOCK:
        for k in _TOTALS:
            _TOTALS[k] = 0


def _register_export():
    from . import profiler
    profiler.register_counter_export("device_feed", stats)


_register_export()


# -- config knobs ------------------------------------------------------------

def enabled():
    """MXNET_DEVICE_FEED gate (default on; 0 restores synchronous feed)."""
    from . import config
    return bool(config.get("MXNET_DEVICE_FEED", 1))


def default_depth():
    from . import config
    return max(1, int(config.get("MXNET_DEVICE_FEED_DEPTH", 2)))


# -- the prefetcher ----------------------------------------------------------

_END = "end"
_ITEM = "item"
_ERR = "err"

# analysis/locklint: DeviceFeed's counters are single-writer by thread
# discipline — stage_us is written ONLY by the feeder thread, wait_us/
# batches/_done ONLY by the consumer thread (close() flips _done after
# the feeder is joined); += with one writer is safe under the GIL and
# readers (overlap_frac/stats) tolerate a one-item-stale value
__analysis_thread_safe__ = {"DeviceFeed.stage_us", "DeviceFeed.wait_us",
                            "DeviceFeed.batches", "DeviceFeed._done"}


class DeviceFeed:
    """Iterate `source` with staging one batch ahead on a feeder thread.

    `stage(item)` runs on the feeder thread and should return the
    device-committed form of `item` (it MUST copy out of any buffer the
    source reuses before it returns; jax.device_put does, and so does
    BlockStager's stack into its own host buffer). Omitting it degrades
    gracefully to host-side prefetch of the raw items.

    Iterator contract: yields staged items in source order; StopIteration
    at exhaustion; a feeder-side exception (from the source or the stage
    fn) is re-raised here, in the consumer thread. Use as a context
    manager or call close() — close is idempotent, drains the ring, and
    joins the thread.
    """

    def __init__(self, source, stage=None, depth=None, name="device_feed"):
        self._source = iter(source)
        self._stage = stage if stage is not None else (lambda item: item)
        self._depth = depth if depth is not None else default_depth()
        self._q = queue.Queue(maxsize=max(1, int(self._depth)))
        self._stop = threading.Event()
        self._done = False
        self.name = name
        # per-instance counters (module totals aggregate across feeds)
        self.wait_us = 0
        self.stage_us = 0
        self.batches = 0
        _bump("feeds_opened", 1)
        self._thread = threading.Thread(
            target=self._feeder, name=f"{name}-feeder", daemon=True)
        self._thread.start()

    # -- feeder side --------------------------------------------------------
    def _put(self, msg):
        """Bounded put that gives up when the consumer closed the feed."""
        while not self._stop.is_set():
            try:
                self._q.put(msg, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    def _feeder(self):
        # feed_stage_us is the full feeder-side cost per item — source
        # pull plus staging — i.e. exactly the host work the feed hides.
        try:
            for seq in itertools.count():
                if self._stop.is_set():
                    break
                # feeder-side work records under "feed_stage", NOT
                # "feed": StepLogger's feed_us/overlap fraction counts
                # only consumer-blocked time (the "feed" phase below)
                with _tracing.stopwatch("feed.stage", phase="feed_stage",
                                        feed=self.name, seq=seq) as sw:
                    try:
                        with _tracing.span("feed.pull"):
                            item = next(self._source)
                    except StopIteration:
                        break
                    staged = self._stage(item)
                dt_us = int(sw.dur_us)
                self.stage_us += dt_us
                _bump("feed_stage_us", dt_us)
                with _tracing.span("feed.enqueue", seq=seq):
                    if not self._put((_ITEM, staged)):
                        return
            self._put((_END, None))
        except BaseException as exc:   # noqa: BLE001 — re-raised consumer-side
            self._put((_ERR, exc))

    # -- consumer side ------------------------------------------------------
    def __iter__(self):
        return self

    def __next__(self):
        if self._done:
            raise StopIteration
        with _tracing.stopwatch("feed.wait", phase="feed", feed=self.name,
                                seq=self.batches) as sw:
            kind, val = self._q.get()
        dt_us = int(sw.dur_us)
        self.wait_us += dt_us
        _bump("feed_wait_us", dt_us)
        if kind == _ITEM:
            self.batches += 1
            _bump("feed_batches", 1)
            return val
        self._done = True
        self.close()
        if kind == _ERR:
            raise val
        raise StopIteration

    def overlap_frac(self):
        """Fraction of this feed's staging time hidden behind compute."""
        if not self.stage_us:
            return 0.0
        return max(0.0, 1.0 - self.wait_us / self.stage_us)

    def close(self):
        """Stop the feeder, drain the ring, join the thread. Idempotent."""
        if self._stop.is_set() and not self._thread.is_alive():
            return
        self._stop.set()
        # drain so a feeder blocked in put() wakes and sees the stop flag
        while True:
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
        if self._thread.is_alive() and \
                self._thread is not threading.current_thread():
            self._thread.join(timeout=10.0)
        self._done = True
        _bump("feeds_closed", 1)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


# -- stage builders ----------------------------------------------------------

def staged_put(put, arrays, **kwargs):
    """`put(arrays, **kwargs)`, a trainer's commit of host arrays to its
    devices (`jax.device_put`), as a stage function calls it: under the
    `feed.put` span, its bytes counted into `feed_staged_bytes`, so that
    the copy rate is bytes over the span's time, read and not deduced."""
    _bump("feed_staged_bytes", sum(int(a.nbytes) for a in arrays))
    with _tracing.span("feed.put"):
        return put(arrays, **kwargs)


_new_buffer = np.empty      # where BlockStager's host buffers come from


def _may_alias(staged, buf):
    """Whether `staged` (what a trainer's put returned for one block) may
    share memory with the host buffer `buf`. Device memory of another
    platform never does; the CPU backend hands a suitably aligned numpy
    buffer to the device array without a copy (64-byte alignment, whatever
    `may_alias` says; JAX 0.9.0), so there the shards' buffer pointers are
    compared with the buffer's range. What cannot be told apart counts as
    aliasing."""
    import jax
    lo = buf.ctypes.data
    hi = lo + buf.nbytes
    for leaf in jax.tree_util.tree_leaves(staged):
        if isinstance(leaf, np.ndarray):
            if np.may_share_memory(leaf, buf):
                return True
            continue
        try:
            for shard in leaf.addressable_shards:
                if shard.device.platform == "cpu" and \
                        lo <= shard.data.unsafe_buffer_pointer() < hi:
                    return True
        except (AttributeError, RuntimeError):    # no pointer to read
            return True
    return False


class BlockStager:
    """The fused drivers' stage step: `stager(columns, **kwargs)` stacks
    each column (the K host arrays of one input) into a `(K, batch, ...)`
    host buffer under the span `feed.stack` and commits the blocks through
    `staged_put(put, blocks, **kwargs)`; returns what `put` returned. The
    blocks hold the bytes `np.stack` would have given.

    The buffers are the stager's own: two sets, taken in turn, each made
    when a block first needs it and kept for as long as the blocks' shape
    and dtype stay what they were. A block of fewer rows (the tail of an
    epoch) fills and commits the leading rows of the same buffer; another
    shape or dtype gets a new buffer and the old one is dropped. The stack
    is synchronous, so the columns may be reused by their source as soon as
    the call returns (DeviceFeed's contract).

    `put` may return before its arrays have been read (jax.device_put
    does: the runtime linearizes and transfers on threads of its own). So
    the stager keeps what was last staged from each set and waits for it
    (`jax.block_until_ready`, span `feed.reuse_wait`) before it writes
    into that set again: with two sets that is the block before last, long
    on the device. Where the staged arrays may alias a buffer
    (`_may_alias`) the buffer is theirs: the stager lets go of it and
    makes a new one at its next turn.

    One thread at a time: the feeder's, or the loop's own where
    MXNET_DEVICE_FEED=0 runs the stage function inline.
    """

    _SETS = 2

    def __init__(self, put):
        self._put = put
        # per set: the columns' buffers, and what was last staged from them
        self._bufs = [[] for _ in range(self._SETS)]
        self._staged = [None] * self._SETS
        self._turn = 0

    def __call__(self, columns, **kwargs):
        import jax
        turn = self._turn % self._SETS
        self._turn += 1
        bufs = self._bufs[turn]
        if self._staged[turn] is not None:
            with _tracing.stopwatch("feed.reuse_wait") as sw:
                jax.block_until_ready(self._staged[turn])
            self._staged[turn] = None
            _bump("feed_reuse_wait_us", int(sw.dur_us))
        with _tracing.span("feed.stack"):
            if len(bufs) != len(columns):
                bufs[:] = [None] * len(columns)
            blocks = []
            for i, col in enumerate(columns):
                rows, shape = len(col), np.shape(col[0])
                dtype = np.result_type(*col)
                buf = bufs[i]
                if buf is None or buf.shape[0] < rows or \
                        buf.shape[1:] != shape or buf.dtype != dtype:
                    buf = bufs[i] = _new_buffer((rows,) + shape, dtype)
                    _bump("feed_stack_allocs", 1)
                else:
                    _bump("feed_stack_reuses", 1)
                blocks.append(np.stack(col, out=buf[:rows]))
        staged = staged_put(self._put, blocks, **kwargs)
        for i, buf in enumerate(bufs):
            if _may_alias(staged, buf):
                bufs[i] = None
        if any(buf is not None for buf in bufs):
            self._staged[turn] = staged
        return staged


def module_stage(module):
    """Stage function for DataBatch streams feeding a bound module: each
    data/label array is committed to the placement the module's executor
    will request in forward — batch-sharded inputs / per-context device
    (executor._arg_sharding) — so forward's own device_put is a no-op.

    Placement is resolved per batch through `module._exec` (rebind /
    reshape swap the executor mid-fit). Arrays whose batch axis doesn't
    divide the mesh are passed through unstaged so forward raises its
    documented divisibility error instead of a feeder-thread jax error;
    modules without a bound executor degrade to host-side prefetch.
    """
    import jax
    from .io import DataBatch
    from .ndarray.ndarray import NDArray

    def _put(ex, name, arr):
        if name not in ex.arg_dict:
            return arr
        data = arr._data if isinstance(arr, NDArray) else arr
        if not isinstance(data, jax.Array):
            data = np.asarray(data)
        if ex._mesh is not None:
            if name in ex._sharded_args and data.shape and \
                    data.shape[0] % ex._mesh.devices.size != 0:
                return arr      # forward owns the divisibility error
            target = ex._arg_sharding(name)
        else:
            target = ex._ctx.jax_device()
        _bump("feed_staged_bytes", int(data.nbytes))
        return NDArray(jax.device_put(data, target))

    def stage(batch):
        ex = getattr(module, "_exec", None)
        if ex is None or getattr(ex, "arg_dict", None) is None:
            return batch
        with _tracing.span("feed.put"):
            data = [_put(ex, n, a)
                    for n, a in zip(module.data_names, batch.data)]
            label = batch.label
            if label:
                lnames = list(getattr(module, "label_names", None) or [])
                label = [_put(ex, n, a) for n, a in zip(lnames, label)]
        return DataBatch(data=data, label=label, pad=batch.pad,
                         index=batch.index, bucket_key=batch.bucket_key,
                         provide_data=batch.provide_data,
                         provide_label=batch.provide_label)

    return stage


def feed_or_inline(source, stage, name="device_feed"):
    """DeviceFeed when MXNET_DEVICE_FEED is on, else a lazy synchronous
    map of the SAME stage function — consumer loops get one code path
    whose math is identical either way (only the thread differs)."""
    if enabled():
        return DeviceFeed(source, stage=stage, name=name)
    return map(stage, source)


def close_feed(feed):
    """close() for DeviceFeed, no-op for the inline map fallback."""
    if isinstance(feed, DeviceFeed):
        feed.close()


# -- smoke entry (tools/ci.sh quick stage) -----------------------------------

def _selftest():
    """Overlap smoke: a source with real per-item host cost feeding a
    consumer with real per-item compute; asserts order + values survive
    the feed, the feeder thread exits, and staging actually overlapped."""
    import os
    import jax

    n, host_ms = 24, 4.0

    def source():
        for i in range(n):
            time.sleep(host_ms / 1e3)        # decode/read stand-in
            yield i, np.full((64,), i, np.float32)

    dev = jax.devices()[0]

    def stage(item):
        i, arr = item
        return i, jax.device_put(arr, dev)

    t0 = time.perf_counter()
    seen = []
    with DeviceFeed(source(), stage=stage, name="selftest") as feed:
        for i, arr in feed:
            time.sleep(host_ms / 1e3)        # device-step stand-in
            assert float(np.asarray(arr)[0]) == float(i)
            seen.append(i)
        thread = feed._thread
    wall = time.perf_counter() - t0
    assert seen == list(range(n)), "order not preserved"
    assert not thread.is_alive(), "feeder thread leaked"
    sync_est = 2 * n * host_ms / 1e3
    print(f"device-feed selftest: {n} items, wall {wall:.2f}s vs "
          f"~{sync_est:.2f}s synchronous, overlap_frac "
          f"{stats()['overlap_frac']}")
    if wall >= sync_est * 0.85:
        raise SystemExit("selftest FAILED: no overlap measured")
    print("PIPELINE-SELFTEST-OK")


def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser(description="async device-feed pipeline")
    ap.add_argument("--selftest", action="store_true")
    args = ap.parse_args(argv)
    if args.selftest:
        _selftest()
        return 0
    ap.print_help()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
