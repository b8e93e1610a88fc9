"""DataLoader (parity: python/mxnet/gluon/data/dataloader.py).

The reference forks worker processes that ship NDArrays through POSIX
shared memory (dataloader.py:23-86 + cpu_shared storage, storage.cc:96).
Here `num_workers>0` selects between two pools via `worker_type`:

- "thread" (default): decode/augment that releases the GIL (cv2, numpy,
  the native recordio engine) scales on threads, and the assembled batch
  makes exactly one host->device transfer — the multiprocessing+shm
  dance exists to feed GPUs from python workers, whereas the TPU input
  bottleneck is the single host->HBM copy.
- "process": forked workers (the reference's model) for PYTHON-transform
  -heavy datasets whose per-sample work holds the GIL — there threads
  serialize and forked processes restore the parallelism. Workers
  assemble pure-NUMPY batches (no device buffers cross the fork; the
  parent does the single wrap + transfer), samples ship back pickled.

Crossover guidance (by construction, not measured here):
GIL-releasing pipelines — threads win (no pickling, shared memory);
GIL-bound python transforms — processes win roughly linearly in cores.
`num_workers=0` runs inline.

Fork caveat (same class as the reference's): create process-worker
loaders EARLY — forking after jax has spawned backend threads is
warned-against by jax and can deadlock on some runtimes; the workers
themselves never touch device state by design.
"""
from __future__ import annotations

import concurrent.futures

import numpy as np

from ...ndarray.ndarray import NDArray, array
from .sampler import BatchSampler, RandomSampler, SequentialSampler

__all__ = ["DataLoader", "default_batchify_fn"]


def default_batchify_fn(data):
    """Stack sample tuples into batch arrays."""
    if isinstance(data[0], NDArray):
        import numpy as _np
        return array(_np.stack([d.asnumpy() for d in data]))
    if isinstance(data[0], tuple):
        data = zip(*data)
        return [default_batchify_fn(list(i)) for i in data]
    data = np.asarray(data)
    return array(data, dtype=data.dtype)


def _numpy_batchify(data):
    """Worker-side batchify for the process pool: identical stacking to
    default_batchify_fn but emits raw numpy — forked children must not
    create device buffers (a forked jax/PJRT runtime is not usable), so
    the single wrap + host->device transfer happens in the parent."""
    first = data[0]
    if isinstance(first, tuple):
        return tuple(_numpy_batchify(list(col)) for col in zip(*data))
    if isinstance(first, NDArray):
        return np.stack([d.asnumpy() for d in data])
    return np.asarray(data)


def _wrap_tree(out):
    """Parent-side: numpy trees from process workers -> NDArrays."""
    if isinstance(out, (tuple, list)):
        return [_wrap_tree(o) for o in out]
    if isinstance(out, np.ndarray):
        return array(out, dtype=out.dtype)
    return out


# process-worker state: installed by the pool initializer, which fork
# inherits by memory — the per-task payload is only the index list (task
# closures would have to pickle, which lambdas/local transforms can't)
_PROC_STATE = {}


def _proc_init(dataset, batchify_fn):
    _PROC_STATE["ds"] = dataset
    _PROC_STATE["fn"] = batchify_fn


def _proc_fetch(batch):
    ds, fn = _PROC_STATE["ds"], _PROC_STATE["fn"]
    samples = [ds[idx] for idx in batch]
    if fn is not None:
        return fn(samples)
    return _numpy_batchify(samples)


class DataLoader:
    def __init__(self, dataset, batch_size=None, shuffle=False, sampler=None,
                 last_batch=None, batch_sampler=None, batchify_fn=None,
                 num_workers=0, worker_type="thread"):
        if worker_type not in ("thread", "process"):
            raise ValueError("worker_type must be 'thread' or 'process'")
        self._worker_type = worker_type
        self._dataset = dataset
        if batch_sampler is None:
            if batch_size is None:
                raise ValueError(
                    "batch_size must be specified unless batch_sampler is "
                    "specified")
            if sampler is None:
                if shuffle:
                    sampler = RandomSampler(len(dataset))
                else:
                    sampler = SequentialSampler(len(dataset))
            elif shuffle:
                raise ValueError(
                    "shuffle must not be specified if sampler is specified")
            batch_sampler = BatchSampler(sampler, batch_size,
                                         last_batch if last_batch else "keep")
        elif batch_size is not None or shuffle or sampler is not None or \
                last_batch is not None:
            raise ValueError(
                "batch_size, shuffle, sampler and last_batch must not be "
                "specified if batch_sampler is specified.")
        self._batch_sampler = batch_sampler
        self._num_workers = max(0, num_workers)
        self._batchify_fn = batchify_fn if batchify_fn is not None \
            else default_batchify_fn
        self._pool = None
        if self._num_workers and worker_type == "process":
            import multiprocessing
            # fork: children inherit the dataset/transform state in
            # memory — the reference's worker model (dataloader.py:23-86)
            user_fn = self._batchify_fn \
                if batchify_fn is not None else None
            self._pool = concurrent.futures.ProcessPoolExecutor(
                max_workers=self._num_workers,
                mp_context=multiprocessing.get_context("fork"),
                initializer=_proc_init, initargs=(dataset, user_fn))
        elif self._num_workers:
            self._pool = concurrent.futures.ThreadPoolExecutor(
                max_workers=self._num_workers)

    def __iter__(self):
        if self._pool is None:
            for batch in self._batch_sampler:
                yield self._batchify_fn([self._dataset[idx]
                                         for idx in batch])
            return

        if self._worker_type == "process":
            fetch = _proc_fetch
            finish = _wrap_tree
        else:
            def fetch(batch):
                return self._batchify_fn([self._dataset[idx]
                                          for idx in batch])

            def finish(out):
                return out

        # pipeline: keep 2*workers batches in flight
        batches = iter(self._batch_sampler)
        futures = []
        try:
            for _ in range(2 * self._num_workers):
                futures.append(self._pool.submit(fetch, next(batches)))
        except StopIteration:
            pass
        while futures:
            out = finish(futures.pop(0).result())
            try:
                futures.append(self._pool.submit(fetch, next(batches)))
            except StopIteration:
                pass
            yield out

    def __len__(self):
        return len(self._batch_sampler)
