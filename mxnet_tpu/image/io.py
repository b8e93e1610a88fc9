"""ImageRecordIter — C++-iterator-compatible record pipeline.

Parity target: src/io/iter_image_recordio_2.cc:727 (SURVEY.md §3.6): recordio
chunk read → parallel JPEG decode (`preprocess_threads` thread pool standing
in for the OMP loop) → augment → batch assembly → background prefetch
(iter_prefetcher.h double buffering == PrefetchingIter).
"""
from __future__ import annotations

import concurrent.futures
import os
import random as pyrandom

import numpy as np

from ..base import MXNetError
from ..ndarray.ndarray import array
from .. import io as io_mod
from .. import recordio
from .image import imdecode, CreateAugmenter


class _RawImageRecordIter(io_mod.DataIter):
    def __init__(self, path_imgrec, data_shape, batch_size,
                 path_imgidx=None, shuffle=False, preprocess_threads=4,
                 label_width=1, data_name="data",
                 label_name="softmax_label", round_batch=True,
                 num_parts=1, part_index=0, seed=0,
                 output_dtype="float32", **aug_kwargs):
        super().__init__(batch_size)
        if output_dtype == "uint8" and (
                aug_kwargs.get("mean") is not None
                or aug_kwargs.get("std") is not None):
            raise MXNetError("uint8 output excludes host-side mean/std — "
                             "normalize on device instead")
        self._out_u8 = output_dtype == "uint8"
        self._rec_path = path_imgrec
        self._idx_path = path_imgidx
        self._shuffle = shuffle
        self._label_width = label_width
        self._round_batch = round_batch
        self.data_shape = tuple(data_shape)
        self._pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=max(1, preprocess_threads))
        self._aug = CreateAugmenter(self.data_shape, **{
            k: v for k, v in aug_kwargs.items()
            if k in ("resize", "rand_crop", "rand_resize", "rand_mirror",
                     "mean", "std", "brightness", "contrast", "saturation",
                     "hue", "pca_noise", "rand_gray", "inter_method")})
        self._rng = pyrandom.Random(seed)

        if path_imgidx:
            self._rec = recordio.MXIndexedRecordIO(path_imgidx, path_imgrec,
                                                   "r")
            seq = list(self._rec.keys)
        elif shuffle or num_parts > 1:
            # no .idx: build the seek table by scanning the framing
            # (native fast path or python walk) — keeps behavior identical
            # to the native iterator, which never needs the .idx
            self._rec = recordio.MXIndexedRecordIO(None, path_imgrec, "r")
            seq = list(self._rec.keys)
        else:
            self._rec = recordio.MXRecordIO(path_imgrec, "r")
            seq = None
        if seq is not None and num_parts > 1:
            part = len(seq) // num_parts
            seq = seq[part_index * part:(part_index + 1) * part]
        self._seq = seq
        self._cur = 0

        c, h, w = self.data_shape
        self.provide_data = [io_mod.DataDesc(data_name, (batch_size, c, h, w))]
        self.provide_label = [io_mod.DataDesc(
            label_name, (batch_size,) if label_width == 1
            else (batch_size, label_width))]
        self.reset()

    def reset(self):
        self._cur = 0
        if self._seq is not None:
            if self._shuffle:
                self._rng.shuffle(self._seq)
        else:
            self._rec.reset()

    def _read_raw(self):
        if self._seq is not None:
            if self._cur >= len(self._seq):
                return None
            s = self._rec.read_idx(self._seq[self._cur])
            self._cur += 1
            return s
        return self._rec.read()

    def _decode_one(self, s):
        header, img = recordio.unpack(s)
        img = imdecode(img)
        for aug in self._aug:
            img = aug(img)
        data = img.asnumpy() if hasattr(img, "asnumpy") else np.asarray(img)
        label = np.asarray(header.label, dtype=np.float32).reshape(-1)
        return data, label

    def next(self):
        raws = []
        while len(raws) < self.batch_size:
            s = self._read_raw()
            if s is None:
                break
            raws.append(s)
        if not raws:
            raise StopIteration
        pad = self.batch_size - len(raws)
        decoded = list(self._pool.map(self._decode_one, raws))
        c, h, w = self.data_shape
        batch_data = np.zeros((self.batch_size, h, w, c), np.float32)
        batch_label = np.zeros((self.batch_size, self._label_width),
                               np.float32)
        for i, (d, l) in enumerate(decoded):
            batch_data[i] = d
            batch_label[i, :len(l)] = l[:self._label_width]
        if pad and self._round_batch and decoded:
            for i in range(len(decoded), self.batch_size):
                d, l = decoded[i % len(decoded)]
                batch_data[i] = d
                batch_label[i, :len(l)] = l[:self._label_width]
        data_nchw = np.transpose(batch_data, (0, 3, 1, 2))
        if self._out_u8:
            data_nchw = np.clip(data_nchw, 0, 255).astype(np.uint8)
        label = batch_label[:, 0] if self._label_width == 1 else batch_label
        return io_mod.DataBatch(data=[array(data_nchw)], label=[array(label)],
                                pad=pad, provide_data=self.provide_data,
                                provide_label=self.provide_label)


class _NativeImageRecordIter(io_mod.DataIter):
    """C++ pipeline path: threaded JPEG decode + augment + batch assembly
    with in-engine prefetch (src/runtime_native.cc mxio_pipe_*; the role of
    iter_image_recordio_2.cc's OMP decode loop + iter_prefetcher.h)."""

    def __init__(self, path_imgrec, data_shape, batch_size, shuffle=False,
                 preprocess_threads=4, label_width=1, data_name="data",
                 label_name="softmax_label", num_parts=1, part_index=0,
                 seed=0, resize=0, rand_crop=False, rand_mirror=False,
                 mean=None, std=None, prefetch_depth=0,
                 output_dtype="float32"):
        from .. import _native
        super().__init__(batch_size)
        from .image import IMAGENET_DEFAULT_MEAN, IMAGENET_DEFAULT_STD
        if mean is True:
            mean = IMAGENET_DEFAULT_MEAN
        if std is True:
            std = IMAGENET_DEFAULT_STD
        offsets, lengths = _native.scan_records(path_imgrec)
        idx = np.arange(len(offsets))
        if num_parts > 1:
            part = len(idx) // num_parts
            idx = idx[part_index * part:(part_index + 1) * part]
        if len(idx) == 0:
            raise MXNetError(f"no records in {path_imgrec}")
        # probe the first record now: non-JPEG payloads (e.g. PNG-packed
        # datasets) must fall back to the python pipeline at construction,
        # not fail mid-epoch
        from .. import recordio as rio
        first = _native.read_records(path_imgrec, offsets[idx[0]:idx[0] + 1],
                                     lengths[idx[0]:idx[0] + 1])[0]
        _, payload = rio.unpack(first)
        if len(payload) < 2 or payload[0] != 0xFF or payload[1] != 0xD8:
            raise _native.MXNetNativeUnavailable("first record is not JPEG")
        self._indices = idx
        self._shuffle = shuffle
        self._rng = np.random.RandomState(seed)
        self.data_shape = tuple(data_shape)
        self._label_width = label_width
        self._pipe = _native.NativeImagePipe(
            path_imgrec, offsets, lengths, batch_size, self.data_shape,
            resize=resize, rand_crop=rand_crop, rand_mirror=rand_mirror,
            mean=mean, std=std, label_width=label_width,
            nthreads=max(1, preprocess_threads), depth=prefetch_depth,
            seed=seed, out_dtype=output_dtype)
        c, h, w = self.data_shape
        self.provide_data = [io_mod.DataDesc(data_name,
                                             (batch_size, c, h, w))]
        self.provide_label = [io_mod.DataDesc(
            label_name, (batch_size,) if label_width == 1
            else (batch_size, label_width))]
        self.reset()

    def reset(self):
        order = self._indices.copy()
        if self._shuffle:
            self._rng.shuffle(order)
        self._pipe.reset(order)

    def next(self):
        out = self._pipe.next()
        if out is None:
            raise StopIteration
        data, label, pad = out
        label = label[:, 0] if self._label_width == 1 else label
        return io_mod.DataBatch(data=[array(data)], label=[array(label)],
                                pad=pad, provide_data=self.provide_data,
                                provide_label=self.provide_label)

    def close(self):
        self._pipe.close()


# augmentations the native pipeline implements; anything else -> python
_NATIVE_AUG_KEYS = {"resize", "rand_crop", "rand_mirror", "mean", "std"}


def ImageRecordIter(path_imgrec, data_shape, batch_size, prefetch_buffer=2,
                    **kwargs):
    """Create the record-image pipeline with background prefetch (matches
    the C++ iterator's registry-factory usage, io.cc:29). Uses the native
    C++ engine when the requested augmentations are within its set and
    every payload is JPEG; falls back to the python pipeline otherwise.

    Beyond-reference knob `output_dtype="uint8"`: deliver RAW bytes (crop/
    mirror only, no mean/std) — 4x less host->device transfer; normalize
    on-device (e.g. DataParallelTrainer input_preproc). The TPU-native
    input regime for transfer-bound hosts."""
    from .. import _native
    _pass_keys = ("shuffle", "preprocess_threads", "label_width",
                  "data_name", "label_name", "num_parts", "part_index",
                  "seed", "output_dtype")
    # augmentation kwargs with EFFECT; a falsy unsupported kwarg
    # (brightness=0.0) is behaviorally absent, so it neither blocks the
    # native path nor is forwarded to it

    def _has_effect(v):
        if isinstance(v, np.ndarray):  # bool(array) raises for size > 1
            return v.size > 0
        return bool(v)

    aug_keys = {k for k, v in kwargs.items()
                if k not in _pass_keys + ("path_imgidx", "round_batch")
                and _has_effect(v)}
    from .. import config
    if (not config.flag("MXNET_TPU_DISABLE_NATIVE_ITER")
            and _native.has_jpeg()
            and tuple(data_shape)[0] == 3
            and kwargs.get("round_batch", True)
            and aug_keys <= _NATIVE_AUG_KEYS):
        try:
            return _NativeImageRecordIter(
                path_imgrec, data_shape, batch_size,
                prefetch_depth=max(2, int(prefetch_buffer or 2)),
                **{k: v for k, v in kwargs.items()
                   if k in _pass_keys or k in (aug_keys & _NATIVE_AUG_KEYS)})
        except (MXNetError, _native.MXNetNativeUnavailable, IOError):
            pass  # non-JPEG payloads / scan failure: python path below
    inner = _RawImageRecordIter(path_imgrec=path_imgrec,
                                data_shape=data_shape,
                                batch_size=batch_size, **kwargs)
    if prefetch_buffer and int(prefetch_buffer) > 0:
        return io_mod.PrefetchingIter(inner)
    return inner
