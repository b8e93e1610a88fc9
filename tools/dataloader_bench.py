"""DataLoader worker-type crossover bench.

Measures inline / thread / process workers on two dataset profiles:
- "gil": a pure-python per-sample transform (holds the GIL) — the
  reference's motivating case for forked workers
- "numpy": a vectorized numpy transform (releases the GIL in C) — the
  thread pool's home turf (no pickling, shared memory)

Guidance (see docstring in gluon/data/dataloader.py): threads for
GIL-releasing pipelines; processes for GIL-bound python transforms,
scaling roughly with cores. NOTE a 1-core host
cannot show the process win — run on a multi-core host for the
crossover; the numbers below still show the bookkeeping overhead of
each path.

Run: python tools/dataloader_bench.py [--n 512] [--workers 4]
"""
from __future__ import annotations

import argparse
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np  # noqa: E402


class GilBound:
    """Pure-python per-element transform: the GIL serializes threads."""

    def __init__(self, n, size=512):
        rng = np.random.RandomState(0)
        self._x = rng.uniform(0, 1, (n, size)).astype(np.float32)

    def __len__(self):
        return len(self._x)

    def __getitem__(self, i):
        row = self._x[i]
        out = [0.0] * len(row)
        for j in range(len(row)):
            out[j] = float(row[j]) * 2.0 + 1.0
        return np.asarray(out, np.float32), np.float32(i % 10)


class NumpyHeavy:
    """Vectorized transform: numpy releases the GIL."""

    def __init__(self, n, size=128):
        rng = np.random.RandomState(0)
        self._x = rng.uniform(0, 1, (n, size, size)).astype(np.float32)

    def __len__(self):
        return len(self._x)

    def __getitem__(self, i):
        a = self._x[i]
        for _ in range(4):
            a = a @ a.T
            a = a / (np.abs(a).max() + 1e-6)
        return a.astype(np.float32), np.float32(i % 10)


def run(ds, batch, workers, worker_type, device_feed=False):
    from mxnet_tpu.gluon.data import DataLoader
    dl = DataLoader(ds, batch_size=batch, shuffle=False,
                    num_workers=workers, worker_type=worker_type)
    for _ in dl:        # warm (spawns pools, pages data)
        break
    if device_feed:
        # stage each batch onto device on the feeder thread — the loader
        # handles host-side collation, the DeviceFeed hides the
        # host->device boundary (the consumer finds batches resident)
        import jax
        from mxnet_tpu.pipeline import DeviceFeed
        dev = jax.devices()[0]

        def stage(b):
            return tuple(jax.device_put(np.asarray(
                getattr(a, "_data", a)), dev) for a in b)

        t0 = time.perf_counter()
        n = 0
        with DeviceFeed(iter(dl), stage=stage, name="dl_bench") as feed:
            for b in feed:
                n += int(b[0].shape[0])
        return n / (time.perf_counter() - t0)
    t0 = time.perf_counter()
    n = 0
    for b in dl:
        n += int(b[0].shape[0])
    return n / (time.perf_counter() - t0)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=512)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--device-feed", action="store_true",
                    help="also time each config with DeviceFeed staging "
                         "batches onto the device (mxnet_tpu.pipeline)")
    a = ap.parse_args()
    print(f"host cores: {os.cpu_count()}")
    for name, ds in (("gil-bound", GilBound(a.n)),
                     ("numpy-heavy", NumpyHeavy(a.n))):
        r0 = run(ds, a.batch, 0, "thread")
        rt = run(ds, a.batch, a.workers, "thread")
        rp = run(ds, a.batch, a.workers, "process")
        print(f"{name:12s}: inline {r0:8.0f}/s  "
              f"threads({a.workers}) {rt:8.0f}/s  "
              f"procs({a.workers}) {rp:8.0f}/s")
        if a.device_feed:
            f0 = run(ds, a.batch, 0, "thread", device_feed=True)
            ft = run(ds, a.batch, a.workers, "thread", device_feed=True)
            print(f"{'':12s}  +device-feed: inline {f0:8.0f}/s  "
                  f"threads({a.workers}) {ft:8.0f}/s")


if __name__ == "__main__":
    main()
