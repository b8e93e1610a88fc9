"""Sparse-vs-dense micro-lane at the reference's benchmark shapes.

Reference: benchmark/python/sparse/sparse_op.py (avazu: feature_dim 1M,
m=500, batch 64/128; kdda: feature_dim 20.2M, m=200, batch 64) and
benchmark/python/sparse/updater.py (row_sparse SGD on an embedding-sized
table). Two lanes, each dense-vs-sparse on the SAME values:

  dot   — dot(csr, dense):   gather kernel (ops/sparse_ops.ell_dot)
          vs dense jnp.dot at matching density
  sgd   — row_sparse SGD update touching B rows of an (F, M) table:
          scatter kernel (rows_sgd_update) vs the dense-masked
          lazy_update op over the full table

Timings are DEVICE time from jax.profiler traces, so host dispatch and
upload time stay out of them. Results land in PARITY.md's sparse section.

    python tools/sparse_bench.py [--json out.json]
"""
from __future__ import annotations

import argparse
import collections
import glob
import gzip
import json
import os
import sys
import tempfile

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def device_ms(trace_dir):
    files = glob.glob(os.path.join(trace_dir, "**", "*.trace.json.gz"),
                      recursive=True)
    with gzip.open(sorted(files)[-1]) as f:
        tr = json.load(f)
    ev = tr["traceEvents"]
    pid_names = {e["pid"]: e["args"].get("name") for e in ev
                 if e.get("ph") == "M" and e.get("name") == "process_name"}
    tot = 0.0
    for e in ev:
        if e.get("ph") != "X" or \
                "TPU" not in str(pid_names.get(e.get("pid"), "")):
            continue
        a = e.get("args") or {}
        if "hlo_category" not in a:
            continue
        c = a["hlo_category"]
        if c.endswith("-start"):
            continue
        tot += int(a.get("device_duration_ps", 0)) / 1e9
    return tot


def timed(fn, args, reps=5):
    import jax
    out = fn(*args)
    np.asarray(jax.tree_util.tree_leaves(out)[0])[:1]
    with tempfile.TemporaryDirectory() as td:
        with jax.profiler.trace(td):
            for _ in range(reps):
                out = fn(*args)
            np.asarray(jax.tree_util.tree_leaves(out)[0])[:1]
        return device_ms(td) / reps


def bench_dot(batch, feat, m, nnz_per_row, rng):
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import sparse_ops as sp

    idx = np.stack([rng.choice(feat, nnz_per_row, replace=False)
                    for _ in range(batch)]).astype(np.int32)
    val = rng.normal(0, 1, (batch, nnz_per_row)).astype(np.float32)
    w = jnp.asarray(rng.normal(0, 1, (feat, m)).astype(np.float32))
    vald, idxd = jnp.asarray(val), jnp.asarray(idx)

    t_sparse = timed(jax.jit(sp.ell_dot), (vald, idxd, w))

    dense_lhs = np.zeros((batch, feat), np.float32)
    np.put_along_axis(dense_lhs, idx, val, axis=1)
    dl = jnp.asarray(dense_lhs)
    t_dense = timed(jax.jit(jnp.dot), (dl, w))

    # parity while we're here — at fp32 matmul precision: the DEFAULT-
    # precision dense dot accumulates a 1M-element contraction in bf16
    # and is the LESS accurate side (the gather sums nnz exact values)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(sp.ell_dot)(vald, idxd, w))
        want = np.asarray(jax.jit(jnp.dot)(dl, w))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    return t_dense, t_sparse


def bench_sgd(feat, m, batch_rows, rng):
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import sparse_ops as sp
    from mxnet_tpu.ops import optimizer_ops  # noqa: F401 (registry)
    import mxnet_tpu as mx

    w = jnp.asarray(rng.normal(0, 1, (feat, m)).astype(np.float32))
    rows = jnp.asarray(np.sort(rng.choice(feat, batch_rows,
                                          replace=False)).astype(np.int32))
    gvals = jnp.asarray(rng.normal(0, 1, (batch_rows, m)).astype(np.float32))

    t_scatter = timed(
        jax.jit(lambda w, r, g: sp.rows_sgd_update(w, r, g, 0.1, wd=0.01)),
        (w, rows, gvals))

    # dense-masked lazy update (what the repo did before components):
    # full-table where(mask) pass on the same values
    dense_grad = jnp.zeros((feat, m), jnp.float32).at[rows].set(gvals)

    def dense_lazy(w, g):
        touched = jnp.any(g != 0, axis=1, keepdims=True)
        new_w = w - 0.1 * (g + 0.01 * w)
        return jnp.where(touched, new_w, w)

    t_dense = timed(jax.jit(dense_lazy), (w, dense_grad))
    return t_dense, t_scatter


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None)
    args = ap.parse_args()
    rng = np.random.RandomState(0)
    out = {}

    # avazu-shaped dot: 1M features, m=500, ~15 nnz/row
    for name, (b, f, m, k) in {
        "avazu_b128": (128, 1_000_000, 500, 16),
        "avazu_b64": (64, 1_000_000, 500, 16),
        "kdda_mini_b64": (64, 2_500_000, 200, 64),
    }.items():
        td, ts = bench_dot(b, f, m, k, rng)
        out[f"dot_{name}"] = {"dense_ms": round(td, 3),
                              "sparse_ms": round(ts, 3),
                              "speedup": round(td / ts, 1)}
        print(f"dot {name:14s}: dense {td:7.3f} ms  sparse {ts:7.3f} ms  "
              f"x{td / ts:6.1f}", flush=True)

    # one sgd point: each lane uploads ~4 GB from the host. Note the
    # conservatism: timed without buffer donation, so the scatter side pays a full
    # table copy (XLA copies the 2 GB operand before .at[].add); in a
    # donated training step the scatter is near-free while dense-masked
    # still streams the whole table.
    for name, (f, m, b) in {"table_1Mx512_b128": (1_000_000, 512, 128),
                            }.items():
        td, ts = bench_sgd(f, m, b, rng)
        out[f"sgd_{name}"] = {"dense_masked_ms": round(td, 3),
                              "scatter_ms": round(ts, 3),
                              "speedup": round(td / ts, 1)}
        print(f"sgd {name:18s}: dense {td:7.3f} ms  scatter {ts:7.3f} ms  "
              f"x{td / ts:6.1f}", flush=True)

    if args.json:
        with open(args.json, "w") as fh:
            json.dump(out, fh, indent=1)
        print("written", args.json)


if __name__ == "__main__":
    main()
