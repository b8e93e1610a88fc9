"""Roofline profiler for the flagship training step on a real TPU.

Captures a jax.profiler device trace of the ResNet-50 DataParallelTrainer
step (the exact bench.py configuration), aggregates device time / model
FLOPs / bytes by HLO category, and prints a roofline verdict: what fraction
of the step runs at the HBM bandwidth limit vs the MXU FLOPs limit.

Rerun it whenever the step changes:

    python tools/tpu_roofline.py [--batch 128] [--out trace_dir]

Role of the reference's profiler + nvprof workflow (SURVEY.md §5 tracing);
here the XLA device trace replaces per-op engine timestamps.
"""
from __future__ import annotations

import argparse
import collections
import glob
import gzip
import json
import os
import re
import sys
import tempfile

V5E_PEAK_FLOPS = 197e12   # bf16 MXU peak
V5E_HBM_BW = 819e9        # bytes/sec


def capture(batch, trace_dir, steps=5):
    import jax
    import numpy as np
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import bench as B
    from mxnet_tpu.parallel import data_parallel_mesh, DataParallelTrainer

    sym = B._resnet50_symbol()
    mesh = data_parallel_mesh(1, jax.devices())
    trainer = DataParallelTrainer(
        sym, mesh, optimizer="sgd", learning_rate=0.05, momentum=0.9,
        rescale_grad=1.0 / batch, dtype="bfloat16")
    params, states, aux = trainer.init_state(
        {"data": (batch, 3, 224, 224), "softmax_label": (batch,)})
    rng = np.random.RandomState(0)
    x = rng.uniform(0, 1, (batch, 3, 224, 224)).astype(np.float32)
    y = rng.randint(0, 1000, (batch,)).astype(np.float32)
    inputs = trainer.shard_inputs([x, y])
    for _ in range(3):
        params, states, aux, loss, _ = trainer.step(params, states, aux,
                                                    inputs)
    float(loss)
    with jax.profiler.trace(trace_dir):
        for _ in range(steps):
            params, states, aux, loss, _ = trainer.step(params, states, aux,
                                                        inputs)
        float(loss)
    return steps


def analyze(trace_dir, steps, batch):
    files = glob.glob(os.path.join(trace_dir, "**", "*.trace.json.gz"),
                      recursive=True)
    if not files:
        raise SystemExit(f"no trace found under {trace_dir}")
    with gzip.open(sorted(files)[-1]) as f:
        tr = json.load(f)
    ev = tr["traceEvents"]
    pid_names = {}
    for e in ev:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            pid_names[e["pid"]] = e["args"].get("name")
    agg = collections.defaultdict(lambda: [0, 0, 0, 0])
    per_op = collections.defaultdict(lambda: [0, 0, 0, 0])
    for e in ev:
        if e.get("ph") != "X":
            continue
        if "TPU" not in str(pid_names.get(e.get("pid"), "")):
            continue
        a = e.get("args") or {}
        if "hlo_category" not in a:
            continue
        cat = a["hlo_category"]
        # two rollups, one rule set: by category, and per-HLO (keyed by
        # instruction name so the same op accumulates across steps)
        for r in (agg[cat], per_op[(e.get("name"), cat)]):
            r[0] += int(a.get("device_duration_ps", 0))
            r[1] += int(a.get("model_flops", 0) or 0)
            # -start events report the same raw_bytes_accessed as their
            # -done counterpart (one DMA, two trace events) — count bytes
            # only on completion so totals aren't double-counted
            if not cat.endswith("-start") and cat != "async-start":
                r[2] += int(a.get("raw_bytes_accessed", 0) or 0)
            r[3] += 1

    tot_ps = sum(v[0] for v in agg.values())
    tot_flops = sum(v[1] for v in agg.values())
    tot_bytes = sum(v[2] for v in agg.values())
    step_s = tot_ps / steps / 1e12
    rows = []
    print(f"device step time : {step_s * 1e3:8.2f} ms")
    print(f"model FLOPs/step : {tot_flops / steps / 1e12:8.2f} TFLOP "
          f"({tot_flops / steps / batch / 1e9:.2f} GFLOP/img)")
    print(f"bytes/step       : {tot_bytes / steps / 1e9:8.1f} GB")
    print(f"achieved         : {tot_flops / steps / step_s / 1e12:8.1f} "
          f"TFLOP/s = {tot_flops / steps / step_s / V5E_PEAK_FLOPS:.1%} "
          "of v5e bf16 peak")
    print(f"HBM floor        : {tot_bytes / steps / V5E_HBM_BW * 1e3:8.2f} "
          "ms (bytes / 819 GB/s) vs measured "
          f"{step_s * 1e3:.2f} ms")
    hdr = (f"{'category':26s} {'ms/step':>8s} {'%time':>6s} "
           f"{'TFLOP/s':>8s} {'GB/s':>6s} {'GB/step':>8s} {'n':>5s}")
    print(hdr)
    print("-" * len(hdr))
    for c, (d, fl, b, n) in sorted(agg.items(), key=lambda kv: -kv[1][0]):
        sec = d / steps / 1e12
        if sec <= 0:
            continue
        rows.append({
            "category": c, "ms_per_step": d / steps / 1e9,
            "pct_time": 100 * d / tot_ps,
            "tflops": fl / steps / sec / 1e12,
            "gbps": b / steps / sec / 1e9,
            "gb_per_step": b / steps / 1e9, "count": n // steps})
        print(f"{c:26s} {d / steps / 1e9:8.2f} {100 * d / tot_ps:6.1f} "
              f"{fl / steps / sec / 1e12:8.1f} {b / steps / sec / 1e9:6.0f} "
              f"{b / steps / 1e9:8.2f} {n // steps:5d}")
    top = sorted(per_op.items(), key=lambda kv: -kv[1][0])[:40]
    print(f"\ntop HLOs by device time "
          f"({'name':s} | cat | ms | GB | TFLOP/s | GB/s):")
    top_rows = []
    for (name, cat), (d, fl, b, n) in top:
        sec = d / steps / 1e12
        if sec <= 0:
            continue
        top_rows.append({
            "name": name, "category": cat, "ms": d / steps / 1e9,
            "gb": b / steps / 1e9,
            "tflops": fl / steps / sec / 1e12,
            "gbps": b / steps / sec / 1e9})
        print(f"  {name[:72]:72s} {cat:18s} {d / steps / 1e9:6.2f} "
              f"{b / steps / 1e9:6.2f} {fl / steps / sec / 1e12:6.1f} "
              f"{b / steps / sec / 1e9:6.0f}")
    return {
        "step_ms": step_s * 1e3,
        "tflop_per_step": tot_flops / steps / 1e12,
        "gb_per_step": tot_bytes / steps / 1e9,
        "mfu": tot_flops / steps / step_s / V5E_PEAK_FLOPS,
        "hbm_floor_ms": tot_bytes / steps / V5E_HBM_BW * 1e3,
        "categories": rows,
        "top_hlos": top_rows,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--out", default=None,
                    help="trace dir (default: temp dir)")
    ap.add_argument("--json", default=None,
                    help="also write the summary as JSON here")
    args = ap.parse_args()
    trace_dir = args.out or tempfile.mkdtemp(prefix="tpu_roofline_")
    steps = capture(args.batch, trace_dir, args.steps)
    summary = analyze(trace_dir, steps, args.batch)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(summary, f, indent=1)
        print(f"summary written to {args.json}")


if __name__ == "__main__":
    main()
