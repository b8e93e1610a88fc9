"""Flash-attention performance curve on the real chip.

Sweeps seq x block-size x causal (+ GQA points) over the Pallas
fwd+bwd kernels, reporting tokens/sec and model-flop MFU per point.
MFU convention matches bench.py: 6 S^2 D matmuls (fwd 2 + bwd 4) at
2 FLOPs/MAC, halved for causal — the algorithmic count; the recompute
passes the flash kernels actually execute are not credited.

Run (on TPU): python tools/attention_sweep.py [--quick]
Writes a markdown table to stdout.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np  # noqa: E402

V5E_PEAK = 197e12


def measure(b, h, s, d, causal, block_q, block_k, h_kv=None, iters=8):
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.attention import flash_attention

    h_kv = h_kv or h
    rng = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(rng, 3)
    q = jax.random.normal(kq, (b, h, s, d), jnp.bfloat16)
    k = jax.random.normal(kk, (b, h_kv, s, d), jnp.bfloat16)
    v = jax.random.normal(kv, (b, h_kv, s, d), jnp.bfloat16)

    @jax.jit
    def step(q, k, v):
        def loss(q, k, v):
            out = flash_attention(q, k, v, causal=causal, force="pallas",
                                  block_q=block_q, block_k=block_k)
            return jnp.sum(out.astype(jnp.float32) ** 2)
        return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

    l, _ = step(q, k, v)
    float(l)
    rates = []
    for _ in range(3):
        t0 = time.perf_counter()
        out = None
        for _ in range(iters):
            out = step(q, k, v)
        float(out[0])
        rates.append(iters * b * s / (time.perf_counter() - t0))
    tps = sorted(rates)[1]
    flops_per_tok = 6 * 2 * h * s * d / (2 if causal else 1)
    mfu = tps * flops_per_tok / V5E_PEAK
    return tps, mfu


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    a = ap.parse_args()
    d = 128
    points = []
    # fixed token budget per point: B scales down as S grows
    seqs = [(4096, 4), (8192, 2), (16384, 1)]
    blocks = [(128, 128)] if a.quick else \
        [(128, 128), (256, 256), (512, 512), (256, 512), (512, 256)]
    print("| seq | batch | blocks | causal | tok/s | MFU |")
    print("|---|---|---|---|---|---|")
    for s, b in seqs:
        for bq, bk in blocks:
            for causal in (True, False):
                try:
                    tps, mfu = measure(b, 8, s, d, causal, bq, bk)
                    points.append((s, b, bq, bk, causal, tps, mfu))
                    print(f"| {s} | {b} | {bq}/{bk} | {causal} | "
                          f"{tps:,.0f} | {mfu:.3f} |", flush=True)
                except Exception as e:
                    print(f"| {s} | {b} | {bq}/{bk} | {causal} | "
                          f"FAILED {type(e).__name__} | |", flush=True)
    # GQA: 8 q-heads over {2, 1} kv heads at seq 8192, best block
    print("| seq | batch | blocks | kv_heads | tok/s | MFU |")
    print("|---|---|---|---|---|---|")
    for h_kv in (8, 2, 1):
        try:
            tps, mfu = measure(2, 8, 8192, d, True, 256, 256, h_kv=h_kv)
            print(f"| 8192 | 2 | 256/256 | {h_kv} | {tps:,.0f} | "
                  f"{mfu:.3f} |", flush=True)
        except Exception as e:
            print(f"| 8192 | 2 | 256/256 | {h_kv} | FAILED "
                  f"{type(e).__name__} | |", flush=True)


if __name__ == "__main__":
    main()
