"""On the chip, one layer-step of flash attention at `trinity_mini.train`'s
shape (2 x 8,192 tokens, bf16, 32 query heads over 4 k/v heads of 128):
the milliseconds of the forward kernel, of the backward kernel (with the
`delta` fusion before it) and of a whole value-and-gradient, causal and
with the cell's window of 2,048 keys (`chiprun_out/window_kernels.json`;
PERF.md quotes them), against the ratio of the block pairs the two visit
(70 / 136 at blocks of 512); and the windowed kernels against the dense
oracle with the window's mask, forward and gradients, on one k/v head's
group of one sequence of 4,096 tokens."""
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from mxnet_tpu.ops import attention as at

B, H, H_KV, S, D, WINDOW = 2, 32, 4, 8192, 128, 2048
OUT = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "chiprun_out", "window_kernels.json")


def _ms(fn, args, reps=10):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e3


def _record(**kv):
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    have = json.load(open(OUT)) if os.path.exists(OUT) else {}
    have.update(kv)
    with open(OUT, "w") as f:
        json.dump(have, f, indent=1)
    print(json.dumps(kv))


def _operands(b, h, h_kv, s, seed=5):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    dims = ((b, h, s, D), (b, h_kv, s, D), (b, h_kv, s, D), (b, h, s, D))
    return tuple(jax.random.normal(key, shape, jnp.float32)
                 .astype(jnp.bfloat16) for key, shape in zip(ks, dims))


def _pairs(window):
    blocks = S // at._auto_block(S)
    return sum(len(range(int(first), int(stop)))
               for qi in range(blocks)
               for first, stop, _ in at._pair_runs(
                   jnp.int32(qi * at._auto_block(S)), at._auto_block(S),
                   at._auto_block(S), blocks, True, window))


def test_windowed_against_causal_milliseconds():
    q, k, v, g = _operands(B, H, H_KV, S)
    scale = D ** -0.5
    row = {}
    for name, window in (("causal", None), ("window_2048", WINDOW)):
        fwd = jax.jit(lambda q, k, v, w=window: at._flash_pallas(
            q, k, v, True, scale, window=w))
        bwd = jax.jit(lambda q, k, v, o, lse, g, w=window:
                      at._flash_pallas_bwd(q, k, v, o, lse, g, True, scale,
                                           window=w))
        both = jax.jit(jax.value_and_grad(
            lambda q, k, v, w=window: at.flash_attention(
                q, k, v, causal=True, scale=scale, platform="tpu",
                window=w).astype(jnp.float32).sum(), argnums=(0, 1, 2)))
        out, lse = fwd(q, k, v)
        row[name] = {"forward_ms": _ms(fwd, (q, k, v)),
                     "backward_ms": _ms(bwd, (q, k, v, out, lse, g)),
                     "value_and_grad_ms": _ms(both, (q, k, v)),
                     "block_pairs": _pairs(window)}
    ratio = {way: row["window_2048"][way] / row["causal"][way]
             for way in ("forward_ms", "backward_ms", "value_and_grad_ms")}
    row["window_over_causal"] = ratio
    row["pairs_ratio"] = row["window_2048"]["block_pairs"] / \
        row["causal"]["block_pairs"]
    _record(layer_step=row)
    assert row["pairs_ratio"] == 70 / 136
    fb = (row["window_2048"]["forward_ms"] + row["window_2048"]["backward_ms"]
          ) / (row["causal"]["forward_ms"] + row["causal"]["backward_ms"])
    assert fb <= 0.6, row


def test_windowed_kernels_against_the_dense_oracle():
    """One sequence of 4,096 tokens (the oracle's (S, S) scores of 8 heads
    at 8,192 are 2 GiB an array), one k/v head and its 8 query heads: the
    kernels' output and gradients against the oracle in float32 of the
    same bf16 operands."""
    q, k, v, g = _operands(1, 8, 1, S // 2, seed=7)
    scale = D ** -0.5

    def kernels(q, k, v):
        return at.flash_attention(q, k, v, causal=True, scale=scale,
                                  platform="tpu", window=WINDOW)

    def oracle(q, k, v):
        with jax.default_matmul_precision("highest"):
            k, v = (jnp.repeat(t, 8, 1).astype(jnp.float32) for t in (k, v))
            return at.reference_attention(q.astype(jnp.float32), k, v,
                                          causal=True, scale=scale,
                                          window=WINDOW)

    got, got_vjp = jax.vjp(kernels, q, k, v)
    want, want_vjp = jax.vjp(jax.jit(oracle), q, k, v)
    errs = {"out": float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))
                         / jnp.max(jnp.abs(want)))}
    for name, a, b in zip(("dq", "dk", "dv"), got_vjp(g),
                          want_vjp(g.astype(jnp.float32))):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        errs[name] = float(np.max(np.abs(a - b)) / np.max(np.abs(b)))
    _record(against_oracle=errs)
    for name, err in errs.items():
        assert err < 3e-2, errs
