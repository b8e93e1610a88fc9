"""On the chip, one layer-step of flash attention at the two shapes the
language-model cells run (2 x 8,192 tokens, bf16, causal: 32 heads of
192 / 128, and 32 query heads over 8 k/v heads of 64): the milliseconds of
the forward and of the one backward kernel (with the `delta` fusion before
it) at the default blocks and at 256, 512 and 1,024 on each side
(`chiprun_out/flash_kernels.json`; PERF.md quotes them), and that what
`_auto_block` picks is within a few percent of the best of them."""
import json
import os
import time

import jax
import jax.numpy as jnp
import pytest

from mxnet_tpu.ops import attention as at

S = 8192
SHAPES = {"32_heads_192_128": (2, 32, 32, 192, 128),
          "32_over_8_heads_64": (2, 32, 8, 64, 64)}
OUT = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "chiprun_out", "flash_kernels.json")


def _ms(fn, args, reps=5):
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


@pytest.mark.parametrize("shape", SHAPES)
def test_layer_step_milliseconds_by_block(shape):
    b, h, h_kv, d, dv = SHAPES[shape]
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    q, k, v, g = (jax.random.normal(key, dims, jnp.float32)
                  .astype(jnp.bfloat16) for key, dims in zip(ks, (
                      (b, h, S, d), (b, h_kv, S, d), (b, h_kv, S, dv),
                      (b, h, S, dv))))
    scale = d ** -0.5

    def kernels(block_q, block_k):
        fwd = jax.jit(lambda q, k, v: at._flash_pallas(
            q, k, v, True, scale, block_q=block_q, block_k=block_k))
        bwd = jax.jit(lambda q, k, v, o, lse, g: at._flash_pallas_bwd(
            q, k, v, o, lse, g, True, scale, block_q=block_q,
            block_k=block_k))
        return fwd, bwd

    fwd, bwd = kernels(None, None)
    out, lse = fwd(q, k, v)
    row = {"default": {"forward_ms": _ms(fwd, (q, k, v)),
                       "backward_ms": _ms(bwd, (q, k, v, out, lse, g))}}
    for block_q in (256, 512, 1024):
        for block_k in (256, 512, 1024):
            fwd, bwd = kernels(block_q, block_k)
            try:
                row[f"{block_q}x{block_k}"] = {
                    "forward_ms": _ms(fwd, (q, k, v), reps=3),
                    "backward_ms": _ms(bwd, (q, k, v, out, lse, g), reps=3)}
            except Exception as e:   # Mosaic refuses the block: recorded
                row[f"{block_q}x{block_k}"] = {"refused": str(e)[-200:]}
    _record(**{shape: row})
    best = {way: min(r[way] for r in row.values() if way in r)
            for way in ("forward_ms", "backward_ms")}
    assert at._auto_block(S) == 512
    for way, least in best.items():
        assert row["default"][way] <= 1.08 * least, (way, row)
