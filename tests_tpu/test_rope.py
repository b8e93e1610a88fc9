"""On the chip, at `kanana2.train`'s shape (2 x 32 heads x 8,192 x 192,
the last 64 dims of a head rotated, bf16): `lm.rope` against the same
rotation of float32 copies, forward and backward, with its milliseconds and
the bytes it has to move (PERF.md quotes them), and the counter."""
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

import mxnet_tpu as mx
from mxnet_tpu.ops import lm
from mxnet_tpu.telemetry import registry

B, H, S, W, ROTARY, OFFSET, THETA = 2, 32, 8192, 192, 64, 128, 1e6
OUT = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "chiprun_out", "rope.json")


def _reference():
    """tests/reference_models/deepseek_v3.py, by path (tests/ is no
    package and this lane has its own conftest)."""
    import importlib.util
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__))), "tests", "reference_models", "deepseek_v3.py")
    spec = importlib.util.spec_from_file_location("deepseek_v3_ref", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _plain(x, interleave):
    """The reference's rotation (float32, every step an array of its own)
    laid over x (B, H, S, W): the rotated dims turned, the rest as they
    are."""
    turned = _reference().rope(
        jnp.moveaxis(x[..., OFFSET:OFFSET + ROTARY], 2, 1), THETA, interleave)
    return jnp.concatenate([x[..., :OFFSET], jnp.moveaxis(turned, 1, 2),
                            x[..., OFFSET + ROTARY:]], -1)


def _both_ways(f):
    def run(x, dy):
        out, vjp = jax.vjp(f, x)
        return out, vjp(dy)[0]
    return jax.jit(f), jax.jit(run)


def _err(got, want):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def _ms(fn, args, reps=10):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e3


def test_rope_at_the_cells_shape_against_float32():
    ks = jax.random.split(jax.random.PRNGKey(30), 2)
    x, dy = (jax.random.normal(k, (B, H, S, W), jnp.float32)
             .astype(jnp.bfloat16) for k in ks)
    record = {"shape": [B, H, S, W], "rotary_dim": ROTARY, "offset": OFFSET,
              # one read and one write of the operand, 2 bytes an element
              "bytes_a_call": 2 * 2 * B * H * S * W}
    for interleave in (True, False):
        fwd, both = _both_ways(
            lambda t: lm.rope(t, ROTARY, OFFSET, THETA, interleave))
        _, exact = _both_ways(lambda t: _plain(t, interleave))
        got = both(x, dy)
        want = exact(x.astype(jnp.float32), dy.astype(jnp.float32))
        errs = [_err(g, w) for g, w in zip(got, want)]
        # bf16 rounds the result once: half a step of 2^-8 of the largest
        assert max(errs) <= 4e-3, errs
        # what is not rotated passes through to the bit
        assert bool(jnp.all(got[0][..., :OFFSET] == x[..., :OFFSET]))
        key = "interleaved" if interleave else "half_split"
        record[key] = {"forward_err": errs[0], "backward_err": errs[1],
                       "forward_ms": _ms(fwd, (x,)),
                       "forward_backward_ms": _ms(both, (x, dy))}
        record[key]["forward_GB_per_s"] = record["bytes_a_call"] / \
            record[key]["forward_ms"] / 1e6
    # float32 operands (check (b)'s side): the product with the signed
    # permutation runs at highest precision and stays exact
    xf = x[:1, :4].astype(jnp.float32) * 1.001
    got = jax.jit(lambda t: lm.rope(t, ROTARY, OFFSET, THETA, True))(xf)
    record["float32_err"] = _err(got, jax.jit(
        lambda t: _plain(t, True))(xf))
    assert record["float32_err"] <= 2e-6, record
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "w") as f:
        json.dump(record, f, indent=1)
    print(json.dumps(record))


def test_the_op_counts_its_calls_on_the_chip():
    counter = registry.counter(lm.ROPE_COUNTER)
    before = counter.value()
    x = mx.nd.ones((1, 2, 128, W), ctx=mx.tpu(0))
    out = mx.nd._contrib_rope(x, rotary_dim=ROTARY, offset=OFFSET,
                              theta=THETA)
    assert out.shape == x.shape and counter.value() == before + 1
