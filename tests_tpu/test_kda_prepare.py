"""On the chip, at `kimi_linear.train`'s shape (2 x 8,192 x 32 x 128, bf16):
the two prepare kernels (`mx_kdaprep_fwd`, `mx_kdaprep_bwd`) against the XLA
path `lm.kda_prepare`, the four outputs and the nine gradients, the
milliseconds of both (PERF.md quotes them), and the counters that say which
path `_contrib_kda` took."""
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

import mxnet_tpu as mx
from mxnet_tpu.ops import kda_pallas, lm
from mxnet_tpu.telemetry import registry

B, S, H, D = 2, 8192, 32, 128
C = H * D
OUT = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "chiprun_out", "kda_prepare.json")
OUTPUTS = ("q", "k", "v", "g")
GRADIENTS = ("dq", "dk", "dv", "df", "dconv_q", "dconv_k", "dconv_v",
             "dA_log", "ddt_bias")


def _inputs(seed=29, b=B, s=S, h=H):
    """(operands, cotangents), as a mixer hands them over: projections of
    unit scale, taps of a half, decays spread by A_log and dt_bias."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 14)
    bf, c = jnp.bfloat16, h * D
    acts = [jax.random.normal(ks[i], (b, s, c), jnp.float32).astype(bf)
            for i in range(4)]
    taps = [(0.5 * jax.random.normal(ks[4 + i], (c, 4), jnp.float32))
            .astype(bf) for i in range(3)]
    a_log = jax.random.uniform(ks[7], (h,), jnp.float32, -2, 2)
    dt_bias = jax.random.uniform(ks[8], (c,), jnp.float32, -4, 4)
    cots = [jax.random.normal(ks[9 + i], (b, s, c), jnp.float32).astype(bf)
            for i in range(3)] + \
        [jax.random.normal(ks[12], (b, s, c), jnp.float32)]
    return tuple(acts + taps + [a_log, dt_bias]), tuple(cots)


def _xla(q, k, v, f, *params):
    b, s, h = q.shape[0], q.shape[1], params[3].shape[0]
    out = lm.kda_prepare(q, k, v, f, jnp.zeros((b, s, h), q.dtype), *params,
                         num_heads=h)[:4]
    return tuple(o.reshape(b, s, -1) for o in out)


def _kernels(*args):
    return kda_pallas.prepare_kernels(*args, num_heads=args[7].shape[0])


def _both_ways(f):
    def run(args, cots):
        out, vjp = jax.vjp(f, *args)
        return out, vjp(cots)
    return jax.jit(f), jax.jit(run)


def _err(got, want):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


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


def test_kernels_match_float32_no_further_than_the_xla_path():
    """Both bf16 paths against `kda_prepare` on float32 copies of the same
    operands, output by output and gradient by gradient, relative to the
    largest entry. The kernels keep float32 where the XLA path rounds (the
    convolution, the cotangents), so they are held to its reading with a
    tenth of room, or to bf16's own step where that is more."""
    args, cots = _inputs()
    exact = tuple(a.astype(jnp.float32) for a in args), \
        tuple(c.astype(jnp.float32) for c in cots)
    (fwd_k, both_k), (fwd_x, both_x) = _both_ways(_kernels), _both_ways(_xla)
    names = OUTPUTS + GRADIENTS

    def flat(result):
        out, grads = result
        return [np.asarray(a, np.float32) for a in tuple(out) + tuple(grads)]

    want = flat(both_x(*exact))
    errs = {}
    for path, fn in (("kernels", both_k), ("xla", both_x)):
        errs[path] = dict(zip(names, map(_err, flat(fn(args, cots)), want)))
    del want
    ms = {"kernels_forward_ms": _ms(fwd_k, args),
          "kernels_forward_backward_ms": _ms(both_k, (args, cots)),
          "xla_forward_ms": _ms(fwd_x, args),
          "xla_forward_backward_ms": _ms(both_x, (args, cots))}
    _record(errors=errs, rows=kda_pallas.PREP_ROWS, tile=kda_pallas.PREP_TILE,
            **ms)
    for name in names:
        assert errs["kernels"][name] <= max(4e-3, 1.1 * errs["xla"][name]), \
            (name, errs)


def test_a_last_block_past_the_sequence_changes_nothing():
    """1,000 tokens: the second block of 512 reaches 24 rows past the end,
    where the chip's memory holds whatever it held (the interpreter pads):
    the outputs and every sum over rows must not see them."""
    args, cots = _inputs(b=1, s=1000, h=2)
    (_, both_k), (_, both_x) = _both_ways(_kernels), _both_ways(_xla)
    (out_k, grads_k), (out_x, grads_x) = both_k(args, cots), both_x(args, cots)
    errs = {name: _err(a, b) for name, a, b in zip(
        OUTPUTS + GRADIENTS, out_k + grads_k, out_x + grads_x)}
    _record(ragged_against_xla=errs)
    assert all(np.isfinite(e) and e <= 2e-2 for e in errs.values()), errs


def test_the_operator_counts_a_prepare_kernel_call_and_no_fallback():
    """`_contrib_kda` at the cell's shape through the executor, forward and
    backward, as `KDAMixer` calls it."""
    names = (lm.KDA_PREPARE_KERNEL_COUNTER, lm.KDA_PREPARE_FALLBACK_COUNTER,
             lm.KDA_KERNEL_COUNTER, lm.KDA_FALLBACK_COUNTER)
    before = [registry.counter(n).value() for n in names]
    ctx = mx.tpu(0)
    rng = np.random.default_rng(0)

    def nd(shape, scale=1.0, dtype="bfloat16"):
        return mx.nd.array(rng.standard_normal(shape) * scale,
                           ctx=ctx).astype(dtype)

    x = [nd((B, S, C)) for _ in range(4)] + [nd((B, S, H))] + \
        [nd((C, 4), 0.5) for _ in range(3)] + \
        [nd((H,), 0.1, "float32"), nd((C,), 0.1, "float32")]
    for a in x:
        a.attach_grad()
    with mx.autograd.record():
        o = mx.nd._contrib_kda(*x, num_heads=H)
    o.backward()
    assert np.isfinite(o.asnumpy().astype("float32")).all()
    for a in x:
        assert np.isfinite(a.grad.asnumpy().astype("float32")).all()
    moved = [registry.counter(n).value() - b for n, b in zip(names, before)]
    _record(counters=dict(zip(names, moved)))
    assert moved[0] > 0 and moved[2] > 0 and moved[1] == 0 and moved[3] == 0
