"""On the chip, at `kimi_linear.train`'s shape (2 x 8,192 x 32 x 128, bf16):
the KDA kernels against the XLA path `lm.kda_chunked`, output and all five
gradients, the milliseconds of both (PERF.md quotes them), and the counters
that say which path a program for the TPU took."""
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.ops import lm
from mxnet_tpu.telemetry import registry

B, S, H, D = 2, 8192, 32, 128
OUT = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "chiprun_out", "kda_kernels.json")


def _inputs(seed=27):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q, k = (jax.random.normal(ks[i], (B, S, H, D), jnp.float32)
            for i in (0, 1))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * D ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (B, S, H, D), jnp.float32)
    # log-decays from almost none to e^-7 a token
    g = -jnp.exp(jax.random.uniform(ks[3], (B, S, H, D), jnp.float32, -6, 2))
    beta = jax.random.uniform(ks[4], (B, S, H), jnp.float32)
    bf = jnp.bfloat16
    return q.astype(bf), k.astype(bf), v.astype(bf), g, beta


def _err(got, want):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def _ms(fn, args, reps=3):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e3


def _paths(chunk):
    def make(force):
        def f(*a):
            return lm.kda(*a, chunk=chunk, force=force)

        def loss(*a):
            return jnp.sum(f(*a).astype(jnp.float32) * jnp.cos(
                jnp.arange(D, dtype=jnp.float32)))
        return jax.jit(f), jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))
    return make("pallas"), make("xla")


def _record(**kv):
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    have = json.load(open(OUT)) if os.path.exists(OUT) else {}
    have.update(kv)
    with open(OUT, "w") as f:
        json.dump(have, f, indent=1)
    print(json.dumps(kv))


def test_kernels_match_the_xla_path_at_the_cells_shape():
    """Both bf16 paths against `kda_chunked` on float32 copies of the same
    operands at HIGHEST. The kernels are held to what the XLA path itself
    reads there (a tenth of room), or to the 5e-3 that PERF.md records for
    the op on the chip (PR 26) where that is more. The two bf16 paths
    differ from each other by as much as each from float32: XLA's backward
    rounds every cotangent of a bf16 operand to bf16, the kernels keep
    them float32 up to the product."""
    args = _inputs()
    exact = tuple(a.astype(jnp.float32) for a in args)
    (fwd_k, grad_k), (fwd_x, grad_x) = _paths(64)
    names = ("o", "dq", "dk", "dv", "dg", "dbeta")
    want = (fwd_x(*exact),) + tuple(grad_x(*exact))
    got_k = (fwd_k(*args),) + tuple(grad_k(*args))
    got_x = (fwd_x(*args),) + tuple(grad_x(*args))
    errs = {"kernels": dict(zip(names, map(_err, got_k, want))),
            "xla": dict(zip(names, map(_err, got_x, want))),
            "kernels_against_xla": dict(zip(names, map(_err, got_k, got_x)))}
    del want, got_k, got_x, exact
    ms = {"kernels_forward_ms": _ms(fwd_k, args),
          "kernels_forward_backward_ms": _ms(grad_k, args),
          "xla_forward_ms": _ms(fwd_x, args),
          "xla_forward_backward_ms": _ms(grad_x, args)}
    _record(chunk_64={"errors": errs, **ms})
    for name in names:
        assert errs["kernels"][name] <= max(5e-3, 1.1 * errs["xla"][name]), \
            errs


@pytest.mark.parametrize("chunk", [32, 128])
def test_other_chunks_time_and_agree(chunk):
    """The chunk of 64 against 32 and 128 (PERF.md section 7): both paths
    where the kernels take the chunk, the XLA path alone where not."""
    from mxnet_tpu.ops import kda_pallas
    args = _inputs()
    (fwd_k, grad_k), (fwd_x, grad_x) = _paths(chunk)
    row = {"xla_forward_ms": _ms(fwd_x, args),
           "xla_forward_backward_ms": _ms(grad_x, args)}
    if chunk in kda_pallas.CHUNKS:
        row["errors"] = {"o": _err(fwd_k(*args), fwd_x(*args))}
        row["kernels_forward_ms"] = _ms(fwd_k, args)
        row["kernels_forward_backward_ms"] = _ms(grad_k, args)
        assert row["errors"]["o"] <= 5e-3
    _record(**{f"chunk_{chunk}": row})


def test_the_operator_on_the_tpu_counts_a_kernel_call_and_no_fallback():
    """`_contrib_kda` at the cell's shape through the executor, forward
    and backward, as `KDAMixer` calls it."""
    calls = registry.counter(lm.KDA_KERNEL_COUNTER)
    falls = registry.counter(lm.KDA_FALLBACK_COUNTER)
    before = calls.value(), falls.value()
    ctx = mx.tpu(0)
    rng = np.random.default_rng(0)

    def nd(shape, scale=1.0, dtype="bfloat16"):
        return mx.nd.array(rng.standard_normal(shape) * scale,
                           ctx=ctx).astype(dtype)

    c = H * D
    x = [nd((B, S, c)) for _ in range(4)] + [nd((B, S, H))] + \
        [nd((c, 4), 0.5) for _ in range(3)] + \
        [nd((H,), 0.1, "float32"), nd((c,), 0.1, "float32")]
    for a in x:
        a.attach_grad()
    with mx.autograd.record():
        o = mx.nd._contrib_kda(*x, num_heads=H)
    o.backward()
    assert np.isfinite(o.asnumpy().astype("float32")).all()
    assert np.isfinite(x[0].grad.asnumpy().astype("float32")).all()
    assert calls.value() > before[0]
    assert falls.value() == before[1]
