"""Flash-attention kernel tests (Pallas interpreter on the CPU lane).

The real-chip compiled-kernel parity check lives in tests_tpu/.
Comparisons run under matmul precision 'highest' — this jax build's
DEFAULT precision is bf16-grade even on CPU, which would mask kernel
bugs behind matmul noise.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.ops import attention as at
from test_kimi_linear import _close


def _qkv(b=2, h=2, s=256, d=128, seed=0):
    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.normal(size=(b, h, s, d))
                             .astype(np.float32)) for _ in range(3))


@pytest.mark.parametrize("causal", [False, True])
def test_flash_kernel_matches_reference(causal):
    q, k, v = _qkv()
    with jax.default_matmul_precision("highest"):
        want = at.reference_attention(q, k, v, causal=causal)
        got = at.flash_attention(q, k, v, causal=causal, force="interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_flash_kernel_single_and_multi_block():
    for s in (128, 512):
        q, k, v = _qkv(b=1, h=1, s=s, seed=s)
        with jax.default_matmul_precision("highest"):
            want = at.reference_attention(q, k, v, causal=True)
            got = at.flash_attention(q, k, v, causal=True,
                                     force="interpret")
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-5)


def test_flash_attention_op_dispatch():
    """Registered op runs (XLA fallback on the CPU lane) and matches."""
    rng = np.random.RandomState(1)
    arr = rng.normal(size=(1, 2, 32, 16)).astype(np.float32)
    q = mx.nd.array(arr)
    out = mx.nd.contrib.flash_attention(q, q, q, causal=True)
    want = at.reference_attention(jnp.asarray(arr), jnp.asarray(arr),
                                  jnp.asarray(arr), causal=True)
    np.testing.assert_allclose(out.asnumpy(), np.asarray(want), rtol=1e-4,
                               atol=1e-5)
    # symbolic composition
    sym = mx.sym.contrib.flash_attention(
        mx.sym.Variable("q"), mx.sym.Variable("k"), mx.sym.Variable("v"))
    ex = sym.simple_bind(mx.cpu(), q=(1, 2, 32, 16), k=(1, 2, 32, 16),
                         v=(1, 2, 32, 16))
    assert ex.forward()[0].shape == (1, 2, 32, 16)


def test_flash_attention_grad():
    """Autodiff through the dispatcher (XLA path) works for training."""
    q, k, v = _qkv(b=1, h=1, s=64, d=32, seed=9)

    def loss(q, k, v):
        return jnp.sum(at.flash_attention(q, k, v, causal=True,
                                          force="xla") ** 2)

    g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    assert all(np.isfinite(np.asarray(x)).all() for x in g)
    assert all(float(jnp.abs(x).sum()) > 0 for x in g)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_backward_matches_dense(causal):
    """Pallas recompute backward (interpret mode) vs dense-XLA vjp: dq/dk/dv
    must agree blockwise — multi-block shapes so the lse/delta streaming
    and the causal skips on both kernels are exercised."""
    q, k, v = _qkv(b=1, h=2, s=256, d=128, seed=3)
    rng = np.random.RandomState(4)
    g = jnp.asarray(rng.normal(size=q.shape).astype(np.float32))

    with jax.default_matmul_precision("highest"):
        _, vjp_flash = jax.vjp(
            lambda a, b, c: at.flash_attention(a, b, c, causal=causal,
                                               force="interpret"), q, k, v)
        got = vjp_flash(g)
        _, vjp_dense = jax.vjp(
            lambda a, b, c: at.reference_attention(a, b, c, causal=causal),
            q, k, v)
        want = vjp_dense(g)
    for name, a, b in zip("qkv", got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3,
                                   atol=2e-4, err_msg=f"d{name}")


def test_flash_backward_single_block():
    """s == one block: first_block/causal bounds degenerate correctly."""
    q, k, v = _qkv(b=1, h=1, s=128, d=128, seed=11)
    with jax.default_matmul_precision("highest"):
        def loss_flash(q, k, v):
            return jnp.sum(at.flash_attention(q, k, v, causal=True,
                                              force="interpret") ** 2)

        def loss_dense(q, k, v):
            return jnp.sum(at.reference_attention(q, k, v, causal=True) ** 2)

        got = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3,
                                   atol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_with_lse_grads_include_lse_cotangent(causal):
    """flash_attention_with_lse is differentiable in BOTH outputs: the
    kernels fold the lse cotangent into the backward row term (glse).
    Oracle: autodiff through the dense (out, lse) formulation. The loss
    mixes out and lse so a dropped/miswired glse fails loudly."""
    q, k, v = _qkv(b=1, h=2, s=256, d=128, seed=21)

    def loss_flash(q, k, v):
        out, lse = at.flash_attention_with_lse(q, k, v, causal=causal,
                                               force="interpret")
        return jnp.sum(out ** 2) + jnp.sum(jnp.sin(lse))

    def loss_dense(q, k, v):
        out, lse = at.reference_attention_with_lse(q, k, v, causal=causal)
        return jnp.sum(out ** 2) + jnp.sum(jnp.sin(lse))

    with jax.default_matmul_precision("highest"):
        got = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4,
                                   err_msg=f"d{name}")


@pytest.mark.parametrize("h_kv", [1, 2])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_gqa_matches_dense(causal, h_kv):
    """GQA/MQA (k/v with fewer heads): kernel fwd+bwd == dense oracle
    (which repeats kv per group). h=4 with h_kv in {2 (GQA), 1 (MQA)}."""
    q, _, _ = _qkv(b=1, h=4, s=256, d=128, seed=31)
    _, k, v = _qkv(b=1, h=h_kv, s=256, d=128, seed=32)

    def loss_flash(q, k, v):
        out = at.flash_attention(q, k, v, causal=causal,
                                 force="interpret")
        return jnp.sum(out.astype(jnp.float32) ** 2)

    def loss_dense(q, k, v):
        out = at.reference_attention(q, k, v, causal=causal)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    with jax.default_matmul_precision("highest"):
        o1 = at.flash_attention(q, k, v, causal=causal, force="interpret")
        o2 = at.reference_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                                   rtol=2e-3, atol=2e-4)
        got = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    assert got[1].shape == k.shape and got[2].shape == v.shape
    for name, a, b in zip("qkv", got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=3e-4,
                                   err_msg=f"d{name}")


def test_flash_block_size_override_matches():
    """block_q/block_k overrides change tiling, not math."""
    q, k, v = _qkv(b=1, h=2, s=512, d=128, seed=33)
    base = at.flash_attention(q, k, v, causal=True, force="interpret")
    for bq, bk in ((256, 128), (128, 256), (256, 256)):
        out = at.flash_attention(q, k, v, causal=True, force="interpret",
                                 block_q=bq, block_k=bk)
        np.testing.assert_allclose(np.asarray(out), np.asarray(base),
                                   rtol=2e-3, atol=2e-4,
                                   err_msg=f"bq={bq} bk={bk}")


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("heads", [(2, 2), (4, 1), (4, 2)],
                         ids=["ungrouped", "4_over_1", "4_over_2"])
@pytest.mark.parametrize("with_glse", [False, True], ids=["out", "out_lse"])
@pytest.mark.parametrize("blocks", [(128, 256), (256, 128)],
                         ids=["128x256", "256x128"])
@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
def test_fused_backward_at_unequal_widths(causal, blocks, with_glse, heads,
                                          dtype):
    """The one backward kernel against the dense VJP at latent attention's
    192/128 and S = 512 with block_q != block_k, so that a q-block meets
    k-blocks wholly below the diagonal (no mask), straddling it (masked)
    and above it (skipped); with and without a cotangent of lse (it folds
    into delta outside the kernel); ungrouped and with a group's query
    heads adding into one resident dK/dV. float32 at `highest` to the
    float32 cases' tolerances; bf16 operands against the float32 oracle on
    the same values to the bf16 kernels' 2e-2 of the largest entry."""
    h, h_kv = heads
    s, d, dv = 512, 192, 128
    bq, bk = blocks
    rng = np.random.RandomState(50 + h + h_kv)
    q, k, v, g = (jnp.asarray(rng.normal(size=shape), jnp.float32)
                  .astype(dtype) for shape in (
        (1, h, s, d), (1, h_kv, s, d), (1, h_kv, s, dv), (1, h, s, dv)))
    g_lse = jnp.asarray(rng.normal(size=(1, h, s)), jnp.float32) \
        if with_glse else None
    scale = d ** -0.5
    with jax.default_matmul_precision("highest"):
        out, lse = at._flash_pallas(q, k, v, causal, scale, interpret=True,
                                    block_q=bq, block_k=bk)
        assert lse.shape == (h, 1, s) and lse.dtype == jnp.float32
        got = at._flash_pallas_bwd(q, k, v, out, lse, g, causal, scale,
                                   interpret=True, g_lse=g_lse, block_q=bq,
                                   block_k=bk)
        exact = tuple(a.astype(jnp.float32) for a in (q, k, v))
        (want_out, want_lse), vjp = jax.vjp(
            lambda *a: at.reference_attention_with_lse(*a, causal, scale),
            *exact)
        want = vjp((g.astype(jnp.float32),
                    jnp.zeros_like(want_lse) if g_lse is None else g_lse))
    assert [(a.dtype, a.shape) for a in got] == [
        (q.dtype, a.shape) for a in want]
    if dtype == "float32":
        np.testing.assert_allclose(np.asarray(out), np.asarray(want_out),
                                   rtol=2e-3, atol=2e-4)
        np.testing.assert_allclose(np.asarray(lse).reshape(1, h, s),
                                   np.asarray(want_lse), rtol=1e-4, atol=1e-4)
        for name, a, b in zip("qkv", got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-3, atol=3e-4,
                                       err_msg=f"d{name}")
    else:
        for a, b in zip((out,) + got, (want_out,) + want):
            _close(a, b, 2e-2)


def test_flash_backward_is_one_counted_kernel():
    """A trace of the backward holds one `mx_flash_attention_bwd` kernel
    and counts it in the telemetry registry, once a trace."""
    from mxnet_tpu.telemetry import registry
    q, k, v = _qkv(b=1, h=2, s=256, d=128, seed=7)
    counter = registry.counter(at.BWD_COUNTER)
    before = counter.value()
    jaxpr = jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(at.flash_attention(
        *a, causal=True, force="interpret")), argnums=(0, 1, 2)))(q, k, v)
    names = [e.params["name"] for e in _stage_eqns(jaxpr.jaxpr)
             if e.primitive.name == "pallas_call"]
    assert sorted(names) == ["mx_flash_attention_bwd",
                             "mx_flash_attention_fwd"]
    assert counter.value() == before + 1


def test_gqa_eligibility():
    import numpy as _np
    q = jnp.zeros((2, 8, 256, 128), jnp.bfloat16)
    kv = jnp.zeros((2, 2, 256, 128), jnp.bfloat16)
    assert at._pallas_eligible(q, kv, platform="tpu")
    # true cross-attention stays ineligible
    cross = jnp.zeros((2, 8, 128, 128), jnp.bfloat16)
    assert not at._pallas_eligible(q, cross, platform="tpu")
    # non-divisible head group ineligible
    kv3 = jnp.zeros((2, 3, 256, 128), jnp.bfloat16)
    assert not at._pallas_eligible(q, kv3, platform="tpu")


def test_forced_indivisible_blocks_error():
    """Explicit blocks that don't tile S must raise, not truncate the
    grid and leave output rows unwritten."""
    q, k, v = _qkv(b=1, h=1, s=384, d=128, seed=40)
    with pytest.raises(ValueError, match="not divisible"):
        at.flash_attention(q, k, v, force="interpret", block_q=256)


def _stage_eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside its equations,
    a kernel's own body left out."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from _stage_eqns(sub)


@pytest.mark.parametrize("with_lse", [False, True], ids=["out", "out_lse"])
def test_mirror_stage_keeps_flash_residuals(with_lse):
    """A rematerialised stage (the executor's checkpoint, as under
    MXNET_BACKWARD_DO_MIRROR) around product -> flash attention at 192/128
    -> product: the kernels' custom VJP declares out and lse as kept, so
    the stage's rerun holds no second forward kernel; its products ARE run
    again, and the gradients are the unmirrored function's to the bit."""
    from mxnet_tpu import executor
    from mxnet_tpu.telemetry import registry
    b, h, s, d, dv, width = 1, 2, 256, 192, 128, 64
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.normal(size=(b, h, s, width)).astype(np.float32))
    w_in = jnp.asarray(rng.normal(size=(width, 2 * d + dv))
                       .astype(np.float32) * 0.2)
    w_out = jnp.asarray(rng.normal(size=(dv, width)).astype(np.float32) * 0.2)

    def stage(x, w_in, w_out):
        qkv = x @ w_in
        q, k, v = qkv[..., :d], qkv[..., d:2 * d], qkv[..., 2 * d:]
        if with_lse:
            o, lse = at.flash_attention_with_lse(q, k, v, causal=True,
                                                 force="interpret")
        else:
            o = at.flash_attention(q, k, v, causal=True, force="interpret")
            lse = jnp.zeros(())
        return jnp.tanh(o @ w_out), lse

    def gradient(fn):
        def loss(*a):
            y, lse = fn(*a)
            return jnp.sum(y ** 2) + jnp.sum(jnp.sin(lse))
        return jax.grad(loss, argnums=(0, 1, 2))

    def count(fn):
        eqns = list(_stage_eqns(jax.make_jaxpr(gradient(fn))(
            x, w_in, w_out).jaxpr))
        return (sum(e.primitive.name == "pallas_call" and
                    e.params["name"] == "mx_flash_attention_fwd"
                    for e in eqns),
                sum(e.primitive.name == "dot_general" for e in eqns))

    def kept():
        return (registry.counter(executor.MIRROR_KEPT_COUNTER).value(),
                registry.counter(executor.MIRROR_KEPT_BYTES_COUNTER).value())

    forwards, products = count(stage)
    assert forwards == 1
    # lse stays a row of s along the lanes from the forward kernel to the
    # backward kernel: no slice of, and no broadcast to, a lane-replicated
    # (b*h, s, 8) array in the gradient's program
    assert not [v.aval.shape for e in _stage_eqns(jax.make_jaxpr(
        gradient(executor._rematerialised(stage)))(x, w_in, w_out).jaxpr)
        for v in e.outvars if v.aval.shape[-2:] == (s, 8)]
    # a bare checkpoint runs the whole stage again, the kernel with it
    assert count(jax.checkpoint(stage)) == (2, products + 2)
    before = kept()
    assert count(executor._rematerialised(stage)) == (1, products + 2)
    # out (b, h, s, dv) and lse (b*h, 1, s) as the kernel wrote it, float32
    assert tuple(np.subtract(kept(), before)) == (
        2, 4 * b * h * s * dv + 4 * b * h * s)

    want = gradient(stage)(x, w_in, w_out)
    got = gradient(executor._rematerialised(stage))(x, w_in, w_out)
    for name, a, g in zip(("x", "w_in", "w_out"), want, got):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(a),
                                      err_msg=f"d{name}")

