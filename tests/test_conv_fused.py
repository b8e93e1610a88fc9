"""conv1x1 megakernel correctness (Pallas interpreter, CPU lane).

Whether these kernels beat XLA's own fused chain on the chip is not
measured on this runtime (ROADMAP D5). The kernels remain supported and
tested.
"""
import numpy as np
import jax.numpy as jnp

from mxnet_tpu.ops import conv_fused as cf


def _data(n=4, ci=64, co=128, p=1024, seed=0):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.normal(size=(n, ci, p)).astype(np.float32))
    w = jnp.asarray(rng.normal(scale=0.1, size=(co, ci)).astype(np.float32))
    return rng, x, w


def test_conv1x1_plain_and_stats():
    _, x, w = _data()
    y, (s1, s2) = cf.conv1x1(x, w, interpret=True)
    want = jnp.einsum("oc,ncp->nop", w, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s1),
                               np.asarray(want.sum(axis=(0, 2))), rtol=1e-4)
    np.testing.assert_allclose(np.asarray(s2),
                               np.asarray((want ** 2).sum(axis=(0, 2))),
                               rtol=1e-4)
    mean, var, rstd = cf.finalize_stats(s1, s2, x.shape[0] * x.shape[2],
                                        1e-5)
    np.testing.assert_allclose(np.asarray(mean),
                               np.asarray(want.mean(axis=(0, 2))),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(var),
                               np.asarray(want.var(axis=(0, 2))),
                               rtol=1e-3, atol=1e-5)


def test_conv1x1_bn_relu_residual_prologue():
    rng, x, w = _data(seed=3)
    ci = x.shape[1]
    scale = jnp.asarray(rng.uniform(0.5, 2.0, ci).astype(np.float32))
    shift = jnp.asarray(rng.normal(size=ci).astype(np.float32))
    res = jnp.asarray(rng.normal(size=x.shape).astype(np.float32))
    y = cf.conv1x1(x, w, bn_in=(scale, shift), residual=res, relu_in=True,
                   want_stats=False, interpret=True)
    xn = jnp.maximum(x * scale[None, :, None] + shift[None, :, None] + res,
                     0.0)
    want = jnp.einsum("oc,ncp->nop", w, xn)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_eligibility_resnet_shapes():
    # every ResNet-50 1x1 shape must be accepted; odd spatials refused
    for ci, co, p in [(64, 256, 56 * 56), (256, 64, 56 * 56),
                      (512, 128, 28 * 28), (1024, 256, 14 * 14),
                      (512, 2048, 7 * 7)]:
        assert cf.eligible(ci, co, p), (ci, co, p)
    assert not cf.eligible(63, 64, 1000)      # ragged channels
