"""Pallas conv+BN(+ReLU) megakernels for the ResNet hot path.

Role: built to test the hypothesis
that XLA would not fuse a reduction epilogue (BN statistics) into a
convolution's output nor keep the normalize/mask chain in VMEM between
a conv and its consumer — which, if true, would have made every
BatchNorm cost a full extra read pass. An early device trace refuted
it: XLA already performed both fusions (that record is gone; ROADMAP D5
asks for the re-measurement that decides whether this file stays). The
kernels implement, for the 1x1 convolutions (2/3 of
ResNet-50's convs, touching its largest tensors):

  - `conv1x1(want_stats=True)`: y = w @ x with the per-channel sum /
                       sum-of-squares accumulated in VMEM while the
                       output tile is still resident — the BN stats pass
                       disappears.
  - prologues:         the same kernel optionally applies BN-apply+ReLU
                       (and a residual add) to its INPUT tile on the fly,
                       so the producer's raw conv output is the only
                       materialized tensor between two convolutions.

Layout: NCHW activations are viewed as (N, C, P=H*W) — the GEMM is
batched over N with C on the sublane axis and the spatial dim on lanes,
so no physical transpose is needed (the reference's 1x1 Convolution via
im2col, src/operator/nn/convolution-inl.h, pays the same GEMM but through
cuDNN). Weights (Co, Ci) live whole in VMEM (<=2 MB for every ResNet
shape).

All kernels are shape-specialized at trace time. They are NOT the default
conv path (nothing in the package imports them); they remain importable
and tested for direct use.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_BLOCK_P = 512          # lanes per grid cell (multiple of 128)


def _pick_block_p(p, ci, co, has_residual=False):
    """Lane-block size. ResNet spatial dims (56^2=3136, 28^2, ...) are
    not 128-divisible, so fall back to a full-P block (legal via the
    equal-dimension escape) when the whole (Ci+Co, P) working set fits
    VMEM comfortably."""
    if p % 128 == 0:
        for b in (_BLOCK_P, 256, 128):
            if p % b == 0:
                return b
    # full-P block: bf16 in+out tiles + fp32 accumulator, plus the
    # optional residual input tile (another Ci x P in bf16)
    vmem = (ci * p + co * p) * 2 + co * p * 4
    if has_residual:
        vmem += ci * p * 2
    return p if vmem <= 8 * 1024 * 1024 else None


def eligible(ci, co, p, has_residual=False):
    """Shapes the megakernel path accepts: both channel dims tile the
    8x128 register grid and the spatial dim blocks into lanes."""
    return (ci % 8 == 0 and co % 8 == 0 and
            _pick_block_p(p, ci, co, has_residual) is not None)


def _c1x1_kernel(x_ref, w_ref, scale_ref, shift_ref, res_ref,
                 y_ref, part_ref, *, prologue, relu_in, want_stats):
    """One (n, p-block) cell: y[n, :, pb] = w @ f(x[n, :, pb]).

    f is the input prologue: identity, or BN-apply (+ReLU) with the
    per-channel scale/shift vectors resident in VMEM, optionally adding a
    residual tile first. Epilogue accumulates per-channel sum / sumsq of
    the fp32 output tile into `part_ref` before the tile leaves VMEM.
    """
    x = x_ref[:]                                   # (Ci, Bp)
    if prologue:
        xf = x.astype(jnp.float32)
        xf = xf * scale_ref[:] + shift_ref[:]      # (Ci,1) broadcast
        if res_ref is not None:
            xf = xf + res_ref[:].astype(jnp.float32)
        if relu_in:
            xf = jnp.maximum(xf, 0.0)
        x = xf.astype(x_ref.dtype)
    y = jax.lax.dot_general(
        w_ref[:], x, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)        # (Co, Bp)
    yc = y.astype(y_ref.dtype)
    y_ref[:] = yc
    if want_stats:
        # stats of the STORED values (post bf16 round-trip) so the fused
        # path normalizes exactly what a separate stats pass would see
        y32 = yc.astype(jnp.float32)
        s1 = jnp.sum(y32, axis=1)                  # (Co,)
        s2 = jnp.sum(y32 * y32, axis=1)
        part_ref[:] = jnp.stack([s1, s2], axis=0)  # (2, Co)


def conv1x1(x, w, *, bn_in=None, residual=None, relu_in=False,
            want_stats=True, interpret=False):
    """Fused 1x1 convolution.

    x         (N, Ci, P)  activations (P = H*W, NCHW view)
    w         (Co, Ci)    weights
    bn_in     optional (scale, shift) fp32 (Ci,) vectors applied to the
              input tile in VMEM (BN-apply folded from the producer)
    residual  optional (N, Ci, P) added before relu_in
    relu_in   apply ReLU after the input BN (the usual BN+ReLU prologue)
    want_stats  also return (sum, sumsq) per output channel, computed
              while the fp32 tile is in VMEM (the fused BN-stats pass)

    Returns y (N, Co, P) [, (sum (Co,), sumsq (Co,)) fp32].
    """
    import jax.experimental.pallas as pl

    n, ci, p = x.shape
    co = w.shape[0]
    bp = _pick_block_p(p, ci, co, has_residual=residual is not None)
    if bp is None:
        raise ValueError(f"spatial dim {p} not blockable")
    prologue = bn_in is not None
    if bn_in is None:
        scale = jnp.ones((ci, 1), jnp.float32)
        shift = jnp.zeros((ci, 1), jnp.float32)
    else:
        scale = bn_in[0].reshape(ci, 1).astype(jnp.float32)
        shift = bn_in[1].reshape(ci, 1).astype(jnp.float32)

    kernel = functools.partial(
        _c1x1_kernel, prologue=prologue, relu_in=relu_in,
        want_stats=want_stats)
    if residual is None:
        kernel = functools.partial(
            lambda xr, wr, sr, hr, yr, pr, k: k(xr, wr, sr, hr, None,
                                                yr, pr),
            k=kernel)

    pt = p // bp
    in_specs = [
        pl.BlockSpec((None, ci, bp), lambda ni, pi: (ni, 0, pi)),
        pl.BlockSpec((co, ci), lambda ni, pi: (0, 0)),
        pl.BlockSpec((ci, 1), lambda ni, pi: (0, 0)),
        pl.BlockSpec((ci, 1), lambda ni, pi: (0, 0)),
    ]
    args = [x, w, scale, shift]
    if residual is not None:
        in_specs.append(pl.BlockSpec((None, ci, bp),
                                     lambda ni, pi: (ni, 0, pi)))
        args.append(residual)

    out_specs = [pl.BlockSpec((None, co, bp), lambda ni, pi: (ni, 0, pi))]
    out_shape = [jax.ShapeDtypeStruct((n, co, p), x.dtype)]
    if want_stats:
        out_specs.append(pl.BlockSpec((None, None, 2, co),
                                      lambda ni, pi: (ni, pi, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((n, pt, 2, co), jnp.float32))
    else:
        # no stats output at all — the kernel receives part_ref=None
        kernel = functools.partial(
            lambda *refs, k: k(*refs, None), k=kernel)
    out = pl.pallas_call(
        kernel,
        grid=(n, pt),
        in_specs=in_specs,
        out_specs=out_specs if want_stats else out_specs[0],
        out_shape=out_shape if want_stats else out_shape[0],
        interpret=interpret,
    )(*args)
    if not want_stats:
        return out
    y, parts = out
    sums = parts.sum(axis=(0, 1))                  # (2, Co)
    return y, (sums[0], sums[1])


def finalize_stats(s1, s2, count, eps):
    """mean/var (biased, matching BN) and the folded apply vectors:
    normalize(x) = x * scale + shift with scale = gamma*rstd,
    shift = beta - mean*scale."""
    mean = s1 / count
    var = jnp.maximum(s2 / count - mean * mean, 0.0)
    rstd = jax.lax.rsqrt(var + eps)
    return mean, var, rstd


def bn_fold(gamma, beta, mean, rstd):
    scale = gamma * rstd
    return scale, beta - mean * scale
