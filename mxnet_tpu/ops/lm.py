"""Operators of decoder language models with hybrid mixers and sparse MLPs.

TPU-first new surface (the reference has none of these): `RMSNorm`, the
Kimi Delta Attention mixer core `_contrib_kda` (short convolutions, decay
and a chunkwise-parallel gated delta rule), the held-experts mixture
`_contrib_moe_experts`, the rotary position embedding `_contrib_rope` (a
part of a head's dims turned in place, in one pass), the gated short
convolution `_contrib_gated_short_conv` (LFM2's operator between its two
products) and the fused head
`_contrib_lm_head_ce` whose output is the per-token loss. Each manages its
own precision (amp/policy.py lists them under MIXED): matrix products take
the dtype their inputs arrive in and accumulate in float32; the decay, the
chunk state, the norms' statistics, the router, the rotation's angles and
sines, the short convolution's taps and sums and the loss are float32
whatever arrives.

The delta rule, per head, with S in R^(dk x dv) and g the log-decay:

    S_t = (I - b_t k_t k_t^T) Diag(exp g_t) S_(t-1) + b_t k_t v_t^T
    o_t = S_t^T q_t

`kda_chunked` computes it a chunk of C tokens at a time (Yang et al. 2024,
"Parallelizing linear transformers with the delta rule", extended to a
per-channel decay as in the Kimi Linear report, arXiv:2510.26692): inside a
chunk the corrections u_t = b_t (v_t - (Diag(exp g_t) S_(t-1))^T k_t) solve
a unit lower-triangular system (I + A) U = b (V - (K * G) S_0), so that
only S_0 -> S_C is sequential. Pairwise decays exp(G_i - G_j) are taken
directly inside sub-blocks of `sub` tokens and through the sub-block's
first row otherwise: every exponent is <= 0, nothing can overflow whatever
the decay.

Which path runs where: `kda` sends a bf16 program for a TPU with head
widths that are multiples of 128 through the Pallas kernels of
`ops/kda_pallas.py` (forward and backward under one custom VJP, the same
work held in VMEM); a CPU program, float32 operands and every other shape
take `kda_chunked`, which is also what the kernels are tested against.
`_contrib_kda` makes the same choice for what comes before the rule (short
convolutions, normalisations, decay): `kda_pallas.prepare_kernels`, one
pass over the operands each way, or `kda_prepare`, its reference.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .registry import Param, register

_HIGHEST = jax.lax.Precision.HIGHEST
_F32 = jnp.float32


def _t(*o):
    return tuple(o)


# -- RMSNorm ------------------------------------------------------------------

def rms_norm(x, gamma, eps=1e-5):
    """x * rsqrt(mean(x^2) + eps) * gamma over the last axis; statistics
    in float32, result in x's dtype."""
    xf = x.astype(_F32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * gamma.astype(_F32)).astype(
        x.dtype)


def _rms_norm_op(attrs, octx, data, gamma):
    return _t(rms_norm(data, gamma, attrs["eps"]))


def _rms_infer(attrs, in_shapes):
    in_shapes = list(in_shapes)
    if in_shapes[0] is not None and in_shapes[1] is None:
        in_shapes[1] = (in_shapes[0][-1],)
    return in_shapes, [in_shapes[0]]


register("RMSNorm", _rms_norm_op, params={"eps": Param("float", 1e-5)},
         inputs=("data", "gamma"), infer_shape=_rms_infer)


# -- Kimi Delta Attention -------------------------------------------------------

def short_conv_silu(x, w):
    """SiLU of the causal depthwise convolution over time: x (B, S, C),
    w (C, kw), zeros before the start."""
    kw = w.shape[1]
    s = x.shape[1]
    pad = jnp.pad(x, ((0, 0), (kw - 1, 0), (0, 0)))
    y = sum(pad[:, j:j + s, :] * w[:, j].astype(x.dtype) for j in range(kw))
    return jax.nn.silu(y)


def _l2_normalize(x, eps=1e-6):
    xf = x.astype(_F32)
    return xf * jax.lax.rsqrt(jnp.sum(jnp.square(xf), -1, keepdims=True)
                              + eps)


def _mm(a, b, spec, dtype):
    """einsum with operands in `dtype` and a float32 result; float32
    operands multiply at highest precision."""
    prec = _HIGHEST if dtype == _F32 else None
    return jnp.einsum(spec, a.astype(dtype), b.astype(dtype),
                      precision=prec, preferred_element_type=_F32)


def _pairwise(q, k, gc, beta, sub, dtype):
    """For chunks (..., C, dk) with cumulative log-decay gc (float32):
    bm[i, j] = sum_d q_i k_j exp(gc_i - gc_j) for j <= i, and
    am[i, j] = beta_i sum_d k_i k_j exp(gc_i - gc_j) for j < i."""
    c, dk = q.shape[-2], q.shape[-1]
    n_sub = c // sub
    lead = q.shape[:-2]
    qs, ks, gs = (a.astype(_F32).reshape(lead + (n_sub, sub, dk))
                  for a in (q, k, gc))
    ref = gs[..., :1, :]                          # a sub-block's first row
    row = jnp.exp(gs - ref)                       # <= 1
    # columns as seen from sub-block I: exp(ref_I - gc_j), only used for
    # j before I (exponent <= 0 there; clamped elsewhere, masked below)
    col = jnp.exp(jnp.minimum(
        ref - gc.astype(_F32)[..., None, :, :], 0.0))   # (.., n_sub, C, dk)
    k_col = k.astype(_F32)[..., None, :, :] * col
    off_b = _mm(qs * row, k_col, "...isd,...ijd->...isj", dtype)
    off_a = _mm(ks * row, k_col, "...isd,...ijd->...isj", dtype)
    sub_of = jnp.arange(c) // sub
    before = (sub_of[None, :] < sub_of[:, None])            # (C, C)
    off_b = off_b.reshape(lead + (c, c)) * before
    off_a = off_a.reshape(lead + (c, c)) * before
    # inside a sub-block: the decays of every pair, directly
    e = jnp.exp(jnp.minimum(gs[..., :, None, :] - gs[..., None, :, :], 0.0))
    kk = ks[..., None, :, :] * e                            # (.., s, s, dk)
    d_b = jnp.sum(qs[..., :, None, :] * kk, -1)
    d_a = jnp.sum(ks[..., :, None, :] * kk, -1)
    tri = jnp.tril(jnp.ones((sub, sub), bool))
    d_b = d_b * tri
    d_a = d_a * jnp.tril(jnp.ones((sub, sub), bool), -1)
    eye = jnp.eye(n_sub, dtype=_F32)

    def block_diag(d):
        full = d[..., :, :, None, :] * eye[:, None, :, None]
        return full.reshape(lead + (c, c))

    bm = off_b + block_diag(d_b)
    am = (off_a + block_diag(d_a)) * beta.astype(_F32)[..., None]
    return am, bm


def _unit_lower_inverse(am):
    """(I + am)^-1 for strictly lower-triangular am (..., C, C), float32:
    the Neumann series of the nilpotent -am, by repeated squaring."""
    c = am.shape[-1]
    x = -am
    t = jnp.eye(c, dtype=_F32) + x
    p = x
    n = 2
    while n < c:
        p = jnp.einsum("...ij,...jk->...ik", p, p, precision=_HIGHEST)
        t = t + jnp.einsum("...ij,...jk->...ik", t, p, precision=_HIGHEST)
        n *= 2
    return t


def kda_chunked(q, k, v, g, beta, chunk=64, sub=16, segment=4):
    """The gated delta rule, chunkwise. q, k, g (B, S, H, dk), v
    (B, S, H, dv), beta (B, S, H); g float32 log-decay (<= 0). Products
    take q's dtype and accumulate in float32; the state is float32.
    Returns (B, S, H, dv) float32. `segment` chunks at a time go through
    the pairwise stage, which bounds its temporaries."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    dtype = q.dtype
    chunk = min(chunk, -(-s // sub) * sub)
    sub = min(sub, chunk)
    pad = -s % chunk
    if pad:     # zeros change neither the state nor the kept outputs
        q, k, v, g = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                      for a in (q, k, v, g))
        beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))
    n = (s + pad) // chunk

    def chunks(a):          # (B, S, H, d) -> (N, B, H, C, d)
        a = a.reshape(b, n, chunk, h, -1)
        return jnp.transpose(a, (1, 0, 3, 2, 4))

    qc, kc, vc, gc = chunks(q), chunks(k), chunks(v), chunks(g.astype(_F32))
    bc = chunks(beta[..., None])[..., 0].astype(_F32)      # (N, B, H, C)
    gc = jnp.cumsum(gc, axis=-2)                           # inclusive
    decay = jnp.exp(gc)                                    # <= 1
    g_last = gc[..., -1:, :]

    @jax.checkpoint     # or the map's backward keeps every pairwise decay
    def stage1(args):
        q_, k_, v_, gc_, b_, decay_ = args
        am, bm = _pairwise(q_, k_, gc_, b_, sub, dtype)
        t = _unit_lower_inverse(am)
        kf = k_.astype(_F32)
        w = _mm(t, kf * decay_ * b_[..., None], "...ij,...jd->...id", dtype)
        u = _mm(t, v_.astype(_F32) * b_[..., None], "...ij,...jd->...id",
                dtype)
        # the scan below multiplies them in `dtype`: keep them so
        return w.astype(dtype), u.astype(dtype), bm.astype(dtype)

    seg = max(1, min(segment, n))
    while n % seg:
        seg -= 1
    grouped = tuple(a.reshape((n // seg, seg) + a.shape[1:])
                    for a in (qc, kc, vc, gc, bc, decay))
    w, u, bm = jax.lax.map(stage1, grouped)
    w, u, bm = (a.reshape((n,) + a.shape[2:]) for a in (w, u, bm))
    q_dec = (qc.astype(_F32) * decay).astype(dtype)
    k_rest = (kc.astype(_F32) * jnp.exp(g_last - gc)).astype(dtype)  # <= 1

    def step(state, xs):
        w_, u_, bm_, qd_, kr_, gl_ = xs
        u_ = u_ - _mm(w_, state, "bhck,bhkv->bhcv", dtype)
        o = _mm(qd_, state, "bhck,bhkv->bhcv", dtype) + \
            _mm(bm_, u_, "bhij,bhjv->bhiv", dtype)
        state = state * jnp.swapaxes(jnp.exp(gl_), -1, -2) + \
            _mm(kr_, u_, "bhck,bhcv->bhkv", dtype)
        return state, o

    state0 = jnp.zeros((b, h, dk, dv), _F32)
    _, o = jax.lax.scan(step, state0, (w, u, bm, q_dec, k_rest, g_last))
    o = jnp.transpose(o, (1, 0, 3, 2, 4)).reshape(b, n * chunk, h, dv)
    return o[:, :s]


KDA_KERNEL_COUNTER = "kda_kernel_calls_total"
KDA_FALLBACK_COUNTER = "kda_xla_fallback_total"


def _count(name, help_text):
    """One more of a path taken, counted once a trace."""
    from ..telemetry import registry
    registry.counter(name, help=help_text).inc()


def kda(q, k, v, g, beta, chunk=64, force=None, platform=None):
    """The gated delta rule by the path the operands call for; shapes as
    `kda_chunked`'s, result in q's dtype.

    force: None (auto) | 'pallas' | 'xla' | 'interpret' (the kernels under
    the Pallas interpreter: CPU-testable), as in `flash_attention`.
    `platform` is the platform the program is compiled for (the executor's
    OpCtx). Both paths are counted once a trace in the telemetry registry:
    a kernel call, and a bf16 program for a TPU whose shapes the kernels
    refuse."""
    from . import kda_pallas
    if force in ("pallas", "interpret") or (
            force is None and kda_pallas.eligible(
                q.dtype, q.shape[-1], v.shape[-1], chunk, platform)):
        if force is None:
            _count(KDA_KERNEL_COUNTER, "KDA cores traced for a TPU that "
                   "went through the Pallas kernels")
        return kda_pallas.kda_kernels(q, k, v, g, beta, chunk=chunk,
                                      interpret=force == "interpret")
    if force is None and q.dtype == jnp.bfloat16 and \
            (platform or jax.default_backend()) == "tpu":
        import logging
        _count(KDA_FALLBACK_COUNTER, "bf16 KDA cores traced for a TPU "
               "whose shapes the Pallas kernels do not take")
        logging.getLogger(__name__).warning(
            "kda: q %s v %s chunk %d not eligible for the TPU kernels; "
            "the XLA path", q.shape, v.shape, chunk)
    return kda_chunked(q, k, v, g, beta, chunk=chunk).astype(q.dtype)


def kda_log_decay(f, a_log, dt_bias, num_heads):
    """g = -exp(A_log[h]) * softplus(f + dt_bias), float32; f (B, S, H*dk)
    -> (B, S, H, dk)."""
    b, s, c = f.shape
    x = f.astype(_F32) + dt_bias.astype(_F32)
    g = -jnp.exp(a_log.astype(_F32))[:, None] * jax.nn.softplus(
        x.reshape(b, s, num_heads, c // num_heads))
    return g


def kda_prepare(q, k, v, f, beta, conv_q, conv_k, conv_v, a_log, dt_bias,
                num_heads):
    """The core's operands from the mixer's projections (B, S, H*d): short
    convolutions and SiLU, q and k L2-normalised per head (q scaled by
    dk^-0.5), the log-decay in float32 and beta's sigmoid; heads split."""
    h = num_heads
    b, s, c = q.shape
    dk = c // h
    q, k, v = (short_conv_silu(x, w).reshape(b, s, h, -1)
               for x, w in ((q, conv_q), (k, conv_k), (v, conv_v)))
    dtype = q.dtype
    q = (_l2_normalize(q) * dk ** -0.5).astype(dtype)
    k = _l2_normalize(k).astype(dtype)
    g = kda_log_decay(f, a_log, dt_bias, h)
    return q, k, v, g, jax.nn.sigmoid(beta.astype(_F32))


KDA_PREPARE_KERNEL_COUNTER = "kda_prepare_kernel_calls_total"
KDA_PREPARE_FALLBACK_COUNTER = "kda_prepare_xla_fallback_total"


def _kda_op(attrs, octx, q, k, v, f, beta, conv_q, conv_k, conv_v, a_log,
            dt_bias):
    h = attrs["num_heads"]
    b, s, c = q.shape

    def core(*args):
        with jax.named_scope("mx.kda.core"):
            o = kda(*args, chunk=attrs["chunk"], platform=octx.platform)
        return o.reshape(b, s, -1)

    from . import kda_pallas
    if kda_pallas.eligible(q.dtype, c // h, v.shape[-1] // h, attrs["chunk"],
                           octx.platform, conv_q.shape[1]):
        # one pass over the operands as they arrive, whose custom VJP keeps
        # its inputs; the core's keeps its operands and its chunk-start
        # states, and nothing of their insides: no checkpoint (the layer's
        # own rematerialisation bounds how long they live)
        _count(KDA_PREPARE_KERNEL_COUNTER, "KDA operand preparations "
               "traced for a TPU that went through the Pallas kernels")
        flat = kda_pallas.prepare_kernels(q, k, v, f, conv_q, conv_k, conv_v,
                                          a_log, dt_bias, num_heads=h)
        return _t(core(*(a.reshape(b, s, h, -1) for a in flat),
                       jax.nn.sigmoid(beta.astype(_F32))))
    if q.dtype == jnp.bfloat16 and \
            (octx.platform or jax.default_backend()) == "tpu":
        import logging
        _count(KDA_PREPARE_FALLBACK_COUNTER, "bf16 KDA operand "
               "preparations traced for a TPU whose shapes the Pallas "
               "kernels do not take")
        logging.getLogger(__name__).warning(
            "_contrib_kda: q %s v %s kernel %d chunk %d not eligible for the "
            "TPU kernels; the XLA path", q.shape, v.shape, conv_q.shape[1],
            attrs["chunk"])
    # the XLA path's backward recomputes the chunks from the inputs
    return _t(jax.checkpoint(
        lambda *a: core(*kda_prepare(*a, num_heads=h)))(
            q, k, v, f, beta, conv_q, conv_k, conv_v, a_log, dt_bias))


def _kda_infer(attrs, in_shapes):
    in_shapes = list(in_shapes)
    qs = in_shapes[0]
    if qs is not None:
        c, h, kw = qs[-1], attrs["num_heads"], attrs["kernel"]
        fill = {1: qs, 2: qs, 3: qs, 4: tuple(qs[:-1]) + (h,),
                5: (c, kw), 6: (c, kw), 7: (c, kw), 8: (h,), 9: (c,)}
        for i, shp in fill.items():
            if in_shapes[i] is None:
                in_shapes[i] = shp
        # v may be wider or narrower than q per head
        return in_shapes, [in_shapes[2]]
    return in_shapes, [None]


register("_contrib_kda", _kda_op,
         params={"num_heads": Param("int", required=True),
                 "kernel": Param("int", 4), "chunk": Param("int", 64)},
         inputs=("query", "key", "value", "decay", "beta", "conv_query",
                 "conv_key", "conv_value", "A_log", "dt_bias"),
         infer_shape=_kda_infer)


# -- rotary position embedding ----------------------------------------------------

ROPE_COUNTER = "rope_calls_total"


def _rope_tables(seq, width, rotary_dim, offset, theta, interleave):
    """(cos (seq, width), sin (seq, width), swap (width, width)). Pair i of
    position p turns by a = p * theta^(-2i / rotary_dim): the frequencies
    are rounded to float32 once, on the host; the product, its cosine and
    its sine are float32 in the program; outside the rotated dims the
    cosine is 1 and the sine 0. `t @ swap` puts at each rotated dim its
    pair's other half, negated at the pair's first: a signed permutation,
    so the product is exact in any dtype."""
    import numpy as np
    half = rotary_dim // 2
    freq = jnp.asarray(np.float32(np.power(
        np.float64(theta), -np.arange(0, rotary_dim, 2) / rotary_dim)))
    angle = jnp.arange(seq, dtype=_F32)[:, None] * freq          # (S, R/2)
    first = offset + (2 * np.arange(half) if interleave else np.arange(half))
    second = first + (1 if interleave else half)
    spread = functools.partial(jnp.repeat, repeats=2, axis=-1) \
        if interleave else functools.partial(jnp.tile, reps=(1, 2))
    pad = ((0, 0), (offset, width - offset - rotary_dim))
    swap = np.zeros((width, width), np.float32)
    swap[second, first] = -1.0
    swap[first, second] = 1.0
    return (jnp.pad(spread(jnp.cos(angle)), pad, constant_values=1.0),
            jnp.pad(spread(jnp.sin(angle)), pad), swap)


def _rotate(x, rotary_dim, offset, theta, interleave, sign):
    cos, sin, swap = _rope_tables(*x.shape[-2:], rotary_dim, offset, theta,
                                  interleave)
    # one pass over x: the TPU compiler fuses the multiply-adds into the
    # product (a lane shift by one, the same thing written as slices, costs
    # it float32 copies of x: compile, PR 30)
    partner = jnp.einsum(
        "...i,ij->...j", x, jnp.asarray(swap, x.dtype),
        precision=_HIGHEST if x.dtype == _F32 else None,
        preferred_element_type=x.dtype)
    return (x.astype(_F32) * cos
            + partner.astype(_F32) * (sign * sin)).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def rope(x, rotary_dim, offset=0, theta=10000.0, interleave=True):
    """Rotary embedding of positions 0..S-1 over dims offset..offset +
    rotary_dim of x (..., S, W), the others passed through: pair i turns
    by a = p * theta^(-2i / rotary_dim), (t0, t1) -> (t0 cos a - t1 sin a,
    t0 sin a + t1 cos a). `interleave`: pairs are (2i, 2i + 1), else
    (i, i + rotary_dim / 2). Angles, sines and the products are float32
    whatever x's dtype, which the result takes. The backward pass is the
    rotation by -a of the cotangent: nothing is kept."""
    return _rotate(x, rotary_dim, offset, theta, interleave, 1.0)


def _rope_fwd(x, rotary_dim, offset, theta, interleave):
    return _rotate(x, rotary_dim, offset, theta, interleave, 1.0), None


def _rope_bwd(rotary_dim, offset, theta, interleave, _, dy):
    return (_rotate(dy, rotary_dim, offset, theta, interleave, -1.0),)


rope.defvjp(_rope_fwd, _rope_bwd)


def _rope_op(attrs, octx, data):
    rotary, offset = attrs["rotary_dim"], attrs["offset"]
    if rotary % 2 or rotary <= 0 or offset < 0 or \
            offset + rotary > data.shape[-1]:
        raise ValueError(
            f"_contrib_rope: rotary_dim {rotary} at offset {offset} does not "
            f"fit an even number of dims into a width of {data.shape[-1]}")
    _count(ROPE_COUNTER, "rotary embeddings traced into a program")
    return _t(rope(data, rotary, offset, attrs["theta"],
                   attrs["interleave"]))


register("_contrib_rope", _rope_op,
         params={"rotary_dim": Param("int", required=True),
                 "offset": Param("int", 0),
                 "theta": Param("float", 10000.0),
                 "interleave": Param("bool", True)},
         inputs=("data",))


# -- gated short convolution --------------------------------------------------------

SHORT_CONV_COUNTER = "short_conv_calls_total"
SHORT_CONV_MAX_TAPS = 8     # the shifted sum below is unrolled over the taps


def _chunks(x):
    c = x.shape[-1] // 3
    return x[..., :c], x[..., c:2 * c], x[..., 2 * c:]


def _gated_products(x, kw):
    """[z_(t - (kw - 1) + j) for j in range(kw)], z = B * u in float32, zeros
    before the start. Written as slices of ONE zero-padded copy of x in its
    own dtype: the TPU compiler then fuses pad, slices, converts and
    products into the consumer's single pass over x (shifted copies of the
    float32 product cost it a float32 copy of x and of z: compile, PR 32)."""
    s = x.shape[1]
    b_gate, _, u = _chunks(jnp.pad(x, ((0, 0), (kw - 1, 0), (0, 0))))
    return [b_gate[:, j:j + s].astype(_F32) * u[:, j:j + s].astype(_F32)
            for j in range(kw)]


def _tap_sum(z, w):
    """c_t = sum_j w[:, j] * z_(t - (kw - 1) + j) from `_gated_products`."""
    return sum(z_j * w[:, j] for j, z_j in enumerate(z))


@jax.custom_vjp
def gated_short_conv(x, w):
    """LFM2's operator between its two products: x (B, S, 3C) holds three
    chunks [B, C, u] of C channels, w (C, kw) the taps of a depthwise causal
    convolution over time: c_t = sum_j w[:, j] * (B * u)_(t - (kw - 1) + j),
    zeros before the start, no activation; y = C * c. Products and sums are
    float32 whatever x's dtype, which the result takes. The backward pass
    keeps x and w and nothing else, and reads x and the cotangent for all
    three chunks' gradients and the taps'."""
    w = w.astype(_F32)
    conv = _tap_sum(_gated_products(x, w.shape[1]), w)
    return (_chunks(x)[1].astype(_F32) * conv).astype(x.dtype)


def _gated_short_conv_fwd(x, w):
    return gated_short_conv(x, w), (x, w)


def _gated_short_conv_bwd(res, dy):
    x, w_in = res
    w = w_in.astype(_F32)
    kw, s = w.shape[1], x.shape[1]
    b_gate, c_gate, u = (t.astype(_F32) for t in _chunks(x))
    z = _gated_products(x, kw)
    # z_t reaches c_(t + k) through tap kw - 1 - k: the gated cotangent at
    # later rows, as slices of copies zero-padded at the end
    later = ((0, 0), (0, kw - 1), (0, 0))
    c_later, dy_later = _chunks(jnp.pad(x, later))[1], jnp.pad(dy, later)
    dz = sum(dy_later[:, k:k + s].astype(_F32)
             * c_later[:, k:k + s].astype(_F32) * w[:, kw - 1 - k]
             for k in range(kw))
    dy = dy.astype(_F32)
    dc = dy * c_gate
    dw = jnp.stack([jnp.sum(z[j] * dc, axis=(0, 1)) for j in range(kw)], -1)
    dx = jnp.concatenate([dz * u, dy * _tap_sum(z, w), dz * b_gate], -1)
    return dx.astype(x.dtype), dw.astype(w_in.dtype)


gated_short_conv.defvjp(_gated_short_conv_fwd, _gated_short_conv_bwd)


def _gated_short_conv_op(attrs, octx, data, weight):
    kw = attrs["kernel"]
    if data.shape[-1] % 3 or weight.shape != (data.shape[-1] // 3, kw) \
            or not 1 <= kw <= SHORT_CONV_MAX_TAPS:
        raise ValueError(
            f"_contrib_gated_short_conv: data {data.shape} is three chunks "
            f"of C channels and weight (C, kernel) with 1 <= kernel <= "
            f"{SHORT_CONV_MAX_TAPS}; got weight {weight.shape}, kernel {kw}")
    _count(SHORT_CONV_COUNTER, "gated short convolutions traced into a "
           "program")
    with jax.named_scope("mx.sconv.conv"):
        return _t(gated_short_conv(data, weight))


def _gated_short_conv_infer(attrs, in_shapes):
    in_shapes = list(in_shapes)
    ds = in_shapes[0]
    if ds is None:
        return in_shapes, [None]
    if in_shapes[1] is None:
        in_shapes[1] = (ds[-1] // 3, attrs["kernel"])
    return in_shapes, [tuple(ds[:-1]) + (ds[-1] // 3,)]


register("_contrib_gated_short_conv", _gated_short_conv_op,
         params={"kernel": Param("int", 3)},
         inputs=("data", "weight"), infer_shape=_gated_short_conv_infer)


# -- held-experts mixture -------------------------------------------------------

def moe_route(x, router_weight, router_bias, top_k, scaling, renormalize):
    """(chosen experts (T, k) int32, their weights (T, k) float32):
    sigmoid scores in float32, the `top_k` largest of score + bias (the
    bias takes no gradient), weights renormalised over the chosen."""
    scores = jax.nn.sigmoid(jnp.einsum(
        "td,ed->te", x.astype(_F32), router_weight.astype(_F32),
        precision=_HIGHEST))
    _, idx = jax.lax.top_k(
        scores + jax.lax.stop_gradient(router_bias.astype(_F32)), top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if renormalize:
        w = w / jnp.sum(w, -1, keepdims=True)
    return idx.astype(jnp.int32), w * scaling


MOE_KERNEL_COUNTER = "moe_grouped_kernel_calls_total"
MOE_FALLBACK_COUNTER = "moe_grouped_xla_fallback_total"


def _swiglu_experts(x, w_gate, w_up, w_down, group_sizes, force=None,
                    platform=None):
    """Grouped SwiGLU: rows of x (M, D) sorted by expert, weights
    (E, D, W) / (E, W, D), float32 (M, D).

    Which path, from what the trace can see: a program for a TPU whose
    widths and rows the tiles divide (`moe_pallas.eligible`), in bf16 or
    float32, takes the Pallas kernels `mx_moe_gmm` / `mx_moe_tgmm`, whose
    cost follows the pairs that arrived and which write the rows past the
    last pair as ZEROS; everything else (a CPU program, shapes the kernels
    refuse) takes `jax.lax.ragged_dot`, which leaves those rows unwritten
    (on the TPU: whatever the memory held). Callers select them away either
    way. force: None (auto) | 'pallas' | 'xla' | 'interpret', as `kda`'s;
    `platform` is the platform the program is compiled for. Both paths are
    counted once a trace: a kernel call, and a bf16 program for a TPU whose
    shapes the kernels refuse (logged with the shapes)."""
    from . import moe_pallas
    m, d = x.shape
    held, _, width = w_gate.shape
    one_type = x.dtype == w_gate.dtype == w_up.dtype == w_down.dtype
    if force in ("pallas", "interpret") or (
            force is None and one_type and moe_pallas.eligible(
                x.dtype, d, width, m, held, platform)):
        if force is None:
            _count(MOE_KERNEL_COUNTER, "grouped SwiGLUs of held experts "
                   "traced for a TPU that went through the Pallas kernels")
        return moe_pallas.swiglu_experts(x, w_gate, w_up, w_down, group_sizes,
                                         interpret=force == "interpret")
    if force is None and x.dtype == jnp.bfloat16 and \
            (platform or jax.default_backend()) == "tpu":
        import logging
        _count(MOE_FALLBACK_COUNTER, "bf16 grouped SwiGLUs of held experts "
               "traced for a TPU whose shapes the Pallas kernels do not take")
        logging.getLogger(__name__).warning(
            "moe_experts: rows %s weights %s not eligible for the TPU "
            "kernels; jax.lax.ragged_dot", x.shape, w_gate.shape)
    dot = functools.partial(jax.lax.ragged_dot, group_sizes=group_sizes,
                            preferred_element_type=_F32)
    hidden = jax.nn.silu(dot(x, w_gate)) * dot(x, w_up)
    return dot(hidden.astype(x.dtype), w_down)


def _swiglu_one(x, wg, wu, wd):
    hid = jax.nn.silu(_mm(x, wg, "td,dw->tw", x.dtype)) * \
        _mm(x, wu, "td,dw->tw", x.dtype)
    return _mm(hid, wd, "tw,wd->td", x.dtype)


@jax.custom_vjp
def _dense_experts(x, per_expert, w_gate, w_up, w_down):
    """sum_e per_expert[:, e] * expert_e(x), every held expert on every
    token, one expert at a time: forward and backward each keep one
    expert's activations (a scan that autodiff transposes would keep all
    eight's)."""
    def one(y, args):
        w_e, wg, wu, wd = args
        return y + w_e[:, None] * _swiglu_one(x, wg, wu, wd), None
    y, _ = jax.lax.scan(one, jnp.zeros(x.shape, _F32),
                        (per_expert.T, w_gate, w_up, w_down))
    return y


def _dense_experts_fwd(x, per_expert, w_gate, w_up, w_down):
    return (_dense_experts(x, per_expert, w_gate, w_up, w_down),
            (x, per_expert, w_gate, w_up, w_down))


def _dense_experts_bwd(res, dy):
    x, per_expert, w_gate, w_up, w_down = res

    def one(dx, args):
        w_e, wg, wu, wd = args
        out, vjp = jax.vjp(_swiglu_one, x, wg, wu, wd)
        gx, gwg, gwu, gwd = vjp(dy * w_e[:, None])
        return dx + gx.astype(_F32), (gwg, gwu, gwd, jnp.sum(dy * out, -1))

    dx, (gwg, gwu, gwd, gw) = jax.lax.scan(
        one, jnp.zeros(x.shape, _F32), (per_expert.T, w_gate, w_up, w_down))
    return dx.astype(x.dtype), gw.T, gwg, gwu, gwd


_dense_experts.defvjp(_dense_experts_fwd, _dense_experts_bwd)


# rows of the grouped products, in balanced shares (T * top_k * held / all):
# four shares hold every step but those in which the tokens crowd onto the
# held experts; past them the dense path takes over, so the number decides
# speed and memory, never the result
MOE_CAPACITY = 4


def moe_experts(x, router_weight, router_bias, w_gate, w_up, w_down, *,
                first_expert, top_k, scaling, renormalize, force=None,
                platform=None):
    """y (T, D) = sum over the chosen experts held here of weight *
    expert(x), and stats (E_held + 3,) int32: tokens per held expert,
    token-expert pairs on held experts, pairs computed, dense fall-backs.

    The pairs that fall on held experts are sorted by expert into
    `capacity` rows (MOE_CAPACITY times the balanced share) and go
    through grouped products; should more pairs than that arrive, the
    layer computes every held expert on every token instead: no pair is
    ever dropped. The grouped products are `_swiglu_experts`'s: Pallas
    kernels in a program for a TPU (the rows past the last pair come back
    as zeros), `jax.lax.ragged_dot` in every other (they come back
    unwritten); `force` and `platform` are passed on to it."""
    t, d = x.shape
    n_held = w_gate.shape[0]
    n_all = router_weight.shape[0]
    with jax.named_scope("mx.moe.route"):
        idx, w = moe_route(x, router_weight, router_bias, top_k, scaling,
                           renormalize)
        local = idx - first_expert
        held = (local >= 0) & (local < n_held)
        key = jnp.where(held, local, n_held).reshape(-1)       # (T*k,)
        load = jnp.bincount(key, length=n_held + 1)[:n_held].astype(
            jnp.int32)
        n_pairs = jnp.sum(load)
        balanced = -(-t * top_k * n_held // n_all)
        capacity = min(t * top_k,
                       -(-balanced * MOE_CAPACITY // 128) * 128)
        order = jnp.argsort(key, stable=True)[:capacity]
        token = order // top_k
        # rows past the held pairs belong to no group: `ragged_dot`
        # leaves them unwritten (on the TPU: whatever the memory held), so
        # they are SELECTED away on both sides of it, never multiplied away
        valid = (jnp.arange(capacity) < n_pairs)[:, None]
        weight = w.reshape(-1)[order][:, None]

    def grouped(_):
        rows = jnp.where(valid, jnp.take(x, token, axis=0), 0)
        with jax.named_scope("mx.moe.experts.matmul"):
            out = _swiglu_experts(rows, w_gate, w_up, w_down, load,
                                  force=force, platform=platform)
        # selected BEFORE the product with the pair's weight: the weight's
        # gradient is a sum over out, and 0 x whatever the memory held is
        # NaN wherever that is no number
        return jnp.zeros((t, d), _F32).at[token].add(
            jnp.where(valid, out, 0.0) * weight)

    def dense(_):
        per_expert = jnp.stack(
            [jnp.sum(jnp.where(local == e, w, 0.0), -1)
             for e in range(n_held)], axis=-1)                 # (T, E_held)
        return _dense_experts(x, per_expert, w_gate, w_up, w_down)

    with jax.named_scope("mx.moe.experts"):
        fits = n_pairs <= capacity
        y = jax.lax.cond(fits, grouped, dense, None)
    stats = jnp.concatenate([load, jnp.stack([
        n_pairs, n_pairs, 1 - fits.astype(jnp.int32)]).astype(jnp.int32)])
    return y.astype(x.dtype), jax.lax.stop_gradient(stats)


def _moe_op(attrs, octx, data, router_weight, router_bias, w_gate, w_up,
            w_down):
    shape = data.shape
    y, stats = moe_experts(
        data.reshape(-1, shape[-1]), router_weight, router_bias,
        w_gate, w_up, w_down, first_expert=attrs["first_expert"],
        top_k=attrs["top_k"], scaling=attrs["scaling"],
        renormalize=attrs["renormalize"], platform=octx.platform)
    return _t(y.reshape(shape), stats)


def _moe_infer(attrs, in_shapes):
    in_shapes = list(in_shapes)
    ds = in_shapes[0]
    n_held, width = attrs["num_held"], attrs["hidden_size"]
    if ds is not None:
        d = ds[-1]
        fill = {1: (attrs["num_experts"], d), 2: (attrs["num_experts"],),
                3: (n_held, d, width), 4: (n_held, d, width),
                5: (n_held, width, d)}
        for i, shp in fill.items():
            if in_shapes[i] is None:
                in_shapes[i] = shp
    return in_shapes, [ds, (n_held + 3,)]


register("_contrib_moe_experts", _moe_op,
         params={"num_experts": Param("int", required=True),
                 "num_held": Param("int", required=True),
                 "first_expert": Param("int", 0),
                 "hidden_size": Param("int", required=True),
                 "top_k": Param("int", required=True),
                 "scaling": Param("float", 1.0),
                 "renormalize": Param("bool", True)},
         inputs=("data", "router_weight", "router_bias", "gate_weight",
                 "up_weight", "down_weight"),
         num_outputs=2, infer_shape=_moe_infer,
         infer_type=lambda attrs, in_types: [in_types[0], "int32"])


# -- the head: per-token cross-entropy --------------------------------------------

def lm_head_ce(x, weight, label, block=2048):
    """Cross-entropy of each row of x (T, D) against label (T,) under the
    logits x @ weight^T, float32 (T,). The logits exist a `block` of rows
    at a time, forward and backward."""
    t, d = x.shape
    block = min(block, t)
    pad = -t % block
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        label = jnp.pad(label, (0, pad))
    label = label.astype(jnp.int32)

    @jax.checkpoint
    def rows(args):
        xb, lb = args
        logits = _mm(xb, weight, "td,vd->tv", xb.dtype)
        lse = jax.nn.logsumexp(logits, axis=-1)
        return lse - jnp.take_along_axis(logits, lb[:, None], -1)[:, 0]

    out = jax.lax.map(rows, (x.reshape(-1, block, d),
                             label.reshape(-1, block)))
    return out.reshape(-1)[:t]


def _lm_head_ce_op(attrs, octx, data, weight, label):
    lead = data.shape[:-1]
    loss = lm_head_ce(data.reshape(-1, data.shape[-1]), weight,
                      label.reshape(-1), attrs["block"])
    return _t(loss.reshape(lead))


def _head_infer(attrs, in_shapes):
    in_shapes = list(in_shapes)
    ds = in_shapes[0]
    if ds is not None:
        if in_shapes[1] is None:
            in_shapes[1] = (attrs["num_classes"], ds[-1])
        if in_shapes[2] is None:
            in_shapes[2] = tuple(ds[:-1])
        return in_shapes, [tuple(ds[:-1])]
    return in_shapes, [None]


register("_contrib_lm_head_ce", _lm_head_ce_op,
         params={"num_classes": Param("int", required=True),
                 "block": Param("int", 2048)},
         inputs=("data", "weight", "label"), infer_shape=_head_infer,
         infer_type=lambda attrs, in_types: ["float32"])
