"""Attention operators — Pallas flash-attention kernel + XLA fallback.

The reference has no attention op (its transformer support is the helper
`_contrib_div_sqrt_dim`, src/operator/contrib/transformer.cc:34); this is
TPU-first new surface: a blockwise online-softmax kernel written in Pallas
(per /opt/skills/guides/pallas_guide.md) that keeps the (S, S) score
matrix out of HBM, gridded over (batch*heads, q-blocks) with the K/V
stream resident in VMEM. Dispatch picks the kernel on TPU for
tile-friendly shapes and falls back to a fused XLA implementation
elsewhere (including the CPU test mesh). The sequence-parallel versions
live in mxnet_tpu.parallel.sp.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from .registry import Param, kept_residual, register

_BLOCK_Q = 128    # floor tile; _auto_block picks larger when S allows
_BLOCK_K = 128
_LSE_LANES = 8    # minor replication of the per-row lse (TPU block tiling)
# what the flash backward needs beyond q, k and v, which a rematerialised
# stage's rerun rebuilds from its products: computing these two again is
# the whole forward kernel, keeping them is 2*B*H*S*Dv + 4*B*H*S bytes
_KEPT_OUT = kept_residual("mx_flash_attention_out")
_KEPT_LSE = kept_residual("mx_flash_attention_lse")


def _auto_block(s):
    """Default block size: the LARGEST of 512/256/128 dividing S —
    bigger tiles amortize the per-block softmax bookkeeping and keep the
    MXU busier (its gain on this runtime is a claim to re-measure,
    ROADMAP S10 (e)). Sequences not
    divisible by 128 fall back to a single block (small-S case)."""
    for blk in (512, 256, 128):
        if s % blk == 0:
            return blk
    return min(_BLOCK_Q, s)


def _t(*o):
    return tuple(o)


def reference_attention(q, k, v, causal=False, scale=None):
    """Dense oracle. One implementation shared with the with-lse variant
    below — the score/mask/softmax math must not fork."""
    return reference_attention_with_lse(q, k, v, causal, scale)[0]


def reference_attention_with_lse(q, k, v, causal=False, scale=None):
    """Dense oracle returning (out, lse (B,H,S) f32) — the merge
    statistic blockwise/ring combiners need. Rows with NO valid key get
    out=0 and lse=-inf (the logsumexp of an empty set), so such a block
    contributes exactly nothing to a logaddexp merge. GQA (k/v with
    fewer heads) is handled by repeating kv across each query group."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    if k.shape[1] != q.shape[1]:
        group = q.shape[1] // k.shape[1]
        k = jnp.repeat(k, group, axis=1)
        v = jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if causal:
        s_q, s_k = scores.shape[-2], scores.shape[-1]
        mask = jnp.tril(jnp.ones((s_q, s_k), bool), k=s_k - s_q)
        scores = jnp.where(mask, scores, -jnp.inf)
    m = jnp.max(scores, axis=-1)
    safe = jnp.where(jnp.isneginf(m), 0.0, m)
    p = jnp.exp(scores - safe[..., None])
    p = jnp.where(jnp.isneginf(scores), 0.0, p)
    l = jnp.sum(p, axis=-1)
    l_safe = jnp.where(l == 0, 1.0, l)
    out = jnp.einsum("bhqk,bhkd->bhqd", p,
                     v.astype(jnp.float32)) / l_safe[..., None]
    lse = jnp.where(l == 0, -jnp.inf, safe + jnp.log(l_safe))
    return out.astype(q.dtype), lse


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_k, seq_len,
                  causal, scale):
    """One (bh, q-block) grid cell: stream K/V blocks with online softmax.
    Also writes the per-row logsumexp — the backward's saved statistic."""
    import jax.experimental.pallas as pl

    q_block = q_ref.shape[0]
    # keep q in its storage dtype: the MXU runs bf16 matmuls at full rate
    # while an fp32 upcast would halve+ throughput; accumulation happens
    # in fp32 via preferred_element_type, and the scale is applied to the
    # fp32 scores (numerically at least as good as scaling q)
    q = q_ref[:]                                        # (Bq, D)
    q_start = pl.program_id(1) * q_block
    q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (q_block, 1), 0)

    acc0 = jnp.zeros((q_block, v_ref.shape[1]), jnp.float32)
    m0 = jnp.full((q_block, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((q_block, 1), jnp.float32)
    n_blocks = seq_len // block_k
    if causal:
        # flash-attention causal skip: blocks fully above the diagonal
        # contribute nothing — bound the scan at the q-block's last row
        n_blocks = jnp.minimum(
            n_blocks, (q_start + q_block + block_k - 1) // block_k)

    def body(i, carry):
        acc, m, l = carry
        start = i * block_k
        k_blk = k_ref[pl.dslice(start, block_k), :]
        v_blk = v_ref[pl.dslice(start, block_k), :]
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (Bq, Bk)
        if causal:
            k_pos = start + jax.lax.broadcasted_iota(jnp.int32,
                                                     (1, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, -jnp.inf)
        m_blk = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m, m_blk)
        safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        p = jnp.exp(s - safe)
        p = jnp.where(jnp.isneginf(s), 0.0, p)
        corr = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - safe))
        l_new = l * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_new = acc * corr + jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return acc_new, m_new, l_new

    acc, m, l = jax.lax.fori_loop(0, n_blocks, body, (acc0, m0, l0))
    l_safe = jnp.where(l == 0, 1.0, l)
    o_ref[:] = (acc / l_safe).astype(o_ref.dtype)
    # rows with no valid key (UNREACHABLE for kernel-eligible shapes:
    # self-attention with s_q == s_k always has the diagonal key): the
    # +inf sentinel makes every backward p = exp(s - lse) collapse to 0,
    # matching the zero forward output. NOTE the dense with-lse oracle
    # uses -inf for empty rows (the merge-correct logsumexp-of-empty
    # convention) — the two only disagree on rows that cannot exist here.
    # The row statistic is replicated across a minor dim of 8 — the
    # smallest lane count the TPU lowering accepts for a blocked store
    lse = jnp.where(l == 0, jnp.inf, m + jnp.log(l_safe))
    lse_ref[:] = jnp.broadcast_to(lse, (q_block, _LSE_LANES))


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
                         glse_ref, dq_ref, *, block_k, seq_len, causal,
                         scale):
    """dQ for one (bh, q-block): stream K/V. With the saved lse the
    softmax re-materializes blockwise (p = exp(s - lse)) — no (S, S)
    tensor ever exists; delta = rowsum(dO * O) is recomputed in-VMEM from
    the O/dO blocks (cheaper than a third saved row array). glse is the
    lse OUTPUT's cotangent (ring/blockwise merging differentiates
    through lse): dlse_i/ds_ij = p_ij, so it simply subtracts from the
    row term — zeros when lse is not a differentiated output."""
    import jax.experimental.pallas as pl

    q_block = q_ref.shape[0]
    q = q_ref[:]
    do = do_ref[:].astype(jnp.float32)                  # (Bq, D)
    lse = lse_ref[:, 0:1]                               # (Bq, 1)
    delta = jnp.sum(do * o_ref[:].astype(jnp.float32), axis=1,
                    keepdims=True)                      # (Bq, 1)
    if glse_ref is not None:
        delta = delta - glse_ref[:, 0:1]
    q_start = pl.program_id(1) * q_block
    q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (q_block, 1), 0)

    n_blocks = seq_len // block_k
    if causal:
        n_blocks = jnp.minimum(
            n_blocks, (q_start + q_block + block_k - 1) // block_k)

    def body(i, dq_acc):
        start = i * block_k
        k_blk = k_ref[pl.dslice(start, block_k), :]
        v_blk = v_ref[pl.dslice(start, block_k), :]
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (Bq, Bk)
        if causal:
            k_pos = start + jax.lax.broadcasted_iota(jnp.int32,
                                                     (1, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, -jnp.inf)
        p = jnp.exp(s - lse)                             # masked rows -> 0
        dp = jax.lax.dot_general(
            do, v_blk.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # (Bq, Bk)
        ds = p * (dp - delta) * scale
        return dq_acc + jax.lax.dot_general(
            ds.astype(k_blk.dtype), k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # (Bq, D)

    dq = jax.lax.fori_loop(0, n_blocks,
                           body, jnp.zeros(q.shape, jnp.float32))
    dq_ref[:] = dq.astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
                          glse_ref, dk_ref, dv_ref, *, block_q, seq_len,
                          causal, scale):
    """dK/dV for one (bh, k-block): stream Q/dO/O blocks. Causal skip from
    the other side — q-blocks strictly above this k-block see none of it
    (fori_loop lower bound derived from the grid position)."""
    import jax.experimental.pallas as pl

    block_k = k_ref.shape[0]
    k = k_ref[:]                                        # (Bk, D)
    v = v_ref[:]
    k_start = pl.program_id(1) * block_k
    k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)

    first_block = k_start // block_q if causal else 0
    n_blocks = seq_len // block_q

    def body(i, carry):
        dk_acc, dv_acc = carry
        start = i * block_q
        q_blk = q_ref[pl.dslice(start, block_q), :]      # (Bq, D)
        do_blk = do_ref[pl.dslice(start, block_q), :].astype(jnp.float32)
        lse = lse_ref[pl.dslice(start, block_q), 0:1]    # (Bq, 1)
        delta = jnp.sum(
            do_blk * o_ref[pl.dslice(start, block_q), :].astype(
                jnp.float32), axis=1, keepdims=True)     # (Bq, 1)
        if glse_ref is not None:
            delta = delta - glse_ref[pl.dslice(start, block_q), 0:1]
        s = jax.lax.dot_general(
            q_blk, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (Bq, Bk)
        if causal:
            q_pos = start + jax.lax.broadcasted_iota(jnp.int32,
                                                     (block_q, 1), 0)
            s = jnp.where(q_pos >= k_pos, s, -jnp.inf)
        p = jnp.exp(s - lse)
        # dV += P^T dO  (contract over the q rows)
        dv_acc = dv_acc + jax.lax.dot_general(
            p.astype(do_blk.dtype), do_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # (Bk, D)
        dp = jax.lax.dot_general(
            do_blk, v.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # (Bq, Bk)
        ds = p * (dp - delta) * scale
        # dK += dS^T Q
        dk_acc = dk_acc + jax.lax.dot_general(
            ds.astype(q_blk.dtype), q_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # (Bk, D)
        return dk_acc, dv_acc

    dk, dv = jax.lax.fori_loop(
        first_block, n_blocks, body,
        (jnp.zeros(k.shape, jnp.float32), jnp.zeros(v.shape, jnp.float32)))
    dk_ref[:] = dk.astype(dk_ref.dtype)
    dv_ref[:] = dv.astype(dv_ref.dtype)


def _sds(shape, dtype, like):
    """ShapeDtypeStruct carrying the input's varying-mesh-axes set, so
    pallas_call outputs typecheck under shard_map's vma analysis (the
    kernels are purely shard-local: outputs vary exactly as q does)."""
    try:
        vma = jax.typeof(like).vma
    except Exception:
        vma = None
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


def _vmem_params(s, d, n_full_streams, interpret, itemsize=2):
    """Mosaic compiler params for long sequences: the kernels keep
    full-length (S, D) K/V (and, in the backward, Q/dO/O) refs resident
    in VMEM with double buffering across grid cells; past ~8k tokens
    that legitimately exceeds the default 16MB scoped-vmem budget
    (measured on v5e: s=12288 wants 16.7M). Raise the per-kernel limit
    toward the physical VMEM when the estimate calls for it — the
    budget is a compiler default, not the hardware bound."""
    if interpret:
        return {}
    # a minor dim under 128 is tiled out to a whole lane row in VMEM (heads
    # of 64: the dK/dV kernel's three streams took 20.75M of the default
    # 16M at 8,192 tokens; compile, PR 32)
    need = n_full_streams * s * max(d, 128) * itemsize * 2   # x2 buffers
    if need <= 8 * 2 ** 20:
        # q/out blocks + lse + scratch ride within the default budget
        return {}
    from jax.experimental.pallas import tpu as pltpu
    # s/d/need are static python shape ints even at trace time, not
    # tracers — the cast never syncs  # analysis: allow=trace-host-cast
    limit = min(110 * 2 ** 20, int(need * 1.5) + 16 * 2 ** 20)
    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=limit)}


def _kv_index_map(h, h_kv):
    """Grid-index map for K/V refs under GQA: q-head `bh % h` reads kv
    head `(bh % h) // group` — the kernels stream the SHARED kv block
    straight from HBM, no repeated copy is ever materialized."""
    if h == h_kv:
        return lambda bh, i: (bh, 0, 0)
    group = h // h_kv
    return lambda bh, i: ((bh // h) * h_kv + (bh % h) // group, 0, 0)


def _flash_pallas(q, k, v, causal, scale, interpret=False, block_q=None,
                  block_k=None):
    """Forward kernel. q, k (B, H | H_kv, S, D) and v (B, H_kv, S, Dv)
    with H % H_kv == 0 (GQA/MQA share kv blocks in-kernel), S % block == 0
    and D, Dv as _pallas_eligible takes them (Dv may differ from D: latent
    attention's 192/128). Returns (out (B,H,S,Dv), lse (B*H, S, 8) f32 —
    the row statistic lane-replicated for TPU block tiling)."""
    import jax.experimental.pallas as pl

    b, h, s, d = q.shape
    d_v = v.shape[-1]
    h_kv = k.shape[1]
    block_q = min(block_q or _auto_block(s), s)
    block_k = min(block_k or _auto_block(s), s)
    if s % block_q or s % block_k:
        # forced/explicit blocks that don't tile S would silently leave
        # grid-truncated output rows unwritten
        raise ValueError(f"flash attention: seq {s} is not divisible by "
                         f"blocks ({block_q}, {block_k})")
    qf = q.reshape(b * h, s, d)
    kf = k.reshape(b * h_kv, s, d)
    vf = v.reshape(b * h_kv, s, d_v)
    kv_map = _kv_index_map(h, h_kv)
    kernel = functools.partial(_flash_kernel, block_k=block_k, seq_len=s,
                               causal=causal, scale=scale)
    out, lse = pl.pallas_call(
        kernel,
        grid=(b * h, s // block_q),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((None, s, d), kv_map),
            pl.BlockSpec((None, s, d_v), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, d_v), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((None, block_q, _LSE_LANES),
                         lambda bh, qi: (bh, qi, 0)),
        ],
        out_shape=[
            _sds((b * h, s, d_v), q.dtype, q),
            _sds((b * h, s, _LSE_LANES), jnp.float32, q),
        ],
        interpret=interpret,
        name="mx_flash_attention_fwd",
        **_vmem_params(s, max(d, d_v), 2, interpret, q.dtype.itemsize),
    )(qf, kf, vf)
    return out.reshape(b, h, s, d_v), lse


def _flash_pallas_kept(*args, **kwargs):
    """`_flash_pallas` for the `fwd` of a custom VJP: (out, lse (B*H, S)),
    both tagged as this op's kept residuals. The caller hands the TAGGED
    values to its primal output and to its residuals alike: a stage that
    keeps them then reads nothing of the rerun's kernel call, which is
    dead code. Of the lane-replicated lse the first lane alone is kept
    (`_lse_lanes` replicates it again for the backward kernels): a minor
    dim of 8 is tiled out to 128 lanes in HBM, 16 times the row
    statistic's bytes for as long as the array lives."""
    out, lse = _flash_pallas(*args, **kwargs)
    return (checkpoint_name(out, _KEPT_OUT),
            checkpoint_name(lse[:, :, 0], _KEPT_LSE))


def _lse_lanes(lse):
    """(B*H, S) -> the kernels' lane-replicated (B*H, S, 8)."""
    return jnp.broadcast_to(lse[:, :, None], (*lse.shape, _LSE_LANES))


def _flash_pallas_bwd(q, k, v, o, lse, g, causal, scale, interpret=False,
                      g_lse=None, block_q=None, block_k=None):
    """Recompute-based flash backward: two single-HBM-pass kernels (dQ
    gridded over q-blocks; dK/dV over k-blocks) re-derive the softmax
    from the saved lse — O(S) extra memory, never an (S, S) tensor.
    g_lse (B, H, S) is the lse output's cotangent when lse is itself a
    differentiated output (blockwise/ring merging); None means zeros.
    GQA: kv blocks stream shared via the index map (like the forward);
    the dK/dV kernel still produces PER-Q-HEAD partials, reduced over
    each group outside the kernel (one cheap XLA sum — the simple,
    correct realization; an in-kernel cross-head accumulation would
    need grid-order-dependent output aliasing)."""
    import jax.experimental.pallas as pl

    b, h, s, d = q.shape
    d_v = v.shape[-1]
    h_kv = k.shape[1]
    kv_map = _kv_index_map(h, h_kv)
    block_q = min(block_q or _auto_block(s), s)
    block_k = min(block_k or _auto_block(s), s)
    if s % block_q or s % block_k:
        raise ValueError(f"flash attention bwd: seq {s} is not divisible "
                         f"by blocks ({block_q}, {block_k})")
    qf = q.reshape(b * h, s, d)
    kf = k.reshape(b * h_kv, s, d)
    vf = v.reshape(b * h_kv, s, d_v)
    dof = g.reshape(b * h, s, d_v)
    of = o.reshape(b * h, s, d_v)
    have_glse = g_lse is not None
    if have_glse:
        # the masked-row lse can be +/-inf sentinels; 0*inf would NaN, so
        # derive the vma-carrying zero from a finitized lse
        glse_args = (jnp.broadcast_to(
            g_lse.astype(jnp.float32).reshape(b * h, s, 1),
            (b * h, s, _LSE_LANES))
            + 0.0 * jnp.where(jnp.isfinite(lse), lse, 0.0),)
    else:
        glse_args = ()

    def _with_optional_glse(kernel, n_lead):
        """The hot no-glse path passes glse_ref=None statically — no
        extra HBM stream for the common training backward."""
        if have_glse:
            return kernel
        return functools.partial(
            lambda *refs, k: k(*refs[:n_lead], None, *refs[n_lead:]),
            k=kernel)

    q_full = pl.BlockSpec((None, s, d), lambda bh, i: (bh, 0, 0))
    o_full = pl.BlockSpec((None, s, d_v), lambda bh, i: (bh, 0, 0))
    k_full = pl.BlockSpec((None, s, d), kv_map)
    v_full = pl.BlockSpec((None, s, d_v), kv_map)
    q_blk = pl.BlockSpec((None, block_q, d), lambda bh, qi: (bh, qi, 0))
    o_blk = pl.BlockSpec((None, block_q, d_v), lambda bh, qi: (bh, qi, 0))
    lse_full = pl.BlockSpec((None, s, _LSE_LANES), lambda bh, i: (bh, 0, 0))
    lse_blk = pl.BlockSpec((None, block_q, _LSE_LANES),
                           lambda bh, qi: (bh, qi, 0))

    dq_kernel = _with_optional_glse(
        functools.partial(_flash_bwd_dq_kernel, block_k=block_k,
                          seq_len=s, causal=causal, scale=scale), 6)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(b * h, s // block_q),
        in_specs=[q_blk, k_full, v_full, o_blk, o_blk, lse_blk]
        + ([lse_blk] if have_glse else []),
        out_specs=q_blk,
        out_shape=_sds((b * h, s, d), q.dtype, q),
        interpret=interpret,
        name="mx_flash_attention_bwd_dq",
        **_vmem_params(s, max(d, d_v), 2, interpret, q.dtype.itemsize),
    )(qf, kf, vf, dof, of, lse, *glse_args)

    if h == h_kv:
        def kv_blk_map(bh, ki):
            return (bh, ki, 0)
    else:
        group = h // h_kv

        def kv_blk_map(bh, ki):
            return ((bh // h) * h_kv + (bh % h) // group, ki, 0)
    k_blk = pl.BlockSpec((None, block_k, d), kv_blk_map)
    v_blk = pl.BlockSpec((None, block_k, d_v), kv_blk_map)
    dkv_kernel = _with_optional_glse(
        functools.partial(_flash_bwd_dkv_kernel, block_q=block_q,
                          seq_len=s, causal=causal, scale=scale), 6)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(b * h, s // block_k),
        in_specs=[q_full, k_blk, v_blk, o_full, o_full, lse_full]
        + ([lse_full] if have_glse else []),
        out_specs=[
            pl.BlockSpec((None, block_k, d), lambda bh, ki: (bh, ki, 0)),
            pl.BlockSpec((None, block_k, d_v), lambda bh, ki: (bh, ki, 0)),
        ],
        out_shape=[
            _sds((b * h, s, d), k.dtype, q),
            _sds((b * h, s, d_v), v.dtype, q),
        ],
        interpret=interpret,
        name="mx_flash_attention_bwd_dkv",
        **_vmem_params(s, max(d, d_v), 3, interpret, q.dtype.itemsize),
    )(qf, kf, vf, dof, of, lse, *glse_args)

    dq = dq.reshape(b, h, s, d)
    dk = dk.reshape(b, h, s, d)
    dv = dv.reshape(b, h, s, d_v)
    if h != h_kv:
        group = h // h_kv
        dk = dk.reshape(b, h_kv, group, s, d).sum(2).astype(k.dtype)
        dv = dv.reshape(b, h_kv, group, s, d_v).sum(2).astype(v.dtype)
    return dq, dk, dv


def _pallas_eligible(q, k, platform=None, block_q=None, block_k=None,
                     v=None):
    b, h, s, d = q.shape
    if k.shape != q.shape:
        # GQA/MQA (fewer kv heads, same seq) stays kernel-eligible; true
        # cross-attention (s_q != s_k) goes to the XLA path
        if k.shape[0] != b or k.shape[2] != s or k.shape[3] != d \
                or k.shape[1] == 0 or h % k.shape[1] != 0:
            return False
    if v is not None and v.shape[:3] != k.shape[:3]:
        return False
    # the query/key width and the value width may differ (latent
    # attention: 192 and 128); each a multiple of 64
    if d % 64 != 0 or (v is not None and v.shape[3] % 64 != 0):
        return False
    if s % min(block_q or _auto_block(s), s) != 0 or \
            s % min(block_k or _auto_block(s), s) != 0:
        return False
    if s < 8:
        return False
    # TPU-only auto-pick: the kernels' lse layout and block tiling are
    # TPU-tuned — a GPU backend falls back to the XLA path unless the
    # caller forces pallas explicitly
    if platform is not None:
        return platform == "tpu"
    return jax.default_backend() == "tpu"


def flash_attention_with_lse(q, k, v, causal=False, scale=None,
                             force=None, platform=None):
    """(out, lse) variant of flash_attention for blockwise/ring
    combiners. BOTH outputs are differentiable: the Pallas backward
    folds the lse cotangent into its row term (glse in the kernels)."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    use_pallas = (force in ("pallas", "interpret") or
                  (force is None and _pallas_eligible(q, k, platform)))
    if not use_pallas:
        return reference_attention_with_lse(q, k, v, causal, scale)
    interpret = force == "interpret"
    b, h, s, _ = q.shape

    @jax.custom_vjp
    def fn(q, k, v):
        out, lse = _flash_pallas(q, k, v, causal, scale,
                                 interpret=interpret)
        return out, lse[:, :, 0].reshape(b, h, s)

    def fwd(q, k, v):
        out, lse = _flash_pallas_kept(q, k, v, causal, scale,
                                      interpret=interpret)
        return (out, lse.reshape(b, h, s)), (q, k, v, out, lse)

    def bwd(res, cotangents):
        g_o, g_lse = cotangents
        q, k, v, out, lse = res
        return _flash_pallas_bwd(q, k, v, out, _lse_lanes(lse), g_o, causal,
                                 scale, interpret=interpret, g_lse=g_lse)

    fn.defvjp(fwd, bwd)
    return fn(q, k, v)


def _flash_pallas_trainable(q, k, v, causal, scale, interpret=False,
                            block_q=None, block_k=None):
    """Pallas forward + Pallas recompute-based backward (FlashAttention-2
    style): the forward saves only O and the per-row logsumexp; the
    backward re-materializes softmax blocks from them in VMEM. Activation
    memory is O(B*H*S*D + B*H*S), never O(S^2) — the long-context
    training path. The two are declared as kept (`_flash_pallas_kept`): a
    rematerialised stage around this call runs the forward kernel once."""

    @jax.custom_vjp
    def fn(q, k, v):
        out, _ = _flash_pallas(q, k, v, causal, scale, interpret=interpret,
                               block_q=block_q, block_k=block_k)
        return out

    def fwd(q, k, v):
        out, lse = _flash_pallas_kept(q, k, v, causal, scale,
                                      interpret=interpret, block_q=block_q,
                                      block_k=block_k)
        return out, (q, k, v, out, lse)

    def bwd(res, g):
        q, k, v, out, lse = res
        return _flash_pallas_bwd(q, k, v, out, _lse_lanes(lse), g, causal,
                                 scale, interpret=interpret, block_q=block_q,
                                 block_k=block_k)

    fn.defvjp(fwd, bwd)
    return fn(q, k, v)


def flash_attention(q, k, v, causal=False, scale=None, force=None,
                    platform=None, block_q=None, block_k=None):
    """Blockwise attention: Pallas kernel on TPU, fused XLA otherwise.

    force: None (auto) | 'pallas' | 'xla' | 'interpret' (kernel under the
    Pallas interpreter — CPU-testable). `platform` is the jit target's
    platform when the caller compiles for a specific device (the executor
    plumbs it via OpCtx); auto mode must not pick the pallas path for a
    cpu-targeted program just because the DEFAULT backend is a TPU.

    GQA/MQA: k/v may carry fewer heads than q (H % H_kv == 0) — the
    kernels stream the SHARED kv blocks (no repeated copy; dK/dV group
    partials reduce outside the kernel). block_q/block_k override the
    default 128 tiling.
    """
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    if force == "xla":
        return reference_attention(q, k, v, causal, scale)
    if force == "interpret":
        return _flash_pallas_trainable(q, k, v, causal, scale,
                                       interpret=True, block_q=block_q,
                                       block_k=block_k)
    if force == "pallas" or (force is None and
                             _pallas_eligible(q, k, platform, block_q,
                                              block_k, v=v)):
        return _flash_pallas_trainable(q, k, v, causal, scale,
                                       block_q=block_q, block_k=block_k)
    if force is None and (platform or jax.default_backend()) == "tpu":
        _count_dense_fallback(q, k, v)
    return reference_attention(q, k, v, causal, scale)


DENSE_FALLBACK_COUNTER = "attention_dense_fallback_total"


def _count_dense_fallback(q, k, v):
    """A program for a TPU took the dense path, which materialises the
    (S, S) scores: counted once per trace in the telemetry registry and
    logged, so that a shape the kernel cannot take is seen, not guessed."""
    import logging
    from ..telemetry import registry
    registry.counter(
        DENSE_FALLBACK_COUNTER,
        help="attention calls traced for a TPU whose shapes the flash "
             "kernel does not take (dense S x S scores instead)").inc()
    logging.getLogger(__name__).warning(
        "flash_attention: q %s k %s v %s not eligible for the TPU kernel; "
        "dense (S, S) scores", q.shape, k.shape, v.shape)


# -- decode mode (q_len = 1 against a KV cache) -----------------------------

def reference_decode_attention(q, k, v, lengths, scale=None):
    """Dense decode-step oracle. q (B, H, D) is the current token's
    query; k/v (B, H_kv, S, D) are KV caches of which only the first
    ``lengths[b]`` positions are valid (the rest is stale pool memory and
    MUST NOT leak into the softmax). Returns (B, H, D). Rows with
    lengths == 0 produce zeros (the empty-softmax convention shared with
    reference_attention_with_lse)."""
    b, h, d = q.shape
    h_kv, s = k.shape[1], k.shape[2]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    if h_kv != h:
        group = h // h_kv
        k = jnp.repeat(k, group, axis=1)
        v = jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("bhd,bhsd->bhs", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    pos = jax.lax.broadcasted_iota(jnp.int32, (1, 1, s), 2)
    valid = pos < jnp.asarray(lengths, jnp.int32).reshape(b, 1, 1)
    scores = jnp.where(valid, scores, -jnp.inf)
    m = jnp.max(scores, axis=-1)
    safe = jnp.where(jnp.isneginf(m), 0.0, m)
    p = jnp.exp(scores - safe[..., None])
    p = jnp.where(jnp.isneginf(scores), 0.0, p)
    l = jnp.sum(p, axis=-1)
    l_safe = jnp.where(l == 0, 1.0, l)
    out = jnp.einsum("bhs,bhsd->bhd", p,
                     v.astype(jnp.float32)) / l_safe[..., None]
    return out.astype(q.dtype)


def _decode_kernel(q_ref, k_ref, v_ref, len_ref, o_ref, *, block_k,
                   seq_len, scale):
    """One kv-head grid cell of the decode step: the q "rows" are the
    GQA group sharing this kv head (the q_len=1 realization of the
    forward kernel's (q-block, kv-stream) structure — the group axis
    stands in for the q-block so the MXU still sees a matmul). K/V
    stream in blocks with the online softmax; positions >= the session's
    length are masked (stale pool memory beyond the write cursor)."""
    import jax.experimental.pallas as pl

    q = q_ref[:]                                        # (G, D)
    l = len_ref[0, 0]                                   # valid kv length
    g = q.shape[0]
    acc0 = jnp.zeros((g, q.shape[1]), jnp.float32)
    m0 = jnp.full((g, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((g, 1), jnp.float32)
    # dynamic block bound: blocks wholly past the write cursor contribute
    # nothing — the decode cost scales with the session's length, not the
    # pool's max_len
    n_blocks = jnp.minimum(seq_len // block_k,
                           (l + block_k - 1) // block_k)

    def body(i, carry):
        acc, m, lsum = carry
        start = i * block_k
        k_blk = k_ref[pl.dslice(start, block_k), :]
        v_blk = v_ref[pl.dslice(start, block_k), :]
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (G, Bk)
        k_pos = start + jax.lax.broadcasted_iota(jnp.int32,
                                                 (1, block_k), 1)
        s = jnp.where(k_pos < l, s, -jnp.inf)
        m_blk = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m, m_blk)
        safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        p = jnp.exp(s - safe)
        p = jnp.where(jnp.isneginf(s), 0.0, p)
        corr = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - safe))
        l_new = lsum * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_new = acc * corr + jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return acc_new, m_new, l_new

    acc, _, lsum = jax.lax.fori_loop(0, n_blocks, body, (acc0, m0, l0))
    l_safe = jnp.where(lsum == 0, 1.0, lsum)
    o_ref[:] = (acc / l_safe).astype(o_ref.dtype)


def _decode_pallas(q, k, v, lengths, scale, interpret=False):
    import jax.experimental.pallas as pl

    b, h, d = q.shape
    h_kv, s = k.shape[1], k.shape[2]
    group = h // h_kv
    block_k = min(_auto_block(s), s)
    qf = q.reshape(b * h_kv, group, d)
    kf = k.reshape(b * h_kv, s, d)
    vf = v.reshape(b * h_kv, s, d)
    lens = jnp.asarray(lengths, jnp.int32).reshape(b, 1, 1)
    kernel = functools.partial(_decode_kernel, block_k=block_k,
                               seq_len=s, scale=scale)
    out = pl.pallas_call(
        kernel,
        grid=(b * h_kv,),
        in_specs=[
            pl.BlockSpec((None, group, d), lambda bh: (bh, 0, 0)),
            pl.BlockSpec((None, s, d), lambda bh: (bh, 0, 0)),
            pl.BlockSpec((None, s, d), lambda bh: (bh, 0, 0)),
            pl.BlockSpec((None, 1, 1), lambda bh: (bh // h_kv, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, group, d), lambda bh: (bh, 0, 0)),
        out_shape=_sds((b * h_kv, group, d), q.dtype, q),
        interpret=interpret,
        **_vmem_params(s, d, 2, interpret, q.dtype.itemsize),
    )(qf, kf, vf, lens)
    return out.reshape(b, h, d)


def _decode_eligible(q, k, platform=None):
    b, h, d = q.shape
    if k.shape[0] != b or k.shape[3] != d or k.shape[1] == 0 \
            or h % k.shape[1] != 0:
        return False
    s = k.shape[2]
    if d % 128 != 0 and d not in (64,):
        return False
    if s % min(_auto_block(s), s) != 0 or s < 8:
        return False
    if platform is not None:
        return platform == "tpu"
    return jax.default_backend() == "tpu"


def decode_attention(q, k, v, lengths, scale=None, force=None,
                     platform=None):
    """Single-token decode attention against a length-masked KV cache.

    q (B, H, D); k/v (B, H_kv, S, D) pool blocks; lengths (B,) int32
    valid-prefix lengths. GQA shares kv in-kernel exactly like
    flash_attention (the kv-head grid cell serves its whole q group).
    force: None (auto: Pallas on TPU-eligible shapes) | 'pallas' |
    'xla' | 'interpret'."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    if force == "xla":
        return reference_decode_attention(q, k, v, lengths, scale)
    if force in ("pallas", "interpret") or \
            (force is None and _decode_eligible(q, k, platform)):
        return _decode_pallas(q, k, v, lengths, scale,
                              interpret=force == "interpret")
    return reference_decode_attention(q, k, v, lengths, scale)


# -- registry surface -------------------------------------------------------

GQA_COUNTER = "gqa_attention_calls_total"


def _flash_attention_op(attrs, octx, q, k, v):
    if k.shape[1] != q.shape[1]:
        from ..telemetry import registry
        registry.counter(
            GQA_COUNTER, help="attention calls traced with fewer k/v heads "
            "than query heads (a group of query heads shares one)").inc()
    with jax.named_scope("mx.flash_attention"):
        return _t(flash_attention(q, k, v, causal=attrs["causal"],
                                  scale=attrs["scale"],
                                  platform=octx.platform))


register("_contrib_flash_attention", _flash_attention_op,
         params={"causal": Param("bool", False),
                 "scale": Param("float", None)},
         inputs=("query", "key", "value"),
         # the output has the value's width (latent attention: 192 / 128)
         infer_shape=lambda attrs, s: (s, [
             None if s[0] is None or s[2] is None
             else tuple(s[0][:-1]) + (s[2][-1],)]))

