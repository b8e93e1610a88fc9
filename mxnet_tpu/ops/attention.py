"""Attention operators — Pallas flash-attention kernel + XLA fallback.

The reference has no attention op (its transformer support is the helper
`_contrib_div_sqrt_dim`, src/operator/contrib/transformer.cc:34); this is
TPU-first new surface: a blockwise online-softmax kernel written in Pallas
(per /opt/skills/guides/pallas_guide.md) that keeps the (S, S) score
matrix out of HBM, gridded over (batch*heads, q-blocks) with the K/V
stream resident in VMEM, and ONE backward kernel that rebuilds each block
pair's softmax once from the saved row statistic and feeds dQ, dK and dV
from it. Forward and backward share the causal loop bounds
(`_causal_bounds`: no mask below the diagonal, a mask on the blocks that
straddle it, nothing above it), and the row statistics (lse, delta) travel
along the lanes, (B*H, 1, S). Dispatch picks the kernels on TPU for
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
# what the flash backward needs beyond q, k and v, which a rematerialised
# stage's rerun rebuilds from its products: computing these two again is
# the whole forward kernel, keeping them is 2*B*H*S*Dv + 4*B*H*S bytes
_KEPT_OUT = kept_residual("mx_flash_attention_out")
_KEPT_LSE = kept_residual("mx_flash_attention_lse")


def _auto_block(s):
    """Default block size: the LARGEST of 512/256/128 dividing S —
    bigger tiles amortize the per-block softmax bookkeeping and keep the
    MXU busier. Timed on a v5e at 8,192 tokens with 256, 512 and 1,024 on
    each side (`tests_tpu/test_flash_kernels.py`; PERF.md §6, PR 33): 512 x
    512 is the fastest or within 1% of it for the forward and the backward
    at 64 heads of 192 / 128, and for the backward at 64 over 16 heads of
    64, whose forward alone prefers 1,024 rows of q, by 5%; 256 on either
    side loses 4 to 90%. Sequences not
    divisible by 128 fall back to a single block (small-S case)."""
    for blk in (512, 256, 128):
        if s % blk == 0:
            return blk
    return min(_BLOCK_Q, s)


def _blocks(s, block_q, block_k):
    """The (q, k) blocks of a call: the override or `_auto_block`'s, each
    tiling S. Both kernels slice rows of S along the LANES (the transposed
    blocks, the (1, S) row statistics), so a block that is not the whole
    sequence is a multiple of 128."""
    blocks = tuple(min(blk or _auto_block(s), s) for blk in (block_q, block_k))
    for blk in blocks:
        # forced/explicit blocks that don't tile S would silently leave
        # grid-truncated output rows unwritten
        if s % blk or (blk != s and blk % 128):
            raise ValueError(
                f"flash attention: seq {s} is not divisible by blocks "
                f"{blocks}, or a block is no multiple of 128")
    return blocks


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


def _causal_bounds(q_start, block_q, block_k, n_blocks, causal):
    """(n_full, n_visited) of the k-blocks a q-block starting at `q_start`
    meets, shared by the forward and the backward so that both rebuild the
    same softmax: blocks [0, n_full) lie wholly on or below the diagonal
    (their last column is at most the q-block's first row) and need no
    mask; [n_full, n_visited) straddle it and are masked element by
    element; the rest lie wholly above it and are skipped."""
    if not causal:
        return n_blocks, n_blocks
    return ((q_start + 1) // block_k,
            jnp.minimum(n_blocks,
                        (q_start + block_q + block_k - 1) // block_k))


def _k_rows(i, block_k, seq_len):
    """(first row, slice) of k-block `i` inside a kernel. A sequence of one
    block (the only case of a block under 128) is sliced statically: the
    backward slices these rows along the lanes of dK^T too, where Mosaic
    takes a dynamic start only if it can see a multiple of 128."""
    import jax.experimental.pallas as pl
    if block_k == seq_len:
        return 0, slice(None)
    start = pl.multiple_of(i * block_k, block_k)
    return start, pl.dslice(start, block_k)


def _scores_t(k_blk, q, scale, k_start, q_start, masked):
    """One pair's scaled scores, TRANSPOSED: (Bk, Bq) float32 from operands
    in their storage dtype, with the causal mask where the pair straddles
    the diagonal. The forward's and the backward's alike: a backward that
    rebuilt p from other scores than the forward's would be a bug to
    chase. The scale is applied to the float32 scores (numerically at
    least as good as scaling q)."""
    s = jax.lax.dot_general(
        k_blk, q, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    if masked:
        k_pos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (k_blk.shape[0], 1), 0)
        q_pos = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (1, q.shape[0]), 1)
        s = jnp.where(q_pos >= k_pos, s, -jnp.inf)
    return s


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_k, seq_len,
                  causal, scale):
    """One (bh, q-block) grid cell: stream K/V blocks with online softmax.
    Each score block is computed TRANSPOSED, (Bk, Bq): the running max and
    sum are ROWS along the lanes (a reduction over sublanes is elementwise
    work where one over lanes goes through the cross-lane unit, and a row
    of 512 is 4 registers where a column is 64), the output accumulates as
    (Dv, Bq) and is transposed once, and the per-row logsumexp — the
    backward's saved statistic — leaves as a row of the (1, S) statistic."""
    import jax.experimental.pallas as pl

    # keep q in its storage dtype: the MXU runs bf16 matmuls at full rate
    # while an fp32 upcast would halve+ throughput; accumulation happens
    # in fp32 via preferred_element_type
    q = q_ref[...]                                      # (Bq, D)
    block_q = q.shape[0]
    q_start = pl.program_id(1) * block_q
    n_full, n_visited = _causal_bounds(q_start, block_q, block_k,
                                       seq_len // block_k, causal)

    def pair(masked, i, carry):
        acc, m, l = carry
        start, rows = _k_rows(i, block_k, seq_len)
        k_blk = k_ref[rows, :]
        v_blk = v_ref[rows, :]
        s = _scores_t(k_blk, q, scale, start, q_start, masked)
        m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
        if masked:
            safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
            p = jnp.where(jnp.isneginf(s), 0.0, jnp.exp(s - safe))
            corr = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - safe))
        else:
            # every score is finite, so the running max is, and
            # exp(-inf - m_new) of the first block's m is the 0 it needs
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m - m_new)
        acc = acc * corr + jax.lax.dot_general(
            v_blk, p.astype(v_blk.dtype), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # (Dv, Bq)
        return acc, m_new, l * corr + jnp.sum(p, axis=0, keepdims=True)

    carry = (jnp.zeros((v_ref.shape[1], block_q), jnp.float32),
             jnp.full((1, block_q), -jnp.inf, jnp.float32),
             jnp.zeros((1, block_q), jnp.float32))
    carry = jax.lax.fori_loop(0, n_full, functools.partial(pair, False),
                              carry)
    if causal:
        carry = jax.lax.fori_loop(n_full, n_visited,
                                  functools.partial(pair, True), carry)
    acc, m, l = carry
    l_safe = jnp.where(l == 0, 1.0, l)
    o_ref[...] = (acc / l_safe).T.astype(o_ref.dtype)
    # rows with no valid key (UNREACHABLE for kernel-eligible shapes:
    # self-attention with s_q == s_k always has the diagonal key): the
    # +inf sentinel makes every backward p = exp(s - lse) collapse to 0,
    # matching the zero forward output. NOTE the dense with-lse oracle
    # uses -inf for empty rows (the merge-correct logsumexp-of-empty
    # convention) — the two only disagree on rows that cannot exist here.
    lse_ref[...] = jnp.where(l == 0, jnp.inf, m + jnp.log(l_safe))


def _flash_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dk_ref, dv_ref, dkt_acc, dvt_acc, *, block_k,
                      seq_len, causal, scale):
    """The whole backward for one (kv head, query head of its group,
    q-block) grid cell: every (q-block, k-block) pair's softmax is rebuilt
    ONCE from the saved lse (p = exp(s - lse); never an (S, S) tensor) and
    feeds all three gradients, five products a pair. Each block is computed
    TRANSPOSED, (Bk, Bq): the row statistics lse and delta arrive as rows
    along the lanes and broadcast over sublanes. The three gradients are
    taken transposed too, dQ^T = k^T ds, dK^T = q^T ds^T and
    dV^T = dO^T p^T, (D, B): the MXU pads a contraction or an output width
    to its 128 but streams any number of rows, so there a width of 192
    costs 192, and one of 64 costs 64, where elsewhere they cost 256 and
    128. dQ^T is the loop's carry; dK^T and dV^T of the kv head accumulate
    in float32 scratch that stays in VMEM across the group and q-block
    axes (every query head of a group adds into the same one) and are
    cast to the storage dtype once, at the head's last cell.
    delta = rowsum(dO * O) - g_lse is computed once a row outside."""
    import jax.experimental.pallas as pl

    block_q = q_ref.shape[0]
    g, qi = pl.program_id(1), pl.program_id(2)
    n_blocks = seq_len // block_k

    @pl.when(jnp.logical_and(g == 0, qi == 0))
    def _():
        dkt_acc[...] = jnp.zeros_like(dkt_acc)
        dvt_acc[...] = jnp.zeros_like(dvt_acc)

    # operands in their storage dtype, float32 accumulation, as the forward
    q = q_ref[...]                                      # (Bq, D)
    q_t = q.T                                           # (D, Bq)
    do = do_ref[...]                                    # (Bq, Dv)
    do_t = do.T                                         # (Dv, Bq)
    lse = lse_ref[...]                                  # (1, Bq)
    delta = delta_ref[...]                              # (1, Bq)
    q_start = qi * block_q
    n_full, n_visited = _causal_bounds(q_start, block_q, block_k, n_blocks,
                                       causal)

    def pair(masked, j, dq_t):
        # (the writes below are to Pallas refs: they are the kernel's
        # stores, traced into it, not Python state of the trace)
        start, rows = _k_rows(j, block_k, seq_len)
        k_blk = k_ref[rows, :]                          # (Bk, D)
        v_blk = v_ref[rows, :]                          # (Bk, Dv)
        s = _scores_t(k_blk, q, scale, start, q_start, masked)
        p = jnp.exp(s - lse)                            # masked -> 0
        dp = jax.lax.dot_general(
            v_blk, do, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # (Bk, Bq)
        # the scale of ds goes onto the (d, rows) sums, once at the end
        ds = (p * (dp - delta)).astype(q.dtype)
        dvt_acc[:, rows] += jax.lax.dot_general(  # analysis: allow=trace-state-mutation
            do_t, p.astype(do.dtype), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # (Dv, Bk)
        dkt_acc[:, rows] += jax.lax.dot_general(  # analysis: allow=trace-state-mutation
            q_t, ds, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # (D, Bk)
        return dq_t + jax.lax.dot_general(
            k_blk, ds, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # (D, Bq)

    dq_t = jax.lax.fori_loop(0, n_full, functools.partial(pair, False),
                             jnp.zeros(q_t.shape, jnp.float32))
    if causal:
        dq_t = jax.lax.fori_loop(n_full, n_visited,
                                 functools.partial(pair, True), dq_t)
    dq_ref[...] = (dq_t * scale).T.astype(dq_ref.dtype)

    @pl.when(jnp.logical_and(g == pl.num_programs(1) - 1,
                             qi == pl.num_programs(2) - 1))
    def _():
        def cast(j, _):
            _, rows = _k_rows(j, block_k, seq_len)
            dk_ref[rows, :] = (dkt_acc[:, rows] * scale).T.astype(  # analysis: allow=trace-state-mutation
                dk_ref.dtype)
            dv_ref[rows, :] = dvt_acc[:, rows].T.astype(dv_ref.dtype)  # analysis: allow=trace-state-mutation
            return _
        jax.lax.fori_loop(0, n_blocks, cast, 0)


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


def _vmem_params(s, d, n_full_streams, interpret, itemsize=2,
                 n_f32_streams=0):
    """Mosaic compiler params for long sequences: the kernels keep
    full-length (S, D) K/V refs (and, in the backward, the dK/dV blocks
    and their float32 accumulators, `n_f32_streams`) resident
    in VMEM with double buffering across grid cells; past ~8k tokens
    that legitimately exceeds the default 16MB scoped-vmem budget
    (measured on v5e: s=12288 wants 16.7M). Raise the per-kernel limit
    toward the physical VMEM when the estimate calls for it — the
    budget is a compiler default, not the hardware bound."""
    if interpret:
        return {}
    # a minor dim under 128 is tiled out to a whole lane row in VMEM (heads
    # of 64: three resident streams took 20.75M of the default 16M at
    # 8,192 tokens; compile, PR 32)
    row = s * max(d, 128)
    need = n_full_streams * row * itemsize * 2 \
        + n_f32_streams * row * 4                        # x2 buffers; x1
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
    attention's 192/128). Returns (out (B,H,S,Dv), lse (B*H, 1, S) f32 —
    the row statistic along the lanes, as the backward reads it)."""
    import jax.experimental.pallas as pl

    b, h, s, d = q.shape
    d_v = v.shape[-1]
    h_kv = k.shape[1]
    block_q, block_k = _blocks(s, block_q, block_k)
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
            pl.BlockSpec((None, 1, block_q), lambda bh, qi: (bh, 0, qi)),
        ],
        out_shape=[
            _sds((b * h, s, d_v), q.dtype, q),
            _sds((b * h, 1, s), jnp.float32, q),
        ],
        interpret=interpret,
        name="mx_flash_attention_fwd",
        **_vmem_params(s, max(d, d_v), 2, interpret, q.dtype.itemsize),
    )(qf, kf, vf)
    return out.reshape(b, h, s, d_v), lse


def _flash_pallas_kept(*args, **kwargs):
    """`_flash_pallas` for the `fwd` of a custom VJP: (out, lse (B*H, 1,
    S)), both tagged as this op's kept residuals. The caller hands the
    TAGGED values to its primal output and to its residuals alike: a stage
    that keeps them then reads nothing of the rerun's kernel call, which is
    dead code. Both are kept as the kernel wrote them and as the backward
    kernel reads them: lse is 4 bytes a row."""
    out, lse = _flash_pallas(*args, **kwargs)
    return (checkpoint_name(out, _KEPT_OUT),
            checkpoint_name(lse, _KEPT_LSE))


BWD_COUNTER = "flash_bwd_kernel_calls_total"


def _flash_pallas_bwd(q, k, v, o, lse, g, causal, scale, interpret=False,
                      g_lse=None, block_q=None, block_k=None):
    """Recompute-based flash backward, ONE kernel (`_flash_bwd_kernel`): a
    single pass over the block pairs re-derives the softmax from the saved
    lse (B*H, 1, S) — O(S) extra memory, never an (S, S) tensor — and
    feeds dQ, dK and dV from it.
    g_lse (B, H, S) is the lse output's cotangent when lse is itself a
    differentiated output (blockwise/ring merging): dlse_i/ds_ij = p_ij,
    so it subtracts from the row term delta = rowsum(dO * O), which one
    XLA fusion computes once a row before the kernel.
    GQA: the grid runs (kv head, query head of its group, q-block) with the
    kv head's K/V and its float32 dK/dV resident in VMEM across the last
    two, so a group's query heads add into the same dK/dV and no per-query-
    head partial ever reaches HBM."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from ..telemetry import registry

    registry.counter(
        BWD_COUNTER, help="flash attention backward kernels traced (one a "
        "call: dQ, dK and dV from a single pass over the block pairs)").inc()
    b, h, s, d = q.shape
    d_v = v.shape[-1]
    h_kv = k.shape[1]
    group = h // h_kv
    block_q, block_k = _blocks(s, block_q, block_k)
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    if g_lse is not None:
        delta = delta - g_lse.astype(jnp.float32)

    def q_map(bh_kv, gi, qi):
        return (bh_kv * group + gi, qi, 0)

    def row_map(bh_kv, gi, qi):
        return (bh_kv * group + gi, 0, qi)

    def kv_map(bh_kv, gi, qi):
        return (bh_kv, 0, 0)

    row_stat = pl.BlockSpec((None, 1, block_q), row_map)
    dq, dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_kernel, block_k=block_k, seq_len=s,
                          causal=causal, scale=scale),
        grid=(b * h_kv, group, s // block_q),
        in_specs=[
            pl.BlockSpec((None, block_q, d), q_map),
            pl.BlockSpec((None, s, d), kv_map),
            pl.BlockSpec((None, s, d_v), kv_map),
            pl.BlockSpec((None, block_q, d_v), q_map),
            row_stat, row_stat,
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, d), q_map),
            pl.BlockSpec((None, s, d), kv_map),
            pl.BlockSpec((None, s, d_v), kv_map),
        ],
        out_shape=[
            _sds((b * h, s, d), q.dtype, q),
            _sds((b * h_kv, s, d), k.dtype, q),
            _sds((b * h_kv, s, d_v), v.dtype, q),
        ],
        scratch_shapes=[pltpu.VMEM((d, s), jnp.float32),
                        pltpu.VMEM((d_v, s), jnp.float32)],
        interpret=interpret,
        name="mx_flash_attention_bwd",
        **_vmem_params(s, max(d, d_v), 4, interpret, q.dtype.itemsize,
                       n_f32_streams=2),
    )(q.reshape(b * h, s, d), k.reshape(b * h_kv, s, d),
      v.reshape(b * h_kv, s, d_v), g.reshape(b * h, s, d_v),
      lse, delta.reshape(b * h, 1, s))
    return (dq.reshape(b, h, s, d), dk.reshape(b, h_kv, s, d),
            dv.reshape(b, h_kv, s, d_v))


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
    try:
        _blocks(s, block_q, block_k)
    except ValueError:
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
    folds the lse cotangent into its row term (`delta`, before the kernel)."""
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
        return out, lse.reshape(b, h, s)

    def fwd(q, k, v):
        out, lse = _flash_pallas_kept(q, k, v, causal, scale,
                                      interpret=interpret)
        return (out, lse.reshape(b, h, s)), (q, k, v, out, lse)

    def bwd(res, cotangents):
        g_o, g_lse = cotangents
        q, k, v, out, lse = res
        return _flash_pallas_bwd(q, k, v, out, lse, g_o, causal, scale,
                                 interpret=interpret, g_lse=g_lse)

    fn.defvjp(fwd, bwd)
    return fn(q, k, v)


def _flash_pallas_trainable(q, k, v, causal, scale, interpret=False,
                            block_q=None, block_k=None):
    """Pallas forward + Pallas recompute-based backward (FlashAttention-2
    style): the forward saves only O and the per-row logsumexp; the
    backward re-materializes softmax blocks from them in VMEM. Activation
    memory is O(B*H*S*D + B*H*S), never O(S^2) — the long-context
    training path. The two are declared as kept (`_flash_pallas_kept`): a
    rematerialised stage around this call runs the forward kernel once,
    and its backward is one kernel (`_flash_pallas_bwd`)."""

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
        return _flash_pallas_bwd(q, k, v, out, lse, g, causal, scale,
                                 interpret=interpret, block_q=block_q,
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
    kernels stream the SHARED kv blocks (no repeated copy; a group's query
    heads add into one dK/dV inside the backward kernel). block_q/block_k
    override `_auto_block`'s tiling.
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

