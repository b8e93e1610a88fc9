"""The chunked gated delta rule as Pallas TPU kernels, forward and backward.

`ops/lm.py::kda_chunked` is the algorithm and the reference; this is the
same work held in VMEM. One grid cell is a few chunks of one (batch, head):
the chunk axis is sequential and carries the float32 state (forward) or its
gradient (backward, chunks in reverse) in VMEM scratch. Per chunk, nothing
of size (C, C, dk) or (n_sub, C, dk) leaves the chip: the cumulative
log-decay, the pairwise decays inside `sub`-token blocks (directly) and
through each block's first row (as products), `am`, `bm`, the unit
lower-triangular inverse, `w`, `u`, the state read, the output and the
state update all live and die in one kernel call.

Precision is `kda_chunked`'s, cast for cast: products take the operands'
dtype (bf16 on the measured path) and accumulate in float32; the decay,
the cumulative sums, the pairwise exponents (every one <= 0), the
triangular inverse and the chunk state are float32. float32 operands
multiply at HIGHEST (the interpreter's tests).

The state is held transposed, (dv, dk): its per-channel decay is then a
row that broadcasts down the sublanes, and every product with it is one
the MXU takes without a transpose of the state.

The forward saves nothing but its inputs and the chunk-start states; the
backward rebuilds a chunk's inner quantities from them and carries dS.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .attention import _sds

_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST
SUB = 16

_NN = (((1,), (0,)), ((), ()))      # a @ b
_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_TN = (((0,), (0,)), ((), ()))      # a.T @ b


def _dot(a, b, dims=_NN, dtype=None):
    """Product with operands in `dtype` (None: as they are, which must be
    float32) and a float32 result; float32 operands multiply at HIGHEST."""
    if dtype is not None:
        a, b = a.astype(dtype), b.astype(dtype)
    prec = _HIGHEST if a.dtype == _F32 else None
    return jax.lax.dot_general(a, b, dims, precision=prec,
                               preferred_element_type=_F32)


class _Chunk:
    """What both kernels need of one chunk: index masks, the cumulative
    log-decay and the operands' float32 copies, staged in VMEM scratch so
    that one row can be read back and spread over a sub-block."""

    def __init__(self, q, k, g, rows, sub):
        # rows: VMEM scratch (3, C, dk) float32 for G, q, k
        c, dk = q.shape
        self.c, self.dk, self.sub, self.n_sub = c, dk, sub, c // sub
        self.rows = rows
        iota = functools.partial(jax.lax.broadcasted_iota, jnp.int32, (c, c))
        row, col = iota(0), iota(1)
        blk_row, blk_col = (sum(((x >= s * sub).astype(jnp.int32)
                                 for s in range(1, self.n_sub)),
                                start=jnp.zeros((c, c), jnp.int32))
                            for x in (row, col))
        self.row, self.col = row, col
        self.rel = col - sub * blk_row      # column inside the row's block
        self.same = blk_row == blk_col
        self.before = blk_col < blk_row
        self.lower = col <= row
        self.strict = col < row
        self.qf, self.kf = q.astype(_F32), k.astype(_F32)
        # inclusive cumulative sum down the chunk, as a product
        self.g_cum = _dot(self.lower.astype(_F32), g.astype(_F32))
        rows[0] = self.g_cum
        rows[1] = self.qf
        rows[2] = self.kf
        # decay from a sub-block's first row to each of its rows: <= 1
        self.row_decay = jnp.exp(self.g_cum - self.spread(0, 0))

    def spread(self, which, j):
        """(C, dk): in every sub-block, its row `j` of G (0), q (1) or
        k (2), repeated over the sub-block's rows."""
        sub = self.sub
        return jnp.concatenate(
            [jnp.broadcast_to(self.rows[which, s * sub + j:s * sub + j + 1,
                                        :], (sub, self.dk))
             for s in range(self.n_sub)], axis=0)

    def col_decay(self, i):
        """(C, dk): exp(G[first row of sub-block i] - G_j), clamped at 1:
        only columns before the sub-block are used (exponent <= 0)."""
        ref = self.rows[0, i * self.sub:i * self.sub + 1, :]
        return jnp.exp(jnp.minimum(ref - self.g_cum, 0.0))

    def column(self, m, j):
        """(C, 1): of a (C, C) matrix that is zero outside the diagonal
        sub-blocks, each row's entry in column `j` of its own block."""
        return jnp.sum(jnp.where(self.rel == j, m, 0.0), axis=1,
                       keepdims=True)


def _pairwise(ch, dtype):
    """(A0, B): A0[i, j] = sum_d k_i k_j exp(G_i - G_j) for j < i (not yet
    scaled by beta) and B[i, j] = sum_d q_i k_j exp(G_i - G_j), j <= i."""
    c, sub = ch.c, ch.sub
    acc_b = jnp.zeros((c, c), _F32)
    acc_a = jnp.zeros((c, c), _F32)
    # inside a sub-block: the decay of every pair, directly, in float32
    for j in range(sub):
        e = jnp.exp(jnp.minimum(ch.g_cum - ch.spread(0, j), 0.0))
        kk = ch.spread(2, j) * e
        pick = ch.rel == j
        acc_b = jnp.where(pick, jnp.sum(ch.qf * kk, axis=1, keepdims=True),
                          acc_b)
        acc_a = jnp.where(pick, jnp.sum(ch.kf * kk, axis=1, keepdims=True),
                          acc_a)
    b = jnp.where(ch.lower, acc_b, 0.0)
    a0 = jnp.where(ch.strict, acc_a, 0.0)
    if ch.n_sub > 1:
        # across sub-blocks: through the later block's first row
        q_row, k_row = ch.qf * ch.row_decay, ch.kf * ch.row_decay
        off_b = [jnp.zeros((sub, c), _F32)]
        off_a = [jnp.zeros((sub, c), _F32)]
        for i in range(1, ch.n_sub):
            lo, hi = i * sub, (i + 1) * sub
            lhs = jnp.concatenate([q_row[lo:hi], k_row[lo:hi]], axis=0)
            off = _dot(lhs, ch.kf * ch.col_decay(i), _NT, dtype)
            off_b.append(off[:sub])
            off_a.append(off[sub:])
        b = b + jnp.where(ch.before, jnp.concatenate(off_b, axis=0), 0.0)
        a0 = a0 + jnp.where(ch.before, jnp.concatenate(off_a, axis=0), 0.0)
    return a0, b


def _unit_lower_inverses(ch, mats):
    """(I + a)^-1 of each strictly lower-triangular a, float32: the
    Neumann series of the nilpotent -a by repeated squaring, as
    kda_chunked's. Two chunks go side by side where both fit the MXU's
    width: [p1 | p2] @ blockdiag(p1, p2) = [p1 p1 | p2 p2] is one product
    of C rows where two separate ones push 2 C."""
    c = ch.c
    eye = jnp.where(ch.row == ch.col, 1.0, 0.0)
    pairs = 2 * c <= 128 and len(mats) >= 2

    def series(x, eye, rhs):
        t, p, n = eye + x, x, 2
        while n < c:
            p = _dot(p, rhs(p))
            t = t + _dot(t, rhs(p))
            n *= 2
        return t

    if not pairs:
        return [series(-a, eye, lambda p: p) for a in mats]
    iota = functools.partial(jax.lax.broadcasted_iota, jnp.int32,
                             (2 * c, 2 * c))
    own = (iota(0) < c) ^ (iota(1) >= c)

    def blockdiag(p):
        return jnp.where(own, jnp.concatenate([p, p], axis=0), 0.0)

    out = []
    for i in range(0, len(mats) - 1, 2):
        t = series(-jnp.concatenate(mats[i:i + 2], axis=1),
                   jnp.concatenate([eye, eye], axis=1), blockdiag)
        out += [t[:, :c], t[:, c:]]
    if len(mats) % 2:
        out.append(series(-mats[-1], eye, lambda p: p))
    return out


def _inner(chunks, v_ref, b_ref, dtype):
    """Per chunk of the block, the inner quantities that no state enters,
    rounded where kda_chunked rounds."""
    pairwise = [_pairwise(ch, dtype) for _, ch in chunks]
    inverses = _unit_lower_inverses(
        chunks[0][1], [a0 * b_ref[at, :] for (at, _), (a0, _) in
                       zip(chunks, pairwise)])
    out = []
    for (at, ch), (a0, b), t in zip(chunks, pairwise, inverses):
        beta = b_ref[at, :]
        decay = jnp.exp(ch.g_cum)
        g_last = ch.rows[0, ch.c - 1:ch.c, :]                # (1, dk)
        kb = ch.kf * decay * beta
        vb = v_ref[at, :].astype(_F32) * beta
        rest = jnp.exp(g_last - ch.g_cum)                    # <= 1
        out.append(dict(
            a0=a0, b=b, t=t, decay=decay, kb=kb, vb=vb,
            decay_last=jnp.exp(g_last),
            w=_dot(t, kb, _NN, dtype).astype(dtype),
            u0=_dot(t, vb, _NN, dtype).astype(dtype),
            q_dec=ch.qf * decay, rest=rest, k_rest=ch.kf * rest))
    return out


def _corrections(x, state_t, dtype):
    """u (C, dv) of a chunk whose start state (transposed) is state_t."""
    return x["u0"].astype(_F32) - _dot(x["w"], state_t, _NT, dtype)


def _chunks(refs, rows, chunk, sub):
    """Per chunk of the grid cell's block: (row slice, _Chunk)."""
    q_ref, k_ref, g_ref = refs
    out = []
    for i in range(q_ref.shape[0] // chunk):
        at = slice(i * chunk, (i + 1) * chunk)
        out.append((at, _Chunk(q_ref[at, :], k_ref[at, :], g_ref[at, :],
                               rows.at[i], sub)))
    return out


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, *rest, chunk, sub,
                save_states):
    import jax.experimental.pallas as pl
    if save_states:
        s_ref, state, rows = rest
    else:
        state, rows = rest

    @pl.when(pl.program_id(1) == 0)
    def _():
        state[...] = jnp.zeros(state.shape, _F32)

    dtype = q_ref.dtype
    # the chunks of a block are independent up to their start states: their
    # inner stages interleave, only the last three products wait in line
    chunks = _chunks((q_ref, k_ref, g_ref), rows, chunk, sub)
    s = state[...]
    for i, ((at, _), x) in enumerate(zip(chunks, _inner(chunks, v_ref, b_ref,
                                                        dtype))):
        if save_states:
            s_ref[i] = s
        # w and q_dec read the same state: one product, its weights once
        on_state = _dot(jnp.concatenate([x["w"].astype(_F32), x["q_dec"]],
                                        axis=0), s, _NT, dtype)
        u = x["u0"].astype(_F32) - on_state[:chunk]
        o = on_state[chunk:] + _dot(x["b"], u, _NN, dtype)
        o_ref[at, :] = o.astype(o_ref.dtype)
        s = s * x["decay_last"] + _dot(u, x["k_rest"], _TN, dtype)
    state[...] = s


def _bwd_chunk(ch, x, u, vf, beta, do, s0, ds1, dtype):
    """One chunk's gradients (dq, dk, dv, dg, dbeta) and the gradient of
    its start state, from that of its end state."""
    c, n_sub, sub = ch.c, ch.n_sub, ch.sub
    mm = functools.partial(_dot, dtype=dtype)

    # the recurrence's products
    # (products that share a side go to the MXU stacked, as one)
    do = do.astype(_F32)
    du = mm(x["b"], do, _TN) + mm(x["k_rest"], ds1, _NT)       # (C, dv)
    ds0 = ds1 * x["decay_last"] + mm(
        jnp.concatenate([do, -du], axis=0),
        jnp.concatenate([x["q_dec"], x["w"].astype(_F32)], axis=0), _TN)
    d_b = jnp.where(ch.lower, mm(do, u, _NT), 0.0)             # (C, C)
    on_state = mm(jnp.concatenate([do, -du], axis=0), s0)      # (2 C, dk)
    d_qdec, d_w = on_state[:c], on_state[c:]
    d_krest = mm(u, ds1)                                       # (C, dk)
    d_glast = jnp.sum(ds1 * s0, axis=0, keepdims=True) * x["decay_last"] + \
        jnp.sum(d_krest * x["k_rest"], axis=0, keepdims=True)

    # w = T kb, u0 = T vb, T = (I + A)^-1, A = A0 * beta
    d_wu = jnp.concatenate([d_w, du], axis=1)                  # (C, dk + dv)
    d_kvb = mm(x["t"], d_wu, _TN)
    d_kb, d_vb = d_kvb[:, :ch.dk], d_kvb[:, ch.dk:]
    d_t = mm(d_wu, jnp.concatenate([x["kb"], x["vb"]], axis=1), _NT)
    d_a = -_dot(_dot(x["t"], d_t, _TN), x["t"], _NT)
    d_a = jnp.where(ch.strict, d_a, 0.0)
    d_beta = jnp.sum(d_a * x["a0"], axis=1, keepdims=True) + \
        jnp.sum(d_vb * vf, axis=1, keepdims=True) + \
        jnp.sum(d_kb * ch.kf * x["decay"], axis=1, keepdims=True)
    d_a0 = d_a * beta

    # the pairwise stage: terms that fall on a pair's row (i) and on its
    # column (j); dG_i takes operand_i * row term, dG_j minus operand_j *
    # column term. The reference rows' own gradients cancel exactly.
    acc_q = jnp.zeros((c, ch.dk), _F32)
    acc_k_row = jnp.zeros((c, ch.dk), _F32)
    acc_k_col = jnp.zeros((c, ch.dk), _F32)
    in_b = jnp.where(ch.same, d_b, 0.0)
    in_a = jnp.where(ch.same, d_a0, 0.0)
    in_bt, in_at = in_b.T, in_a.T
    for r in range(sub):
        # exp(-|G_i - G_r|): the decay from r down to the rows after it and
        # from the rows before it down to r; only the side a pair is on
        # has a non-zero entry in the matrices' columns
        diff = ch.g_cum - ch.spread(0, r)
        decay = jnp.exp(jnp.minimum(diff, -diff))
        k_dec, q_dec = ch.spread(2, r) * decay, ch.spread(1, r) * decay
        acc_q = acc_q + ch.column(in_b, r) * k_dec
        acc_k_row = acc_k_row + ch.column(in_a, r) * k_dec
        acc_k_col = acc_k_col + ch.column(in_bt, r) * q_dec + \
            ch.column(in_at, r) * k_dec
    if n_sub > 1:
        off_b = jnp.where(ch.before, d_b, 0.0)
        off_a = jnp.where(ch.before, d_a0, 0.0)
        q_row, k_row = ch.qf * ch.row_decay, ch.kf * ch.row_decay
        rows_q = [jnp.zeros((sub, ch.dk), _F32)]
        rows_k = [jnp.zeros((sub, ch.dk), _F32)]
        for i in range(1, n_sub):
            lo, hi = i * sub, (i + 1) * sub
            col = ch.col_decay(i)
            both = jnp.concatenate([off_b[lo:hi], off_a[lo:hi]], axis=0)
            on_rows = mm(both, ch.kf * col)                    # (2 sub, dk)
            rows_q.append(on_rows[:sub])
            rows_k.append(on_rows[sub:])
            acc_k_col = acc_k_col + col * mm(
                both, jnp.concatenate([q_row[lo:hi], k_row[lo:hi]], axis=0),
                _TN)
        acc_q = acc_q + ch.row_decay * jnp.concatenate(rows_q, axis=0)
        acc_k_row = acc_k_row + ch.row_decay * jnp.concatenate(rows_k,
                                                               axis=0)

    d_q = acc_q + d_qdec * x["decay"]
    d_k = acc_k_row + acc_k_col + d_kb * x["decay"] * beta + \
        d_krest * x["rest"]
    d_gcum = ch.qf * acc_q + ch.kf * (acc_k_row - acc_k_col) + \
        d_kb * x["kb"] + d_qdec * x["q_dec"] - d_krest * x["k_rest"]
    last = jax.lax.broadcasted_iota(jnp.int32, (c, ch.dk), 0) == c - 1
    d_gcum = d_gcum + jnp.where(last, d_glast, 0.0)
    # G is an inclusive cumulative sum: dg_t = sum of dG_i over i >= t
    d_g = _dot((ch.col >= ch.row).astype(_F32), d_gcum)
    return (d_q, d_k, d_vb * beta, d_g, d_beta), ds0


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, s_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, db_ref, dstate, rows, *,
                chunk, sub):
    import jax.experimental.pallas as pl

    @pl.when(pl.program_id(1) == 0)
    def _():
        dstate[...] = jnp.zeros(dstate.shape, _F32)

    dtype = q_ref.dtype
    # rebuilt from the inputs and the saved start states, every chunk of
    # the block on its own; only dS walks the chunks, last to first
    chunks = _chunks((q_ref, k_ref, g_ref), rows, chunk, sub)
    work = [(at, ch, x, _corrections(x, s_ref[i], dtype), i)
            for i, ((at, ch), x) in enumerate(zip(chunks, _inner(
                chunks, v_ref, b_ref, dtype)))]
    ds = dstate[...]
    for at, ch, x, u, i in reversed(work):
        grads, ds = _bwd_chunk(ch, x, u, v_ref[at, :].astype(_F32),
                               b_ref[at, :], do_ref[at, :], s_ref[i], ds,
                               dtype)
        for ref, value in zip((dq_ref, dk_ref, dv_ref, dg_ref, db_ref),
                              grads):
            ref[at, :] = value.astype(ref.dtype)
    dstate[...] = ds


def _layout(q, k, v, g, beta, chunk):
    """Operands as the kernels read them: (B, S', H*d) with S' a multiple
    of the chunk (zeros change neither the state nor the kept outputs) and
    beta as (B*H, S', 1)."""
    b, s, h, dk = q.shape
    pad = -s % chunk
    if pad:
        q, k, v, g = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                      for a in (q, k, v, g))
        beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))
    sp = s + pad
    flat = tuple(a.reshape(b, sp, -1) for a in (q, k, v, g.astype(_F32)))
    beta = jnp.transpose(beta.astype(_F32), (0, 2, 1)).reshape(b * h, sp, 1)
    return flat + (beta,), sp


# rows a grid cell: its chunks' inner stages are independent and interleave,
# which hides the latency of the chains of small products (at 2 x 8,192 x 32
# heads, 4 chunks of 64 a cell read 30% faster than 1, 8 read 5-7% faster
# still). The backward stays at 4: its unrolled body is the longer one, and
# at 8 its trace and lowering add 16 s to a warm start (PERF.md); 1,024 rows
# pass the 16 MB of VMEM a kernel gets
ROWS_PER_CELL = {False: 512, True: 256}      # forward, backward
CHUNKS = (64, 128)      # what Mosaic compiles today (32 does not: PERF.md)


def _per_cell(n, chunk, rows):
    return next(m for m in (8, 4, 2, 1)
                if m * chunk <= rows and n % m == 0)


def _specs(h, chunk, dk, dv, reverse, n):
    """Block specs over a grid (B*H, N / per): `per` chunks of one head a
    cell, the cells last to first where `reverse`."""
    import jax.experimental.pallas as pl
    per = _per_cell(n, chunk, ROWS_PER_CELL[reverse])
    cells = n // per

    def at(c):
        return cells - 1 - c if reverse else c

    def head(d):
        return pl.BlockSpec((None, per * chunk, d),
                            lambda bh, c: (bh // h, at(c), bh % h))

    beta = pl.BlockSpec((None, per * chunk, 1),
                        lambda bh, c: (bh, at(c), 0))
    state = pl.BlockSpec((None, per, dv, dk),
                         lambda bh, c: (bh, at(c), 0, 0))
    return cells, (head(dk), head(dv), beta, state), per


def _compiler_params(interpret, grid=("parallel", "arbitrary")):
    if interpret:
        return {}
    from jax.experimental.pallas import tpu as pltpu
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=grid)}


# Both calls are jitted by themselves: a model's layers then share one trace
# of each kernel and one lowering a program (the bodies are long unrolled
# programs: traced a call site, four KDA layers added 60 s to a warm start)
_STATIC = ("chunk", "sub", "interpret", "save_states")


def _under_scope(name):
    """Run a function under the scope `name`, where the benchmark's readers
    look for its kernels, forward and backward."""
    def wrap(f):
        @functools.wraps(f)
        def scoped(*args, **kwargs):
            with jax.named_scope(name):
                return f(*args, **kwargs)
        return scoped
    return wrap


@functools.partial(jax.jit, static_argnames=_STATIC)
@_under_scope("mx.kda.core")
def _forward(q, k, v, g, beta, chunk, sub, interpret, save_states):
    """o (B, S, H, dv) in q's dtype and, if asked, the chunk-start states
    (B*H, N, dv, dk) float32."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s, h, dk = q.shape
    dv = v.shape[-1]
    (qf, kf, vf, gf, bf), sp = _layout(q, k, v, g, beta, chunk)
    n = sp // chunk
    cells, (head_k, head_v, beta_spec, state_spec), per = _specs(
        h, chunk, dk, dv, False, n)
    out_specs = [head_v]
    out_shape = [_sds((b, sp, h * dv), q.dtype, q)]
    if save_states:
        out_specs.append(state_spec)
        out_shape.append(_sds((b * h, n, dv, dk), _F32, q))
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk, sub=sub,
                          save_states=save_states),
        grid=(b * h, cells),
        in_specs=[head_k, head_k, head_v, head_k, beta_spec],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((dv, dk), _F32),
                        pltpu.VMEM((per, 3, chunk, dk), _F32)],
        interpret=interpret, name="mx_kda_fwd",
        **_compiler_params(interpret),
    )(qf, kf, vf, gf, bf)
    o = out[0].reshape(b, sp, h, dv)[:, :s]
    return (o, out[1]) if save_states else (o, None)


@functools.partial(jax.jit, static_argnames=_STATIC[:3])
@_under_scope("mx.kda.core")
def _backward(q, k, v, g, beta, states, do, chunk, sub, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s, h, dk = q.shape
    dv = v.shape[-1]
    (qf, kf, vf, gf, bf), sp = _layout(q, k, v, g, beta, chunk)
    if sp != s:
        do = jnp.pad(do, ((0, 0), (0, sp - s), (0, 0), (0, 0)))
    dof = do.astype(q.dtype).reshape(b, sp, h * dv)
    n = sp // chunk
    cells, (head_k, head_v, beta_spec, state_spec), per = _specs(
        h, chunk, dk, dv, True, n)
    dq, dk_, dv_, dg, db = pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk, sub=sub),
        grid=(b * h, cells),
        in_specs=[head_k, head_k, head_v, head_k, beta_spec, state_spec,
                  head_v],
        out_specs=[head_k, head_k, head_v, head_k, beta_spec],
        out_shape=[_sds((b, sp, h * dk), q.dtype, q),
                   _sds((b, sp, h * dk), k.dtype, q),
                   _sds((b, sp, h * dv), v.dtype, q),
                   _sds((b, sp, h * dk), _F32, q),
                   _sds((b * h, sp, 1), _F32, q)],
        scratch_shapes=[pltpu.VMEM((dv, dk), _F32),
                        pltpu.VMEM((per, 3, chunk, dk), _F32)],
        interpret=interpret, name="mx_kda_bwd",
        **_compiler_params(interpret),
    )(qf, kf, vf, gf, bf, states, dof)
    db = jnp.transpose(db.reshape(b, h, sp), (0, 2, 1))
    return (dq.reshape(b, sp, h, dk)[:, :s], dk_.reshape(b, sp, h, dk)[:, :s],
            dv_.reshape(b, sp, h, dv)[:, :s],
            dg.reshape(b, sp, h, dk)[:, :s].astype(g.dtype),
            db[:, :s].astype(beta.dtype))


def eligible(dtype, dk, dv, chunk, platform=None, kernel=4):
    """The kernels take a program for a TPU whose operands are bf16, whose
    head widths are multiples of 128, whose chunk is one of CHUNKS and whose
    short convolution reaches back no further than the staged HALO."""
    return ((platform or jax.default_backend()) == "tpu"
            and dtype == jnp.bfloat16 and dk % 128 == 0 and dv % 128 == 0
            and chunk in CHUNKS and kernel - 1 <= HALO)


def kda_kernels(q, k, v, g, beta, chunk=64, interpret=False):
    """The gated delta rule through the kernels, under one custom VJP.
    Shapes as kda_chunked's; returns (B, S, H, dv) in q's dtype."""
    sub = min(SUB, chunk)

    @jax.custom_vjp
    def fn(q, k, v, g, beta):
        return _forward(q, k, v, g, beta, chunk, sub, interpret, False)[0]

    def fwd(q, k, v, g, beta):
        o, states = _forward(q, k, v, g, beta, chunk, sub, interpret, True)
        return o, (q, k, v, g, beta, states)

    def bwd(res, do):
        return _backward(*res, do, chunk, sub, interpret)

    fn.defvjp(fwd, bwd)
    return fn(q, k, v, g, beta)


# -- prepare: the core's operands from the mixer's projections ------------
#
# `ops/lm.py::kda_prepare` is the algorithm and the reference: a causal short
# convolution and SiLU on q, k and v, the L2 normalisation of q and k over each
# head, and the log-decay g = -exp(A_log[h]) * softplus(f + dt_bias). Here it
# is ONE pass over the (B, S, H*d) arrays as they arrive, forward, and one
# pass backward: a grid cell is PREP_ROWS tokens of one head's columns (the
# convolution is per channel, the normalisation per head, so heads are
# independent), staged as float32 in VMEM under the last rows of the block
# before it (an overlapping read), which the taps then reach with shifted
# loads. Everything between the bf16 operands and the bf16 results is float32
# (the XLA path multiplies and adds the convolution in the operands' dtype);
# g is float32 as there. The backward keeps nothing but the inputs: it
# rebuilds a block's forward, walks the row blocks last to first with the
# first rows of the later block's convolution gradient carried in VMEM, and
# leaves the gradients of the small parameters as per-(batch, channel) sums
# that XLA adds.

# rows a grid cell and rows worked on at a time (what the registers hold). On
# a v5e at 2 x 8,192 x 32 x 128 one head a cell beat two, four and eight, 512
# rows beat 128 and 256 (fewer halos and carries), and a tile of 64 beat 32
# (PERF.md, PR 29)
PREP_ROWS = 512
PREP_TILE = 64
HALO = 8                # rows staged before a block: a tap reaches back kw - 1
_HALO_BLOCK = 16        # rows of the overlapping read: a tile of bf16
L2_EPS = 1e-6           # lm._l2_normalize's


def _softplus(x):
    return jnp.maximum(x, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(x)))


def _stage(stage, x_ref, halo_ref, first, kept=lambda x, t0: x):
    """A block as float32 rows [HALO, HALO + rows) of its staging buffer,
    under the last HALO rows of the block before it: zeros before the
    sequence's start (`first`). `kept` selects rows past its end away."""
    import jax.experimental.pallas as pl
    rows, cols = x_ref.shape

    @pl.when(first)
    def _():
        stage[0:HALO, 0:cols] = jnp.zeros((HALO, cols), _F32)

    @pl.when(jnp.logical_not(first))
    def _():
        stage[0:HALO, 0:cols] = halo_ref[...].astype(_F32)[-HALO:]

    stage[HALO:HALO + rows, 0:cols] = kept(x_ref[...].astype(_F32), 0)


def _conv_gate(stage, t0, tile, w_ref):
    """For `tile` rows from t0 of a staged block: the rows each tap
    multiplies, the convolution y and sigmoid(y)."""
    kw, cols = w_ref.shape
    first = t0 + HALO - (kw - 1)
    taps = [stage[first + j:first + j + tile, 0:cols] for j in range(kw)]
    y = sum(w_ref[j:j + 1, :] * x for j, x in enumerate(taps))
    return taps, y, jax.nn.sigmoid(y)


def _tiles(rows):
    """(first row, rows, slice) of the tiles a block is worked in. They
    unroll: a shifted load needs an offset that Mosaic can see. A short
    sequence's one block may end in a shorter tile."""
    return [(t0, min(PREP_TILE, rows - t0), slice(t0, min(t0 + PREP_TILE,
                                                         rows)))
            for t0 in range(0, rows, PREP_TILE)]


def _prep_fwd_kernel(q_ref, k_ref, v_ref, f_ref, hq_ref, hk_ref, hv_ref,
                     wq_ref, wk_ref, wv_ref, a_ref, dt_ref,
                     qo_ref, ko_ref, vo_ref, g_ref, stage):
    import jax.experimental.pallas as pl
    first = pl.program_id(2) == 0
    rows, dk = q_ref.shape
    convs = ((q_ref, hq_ref, wq_ref, qo_ref, dk ** -0.5),
             (k_ref, hk_ref, wk_ref, ko_ref, 1.0),
             (v_ref, hv_ref, wv_ref, vo_ref, None))
    for i, (x_ref, h_ref, _, _, _) in enumerate(convs):
        _stage(stage.at[i], x_ref, h_ref, first)
    for t0, tile, at in _tiles(rows):
        for i, (_, _, w_ref, o_ref, norm) in enumerate(convs):
            _, y, gate = _conv_gate(stage.at[i], t0, tile, w_ref)
            s = y * gate
            if norm is not None:
                s = s * (jax.lax.rsqrt(jnp.sum(
                    s * s, axis=1, keepdims=True) + L2_EPS) * norm)
            o_ref[at, :] = s.astype(o_ref.dtype)
        x = f_ref[at, :].astype(_F32) + dt_ref[...]
        g_ref[at, :] = a_ref[...] * _softplus(x)


def _fold(x):
    """(tile, d) -> (8, d): the sum over groups of 8 rows, adds of whole
    registers; XLA adds the 8 that are left."""
    return functools.reduce(
        lambda a, b: a + b, [x[i:i + 8] for i in range(0, x.shape[0], 8)])


def _prep_bwd_kernel(dq_ref, dk_ref, dv_ref, dg_ref, q_ref, k_ref, v_ref,
                     f_ref, hq_ref, hk_ref, hv_ref, wq_ref, wk_ref, wv_ref,
                     a_ref, dt_ref, xq_ref, xk_ref, xv_ref, df_ref, gwq_ref,
                     gwk_ref, gwv_ref, gdt_ref, ga_ref, stage, dys, *, seq):
    import jax.experimental.pallas as pl
    cell = pl.program_id(2)                      # row blocks, last to first
    block = pl.num_programs(2) - 1 - cell
    rows, dk = q_ref.shape
    kw = wq_ref.shape[0]

    @pl.when(cell == 0)
    def _():
        for ref in (gwq_ref, gwk_ref, gwv_ref, gdt_ref, ga_ref):
            ref[...] = jnp.zeros(ref.shape, _F32)
        dys[:, rows:rows + HALO, :] = jnp.zeros((3, HALO, dys.shape[2]), _F32)

    # a last block that reaches past the sequence holds whatever the memory
    # held there: selected away wherever a sum over rows could take it in
    ragged = seq % rows != 0

    def kept(x, t0):
        if not ragged:
            return x
        row = block * rows + t0 + jax.lax.broadcasted_iota(
            jnp.int32, x.shape, 0)
        return jnp.where(row < seq, x, 0.0)

    convs = ((q_ref, hq_ref, wq_ref, dq_ref, xq_ref, gwq_ref, dk ** -0.5),
             (k_ref, hk_ref, wk_ref, dk_ref, xk_ref, gwk_ref, 1.0),
             (v_ref, hv_ref, wv_ref, dv_ref, xv_ref, gwv_ref, None))
    for i, (x_ref, h_ref, *_) in enumerate(convs):
        _stage(stage.at[i], x_ref, h_ref, block == 0, kept)
    # dy, the gradient of every convolution's result, and with it the sums
    # for the taps; the decay's whole backward
    for t0, tile, at in _tiles(rows):
        for i, (_, _, w_ref, do_ref, _, gw_ref, norm) in enumerate(convs):
            cols = w_ref.shape[1]
            taps, y, gate = _conv_gate(stage.at[i], t0, tile, w_ref)
            ds = kept(do_ref[at, :].astype(_F32), t0)
            if norm is not None:
                s = y * gate
                r = jax.lax.rsqrt(jnp.sum(s * s, axis=1, keepdims=True)
                                  + L2_EPS)
                m = jnp.sum(ds * s, axis=1, keepdims=True)
                ds = (r * norm) * (ds - s * (r * r * m))
            dy = ds * (gate * (1.0 + y * (1.0 - gate)))
            dys[i, at, 0:cols] = dy
            for j, x in enumerate(taps):
                gw_ref[8 * j:8 * j + 8, :] += _fold(dy * x)
        x = kept(f_ref[at, :].astype(_F32), t0) + dt_ref[...]
        scaled = kept(dg_ref[at, :], t0) * a_ref[...]
        dx = scaled * jax.nn.sigmoid(x)
        df_ref[at, :] = dx.astype(df_ref.dtype)
        gdt_ref[...] += _fold(dx)
        ga_ref[...] += _fold(scaled * _softplus(x))
    # dx[t] = sum_j w_j dy[t + kw - 1 - j]: the rows after a block's last
    # are the first of the block after it
    for t0, tile, at in _tiles(rows):
        for i, (_, _, w_ref, _, dx_ref, _, _) in enumerate(convs):
            cols = w_ref.shape[1]
            dx = sum(w_ref[j:j + 1, :] *
                     dys[i, t0 + kw - 1 - j:t0 + kw - 1 - j + tile, 0:cols]
                     for j in range(kw))
            dx_ref[at, :] = dx.astype(dx_ref.dtype)
    dys[:, rows:rows + HALO, :] = dys[:, 0:HALO, :]


def _prep_specs(s, kw, reverse):
    """(rows a cell, row blocks, block specs by head width) over a grid
    (B, H, row blocks), the row blocks last to first where `reverse`."""
    import jax.experimental.pallas as pl
    rows = min(PREP_ROWS, -(-s // _HALO_BLOCK) * _HALO_BLOCK)
    cells = -(-s // rows)

    def at(r):
        return cells - 1 - r if reverse else r

    def block(d):
        return pl.BlockSpec((None, rows, d), lambda b, h, r: (b, at(r), h))

    def halo(d):        # the _HALO_BLOCK rows that end where the block starts
        per = rows // _HALO_BLOCK
        return pl.BlockSpec(
            (None, _HALO_BLOCK, d),
            lambda b, h, r: (b, jnp.maximum(at(r) * per - 1, 0), h))

    def shared(n, d):   # a parameter's rows: taps (kw, C), a and dt (1, C)
        return pl.BlockSpec((n, d), lambda b, h, r: (0, h))

    def sums(n, d):     # per (batch, channel) sums, resident over the rows
        return pl.BlockSpec((None, n, d), lambda b, h, r: (b, 0, h))

    return rows, cells, (block, halo, functools.partial(shared, kw), shared,
                         sums)


def _prep_params(dtype, convs, a_log, dt_bias, dk):
    """The parameters as the kernels read them: taps (kw, C) float32 of the
    values the XLA path multiplies with (rounded to the operands' dtype),
    a = -exp(A_log) spread over its head's channels and dt_bias, (1, C)."""
    taps = tuple(w.astype(dtype).astype(_F32).T for w in convs)
    a = jnp.repeat(-jnp.exp(a_log.astype(_F32)), dk)[None, :]
    return taps, a, dt_bias.astype(_F32)[None, :]


_PREP_GRID = ("parallel", "parallel", "arbitrary")   # batch, head, rows


# jitted by themselves for the core kernels' reason: the tiles unroll
@functools.partial(jax.jit, static_argnames=("num_heads", "interpret"))
@_under_scope("mx.kda.prepare")
def _prepare_forward(q, k, v, f, conv_q, conv_k, conv_v, a_log, dt_bias,
                     num_heads, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s, c = q.shape
    dk, dv = c // num_heads, v.shape[-1] // num_heads
    kw = conv_q.shape[1]
    taps, a, dt = _prep_params(q.dtype, (conv_q, conv_k, conv_v), a_log,
                               dt_bias, dk)
    rows, cells, (block, halo, tap, shared, _) = _prep_specs(s, kw, False)
    return pl.pallas_call(
        _prep_fwd_kernel,
        grid=(b, num_heads, cells),
        in_specs=[block(dk), block(dk), block(dv), block(dk),
                  halo(dk), halo(dk), halo(dv), tap(dk), tap(dk), tap(dv),
                  shared(1, dk), shared(1, dk)],
        out_specs=[block(dk), block(dk), block(dv), block(dk)],
        out_shape=[_sds(q.shape, q.dtype, q), _sds(k.shape, k.dtype, q),
                   _sds(v.shape, v.dtype, q), _sds(q.shape, _F32, q)],
        scratch_shapes=[pltpu.VMEM((3, HALO + rows, max(dk, dv)), _F32)],
        interpret=interpret, name="mx_kdaprep_fwd",
        **_compiler_params(interpret, _PREP_GRID),
    )(q, k, v, f, q, k, v, *taps, a, dt)


@functools.partial(jax.jit, static_argnames=("num_heads", "interpret"))
@_under_scope("mx.kda.prepare")
def _prepare_backward(q, k, v, f, conv_q, conv_k, conv_v, a_log, dt_bias,
                      dq, dk_, dv_, dg, num_heads, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s, c = q.shape
    dk, dv = c // num_heads, v.shape[-1] // num_heads
    kw = conv_q.shape[1]
    convs = (conv_q, conv_k, conv_v)
    taps, a, dt = _prep_params(q.dtype, convs, a_log, dt_bias, dk)
    rows, cells, (block, halo, tap, shared, sums) = _prep_specs(s, kw, True)
    *dx, gwq, gwk, gwv, gdt, ga = pl.pallas_call(
        functools.partial(_prep_bwd_kernel, seq=s),
        grid=(b, num_heads, cells),
        in_specs=[block(dk), block(dk), block(dv), block(dk),
                  block(dk), block(dk), block(dv), block(dk),
                  halo(dk), halo(dk), halo(dv), tap(dk), tap(dk), tap(dv),
                  shared(1, dk), shared(1, dk)],
        out_specs=[block(dk), block(dk), block(dv), block(dk),
                   sums(8 * kw, dk), sums(8 * kw, dk), sums(8 * kw, dv),
                   sums(8, dk), sums(8, dk)],
        out_shape=[_sds(q.shape, q.dtype, q), _sds(k.shape, k.dtype, q),
                   _sds(v.shape, v.dtype, q), _sds(f.shape, f.dtype, q)] +
        [_sds((b, 8 * kw, x.shape[-1]), _F32, q) for x in (q, k, v)] +
        [_sds((b, 8, c), _F32, q)] * 2,
        scratch_shapes=[pltpu.VMEM((3, HALO + rows, max(dk, dv)), _F32)] * 2,
        interpret=interpret, name="mx_kdaprep_bwd",
        **_compiler_params(interpret, _PREP_GRID),
    )(dq.astype(q.dtype), dk_.astype(k.dtype), dv_.astype(v.dtype),
      dg.astype(_F32), q, k, v, f, q, k, v, *taps, a, dt)
    gw = tuple(g.reshape(b, kw, 8, -1).sum((0, 2)).T.astype(w.dtype)
               for g, w in zip((gwq, gwk, gwv), convs))
    ga = ga.sum((0, 1)).reshape(num_heads, dk).sum(-1).astype(a_log.dtype)
    return (*dx, *gw, ga, gdt.sum((0, 1)).astype(dt_bias.dtype))


def prepare_kernels(q, k, v, f, conv_q, conv_k, conv_v, a_log, dt_bias,
                    num_heads, interpret=False):
    """(q, k, v, g) as the core takes them, each (B, S, H*d): `lm.kda_prepare`
    through the kernels, under one custom VJP that keeps its inputs."""
    @jax.custom_vjp
    def fn(*args):
        return tuple(_prepare_forward(*args, num_heads, interpret))

    def fwd(*args):
        return fn(*args), args

    def bwd(args, grads):
        return _prepare_backward(*args, *grads, num_heads, interpret)

    fn.defvjp(fwd, bwd)
    return fn(q, k, v, f, conv_q, conv_k, conv_v, a_log, dt_bias)
