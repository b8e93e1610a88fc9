"""The held experts' grouped products, and the sum of a token's rows of
them, as Pallas TPU kernels.

`ops/lm.py::_swiglu_experts` over `jax.lax.ragged_dot` is the algorithm and
the reference: rows of x (M, D) sorted by expert, `group_sizes` (E,) rows an
expert, weights (E, D, W) / (E, W, D); out = (silu(x W_gate) * x W_up) W_down
expert by expert. Here the same work goes through two kernels:

  mx_moe_gmm   rows (M, K) x weights (E, K, N) -> (M, N). A grid cell is one
               VISIT: a tile of `tm` rows under one expert's (K, tn) block,
               the whole contraction in one product. Which tile, which expert
               and which of the tile's rows are the expert's come from
               `group_sizes` through scalar prefetch (`_schedule`, a few XLA
               operations on the load); a tile that straddles a boundary is
               visited once an expert, each visit writing its own rows. Row
               tiles past the last pair are not computed: in a result that
               leaves the kernels (`out`, x's gradient) they are visited to
               be written as zeros, with the operands' block indices left
               where they were, so nothing is fetched for them. The cost
               follows the pairs that arrived, and no row of such a result
               is "whatever the memory held". One body serves x W_gate and
               x W_up in one pass (both accumulators in VMEM, silu(g) * u
               rounded once to the operands' type in the epilogue),
               hidden W_down, and, with the weights' last two dims swapped
               in the index map, the gradients with respect to rows:
               hidden's, taken through silu(g) * u in the epilogue, and
               x's, the sum of two products.
  mx_moe_tgmm  the weights' gradients, rows (M, K)^T x rows (M, N) ->
               (E, K, N): an expert's block is accumulated in float32 over
               its own row tiles only and written once; an expert without
               rows is visited once, to be written as zeros.

The roundings are the reference's: every product accumulates in float32
whatever the operands' type; `hidden` is rounded to the operands' type once;
`out` leaves in float32; a cotangent enters a product in the operands' type
(what the chip's default precision does to a float32 operand of
`ragged_dot`), and a gradient is rounded where autodiff's transpose rounds it
to its primal's type. float32 operands multiply at the precision the trace's
`jax.default_matmul_precision` asks, as `ragged_dot`'s do.

Beside them `mx_moe_combine` (`gather_sum`) sums each token's computed rows,
of the products' result with the routing weights (the combine) or of x's
gradient without (the dispatch's transpose); `lm._sum_computed` over
`lm._rows_of_pairs` is its algorithm and reference, to the bit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .attention import _sds
from .kda_pallas import _under_scope

_F32 = jnp.float32
SCOPE = "mx.moe.experts.matmul"

_NN = (((1,), (0,)), ((), ()))      # a @ b
_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_TN = (((0,), (0,)), ((), ()))      # a.T @ b

# rows a visit: an expert's balanced share of the rows (rows / held) holds at
# least ROW_TILES_PER_EXPERT of them, between MIN_ROWS (an MXU pass) and
# MAX_ROWS; columns a visit: the widest multiple of LANES that divides the
# width, is no wider than MAX_COLS and keeps the visit's blocks inside
# VMEM_BUDGET (PERF.md, PR 35: the tilings measured on a v5e)
LANES = 128
ROW_TILES_PER_EXPERT = 16
MIN_ROWS, MAX_ROWS = 128, 512
MAX_COLS = 1024
VMEM_BUDGET = 40 * 2 ** 20
VMEM_LIMIT = 100 * 2 ** 20


def row_tile(rows, held):
    """Rows a visit, from static shapes: a power of two that divides `rows`,
    MIN_ROWS..MAX_ROWS where the rows allow (8,192 rows an expert -> 512;
    3,072 and 2,048 -> 128)."""
    want = max(MIN_ROWS, min(MAX_ROWS, rows // max(1, held)
                             // ROW_TILES_PER_EXPERT))
    tm = 1
    while tm * 2 <= want and rows % (tm * 2) == 0:
        tm *= 2
    return tm


def col_tile(n, per_col_bytes, fixed_bytes=0):
    """Columns a visit: `n` itself where LANES does not divide it (toy
    widths, the interpreter), else the widest multiple of LANES dividing `n`
    within MAX_COLS and the VMEM budget (`per_col_bytes` a column of the
    visit's blocks, `fixed_bytes` beside them)."""
    if n % LANES:
        return n
    fits = [t for t in range(LANES, min(n, MAX_COLS) + 1, LANES)
            if n % t == 0 and fixed_bytes + t * per_col_bytes <= VMEM_BUDGET]
    return fits[-1] if fits else LANES


def eligible(dtype, k, n, rows, held, platform=None):
    """The kernels take a program for a TPU whose operands are bf16 or
    float32, whose two widths are multiples of LANES and whose rows are
    whole tiles of at least MIN_ROWS."""
    return ((platform or jax.default_backend()) == "tpu"
            and dtype in (jnp.bfloat16, jnp.float32)
            and k % LANES == 0 and n % LANES == 0 and held >= 1
            and rows % MIN_ROWS == 0 and row_tile(rows, held) >= MIN_ROWS)


# -- which visit works on what ----------------------------------------------------

def _schedule(group_sizes, m, tm, every_group=False, zero_tail=False):
    """The visits of a grid of static length tiles + E - 1, int32 (V,) each:

      group   the expert whose weights (gmm) or whose gradient block (tgmm)
              the visit holds
      rows    the row tile it reads
      out     the row tile it writes (gmm)
      lo, hi  its rows of that tile, [lo, hi); lo == hi: nothing to compute
      first   1 on the first visit of its output block (a row tile in gmm,
              an expert in tgmm): what the block held does not count
      last    1 on the last visit of its expert (tgmm: write the block)

    A non-empty expert visits every tile it has a row in, in order. With
    `every_group` (tgmm) an empty expert gets one visit, to write zeros.
    With `zero_tail` (a gmm whose result leaves the kernels) the visits
    after the last pair walk the row tiles no expert reaches, each once and
    `first`, to write zeros; without it those tiles stay unwritten (a
    result that only these kernels read: they never visit them). What is
    left of the grid repeats the last block and does nothing."""
    e = group_sizes.shape[0]
    tiles = m // tm
    n_visits = tiles + e - 1
    sizes = group_sizes.astype(jnp.int32)
    upto_row = jnp.cumsum(sizes)
    ends = jnp.minimum(upto_row, m)
    starts = jnp.minimum(upto_row - sizes, m)
    sizes = ends - starts
    per_group = jnp.where(sizes > 0, -(-ends // tm) - starts // tm,
                          1 if every_group else 0)
    upto = jnp.cumsum(per_group)                     # visits through group g
    valid = upto[-1]
    v = jnp.arange(n_visits, dtype=jnp.int32)
    real = v < valid
    at = jnp.clip(jnp.minimum(v, valid - 1), 0)      # the visit a spare copies
    group = jnp.minimum(
        jnp.sum(at[:, None] >= upto[None, :], axis=1), e - 1).astype(jnp.int32)
    nth = at - (upto - per_group)[group]             # the group's nth visit
    rows = jnp.clip(starts[group] // tm + nth, 0, tiles - 1)
    lo = jnp.clip(starts[group] - rows * tm, 0, tm)
    hi = jnp.clip(ends[group] - rows * tm, 0, tm)
    lo, hi = jnp.where(real, lo, 0), jnp.where(real, hi, 0)
    out = group if every_group else rows
    if zero_tail:
        reached = -(-ends[-1] // tm)                 # tiles that hold a pair
        out = jnp.where(real, rows,
                        jnp.minimum(reached + v - valid, tiles - 1))
    edge = jnp.full((1,), -1, out.dtype)             # no block has this index
    first = out != jnp.concatenate([edge, out[:-1]])
    last = real & ((out != jnp.concatenate([out[1:], edge]))
                   | (v == valid - 1))
    return tuple(a.astype(jnp.int32)
                 for a in (group, rows, out, lo, hi, first, last))


@functools.partial(jax.jit, static_argnames=("m", "tm"))
@_under_scope(SCOPE)
def _schedules(group_sizes, m, tm):
    """The three schedules of a grouped SwiGLU: of a result that leaves the
    kernels, of one that stays among them, of the weights' gradients. Jitted
    by itself: their few dozen small operations, traced where they stand,
    cost a layer more than its kernels' calls do (0.3 s a trace of a layer
    against 0.1: PERF.md, PR 35), and a step program is traced twice."""
    return (_schedule(group_sizes, m, tm, zero_tail=True),
            _schedule(group_sizes, m, tm),
            _schedule(group_sizes, m, tm, every_group=True))


def _row_mask(lo, hi, shape):
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    return (row >= lo) & (row < hi)


def _params(interpret, semantics=("parallel", "arbitrary")):
    if interpret:
        return {}
    from jax.experimental.pallas import tpu as pltpu
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=semantics, vmem_limit_bytes=VMEM_LIMIT)}


# -- mx_moe_gmm -------------------------------------------------------------------

def _swiglu(g, u):
    return jax.nn.silu(g) * u


def _epilogue(kind, parts, extras, dtype):
    """What a visit writes, from its float32 products (and, 'swiglu_grad',
    the forward's two products beside them)."""
    if kind == "swiglu":            # hidden, rounded once
        return [_swiglu(*parts).astype(dtype)]
    if kind == "swiglu_parts":      # and the two products, for the backward
        return [_swiglu(*parts).astype(dtype)] + list(parts)
    if kind == "swiglu_grad":       # hidden's gradient, rounded, through it
        dhidden = parts[0].astype(dtype).astype(_F32)
        return [d.astype(dtype) for d in jax.vjp(_swiglu, *extras)[1](dhidden)]
    if kind == "sum":               # two gradients, each rounded, then added
        a, b = (p.astype(dtype).astype(_F32) for p in parts)
        return [(a + b).astype(dtype)]
    return [parts[0].astype(dtype)]


def _gmm_kernel(sched, *refs, n_lhs, n_products, n_extras, transpose, kind,
                tm):
    import jax.experimental.pallas as pl
    _, _, _, lo_ref, hi_ref, first_ref, _ = sched
    lhs = refs[:n_lhs] * (n_products // n_lhs)
    rhs = refs[n_lhs:n_lhs + n_products]
    extras = refs[n_lhs + n_products:n_lhs + n_products + n_extras]
    outs = refs[n_lhs + n_products + n_extras:]
    v = pl.program_id(1)
    lo, hi, first = lo_ref[v], hi_ref[v], first_ref[v] == 1
    dims = _NT if transpose else _NN

    @pl.when(hi > lo)
    def _():
        parts = [jax.lax.dot_general(a[...], b[...], dims,
                                     preferred_element_type=_F32)
                 for a, b in zip(lhs, rhs)]
        vals = _epilogue(kind, parts, [x[...] for x in extras],
                         outs[0].dtype)
        whole = (lo == 0) & (hi == tm)

        @pl.when(whole)
        def _():
            for o, val in zip(outs, vals):
                o[...] = val.astype(o.dtype)

        # a tile shared by experts (or ending in rows of no expert): this
        # visit's rows, over zeros on the tile's first visit and over what
        # the earlier visits wrote after it
        @pl.when(jnp.logical_not(whole) & first)
        def _():
            for o, val in zip(outs, vals):
                o[...] = jnp.where(_row_mask(lo, hi, val.shape),
                                   val.astype(o.dtype), jnp.zeros_like(o))

        @pl.when(jnp.logical_not(whole) & jnp.logical_not(first))
        def _():
            for o, val in zip(outs, vals):
                o[...] = jnp.where(_row_mask(lo, hi, val.shape),
                                   val.astype(o.dtype), o[...])

    @pl.when((hi <= lo) & first)
    def _():
        for o in outs:
            o[...] = jnp.zeros_like(o)


@functools.partial(jax.jit, static_argnames=(
    "tm", "transpose", "kind", "out_dtype", "interpret"))
@_under_scope(SCOPE)
def _gmm(lhs, rhs, sched, tm, extras=(), transpose=False, kind="plain",
         out_dtype=None, interpret=False):
    """lhs: the rows (M, K), or a tuple of two of them (`kind` 'sum');
    rhs: a tuple of weights (E, K, N), (E, N, K) where `transpose`; sched:
    `_schedule`'s visits of the row tiles; extras: (M, N) arrays the
    epilogue reads ('swiglu_grad': the forward's two float32 products).
    Returns a tuple: (M, N) in `out_dtype` ('swiglu_grad': two of them, the
    gradients of the two products) and, for 'swiglu_parts', the two float32
    products after it."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    lhs = lhs if isinstance(lhs, tuple) else (lhs,)
    m, k = lhs[0].shape
    n = rhs[0].shape[1 if transpose else 2]
    out_dtype = out_dtype or lhs[0].dtype
    size = lhs[0].dtype.itemsize
    n_out = 2 if kind == "swiglu_grad" else 1
    n_out_f32 = 2 if kind == "swiglu_parts" else 0
    # a column of the visit's blocks: the weights', the results' and the
    # extras' two buffers each, the float32 products
    tn = col_tile(n, 2 * len(rhs) * k * size + 4 * tm * len(rhs) + 2 * tm * (
        n_out * jnp.dtype(out_dtype).itemsize + 4 * n_out_f32
        + 4 * len(extras)), 2 * len(lhs) * tm * k * size)

    rows = pl.BlockSpec((tm, k), lambda j, v, s: (s[1][v], 0))
    if transpose:
        weight = pl.BlockSpec((None, tn, k), lambda j, v, s: (s[0][v], j, 0))
    else:
        weight = pl.BlockSpec((None, k, tn), lambda j, v, s: (s[0][v], 0, j))
    beside = pl.BlockSpec((tm, tn), lambda j, v, s: (s[1][v], j))
    written = pl.BlockSpec((tm, tn), lambda j, v, s: (s[2][v], j))
    out_shape = [_sds((m, n), out_dtype, lhs[0])] * n_out + \
        [_sds((m, n), _F32, lhs[0])] * n_out_f32
    return tuple(pl.pallas_call(
        functools.partial(_gmm_kernel, n_lhs=len(lhs), n_products=len(rhs),
                          n_extras=len(extras), transpose=transpose,
                          kind=kind, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(n // tn, len(sched[0])),
            in_specs=[rows] * len(lhs) + [weight] * len(rhs)
            + [beside] * len(extras),
            out_specs=[written] * len(out_shape)),
        out_shape=out_shape, interpret=interpret, name="mx_moe_gmm",
        **_params(interpret),
    )(sched, *lhs, *rhs, *extras))


# -- mx_moe_tgmm ------------------------------------------------------------------

def _tgmm_kernel(sched, lhs_ref, *refs, n_products, tm):
    import jax.experimental.pallas as pl
    _, _, _, lo_ref, hi_ref, first_ref, last_ref = sched
    rhs = refs[:n_products]
    outs = refs[n_products:2 * n_products]
    accs = refs[2 * n_products:]
    v = pl.program_id(1)
    lo, hi = lo_ref[v], hi_ref[v]

    @pl.when(first_ref[v] == 1)
    def _():
        for acc in accs:
            acc[...] = jnp.zeros_like(acc)

    def add(a, bs):
        for acc, b in zip(accs, bs):
            acc[...] += jax.lax.dot_general(a, b, _TN,
                                            preferred_element_type=_F32)

    whole = (lo == 0) & (hi == tm)

    @pl.when(whole)
    def _():
        add(lhs_ref[...], [b[...] for b in rhs])

    # the rows of other experts, and of none, SELECTED away on both sides
    @pl.when((hi > lo) & jnp.logical_not(whole))
    def _():
        def mine(ref):
            x = ref[...]
            return jnp.where(_row_mask(lo, hi, x.shape), x.astype(_F32),
                             0.0).astype(x.dtype)
        add(mine(lhs_ref), [mine(b) for b in rhs])

    @pl.when(last_ref[v] == 1)
    def _():
        for o, acc in zip(outs, accs):
            o[...] = acc[...].astype(o.dtype)


@functools.partial(jax.jit, static_argnames=("tm", "out_dtype", "interpret"))
@_under_scope(SCOPE)
def _tgmm(lhs, rhs, sched, tm, out_dtype=None, interpret=False):
    """lhs (M, K); rhs a tuple of (M, N); sched: `_schedule`'s visits of
    the experts. Returns a tuple of (E, K, N) in `out_dtype`, expert e's
    block the sum over its rows of lhs^T rhs."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = lhs.shape
    n = rhs[0].shape[1]
    e = len(sched[0]) - m // tm + 1      # `_schedule`: tiles + E - 1 visits
    out_dtype = out_dtype or lhs.dtype
    size = lhs.dtype.itemsize
    # a column: the rows' two buffers, the accumulator, the block's two
    tn = col_tile(n, len(rhs) * (2 * tm * size + k * 4 + 2 * k * jnp.dtype(
        out_dtype).itemsize), 2 * tm * k * size)
    return tuple(pl.pallas_call(
        functools.partial(_tgmm_kernel, n_products=len(rhs), tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(n // tn, len(sched[0])),
            in_specs=[pl.BlockSpec((tm, k), lambda j, v, s: (s[1][v], 0))] +
            [pl.BlockSpec((tm, tn), lambda j, v, s: (s[1][v], j))] * len(rhs),
            out_specs=[pl.BlockSpec(
                (None, k, tn), lambda j, v, s: (s[2][v], 0, j))] * len(rhs),
            scratch_shapes=[pltpu.VMEM((k, tn), _F32)] * len(rhs)),
        out_shape=[_sds((e, k, n), out_dtype, lhs)] * len(rhs),
        interpret=interpret, name="mx_moe_tgmm", **_params(interpret),
    )(sched, lhs, *rhs))


# -- the grouped SwiGLU under one custom VJP --------------------------------------

def swiglu_experts(x, w_gate, w_up, w_down, group_sizes, interpret=False):
    """`lm._swiglu_experts` through the kernels: x (M, D) sorted by expert,
    weights (E, D, W) / (E, W, D), group_sizes (E,) -> (M, D) float32; the
    rows past the last pair are zeros, here and in x's gradient. The
    backward keeps x, the two float32 products and `hidden`, as autodiff
    keeps them; what only the kernels read (those three, and the gradients
    of the two products) is left unwritten in the row tiles no expert
    reaches."""
    tm = row_tile(x.shape[0], w_gate.shape[0])
    gmm = functools.partial(_gmm, tm=tm, interpret=interpret)
    tgmm = functools.partial(_tgmm, tm=tm, interpret=interpret)

    @jax.custom_vjp
    def fn(x, w_gate, w_up, w_down, leaving, inner, by_expert):
        hidden, = gmm(x, (w_gate, w_up), inner, kind="swiglu")
        return gmm(hidden, (w_down,), leaving, out_dtype=_F32)[0]

    def fwd(x, w_gate, w_up, w_down, leaving, inner, by_expert):
        hidden, g, u = gmm(x, (w_gate, w_up), inner, kind="swiglu_parts")
        out, = gmm(hidden, (w_down,), leaving, out_dtype=_F32)
        return out, (x, w_gate, w_up, w_down, leaving, inner, by_expert, g,
                     u, hidden)

    def bwd(res, dout):
        x, w_gate, w_up, w_down, leaving, inner, by_expert, g, u, hidden = res
        with jax.named_scope(SCOPE):
            dout = dout.astype(x.dtype)
        dg, du = gmm(dout, (w_down,), inner, extras=(g, u), transpose=True,
                     kind="swiglu_grad")
        dx, = gmm((dg, du), (w_gate, w_up), leaving, transpose=True,
                  kind="sum")
        dw_gate, dw_up = tgmm(x, (dg, du), by_expert, out_dtype=w_gate.dtype)
        dw_down, = tgmm(hidden, (dout,), by_expert, out_dtype=w_down.dtype)
        return dx, dw_gate, dw_up, dw_down, None, None, None

    fn.defvjp(fwd, bwd)
    return fn(x, w_gate, w_up, w_down,
              *_schedules(group_sizes, m=x.shape[0], tm=tm))


# -- mx_moe_combine: a token's computed rows, summed where they land ---------------
#
# `lm._dispatch` / `lm._combine` move rows between tokens (T, k pairs each,
# token-major) and the rows sorted by expert. The sort is stable, so inside
# one expert's rows the tokens ascend, and for a tile of consecutive tokens
# the rows of its computed pairs are ONE contiguous run of rows an expert.
# `mx_moe_combine` walks the token tiles: it copies each run from the rows in
# HBM into VMEM (whole blocks of CHUNK rows, the copies of the next tile in
# flight while this one is summed), then adds each computed pair's row,
# times its weight where there is one, into its token's float32 row in the
# order j = 0 .. k-1 of `lm._sum_computed`, and writes the tile rounded once:
# what `lm._sum_computed(lm._rows_of_pairs(...))` gives, to the bit, from the
# computed rows only (and at most a partial block at each end of a run).

COMBINE_TOKENS = 256            # tokens a grid step, at most
COMBINE_VMEM = 48 * 2 ** 20     # the runs' two buffers and the tile's rows
SMEM_BLOCK = 1024               # a 1-D SMEM block's entries come in these
CHUNK = 16                      # rows a copy: a bf16 (16, 128) tile's


def _combine_buffer_rows(tt, k, held):
    """Rows the runs of one token tile can take: a token's pairs are on
    distinct experts, and a run's copy adds at most a block at each end."""
    return tt * min(k, held) + 2 * CHUNK * held


def combine_tile(t, k, held, d, dtype):
    """Tokens a grid step: the largest power of two up to COMBINE_TOKENS
    that divides t and whose buffers fit COMBINE_VMEM; 0 if none of 16
    tokens or more does."""
    size = jnp.dtype(dtype).itemsize
    tt = COMBINE_TOKENS
    while tt >= 16:
        rows = _combine_buffer_rows(tt, k, held)
        vmem = rows * d * (2 * size + (4 if size < 4 else 0)) + 3 * tt * d * 4
        if t % tt == 0 and vmem <= COMBINE_VMEM:
            return tt
        tt //= 2
    return 0


def combine_eligible(d, rows, t, k, held, dtype, platform=None):
    """The kernel takes a program for a TPU whose rows (the products' `out`
    or x's gradient, bf16 or float32) are LANES-wide multiples and whole
    blocks of copies, over a token count some tile divides, with a
    token's pairs fewer than the bits of its int32 mask."""
    return ((platform or jax.default_backend()) == "tpu"
            and dtype in (jnp.bfloat16, jnp.float32)
            and d % LANES == 0 and rows % CHUNK == 0 and k < 31
            and combine_tile(t, k, held, d, dtype) > 0)


def _lanes_a_token(k):
    """A token's slots in the per-pair lists: k rounded up to a power of two,
    so that a tile's slots are whole SMEM blocks."""
    return 1 << (k - 1).bit_length()


def _smem_block(total, per_tile):
    """Entries of a 1-D SMEM block that holds a tile's `per_tile` of the
    `total`: Mosaic takes whole multiples of SMEM_BLOCK, or the whole
    array."""
    if per_tile % SMEM_BLOCK == 0:
        return per_tile
    if SMEM_BLOCK % per_tile == 0 and total % SMEM_BLOCK == 0:
        return SMEM_BLOCK
    return total


@functools.partial(jax.jit, static_argnames=("tt",))
@_under_scope("mx.moe.experts")
def combine_plan(expert, inv, load, tt):
    """What the kernel reads besides the rows, from a pair's held expert
    (T, k) (`held` where it has none), its row `inv` (T, k) and the rows an
    expert (held,); elementwise work and reductions over the held experts,
    no sort and no gather. For each (token tile, expert): the run's first
    row (a whole block), its number of blocks and where it lands in the
    tile's buffer, int32 (tiles, held) each, for scalar prefetch; a
    token's computed pairs as a bit mask, int32 (T,); each pair's row in
    its tile's buffer, int32 (T * kp,) (-1 where not computed; kp slots a
    token, `_lanes_a_token`)."""
    t, k = inv.shape
    held = load.shape[0]
    tiles, per_tile = t // tt, tt * k
    ex = expert.reshape(tiles, per_tile)
    mine = ex[:, :, None] == jnp.arange(held, dtype=ex.dtype)
    count = jnp.sum(mine, axis=1, dtype=jnp.int32)              # (tiles, E)
    first = (jnp.cumsum(load) - load).astype(jnp.int32)
    start = first[None, :] + jnp.cumsum(count, axis=0) - count
    lo = start // CHUNK * CHUNK
    blocks = jnp.where(count > 0, -(-(start + count) // CHUNK) - lo // CHUNK,
                       0)
    at = (jnp.cumsum(blocks, axis=1) - blocks) * CHUNK           # in the buffer
    base = jnp.sum(jnp.where(mine, (at - lo)[:, None, :], 0), axis=2)
    computed = (ex < held).reshape(t, k)
    row = jnp.where(computed, (base + inv.reshape(tiles, per_tile)).reshape(
        t, k), -1)
    mask = jnp.sum(jnp.where(computed, 1 << jnp.arange(k, dtype=jnp.int32),
                             0), axis=1, dtype=jnp.int32)
    row = jnp.pad(row, ((0, 0), (0, _lanes_a_token(k) - k)),
                  constant_values=-1)
    return lo, blocks, at, mask, row.reshape(-1)


def _combine_kernel(lo_ref, blocks_ref, at_ref, mask_ref, row_ref, *refs,
                    held, tiles, tt, k, kp, blocks_of, weighted):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    if weighted:
        w_ref, rows_hbm, y_ref, buf, acc, sem, *wide = refs
    else:
        rows_hbm, y_ref, buf, acc, sem, *wide = refs
    i = pl.program_id(0)
    slot = i % 2

    def copies(tile, slot, start):
        """Start (or wait for) every block of a tile's runs."""
        for e in range(held):
            run = tile * held + e

            def one(b, carry, run=run):
                dma = pltpu.make_async_copy(
                    rows_hbm.at[pl.ds(pl.multiple_of(
                        lo_ref[run] + b * CHUNK, CHUNK), CHUNK)],
                    buf.at[slot, pl.ds(pl.multiple_of(
                        at_ref[run] + b * CHUNK, CHUNK), CHUNK)],
                    sem.at[slot])
                if start:
                    dma.start()
                else:
                    dma.wait()
                return carry
            jax.lax.fori_loop(0, blocks_ref[run], one, 0)

    @pl.when(i == 0)
    def _():
        copies(0, 0, True)

    @pl.when(i + 1 < tiles)
    def _():
        copies(i + 1, 1 - slot, True)

    copies(i, slot, False)
    rows = buf.at[slot]
    if wide:            # bf16 rows: widened a block at a time, then picked
        rows = wide[0]
        last = i * held + held - 1
        used = at_ref[last] // CHUNK + blocks_ref[last]

        def widen(b, carry):
            b0 = pl.multiple_of(b * CHUNK, CHUNK)
            wide_rows = buf[slot, pl.ds(b0, CHUNK), :].astype(_F32)
            rows[pl.ds(b0, CHUNK), :] = wide_rows  # analysis: allow=trace-state-mutation
            return carry
        jax.lax.fori_loop(0, used, widen, 0)
    acc[...] = jnp.zeros_like(acc)
    mask_at, row_at = ((i * n) % blocks_of[m] for m, n in
                       (("mask", tt), ("row", tt * kp)))

    def token(t, carry):
        m = mask_ref[mask_at + t]

        @pl.when(m != 0)
        def _():
            for j in range(k):          # in the order of `lm._sum_computed`
                @pl.when(((m >> j) & 1) == 1)
                def _(j=j):
                    q = row_at + t * kp + j
                    row = rows[pl.ds(row_ref[q], 1), :].astype(_F32)
                    if weighted:
                        row = w_ref[q] * row
                    row = acc[pl.ds(t, 1), :] + row
                    acc[pl.ds(t, 1), :] = row  # analysis: allow=trace-state-mutation
        return carry
    jax.lax.fori_loop(0, tt, token, 0)
    y_ref[...] = acc[...].astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("k", "out_dtype", "interpret"))
@_under_scope("mx.moe.experts")
def gather_sum(rows, plan, weight, k, out_dtype, interpret=False):
    """y (T, D) in `out_dtype`: for each token the sum over its computed
    pairs, in the order j = 0 .. k-1, of the pair's row of `rows` (R, D),
    times its weight where `weight` (T, k) float32 is given, in float32.
    `plan` is `combine_plan`'s; no row of `rows` outside the computed
    pairs' runs is read."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    lo, blocks, at, mask, row = plan
    d = rows.shape[1]
    t, kp = mask.shape[0], _lanes_a_token(k)
    tiles, held = lo.shape
    tt = t // tiles
    buffer_rows = _combine_buffer_rows(tt, k, held)
    weighted = weight is not None
    blocks_of = {"mask": _smem_block(t, tt), "row": _smem_block(t * kp,
                                                                tt * kp)}

    def smem(name, per_tile):
        size = blocks_of[name]
        return pl.BlockSpec((size,), lambda i, *_: (i * per_tile // size,),
                            memory_space=pltpu.SMEM)
    operands, specs = [mask, row], [smem("mask", tt), smem("row", tt * kp)]
    if weighted:
        operands.append(jnp.pad(weight, ((0, 0), (0, kp - k))).reshape(-1))
        specs.append(smem("row", tt * kp))
    scratch = [pltpu.VMEM((2, buffer_rows, d), rows.dtype),
               pltpu.VMEM((tt, d), _F32), pltpu.SemaphoreType.DMA((2,))]
    if rows.dtype != _F32:
        scratch.append(pltpu.VMEM((buffer_rows, d), _F32))
    return pl.pallas_call(
        functools.partial(_combine_kernel, held=held, tiles=tiles, tt=tt, k=k,
                          kp=kp, blocks_of=blocks_of, weighted=weighted),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(tiles,),
            in_specs=specs + [pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tt, d), lambda i, *_: (i, 0)),
            scratch_shapes=scratch),
        out_shape=_sds((t, d), out_dtype, rows), interpret=interpret,
        name="mx_moe_combine", **_params(interpret, ("arbitrary",)),
    )(*(a.reshape(-1) for a in (lo, blocks, at)), *operands, rows)
