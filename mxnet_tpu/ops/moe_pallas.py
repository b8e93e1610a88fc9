"""The held experts' grouped products as Pallas TPU kernels.

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


def _params(interpret):
    if interpret:
        return {}
    from jax.experimental.pallas import tpu as pltpu
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT)}


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
