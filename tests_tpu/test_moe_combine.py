"""On the chip, what moves rows to the held experts' grouped products and
their results back to the tokens, at the four language-model cells' shapes
(tokens T, experts a token k, rows `capacity`, hidden D, with the pairs of a
traced layer-step): a gather and a scatter-add combine (what the grouped
path ran where T * k exceeded its rows, before `mx_moe_combine`) against the
gathers both ways (`lm._dispatch` / `lm._combine`) with the rows summed by
XLA's gathers (`lm._rows_of_pairs` + `lm._sum_computed`, the CPU's path)
and by `mx_moe_combine` (`ops/moe_pallas.py`, the TPU's). Alone, the
products left out, forward and forward-and-backward, in milliseconds and as
a share of the memory's rate (819 GB/s) by the bytes each pass has to move,
and the inverse permutation by a scatter of int32 scalars against a second
argsort; the kernel alone in both directions against XLA's gathers, to the
bit; and in place, one mixture layer's value and gradients with the
kernels, the router and the forward's rerun, through each way of summing
(`chiprun_out/moe_combine.json`; PERF.md quotes them)."""
import functools
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.ops import lm

# T, k, capacity, D, pairs on held experts in a layer-step, held experts
SHAPES = {
    "lfm2_moe": (16384, 4, 65536, 2048, 30000, 8),
    "kanana2": (16384, 6, 49152, 2048, 13000, 16),
    "trinity_mini": (16384, 8, 32768, 2048, 6700, 8),
    "kimi_linear": (16384, 8, 16384, 2304, 4096, 8),
}
HBM_BYTES_PER_S = 819e9
OUT = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "chiprun_out", "moe_combine.json")


def _ms(fn, args, reps=20):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e3


def _record(**kv):
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    have = json.load(open(OUT)) if os.path.exists(OUT) else {}
    have.update(kv)
    with open(OUT, "w") as f:
        json.dump(have, f, indent=1)
    print(json.dumps(kv))


def _operands(shape, seed=0):
    """x (T, D) bf16, the products' result (capacity, D) float32, the
    weights (T, k) float32, each pair's expert (held ones first in a random
    n_pairs of the pairs), and the two cotangents."""
    t, k, cap, d, n_pairs, held = SHAPES[shape]
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (t, d), jnp.float32).astype(jnp.bfloat16)
    out = jax.random.normal(ks[1], (cap, d), jnp.float32)
    w = jax.random.uniform(ks[2], (t, k), jnp.float32)
    pick = jax.random.permutation(ks[3], t * k) < n_pairs
    key = jnp.where(pick, jax.random.randint(ks[4], (t * k,), 0, held),
                    held).astype(jnp.int32)
    drows = jax.random.normal(ks[5], (cap, d), jnp.float32).astype(
        jnp.bfloat16)
    dy = jax.random.normal(ks[0], (t, d), jnp.float32)
    return x, out, w, key, drows, dy


def _scatter_glue(k, cap, held):
    """The dispatch and combine as they were: a gather of x's rows, two
    selects and a scatter-add, with autodiff's transposes."""
    def glue(x, out, w, key):
        n_pairs = jnp.sum(key < held)
        order = jnp.argsort(key, stable=True)[:cap]
        token = order // k
        valid = (jnp.arange(cap) < n_pairs)[:, None]
        weight = w.reshape(-1)[order][:, None]
        rows = jnp.where(valid, jnp.take(x, token, axis=0), 0)
        y = jnp.zeros((x.shape[0], x.shape[1]), jnp.float32).at[token].add(
            jnp.where(valid, out, 0.0) * weight)
        return rows, y
    return glue


def _gather_glue(k, cap, held, force="xla"):
    """`moe_experts`'s grouped branch without its products, a token's rows
    summed by XLA's gathers ('xla') or by `mx_moe_combine` ('pallas')."""
    def glue(x, out, w, key):
        n_pairs = jnp.sum(key < held)
        order = jnp.argsort(key, stable=True)
        inv = jnp.argsort(order).astype(order.dtype).reshape(-1, k)
        load = jnp.bincount(key, length=held + 1)[:held].astype(jnp.int32)
        links = lm._combine_links(key.reshape(-1, k), inv, n_pairs, load, cap,
                                  x.shape[1], x.dtype, force, "tpu")
        rows = lm._dispatch(x, order[:cap], links, False)
        return rows, lm._combine(out, w, order[:cap], links, jnp.float32,
                                 False)
    return glue


WAYS = {"scatter": _scatter_glue, "gathers": _gather_glue,
        "kernel": lambda k, cap, held: _gather_glue(k, cap, held, "pallas")}


def _bytes(shape, way):
    """What a pass has to read and write, by its shapes: (forward,
    backward)."""
    t, k, cap, d, n_pairs, _ = SHAPES[shape]
    rows = cap * d * 2 * 2                      # x's rows read, rows written
    if way == "scatter":
        fwd = rows + cap * d * 4 + t * d * 4    # out read, y written
        bwd = (cap * d * 2 + t * d * 2          # dx: drows read, dx written
               + cap * d * 4 * 2                # dout: dy's rows, dout
               + cap * d * 4 * 2)               # dw: out and dy's rows
    elif way == "gathers":
        fwd = rows + t * k * d * 4 + t * d * 4  # k rows a token read
        bwd = (t * k * d * 2 + t * d * 2
               + cap * d * 4 * 2
               + t * k * d * 4 + t * d * 4)
    else:                                       # the computed rows only
        fwd = rows + n_pairs * d * 4 + t * d * 4
        bwd = (n_pairs * d * 2 + t * d * 2
               + cap * d * 4 * 2                # dout
               + cap * d * 4)                   # dw: out, beside dout
    return fwd, bwd


@pytest.mark.parametrize("shape", list(SHAPES))
def test_dispatch_and_combine_by_scatter_and_by_gathers(shape):
    t, k, cap, d, n_pairs, held = SHAPES[shape]
    x, out, w, key, drows, dy = _operands(shape)
    row = {"T": t, "k": k, "capacity": cap, "D": d, "pairs": n_pairs}
    got = {}
    for way, make in WAYS.items():
        glue = make(k, cap, held)

        def both(x, out, w, key, drows, dy, glue=glue):
            prim, vjp = jax.vjp(lambda x, out, w: glue(x, out, w, key),
                                x, out, w)
            return prim, vjp((drows, dy))

        fwd_ms = _ms(jax.jit(glue), (x, out, w, key))
        both_ms = _ms(jax.jit(both), (x, out, w, key, drows, dy))
        fb, bb = _bytes(shape, way)
        bwd_s = (both_ms - fwd_ms) / 1e3
        row[way] = {
            "forward_ms": round(fwd_ms, 4),
            "forward_backward_ms": round(both_ms, 4),
            "forward_memory_rate_share": round(
                fb / HBM_BYTES_PER_S / (fwd_ms / 1e3), 4),
            "backward_memory_rate_share": round(
                bb / HBM_BYTES_PER_S / bwd_s, 4) if bwd_s > 0 else None,
            # a layer-step under a mirror stage: forward, its rerun, backward
            "step_ms": round(fwd_ms + both_ms, 4)}
        (rows, y), (dx, dout, dw) = jax.jit(both)(x, out, w, key, drows, dy)
        got[way] = (rows, y, dx, dout, dw)
    # the pairs' results agree; `dout` past the last pair is never read
    valid = np.arange(cap) < n_pairs
    for way in ("scatter", "kernel"):
        for name, a, b in zip(("rows", "y", "dx", "dout", "dw"), got[way],
                              got["gathers"]):
            a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
            if name in ("rows", "dout"):
                a, b = a[valid], b[valid]
            err = float(np.linalg.norm(a - b) / (np.linalg.norm(a) + 1e-30))
            row["rel_err_%s_%s" % (way, name)] = err
            assert err < 1e-2, (way, name, err)
    # the kernel sums what XLA's gathers sum, in their order
    for name in ("y", "dx"):
        i = ("rows", "y", "dx").index(name)
        assert np.array_equal(np.asarray(got["kernel"][i]),
                              np.asarray(got["gathers"][i])), name
    pairs = jnp.argsort(key, stable=True)
    ways = {
        "scatter": jax.jit(lambda o: jnp.zeros_like(o).at[o].set(
            jnp.arange(o.shape[0], dtype=o.dtype), unique_indices=True)),
        "argsort": jax.jit(lambda o: jnp.argsort(o).astype(o.dtype))}
    row["inverse_permutation_ms"] = {n: round(_ms(f, (pairs,)), 4)
                                     for n, f in ways.items()}
    _record(**{shape: row})


# held experts, their width, the router's experts: one mixture layer of each
# cell, products and all (T and k as in SHAPES)
LAYERS = {"lfm2_moe": (8, 1792, 32), "kanana2": (16, 768, 128),
          "trinity_mini": (8, 1024, 128), "kimi_linear": (8, 1024, 256)}


@pytest.mark.parametrize("shape", list(LAYERS))
def test_one_layer_by_path_where_it_runs(shape, monkeypatch):
    """One mixture layer's value and gradients, the kernels and the router
    included and the forward rerun as a mirror stage reruns it, a token's
    rows summed by XLA's gathers and by `mx_moe_combine`: the two in the
    program around them, where XLA fuses what it can of each."""
    t, k, cap, d, _, _ = SHAPES[shape]
    held, w, n_all = LAYERS[shape]
    ks = jax.random.split(jax.random.PRNGKey(1), 7)

    def normal(key, shape, scale, dtype=jnp.bfloat16):
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(
            dtype)

    args = (normal(ks[0], (t, d), 1.0), normal(ks[1], (n_all, d), 0.02,
                                                  jnp.float32),
            jnp.zeros((n_all,), jnp.float32),
            normal(ks[2], (held, d, w), d ** -0.5),
            normal(ks[3], (held, d, w), d ** -0.5),
            normal(ks[4], (held, w, d), w ** -0.5), normal(ks[5], (t, d), 1.0))

    def program():
        # a function of its own a path: jax.checkpoint keeps a trace by
        # the function it wraps, which would hand the second path the first
        @jax.checkpoint
        def layer(x, rw, rb, wg, wu, wd):
            return lm.moe_experts(x, rw, rb, wg, wu, wd, first_expert=0,
                                  top_k=k, scaling=1.0, renormalize=True)

        def loss(x, rw, rb, wg, wu, wd, c):
            y, stats = layer(x, rw, rb, wg, wu, wd)
            return (y.astype(jnp.float32) * c).sum(), stats
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 3, 4, 5),
                                          has_aux=True))

    from mxnet_tpu.ops import moe_pallas
    from mxnet_tpu.telemetry import registry
    calls = registry.counter(lm.MOE_COMBINE_COUNTER)
    take = moe_pallas.combine_eligible
    row = {}
    for way in ("gathers", "kernel"):
        monkeypatch.setattr(moe_pallas, "combine_eligible",
                            take if way == "kernel" else lambda *a: False)
        fn, before = program(), calls.value()
        (_, stats), _ = fn(*args)
        assert (calls.value() > before) == (way == "kernel")
        stats = np.asarray(stats)
        assert stats[-1] == 0, "the dense path"
        row[way + "_value_and_grad_ms"] = round(_ms(fn, args, reps=10), 4)
        row["pairs"] = int(stats[-3])
    _record(**{"layer_" + shape: row})


def _routed(shape, seed=0):
    """Each token's k experts distinct, as a router's top-k gives them: the
    held ones a random n_pairs of the pairs. Returns a pair's held expert
    (T, k) (`held` where none), its row (T, k), the rows an expert, and the
    pair count."""
    t, k, _, _, n_pairs, held = SHAPES[shape]
    ks = jax.random.split(jax.random.PRNGKey(seed + 7), 2)
    expert = jnp.argsort(jax.random.uniform(ks[0], (t, held)), axis=1)[:, :k]
    pick = jax.random.uniform(ks[1], (t, k)) < n_pairs / (t * k)
    expert = jnp.where(pick, expert, held).astype(jnp.int32)
    order = jnp.argsort(expert.reshape(-1), stable=True)
    inv = jnp.argsort(order).astype(jnp.int32).reshape(t, k)
    load = jnp.bincount(expert.reshape(-1), length=held + 1)[:held].astype(
        jnp.int32)
    return expert, inv, load, int(jnp.sum(load))


@pytest.mark.parametrize("shape", list(SHAPES))
def test_combine_kernel_alone_against_the_gathers(shape):
    """`mx_moe_combine` alone against XLA's gathers (`lm._rows_of_pairs` +
    `lm._sum_computed`) in both directions that sum a token's rows: the
    combine's y from the products' float32 rows with the weights, and x's
    gradient from the bf16 rows' gradients; the two agree to the bit. The
    share of the memory's rate is by the bytes of the computed rows read
    and of y written; the plan (`moe_pallas.combine_plan`, XLA's work on
    (T, k) scalars) is timed by itself, once a layer-step's forward."""
    from mxnet_tpu.ops import moe_pallas as mp
    t, k, cap, d, _, held = SHAPES[shape]
    _, out, w, _, drows, _ = _operands(shape)
    expert, inv, load, n_pairs = _routed(shape)
    computed = inv < n_pairs
    row = {"T": t, "k": k, "capacity": cap, "D": d, "pairs": n_pairs}
    tt = min(mp.combine_tile(t, k, held, d, dt)
             for dt in (jnp.float32, jnp.bfloat16))
    plan_fn = jax.jit(lambda e, i, l: mp.combine_plan(e, i, l, tt=tt))
    plan = plan_fn(expert, inv, load)
    row["tile"] = tt
    row["plan_ms"] = round(_ms(plan_fn, (expert, inv, load)), 4)
    for name, rows, weight in (("combine", out, w), ("x_grad", drows, None)):
        # timed as the layer leaves y, in bf16; compared before the rounding
        @functools.partial(jax.jit, static_argnames="dtype")
        def kernel(r, p, w, dtype=jnp.bfloat16):
            return mp.gather_sum(r, p, w, k=k, out_dtype=dtype)

        @functools.partial(jax.jit, static_argnames="dtype")
        def xla(r, i, c, w, dtype=jnp.bfloat16):
            return lm._sum_computed(lm._rows_of_pairs(r, i), c, w).astype(
                dtype)

        got = kernel(rows, plan, weight, dtype=jnp.float32)
        want = xla(rows, inv, computed, weight, dtype=jnp.float32)
        same = bool(jnp.all(jax.lax.bitcast_convert_type(got, jnp.uint32)
                            == jax.lax.bitcast_convert_type(want, jnp.uint32)))
        kernel_ms = _ms(kernel, (rows, plan, weight))
        moved = n_pairs * d * rows.dtype.itemsize + t * d * 2
        row[name] = {
            "kernel_ms": round(kernel_ms, 4),
            "gathers_ms": round(_ms(xla, (rows, inv, computed, weight)), 4),
            "kernel_memory_rate_share": round(
                moved / HBM_BYTES_PER_S / (kernel_ms / 1e3), 4),
            "bit_equal": same}
        _record(**{"kernel_" + shape: row})
        assert same, (name, row[name])
