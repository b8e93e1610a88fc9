"""On the chip, what moves rows to the held experts' grouped products and
their results back to the tokens, at the four language-model cells' shapes
(tokens T, experts a token k, rows `capacity`, hidden D, with the pairs of a
traced layer-step): the gather and scatter-add combine (what
`ops/lm.py::moe_experts` keeps past `lm.MOE_GATHER_MAX_RATIO`) against the
gathers both ways (`lm._dispatch` / `lm._combine`). Alone, the products left
out, forward and forward-and-backward, in milliseconds and as a share of
the memory's rate (819 GB/s) by the bytes each pass has to move, and the
inverse permutation by a scatter of int32 scalars against a second argsort;
and in place, one mixture layer's value and gradients with the kernels, the
router and the forward's rerun, through each path
(`chiprun_out/moe_combine.json`; PERF.md quotes them)."""
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


def _gather_glue(k, cap, held):
    """`moe_experts`'s grouped branch without its products."""
    def glue(x, out, w, key):
        n_pairs = jnp.sum(key < held)
        order = jnp.argsort(key, stable=True)
        inv = jnp.argsort(order).astype(order.dtype).reshape(-1, k)
        computed = inv < n_pairs
        rows = lm._dispatch(x, order[:cap], inv, computed)
        return rows, lm._combine(out, w, order[:cap], inv, computed,
                                 jnp.float32)
    return glue


def _bytes(shape, way):
    """What a pass has to read and write, by its shapes: (forward,
    backward)."""
    t, k, cap, d, _, _ = SHAPES[shape]
    rows = cap * d * 2 * 2                      # x's rows read, rows written
    if way == "scatter":
        fwd = rows + cap * d * 4 + t * d * 4    # out read, y written
        bwd = (cap * d * 2 + t * d * 2          # dx: drows read, dx written
               + cap * d * 4 * 2                # dout: dy's rows, dout
               + cap * d * 4 * 2)               # dw: out and dy's rows
    else:
        fwd = rows + t * k * d * 4 + t * d * 4  # k rows a token read
        bwd = (t * k * d * 2 + t * d * 2
               + cap * d * 4 * 2
               + t * k * d * 4 + t * d * 4)
    return fwd, bwd


@pytest.mark.parametrize("shape", list(SHAPES))
def test_dispatch_and_combine_by_scatter_and_by_gathers(shape):
    t, k, cap, d, n_pairs, held = SHAPES[shape]
    x, out, w, key, drows, dy = _operands(shape)
    row = {"T": t, "k": k, "capacity": cap, "D": d, "pairs": n_pairs}
    got = {}
    for way, make in (("scatter", _scatter_glue), ("gathers", _gather_glue)):
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
    for name, a, b in zip(("rows", "y", "dx", "dout", "dw"), got["scatter"],
                          got["gathers"]):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        if name in ("rows", "dout"):
            a, b = a[valid], b[valid]
        err = float(np.linalg.norm(a - b) / (np.linalg.norm(a) + 1e-30))
        row["rel_err_" + name] = err
        assert err < 1e-2, (name, err)
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
    included and the forward rerun as a mirror stage reruns it, through
    the scatter-add and through the gathers: the two in the program around
    them, where XLA fuses what it can of each."""
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

    from mxnet_tpu.telemetry import registry
    calls = registry.counter(lm.MOE_GATHER_COUNTER)
    row = {}
    for way, ratio in (("scatter", 0), ("gathers", 8)):
        monkeypatch.setattr(lm, "MOE_GATHER_MAX_RATIO", ratio)
        fn, before = program(), calls.value()
        (_, stats), _ = fn(*args)
        assert (calls.value() > before) == (way == "gathers")
        stats = np.asarray(stats)
        assert stats[-1] == 0, "the dense path"
        row[way + "_value_and_grad_ms"] = round(_ms(fn, args, reps=10), 4)
        row["pairs"] = int(stats[-3])
    _record(**{"layer_" + shape: row})
