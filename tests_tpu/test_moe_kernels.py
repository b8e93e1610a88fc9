"""On the chip, the held experts' grouped products at the three shapes the
language-model cells run (bf16; rows x D x W x held experts, with the pairs
of a traced step): one product through `jax.lax.ragged_dot`, through the
installed JAX's `megablox.gmm` (the yardstick) and through `mx_moe_gmm` at
several tilings; every kernel call of one layer-step, forward and backward;
and the whole grouped SwiGLU with its gradients through the kernels against
the `ragged_dot` path, in milliseconds and as results
(`chiprun_out/moe_kernels.json`; PERF.md quotes them)."""
import functools
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.ops import lm, moe_pallas as mp

# rows, D, W, pairs on each held expert in a traced layer-step (PERF.md,
# PR 35: `lfm2_moe.train` 32,252 pairs, the fullest expert 16,023;
# `kanana2.train` 4,203; `kimi_linear.train` its balanced 4,096)
LFM2_LOAD = [16023, 5210, 3377, 2890, 2001, 1502, 850, 399]
SHAPES = {
    "lfm2_moe": (65536, 2048, 1792, LFM2_LOAD),
    "lfm2_moe_half": (65536, 2048, 1792,
                      [8140, 2647, 1716, 1468, 1016, 763, 432, 202]),
    "kanana2": (49152, 2048, 768, [1203, 611, 498, 402, 333, 290, 214, 170,
                                   131, 102, 84, 63, 44, 31, 18, 9]),
    "kimi_linear": (16384, 2304, 1024, [1102, 788, 611, 540, 433, 311, 204,
                                        107]),
}
OUT = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "chiprun_out", "moe_kernels.json")
BF16 = jnp.bfloat16


def _ms(fn, args, reps=10):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return round((time.perf_counter() - t0) / reps * 1e3, 4)


def _record(**kv):
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    have = json.load(open(OUT)) if os.path.exists(OUT) else {}
    have.update(kv)
    with open(OUT, "w") as f:
        json.dump(have, f, indent=1)
    print(json.dumps(kv))


def _operands(shape, seed=0):
    m, d, w, load = SHAPES[shape]
    e = len(load)
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    n = sum(load)
    held = (jnp.arange(m) < n)[:, None]
    x = jnp.where(held, jax.random.normal(ks[0], (m, d), jnp.float32),
                  0).astype(BF16)
    ct = jnp.where(held, jax.random.normal(ks[1], (m, d), jnp.float32), 0)
    wg, wu = (jax.random.normal(k, (e, d, w), jnp.float32).astype(BF16)
              * d ** -0.5 for k in ks[2:4])
    wd = jax.random.normal(ks[4], (e, w, d), jnp.float32).astype(BF16) \
        * w ** -0.5
    return x, wg, wu, wd, jnp.asarray(load, jnp.int32), ct


def _try(fn, args, reps=5):
    try:
        return _ms(fn, args, reps)
    except Exception as e:      # the compiler refuses the tiling: recorded
        return "refused: " + " ".join(repr(e).split())[:300]


@pytest.mark.parametrize("shape", ["lfm2_moe", "lfm2_moe_half"])
def test_one_product_by_implementation_and_tiling(shape, monkeypatch):
    m, d, w, load = SHAPES[shape]
    x, wg, _, _, gs, _ = _operands(shape)
    row = {"pairs": sum(load),
           "least_ms_at_the_peak": round(2 * sum(load) * d * w / 197e12 * 1e3,
                                         4)}
    row["ragged_dot_ms"] = _ms(jax.jit(functools.partial(
        jax.lax.ragged_dot, preferred_element_type=jnp.float32)),
        (x, wg, gs))
    import importlib
    megablox = importlib.import_module(      # the package exports a function
        "jax.experimental.pallas.ops.tpu.megablox.gmm")     # of that name
    for tiling in ((128, 128, 128), (512, 512, 896), (512, 1024, 896),
                   (512, 2048, 256), (256, 2048, 896)):
        row["megablox_%dx%dx%d_ms" % tiling] = _try(jax.jit(
            lambda x, w, gs, t=tiling: megablox.gmm(
                x, w, gs, jnp.float32, t)), (x, wg, gs))
    for tm in (128, 256, 512):
        for cols in (896, 1792):
            monkeypatch.setattr(mp, "MAX_COLS", cols)
            jax.clear_caches()
            for tail in (True, False):
                sched = mp._schedule(gs, m, tm, zero_tail=tail)
                row[f"mx_moe_gmm_{tm}x{cols}_{'zeros' if tail else 'unwritten'}"
                    "_past_the_pairs_ms"] = _try(
                    lambda x, w, s: mp._gmm(
                        x, (w,), s, tm=tm, out_dtype=jnp.float32),
                    (x, wg, sched))
    monkeypatch.undo()
    jax.clear_caches()
    _record(**{"one_product_" + shape: row})


@pytest.mark.parametrize("shape", list(SHAPES))
def test_layer_step_by_kernel_and_against_ragged_dot(shape):
    m, d, w, load = SHAPES[shape]
    x, wg, wu, wd, gs, ct = _operands(shape)
    n, tm = sum(load), mp.row_tile(m, len(load))
    leaving = mp._schedule(gs, m, tm, zero_tail=True)
    inner = mp._schedule(gs, m, tm)
    by_expert = mp._schedule(gs, m, tm, every_group=True)
    gmm = functools.partial(mp._gmm, tm=tm)
    hidden, g, u = gmm(x, (wg, wu), inner, kind="swiglu_parts")
    ctb = ct.astype(BF16)
    dg, du = gmm(ctb, (wd,), inner, extras=(g, u), transpose=True,
                 kind="swiglu_grad")
    row = {"pairs": n, "row_tile": tm, "kernels_ms": {
        "gate_up_hidden": _ms(lambda: gmm(x, (wg, wu), inner,
                                          kind="swiglu"), ()),
        "gate_up_hidden_and_parts": _ms(lambda: gmm(
            x, (wg, wu), inner, kind="swiglu_parts"), ()),
        "down": _ms(lambda: gmm(hidden, (wd,), leaving,
                                out_dtype=jnp.float32), ()),
        "dhidden_through_swiglu": _ms(lambda: gmm(
            ctb, (wd,), inner, extras=(g, u), transpose=True,
            kind="swiglu_grad"), ()),
        "dx": _ms(lambda: gmm((dg, du), (wg, wu), leaving, transpose=True,
                              kind="sum"), ()),
        "dw_gate_up": _ms(lambda: mp._tgmm(x, (dg, du), by_expert, tm=tm), ()),
        "dw_down": _ms(lambda: mp._tgmm(hidden, (ctb,), by_expert, tm=tm),
                       ())}}

    def layer(fn):
        def loss(x, wg, wu, wd):
            out = fn(x, wg, wu, wd, gs)
            # rows of no expert hold anything on the ragged_dot path
            return jnp.sum(jnp.where((jnp.arange(m) < n)[:, None], out, 0.0)
                           * ct), out
        return (jax.jit(lambda *a: loss(*a)[1]),
                jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3),
                                           has_aux=True)))

    ways = {"ragged_dot": layer(functools.partial(lm._swiglu_experts,
                                                  force="xla")),
            "kernels": layer(lm._swiglu_experts)}       # the auto pick
    got = {}
    for way, (fwd, both) in ways.items():
        row[way + "_forward_ms"] = _ms(fwd, (x, wg, wu, wd))
        row[way + "_forward_backward_ms"] = _ms(both, (x, wg, wu, wd))
        (_, out), grads = both(x, wg, wu, wd)
        # `ragged_dot` leaves the rows past the pairs unwritten, in the
        # result and in the rows' gradient
        got[way] = [np.asarray(out[:n], np.float32),
                    np.asarray(grads[0][:n], np.float32)] + [
            np.asarray(a, np.float32) for a in grads[1:]]
        if way == "kernels":                        # zeros past the pairs
            assert not np.asarray(out[n:]).any()
            assert not np.asarray(grads[0][n:], np.float32).any()
    errs = [float(np.linalg.norm(a - b) / (np.linalg.norm(b) + 1e-30))
            for a, b in zip(got["kernels"], got["ragged_dot"])]
    row["rel_err_out_dx_dwg_dwu_dwd"] = [round(e, 6) for e in errs]
    _record(**{"layer_step_" + shape: row})
    assert max(errs) < 2e-2, errs
    assert row["kernels_forward_backward_ms"] < \
        row["ragged_dot_forward_backward_ms"], row
