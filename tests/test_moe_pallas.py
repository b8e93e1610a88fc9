"""`ops/moe_pallas.py`: the held experts' grouped products as Pallas kernels,
under the Pallas interpreter (the TPU's compiler is not asked here:
`tests/test_tpu_compile.py` compiles them for a described v5e, and
`tests_tpu/test_moe_kernels.py` runs them on the chip)."""
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.ops import lm, moe_pallas as mp

# K / N of the three families' held experts (LFM2 2,048 / 1,792, Kanana-2
# 2,048 / 768, Kimi-Linear 2,304 / 1,024), and every tile parameter with
# them, scaled down by ONE factor of 16: lanes of 8 where the chip has 128
SCALE = 16
FAMILIES = {"lfm2_moe": (2048 // SCALE, 1792 // SCALE),
            "kanana2": (2048 // SCALE, 768 // SCALE),
            "kimi_linear": (2304 // SCALE, 1024 // SCALE)}
M, E = 64, 4            # rows, held experts; a row tile of 8
LAYOUTS = {
    "balanced": [16, 16, 16, 16],
    "one_expert": [0, 0, 64, 0],
    "empty_first": [0, 24, 16, 8],
    "empty_middle": [24, 0, 0, 16],
    "empty_last": [24, 16, 8, 0],
    "boundary_inside_a_tile": [5, 13, 21, 9],
    "fewer_pairs_than_rows": [8, 8, 3, 8],
    "every_row_a_pair": [3, 29, 20, 12],
    "no_pair": [0, 0, 0, 0],
}


@pytest.fixture(scope="class")
def toy_tiles():
    # once for the class: the kernels' jits keep what they compiled for a
    # family and a type over the layouts (a trace reads the tile parameters)
    with pytest.MonkeyPatch.context() as patch:
        for name in ("LANES", "MIN_ROWS", "MAX_ROWS", "MAX_COLS"):
            patch.setattr(mp, name, getattr(mp, name) // SCALE)
        jax.clear_caches()
        # what a kernel leaves unwritten reads NaN under the interpreter
        assert _uninitialised_reads_nan()
        yield
    jax.clear_caches()


def _per_expert_loop(x, w_gate, w_up, w_down, sizes):
    """The same sum with one dense product an expert, float32 all through."""
    out, start = jnp.zeros(x.shape, jnp.float32), 0
    f32 = lambda a: a.astype(jnp.float32)
    for e, size in enumerate(sizes):
        rows = f32(x[start:start + size])
        hidden = jax.nn.silu(rows @ f32(w_gate[e])) * (rows @ f32(w_up[e]))
        hidden = f32(hidden.astype(x.dtype))
        out = out.at[start:start + size].set(hidden @ f32(w_down[e]))
        start += size
    return out


def _uninitialised_reads_nan():
    import jax.experimental.pallas as pl
    out = pl.pallas_call(lambda x_ref, o_ref: None, interpret=True,
                         out_shape=jax.ShapeDtypeStruct((8, 8), jnp.float32)
                         )(jnp.zeros((8, 8)))
    return bool(np.isnan(np.asarray(out)).all())


class TestAtToyWidths:
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("family", FAMILIES)
    @pytest.mark.parametrize("layout", LAYOUTS)
    def test_kernels_match_ragged_dot_and_a_loop_over_experts(
            self, layout, family, dtype, toy_tiles):
        """Forward, the rows' gradient and the three weights' gradients of the
        grouped SwiGLU through `mx_moe_gmm` / `mx_moe_tgmm` against
        `jax.lax.ragged_dot` and against one dense product an expert; the rows
        past the last pair come back as exact zeros from memory the interpreter
        hands over as NaN."""
        k, n = FAMILIES[family]
        sizes = LAYOUTS[layout]
        pairs = sum(sizes)
        rng = np.random.default_rng(len(layout) + k + n)
        held = (np.arange(M) < pairs)[:, None]
        x = jnp.asarray(np.where(held, rng.standard_normal((M, k)), 0), dtype)
        ct = jnp.asarray(np.where(held, rng.standard_normal((M, k)), 0),
                         jnp.float32)
        w_gate, w_up = (jnp.asarray(rng.standard_normal((E, k, n)) * k ** -0.5,
                                    dtype) for _ in range(2))
        w_down = jnp.asarray(rng.standard_normal((E, n, k)) * n ** -0.5, dtype)
        group_sizes = jnp.asarray(sizes, jnp.int32)
        assert mp.row_tile(M, E) == 8 and mp.col_tile(1792 // SCALE, 1) == 56

        def run(fn):
            def loss(x, w_gate, w_up, w_down):
                out = fn(x, w_gate, w_up, w_down)
                # `ragged_dot` leaves the rows of no expert unwritten
                return jnp.sum(jnp.where(held, out, 0.0) * ct), out
            (_, out), grads = jax.value_and_grad(
                loss, argnums=(0, 1, 2, 3), has_aux=True)(x, w_gate, w_up, w_down)
            return (out,) + grads

        got = run(lambda *a: lm._swiglu_experts(*a, group_sizes,
                                                force="interpret"))
        assert got[0].dtype == jnp.float32 and got[1].dtype == x.dtype
        for leaving in got[:2]:                             # zeros, not NaN
            assert not np.asarray(leaving[pairs:], np.float32).any()
        wants = {"ragged_dot": run(lambda *a: lm._swiglu_experts(
            *a, group_sizes, force="xla")),
            "loop": run(lambda *a: _per_expert_loop(*a, sizes))}
        # bf16: a cotangent enters a product rounded to bf16, as on the chip;
        # the CPU's `ragged_dot` and the loop multiply it as float32
        tol = 1e-5 if dtype == "float32" else 2e-2
        for way, want in wants.items():
            for name, a, b in zip(("out", "dx", "dw_gate", "dw_up", "dw_down"),
                                  got, want):
                a, b = (np.asarray(t, np.float32) for t in (a, b))
                if name in ("out", "dx"):       # `ragged_dot`'s are unwritten
                    a, b = a[:pairs], b[:pairs]
                assert a.shape == b.shape and np.isfinite(a).all(), (way, name)
                scale = np.abs(b).max() if b.any() else 1.0
                assert not a.size or np.abs(a - b).max() <= tol * scale, (
                    way, name)


# rows (capacity), D, W, held experts of the three cells' mixture layers
CELLS = {"lfm2_moe.train": (65536, 2048, 1792, 8, 512),
         "kanana2.train": (49152, 2048, 768, 16, 128),
         "kimi_linear.train": (16384, 2304, 1024, 8, 128)}


@pytest.mark.parametrize("cell", CELLS)
def test_eligible_counters_and_the_logged_fallback(cell, caplog):
    from mxnet_tpu.telemetry import registry
    rows, d, w, held, tile = CELLS[cell]
    for dtype in (jnp.bfloat16, jnp.float32):
        assert mp.eligible(dtype, d, w, rows, held, "tpu")
        assert mp.eligible(dtype, w, d, rows, held, "tpu")
        assert not mp.eligible(dtype, d, w, rows, held, "cpu")
    assert not mp.eligible(jnp.float16, d, w, rows, held, "tpu")
    assert not mp.eligible(jnp.bfloat16, d + 64, w, rows, held, "tpu")
    assert not mp.eligible(jnp.bfloat16, d, w, rows + 64, held, "tpu")
    assert mp.row_tile(rows, held) == tile

    calls = registry.counter(lm.MOE_KERNEL_COUNTER)
    falls = registry.counter(lm.MOE_FALLBACK_COUNTER)

    def traced(rows, platform, dtype=jnp.bfloat16):
        shapes = [jax.ShapeDtypeStruct(s, dtype) for s in (
            (rows, d), (held, d, w), (held, d, w), (held, w, d))]
        before = calls.value(), falls.value()
        out = jax.eval_shape(
            lambda *a: lm._swiglu_experts(*a, platform=platform), *shapes,
            jax.ShapeDtypeStruct((held,), jnp.int32))
        assert out.shape == (rows, d) and out.dtype == jnp.float32
        return calls.value() - before[0], falls.value() - before[1]

    assert traced(rows, "cpu") == (0, 0)
    assert traced(rows, "tpu") == (1, 0)                # once a trace
    assert traced(rows, "tpu", jnp.float32) == (1, 0)   # check (b)'s program
    with caplog.at_level(logging.WARNING, logger=lm.__name__):
        assert traced(rows + 64, "tpu") == (0, 1)
    assert str((rows + 64, d)) in caplog.text and \
        str((held, d, w)) in caplog.text
    assert traced(rows + 64, "tpu", jnp.float32) == (0, 0)


# -- mx_moe_combine ------------------------------------------------------------------

# T * k over the grouped rows (1, 2, 4, 8: `lfm2_moe.train`, `kanana2.train`,
# `trinity_mini.train`, `kimi_linear.train`): experts a token, held, router
T_TOY = 128
COMBINE_RATIOS = {1: (4, 4, 8), 2: (4, 2, 8), 4: (8, 2, 16), 8: (8, 2, 32)}


def _routing(ratio, seed):
    """A token's k distinct experts of the router's, as top-k chooses them,
    with tokens 32-63 (the second tile of 32) on no held expert and the
    last held expert chosen by no token. Returns a pair's held expert
    (T, k) (`held` where none), its row `inv` (T, k), the rows an expert
    and the grouped rows."""
    k, held, n_all = COMBINE_RATIOS[ratio]
    rng = np.random.default_rng(seed)
    chosen = np.stack([rng.choice(n_all, k, replace=False)
                       for _ in range(T_TOY)])
    chosen[32:64] = np.stack([rng.choice(np.arange(held, n_all), k,
                                         replace=False) for _ in range(32)])
    chosen = np.where(chosen == held - 1, n_all + 1, chosen)  # never held
    expert = np.where(chosen < held, chosen, held)
    order = np.argsort(expert.reshape(-1), kind="stable")
    inv = np.argsort(order).reshape(T_TOY, k)
    load = np.bincount(expert.reshape(-1), minlength=held + 1)[:held]
    rows = T_TOY * k // ratio
    assert load[-1] == 0 and load.sum() <= rows
    return (jnp.asarray(expert, jnp.int32), jnp.asarray(inv, jnp.int32),
            jnp.asarray(load, jnp.int32), rows)


@pytest.fixture(scope="class")
def toy_tokens():
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(mp, "COMBINE_TOKENS", 32)
        jax.clear_caches()
        yield
    jax.clear_caches()


class TestCombine:
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("weighted", [True, False],
                             ids=["combine", "x_grad"])
    @pytest.mark.parametrize("ratio", sorted(COMBINE_RATIOS))
    def test_kernel_sums_the_computed_rows_as_the_gathers_do(
            self, ratio, weighted, dtype, toy_tokens):
        """`mx_moe_combine` under the interpreter against XLA's gathers
        (`lm._sum_computed(lm._rows_of_pairs(...))`), to the bit, at each
        ratio of pairs to rows: weighted (the combine) and not (x's
        gradient), float32 and bf16 rows; every row past the last pair,
        the rows of the pairs not computed among them, holds NaN, which the
        kernel never reads. Four tiles of 32 tokens, whose runs start and
        end inside blocks and span experts' boundaries; the second tile has
        no computed pair and the last held expert no pair at all. The
        weights are powers of two, so each product is exact and the CPU's
        fused multiply-adds round as the chip's separate steps do: what is
        compared is which rows are summed, and in which order."""
        k = COMBINE_RATIOS[ratio][0]
        expert, inv, load, n_rows = _routing(ratio, ratio)
        n_pairs = int(load.sum())
        rng = np.random.default_rng(ratio + 10 * weighted)
        rows = rng.standard_normal((n_rows, 32)).astype(np.float32)
        rows[n_pairs:] = np.nan
        rows = jnp.asarray(rows, dtype)
        weight = jnp.asarray(2.0 ** rng.integers(-3, 3, (T_TOY, k)),
                             jnp.float32) if weighted else None
        computed = inv < n_pairs
        assert not bool(computed[32:64].any()) and bool(computed.any())
        tt = mp.combine_tile(T_TOY, k, load.shape[0], 32, rows.dtype)
        assert tt == 32
        plan = mp.combine_plan(expert, inv, load, tt=tt)
        out_dtype = rows.dtype if not weighted else jnp.float32
        got = mp.gather_sum(rows, plan, weight, k=k, out_dtype=out_dtype,
                            interpret=True)
        want = lm._sum_computed(lm._rows_of_pairs(rows, inv), computed,
                                weight).astype(out_dtype)
        got, want = (np.asarray(a, np.float32) for a in (got, want))
        assert np.isfinite(got).all() and not got[32:64].any()
        assert np.array_equal(got, want)

    @pytest.mark.parametrize("ratio", sorted(COMBINE_RATIOS))
    def test_plan_reads_only_the_computed_rows(self, ratio, toy_tokens):
        """The runs a tile copies cover its computed pairs' rows, in whole
        blocks of CHUNK rows below n_pairs rounded up to a block: the bytes
        read are the computed rows and at most a block at each end of a
        run; each computed pair's row in its tile's buffer holds the row it
        names, and no other pair has one."""
        k, held, _ = COMBINE_RATIOS[ratio]
        expert, inv, load, n_rows = _routing(ratio, ratio)
        n_pairs = int(load.sum())
        tt = 32
        lo, blocks, at, mask, row = (np.asarray(a) for a in mp.combine_plan(
            expert, inv, load, tt=tt))
        kp = mp._lanes_a_token(k)
        row = row.reshape(T_TOY, kp)
        inv, expert = np.asarray(inv), np.asarray(expert)
        assert not (row[:, k:] + 1).any()
        copied = 0
        for i in range(T_TOY // tt):
            buffer = np.full(tt * k + 2 * mp.CHUNK * held, -1)
            for e in range(held):
                n = blocks[i, e] * mp.CHUNK
                assert lo[i, e] % mp.CHUNK == 0 and at[i, e] % mp.CHUNK == 0
                assert lo[i, e] + n <= -(-n_pairs // mp.CHUNK) * mp.CHUNK
                buffer[at[i, e]:at[i, e] + n] = np.arange(lo[i, e],
                                                          lo[i, e] + n)
                copied += n
            for t in range(i * tt, (i + 1) * tt):
                for j in range(k):
                    on = expert[t, j] < held
                    assert bool(mask[t] >> j & 1) == on
                    assert (buffer[row[t, j]] == inv[t, j]) if on else \
                        row[t, j] == -1
        runs = int(np.sum(blocks > 0))
        assert n_pairs <= copied <= n_pairs + 2 * mp.CHUNK * runs


# T, k, grouped rows, D, held experts of the four cells' mixture layers
COMBINE_CELLS = {"lfm2_moe.train": (16384, 4, 65536, 2048, 8),
                 "kanana2.train": (16384, 6, 49152, 2048, 16),
                 "trinity_mini.train": (16384, 8, 32768, 2048, 8),
                 "kimi_linear.train": (16384, 8, 16384, 2304, 8)}


@pytest.mark.parametrize("cell", COMBINE_CELLS)
def test_combine_eligible_counters_and_the_logged_fallback(cell, caplog):
    """`mx_moe_combine` takes every cell's shapes in a program for a TPU, at
    tiles of 256 tokens; a bf16 program for a TPU whose shapes it refuses
    is counted and logged with them, and takes XLA's gathers."""
    from mxnet_tpu.telemetry import registry
    t, k, rows, d, held = COMBINE_CELLS[cell]
    for dtype in (jnp.bfloat16, jnp.float32):
        assert mp.combine_eligible(d, rows, t, k, held, dtype, "tpu")
        assert mp.combine_tile(t, k, held, d, dtype) == 256
        assert not mp.combine_eligible(d, rows, t, k, held, dtype, "cpu")
        assert not mp.combine_eligible(d + 64, rows, t, k, held, dtype,
                                       "tpu")
        assert not mp.combine_eligible(d, rows + 8, t, k, held, dtype, "tpu")
    assert not mp.combine_eligible(d, rows, t, k, held, jnp.float16, "tpu")

    calls = registry.counter(lm.MOE_COMBINE_COUNTER)
    falls = registry.counter(lm.MOE_COMBINE_FALLBACK_COUNTER)

    def traced(d, platform, dtype=jnp.bfloat16):
        pairs = jax.ShapeDtypeStruct((t, k), jnp.int32)
        before = calls.value(), falls.value()
        inv, computed, plan = jax.eval_shape(
            lambda e, i, n, l: lm._combine_links(e, i, n, l, rows, d, dtype,
                                                 None, platform),
            pairs, pairs, jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((held,), jnp.int32))
        assert inv.shape == computed.shape == (t, k)
        return calls.value() - before[0], falls.value() - before[1], plan

    assert traced(d, "cpu")[:2] == (0, 0)
    calls_, falls_, plan = traced(d, "tpu")             # once a trace
    assert (calls_, falls_) == (1, 0) and plan[0].shape == (t // 256, held)
    assert traced(d, "tpu", jnp.float32)[:2] == (1, 0)  # check (b)'s program
    with caplog.at_level(logging.WARNING, logger=lm.__name__):
        assert traced(d + 64, "tpu") == (0, 1, None)
    assert str((rows, d + 64)) in caplog.text
    assert traced(d + 64, "tpu", jnp.float32) == (0, 0, None)
