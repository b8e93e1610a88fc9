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
