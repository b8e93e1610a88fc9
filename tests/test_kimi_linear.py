"""Kimi-Linear's mechanisms against the plain reference
(tests/reference_models/kimi_linear.py), at a small size on the CPU: the
chunked delta rule, the flash kernels at unequal head widths, latent
attention, the held-experts mixture and its shares, the whole model, the
fused fit with adam, and what amp must leave exact."""
import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import amp
from mxnet_tpu.ops import attention, lm

HERE = os.path.dirname(os.path.abspath(__file__))
REF_PATH = os.path.join(HERE, "reference_models", "kimi_linear.py")
COPY_PATH = os.path.join(os.path.dirname(HERE), "benchmarks", "models",
                         "kimi_linear_reference.py")


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load(REF_PATH, "kimi_linear_reference_under_test")

CFG = {
    "hidden_size": 32, "intermediate_size": 64, "num_hidden_layers": 5,
    "first_k_dense_replace": 1, "rms_norm_eps": 1e-5,
    "linear_attn_config": {"kda_layers": [1, 2, 3, 5],
                           "full_attn_layers": [4], "num_heads": 2,
                           "head_dim": 16, "short_conv_kernel_size": 4},
    "num_attention_heads": 2, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "kv_lora_rank": 24,
    "q_lora_rank": None, "mla_use_nope": True, "num_experts": 16, "num_experts_per_token": 4,
    "num_shared_experts": 1, "moe_intermediate_size": 24,
    "moe_renormalize": True, "routed_scaling_factor": 2.446,
    "experts_held": [4, 4], "vocab_size": 300,
}
PREFIX = "kimi_"


def _close(a, b, tol):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    err = np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30)
    assert err <= tol, f"relative error {err} > {tol}"


def _batch(seed, b=2, s=40, vocab=CFG["vocab_size"]):
    tokens = np.random.default_rng(seed).integers(0, vocab, (b, s))
    return tokens, np.roll(tokens, -1, axis=1)


def _net(cfg, params):
    net = mx.gluon.nn.DecoderLM(cfg, prefix=PREFIX)
    net.collect_params().initialize()
    system = ref.system_params(params, PREFIX)
    assert set(net.collect_params().keys()) == set(system)
    for name, p in net.collect_params().items():
        p.set_data(mx.nd.array(system[name]))
    return net


@pytest.fixture(autouse=True)
def _amp_off():
    amp._reset_for_tests()
    yield
    amp._reset_for_tests()


def _kda_inputs(s, b=2, h=3, dk=8, dv=8, seed=0):
    rng = np.random.default_rng(seed)
    q, k = (rng.standard_normal((b, s, h, dk)).astype("float32")
            for _ in range(2))
    q /= np.linalg.norm(q, axis=-1, keepdims=True)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.standard_normal((b, s, h, dv)).astype("float32")
    # decays from almost none to exp(-20) a step: the pairwise stage must
    # not overflow on the steep ones
    g = -np.exp(rng.uniform(-3, 3, (b, s, h, dk))).astype("float32")
    beta = rng.uniform(0, 1, (b, s, h)).astype("float32")
    return tuple(jnp.asarray(a) for a in (q, k, v, g, beta))


def _kernel_case(s, dtype="float32", tol=(2e-5, 5e-5), decay=None,
                 zero_g=False, **shape):
    """The Pallas kernels under the interpreter at head widths they take;
    `tol` on the output and on the gradients, relative to the largest."""
    return dict(s=s, dtype=dtype, tol=tol, decay=decay, zero_g=zero_g,
                kernel=True,
                shape={"b": 1, "h": 1, "dk": 128, "dv": 128, **shape})


KDA_CASES = {
    "several_chunks": dict(s=150), "ragged": dict(s=37),
    "kernel_one_chunk": _kernel_case(64),
    "kernel_three_chunks": _kernel_case(192),
    "kernel_padded": _kernel_case(200),
    "kernel_two_batches_two_heads": _kernel_case(128, b=2, h=2),
    # exp(-7) to exp(-33) a token: the cumulative log-decay reaches -2,000
    # in a chunk, and a float32 difference of two such sums carries 1e-4
    # (kda_chunked reads 1.5e-4 on dg, the kernels 3.6e-4)
    "kernel_steep_decay": _kernel_case(128, tol=(2e-5, 1e-3),
                                       decay=(2.0, 3.5)),
    "kernel_no_decay": _kernel_case(128, zero_g=True),
    # bf16 operands against the float32 recurrence on the same values: both
    # paths read 3e-3 to 1e-2 at this size (PERF.md: 5e-3 on the chip at
    # 2 x 8,192 x 32 heads, where the largest entry is larger)
    "kernel_bf16": _kernel_case(128, dtype="bfloat16", tol=(2e-2, 2e-2)),
}


@pytest.mark.parametrize("case", KDA_CASES.values(), ids=KDA_CASES.keys())
def test_kda_chunked_matches_recurrence(case):
    q, k, v, g, beta = _kda_inputs(case["s"], **case.get("shape", {}))
    if case.get("decay"):
        g = -jnp.exp(jnp.asarray(np.random.default_rng(1).uniform(
            *case["decay"], g.shape), jnp.float32))
    if case.get("zero_g"):
        g = jnp.zeros_like(g)
    dtype = case.get("dtype", "float32")
    args = tuple(a.astype(dtype) for a in (q, k, v)) + (g, beta)
    exact = tuple(a.astype("float32") for a in args)
    tol_o, tol_g = case.get("tol", (2e-5, 5e-5))
    paths = {"xla": lm.kda_chunked}
    if case.get("kernel"):
        paths["kernels"] = functools.partial(lm.kda, force="interpret")

    def run(f, a):
        return f(*a), jax.grad(
            lambda *a: jnp.sum(jnp.sin(f(*a).astype("float32"))),
            argnums=(0, 1, 2, 3, 4))(*a)

    got = {name: run(f, args) for name, f in paths.items()}
    got["recurrence"] = run(ref.kda_recurrence, exact)
    pairs = [(name, "recurrence") for name in paths] + \
        ([("kernels", "xla")] if "kernels" in paths else [])
    for pair in pairs:
        (o, grads), (want_o, want_g) = (got[name] for name in pair)
        _close(o, want_o, tol_o)
        for i, (a, b) in enumerate(zip(grads, want_g)):
            # at g = 0 every clamp min(G_i - G_j, 0) of kda_chunked is a
            # tie, of which jnp.minimum's gradient passes half: its dg is
            # wrong there and nowhere else (a log-decay is < 0)
            if not (case.get("zero_g") and i == 3 and "xla" in pair):
                _close(a, b, tol_g)


def test_kda_fallback_on_tpu_is_counted():
    from mxnet_tpu.telemetry import registry
    calls = registry.counter(lm.KDA_KERNEL_COUNTER)
    falls = registry.counter(lm.KDA_FALLBACK_COUNTER)

    def traced(dk, platform):
        q, k, v, g, beta = _kda_inputs(64, b=1, h=1, dk=dk, dv=dk)
        before = calls.value(), falls.value()
        jax.eval_shape(
            lambda *a: lm.kda(*a, platform=platform),
            *(a.astype("bfloat16") for a in (q, k, v)), g, beta)
        return calls.value() - before[0], falls.value() - before[1]

    assert traced(8, "cpu") == (0, 0)
    assert traced(128, "cpu") == (0, 0)
    assert traced(8, "tpu") == (0, 1)
    assert traced(128, "tpu") == (1, 0)


def _prepare_inputs(b, s, h, d, dtype, dt_bias=0.0, seed=0):
    """The mixer's projections (B, S, H*d), three taps (C, 4), A_log and
    dt_bias, and a cotangent for each of q, k, v, g."""
    rng = np.random.default_rng(seed)
    c = h * d
    args = [jnp.asarray(rng.standard_normal((b, s, c)), dtype)
            for _ in range(4)] + \
        [jnp.asarray(0.5 * rng.standard_normal((c, 4)), dtype)
         for _ in range(3)] + \
        [jnp.asarray(0.3 * rng.standard_normal((h,)), jnp.float32),
         jnp.asarray(0.3 * rng.standard_normal((c,)) + dt_bias, jnp.float32)]
    cots = tuple(jnp.asarray(rng.standard_normal((b, s, c)), t)
                 for t in (dtype, dtype, dtype, "float32"))
    return tuple(args), cots


# rows a grid cell (None: the kernels' own), then the shape; the tolerance
# on every output and gradient, relative to the largest entry (float32:
# A_log's gradient is one sum of S * d terms of either sign, 6e-6 apart)
PREPARE_CASES = {
    "one_block": dict(shape=(1, 64, 1, 128)),
    # the halo crosses a boundary twice, forward and backward
    "three_row_blocks": dict(rows=32, shape=(1, 96, 1, 128)),
    # the last block reaches 16 rows past the sequence
    "ragged": dict(rows=32, shape=(1, 80, 1, 128)),
    # one block of 80 rows, worked as a tile of 64 and one of 16
    "short_last_tile": dict(shape=(1, 80, 1, 128)),
    "two_column_blocks": dict(shape=(1, 64, 2, 128)),
    "two_batches": dict(rows=32, shape=(2, 64, 2, 128)),
    # decays of exp(-8) a token and steeper
    "steep_decay": dict(shape=(1, 64, 1, 128), dt_bias=8.0),
    # bf16 operands: the XLA path rounds the convolution and every
    # cotangent to bf16, the kernels keep float32 between their operands
    # and their results: each within bf16 of the other, and the kernels no
    # further from float32 than the XLA path
    "bf16": dict(shape=(1, 64, 2, 128), dtype="bfloat16", tol=2e-2),
}


@pytest.mark.parametrize("case", PREPARE_CASES.values(),
                         ids=PREPARE_CASES.keys())
def test_kda_prepare_kernels_match_the_xla_path(case, monkeypatch):
    """`mx_kdaprep_fwd` and `mx_kdaprep_bwd` under the Pallas interpreter
    against `lm.kda_prepare` and its autodiff: q, k, v, g and all nine
    gradients (the three taps, A_log and dt_bias among them)."""
    from mxnet_tpu.ops import kda_pallas
    if case.get("rows"):
        monkeypatch.setattr(kda_pallas, "PREP_ROWS", case["rows"])
        jax.clear_caches()
    b, s, h, d = case["shape"]
    dtype = case.get("dtype", "float32")
    args, cots = _prepare_inputs(b, s, h, d, dtype, case.get("dt_bias", 0.0))

    def xla(q, k, v, f, *params):
        out = lm.kda_prepare(q, k, v, f, jnp.zeros((b, s, h), q.dtype),
                             *params, num_heads=h)[:4]
        return tuple(o.reshape(b, s, -1) for o in out)

    def kernels(*a):
        return kda_pallas.prepare_kernels(*a, num_heads=h, interpret=True)

    def run(f, args, cots):
        out, vjp = jax.vjp(f, *args)
        return tuple(out) + tuple(vjp(cots))

    got, want = run(kernels, args, cots), run(xla, args, cots)
    assert len(got) == len(want) == 13
    for a, w in zip(got, want):
        assert a.shape == w.shape and a.dtype == w.dtype
        _close(a, w, case.get("tol", 2e-5))
    if dtype != "float32":
        exact = run(xla, tuple(a.astype("float32") for a in args),
                    tuple(c.astype("float32") for c in cots))

        def err(a, e):
            a, e = (np.asarray(x, np.float64) for x in (a, e))
            return np.max(np.abs(a - e)) / np.max(np.abs(e))

        for a, w, e in zip(got, want, exact):
            # (g's path is float32 on both: there the float32 cases' room)
            assert err(a, e) <= max(1.1 * err(w, e), 2e-5)
    if case.get("rows"):
        jax.clear_caches()


def test_contrib_kda_counts_its_prepare_path(caplog):
    """`_contrib_kda` through the executor's runner, traced for a TPU: a
    prepare-kernel call at head width 128, a logged fall-back at 64, and
    neither on a program for the CPU."""
    from mxnet_tpu.executor import _build_runner
    from mxnet_tpu.telemetry import registry
    names = (lm.KDA_PREPARE_KERNEL_COUNTER, lm.KDA_PREPARE_FALLBACK_COUNTER)
    h, s = 2, 64
    inputs = ("query", "key", "value", "decay", "beta", "conv_query",
              "conv_key", "conv_value", "A_log", "dt_bias")
    sym = mx.sym._contrib_kda(*(mx.sym.Variable(n) for n in inputs),
                              num_heads=h)

    def traced(d, platform):
        c = h * d
        shapes = [(1, s, c)] * 4 + [(1, s, h)] + [(c, 4)] * 3 + [(h,), (c,)]
        dtypes = ["bfloat16"] * 8 + ["float32"] * 2
        run = _build_runner(sym, True, platform=platform)
        before = [registry.counter(n).value() for n in names]
        jax.eval_shape(
            lambda args: run(args, (), jax.random.PRNGKey(0)),
            tuple(jax.ShapeDtypeStruct(shp, t)
                  for shp, t in zip(shapes, dtypes)))
        return tuple(registry.counter(n).value() - b
                     for n, b in zip(names, before))

    assert traced(128, "cpu") == (0, 0)
    assert traced(128, "tpu") == (1, 0)
    with caplog.at_level("WARNING", logger="mxnet_tpu.ops.lm"):
        assert traced(64, "tpu") == (0, 1)
    assert any("_contrib_kda" in r.getMessage() and "(1, 64, 128)"
               in r.getMessage() for r in caplog.records)


def test_flash_kernels_unequal_head_widths():
    """Latent attention's 192/128 through the Pallas kernels (interpret
    mode), forward and both backward kernels, against explicit softmax."""
    rng = np.random.default_rng(0)
    b, h, s = 1, 2, 256
    q, k = (jnp.asarray(rng.standard_normal((b, h, s, 192)), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.standard_normal((b, h, s, 128)), jnp.float32)
    scale = 192 ** -0.5

    def run(force):
        def f(q, k, v):
            o = attention.flash_attention(q, k, v, causal=True, scale=scale,
                                          force=force, block_q=128,
                                          block_k=128)
            return jnp.sum(jnp.sin(o)), o
        (_, o), g = jax.value_and_grad(f, argnums=(0, 1, 2),
                                       has_aux=True)(q, k, v)
        return (o,) + g

    for got, want in zip(run("interpret"), run("xla")):
        assert got.shape == want.shape
        _close(got, want, 2e-5)


def test_dense_fallback_on_tpu_is_counted():
    from mxnet_tpu.telemetry import registry
    counter = registry.counter(attention.DENSE_FALLBACK_COUNTER)
    x = jnp.ones((1, 1, 16, 40), jnp.float32)      # 40: no kernel takes it
    before = counter.value()
    attention.flash_attention(x, x, x, platform="cpu")
    assert counter.value() == before
    attention.flash_attention(x, x, x, platform="tpu")
    assert counter.value() == before + 1


def _layer(params, li):
    return {k: jnp.asarray(v) for k, v in ref.layer_params(params, li).items()}


def test_mla_block_matches_reference():
    params = ref.init_params(CFG, 5)
    x = np.random.default_rng(2).standard_normal((2, 24, 32)).astype("f4")
    block = mx.gluon.nn.MLAMixer(CFG, prefix="l3_")
    block.collect_params().initialize()
    for name, p in block.collect_params().items():
        p.set_data(mx.nd.array(params[name]))
    want = ref.mla_mixer(CFG, _layer(params, 3), jnp.asarray(x))
    _close(block(mx.nd.array(x)).asnumpy(), want, 2e-5)
    _close(ref.mla_mixer(CFG, _layer(params, 3), jnp.asarray(x), q_block=8),
           want, 2e-6)


def _moe_layer(cfg, p, x, way=None):
    first, n = cfg["experts_held"]
    t = jnp.asarray(x).reshape(-1, x.shape[-1])
    y, stats = lm.moe_experts(
        t, p["w_r"], p["r_bias"], jnp.swapaxes(p["e_gate"], 1, 2),
        jnp.swapaxes(p["e_up"], 1, 2), jnp.swapaxes(p["e_down"], 1, 2),
        first_expert=first, top_k=cfg["num_experts_per_token"],
        scaling=cfg["routed_scaling_factor"],
        renormalize=cfg["moe_renormalize"], force=way)
    return y.reshape(x.shape), stats


def _moe_system(cfg, p, x, way=None):
    y, stats = _moe_layer(cfg, p, x, way)
    return y, np.asarray(stats)


@pytest.mark.parametrize("way", ["xla", "interpret"])
@pytest.mark.parametrize("crowd", [0.0, 10.0],
                         ids=["grouped", "dense_fallback"])
def test_moe_routed_part_matches_reference(crowd, way):
    """The pairs on held experts through grouped products, and through
    the dense path that takes over when they exceed the capacity (here:
    the router's selection bias sends every token to both held experts,
    twice the rows the grouped products have): the same result, nothing
    dropped either way; with the grouped products as `jax.lax.ragged_dot`
    and as the Pallas kernels under the interpreter, the fork around
    them the same."""
    cfg = dict(CFG, num_experts=32, experts_held=[0, 2])
    params = ref.init_params(cfg, 6)
    p = _layer(params, 1)
    bias = np.random.default_rng(0).normal(0, 0.1, 32)
    bias[:2] += crowd
    p["r_bias"] = jnp.asarray(bias, jnp.float32)
    x = np.random.default_rng(3).standard_normal((2, 64, 32)).astype("f4")
    y, stats = _moe_system(cfg, p, x, way)
    _close(y, ref.moe_mlp(cfg, p, jnp.asarray(x), shared=False), 2e-5)
    load, n_pairs, computed, fell_back = (stats[:2], stats[2], stats[3],
                                          stats[4])
    assert load.sum() == n_pairs == computed
    assert fell_back == (crowd > 0)
    if crowd:
        assert n_pairs == 2 * 2 * 64
    fn = lambda x: jnp.sum(jnp.sin(ref.moe_mlp(cfg, p, x, shared=False)))
    fs = lambda x: jnp.sum(jnp.sin(_moe_system(cfg, p, x, way)[0]))
    _close(jax.grad(fs)(jnp.asarray(x)), jax.grad(fn)(jnp.asarray(x)), 5e-5)


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside its equations."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


# tokens (2 sequences of S), experts a token, router, held: T * k is 1, 2,
# 4 and 8 times the grouped rows (`capacity`), as in `lfm2_moe.train`,
# `kanana2.train`, `trinity_mini.train` and `kimi_linear.train`
RATIOS = {1: (32, 4, 16, 4), 2: (32, 4, 16, 2), 4: (64, 4, 64, 4),
          8: (64, 8, 64, 2)}


def _ratio_case(ratio):
    s, k, e, held = RATIOS[ratio]
    cfg = dict(CFG, num_experts=e, num_experts_per_token=k,
               experts_held=[0, held])
    p = _layer(ref.init_params(cfg, 6), 1)
    x = np.random.default_rng(3).standard_normal((2, s, 32)).astype("f4")
    t = 2 * s
    balanced = -(-t * k * held // e)
    capacity = min(t * k, -(-balanced * lm.MOE_CAPACITY // 128) * 128)
    assert t * k == ratio * capacity
    return cfg, p, jnp.asarray(x)


@pytest.mark.parametrize("path", ["xla", "interpret"],
                         ids=["gathers", "kernel"])
@pytest.mark.parametrize("ratio", sorted(RATIOS))
def test_moe_gathers_both_ways_and_rows_of_no_group(ratio, path,
                                                     monkeypatch):
    """At every ratio of T * k to the grouped rows the gradient program of
    the layer holds no scatter-add into an array of the hidden width: rows
    reach the products and their tokens by gathers both ways, XLA's or
    `mx_moe_combine`'s (under the Pallas interpreter). Either way the rows
    that belong to no group may hold anything: planted as NaN, they reach
    no gradient, the router's included, and every gradient is the
    reference's."""
    cfg, p, x = _ratio_case(ratio)
    names = ("w_r", "e_gate", "e_up", "e_down")
    grouped = lm._swiglu_experts

    def unwritten(rows, w_gate, w_up, w_down, group_sizes, **path):
        out = grouped(rows, w_gate, w_up, w_down, group_sizes, **path)
        in_a_group = jnp.arange(out.shape[0]) < jnp.sum(group_sizes)
        return jnp.where(in_a_group[:, None], out, jnp.nan)

    def loss(x, *w, system=True):
        q = dict(p, **dict(zip(names, w)))
        if not system:
            return jnp.sum(jnp.sin(ref.moe_mlp(cfg, q, x, shared=False)))
        return jnp.sum(jnp.sin(_moe_layer(cfg, q, x, path)[0]))

    args = (x,) + tuple(p[n] for n in names)
    assert _moe_system(cfg, p, x)[1][-1] == 0          # the grouped path
    grad = jax.value_and_grad(loss, argnums=range(5))
    jaxpr = jax.make_jaxpr(grad)(*args).jaxpr
    wide = [eqn for eqn in _eqns(jaxpr) if eqn.primitive.name == "scatter-add"
            and eqn.invars[0].aval.shape[-1:] == (32,)]
    assert not wide, wide
    monkeypatch.setattr(lm, "_swiglu_experts", unwritten)
    _, got = grad(*args)
    _, want = jax.value_and_grad(functools.partial(loss, system=False),
                                 argnums=range(5))(*args)
    for name, g, a in zip(("x",) + names, got, want):
        assert np.isfinite(np.asarray(g)).all(), name
        _close(g, a, 5e-5)


@pytest.mark.parametrize("ratio", [1, 8])
def test_gather_combine_is_counted_once_a_trace(ratio):
    """Every grouped layer is traced through the gathers, at every ratio;
    on the CPU its rows reach their tokens through XLA's, so the kernel's
    counters stay where they were."""
    from mxnet_tpu.telemetry import registry
    cfg, p, x = _ratio_case(ratio)
    names = (lm.MOE_GATHER_COUNTER, lm.MOE_COMBINE_COUNTER,
             lm.MOE_COMBINE_FALLBACK_COUNTER)
    before = [registry.counter(n).value() for n in names]
    jax.make_jaxpr(lambda x: _moe_layer(cfg, p, x)[0])(x)
    assert [registry.counter(n).value() - b
            for n, b in zip(names, before)] == [1, 0, 0]


def test_shares_add_up_to_the_uncut_layer():
    """The model-configs guide's share test: the routed parts of the four
    shares (4 experts each) plus the shared expert, counted once, are the
    uncut layer of 16 experts."""
    whole = dict(CFG, experts_held=[0, 16])
    params = ref.init_params(whole, 7)
    p = _layer(params, 2)
    x = np.random.default_rng(4).standard_normal((2, 24, 32)).astype("f4")
    total = np.asarray(ref.moe_mlp(whole, p, jnp.asarray(x), routed=False))
    pairs = 0
    for first in range(0, 16, 4):
        share = dict(p, **{k: p[k][first:first + 4]
                           for k in ("e_gate", "e_up", "e_down")})
        y, stats = _moe_system(dict(CFG, experts_held=[first, 4]), share, x)
        total = total + np.asarray(y)
        pairs += stats[4]
    assert pairs == 2 * 24 * CFG["num_experts_per_token"]
    _close(total, ref.moe_mlp(whole, p, jnp.asarray(x)), 2e-5)


def test_model_logits_and_loss_match_reference():
    params = ref.init_params(CFG, 3)
    tokens, labels = _batch(1)
    net = _net(CFG, params)
    _close(net(mx.nd.array(tokens)).asnumpy(),
           ref.logits(CFG, params, jnp.asarray(tokens)), 2e-5)
    loss, stats = net(mx.nd.array(tokens), mx.nd.array(labels))
    _close(loss.asnumpy(), ref.token_losses(
        CFG, params, jnp.asarray(tokens), jnp.asarray(labels)), 2e-5)
    stats = stats.asnumpy()
    assert stats.shape == (4, 7) and (stats[:, 6] == 0).all()


def _symbol(cfg):
    net = mx.gluon.nn.DecoderLM(cfg, prefix=PREFIX)
    return mx.sym.Group(list(net(mx.sym.Variable("data"),
                                 mx.sym.Variable("label"))))


def _grads_through_executor(params, tokens, labels):
    sym = _symbol(CFG)
    system = ref.system_params(params, PREFIX)
    args = {k: mx.nd.array(v) for k, v in system.items()}
    args["data"] = mx.nd.array(tokens)
    args["label"] = mx.nd.array(labels)
    grads = {k: mx.nd.zeros(v.shape) for k, v in system.items()}
    exe = sym.bind(mx.cpu(), args, args_grad=grads)
    out = exe.forward(is_train=True)
    exe.backward([mx.nd.ones(out[0].shape) / out[0].size,
                  mx.nd.zeros(out[1].shape)])
    return out[0].asnumpy(), {k: g.asnumpy() for k, g in grads.items()}


@functools.lru_cache(maxsize=None)
def _reference_grads():
    params = ref.init_params(CFG, 3)
    tokens, labels = _batch(1)
    return jax.jit(lambda p, t, l: ref.loss_and_grads(CFG, p, t, l))(
        {k: jnp.asarray(v) for k, v in params.items()},
        jnp.asarray(tokens), jnp.asarray(labels))


@pytest.mark.parametrize("mirror", [False, True],
                         ids=["saved", "mirror_stages"])
def test_symbol_gradients_match_reference(mirror, monkeypatch):
    """The graph the trainers run: every parameter's gradient against
    jax.grad of the reference; with MXNET_BACKWARD_DO_MIRROR each layer is
    rematerialised as one stage and nothing changes."""
    if mirror:
        monkeypatch.setenv("MXNET_BACKWARD_DO_MIRROR", "1")
    params = ref.init_params(CFG, 3)
    tokens, labels = _batch(1)
    loss, grads = _grads_through_executor(params, tokens, labels)
    want_loss, want = _reference_grads()
    _close(loss.mean(), want_loss, 1e-5)
    want = ref.system_params({k: np.asarray(v) for k, v in want.items()},
                             PREFIX)
    assert set(grads) == set(want)
    for name in sorted(want):
        if name.endswith("r_bias"):
            assert not grads[name].any()       # selection only: no gradient
        else:
            _close(grads[name], want[name], 2e-3)


def test_fit_fused_adam_reproduces_reference_losses():
    """Module.fit(steps_per_dispatch=2) with adam in fp32: the losses of
    four steps are the reference's plain Adam's, and fall; so are the
    parameters they leave."""
    params = ref.init_params(CFG, 3)
    batches = [_batch(10 + i) for i in range(4)]
    lr = 3e-3
    trained, want = ref.adam_steps(
        CFG, params, [(jnp.asarray(t), jnp.asarray(l)) for t, l in batches],
        lr=lr)
    sym = _symbol(CFG)
    loss_name = sym.list_outputs()[0]
    it = mx.io.NDArrayIter(
        data={"data": np.concatenate([t for t, _ in batches]).astype("f4")},
        label={"label": np.concatenate([l for _, l in batches]).astype("f4")},
        batch_size=2)
    seen, got = [0.0, 0], []

    def watch(param):
        m = param.eval_metric
        got.append((m.sum_metric - seen[0]) / (m.num_inst - seen[1]))
        seen[:] = [m.sum_metric, m.num_inst]
        assert "trainer" in param.locals

    mod = mx.mod.Module(sym, data_names=["data"], label_names=["label"],
                        context=mx.cpu())
    system = ref.system_params(params, PREFIX)
    mod.fit(it, num_epoch=1, optimizer="adam",
            optimizer_params={"learning_rate": lr, "beta1": 0.9,
                              "beta2": 0.95, "epsilon": 1e-8,
                              "rescale_grad": 1.0 / (2 * 40)},
            arg_params={k: mx.nd.array(v) for k, v in system.items()},
            eval_metric=mx.metric.Loss(output_names=[loss_name]),
            batch_end_callback=watch, steps_per_dispatch=2)
    _close(got, [np.mean(want[:2]), np.mean(want[2:])], 2e-5)
    assert got[1] < got[0]
    # Adam divides a gradient by its own size: where one is all rounding
    # its sign is too, so the parameters' CHANGE agrees to a percent, not
    # to rounding; a state left unchanged would read 1
    after = mod.get_params()[0]
    for name, value in ref.system_params(
            {k: np.asarray(v) for k, v in trained.items()}, PREFIX).items():
        if not name.endswith("r_bias"):
            _close(after[name].asnumpy() - system[name],
                   value - system[name], 2e-2)
        else:
            assert not (after[name].asnumpy() - system[name]).any()


def test_amp_leaves_ids_and_fp32_parameters_exact():
    """bf16 holds integers only up to 256 and the decay only to 3 digits:
    under amp the trainer hands ids (float arrays, MXNet's convention) and
    the parameters amp/policy.py lists to the graph as they are."""
    sym = _symbol(CFG)
    exact = amp.exact_variables(sym)
    assert {"data", "label"} <= exact
    assert {n.split("_", 2)[-1] for n in exact - {"data", "label"}} == \
        {"A_log", "dt_bias", "w_r", "r_bias"}
    from mxnet_tpu.parallel.dp import DataParallelTrainer
    from mxnet_tpu.parallel.mesh import mesh_for_contexts
    bits = 9
    table = (np.arange(300)[:, None] >> np.arange(bits)) & 1
    out = mx.sym.Embedding(mx.sym.Variable("data"), mx.sym.Variable("w"),
                           input_dim=300, output_dim=bits)
    trainer = DataParallelTrainer(
        mx.sym.MakeLoss(out), mesh_for_contexts([mx.cpu(0)]),
        data_names=("data",), label_names=(), dtype="bfloat16")
    params, states, aux = trainer.init_state(
        {"data": (4,)}, arg_params={"w": mx.nd.array(table)})
    ids = np.array([255.0, 257.0, 283.0, 299.0], "f4")
    res = trainer.step(params, states, aux, trainer.shard_inputs([ids]))
    got = np.asarray(res[4][0], np.float32)
    assert ((got > 0.5) * (1 << np.arange(bits))).sum(1).tolist() == \
        ids.astype(int).tolist()


def test_reference_copy_is_equal():
    with open(REF_PATH) as a, open(COPY_PATH) as b:
        assert a.read() == b.read()
