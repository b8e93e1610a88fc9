"""An `afmoe` model (Trinity-Mini's keys) against the plain reference
(benchmarks/models/afmoe_reference.py), at a small size on the CPU: the
flash kernels with a sliding window in interpret mode, the blocks they
visit, grouped-query attention in windowed and full layers with the output
gate, the mixture's shares with a shared expert, the sandwich norms and the
scaled embedding through the executor and the fused fit, what the config
may not ask for, and that the other users' graphs are what they were."""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import amp
from mxnet_tpu.gluon.nn import decoder
from mxnet_tpu.ops import attention, lm
from test_kimi_linear import _close, _load
from test_lfm2_moe import CFG as LFM2

HERE = os.path.dirname(os.path.abspath(__file__))
REF_PATH = os.path.join(os.path.dirname(HERE), "benchmarks", "models",
                        "afmoe_reference.py")

ref = _load(REF_PATH, "afmoe_reference_under_test")

CFG = {
    "model_type": "afmoe", "hidden_size": 32, "intermediate_size": 64,
    "num_hidden_layers": 5, "num_dense_layers": 1, "rms_norm_eps": 1e-5,
    "layer_types": ["sliding_attention", "sliding_attention",
                    "full_attention", "sliding_attention",
                    "sliding_attention"],
    "sliding_window": 150, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "rope_theta": 10000,
    "rope_scaling": None, "num_experts": 32, "num_experts_per_tok": 8,
    "moe_intermediate_size": 24, "num_shared_experts": 1,
    "score_func": "sigmoid", "route_norm": True, "route_scale": 2.826,
    "n_group": 1, "num_expert_groups": 1, "topk_group": 1,
    "num_limited_groups": 1, "experts_held": [8, 8], "vocab_size": 300,
    "tie_word_embeddings": False, "mup_enabled": True, "hidden_act": "silu",
    "load_balance_coeff": 0.001,
}
PREFIX = "afmoe_"
# longer than the window, and three blocks of 128 for the flash kernels
SEQ = 384


def _batch(seed, b=1, s=SEQ, vocab=CFG["vocab_size"]):
    tokens = np.random.default_rng(seed).integers(0, vocab, (b, s))
    return tokens, np.roll(tokens, -1, axis=1)


@pytest.fixture(autouse=True)
def _amp_off():
    amp._reset_for_tests()
    yield
    amp._reset_for_tests()


def _layer(params, li):
    return {k: jnp.asarray(v) for k, v in ref.layer_params(params, li).items()}


def _flash_path(monkeypatch, way):
    """Steer every flash attention call of a graph onto `way` (the op has
    no option for it: the test does it)."""
    exact = attention.flash_attention
    monkeypatch.setattr(attention, "flash_attention", lambda *a, **kw: exact(
        *a, **dict(kw, force=way)))


# -- the flash kernels with a window ----------------------------------------------

@pytest.mark.parametrize("way", ["forward", "backward"])
@pytest.mark.parametrize("window", [100, 128, 300, 129, 2],
                         ids=["under_a_block", "one_block", "not_a_multiple",
                              "a_block_and_one", "two_keys"])
def test_windowed_kernels_match_the_dense_oracle(window, way):
    """Both kernels under the Pallas interpreter at this family's grouping,
    8 query heads over one k/v head of 128, three blocks of 128 a side,
    against the dense oracle with the window's mask and k and v repeated by
    group: the output and the gradients of q, k and v."""
    rng = np.random.default_rng(window)
    q = jnp.asarray(rng.standard_normal((1, 8, 384, 128)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((1, 1, 384, 128)), jnp.float32)
            for _ in range(2))
    g = jnp.asarray(rng.standard_normal((1, 8, 384, 128)), jnp.float32)

    def kernels(q, k, v):
        return attention.flash_attention(
            q, k, v, causal=True, scale=128 ** -0.5, force="interpret",
            block_q=128, block_k=128, window=window)

    def oracle(q, k, v):
        return attention.reference_attention(
            q, jnp.repeat(k, 8, axis=1), jnp.repeat(v, 8, axis=1),
            causal=True, scale=128 ** -0.5, window=window)

    if way == "forward":
        _close(kernels(q, k, v), oracle(q, k, v), 2e-5)
        return
    got = jax.vjp(kernels, q, k, v)[1](g)
    want = jax.vjp(oracle, q, k, v)[1](g)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        _close(a, b, 5e-5)


@pytest.mark.parametrize("window", [384, 385, 10_000])
def test_a_window_that_covers_the_sequence_is_the_causal_call(window):
    rng = np.random.default_rng(3)
    q, k, v = (jnp.asarray(rng.standard_normal((1, 2, 384, 64)), jnp.float32)
               for _ in range(3))

    def call(window):
        return jax.value_and_grad(lambda q, k, v: attention.flash_attention(
            q, k, v, causal=True, force="interpret", block_q=128,
            block_k=128, window=window).sum(), argnums=(0, 1, 2))(q, k, v)

    got, want = call(window), call(None)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert (np.asarray(a) == np.asarray(b)).all()


def test_a_window_needs_a_causal_call_and_a_key():
    q = jnp.zeros((1, 1, 256, 64))
    for kwargs in (dict(causal=False, window=16), dict(causal=True, window=0)):
        with pytest.raises(ValueError, match="sliding window"):
            attention.flash_attention(q, q, q, force="xla", **kwargs)


def _visited(s, block, window):
    """{q-block: [(k-block, masked)]} as the kernels' loops visit them."""
    out = {}
    for qi in range(s // block):
        runs = attention._pair_runs(jnp.int32(qi * block), block, block,
                                    s // block, True, window)
        out[qi] = [(i, masked) for first, stop, masked in runs
                   for i in range(int(first), int(stop))]
    return out


@pytest.mark.parametrize("s,block,window", [
    (8192, 512, 2048), (8192, 512, 1000), (2048, 128, 300), (1024, 256, 1),
    (1024, 256, 256)])
def test_the_kernels_visit_the_window_and_nothing_else(s, block, window):
    """Every (q-block, k-block) pair that holds a key some row of the
    q-block sees is visited once, in order, and no other; a pair is
    unmasked only where every row sees every key of it. At the cell's
    shape: 70 pairs of the causal kernels' 136, 28 of them masked."""
    visited = _visited(s, block, window)
    for qi, pairs in visited.items():
        rows = np.arange(qi * block, (qi + 1) * block)[:, None]
        want, masked = [], []
        for kb in range(s // block):
            cols = np.arange(kb * block, (kb + 1) * block)[None, :]
            seen = (rows >= cols) & (rows - cols < window)
            if seen.any():
                want.append(kb)
                masked.append(not seen.all())
        assert [i for i, _ in pairs] == want
        # a mask where one is needed; none on a block every row sees whole
        assert all(m or not need for (_, m), need in zip(pairs, masked))
        assert all(not m for (_, m), need in zip(pairs, masked)
                   if not need)
    if (s, block, window) == (8192, 512, 2048):
        assert sum(map(len, visited.values())) == 70
        assert sum(m for p in visited.values() for _, m in p) == 28
        assert sum(map(len, _visited(s, block, None).values())) == 136


def test_windowed_op_is_scoped_and_counted():
    from mxnet_tpu.telemetry import registry
    counter = registry.counter(attention.WINDOW_COUNTER)
    before = counter.value()
    rng = np.random.default_rng(5)
    q, k, v = (rng.standard_normal((1, 2, 48, 16)).astype("f4")
               for _ in range(3))
    got = mx.nd._contrib_flash_attention(
        mx.nd.array(q), mx.nd.array(k), mx.nd.array(v), causal=True,
        window=20).asnumpy()
    assert counter.value() == before + 1
    _close(got, attention.reference_attention(q, k, v, causal=True,
                                              window=20), 2e-6)
    # a window that covers the sequence, or none, is not counted
    for window in (48, None):
        kw = {} if window is None else {"window": window}
        mx.nd._contrib_flash_attention(mx.nd.array(q), mx.nd.array(k),
                                       mx.nd.array(v), causal=True, **kw)
    assert counter.value() == before + 1
    from mxnet_tpu.ops.registry import OpCtx
    text = jax.jit(lambda q, k, v: attention._flash_attention_op(
        {"causal": True, "scale": None, "window": 20}, OpCtx(), q, k, v)
    ).lower(q, k, v).as_text(debug_info=True)
    assert "mx.flash_attention.window" in text


# -- grouped-query attention, windowed and full -------------------------------------

def _mixer_params(li, seed=5):
    """Weights large enough for the softmax to tell one key from another,
    and head norms that are not all ones."""
    params = ref.init_params(CFG, seed, std=0.3)
    rng = np.random.default_rng(seed)
    for n in ("q_norm", "k_norm"):
        params[f"l{li}_{n}"] = (1 + 0.3 * rng.standard_normal(16)).astype("f4")
    return params


@pytest.mark.parametrize("li,scope", [(0, "mx.swa"), (2, "mx.gqa")])
def test_attention_block_matches_reference(li, scope):
    """A windowed layer (rotated, 150 keys) and the full layer (nothing
    rotated, every earlier key), each with its output gate, against the
    reference; each control (no window, a rotation where there is none or
    none where there is one, no gate) is another model."""
    kind = CFG["layer_types"][li]
    params = _mixer_params(li)
    x = np.random.default_rng(2).standard_normal((1, 200, 32)).astype("f4")
    block = mx.gluon.nn.GQAMixer(CFG, kind=kind, prefix=f"l{li}_")
    block.collect_params().initialize()
    for name, p in block.collect_params().items():
        p.set_data(mx.nd.array(params[name]))
    assert {n.split("_", 1)[1] for n in block.collect_params().keys()} == \
        {"wq", "wk", "wv", "q_norm", "k_norm", "wg", "wo"}
    p = _layer(params, li)
    want = ref.attention(CFG, kind, p, jnp.asarray(x))
    got = block(mx.nd.array(x)).asnumpy()
    _close(got, want, 2e-5)
    _close(ref.attention(CFG, kind, p, jnp.asarray(x), q_block=40), want,
           2e-6)
    sliding = kind == "sliding_attention"
    for other in (dict(window=200 if sliding else 150),
                  dict(rotate=not sliding), dict(gate=False)):
        far = ref.attention(CFG, kind, p, jnp.asarray(x), **other)
        assert np.max(np.abs(got - np.asarray(far))) > \
            1e-2 * np.max(np.abs(got)), other
    ops = [(n.op.name, n.user_attrs.get("profiler_scope"))
           for n in block(mx.sym.Variable("x"))._topo() if n.op is not None]
    flash = [a for a in ops if a[0] == "_contrib_flash_attention"]
    assert flash == [("_contrib_flash_attention", scope)]
    assert [a[1] for a in ops if a[0] == "_contrib_rope"] == \
        ([scope + ".rope"] * 2 if sliding else [])
    assert (scope + ".gate") in {a[1] for a in ops}


# -- the mixture -------------------------------------------------------------------

def test_sixteen_shares_of_eight_add_up_to_the_uncut_layer():
    """The model-configs guide's share test at the published counts: a
    router of 128, 8 a token, sixteen chips with 8 experts each; their
    routed parts with the shared expert counted once are the uncut
    layer's, and every token-expert pair falls on exactly one share."""
    whole = dict(CFG, num_experts=128, experts_held=[0, 128])
    params = ref.init_params(whole, 7)
    p = _layer(params, 1)
    x = np.random.default_rng(4).standard_normal((2, 24, 32)).astype("f4")
    t = jnp.asarray(x).reshape(-1, 32)
    total, pairs = 0.0, 0
    for first in range(0, 128, 8):
        share = dict(p, **{k: p[k][first:first + 8]
                           for k in ("e_gate", "e_up", "e_down")})
        cfg = dict(whole, experts_held=[first, 8])
        y, stats = lm.moe_experts(
            t, share["w_r"], share["r_bias"],
            *(jnp.swapaxes(share[k], 1, 2) for k in ("e_gate", "e_up",
                                                     "e_down")),
            first_expert=first, top_k=8, scaling=2.826, renormalize=True)
        _close(y.reshape(x.shape),
               ref.moe_mlp(cfg, share, jnp.asarray(x), shared=False), 2e-5)
        total = total + np.asarray(y).reshape(x.shape)
        pairs += int(stats[9])
    assert pairs == 2 * 24 * 8
    shared = ref.moe_mlp(whole, p, jnp.asarray(x), routed=False)
    _close(total + np.asarray(shared), ref.moe_mlp(whole, p, jnp.asarray(x)),
           2e-5)


def test_the_fourth_spelling_of_the_mixtures_keys():
    got = decoder.mixture_settings(CFG)
    assert got == {"num_experts": 32, "top_k": 8, "num_shared": 1,
                   "renormalize": True, "scaling": 2.826,
                   "scoring": "sigmoid", "groups": 1, "method": "noaux_tc",
                   "bias": True}
    block = mx.gluon.nn.MoEMLP(CFG, prefix="l1_")
    assert block._attrs["scaling"] == 2.826 and block.shared is not None


# -- the whole model ---------------------------------------------------------------

def _net(cfg, params):
    net = mx.gluon.nn.DecoderLM(cfg, prefix=PREFIX)
    net.collect_params().initialize()
    system = ref.system_params(params, PREFIX)
    assert set(net.collect_params().keys()) == set(system)
    for name, p in net.collect_params().items():
        p.set_data(mx.nd.array(system[name]))
    return net


def test_model_logits_and_loss_match_reference():
    params = ref.init_params(CFG, 3)
    tokens, labels = _batch(1)
    net = _net(CFG, params)
    _close(net(mx.nd.array(tokens)).asnumpy(),
           ref.logits(CFG, params, jnp.asarray(tokens)), 2e-5)
    loss, stats = net(mx.nd.array(tokens), mx.nd.array(labels))
    _close(loss.asnumpy(), ref.token_losses(
        CFG, params, jnp.asarray(tokens), jnp.asarray(labels)), 2e-5)
    assert stats.asnumpy().shape == (4, 8 + 3)
    # the scaled embedding and the sandwich norms are part of the model
    for other in (dict(mup_enabled=False),):
        far = ref.logits(dict(CFG, **other), params, jnp.asarray(tokens))
        assert np.max(np.abs(net(mx.nd.array(tokens)).asnumpy()
                             - np.asarray(far))) > 1e-3


def _symbol(cfg, prefix=PREFIX):
    net = mx.gluon.nn.DecoderLM(cfg, prefix=prefix)
    return mx.sym.Group(list(net(mx.sym.Variable("data"),
                                 mx.sym.Variable("label"))))


@functools.lru_cache(maxsize=None)
def _reference_grads():
    params = ref.init_params(CFG, 3)
    tokens, labels = _batch(1)
    return jax.jit(lambda p, t, l: ref.loss_and_grads(CFG, p, t, l))(
        {k: jnp.asarray(v) for k, v in params.items()},
        jnp.asarray(tokens), jnp.asarray(labels))


@functools.lru_cache(maxsize=None)
def _system_grads(way):
    params = ref.init_params(CFG, 3)
    tokens, labels = _batch(1)
    system = ref.system_params(params, PREFIX)
    args = {k: mx.nd.array(v) for k, v in system.items()}
    args["data"] = mx.nd.array(tokens)
    args["label"] = mx.nd.array(labels)
    grads = {k: mx.nd.zeros(v.shape) for k, v in system.items()}
    exe = _symbol(CFG).bind(mx.cpu(), args, args_grad=grads)
    out = exe.forward(is_train=True)
    exe.backward([mx.nd.ones(out[0].shape) / out[0].size,
                  mx.nd.zeros(out[1].shape)])
    return out[0].asnumpy(), {k: g.asnumpy() for k, g in grads.items()}


PARAMETERS = sorted(PREFIX + name for name in ref.param_shapes(CFG))


@pytest.mark.parametrize("way", ["xla", "interpret"])
@pytest.mark.parametrize("name", PARAMETERS)
def test_symbol_gradient_matches_reference(name, way, monkeypatch):
    """The graph the trainers run, every layer a mirror stage: each
    parameter's gradient against jax.grad of the reference, with the
    attention's cores on the dense path (a CPU program's) and through the
    flash kernels under the interpreter, windowed in four layers."""
    monkeypatch.setenv("MXNET_BACKWARD_DO_MIRROR", "1")
    _flash_path(monkeypatch, way)
    loss, grads = _system_grads(way)
    want_loss, want = _reference_grads()
    _close(loss.mean(), want_loss, 1e-5)
    want = ref.system_params({k: np.asarray(v) for k, v in want.items()},
                             PREFIX)
    assert set(grads) == set(want)
    if name.endswith("r_bias"):
        assert not grads[name].any()            # selection only: no gradient
    else:
        _close(grads[name], want[name], 2e-3)


def test_fit_fused_adam_reproduces_reference_losses():
    """Module.fit(steps_per_dispatch=2) with adam in fp32: the losses of
    four steps (two batches, seen twice) are the reference's plain Adam's,
    and fall."""
    params = ref.init_params(CFG, 3)
    batches = [_batch(10 + i % 2, s=160) for i in range(4)]
    lr = 3e-3
    _, want = ref.adam_steps(
        CFG, params, [(jnp.asarray(t), jnp.asarray(l)) for t, l in batches],
        lr=lr)
    sym = _symbol(CFG)
    loss_name = sym.list_outputs()[0]
    it = mx.io.NDArrayIter(
        data={"data": np.concatenate([t for t, _ in batches]).astype("f4")},
        label={"label": np.concatenate([l for _, l in batches]).astype("f4")},
        batch_size=1)
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
                              "rescale_grad": 1.0 / 160},
            arg_params={k: mx.nd.array(v) for k, v in system.items()},
            eval_metric=mx.metric.Loss(output_names=[loss_name]),
            batch_end_callback=watch, steps_per_dispatch=2)
    _close(got, [np.mean(want[:2]), np.mean(want[2:])], 2e-5)
    assert got[1] < got[0]


# -- what a config may not ask for ---------------------------------------------------

@pytest.mark.parametrize("key,value", [
    ("layer_types", ["sliding_attention", "chunked_attention",
                     "full_attention", "sliding_attention",
                     "sliding_attention"]),
    ("rope_scaling", {"type": "yarn", "factor": 4}),
    ("score_func", "softmax"), ("num_expert_groups", 4),
    ("hidden_act", "gelu"),
])
def test_what_no_layer_computes_raises_by_the_keys_name(key, value):
    with pytest.raises(NotImplementedError, match=key):
        mx.gluon.nn.DecoderLM(dict(CFG, **{key: value}), prefix=PREFIX)


@pytest.mark.parametrize("key,value", [("index_n_heads", 32),
                                       ("index_topk", 2048)])
def test_latent_attention_refuses_a_learned_indexer(key, value):
    from test_deepseek_v3 import CFG as DSV3
    with pytest.raises(NotImplementedError, match=key):
        mx.gluon.nn.DecoderLM(dict(DSV3, **{key: value}), prefix="x_")


def test_a_window_without_a_windowed_layer_raises():
    with pytest.raises(NotImplementedError, match="sliding_window"):
        mx.gluon.nn.DecoderLM(dict(LFM2, sliding_window=4096), prefix="x_")


# -- the other users' graphs ----------------------------------------------------------

def test_other_users_graphs_hold_what_they_held():
    """An `lfm2_moe` config reaches none of this family's pieces: no
    window, no gate, no sandwich norm, no scaled embedding, the rotation
    in its full layer; this family's graph has four windowed calls and one
    full, and rotates in the windowed layers alone."""
    lfm2 = [n for n in _symbol(LFM2, "other_")._topo() if n.op is not None]
    flash = [n for n in lfm2 if n.op.name == "_contrib_flash_attention"]
    assert len(flash) == 1 and flash[0].attrs.get("window") is None
    assert not any(n.user_attrs.get("profiler_scope", "").startswith(
        ("mx.swa", "mx.gqa.gate")) for n in lfm2)
    args = _symbol(LFM2, "other_").list_arguments()
    assert not any(a.endswith(("_wg", "post_norm1", "post_norm2"))
                   for a in args)
    mine = [n for n in _symbol(CFG)._topo() if n.op is not None]
    assert [n.attrs.get("window") for n in mine
            if n.op.name == "_contrib_flash_attention"] == \
        [150, 150, None, 150, 150]
    assert [n.user_attrs.get("profiler_scope") for n in mine
            if n.op.name == "_contrib_rope"] == ["mx.swa.rope"] * 8
    assert sum(n.op.name == "_contrib_moe_experts" for n in mine) == 4
