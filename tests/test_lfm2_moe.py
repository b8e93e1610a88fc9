"""An `lfm2_moe` model (LFM2-8B-A1B's keys) against the plain reference
(tests/reference_models/lfm2_moe.py), at a small size on the CPU: the gated
short convolution op, grouped-query attention through the flash kernels in
interpret mode at heads of 64, the mixture without a shared expert and its
four shares, the tied head, the whole model through the executor and the
fused fit, what the config may not ask for, and that the other two users'
graphs are what they were."""
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
from test_deepseek_v3 import CFG as DSV3
from test_kimi_linear import CFG as KIMI, _close, _load

HERE = os.path.dirname(os.path.abspath(__file__))
REF_PATH = os.path.join(HERE, "reference_models", "lfm2_moe.py")
COPY_PATH = os.path.join(os.path.dirname(HERE), "benchmarks", "models",
                         "lfm2_moe_reference.py")

ref = _load(REF_PATH, "lfm2_moe_reference_under_test")

CFG = {
    "model_type": "lfm2_moe", "hidden_size": 32, "intermediate_size": 64,
    "num_hidden_layers": 5, "num_dense_layers": 1, "norm_eps": 1e-5,
    "layer_types": ["conv", "full_attention", "conv", "conv", "conv"],
    "conv_L_cache": 3, "conv_bias": False, "num_attention_heads": 4,
    "num_key_value_heads": 2, "rope_theta": 1000000, "num_experts": 16,
    "num_experts_per_tok": 4, "moe_intermediate_size": 24,
    "norm_topk_prob": True, "use_expert_bias": True,
    "routed_scaling_factor": 1, "experts_held": [4, 4], "vocab_size": 300,
    "tie_word_embeddings": True,
}
PREFIX = "lfm2_"


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


def _layer(params, li):
    return {k: jnp.asarray(v) for k, v in ref.layer_params(params, li).items()}


# -- the gated short convolution ------------------------------------------------

def _loop_conv(x, w):
    """y[b, t, c] = C * sum_j w[c, j] * (B * u)[b, t - (kw - 1) + j], one
    element at a time, float64."""
    x, w = np.asarray(x, np.float64), np.asarray(w, np.float64)
    c, kw = w.shape
    b_gate, c_gate, u = x[..., :c], x[..., c:2 * c], x[..., 2 * c:]
    z = b_gate * u
    y = np.zeros_like(z)
    for t in range(x.shape[1]):
        for j in range(kw):
            if t - (kw - 1) + j >= 0:
                y[:, t] += w[:, j] * z[:, t - (kw - 1) + j]
    return c_gate * y


def _loop_conv_grads(x, w, dy):
    """Gradients of sum(y * dy) by the definition, float64."""
    x, w, dy = (np.asarray(a, np.float64) for a in (x, w, dy))
    c, kw = w.shape
    b_gate, c_gate, u = x[..., :c], x[..., c:2 * c], x[..., 2 * c:]
    z, dc = b_gate * u, dy * c_gate
    conv, dz, dw = np.zeros_like(z), np.zeros_like(z), np.zeros_like(w)
    for t in range(x.shape[1]):
        for j in range(kw):
            src = t - (kw - 1) + j
            if src >= 0:
                conv[:, t] += w[:, j] * z[:, src]
                dz[:, src] += w[:, j] * dc[:, t]
                dw[:, j] += np.sum(dc[:, t] * z[:, src], axis=0)
    return np.concatenate([dz * u, dy * conv, dz * b_gate], -1), dw


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-6), ("bfloat16", 2e-2)])
@pytest.mark.parametrize("kw,seq", [(3, 24), (3, 2), (3, 1), (1, 5), (4, 9)],
                         ids=["kernel3", "shorter_than_the_kernel",
                              "one_token", "one_tap", "kernel4"])
def test_gated_short_conv_matches_an_explicit_loop(kw, seq, dtype, tol):
    """Values and all gradients, at kernel 3 and at the sequence's ends:
    the first kw - 1 tokens see zeros before them (a sequence shorter than
    the kernel sees nothing else), and the last token's cotangent reaches
    kw tokens back and none forward."""
    rng = np.random.default_rng(kw * 100 + seq)
    x = jnp.asarray(rng.standard_normal((2, seq, 3 * 8)), dtype)
    w = jnp.asarray(rng.standard_normal((8, kw)), jnp.float32)
    dy = jnp.asarray(rng.standard_normal((2, seq, 8)), dtype)
    y, vjp = jax.vjp(lm.gated_short_conv, x, w)
    dx, dw = vjp(dy)
    assert y.dtype == x.dtype and dx.dtype == x.dtype
    assert dw.dtype == jnp.float32 and dw.shape == w.shape
    _close(y, _loop_conv(x, w), tol)
    want_dx, want_dw = _loop_conv_grads(x, w, dy)
    _close(dx, want_dx, tol)
    _close(dw, want_dw, tol)
    # causal: a later token changes no earlier output
    if seq > 1:
        moved = x.at[:, -1].add(1.0)
        assert (np.asarray(lm.gated_short_conv(moved, w)[:, :-1]) ==
                np.asarray(y[:, :-1])).all()


def test_gated_short_conv_op_is_registered_counted_and_refuses_shapes():
    from mxnet_tpu.telemetry import registry
    counter = registry.counter(lm.SHORT_CONV_COUNTER)
    before = counter.value()
    x = np.random.default_rng(1).standard_normal((2, 6, 12)).astype("f4")
    w = np.random.default_rng(2).standard_normal((4, 3)).astype("f4")
    out = mx.nd._contrib_gated_short_conv(mx.nd.array(x), mx.nd.array(w),
                                          kernel=3).asnumpy()
    assert counter.value() == before + 1
    _close(out, _loop_conv(x, w), 2e-6)
    for bad in (dict(kernel=2), dict(kernel=lm.SHORT_CONV_MAX_TAPS + 1)):
        with pytest.raises(Exception, match="_contrib_gated_short_conv"):
            mx.nd._contrib_gated_short_conv(mx.nd.array(x), mx.nd.array(w),
                                            **bad)
    assert "_contrib_gated_short_conv" in amp.MIXED
    assert amp.KEEP_FP32["_contrib_gated_short_conv"] == ("weight",)


def test_conv_block_matches_reference():
    params = ref.init_params(CFG, 5, std=0.3)
    x = np.random.default_rng(2).standard_normal((2, 24, 32)).astype("f4")
    block = mx.gluon.nn.ShortConvMixer(CFG, prefix="l0_")
    block.collect_params().initialize()
    for name, p in block.collect_params().items():
        p.set_data(mx.nd.array(params[name]))
    _close(block(mx.nd.array(x)).asnumpy(),
           ref.conv_operator(CFG, _layer(params, 0), jnp.asarray(x)), 2e-5)


# -- grouped-query attention ----------------------------------------------------

@pytest.mark.parametrize("way", ["forward", "backward"])
def test_flash_kernels_at_8_over_2_heads_of_64(way):
    """The three flash kernels under the Pallas interpreter at the grouped
    shape of this family, scaled down in counts only: 8 query heads over 2
    k/v heads of 64 (a group of 4, as 32 over 8), against the dense oracle
    with k and v repeated by group; dK and dV are the sums over a group."""
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((1, 8, 256, 64)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((1, 2, 256, 64)), jnp.float32)
            for _ in range(2))
    g = jnp.asarray(rng.standard_normal((1, 8, 256, 64)), jnp.float32)

    def kernels(q, k, v):
        return attention.flash_attention(q, k, v, causal=True,
                                         scale=64 ** -0.5, force="interpret",
                                         block_q=128, block_k=128)

    def oracle(q, k, v):
        return attention.reference_attention(
            q, jnp.repeat(k, 4, axis=1), jnp.repeat(v, 4, axis=1),
            causal=True, scale=64 ** -0.5)

    if way == "forward":
        _close(kernels(q, k, v), oracle(q, k, v), 2e-5)
        return
    got = jax.vjp(kernels, q, k, v)[1](g)
    want = jax.vjp(oracle, q, k, v)[1](g)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        _close(a, b, 5e-5)


def test_gqa_block_matches_reference_and_is_counted():
    from mxnet_tpu.telemetry import registry
    counter = registry.counter(attention.GQA_COUNTER)
    before = counter.value()
    # weights large enough for the softmax to tell one key from another
    params = ref.init_params(CFG, 5, std=0.3)
    params["l1_q_norm"] = 1 + 0.3 * np.random.default_rng(8) \
        .standard_normal(8).astype("f4")
    params["l1_k_norm"] = 1 + 0.3 * np.random.default_rng(9) \
        .standard_normal(8).astype("f4")
    x = np.random.default_rng(2).standard_normal((2, 24, 32)).astype("f4")
    block = mx.gluon.nn.GQAMixer(CFG, prefix="l1_")
    block.collect_params().initialize()
    for name, p in block.collect_params().items():
        p.set_data(mx.nd.array(params[name]))
    p = _layer(params, 1)
    want = ref.gqa_operator(CFG, p, jnp.asarray(x))
    got = block(mx.nd.array(x)).asnumpy()
    _close(got, want, 2e-5)
    assert counter.value() == before + 1
    _close(ref.gqa_operator(CFG, p, jnp.asarray(x), q_block=8), want, 2e-6)
    # heads grouped the other way (query head h to k/v head h % 2) is
    # another model, and so is one without the head norms
    other = ref.gqa_operator(
        CFG, dict(p, wk=p["wk"].reshape(2, 8, 32)[::-1].reshape(16, 32)),
        jnp.asarray(x))
    assert np.max(np.abs(got - np.asarray(other))) > \
        1e-2 * np.max(np.abs(got))
    plain = ref.gqa_operator(CFG, dict(p, q_norm=jnp.ones(8)), jnp.asarray(x))
    assert np.max(np.abs(got - np.asarray(plain))) > \
        1e-2 * np.max(np.abs(got))


# -- the mixture ---------------------------------------------------------------

def _moe_system(cfg, p, x):
    first, n = cfg["experts_held"]
    t = jnp.asarray(x).reshape(-1, x.shape[-1])
    y, stats = lm.moe_experts(
        t, p["w_r"], p["r_bias"], jnp.swapaxes(p["e_gate"], 1, 2),
        jnp.swapaxes(p["e_up"], 1, 2), jnp.swapaxes(p["e_down"], 1, 2),
        first_expert=first, top_k=cfg["num_experts_per_tok"],
        scaling=cfg["routed_scaling_factor"],
        renormalize=cfg["norm_topk_prob"])
    return y.reshape(x.shape), np.asarray(stats)


def test_four_shares_of_eight_add_up_to_the_uncut_layer():
    """The model-configs guide's share test at the published counts: a
    router of 32, 4 a token, four chips with 8 experts each
    (`first_expert_held` 0, 8, 16, 24); their routed parts are the uncut
    layer. Nothing is counted once: there is no shared expert. Every
    token-expert pair falls on exactly one share, and at 8 of 32 with 4 a
    token the grouped products' capacity is every pair, so no share can
    take the dense path."""
    whole = dict(CFG, num_experts=32, experts_held=[0, 32])
    params = ref.init_params(whole, 7)
    p = _layer(params, 1)
    x = np.random.default_rng(4).standard_normal((2, 24, 32)).astype("f4")
    total, pairs = 0.0, 0
    for first in (0, 8, 16, 24):
        share = dict(p, **{k: p[k][first:first + 8]
                           for k in ("e_gate", "e_up", "e_down")})
        cfg = dict(whole, experts_held=[first, 8])
        y, stats = _moe_system(cfg, share, x)
        _close(y, ref.moe_mlp(cfg, share, jnp.asarray(x)), 2e-5)
        total = total + np.asarray(y)
        pairs += stats[8]
        assert stats[10] == 0                   # the grouped path
    assert pairs == 2 * 24 * 4
    _close(total, ref.moe_mlp(whole, p, jnp.asarray(x)), 2e-5)


def test_no_shared_branch_is_built_at_zero_shared_experts():
    block = mx.gluon.nn.MoEMLP(dict(CFG), prefix="l1_")
    assert block.shared is None
    assert {n[len("l1_"):] for n in block.collect_params().keys()} == \
        {"w_r", "r_bias", "e_gate", "e_up", "e_down"}
    ops = [n.op.name for n in block(mx.sym.Variable("x"))[0]._topo()
           if n.op is not None]
    assert ops == ["_contrib_moe_experts"]
    # a config that has shared experts still gets its branch
    shared = mx.gluon.nn.MoEMLP(DSV3, prefix="l1_")
    assert shared.shared is not None


def test_the_third_spelling_of_the_mixtures_keys():
    got = decoder.mixture_settings(CFG)
    assert got == {"num_experts": 16, "top_k": 4, "num_shared": 0,
                   "renormalize": True, "scaling": 1, "scoring": "sigmoid",
                   "groups": 1, "method": "noaux_tc", "bias": True}
    assert decoder.mixture_settings(DSV3)["num_shared"] == 2
    assert decoder.mixture_settings(KIMI)["bias"] is True


@pytest.mark.parametrize("family,key", [
    ("DSV3", "n_shared_experts"), ("KIMI", "num_shared_experts")])
def test_only_the_third_spelling_may_be_silent_on_shared_experts(family, key):
    """LFM2's family has no key for shared experts; a Kimi or DeepSeek-V3
    config that lacks its own is a broken config, not a model without."""
    cfg = {k: v for k, v in globals()[family].items() if k != key}
    with pytest.raises(KeyError, match=key):
        decoder.mixture_settings(cfg)


# -- the whole model --------------------------------------------------------------

def test_model_logits_and_loss_match_reference():
    params = ref.init_params(CFG, 3)
    tokens, labels = _batch(1)
    net = _net(CFG, params)
    assert "lfm2_head" not in net.collect_params().keys()
    _close(net(mx.nd.array(tokens)).asnumpy(),
           ref.logits(CFG, params, jnp.asarray(tokens)), 2e-5)
    loss, stats = net(mx.nd.array(tokens), mx.nd.array(labels))
    _close(loss.asnumpy(), ref.token_losses(
        CFG, params, jnp.asarray(tokens), jnp.asarray(labels)), 2e-5)
    stats = stats.asnumpy()
    assert stats.shape == (4, 7) and (stats[:, 6] == 0).all()


def _symbol(cfg, prefix=PREFIX):
    net = mx.gluon.nn.DecoderLM(cfg, prefix=prefix)
    return mx.sym.Group(list(net(mx.sym.Variable("data"),
                                 mx.sym.Variable("label"))))


def _grads_through_executor(sym, system, tokens, labels):
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


@functools.lru_cache(maxsize=None)
def _system_grads(mirror, way):
    params = ref.init_params(CFG, 3)
    tokens, labels = _batch(1)
    return _grads_through_executor(
        _symbol(CFG), ref.system_params(params, PREFIX), tokens, labels)


PARAMETERS = sorted(PREFIX + name for name in ref.param_shapes(CFG))


@pytest.mark.parametrize("way", ["xla", "interpret"])
@pytest.mark.parametrize("mirror", [False, True],
                         ids=["saved", "mirror_stages"])
@pytest.mark.parametrize("name", PARAMETERS)
def test_symbol_gradient_matches_reference(name, mirror, way, monkeypatch):
    """The graph the trainers run: each parameter's gradient against
    jax.grad of the reference; with MXNET_BACKWARD_DO_MIRROR each layer is
    rematerialised as one stage and nothing changes; with the mixture
    layers' grouped products as `jax.lax.ragged_dot` (a CPU program's path)
    and as the Pallas kernels under the interpreter (steered here, in the
    test: the op has no option for it)."""
    if mirror:
        monkeypatch.setenv("MXNET_BACKWARD_DO_MIRROR", "1")
    grouped = lm._swiglu_experts
    monkeypatch.setattr(lm, "_swiglu_experts", lambda *a, **path: grouped(
        *a, **dict(path, force=way)))
    loss, grads = _system_grads(mirror, way)
    want_loss, want = _reference_grads()
    _close(loss.mean(), want_loss, 1e-5)
    want = ref.system_params({k: np.asarray(v) for k, v in want.items()},
                             PREFIX)
    assert set(grads) == set(want)
    if name.endswith("r_bias"):
        assert not grads[name].any()            # selection only: no gradient
    else:
        _close(grads[name], want[name], 2e-3)


def test_tied_heads_gradient_is_the_sum_of_its_two_uses():
    """`embed` is read by the embedding and by the head. With the head
    untied (the same model but for `tie_word_embeddings`, the head's
    weights set equal to the embedding's) the two uses have a gradient
    each, and their sum is the tied parameter's."""
    params = ref.init_params(CFG, 3)
    tokens, labels = _batch(1)
    system = ref.system_params(params, PREFIX)
    _, tied = _grads_through_executor(_symbol(CFG), system, tokens, labels)
    untied_cfg = dict(CFG, tie_word_embeddings=False)
    _, untied = _grads_through_executor(
        _symbol(untied_cfg), dict(system, lfm2_head=system["lfm2_embed"]),
        tokens, labels)
    assert "lfm2_head" not in tied
    assert np.abs(untied["lfm2_head"]).max() > 0 and \
        np.abs(untied["lfm2_embed"]).max() > 0
    _close(tied["lfm2_embed"], untied["lfm2_embed"] + untied["lfm2_head"],
           1e-5)


def test_fit_fused_adam_reproduces_reference_losses():
    """Module.fit(steps_per_dispatch=2) with adam in fp32: the losses of
    four steps (two batches, seen twice) are the reference's plain Adam's,
    and fall; so are the parameters they leave, the tied one among them."""
    params = ref.init_params(CFG, 3)
    batches = [_batch(10 + i % 2) for i in range(4)]
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
    after = mod.get_params()[0]
    for name, value in ref.system_params(
            {k: np.asarray(v) for k, v in trained.items()}, PREFIX).items():
        if not name.endswith("r_bias"):
            _close(after[name].asnumpy() - system[name],
                   value - system[name], 2e-2)
        else:
            assert not (after[name].asnumpy() - system[name]).any()


def test_amp_keeps_router_and_taps_in_fp32_and_ids_exact():
    exact = amp.exact_variables(_symbol(CFG))
    assert {"data", "label"} <= exact
    assert {n.split("_", 2)[-1] for n in exact - {"data", "label"}} == \
        {"w_r", "r_bias", "taps"}


# -- what a config may not ask for -------------------------------------------------

@pytest.mark.parametrize("key,value", [
    ("conv_bias", True),
    # (a windowed layer is computed since the `afmoe` family: a kind that
    # no layer computes)
    ("layer_types", ["conv", "chunked_attention", "conv", "conv", "conv"]),
    ("use_expert_bias", False),
    ("conv_L_cache", lm.SHORT_CONV_MAX_TAPS + 1), ("conv_L_cache", 0),
    ("rope_scaling", {"type": "yarn", "factor": 4}),
    ("attention_bias", True), ("scoring_func", "softmax"), ("n_group", 4),
])
def test_what_no_layer_computes_raises_by_the_keys_name(key, value):
    with pytest.raises(NotImplementedError, match=key):
        mx.gluon.nn.DecoderLM(dict(CFG, **{key: value}), prefix=PREFIX)


# -- the other two users' graphs ------------------------------------------------------

@pytest.mark.parametrize("family", ["kimi_linear", "deepseek_v3"])
def test_other_users_graphs_hold_the_operations_they_held(family):
    """Neither a Kimi-Linear nor a `deepseek_v3` config reaches a layer of
    this family: no gated convolution, every attention call at equal head
    counts, an untied head, the shared expert's branch where it was; and
    this family's graph has none of theirs."""
    cfg = {"kimi_linear": KIMI, "deepseek_v3": DSV3}[family]
    nodes = [n for n in _symbol(cfg, "other_")._topo() if n.op is not None]
    names = [n.op.name for n in nodes]
    assert "_contrib_gated_short_conv" not in names
    assert "other_head" in _symbol(cfg, "other_").list_arguments()
    mixture_layers = names.count("_contrib_moe_experts")
    assert mixture_layers and sum(
        n.user_attrs.get("profiler_scope") == "mx.moe.shared" and
        n.op.name == "FullyConnected" for n in nodes) == 3 * mixture_layers
    mine = [n.op.name for n in _symbol(CFG)._topo() if n.op is not None]
    assert mine.count("_contrib_gated_short_conv") == 4
    assert mine.count("_contrib_flash_attention") == 1
    assert mine.count("_contrib_rope") == 2 and "_contrib_kda" not in mine
    assert mine.count("_contrib_moe_experts") == 4
    assert "lfm2_head" not in _symbol(CFG).list_arguments()


def test_the_two_reference_files_are_equal():
    with open(REF_PATH) as a, open(COPY_PATH) as b:
        assert a.read() == b.read()
