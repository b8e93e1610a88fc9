"""A `deepseek_v3` model (Kanana-2-30B-A3B's keys) against the plain
reference (tests/reference_models/deepseek_v3.py), at a small size on the
CPU: the rotary embedding op, latent attention with its rotated key, the
mixture in DeepSeek-V3's spelling and its eight shares, the whole model,
the fused fit with adam, what the config may not ask for, and that
Kimi-Linear's graph is what it was."""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import amp
from mxnet_tpu.ops import lm
from test_kimi_linear import CFG as KIMI, _close, _load, ref as kimi_ref

HERE = os.path.dirname(os.path.abspath(__file__))
REF_PATH = os.path.join(HERE, "reference_models", "deepseek_v3.py")
COPY_PATH = os.path.join(os.path.dirname(HERE), "benchmarks", "models",
                         "deepseek_v3_reference.py")


ref = _load(REF_PATH, "deepseek_v3_reference_under_test")

CFG = {
    "model_type": "deepseek_v3", "hidden_size": 32, "intermediate_size": 64,
    "num_hidden_layers": 3, "first_k_dense_replace": 1, "rms_norm_eps": 1e-6,
    "num_attention_heads": 2, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "kv_lora_rank": 24, "q_lora_rank": None,
    "rope_theta": 1000000, "rope_interleave": True, "rope_scaling": None,
    "attention_bias": False, "n_routed_experts": 16,
    "num_experts_per_tok": 3, "n_shared_experts": 2,
    "moe_intermediate_size": 24, "norm_topk_prob": True,
    "routed_scaling_factor": 2.448, "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
    "experts_held": [4, 4], "vocab_size": 300,
}
PREFIX = "dsv3_"


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


def _reference_rope(x, rotary_dim, offset, theta, interleave):
    """The reference's rotation laid over x (B, H, S, W) as the op sees it:
    dims offset..offset + rotary_dim turned, the rest passed through."""
    turned = ref.rope(jnp.moveaxis(x[..., offset:offset + rotary_dim], 2, 1),
                      theta, interleave)
    return jnp.concatenate([x[..., :offset], jnp.moveaxis(turned, 1, 2),
                            x[..., offset + rotary_dim:]], -1)


@pytest.mark.parametrize("dtype,tol", [("float32", (1e-6, 1e-6)),
                                       ("bfloat16", (4e-3, 2e-2))])
@pytest.mark.parametrize("offset", [0, 16], ids=["whole", "inside_the_head"])
@pytest.mark.parametrize("interleave", [True, False],
                         ids=["interleaved", "half_split"])
def test_rope_matches_the_reference_rotation(interleave, offset, dtype, tol):
    """Forward and gradient, both pairings, the rotated dims at the head's
    start and inside it (16 passed through before, 8 after); bf16 operands
    against the float32 rotation of the same values: the result is rounded
    once, the angles never (the test's cotangent is the cosine of that
    rounded result, hence the gradient's wider limit)."""
    rng = np.random.default_rng(0)
    rotary = 8
    width = rotary if not offset else offset + rotary + 8
    x = jnp.asarray(rng.standard_normal((2, 3, 40, width)), dtype)
    exact = x.astype("float32")

    def run(f, x):
        return f(x), jax.grad(
            lambda x: jnp.sum(jnp.sin(f(x).astype("float32"))))(x)

    got = run(lambda x: lm.rope(x, rotary, offset, 1e6, interleave), x)
    want = run(lambda x: _reference_rope(x, rotary, offset, 1e6, interleave),
               exact)
    for a, w, limit in zip(got, want, tol):
        assert a.dtype == x.dtype and a.shape == x.shape
        _close(a, w, limit)
    if offset:      # what is not rotated passes through to the bit
        for lo, hi in ((0, offset), (offset + rotary, width)):
            assert (np.asarray(got[0][..., lo:hi]) ==
                    np.asarray(x[..., lo:hi])).all()
    # the rotation keeps every pair's length: no angle can be wrong alone
    if dtype == "float32":
        pairs = np.asarray(got[0][..., offset:offset + rotary]) ** 2
        before = np.asarray(x[..., offset:offset + rotary]) ** 2
        _close(pairs.sum(-1), before.sum(-1), 1e-6)


def test_rope_op_is_registered_counted_and_position_dependent():
    from mxnet_tpu.telemetry import registry
    counter = registry.counter(lm.ROPE_COUNTER)
    before = counter.value()
    x = np.random.default_rng(1).standard_normal((1, 2, 6, 12)).astype("f4")
    out = mx.nd._contrib_rope(mx.nd.array(x), rotary_dim=4, offset=8,
                              theta=100.0, interleave=True).asnumpy()
    assert counter.value() == before + 1
    assert (out[:, :, 0] == x[:, :, 0]).all()          # position 0: angle 0
    assert (out[..., :8] == x[..., :8]).all()
    assert not np.allclose(out[:, :, 1:, 8:], x[:, :, 1:, 8:])
    a = 3 * 100.0 ** (-2 / 4)                   # position 3, pair 1
    _close(out[0, 0, 3, 10], x[0, 0, 3, 10] * np.cos(a)
           - x[0, 0, 3, 11] * np.sin(a), 1e-6)
    with pytest.raises(Exception, match="_contrib_rope"):
        mx.nd._contrib_rope(mx.nd.array(x), rotary_dim=6, offset=8)
    assert "_contrib_rope" in amp.MIXED


def _layer(params, li):
    return {k: jnp.asarray(v) for k, v in ref.layer_params(params, li).items()}


@pytest.mark.parametrize("interleave", [True, False],
                         ids=["interleaved", "half_split"])
def test_mla_block_with_rotation_matches_reference(interleave):
    cfg = dict(CFG, rope_interleave=interleave)
    # weights large enough for the softmax to tell one key from another
    params = ref.init_params(cfg, 5, std=0.3)
    x = np.random.default_rng(2).standard_normal((2, 24, 32)).astype("f4")
    block = mx.gluon.nn.MLAMixer(cfg, prefix="l1_")
    block.collect_params().initialize()
    for name, p in block.collect_params().items():
        p.set_data(mx.nd.array(params[name]))
    want = ref.mla_mixer(cfg, _layer(params, 1), jnp.asarray(x))
    got = block(mx.nd.array(x)).asnumpy()
    _close(got, want, 2e-5)
    _close(ref.mla_mixer(cfg, _layer(params, 1), jnp.asarray(x), q_block=8),
           want, 2e-6)
    # the rotation is there: the other pairing, and none, are other models
    other = ref.mla_mixer(dict(cfg, rope_interleave=not interleave),
                          _layer(params, 1), jnp.asarray(x))
    assert np.max(np.abs(got - np.asarray(other))) > \
        1e-2 * np.max(np.abs(got))


def test_rotated_scores_depend_on_distance_only():
    """What the rotation is for: q_p . k_r after it is a function of p - r,
    so shifting both positions leaves a score where it was."""
    rng = np.random.default_rng(3)
    q, k = (jnp.asarray(np.broadcast_to(
        rng.standard_normal((1, 1, 1, 8)), (1, 1, 12, 8)), jnp.float32)
        for _ in range(2))
    scores = np.asarray(jnp.einsum("bhqd,bhkd->bhqk",
                                   lm.rope(q, 8, 0, 100.0, True),
                                   lm.rope(k, 8, 0, 100.0, True)))[0, 0]
    for shift in range(1, 6):
        _close(np.diagonal(scores, -shift),
               np.full(12 - shift, scores[shift, 0]), 1e-5)


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


def test_eight_shares_of_sixteen_add_up_to_the_uncut_layer():
    """The model-configs guide's share test at the published counts: a
    router of 128, 6 a token, eight chips with 16 experts each; their
    routed parts plus the two shared experts, counted once, are the uncut
    layer, and every token-expert pair falls on exactly one share."""
    whole = dict(CFG, n_routed_experts=128, num_experts_per_tok=6,
                 experts_held=[0, 128])
    params = ref.init_params(whole, 7)
    p = _layer(params, 1)
    x = np.random.default_rng(4).standard_normal((2, 24, 32)).astype("f4")
    total = np.asarray(ref.moe_mlp(whole, p, jnp.asarray(x), routed=False))
    pairs = 0
    for first in range(0, 128, 16):
        share = dict(p, **{k: p[k][first:first + 16]
                           for k in ("e_gate", "e_up", "e_down")})
        y, stats = _moe_system(dict(whole, experts_held=[first, 16]),
                               share, x)
        total = total + np.asarray(y)
        pairs += stats[16]
        assert stats[18] == 0                   # the grouped path
    assert pairs == 2 * 24 * 6
    _close(total, ref.moe_mlp(whole, p, jnp.asarray(x)), 2e-5)


def test_rows_of_no_group_hold_anything(monkeypatch):
    """On the TPU a grouped product leaves the rows that belong to no group
    unwritten: whatever the memory held. Planted here as NaN, they may
    reach neither the layer's output nor a gradient, the routing weight's
    included: its gradient is a sum over the experts' output (the float32
    check of `kanana2.train` read NaN there: PERF.md section 6, PR 31)."""
    share = dict(CFG, n_routed_experts=128, num_experts_per_tok=6,
                 experts_held=[16, 16])
    p = _layer(ref.init_params(dict(share, experts_held=[0, 128]), 7), 1)
    p = dict(p, **{k: p[k][16:32] for k in ("e_gate", "e_up", "e_down")})
    x = jnp.asarray(np.random.default_rng(4).standard_normal((2, 24, 32)),
                    jnp.float32)
    grouped = lm._swiglu_experts

    def unwritten(rows, w_gate, w_up, w_down, group_sizes, **path):
        out = grouped(rows, w_gate, w_up, w_down, group_sizes, **path)
        in_a_group = jnp.arange(out.shape[0]) < jnp.sum(group_sizes)
        return jnp.where(in_a_group[:, None], out, jnp.nan)

    def loss(x, w_r):
        y, stats = _moe_system(share, dict(p, w_r=w_r), x)
        assert stats[18] == 0                   # the grouped path
        return jnp.sum(y ** 2)

    want = jax.grad(loss, argnums=(0, 1))(x, p["w_r"])
    monkeypatch.setattr(lm, "_swiglu_experts", unwritten)
    got = jax.grad(loss, argnums=(0, 1))(x, p["w_r"])
    for a, g in zip(want, got):
        _close(g, a, 1e-6)


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
    assert stats.shape == (2, 7) and (stats[:, 6] == 0).all()


def _symbol(cfg, prefix=PREFIX):
    net = mx.gluon.nn.DecoderLM(cfg, prefix=prefix)
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
    jax.grad of the reference (the rotation's backward is the rotation by
    the opposite angle); with MXNET_BACKWARD_DO_MIRROR each layer is
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
    four steps (two batches, seen twice) are the reference's plain Adam's,
    and fall; so are the parameters they leave."""
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


def test_amp_keeps_the_router_in_fp32_and_ids_exact():
    exact = amp.exact_variables(_symbol(CFG))
    assert {"data", "label"} <= exact
    assert {n.split("_", 2)[-1] for n in exact - {"data", "label"}} == \
        {"w_r", "r_bias"}


@pytest.mark.parametrize("key,value", [
    ("q_lora_rank", 1536), ("rope_scaling", {"type": "yarn", "factor": 40}),
    ("attention_bias", True), ("n_group", 8), ("scoring_func", "softmax"),
    ("topk_method", "group_limited_greedy"),
    ("num_expert_group", 4), ("moe_router_activation_func", "softmax"),
])
def test_what_no_layer_computes_raises_by_the_keys_name(key, value):
    with pytest.raises(NotImplementedError, match=key):
        mx.gluon.nn.DecoderLM(dict(CFG, **{key: value}), prefix=PREFIX)


def test_either_spelling_of_the_mixtures_keys_builds_the_same_layer():
    from mxnet_tpu.gluon.nn import decoder
    kimi = {k: v for k, v in CFG.items() if k not in {
        "n_routed_experts", "num_experts_per_tok", "n_shared_experts",
        "norm_topk_prob", "scoring_func", "n_group", "topk_method"}}
    kimi.update(num_experts=16, num_experts_per_token=3,
                num_shared_experts=2, moe_renormalize=True,
                moe_router_activation_func="sigmoid", num_expert_group=1)
    assert decoder.mixture_settings(kimi) == decoder.mixture_settings(CFG)
    ops = [[n.op.name for n in _symbol(c)._topo() if n.op is not None]
           for c in (kimi, CFG)]
    assert ops[0] == ops[1] and ops[0].count("_contrib_rope") == 2 * 3
    with pytest.raises(KeyError, match="n_routed_experts"):
        decoder.mixture_settings({"num_experts_per_tok": 6})


def test_kimi_linears_graph_is_unchanged_by_the_new_keys():
    """A config with `linear_attn_config` and `mla_use_nope` builds the
    graph it built before this model came: no rotation anywhere, and the
    keys DeepSeek-V3 spells (were a Kimi config to carry them) change
    neither an operation nor an output."""
    assert KIMI["mla_use_nope"] is True
    extra = dict(KIMI, rope_theta=10000, rope_interleave=True,
                 rope_scaling=None, n_group=1, topk_method="noaux_tc",
                 scoring_func="sigmoid", attention_bias=False)
    syms = [_symbol(c, "kimi_") for c in (KIMI, extra)]
    ops = [[(n.op.name, sorted(n.attrs.items())) for n in s._topo()
            if n.op is not None] for s in syms]
    assert ops[0] == ops[1]
    names = [name for name, _ in ops[0]]
    assert "_contrib_rope" not in names
    assert names.count("_contrib_kda") == 4
    assert names.count("_contrib_flash_attention") == 1
    assert names.count("_contrib_moe_experts") == 4
    params = kimi_ref.init_params(KIMI, 3)
    tokens, _ = _batch(1)
    outs = []
    for cfg in (KIMI, extra):
        net = mx.gluon.nn.DecoderLM(cfg, prefix="kimi_")
        net.collect_params().initialize()
        for name, p in net.collect_params().items():
            p.set_data(mx.nd.array(
                kimi_ref.system_params(params, "kimi_")[name]))
        outs.append(net(mx.nd.array(tokens)).asnumpy())
    assert (outs[0] == outs[1]).all()
    _close(outs[0], kimi_ref.logits(KIMI, params, jnp.asarray(tokens)), 2e-5)


def test_reference_copy_is_equal():
    with open(REF_PATH) as a, open(COPY_PATH) as b:
        assert a.read() == b.read()
