"""An `ouro` model (Ouro-2.6B's keys: one stack of layers run
`total_ut_steps` times with shared weights, an exit gate after every pass
and a loss weighted by where each position exits) against the plain
reference (benchmarks/models/ouro_reference.py), at a small size on the
CPU: the last pass's logits, the exit distribution, the per-position loss
and every parameter's gradient through the executor with and without
rematerialisation by (pass, layer), the fused fit, the models each
tolerance has to refuse, what the config may not ask for, and that the
other families' graphs are what they were."""
import functools
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import amp, executor
from test_kimi_linear import _close, _load

HERE = os.path.dirname(os.path.abspath(__file__))
REF_PATH = os.path.join(os.path.dirname(HERE), "benchmarks", "models",
                        "ouro_reference.py")

ref = _load(REF_PATH, "ouro_reference_under_test")

CFG = {
    "model_type": "ouro", "hidden_size": 64, "intermediate_size": 96,
    "num_hidden_layers": 2, "layer_types": ["full_attention"] * 2,
    "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
    "rms_norm_eps": 1e-6, "rope_theta": 1000000, "rope_scaling": None,
    "sliding_window": None, "use_sliding_window": False,
    "max_window_layers": 2, "total_ut_steps": 4, "early_exit_threshold": 1,
    "vocab_size": 512, "tie_word_embeddings": False, "hidden_act": "silu",
}
PREFIX = "ouro_"
PASSES = CFG["total_ut_steps"]


def _batch(seed, b=2, s=64, vocab=CFG["vocab_size"]):
    tokens = np.random.default_rng(seed).integers(0, vocab, (b, s))
    return tokens, np.roll(tokens, -1, axis=1)


@pytest.fixture(autouse=True)
def _amp_off():
    amp._reset_for_tests()
    yield
    amp._reset_for_tests()


def _params(seed=3):
    """Weights large enough for the attention and the gates to tell one
    position from another: every pass's exit share well away from 0 and
    from 1, and norm scales that are not all ones."""
    params = ref.init_params(CFG, seed, std=0.1)
    rng = np.random.default_rng(seed)
    for name in params:
        if name.endswith("norm1") or name.endswith("norm2") or \
                name == "norm_f":
            params[name] = (1 + 0.2 * rng.standard_normal(
                params[name].shape)).astype("f4")
    params["exit_gate"] = (0.05 * rng.standard_normal((1, 64))).astype("f4")
    params["exit_gate_bias"] = np.full((1,), -0.5, "f4")
    return params


def _jnp(params):
    return {k: jnp.asarray(v) for k, v in params.items()}


def _net(cfg, params):
    net = mx.gluon.nn.DecoderLM(cfg, prefix=PREFIX)
    net.collect_params().initialize()
    system = ref.system_params(params, PREFIX)
    assert set(net.collect_params().keys()) == set(system)
    for name, p in net.collect_params().items():
        p.set_data(mx.nd.array(system[name]))
    return net


def _symbol(cfg, prefix=PREFIX):
    net = mx.gluon.nn.DecoderLM(cfg, prefix=prefix)
    out = net(mx.sym.Variable("data"), mx.sym.Variable("label"))
    return mx.sym.Group(list(out) if isinstance(out, tuple) else [out])


@functools.lru_cache(maxsize=None)
def _reference():
    tokens, labels = _batch(1)
    with jax.default_matmul_precision("highest"):
        lg, losses, p = ref.forward(CFG, _jnp(_params()), jnp.asarray(tokens),
                                    jnp.asarray(labels))
    return np.asarray(lg), np.asarray(losses), np.asarray(p)


@functools.lru_cache(maxsize=None)
def _system_forward():
    tokens, labels = _batch(1)
    net = _net(CFG, _params())
    with jax.default_matmul_precision("highest"):
        lg = net(mx.nd.array(tokens)).asnumpy()
        loss, p = net(mx.nd.array(tokens), mx.nd.array(labels))
    return lg, loss.asnumpy(), p.asnumpy()


# -- the forward ------------------------------------------------------------------

@pytest.mark.parametrize("what", ["logits", "losses", "exit_probs"])
def test_forward_matches_reference(what):
    """The last pass's logits, the per-position loss (the exit-weighted
    objective) and p, each against the reference."""
    i = ["logits", "losses", "exit_probs"].index(what)
    _close(_system_forward()[i], _reference()[i], 2e-5)


def test_exit_distribution_sums_to_one_and_spreads():
    p = _system_forward()[2]
    assert p.shape == (2, 64, PASSES)
    np.testing.assert_allclose(p.sum(-1), 1.0, atol=1e-6)
    # every pass takes a share a test can see: the gate is no constant
    assert (p.mean((0, 1)) > 0.05).all()
    assert p[..., 0].std() > 1e-3


# each of these is another model: the tolerances above refuse it
CONTROLS = {
    "one_pass": dict(passes=1),
    "no_norm_between_passes": dict(norm_between=False),
    "last_pass_plain_ce": dict(last_only=True),
    "qk_norms": dict(qk_norm=True),
    "no_sandwich_norms": dict(sandwich=False),
}


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_controls_are_refused(control):
    """The reference of each planted control against the system: the
    per-position loss (and, but where the control changes the loss alone,
    the logits) lies far outside the tolerance the system meets."""
    tokens, labels = _batch(1)
    kw = dict(CONTROLS[control])
    with jax.default_matmul_precision("highest"):
        if kw.get("passes") == 1:
            lg = ref.logits(CFG, _jnp(_params()), jnp.asarray(tokens), **kw)
            losses = ref.losses_of_logits(lg, jnp.asarray(labels))
        else:
            lg, losses, _ = ref.forward(CFG, _jnp(_params()),
                                        jnp.asarray(tokens),
                                        jnp.asarray(labels), **kw)
    got_lg, got_losses, _ = _system_forward()

    def err(a, b):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        return np.max(np.abs(a - b)) / np.max(np.abs(b))
    assert err(got_losses, losses) > 100 * 2e-5, control
    if control != "last_pass_plain_ce":
        assert err(got_lg, lg) > 100 * 2e-5, control


# -- the graph the trainers run ---------------------------------------------------

@functools.lru_cache(maxsize=None)
def _reference_grads():
    tokens, labels = _batch(1)
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda p, t, l: ref.loss_and_grads(CFG, p, t, l))(
            _jnp(_params()), jnp.asarray(tokens), jnp.asarray(labels))


@functools.lru_cache(maxsize=None)
def _system_grads(mirror):
    tokens, labels = _batch(1)
    system = ref.system_params(_params(), PREFIX)
    args = {k: mx.nd.array(v) for k, v in system.items()}
    args["data"] = mx.nd.array(tokens)
    args["label"] = mx.nd.array(labels)
    grads = {k: mx.nd.zeros(v.shape) for k, v in system.items()}
    with jax.default_matmul_precision("highest"):
        exe = _symbol(CFG).bind(mx.cpu(), args, args_grad=grads)
        out = exe.forward(is_train=True)
        exe.backward([mx.nd.ones(out[0].shape) / out[0].size,
                      mx.nd.zeros(out[1].shape)])
        return (out[0].asnumpy(), out[1].asnumpy(),
                {k: g.asnumpy() for k, g in grads.items()})


PARAMETERS = sorted(PREFIX + name for name in ref.param_shapes(CFG))


@pytest.mark.parametrize("mirror", ["0", "1"])
@pytest.mark.parametrize("name", PARAMETERS)
def test_symbol_gradient_matches_reference(name, mirror, monkeypatch):
    """Each parameter's gradient, the sum of its uses over the four
    passes, against jax.grad of the reference: with every (pass, layer)
    application a mirror stage of its own and without rematerialisation."""
    monkeypatch.setenv("MXNET_BACKWARD_DO_MIRROR", mirror)
    loss, p, grads = _system_grads(mirror)
    want_loss, want = _reference_grads()
    _close(loss.mean(), want_loss, 1e-5)
    _close(p, _reference()[2], 2e-5)
    want = ref.system_params({k: np.asarray(v) for k, v in want.items()},
                             PREFIX)
    assert set(grads) == set(want)
    assert np.abs(want[name]).max() > 0
    _close(grads[name], want[name], 2e-4)


def test_mirror_stages_are_one_a_pass_and_layer():
    """Every (pass, layer) application and every pass's head is a stage of
    its own; the final norm between passes belongs to none; the passes run
    under mx.loop, the heads under mx.lm_head, the gates and the objective
    under mx.exit_gate."""
    topo = _symbol(CFG)._topo()
    nodes = [n for n in topo if n.op is not None]
    stages = {n.user_attrs.get("mirror_stage") for n in nodes} - {None}
    assert stages == {f"{PREFIX}l{i}_p{t}_layer" for i in range(2)
                      for t in range(PASSES)} | \
        {f"p{t}_head" for t in range(PASSES)}
    pos = {id(n): i for i, n in enumerate(topo)}
    units = executor._mirror_units(topo, pos, [(pos[id(n)], 0)
                                               for n in nodes[-1:]])
    assert sum(u[2] is not None for u in units) == len(stages)
    scopes = {n.user_attrs.get("profiler_scope") for n in nodes}
    assert {"mx.loop", "mx.gqa", "mx.mlp", "mx.lm_head",
            "mx.exit_gate"} <= scopes
    heads = [n for n in nodes if n.op.name == "_contrib_lm_head_ce"]
    assert [n.user_attrs["mirror_stage"] for n in heads] == \
        [f"p{t}_head" for t in range(PASSES)]
    gates = [n for n in nodes if n.op.name == "FullyConnected"
             and n.user_attrs.get("profiler_scope") == "mx.exit_gate"]
    assert len(gates) == PASSES - 1
    assert all("mirror_stage" not in n.user_attrs for n in gates)
    norms = [n for n in nodes if n.op.name == "RMSNorm"
             and n.user_attrs.get("profiler_scope") == "mx.lm_head"]
    assert len(norms) == PASSES
    assert all("mirror_stage" not in n.user_attrs for n in norms)
    # one variable a parameter, whatever its number of uses
    args = _symbol(CFG).list_arguments()
    assert len(args) == len(set(args)) == len(PARAMETERS) + 2
    # no q/k norm, no output gate; the sandwich norms
    assert not any(a.endswith(("q_norm", "k_norm", "_wg")) for a in args)
    assert f"{PREFIX}l1_post_norm2" in args


def test_fit_fused_adam_reproduces_reference_losses(monkeypatch):
    """Module.fit(steps_per_dispatch=2) with adam in fp32, every (pass,
    layer) a mirror stage: the losses of four steps (two batches, seen
    twice) are the reference's plain Adam's, and fall."""
    monkeypatch.setenv("MXNET_BACKWARD_DO_MIRROR", "1")
    params = _params()
    batches = [_batch(10 + i % 2, b=1) for i in range(4)]
    lr = 3e-3
    with jax.default_matmul_precision("highest"):
        _, want = ref.adam_steps(
            CFG, params,
            [(jnp.asarray(t), jnp.asarray(l)) for t, l in batches], lr=lr)
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
    with jax.default_matmul_precision("highest"):
        mod.fit(it, num_epoch=1, optimizer="adam",
                optimizer_params={"learning_rate": lr, "beta1": 0.9,
                                  "beta2": 0.95, "epsilon": 1e-8,
                                  "rescale_grad": 1.0 / 64},
                arg_params={k: mx.nd.array(v) for k, v in system.items()},
                eval_metric=mx.metric.Loss(output_names=[loss_name]),
                batch_end_callback=watch, steps_per_dispatch=2)
    _close(got, [np.mean(want[:2]), np.mean(want[2:])], 2e-5)
    assert got[1] < got[0]


# -- what a config may not ask for ---------------------------------------------------

@pytest.mark.parametrize("key,value", [
    ("early_exit_threshold", 0.9), ("use_sliding_window", True),
    ("rope_scaling", {"type": "yarn", "factor": 4}),
    ("hidden_act", "gelu"),
])
def test_what_no_layer_computes_raises_by_the_keys_name(key, value):
    with pytest.raises(NotImplementedError, match=key):
        mx.gluon.nn.DecoderLM(dict(CFG, **{key: value}), prefix=PREFIX)


def test_a_loop_over_mixture_layers_is_refused():
    from test_afmoe import CFG as AFMOE
    with pytest.raises(NotImplementedError, match="total_ut_steps"):
        mx.gluon.nn.DecoderLM(dict(AFMOE, total_ut_steps=2), prefix="x_")


# -- the other families' graphs ------------------------------------------------------

def _fingerprint(cfg):
    """sha256 of the graph a config builds: every node's operator, its
    attributes (`mirror_stage` and `profiler_scope` among them) and
    inputs, and the arguments' names."""
    topo = _symbol(cfg, "x_")._topo()
    pos = {id(n): i for i, n in enumerate(topo)}
    rows = []
    for n in topo:
        if n.op is None:
            rows.append(["var", n.name])
        else:
            rows.append([n.op.name,
                         sorted((k, str(v)) for k, v in n.attrs.items()),
                         sorted(n.user_attrs.items()),
                         [(pos[id(a)], i) for a, i in n.inputs]])
    rows.append(_symbol(cfg, "x_").list_arguments())
    return hashlib.sha256(json.dumps(rows).encode()).hexdigest()


# computed on the tree before the loop, the `ouro` family and its switch
# for q/k norms were added
BEFORE = {
    "afmoe": "d9605662ca5168f455107489f347e7ea38c61ac8ecf7c151c88a89f4e522dd8a",
    "lfm2_moe":
        "a4085625426cb260b6f739e30532da7d2ed5f3c8061c21bc190e13d436f04ea5",
    "deepseek_v3":
        "3aaa38384f7d212d287f95b3737d5adf461998506e0abcc147012711ea229c07",
    "kimi_linear":
        "7c1c5b68e935b43e238d661b407aba6f0c6011c2602899efd13f55aad52548e9",
}


@pytest.mark.parametrize("family", sorted(BEFORE))
def test_configs_without_a_loop_build_what_they_built(family):
    """A config without `total_ut_steps` (Trinity-Mini's and LFM2's keys,
    and the two latent-attention families', at toy widths) builds the same
    symbol as before, node for node: the same operators, attributes,
    mirror stages (`<layer>layer`, `head`) and scopes."""
    module = {"afmoe": "test_afmoe", "lfm2_moe": "test_lfm2_moe",
              "deepseek_v3": "test_deepseek_v3",
              "kimi_linear": "test_kimi_linear"}[family]
    cfg = __import__(module).CFG
    assert "total_ut_steps" not in cfg
    assert _fingerprint(cfg) == BEFORE[family]
    stages = {n.user_attrs.get("mirror_stage")
              for n in _symbol(cfg, "x_")._topo() if n.op is not None}
    assert stages - {None} == {f"x_l{i}_layer" for i in range(
        cfg["num_hidden_layers"])} | {"head"}
