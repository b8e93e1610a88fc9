"""Per-stage compiled model parallelism.

The group2ctx path must (a) compile once per stage — not retrace per
step, (b) place each stage's compute on its group's device, (c) match
the single-program executor numerically for forward, backward, and aux
updates, and (d) not fall back to the old eager per-op walk (here we pin
the compile counts that make the difference structural).
"""
import numpy as np
import pytest

import mxnet_tpu as mx


def _staged_sym(stages=4, hidden=16):
    """A `stages`-deep MLP with BatchNorm (aux traffic) + Dropout (rng
    traffic), one ctx_group per stage."""
    x = mx.sym.Variable("data")
    for s in range(stages):
        with mx.AttrScope(ctx_group=f"stage{s}"):
            x = mx.sym.FullyConnected(x, num_hidden=hidden,
                                      name=f"fc{s}")
            x = mx.sym.BatchNorm(x, name=f"bn{s}")
            x = mx.sym.Activation(x, act_type="relu")
    with mx.AttrScope(ctx_group=f"stage{stages - 1}"):
        x = mx.sym.FullyConnected(x, num_hidden=3, name="head")
    return mx.sym.SoftmaxOutput(x, name="softmax")


def _bind_staged(sym, stages=4):
    import jax
    devs = jax.local_devices(backend="cpu")
    g2c = {f"stage{s}": mx.Context("cpu", s % len(devs))
           for s in range(stages)}
    return sym.simple_bind(mx.cpu(0), data=(8, 12),
                           softmax_label=(8,), group2ctx=g2c)


def test_compiles_once_per_stage_across_steps():
    """N training steps -> each stage traces at most twice (fwd + bwd),
    never per step. The r4 eager path re-ran jax.vjp every step."""
    sym = _staged_sym()
    ex = _bind_staged(sym)
    rng = np.random.RandomState(0)
    for k in ex.arg_dict:
        ex.arg_dict[k][:] = rng.normal(0, 0.1, ex.arg_dict[k].shape)
    for step in range(5):
        ex.forward(is_train=True,
                   data=mx.nd.array(rng.normal(size=(8, 12))),
                   softmax_label=mx.nd.array(
                       rng.randint(0, 3, 8).astype(np.float32)))
        ex.backward()
    seg = ex._segmented_train
    assert len(seg.segments) >= 4      # one run per stage at least
    assert all(c <= 2 for c in seg.trace_counts), seg.trace_counts
    # and the head stage really traced a backward too
    assert max(seg.trace_counts) == 2


def test_stage_placement():
    """Each stage's outputs live on its group's device (the
    _CrossDeviceCopy role is real transfers, not numerics-only)."""
    import jax
    devs = jax.local_devices(backend="cpu")
    with mx.AttrScope(ctx_group="dev1"):
        a = mx.sym.Variable("a")
        h = mx.sym.FullyConnected(a, num_hidden=8, name="fc1")
    with mx.AttrScope(ctx_group="dev2"):
        out = mx.sym.FullyConnected(h, num_hidden=4, name="fc2")
    ex = out.simple_bind(mx.cpu(0), a=(2, 6),
                         group2ctx={"dev1": mx.cpu(0),
                                    "dev2": mx.cpu(3)})
    rng = np.random.RandomState(3)
    for k in ex.arg_dict:
        ex.arg_dict[k][:] = rng.normal(size=ex.arg_dict[k].shape)
    res = ex.forward(is_train=True)[0]
    assert list(res._data.devices())[0] == devs[3]


def test_matches_single_program_fwd_bwd_aux():
    """Same params, same batch: staged executor == unplaced executor for
    outputs, every arg grad, and the BN aux updates."""
    sym = _staged_sym(stages=3)
    rng = np.random.RandomState(1)
    x = rng.normal(size=(8, 12)).astype(np.float32)
    y = rng.randint(0, 3, 8).astype(np.float32)

    ex = _bind_staged(sym, stages=3)
    ref = sym.simple_bind(mx.cpu(0), data=(8, 12), softmax_label=(8,))
    for k in ex.arg_dict:
        v = rng.normal(0, 0.1, ex.arg_dict[k].shape)
        ex.arg_dict[k][:] = v
        ref.arg_dict[k][:] = v

    for e in (ex, ref):
        e.forward(is_train=True, data=mx.nd.array(x),
                  softmax_label=mx.nd.array(y))
        e.backward()
    np.testing.assert_allclose(ex.outputs[0].asnumpy(),
                               ref.outputs[0].asnumpy(), rtol=2e-5,
                               atol=1e-6)
    for k in ref.grad_dict:
        np.testing.assert_allclose(
            ex.grad_dict[k].asnumpy(), ref.grad_dict[k].asnumpy(),
            rtol=2e-4, atol=1e-5, err_msg=k)
    for k in ref.aux_dict:
        np.testing.assert_allclose(
            ex.aux_dict[k].asnumpy(), ref.aux_dict[k].asnumpy(),
            rtol=2e-5, atol=1e-6, err_msg=k)


def test_eval_path_segmented_and_matches():
    sym = _staged_sym(stages=3)
    ex = _bind_staged(sym, stages=3)
    ref = sym.simple_bind(mx.cpu(0), data=(8, 12), softmax_label=(8,))
    rng = np.random.RandomState(2)
    for k in ex.arg_dict:
        v = rng.normal(0, 0.1, ex.arg_dict[k].shape)
        ex.arg_dict[k][:] = v
        ref.arg_dict[k][:] = v
    x = mx.nd.array(rng.normal(size=(8, 12)).astype(np.float32))
    a = ex.forward(is_train=False, data=x)[0].asnumpy()
    b = ref.forward(is_train=False, data=x)[0].asnumpy()
    np.testing.assert_allclose(a, b, rtol=2e-5, atol=1e-6)
    assert hasattr(ex, "_segmented_eval")
    # eval stages traced once each
    assert all(c == 1 for c in ex._segmented_eval.trace_counts)


def test_dropout_rng_stage_chain():
    """Stages containing rng consumers (Dropout) run under the shared
    per-step key split; two train forwards draw different masks."""
    x = mx.sym.Variable("data")
    with mx.AttrScope(ctx_group="s0"):
        h = mx.sym.FullyConnected(x, num_hidden=32, name="fc0")
        h = mx.sym.Dropout(h, p=0.5)
    with mx.AttrScope(ctx_group="s1"):
        out = mx.sym.FullyConnected(h, num_hidden=32, name="fc1")
    sym = mx.sym.MakeLoss(mx.sym.sum(out))
    ex = sym.simple_bind(mx.cpu(0), data=(4, 8),
                         group2ctx={"s0": mx.cpu(0), "s1": mx.cpu(1)})
    rng = np.random.RandomState(5)
    for k in ex.arg_dict:
        ex.arg_dict[k][:] = rng.normal(size=ex.arg_dict[k].shape)
    d = mx.nd.array(rng.normal(size=(4, 8)).astype(np.float32))
    o1 = ex.forward(is_train=True, data=d)[0].asnumpy()
    o2 = ex.forward(is_train=True, data=d)[0].asnumpy()
    assert not np.allclose(o1, o2)


def test_variable_output_in_group():
    """Group([Variable, net]) outputs under group2ctx: the bare-Variable
    output resolves from the leaf values (parity with _build_runner)."""
    with mx.AttrScope(ctx_group="g1"):
        a = mx.sym.Variable("a")
        h = mx.sym.FullyConnected(a, num_hidden=4, name="fc")
    grouped = mx.sym.Group([mx.sym.Variable("a"), h])
    ex = grouped.simple_bind(mx.cpu(0), a=(2, 3),
                             group2ctx={"g1": mx.cpu(1)})
    rng = np.random.RandomState(0)
    for k in ex.arg_dict:
        ex.arg_dict[k][:] = rng.normal(size=ex.arg_dict[k].shape)
    outs = ex.forward(is_train=True)
    np.testing.assert_allclose(outs[0].asnumpy(),
                               ex.arg_dict["a"].asnumpy())
    assert outs[1].shape == (2, 4)
    ex.backward([mx.nd.ones((2, 3)), mx.nd.ones((2, 4))])
