"""Inference export + standalone predictor (reference role: include/mxnet/c_predict_api.h:1-250, amalgamation/)."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.contrib.export import export_model
from mxnet_tpu.predictor import Predictor


def _convnet():
    data = mx.sym.Variable("data")
    net = mx.sym.Convolution(data, kernel=(3, 3), num_filter=8, name="c1")
    net = mx.sym.BatchNorm(net, name="bn1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.Pooling(net, pool_type="max", kernel=(2, 2), stride=(2, 2))
    net = mx.sym.FullyConnected(mx.sym.Flatten(net), num_hidden=10,
                                name="fc")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _trained_module(sym, shapes):
    mod = mx.mod.Module(sym, context=mx.cpu(0))
    mod.bind(data_shapes=[("data", shapes)],
             label_shapes=[("softmax_label", (shapes[0],))])
    mod.init_params(mx.init.Xavier())
    return mod


def test_export_reload_bitwise_equal_logits(tmp_path):
    """The exported StableHLO module reproduces the executor's logits
    BITWISE on the same backend (it IS the same XLA program)."""
    sym = _convnet()
    shapes = (2, 3, 16, 16)
    mod = _trained_module(sym, shapes)
    args, auxs = mod.get_params()
    path = str(tmp_path / "model.mxa")
    export_model(path, sym, args, auxs, {"data": shapes})

    x = np.random.RandomState(0).uniform(0, 1, shapes).astype(np.float32)
    it = mx.io.NDArrayIter(x, np.zeros(2, np.float32), batch_size=2,
                           label_name="softmax_label")
    ref = mod.predict(it).asnumpy()

    pred = Predictor(path)
    out = pred.forward(x)
    assert pred.output_names == ["softmax_output"]
    np.testing.assert_array_equal(out[0], ref)   # bitwise


def test_predictor_contract(tmp_path):
    sym = _convnet()
    shapes = (1, 3, 16, 16)
    mod = _trained_module(sym, shapes)
    args, auxs = mod.get_params()
    path = str(tmp_path / "model.mxa")
    export_model(path, sym, args, auxs, {"data": shapes})
    pred = Predictor(path)
    assert pred.input_info == [{"name": "data",
                                "shape": [1, 3, 16, 16],
                                "dtype": "float32"}]
    assert pred.output_shapes == [("softmax_output", (1, 10))]
    x = np.zeros(shapes, np.float32)
    # keyword feeding
    out = pred.forward(data=x)
    np.testing.assert_allclose(out[0].sum(), 1.0, rtol=1e-5)
    # wrong shape -> the MXPredCreate fixed-shape contract error
    with pytest.raises(ValueError, match="exported shape"):
        pred.forward(np.zeros((2, 3, 16, 16), np.float32))
    with pytest.raises(ValueError, match="unknown inputs"):
        pred.forward(data=x, bogus=x)


def test_predictor_is_standalone(tmp_path):
    """predictor.py runs WITHOUT the mxnet_tpu package imported: the
    artifact serves inference on a host with no operator library (the
    amalgamation role). The subprocess loads predictor.py from its file
    path and asserts mxnet_tpu never enters sys.modules."""
    sym = _convnet()
    shapes = (1, 3, 16, 16)
    mod = _trained_module(sym, shapes)
    args, auxs = mod.get_params()
    path = str(tmp_path / "model.mxa")
    export_model(path, sym, args, auxs, {"data": shapes})

    import mxnet_tpu.predictor as predictor_mod
    script = textwrap.dedent(f"""
        import importlib.util, sys
        import numpy as np
        spec = importlib.util.spec_from_file_location(
            "standalone_predictor", {predictor_mod.__file__!r})
        m = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(m)
        assert not any(k == "mxnet_tpu" or k.startswith("mxnet_tpu.")
                       for k in sys.modules), "training stack got imported"
        p = m.Predictor({path!r})
        out = p.forward(np.zeros((1, 3, 16, 16), np.float32))
        assert out[0].shape == (1, 10)
        assert abs(float(out[0].sum()) - 1.0) < 1e-4
        print("STANDALONE_OK")
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=300)
    assert "STANDALONE_OK" in r.stdout, (r.stdout, r.stderr)


def test_export_cli_smoke(tmp_path):
    sym = _convnet()
    shapes = (2, 3, 16, 16)
    mod = _trained_module(sym, shapes)
    args, auxs = mod.get_params()
    path = str(tmp_path / "model.mxa")
    export_model(path, sym, args, auxs, {"data": shapes})
    np.save(tmp_path / "x.npy",
            np.zeros(shapes, np.float32))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.dirname(os.path.dirname(
                   os.path.abspath(mx.__file__))))
    r = subprocess.run(
        [sys.executable, "-m", "mxnet_tpu.predictor", path,
         str(tmp_path / "x.npy")],
        env=env, capture_output=True, text=True, timeout=300)
    assert "softmax_output" in r.stdout, (r.stdout, r.stderr)
