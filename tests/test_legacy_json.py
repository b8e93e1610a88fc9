"""Legacy/reference symbol-JSON loading (role of
src/nnvm/legacy_json_util.cc:1-228 + c_api_symbolic.cc kHiddenKeys)."""
import json

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu.symbol.symbol import load_json


def _ref_json(nodes, arg_nodes, heads, version=10100):
    return json.dumps({
        "nodes": nodes, "arg_nodes": arg_nodes, "heads": heads,
        "attrs": {"mxnet_version": ["int", version]}})


def test_reference_v1_json_loads_and_binds():
    """Reference-1.x style JSON ('param' node key, mxnet_version graph
    attr) loads and produces a working executor."""
    js = _ref_json(
        [{"op": "null", "name": "data", "inputs": []},
         {"op": "null", "name": "fc_weight", "inputs": []},
         {"op": "null", "name": "fc_bias", "inputs": []},
         {"op": "FullyConnected", "name": "fc",
          "param": {"num_hidden": "4", "no_bias": "False"},
          "inputs": [[0, 0, 0], [1, 0, 0], [2, 0, 0]]}],
        [0, 1, 2], [[3, 0, 0]])
    sym = load_json(js)
    assert sym.list_arguments() == ["data", "fc_weight", "fc_bias"]
    ex = sym.simple_bind(ctx=mx.cpu(0), data=(2, 3))
    out = ex.forward(data=mx.nd.ones((2, 3)))
    assert out[0].shape == (2, 4)


def test_hidden_keys_upgraded():
    """Raw ctx_group/lr_mult keys (pre-C-API-rename files) become __key__
    user attrs; '{arg}_{key}' forms land on the input variable
    (legacy_json_util.cc:49-110)."""
    js = _ref_json(
        [{"op": "null", "name": "data", "inputs": [],
          "attrs": {"lr_mult": "2.0"}},
         {"op": "null", "name": "fc_weight", "inputs": []},
         {"op": "FullyConnected", "name": "fc",
          "attrs": {"num_hidden": "4", "no_bias": "True",
                    "ctx_group": "dev1", "weight_lr_mult": "0.5"},
          "inputs": [[0, 0, 0], [1, 0, 0]]}],
        [0, 1], [[2, 0, 0]])
    sym = load_json(js)
    ad = sym.attr_dict()
    assert ad["data"]["__lr_mult__"] == "2.0"
    assert ad["fc"]["__ctx_group__"] == "dev1"
    assert ad["fc_weight"]["__lr_mult__"] == "0.5"
    # the moved keys must not linger as (unparseable) op attrs
    ex = sym.simple_bind(ctx=mx.cpu(0), data=(2, 3))
    assert ex.forward(data=mx.nd.ones((2, 3)))[0].shape == (2, 4)


def test_v080_missing_aux_inputs_materialized():
    """Pre-0.9 JSON stored no aux variables: BatchNorm's moving stats are
    appended as '{node}_{arg}' variables (legacy_json_util.cc:134-151)."""
    js = _ref_json(
        [{"op": "null", "name": "data", "inputs": []},
         {"op": "null", "name": "bn_gamma", "inputs": []},
         {"op": "null", "name": "bn_beta", "inputs": []},
         {"op": "BatchNorm", "name": "bn", "param": {},
          "inputs": [[0, 0, 0], [1, 0, 0], [2, 0, 0]]}],
        [0, 1, 2], [[3, 0, 0]], version=800)
    sym = load_json(js)
    args = sym.list_arguments()
    assert args[:3] == ["data", "bn_gamma", "bn_beta"]
    assert sym.list_auxiliary_states() == ["bn_moving_mean",
                                          "bn_moving_var"]
    ex = sym.simple_bind(ctx=mx.cpu(0), data=(2, 3))
    assert ex.forward(data=mx.nd.ones((2, 3)))[0].shape == (2, 3)


def test_v094_argmax_axis_upgrade():
    """axis=-1 on argmin/argmax meant 'flatten' pre-0.9.5 — the attr is
    dropped to recover the op default (legacy_json_util.cc:173-184)."""
    js = _ref_json(
        [{"op": "null", "name": "data", "inputs": []},
         {"op": "argmax", "name": "am", "param": {"axis": "-1"},
          "inputs": [[0, 0, 0]]}],
        [0], [[1, 0, 0]], version=904)
    sym = load_json(js)
    x = np.arange(6, dtype=np.float32).reshape(2, 3)
    ex = sym.simple_bind(ctx=mx.cpu(0), data=(2, 3))
    out = ex.forward(data=mx.nd.array(x))[0].asnumpy()
    # default (axis dropped -> global) semantics, not axis=-1-as-int
    # (which would have been per-row, shape (2,))
    assert out.shape in ((), (1,))
    assert float(out.reshape(-1)[0]) == 5.0


def test_own_json_untouched():
    """mxnet_tpu-written JSON round-trips without the upgrade pass."""
    data = mx.sym.Variable("data", lr_mult=3.0)
    sym = mx.sym.FullyConnected(data, num_hidden=2, name="fc")
    sym2 = load_json(sym.tojson())
    assert sym2.list_arguments() == sym.list_arguments()
    assert sym2.attr_dict()["data"]["__lr_mult__"] == "3.0"


def test_v080_optional_inputs_not_phantomized():
    """A pre-0.9 no_bias FullyConnected stores 2 inputs on purpose — the
    aux-materializing upgrader must not grow a phantom bias variable."""
    js = _ref_json(
        [{"op": "null", "name": "data", "inputs": []},
         {"op": "null", "name": "fc_weight", "inputs": []},
         {"op": "FullyConnected", "name": "fc",
          "param": {"num_hidden": "4", "no_bias": "True"},
          "inputs": [[0, 0, 0], [1, 0, 0]]}],
        [0, 1], [[2, 0, 0]], version=800)
    sym = load_json(js)
    assert sym.list_arguments() == ["data", "fc_weight"]
    ex = sym.simple_bind(ctx=mx.cpu(0), data=(2, 3))
    assert ex.forward(data=mx.nd.ones((2, 3)))[0].shape == (2, 4)


def test_unrelocatable_hidden_key_survives_as_hidden():
    """A '{arg}_{key}' hidden attr whose target input isn't a loadable
    variable (pre-0.9 aux not yet materialized) must become a __hidden__
    attr, not crash parse_attrs as an unknown op param."""
    js = _ref_json(
        [{"op": "null", "name": "data", "inputs": []},
         {"op": "null", "name": "bn_gamma", "inputs": []},
         {"op": "null", "name": "bn_beta", "inputs": []},
         {"op": "BatchNorm", "name": "bn",
          "param": {"moving_mean_lr_mult": "0.0"},
          "inputs": [[0, 0, 0], [1, 0, 0], [2, 0, 0]]}],
        [0, 1, 2], [[3, 0, 0]], version=800)
    sym = load_json(js)
    # loads, binds, and keeps the data as a hidden attr on the node
    assert sym.attr_dict()["bn"]["__moving_mean_lr_mult__"] == "0.0"
    ex = sym.simple_bind(mx.cpu(0), data=(2, 3))
    assert ex.forward(data=mx.nd.ones((2, 3)))[0].shape == (2, 3)
