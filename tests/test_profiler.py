"""Profiler / visualization / env-config tests.

Reference pattern: tests/python/unittest/test_profiler.py (set_config,
run, dump chrome trace) + visualization print_summary smoke.
"""
import json
import os
import subprocess
import sys

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import profiler


def test_profiler_imperative_dump(tmp_path):
    f = tmp_path / "prof.json"
    profiler.set_config(filename=str(f), aggregate_stats=True)
    profiler.set_state("run")
    a = mx.nd.array(np.ones((32, 32), np.float32))
    b = mx.nd.dot(a, a)
    c = mx.nd.relu(b)
    c.wait_to_read()
    profiler.set_state("stop")
    path = profiler.dump()
    trace = json.load(open(path))
    names = [e["name"] for e in trace["traceEvents"]]
    assert "dot" in names and "relu" in names
    for e in trace["traceEvents"]:
        assert e["ph"] in ("X", "C", "i")
        if e["ph"] == "X":
            assert e["dur"] >= 0
    stats = profiler.dumps(reset=True)
    assert "dot" in stats and "Avg(us)" in stats


def test_profiler_symbolic_span(tmp_path):
    f = tmp_path / "prof_sym.json"
    profiler.set_config(filename=str(f))
    sym = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=4,
                                name="fc")
    ex = sym.simple_bind(mx.cpu(), data=(2, 8))
    ex.arg_dict["data"][:] = np.ones((2, 8), np.float32)
    profiler.set_state("run")
    ex.forward(is_train=True)
    ex.backward()
    profiler.set_state("stop")
    trace = json.load(open(profiler.dump()))
    names = [e["name"] for e in trace["traceEvents"]]
    assert any(n.startswith("Forward") for n in names)
    assert any(n.startswith("Backward") for n in names)


def test_profiler_pause_and_objects(tmp_path):
    profiler.set_config(filename=str(tmp_path / "p.json"))
    profiler.set_state("run")
    profiler.pause()
    assert not profiler.is_running()
    profiler.resume()
    dom = profiler.Domain("custom")
    with dom.new_task("mytask"):
        mx.nd.array([1.0]).wait_to_read()
    cnt = dom.new_counter("items", 5)
    cnt.increment(2)
    dom.new_marker("here").mark()
    profiler.set_state("stop")
    trace = json.load(open(profiler.dump()))
    names = [e["name"] for e in trace["traceEvents"]]
    assert "mytask" in names and "items" in names and "here" in names


def test_print_summary():
    data = mx.sym.Variable("data")
    net = mx.sym.Convolution(data, kernel=(3, 3), num_filter=8, name="c1")
    net = mx.sym.Activation(net, act_type="relu", name="a1")
    net = mx.sym.FullyConnected(mx.sym.Flatten(net), num_hidden=10,
                                name="fc1")
    out = mx.viz.print_summary(net, shape={"data": (1, 3, 8, 8)})
    assert "c1 (Convolution)" in out
    assert "Total params:" in out
    # conv: 8*3*3*3 + 8 = 224; fc: 10*(8*6*6)+10 = 2890
    assert "Total params: 3114" in out


def test_plot_network_gated():
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=2)
    try:
        import graphviz  # noqa: F401
    except ImportError:
        import pytest
        with pytest.raises(ImportError):
            mx.viz.plot_network(net)
        return
    dot = mx.viz.plot_network(net)
    assert "node0" in dot.source


def test_env_config_surface():
    assert mx.config.get("MXNET_KVSTORE_BIGARRAY_BOUND") == 1000000
    allv = mx.config.list_vars()
    assert "MXNET_ENGINE_TYPE" in allv and len(allv) >= 25


def test_naive_engine_env():
    code = (
        "import numpy as np, mxnet_tpu as mx\n"
        "from mxnet_tpu import engine\n"
        "assert engine._sync_mode\n"
        "x = mx.nd.array(np.ones((4, 4), np.float32))\n"
        "y = (x * 2 + 1).asnumpy()\n"
        "np.testing.assert_allclose(y, 3.0)\n"
        "print('SYNC-OK')\n"
    )
    env = dict(os.environ, MXNET_ENGINE_TYPE="NaiveEngine",
               JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=240)
    assert "SYNC-OK" in out.stdout, out.stderr
