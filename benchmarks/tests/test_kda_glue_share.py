"""`kda_glue_share.train`'s reader, checked without a chip on synthetic
operation lists, and its entry in `BENCHMARK.json`."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, os.path.dirname(os.path.abspath(__file__))]

from test_harness import named                          # noqa: E402

CELL = "kimi_linear.train"

GLUE_OPS = {
    # this PR's program: the prepare kernels under their own scope inside
    # the mixer's, the core's kernels under theirs, other layers beside
    "prepare_scope": (
        [["mx.kda", 0.0, 0.2, "f", "%fusion.1"],
         ["mx.kda.prepare", 0.2, 0.1, "f", "%mx_kdaprep_fwd.2"],
         ["mx.kda.core", 0.3, 0.4, "f", "%mx_kda_fwd.3"],
         ["mx.kda.prepare", 0.7, 0.1, "b", "%mx_kdaprep_bwd.4"],
         ["mx.mlp", 0.8, 1.2, "f", "%fusion.5"]], 20.0),
    # the parent's program: the preparation is XLA's, under `mx.kda`
    "parent": ([["mx.kda", 0.0, 0.6, "f", "%fusion.1"],
                ["mx.kda.core", 0.6, 0.4, "f", "%mx_kda_fwd.3"],
                ["mx.optimizer", 1.0, 1.0, "f", "%fusion.9"]], 30.0),
    # outside the window nothing counts
    "window": ([["mx.kda", 0.0, 1.0, "f", "%fusion.1"],
                ["mx.kda.core", 1.0, 1.0, "f", "%mx_kda_fwd.3"],
                ["mx.kda", 2.5, 1.0, "f", "%fusion.1"]], 50.0),
    "no_mixer": ([["mx.mlp", 0.0, 1.0, "f", "%fusion.5"]], None),
    "no_operations": ([], None),
}


@pytest.mark.parametrize("ops,want", GLUE_OPS.values(), ids=GLUE_OPS.keys())
def test_kda_glue_share_reader(ops, want):
    from reduce import op_scopes
    from run import load_file_module
    reader = load_file_module("layer_metrics", "kda_glue_share.train")
    got = reader.glue_share(op_scopes.Scopes(ops, (0.0, 2.0)))
    assert got == (want if want is None else pytest.approx(want))


def test_kda_glue_share_is_declared():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = named(bench, "per_layer", CELL)["kda_glue_share.train"]
    assert entry["layer"] == "executor and ops"
    assert entry["better"] == "lower" and entry["moves"] == "train_rate"
    assert entry["workloads"] == [CELL]
    assert bench["per_layer"][-1]["name"] == "kda_glue_share.train"
