"""`kda_kernel_share.train`'s reader, checked without a chip on synthetic
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

KDA_OPS = {
    # two kernels and an XLA fusion under the scope, a kernel outside the
    # window, another scope's operation
    "kernels_and_a_fusion": (
        [["mx.kda.core", 0.0, 0.3, "f", "%mx_kda_fwd.3"],
         ["mx.kda.core", 0.3, 0.5, "b", "%mx_kda_bwd.4"],
         ["mx.kda.core", 0.8, 0.2, "f", "%fusion.17"],
         ["mx.kda.core", 2.5, 0.3, "f", "%mx_kda_fwd.3"],
         ["mx.optimizer", 1.0, 0.5, "f", "%fusion.9"]], 80.0),
    # the parent's program: the scope is there, the kernels are not
    "xla_only": ([["mx.kda.core", 0.0, 0.5, "f", "%fusion.3"],
                  ["mx.kda", 0.5, 0.5, "f", "%fusion.4"]], 0.0),
    "no_scope": ([[None, 0.0, 1.0, "f", "%fusion.5"]], None),
    "no_operations": ([], None),
}


@pytest.mark.parametrize("ops,want", KDA_OPS.values(), ids=KDA_OPS.keys())
def test_kda_kernel_share_reader(ops, want):
    from reduce import op_scopes
    from run import load_file_module
    reader = load_file_module("layer_metrics", "kda_kernel_share.train")
    ops = [[op_scopes.UNSCOPED if e[0] is None else e[0]] + e[1:]
           for e in ops]
    got = reader.share(ops, (0.0, 2.0))
    assert got == (want if want is None else pytest.approx(want))


def test_kda_kernel_share_is_declared():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = named(bench, "per_layer", CELL)["kda_kernel_share.train"]
    assert entry["layer"] == "kernels" and entry["moves"] == "train_rate"
    assert entry["workloads"] == [CELL]
