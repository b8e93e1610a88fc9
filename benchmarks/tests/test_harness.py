"""The harness, checked without a chip (run by hand and in the rehearsal:
`python -m pytest benchmarks/tests -q`; it is outside tier-1's tests/).

The end-to-end tests start `run.py --rehearse` as a user would, one process
per run, at the toy sizes of the `rehearse` blocks (8 images of 32x32, 2 s).
"""
import importlib.util
import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE]

import flops                                    # noqa: E402
from reduce import xplane                       # noqa: E402

CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


def load_run():
    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(HERE, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def rehearse(cell, trace, root=ROOT, seed=2147483659):
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks", "run.py"),
         "--workload", cell, "--seed", str(seed), "--seconds", "2",
         "--trace", str(trace), "--rehearse"],
        cwd=root, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    return json.loads(lines[-1]), [json.loads(ln) for ln in lines[:-1]]


def named(bench, section, cell):
    return {m["name"]: m for m in bench[section]
            if "workloads" not in m or cell in m["workloads"]}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def copy_with(bench, root):
    """A copy of the benchmark under `root` (the program linked in), with
    `bench` as its BENCHMARK.json: what a later PR's tree looks like."""
    shutil.copytree(HERE, root / "benchmarks", ignore=shutil.ignore_patterns(
        ".jax_cache", ".bench_scratch", "__pycache__"))
    for name in ("mxnet_tpu", "src"):
        os.symlink(os.path.join(ROOT, name), root / name)
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    return root


def with_pending(bench, cell):
    """BENCHMARK.json with the entries of pending/<cell>.json merged in,
    as the PR that proves that cell will add them."""
    with open(os.path.join(HERE, "pending", cell + ".json")) as f:
        add = json.load(f)
    out = json.loads(json.dumps(bench))
    out["configs"] += add["configs"]
    out["workloads"] += add["workloads"]
    for section in ("end_to_end", "per_layer"):
        have = {m["name"]: m for m in out[section]}
        for m in add[section]:
            if m["name"] in have:
                have[m["name"]]["workloads"] += m["workloads"]
            else:
                out[section].append(m)
    return out


@pytest.fixture(scope="module")
def tree(bench, tmp_path_factory):
    """(root, benchmark) for each runner's cell: the repo itself for a cell
    BENCHMARK.json has, a copy with its pending entries for one it has not."""
    def get(cell):
        if cell in {w["name"] for w in bench["workloads"]}:
            return ROOT, bench
        merged = with_pending(bench, cell)
        return str(copy_with(merged, tmp_path_factory.mktemp(cell))), merged
    return get


# one cell per runner: both train cells share runners/train_fit.py
@pytest.mark.parametrize("cell", ["resnet50.train", "resnet50.serve"])
def test_end_to_end_line(tree, cell):
    root, bench = tree(cell)
    out, _ = rehearse(cell, 0, root=root)
    assert set(out) == CONTRACT_KEYS
    assert set(out["device"]) == DEVICE_KEYS
    assert out["device"]["platform"] == "cpu" and out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    want = named(bench, "end_to_end", cell)
    assert set(out["metrics"]) == set(want)
    for name, m in out["metrics"].items():
        assert m["unit"] == want[name]["unit"] and m["value"] > 0


@pytest.mark.parametrize("cell", ["resnet50.train", "resnet50.serve"])
def test_per_layer_line(tree, cell):
    root, bench = tree(cell)
    out, _ = rehearse(cell, 1, root=root)
    assert set(out) - {"breakdown"} == CONTRACT_KEYS
    want = named(bench, "per_layer", cell)
    assert set(out["metrics"]) <= set(want)
    # a CPU gives no device trace and no memory statistics: those readers
    # return nothing and are left out; every clock and span metric is there
    host = {n for n, m in want.items()
            if m["source"] in ("host_clock", "program_span")}
    assert host <= set(out["metrics"])
    assert out["metrics"]["compiles_in_window." + cell.split(".")[1]][
        "value"] == 0


def test_added_files_are_found_by_name(bench, tmp_path):
    """A later PR adds a configuration, a mix, a per-layer metric and a
    cell as new files and entries, and edits no file that is there."""
    root = tmp_path / "copy"
    root.mkdir()
    copy_with(bench, root)
    b = root / "benchmarks"
    cfg = json.load(open(b / "configs" / "resnet50_v1.json"))
    cfg["zoo_name"], cfg["flops"]["depth"] = "resnet18_v1", 18
    json.dump(cfg, open(b / "configs" / "added_model.json", "w"))
    mix = json.load(open(b / "traffic" / "fit_fused_k4_synthetic.json"))
    mix["steps_per_dispatch"] = 2
    json.dump(mix, open(b / "traffic" / "added_mix.json", "w"))
    (b / "layer_metrics" / "added.metric.py").write_text(
        "def compute(ctx):\n"
        "    return float(ctx.traffic['steps_per_dispatch'])\n")
    new = json.loads(json.dumps(bench))
    new["configs"].append({"name": "added_model", "source": "test",
                           "file": "benchmarks/configs/added_model.json",
                           "reduced": [], "why": "test"})
    new["workloads"].append({"name": "added.cell", "config": "added_model",
                             "traffic": "added_mix", "chips": 1,
                             "why": "test"})
    new["per_layer"].append({"name": "added.metric", "unit": "steps",
                             "better": "higher", "source": "program_counter",
                             "layer": "training loops", "moves": "train_rate",
                             "workloads": ["added.cell"]})
    for m in new["end_to_end"] + new["per_layer"]:
        if "workloads" in m and "resnet50.train" in m["workloads"]:
            m["workloads"].append("added.cell")
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(new, f)
    out, lines = rehearse("added.cell", 1, root=str(root))
    assert out["metrics"]["added.metric"] == {"value": 2.0, "unit": "steps"}
    train = [ln for ln in lines if ln["line"] == "train"][0]
    assert train["steps_per_dispatch"] == 2


def test_no_chip_and_no_rehearsal_is_an_error():
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload",
         "resnet50.train", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0
    assert not proc.stdout.strip().startswith('{"correct"')
    assert "needs a TPU" in proc.stderr


def test_unknown_device_kind_raises():
    run = load_run()
    assert run.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(SystemExit):
        run.peaks_for("TPU v9 imaginary")
    with pytest.raises(SystemExit):
        run.peaks_for("source")         # a key of the file, not a device


@pytest.mark.parametrize("depth,paper_macs", [
    (18, 1.8e9), (34, 3.6e9), (50, 3.8e9), (101, 7.6e9), (152, 11.3e9)])
def test_flops_against_he_et_al_table_1(depth, paper_macs):
    """Table 1 of arXiv:1512.03385 gives multiply-adds per 224x224 image
    to two digits; the zoo's v1 strides in the first 1x1 convolution of a
    bottleneck, which the paper's count does too."""
    macs = flops.resnet_v1_forward_macs(depth)
    assert abs(macs - paper_macs) / paper_macs < 0.03
    cfg = {"flops": {"family": "resnet_v1", "depth": depth},
           "image_size": 224, "num_classes": 1000}
    assert flops.train_flops(cfg) == 6 * macs


# -- the reduction -----------------------------------------------------------

def events(dev_events, host_events=()):
    return {"planes": [
        {"name": "/device:TPU:0",
         "lines": [{"name": "XLA Ops", "events": list(dev_events)}]},
        {"name": "/host:CPU",
         "lines": [{"name": "python3", "events": list(host_events)}]}]}


def test_reduction_on_synthetic_events():
    red = xplane.Reduced(events(
        [["while.1", 1.0, 4.0, "while"],                 # encloses them all
         ["fusion.1", 1.0, 1.0, "convolution fusion"],
         ["all-reduce.1", 2.0, 1.0, "all-reduce"],
         ["fusion.2", 2.5, 1.5, "loop fusion"],           # overlaps 0.5
         ["copy.2", 4.0, 0.5, "data formatting"],
         ["copy.1", 7.0, 1.0, "data formatting"]],
        [["bench.window_start", 0.0, 0.0, ""],
         ["bench.batch_end", 5.2, 1.6, ""],
         ["bench.window_end", 10.0, 0.0, ""]]))
    assert red.window == (0.0, 10.0) and red.window_from == "annotations"
    d = red.devices[0]
    assert d.busy == [(1.0, 5.0), (7.0, 8.0)]
    assert d.gaps == [(0.0, 1.0), (5.0, 7.0), (8.0, 10.0)]
    assert red.idle_share() == pytest.approx(0.5)
    assert d.cat_s["convolution fusion"] == pytest.approx(1.0)
    assert d.cat_s["collective"] == pytest.approx(1.0)
    assert d.cat_s["other fusion"] == pytest.approx(1.5)
    assert d.cat_s["copy/transfer"] == pytest.approx(1.5)
    assert d.cat_s["other"] == pytest.approx(0.0)        # while: self time
    # the all-reduce runs alone from 2.0 to 2.5
    assert d.exposed_s("collective") == pytest.approx(0.5)
    assert red.host_activity(5.0, 7.0) == "bench.batch_end"
    assert red.host_activity(8.0, 10.0) == xplane.IN_PROGRAM
    bd = red.breakdown()
    assert bd["device_ops"][0] == ["fusion.2 [other fusion]", 1.5]
    assert bd["idle_gaps"][0] == ["bench.batch_end", 2.0]
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 5


def test_reduction_without_device_events_is_nothing():
    assert not xplane.Reduced(events([], [["bench.batch_end", 0, 1, ""]]))
    assert xplane.reduce_trace(os.path.join(HERE, "no_such_dir")) is None


def test_reduction_on_the_recorded_trace():
    """fixtures/<name>.events.json.gz is read_events() of a trace taken on
    the chip, cut to a few dispatches; fixtures/<name>.expect.json is what
    the reduction gave when the fixture was recorded and looked at by hand."""
    import gzip
    names = [f[:-len(".events.json.gz")] for f in
             os.listdir(os.path.join(HERE, "fixtures"))
             if f.endswith(".events.json.gz")]
    assert names, "no recorded trace in fixtures/"
    for name in names:
        base = os.path.join(HERE, "fixtures", name)
        with gzip.open(base + ".events.json.gz", "rt") as f:
            red = xplane.Reduced(json.load(f))
        want = json.load(open(base + ".expect.json"))
        assert red.window_from == want["window_from"]
        assert red.window_s == pytest.approx(want["window_s"])
        assert red.busy_s == pytest.approx(want["busy_s"])
        assert len(red.devices[0].gaps) == want["n_gaps"]
        for cat, t in want["category_s"].items():
            assert red.devices[0].cat_s[cat] == pytest.approx(t)
        assert red.breakdown()["idle_gaps"][0][0] == want["longest_gap_host"]
