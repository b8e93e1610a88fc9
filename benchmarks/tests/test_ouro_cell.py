"""`ouro.train`, checked without a chip: the cell's rehearsal with and
without a trace (toy widths of the configuration's `rehearse` block, the
same runner, reference and checks as on the chip), the faults each limit
of `correct` has to refuse, the program without a loop stopping at build,
the operation and parameter counts its readers use, the two scope readers,
and the configuration against the catalog's row."""
import importlib.util
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, os.path.dirname(os.path.abspath(__file__))]

import flops_ouro as flops                              # noqa: E402
from test_harness import (CONTRACT_KEYS, DEVICE_KEYS, named,  # noqa: E402
                          rehearse)

CELL = "ouro.train"
TRAFFIC = "fit_fused_k2_tokens_8k_loop"
NEW_READERS = {"loop_share.train", "exit_share.train", "mfu_ouro.train"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(HERE, "configs", "ouro_2_6b.json")) as f:
        return json.load(f)


def test_end_to_end_line(bench):
    out, lines = rehearse(CELL, 0)
    assert set(out) == CONTRACT_KEYS
    assert set(out["device"]) == DEVICE_KEYS
    assert out["device"]["platform"] == "cpu"
    assert out["correct"] is True, \
        [ln for ln in lines if ln["line"] == "fault"]
    assert out["attempted"] > 0 and out["failed"] == 0
    want = named(bench, "end_to_end", CELL)
    assert set(out["metrics"]) == set(want) == {"train_rate", "setup_s"}
    train = [ln for ln in lines if ln["line"] == "train"][0]
    assert train["dense_attention_calls"] == 0 and "moe" not in train
    errs = {k: v for ln in lines if ln["line"] == "against_reference"
            for k, v in ln.items()}
    with open(os.path.join(HERE, "traffic", TRAFFIC + ".json")) as f:
        checked = json.load(f)["checked_parameters"]
    # the gate, the head, one layer's q, k, o, w_up and its four norms
    assert {"exit_gate", "exit_gate_bias", "head", "l0_wq", "l0_wk",
            "l0_wo", "l0_w_up", "l0_norm1", "l0_post_norm1", "l0_norm2",
            "l0_post_norm2"} <= set(checked)
    assert {"logits", "exit_probs", "first_step_loss_rel_err",
            "update_rel_err", "update_rel_err_worst"} <= set(errs)
    assert 0 < errs["update_rel_err"] < 1
    assert errs["fp32_sequence"] == [0, 0]
    # every pass takes a share of the exits in the reference
    assert min(errs["exit_probs_mean_reference"]) > 0.01
    assert {"ouro_head", "ouro_exit_gate", "ouro_l1_post_norm2"} <= \
        set(errs["update_rel_err_by_parameter"])
    # the gate's bias is compared within the gate's leaf, by (u) and (b)
    assert "ouro_exit_gate_bias" not in errs["update_rel_err_by_parameter"]
    assert "exit_gate" in errs and "exit_gate_bias" not in errs


def test_per_layer_line(bench):
    out, lines = rehearse(CELL, 1)
    assert set(out) - {"breakdown"} == CONTRACT_KEYS and out["correct"]
    want = named(bench, "per_layer", CELL)
    assert NEW_READERS <= set(want)
    # no mixture's, no other family's table or scope reader
    assert not {n for n in want if n.startswith((
        "kda_", "linear_attn", "latent_attn", "dsv3_", "rope_share",
        "lfm2_", "short_conv", "gqa_attn", "swa_", "global_attn", "expert",
        "conv_share", "model_flops_util", "collective"))}
    assert not {"mfu.train", "mfu_lm.train"} & set(want)
    assert set(out["metrics"]) <= set(want)
    # a CPU gives no device trace: the scope readers return nothing; every
    # clock, span and counter metric of the cell is there
    host = {n for n, m in want.items()
            if m["source"] in ("host_clock", "program_span")}
    assert "mfu_ouro.train" in host
    assert host | {"compiles_in_window.train"} <= set(out["metrics"])
    assert out["metrics"]["compiles_in_window.train"]["value"] == 0


def test_new_cell_is_refused_by_name_without_its_entries(tmp_path):
    """A BENCHMARK.json without the cell stops at once at `by_name`,
    before jax is imported."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["workloads"] = [w for w in bench["workloads"]
                          if w["name"] != CELL]
    os.symlink(HERE, tmp_path / "benchmarks")
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    proc = subprocess.run(
        [sys.executable, str(tmp_path / "benchmarks" / "run.py"),
         "--workload", CELL], cwd=tmp_path, capture_output=True, text=True,
        timeout=120)
    assert proc.returncode != 0
    assert "is not in BENCHMARK.json" in proc.stderr


def load_runner(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(HERE, "runners", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_update_check_takes_the_gate_and_its_bias_as_one_leaf():
    """Check (u) where the bias's first step went the other way and the
    reference's two steps nearly cancel: the bias alone reads over the
    leaf limit, the gate as one leaf does not, and the error over all
    parameters is the same number either way."""
    tokens_runner = load_runner("train_fit_tokens")
    dense = load_runner("train_fit_tokens_dense")
    traffic = {"update_tolerance": 0.3, "update_leaf_tolerance": 0.4}
    env = type("Env", (), {"traffic": traffic})()
    rng = np.random.default_rng(5)
    lr = 1e-4
    before = {"m_exit_gate": rng.normal(size=2048),
              "m_exit_gate_bias": np.zeros(1), "m_head": rng.normal(size=64)}
    want = {"m_exit_gate": 1.9 * lr * rng.choice([-1.0, 1.0], 2048),
            "m_exit_gate_bias": np.array([0.27 * lr]),
            "m_head": 1.9 * lr * rng.choice([-1.0, 1.0], 64)}
    flip = rng.random(2048) < 0.016          # a weight leaf reading ~0.25
    got = {"m_exit_gate": before["m_exit_gate"] + np.where(
               flip, -1.0, 1.0) * want["m_exit_gate"],
           "m_exit_gate_bias": np.array([-1.7 * lr]),
           "m_head": before["m_head"] + want["m_head"]}
    alone, joined = [], []
    errs = tokens_runner.check_update(env, before, got, want, alone)
    assert errs["update_worst_parameter"] == "m_exit_gate_bias"
    assert errs["update_rel_err_worst"] > 7 and len(alone) == 1
    groups = [["exit_gate", "exit_gate_bias"]]
    joint = tokens_runner.check_update(
        env, *dense.joined_leaves(groups, "m_", before, got, want), joined)
    assert joined == [] and set(joint["update_rel_err_by_parameter"]) == {
        "m_exit_gate", "m_head"}
    assert 0.2 < joint["update_rel_err_by_parameter"]["m_exit_gate"] < 0.3
    assert joint["update_rel_err"] == pytest.approx(errs["update_rel_err"],
                                                    rel=1e-12)
    # the trees it was given are left as they were
    assert set(before) == {"m_exit_gate", "m_exit_gate_bias", "m_head"}


# -- controls: what each limit has to refuse, planted into the rehearsal ------
# A plant is Python run before benchmarks/run.py in the same process: it
# swaps a piece of the SYSTEM for a faulty one (the reference imports
# nothing from it), or nothing where the control is a key of the traffic
# file.

PLANTS = {
    "float8_reference": "",
    # the last pass's plain cross-entropy trained in place of the objective
    "last_pass_plain_ce": """
from mxnet_tpu.gluon.nn import decoder
exact = decoder._exit_objective
def last(F, ces, gates, beta):
    return ces[-1], exact(F, ces, gates, beta)[1]
decoder._exit_objective = last
""",
    # the gates' logits held at 0: every lambda one half
    "gate_held_at_half": """
from mxnet_tpu.gluon.nn import decoder
exact = decoder._exit_objective
decoder._exit_objective = lambda F, ces, gates, beta: exact(
    F, ces, [g * 0 for g in gates], beta)
""",
    # a DecoderLM that does not loop (the parent program's): no exit gate
    "no_loop": """
from mxnet_tpu.gluon.nn import decoder
exact = decoder.DecoderLM.__init__
def init(self, cfg, **kw):
    exact(self, {k: v for k, v in cfg.items() if k != "total_ut_steps"},
          **kw)
decoder.DecoderLM.__init__ = init
""",
}


def rehearse_planted(plant, *arguments):
    code = ("import os, sys, runpy\n"
            "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
            f"sys.path.insert(0, {ROOT!r})\n" + PLANTS[plant] +
            f"sys.argv = [{os.path.join(HERE, 'run.py')!r}, '--workload', "
            f"{CELL!r}, '--seed', '2147483659', '--seconds', '2', "
            f"'--trace', '0', '--rehearse', *{list(arguments)!r}]\n"
            "runpy.run_path(sys.argv[0], run_name='__main__')\n")
    return subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          capture_output=True, text=True, timeout=900)


@pytest.mark.parametrize("plant,arguments,refused_by", [
    ("float8_reference",
     ["--traffic-set", 'reference_rounding="float8_e4m3fn"'],
     "first step's per-token losses"),
    ("last_pass_plain_ce", [], "first step's per-token losses"),
    ("last_pass_plain_ce", [], "float32 exit_gate,"),
    ("gate_held_at_half", [], "float32 exit_probs"),
])
def test_controls_come_out_not_correct(plant, arguments, refused_by):
    """Each limit of `correct` refuses the fault it is there for, through
    the runner's own comparison."""
    proc = rehearse_planted(plant, *arguments)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(ln) for ln in proc.stdout.strip().splitlines()]
    faults = [ln["what"] for ln in lines if ln.get("line") == "fault"]
    assert lines[-1]["correct"] is False
    assert any(refused_by in f for f in faults), faults


def test_a_program_without_the_loop_stops_at_build():
    """The parent's DecoderLM builds no exit gate: the run stops before a
    weight is drawn, within seconds, and says why."""
    t0 = time.perf_counter()
    proc = rehearse_planted("no_loop")
    assert proc.returncode != 0
    assert "exit_gate" in proc.stderr and "total_ut_steps" in proc.stderr
    assert time.perf_counter() - t0 < 60
    assert "against_reference" not in proc.stdout


# -- operations and parameters ----------------------------------------------------

def test_operation_counts_of_the_held_stage(config):
    """The held parameters and the forward multiply-adds a token, part by
    part, counted by hand from the published widths."""
    assert flops.attention_params(config) == 4 * 2048 * 2048 == 16_777_216
    assert flops.mlp_params(config) == 3 * 2048 * 5632 == 34_603_008
    assert flops.layer_params(config) == 51_388_416
    assert flops.head_params(config) == 201_326_592
    assert flops.exit_params(config) == 4_097
    assert flops.total_params(config) == 509_661_185
    # 16 B a parameter: 8.15 GB; at 4 layers 406,884,353
    assert 8.15e9 < 16 * flops.total_params(config) < 8.16e9
    assert flops.total_params(dict(config, num_hidden_layers=4)) == \
        406_884_353
    assert flops.mean_keys(config) == 4096.5
    macs = flops.macs_by_mechanism(config)
    assert macs["attention_products"] == 24 * 16_777_216
    assert macs["attention_cores"] == 24 * 4096.5 * 16 * 2 * 128
    assert macs["mlp"] == 24 * 34_603_008
    assert macs["head"] == 4 * 100_663_296
    assert macs["exit_gate"] == 3 * 2048
    assert abs(sum(macs.values()) - 2_038.5e6) < 0.05e6
    assert abs(flops.train_flops_per_sequence(config) - 100.2e12) < 0.05e12
    # the heads' share of the operations: 19.8% here, 3.0% at 48 layers
    share = macs["head"] / sum(macs.values())
    assert abs(share - 0.198) < 0.001
    published = flops.macs_by_mechanism(dict(config, num_hidden_layers=48))
    assert abs(published["head"] / sum(published.values()) - 0.030) < 0.001
    # and the loop 80% of them
    loop = macs["attention_products"] + macs["attention_cores"] + macs["mlp"]
    assert abs(loop / sum(macs.values()) - 0.80) < 0.005


@pytest.mark.parametrize("reader,scopes,want", [
    ("loop_share.train", {"mx.loop": 10, "mx.gqa": 20, "mx.gqa.rope": 1,
                          "mx.flash_attention": 30, "mx.mlp": 14,
                          "mx.lm_head": 15, "mx.exit_gate": 1,
                          "mx.optimizer": 9}, 75),
    ("exit_share.train", {"mx.loop": 10, "mx.gqa": 20, "mx.gqa.rope": 1,
                          "mx.flash_attention": 30, "mx.mlp": 14,
                          "mx.lm_head": 15, "mx.exit_gate": 1,
                          "mx.optimizer": 9}, 16),
    ("loop_share.train", {"mx.gqa": 20, "mx.flash_attention": 30}, None),
    ("exit_share.train", {"mx.lm_head": 15}, None),
])
def test_scope_readers_take_their_own_seconds(monkeypatch, reader, scopes,
                                              want):
    """The loop's share sums `mx.loop` and the layers' scopes under it; the
    exit's, the heads' and the gates'; a program without `mx.loop` or
    `mx.exit_gate` (another family's) reads nothing."""
    from reduce import op_scopes
    from test_harness import load_run
    module = load_run().load_file_module("layer_metrics", reader)
    seconds = op_scopes.Scopes([], (0, 1))
    seconds.self_s = {k: {"f": v / 2, "b": v / 2} for k, v in scopes.items()}
    seconds.busy_s = 100.0
    monkeypatch.setattr(op_scopes, "of_run", lambda ctx: seconds)
    got = module.compute(object())
    if want is None:
        assert got is None
    else:
        assert abs(got - want) < 1e-9


def test_configuration_keeps_every_published_width(config):
    """Every number of the catalog's row is the file's under the same key
    but those `reduced` names, whose published values the file keeps; the
    file states the deployment and what it assumed."""
    assert config["reduced"] == ["num_hidden_layers", "layer_types"]
    assert config["published"]["num_hidden_layers"] == 48
    assert config["published"]["layer_types"] == ["full_attention"] * 48
    assert config["layer_types"] == ["full_attention"] * 6
    assert config["num_hidden_layers"] == 6
    for key, value in {
            "hidden_size": 2048, "num_attention_heads": 16,
            "num_key_value_heads": 16, "head_dim": 128,
            "intermediate_size": 5632, "vocab_size": 49152,
            "total_ut_steps": 4, "early_exit_threshold": 1,
            "use_sliding_window": False, "rms_norm_eps": 1e-06,
            "rope_theta": 1000000, "tie_word_embeddings": False}.items():
        assert config[key] == value, key
    assert "8 pipeline stages" in config["deployment"]
    assert {"exit_entropy_weight", "family_features", "attention",
            "optimizer"} <= set(config["assumed"])
