"""`trinity_mini.train`, checked without a chip: the cell's rehearsal with
and without a trace (toy widths of the configuration's `rehearse` block,
the same runner, reference and checks as on the chip), the faults each
limit of `correct` has to refuse, the operation counts of the table its
readers use, and the configuration against the catalog's row."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, os.path.dirname(os.path.abspath(__file__))]

import flops_afmoe as flops                             # noqa: E402
import op_tables                                        # noqa: E402
from test_harness import (CONTRACT_KEYS, DEVICE_KEYS, named,  # noqa: E402
                          rehearse)

CELL = "trinity_mini.train"
NEW_READERS = {"swa_attn_share.train", "swa_roofline_share.train",
               "global_attn_roofline_share.train", "mfu.train"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(HERE, "configs", "trinity_mini_26b_a3b.json")) as f:
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
    assert train["moe"]["moe_tokens_dropped_total"] == 0
    assert train["moe"]["moe_tokens_routed_total"] > 0
    assert train["dense_attention_calls"] == 0
    errs = {k: v for ln in lines if ln["line"] == "against_reference"
            for k, v in ln.items()}
    with open(os.path.join(HERE, "traffic",
                           "fit_fused_k2_tokens_8k_swa.json")) as f:
        checked = json.load(f)["checked_parameters"]
    # a windowed and the full layer's attention, the gate, a sandwich
    # norm, the router, a held and the shared expert, both ends
    assert {"l0_wq", "l0_wg", "l0_post_norm1", "l2_wq", "l1_w_r", "l1_e_up",
            "l1_s_up", "embed", "head"} <= set(checked)
    assert set(checked) | {"logits", "first_step_loss_rel_err",
                           "update_rel_err", "update_rel_err_worst"} <= \
        set(errs)
    assert 0 < errs["update_rel_err"] < 1
    assert {"afmoe_head", "afmoe_l0_wg", "afmoe_l3_post_norm2"} <= \
        set(errs["update_rel_err_by_parameter"])


def test_per_layer_line(bench):
    out, lines = rehearse(CELL, 1)
    assert set(out) - {"breakdown"} == CONTRACT_KEYS and out["correct"]
    want = named(bench, "per_layer", CELL)
    assert NEW_READERS <= set(want)
    # no other family's table or scope reader
    assert not {n for n in want if n.startswith((
        "kda_", "linear_attn", "latent_attn", "dsv3_", "rope_share",
        "lfm2_", "short_conv", "gqa_attn", "mfu_", "conv_share",
        "model_flops_util", "expert_matmul"))}
    assert set(out["metrics"]) <= set(want)
    # a CPU gives no device trace: the scope and roofline readers return
    # nothing; every clock, span and counter metric of the cell is there
    host = {n for n, m in want.items()
            if m["source"] in ("host_clock", "program_span")}
    assert "mfu.train" in host
    assert host | {"expert_load_max_over_mean.train",
                   "expert_dense_fallback_share.train",
                   "compiles_in_window.train"} <= set(out["metrics"])
    assert out["metrics"]["compiles_in_window.train"]["value"] == 0
    calls = [ln for ln in lines if ln["line"] == "attention_calls"]
    # the four windowed layers' calls, and the full layer's, were traced
    assert calls and calls[0]["window_attention_calls_total"] >= 4
    assert calls[0]["gqa_attention_calls_total"] >= 5
    assert calls[0]["attention_dense_fallback_total"] == 0


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


# -- controls: what each limit has to refuse, planted into the rehearsal ------
# A plant is Python run before benchmarks/run.py in the same process: it
# swaps a piece of the SYSTEM for a faulty one (the reference imports
# nothing from it), or nothing where the control is a key of the traffic
# file.

PLANTS = {
    "float8_reference": "",
    # every windowed call computed causal over the whole sequence
    "no_window": """
from mxnet_tpu.ops import attention
exact = attention.flash_attention
attention.flash_attention = lambda *a, **kw: exact(*a, **dict(kw,
                                                              window=None))
""",
    # the full layer rotated as the windowed ones are
    "rotation_on_the_full_layer": """
from mxnet_tpu.gluon.nn import decoder
decoder.FAMILIES["afmoe"]["rotate"] = ("full_attention", "sliding_attention")
""",
    # the output gate a constant one half: its weights have no say
    "no_gate": """
from mxnet_tpu.gluon.nn import decoder
exact = decoder._dense
def dense(F, x, weight, units):
    y = exact(F, x, weight, units)
    return y * 0 if getattr(weight, "name", "").endswith("_wg") else y
decoder._dense = dense
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
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(ln) for ln in proc.stdout.strip().splitlines()]
    return lines[-1], [ln["what"] for ln in lines if ln.get("line") == "fault"]


@pytest.mark.parametrize("plant,arguments,refused_by", [
    ("float8_reference",
     ["--traffic-set", 'reference_rounding="float8_e4m3fn"'],
     "first step's per-token losses"),
    ("no_window", [], "float32 l0_wq"),
    ("rotation_on_the_full_layer", [], "float32 l2_wq"),
    ("no_gate", [], "float32 l0_wg"),
])
def test_controls_come_out_not_correct(plant, arguments, refused_by):
    """Each limit of `correct` refuses the fault it is there for, through
    the runner's own comparison."""
    out, faults = rehearse_planted(plant, *arguments)
    assert out["correct"] is False
    assert any(refused_by in f for f in faults), faults


# -- the table of operations ------------------------------------------------------

def test_operation_counts_of_the_held_share(config):
    """The held parameters and the forward multiply-adds a token of the
    configuration, part by part, counted by hand from the published
    widths."""
    assert op_tables.table(config) is flops
    assert op_tables.table({"model": "lfm2_moe"}) is None
    assert flops.attention_params(config) == 27_263_232
    assert flops.dense_layer_params(config) == 65_020_160
    assert flops.expert_layer_params(config) == 84_156_800
    assert flops.router_params(config) == 262_144 + 128
    assert flops.shared_params(config) == flops.expert_params(config) == \
        6_291_456
    assert flops.head_params(config) == 102_500_352
    assert flops.total_params(config) == 504_147_712
    # 16 B a parameter: 8.07 GB
    assert 8.06e9 < 16 * flops.total_params(config) < 8.07e9
    macs = flops.macs_by_mechanism(config)

    def close(got, want):
        return abs(got - want) <= 0.0015 * want
    assert close(macs["attention_products"], 5 * 27.26e6)
    assert flops.mean_keys(config, 2048) == 1792.125
    assert close(macs["window_cores"], 58.72e6)
    assert close(macs["full_cores"], 33.55e6)
    assert close(macs["mlp"], 37.75e6) and close(macs["shared"], 25.17e6)
    assert close(macs["routed"], 12.58e6) and close(macs["router"], 1.05e6)
    assert close(macs["head"], 51.25e6)
    assert close(sum(macs.values()), 356.4e6)
    assert close(flops.train_flops_per_sequence(config), 17.52e12)
    # k and v once a group: 4 heads, not 32; the four windowed layers
    flops_w, bytes_w = flops.window_core_step(config, 16384)
    assert bytes_w == 4 * 3 * 16384 * 2 * 128 * (2 * 32 + 2 * 4)
    assert flops_w == 4 * 6 * 16384 * 1792.125 * 32 * 2 * 128
    assert flops.full_core_step(config, 16384)[1] == bytes_w // 4


@pytest.mark.parametrize("reader,scopes,want", [
    ("swa_roofline_share.train",
     {"mx.flash_attention.window": 0.06, "mx.flash_attention": 0.02}, 1),
    ("global_attn_roofline_share.train",
     {"mx.flash_attention.window": 0.06, "mx.flash_attention": 0.02}, 2),
    ("global_attn_roofline_share.train", {"mx.flash_attention.window": 0.06},
     None),
])
def test_roofline_readers_take_their_own_seconds(config, monkeypatch, reader,
                                                 scopes, want):
    """The windowed cores' share reads the seconds under
    `mx.flash_attention.window` alone; the full layer's, those under
    `mx.flash_attention` without it (the windowed scope lies inside it),
    and nothing where the full layer has no seconds."""
    from reduce import op_scopes
    from test_harness import load_run
    module = load_run().load_file_module("layer_metrics", reader)

    class Ctx:
        host = {"sequences_per_step": 2, "steps_per_dispatch": 2}
        traffic = {"traced_dispatches": 3}
        peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
        trace = True

    Ctx.config = config
    from mxnet_tpu.telemetry import registry
    registry.counter("window_attention_calls_total").inc()
    seconds = op_scopes.Scopes([], (0, 1))
    seconds.self_s = {k: {"f": v / 2, "b": v / 2} for k, v in scopes.items()}
    monkeypatch.setattr(op_scopes, "of_run", lambda ctx: seconds)
    got = module.compute(Ctx)
    if want is None:
        assert got is None
        return
    steps_s = {1: 0.06, 2: 0.02}[want] / 6
    step = flops.window_core_step if want == 1 else flops.full_core_step
    least = max(a / b for a, b in zip(step(config, 16384),
                                      (197e12, 819e9)))
    assert abs(got - 100 * least / steps_s) < 1e-9 * got


def test_configuration_keeps_every_published_width(config):
    """Every number of the catalog's row is the file's under the same key
    but those `reduced` names, whose published values the file keeps; the
    file states the deployment and what it assumed."""
    published = dict(config, **config["published"])
    assert config["reduced"] == ["num_hidden_layers", "layer_types",
                                 "num_dense_layers", "num_experts",
                                 "vocab_size"]
    assert (published["num_hidden_layers"], published["num_dense_layers"],
            published["num_experts"], published["vocab_size"]) == \
        (32, 2, 128, 200192)
    # published layers 2-6: the second leading dense layer, counted once,
    # then one whole 3 : 1 period
    assert config["layer_types"] == published["layer_types"][1:6] == [
        "sliding_attention", "sliding_attention", "full_attention",
        "sliding_attention", "sliding_attention"]
    assert (config["num_hidden_layers"], config["num_dense_layers"],
            config["num_experts"], config["vocab_size"]) == (5, 1, 8, 25024)
    for key, value in {
            "hidden_size": 2048, "num_attention_heads": 32,
            "num_key_value_heads": 4, "head_dim": 128,
            "sliding_window": 2048, "intermediate_size": 6144,
            "moe_intermediate_size": 1024, "num_experts_per_tok": 8,
            "num_shared_experts": 1, "route_scale": 2.826,
            "rms_norm_eps": 1e-05, "rope_theta": 10000}.items():
        assert config[key] == value, key
    assert "16 chips share each layer" in config["deployment"]
    assert "a sixteenth" in config["assumed"]["experts_load"]
    assert "load_balance_coeff" in config["assumed"]
