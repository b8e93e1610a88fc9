"""`lfm2_moe.train`, checked without a chip: the cell's rehearsal with and
without a trace (toy widths of the configuration's `rehearse` block, the
same runner, reference and checks as on the chip), the controls each limit
of `correct` has to refuse, and the operation counts against ISSUE 32."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, os.path.dirname(os.path.abspath(__file__))]

import flops_lfm2_moe as flops                          # noqa: E402
from test_harness import (CONTRACT_KEYS, DEVICE_KEYS, named,  # noqa: E402
                          rehearse)
from test_kimi_linear_cell import PLANTS as KIMI_PLANTS  # noqa: E402

CELL = "lfm2_moe.train"
NEW_READERS = {"mfu_lfm2_moe.train", "short_conv_share.train",
               "gqa_attn_share.train",
               "short_conv_bwd_roofline_share.train",
               "lfm2_attn_roofline_share.train",
               "lfm2_expert_matmul_roofline_share.train"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(HERE, "configs", "lfm2_8b_a1b.json")) as f:
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
    # at 8 held of 32 and 4 a token the grouped rows are every pair: the
    # dense path cannot be taken
    assert train["moe"]["moe_dense_fallback_total"] == 0
    assert train["dense_attention_calls"] == 0
    # every mechanism's gradient was compared with the reference's
    with open(os.path.join(HERE, "traffic",
                           "fit_fused_k2_tokens_8k_sconv.json")) as f:
        checked = json.load(f)["checked_parameters"]
    assert {"l0_w_in", "l0_taps", "l0_w_up", "l1_wq", "l1_wk", "l1_q_norm",
            "l1_w_r", "l1_e_up", "l4_taps", "l4_w_out", "embed"} <= \
        set(checked)
    errs = {k: v for ln in lines if ln["line"] == "against_reference"
            for k, v in ln.items()}
    assert set(checked) | {"logits", "first_step_loss_rel_err",
                           "update_rel_err", "update_rel_err_worst"} <= \
        set(errs)
    assert 0 < errs["update_rel_err"] < 1
    # the tied head: one parameter, no `head` among the updated ones
    assert "lfm2_embed" in errs["update_rel_err_by_parameter"]
    assert "lfm2_head" not in errs["update_rel_err_by_parameter"]


def test_per_layer_line(bench):
    out, lines = rehearse(CELL, 1)
    assert set(out) - {"breakdown"} == CONTRACT_KEYS and out["correct"]
    want = named(bench, "per_layer", CELL)
    assert NEW_READERS <= set(want)
    assert not {n for n in want if n.startswith((
        "kda_", "linear_attn", "latent_attn", "dsv3_", "rope_share"))
        or n in ("mfu_lm.train", "mfu_deepseek_v3.train",
                 "expert_matmul_roofline_share.train")}
    assert set(out["metrics"]) <= set(want)
    # a CPU gives no device trace: the scope and roofline readers return
    # nothing; every clock, span and counter metric of the cell is there
    host = {n for n, m in want.items()
            if m["source"] in ("host_clock", "program_span")}
    assert "mfu_lfm2_moe.train" in host
    assert host | {"expert_load_max_over_mean.train",
                   "expert_dense_fallback_share.train",
                   "compiles_in_window.train"} <= set(out["metrics"])
    assert out["metrics"]["compiles_in_window.train"]["value"] == 0
    assert out["metrics"]["expert_dense_fallback_share.train"]["value"] == 0
    assert out["metrics"]["expert_load_max_over_mean.train"]["value"] >= 1


def test_new_cell_is_refused_by_name_without_its_entries(tmp_path):
    """What the parent commit does with `--workload lfm2_moe.train`: a
    BENCHMARK.json without the cell stops at once at `by_name`, before jax
    is imported."""
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
# swaps a function of the SYSTEM for a faulty one (the reference imports
# nothing from it), or nothing where the control is a key of the traffic
# file.

PLANTS = {
    "float8_reference": "",
    # interleaved pairs (2i, 2i + 1) where the halves (i, i + 32) are asked
    "interleaved_pairs": """
from mxnet_tpu.ops import lm
asked = lm.rope
lm.rope = lambda x, rotary_dim, offset, theta, interleave: asked(
    x, rotary_dim, offset, theta, not interleave)
""",
    # k/v heads grouped by 8 where 4 are asked: the program keeps the
    # first half of the k/v heads and hands each to twice its group
    "grouped_by_eight": """
from mxnet_tpu.ops import attention
import jax.numpy as jnp
exact = attention.flash_attention
def regrouped(q, k, v, **kw):
    half = k.shape[1] // 2
    if half and k.shape[1] != q.shape[1]:
        k, v = k[:, :half], v[:, :half]
    return exact(q, k, v, **kw)
attention.flash_attention = regrouped
""",
    # the backward sees half the batch (the forward, check (a), is whole)
    "half_batch_gradient": KIMI_PLANTS["half_batch_gradient"],
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


@pytest.mark.parametrize("plant,arguments,refused_by,passed_by", [
    ("float8_reference",
     ["--traffic-set", 'reference_rounding="float8_e4m3fn"'],
     "first step's per-token losses", None),
    ("interleaved_pairs", [], "float32 l1_wq", None),
    ("grouped_by_eight", [], "float32 l1_wk", None),
    ("half_batch_gradient", [],
     "change of the parameters over the first dispatch",
     "first step's per-token losses"),
])
def test_controls_come_out_not_correct(plant, arguments, refused_by,
                                       passed_by):
    """Each limit of `correct` refuses the fault it is there for, through
    the runner's own comparison, and the faults it is not there for leave
    it alone."""
    out, faults = rehearse_planted(plant, *arguments)
    assert out["correct"] is False
    assert any(refused_by in f for f in faults), faults
    if passed_by:
        assert not any(passed_by in f for f in faults), faults


def test_operation_counts_against_issue_32(config):
    assert flops.total_params(config) == 507_820_288
    assert flops.conv_operator_params(config) == 16_783_360
    assert flops.gqa_operator_params(config) == 10_485_888
    assert flops.dense_mlp_params(config) == 44_040_192
    assert flops.expert_params(config) == 11_010_048
    assert flops.expert_layer_mlp_params(config) == \
        32 * 2048 + 32 + 8 * 11_010_048
    # 16 B a parameter: 8.13 GB, half the chip
    assert 8.12e9 < 16 * flops.total_params(config) < 8.13e9
    macs = flops.macs_by_mechanism(config)
    close = lambda got, want: abs(got - want) <= 0.0005 * want
    assert close(macs["sconv"], 4 * 16.78e6)
    assert close(macs["gqa"], 10.49e6 + 16.78e6)
    assert flops.gqa_core_macs(config) == 32 * 8192 * (64 + 64) // 2
    assert close(macs["moe"], 4 * 0.0655e6 + 44.04e6)
    assert close(flops.routed_expert_macs(config), 11.01e6)
    assert close(macs["mlp"], 44.04e6) and close(macs["head"], 33.55e6)
    assert close(sum(macs.values()), 216.29e6)
    assert close(flops.train_flops_per_sequence(config), 10.63e12)
    # the gated pass: forward reads 3C and writes C a token, backward reads
    # 4C and writes 3C, 2 bytes each, four layers
    forward, backward = flops.short_conv_step(config, 16384)
    assert (forward[1], backward[1]) == (4 * 16384 * 2 * 2048 * 4,
                                         4 * 16384 * 2 * 2048 * 7)
    # k and v are counted once a group: 8 heads, not 32
    assert flops.gqa_core_step(config, 16384)[1] == \
        3 * 16384 * 2 * 64 * (2 * 32 + 2 * 8)


@pytest.mark.parametrize("forward_s,backward_s,want", [
    (0.0, 0.06576, 20.93),      # the forward fused into its product: PR 32
    (0.02, 0.06576, 20.93),     # forward seconds under the scope: left out
    (0.0, 0.0125, 110.13),      # bytes counted too high read over 100
    (0.02, 0.0, None),          # no backward under the scope: nothing
])
def test_short_conv_roofline_is_the_backward_pass_alone(
        config, monkeypatch, forward_s, backward_s, want):
    """The backward gated pass's bytes over the backward seconds under
    `mx.sconv.conv`, whatever the reading comes to: the traced runs of
    PR 32 (10.96 ms a step under the scope, backward) read 20.9; a count of
    bytes that is too high, or seconds filed elsewhere, shows as a share
    over 100 and is not cut."""
    from reduce import op_scopes
    from test_harness import load_run
    reader = load_run().load_file_module(
        "layer_metrics", "short_conv_bwd_roofline_share.train")

    class Scopes:
        self_s = {"mx.sconv.conv": {"f": forward_s, "b": backward_s}}

    class Ctx:
        host = {"sequences_per_step": 2, "steps_per_dispatch": 2}
        traffic = {"traced_dispatches": 3}
        peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

    Ctx.config = config
    monkeypatch.setattr(op_scopes, "of_run", lambda ctx: Scopes)
    got = reader.compute(Ctx)
    assert got is None if want is None else abs(got - want) < 0.01
    Scopes.self_s = {}
    assert reader.compute(Ctx) is None


def test_configuration_keeps_every_published_width(config):
    """No width differs from the source: what `reduced` names is all that
    the file changes of config.json, and the file says what the uncut
    values were and what deployment the cut stands for."""
    types = ["conv", "conv", "full_attention", "conv", "conv", "conv",
             "full_attention", "conv", "conv", "conv", "full_attention",
             "conv", "conv", "conv", "full_attention", "conv", "conv", "conv",
             "full_attention", "conv", "conv", "full_attention", "conv",
             "conv"]
    published = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 7168, "layer_types": types,
        "max_position_embeddings": 128000, "model_type": "lfm2_moe",
        "moe_intermediate_size": 1792, "norm_eps": 1e-05,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4,
        "num_hidden_layers": 24, "num_key_value_heads": 8,
        "rope_theta": 1000000, "routed_scaling_factor": 1,
        "use_expert_bias": True, "vocab_size": 65536}
    differs = {k for k, v in published.items() if config[k] != v}
    assert differs == set(config["reduced"]) == \
        {"num_hidden_layers", "layer_types", "num_dense_layers",
         "num_experts", "vocab_size"}
    assert config["published"] == {k: published[k] for k in differs}
    # the second leading dense layer, counted once, then one whole period
    assert config["layer_types"] == types[1:6] == \
        ["conv", "full_attention", "conv", "conv", "conv"]
    assert (config["num_hidden_layers"], config["num_dense_layers"],
            config["num_experts"], config["vocab_size"]) == (5, 1, 8, 16384)
    assert config["tie_word_embeddings"] is True
    assert "tie_word_embeddings" in config["assumed"]
    assert "4 chips share each layer" in config["deployment"]
    assert "a quarter" in config["assumed"]["experts_load"]
