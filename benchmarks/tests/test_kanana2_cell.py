"""`kanana2.train`, checked without a chip: the cell's rehearsal with and
without a trace (toy widths of the configuration's `rehearse` block, the
same runner, reference and checks as on the chip), the controls each limit
of `correct` has to refuse, and the operation counts against ISSUE 30."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, os.path.dirname(os.path.abspath(__file__))]

import flops_deepseek_v3 as flops                       # noqa: E402
from test_harness import (CONTRACT_KEYS, DEVICE_KEYS, named,  # noqa: E402
                          rehearse)
from test_kimi_linear_cell import PLANTS as KIMI_PLANTS  # noqa: E402

CELL = "kanana2.train"
NEW_READERS = {"mfu_deepseek_v3.train", "rope_share.train",
               "dsv3_latent_attn_roofline_share.train",
               "dsv3_expert_matmul_roofline_share.train"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(HERE, "configs", "kanana_2_30b_a3b.json")) as f:
        return json.load(f)


def test_end_to_end_line(bench):
    out, lines = rehearse(CELL, 0)
    assert set(out) == CONTRACT_KEYS
    assert set(out["device"]) == DEVICE_KEYS
    assert out["device"]["platform"] == "cpu" and out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    want = named(bench, "end_to_end", CELL)
    assert set(out["metrics"]) == set(want) == {"train_rate", "setup_s"}
    train = [ln for ln in lines if ln["line"] == "train"][0]
    assert train["moe"]["moe_tokens_dropped_total"] == 0
    assert train["moe"]["moe_tokens_routed_total"] > 0
    assert train["dense_attention_calls"] == 0
    # every mechanism's gradient was compared with the reference's
    with open(os.path.join(HERE, "traffic",
                           "fit_fused_k2_tokens_8k_mla.json")) as f:
        checked = json.load(f)["checked_parameters"]
    assert {"l0_wq", "l0_w_kva", "l0_w_kvb", "l0_w_up", "l1_w_r", "l1_e_up",
            "l1_s_gate", "head"} <= set(checked)
    errs = {k: v for ln in lines if ln["line"] == "against_reference"
            for k, v in ln.items()}
    assert set(checked) | {"logits", "first_step_loss_rel_err",
                           "update_rel_err", "update_rel_err_worst"} <= \
        set(errs)
    assert 0 < errs["update_rel_err"] < 1


def test_per_layer_line(bench):
    out, lines = rehearse(CELL, 1)
    assert set(out) - {"breakdown"} == CONTRACT_KEYS and out["correct"]
    want = named(bench, "per_layer", CELL)
    assert NEW_READERS <= set(want)
    assert not {n for n in want if n.startswith(("kda_", "linear_attn"))
                or n in ("mfu_lm.train", "latent_attn_roofline_share.train",
                         "expert_matmul_roofline_share.train")}
    assert set(out["metrics"]) <= set(want)
    # a CPU gives no device trace: the scope and roofline readers return
    # nothing; every clock, span and counter metric of the cell is there
    host = {n for n, m in want.items()
            if m["source"] in ("host_clock", "program_span")}
    assert "mfu_deepseek_v3.train" in host
    assert host | {"expert_load_max_over_mean.train",
                   "expert_dense_fallback_share.train",
                   "compiles_in_window.train"} <= set(out["metrics"])
    assert out["metrics"]["compiles_in_window.train"]["value"] == 0
    assert out["metrics"]["expert_load_max_over_mean.train"]["value"] >= 1


# -- controls: what each limit has to refuse, planted into the rehearsal ------
# A plant is Python run before benchmarks/run.py in the same process: it
# swaps a function of the SYSTEM for a faulty one (the reference imports
# nothing from it, so a system that lacks what the reference has and a
# reference that lacks what the system has read the same), or nothing where
# the control is a key of the traffic file.

PLANTS = {
    "float8_reference": "",
    # the system without the rotation against the reference with it
    "no_rotation": """
from mxnet_tpu.ops import lm
lm.rope = lambda x, rotary_dim, offset, theta, interleave: x
""",
    # half-split pairs (i, i + 32) where interleaved (2i, 2i + 1) are asked
    "half_split_pairs": """
from mxnet_tpu.ops import lm
asked = lm.rope
lm.rope = lambda x, rotary_dim, offset, theta, interleave: asked(
    x, rotary_dim, offset, theta, not interleave)
""",
    # attention's operands at a lower precision than check (b) states:
    # float32 rounded to bfloat16 (the timed path's are already)
    "bfloat16_attention": """
from mxnet_tpu.ops import attention
import jax.numpy as jnp
exact = attention.flash_attention
def rounded(q, k, v, **kw):
    if q.dtype == jnp.float32:
        q, k, v = (a.astype(jnp.bfloat16).astype(jnp.float32)
                   for a in (q, k, v))
    return exact(q, k, v, **kw)
attention.flash_attention = rounded
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
    ("no_rotation", [], "float32 l0_wq", None),
    ("half_split_pairs", [], "float32 l0_wq", None),
    ("bfloat16_attention", [], "float32 l", "change of the parameters"),
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


def test_operation_counts_against_issue_30(config):
    assert flops.total_params(config) == 575_955_968
    assert flops.mla_mixer_params(config) == 26_345_984
    assert flops.dense_mlp_params(config) == 37_748_736
    assert flops.expert_layer_mlp_params(config) == \
        262_272 + 9_437_184 + 16 * 4_718_592
    # 16 B a parameter: 9.22 GB, 58% of the chip
    assert 9.2e9 < 16 * flops.total_params(config) < 9.23e9
    macs = flops.macs_by_mechanism(config)
    close = lambda got, want: abs(got - want) <= 0.0005 * want
    assert close(macs["mla"], 5 * 68.29e6)
    assert close(flops.mla_core_macs(config), 41.94e6)
    assert close(macs["moe"], 4 * 13.24e6)
    assert close(flops.routed_expert_macs(config), 0.75 * 4.72e6)
    assert close(macs["mlp"], 37.75e6) and close(macs["head"], 32.83e6)
    assert close(sum(macs.values()), 464.98e6)
    assert close(flops.train_flops_per_sequence(config), 22.85e12)


def test_configuration_keeps_every_published_width(config):
    """No width differs from the source: what `reduced` names is all that
    the file changes of config.json, and the file says what the uncut
    counts were and what deployment the cut stands for."""
    published = {
        "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "kv_lora_rank": 512, "max_position_embeddings": 32768,
        "model_type": "deepseek_v3", "moe_intermediate_size": 768,
        "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128,
        "n_shared_experts": 2, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 6,
        "num_hidden_layers": 48, "num_key_value_heads": 32,
        "q_lora_rank": None, "qk_head_dim": 192, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
        "rope_interleave": True, "rope_scaling": None, "rope_theta": 1000000,
        "routed_scaling_factor": 2.448, "scoring_func": "sigmoid",
        "tie_word_embeddings": False, "topk_group": 1,
        "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 128256}
    differs = {k for k, v in published.items() if config[k] != v}
    assert differs == set(config["reduced"]) == \
        {"num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert config["published"] == {k: published[k] for k in differs}
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (5, 16, 16032)
    assert "8 chips share each layer" in config["deployment"]
    assert "an eighth" in config["assumed"]["experts_load"]
