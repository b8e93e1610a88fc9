"""`kimi_linear.train`, checked without a chip: the cell's rehearsal with
and without a trace (toy widths of the configuration's `rehearse` block,
the same runner, reference and checks as on the chip), and the operation
counts against the parameter counts of ISSUE 26."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, os.path.dirname(os.path.abspath(__file__))]

import flops_kimi_linear as flops                       # noqa: E402
from test_harness import (CONTRACT_KEYS, DEVICE_KEYS, named,  # noqa: E402
                          rehearse)

CELL = "kimi_linear.train"


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(HERE, "configs", "kimi_linear_48b_a3b.json")) as f:
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
    with open(os.path.join(HERE, "traffic", "fit_fused_k2_tokens_8k.json")) \
            as f:
        checked = json.load(f)["checked_parameters"]
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
    assert set(out["metrics"]) <= set(want)
    # a CPU gives no device trace: the scope and roofline readers return
    # nothing; every clock, span and counter metric of the cell is there
    host = {n for n, m in want.items()
            if m["source"] in ("host_clock", "program_span")}
    assert host | {"expert_load_max_over_mean.train",
                   "compiles_in_window.train"} <= set(out["metrics"])
    assert out["metrics"]["compiles_in_window.train"]["value"] == 0
    assert out["metrics"]["expert_load_max_over_mean.train"]["value"] >= 1


# -- controls: what each limit has to refuse, planted into the rehearsal ------
# A plant is Python run before benchmarks/run.py in the same process: it
# swaps a function of the SYSTEM for a faulty one (the reference imports
# nothing from it), or nothing where the control is a key of the traffic file.

PLANTS = {
    # the backward sees half the batch: the second half of the step's rows
    # gives its loss and no gradient (the forward, check (a), is untouched)
    "half_batch_gradient": """
from mxnet_tpu.ops import lm
import jax, jax.numpy as jnp
whole = lm.lm_head_ce
def half(x, weight, label, block=2048):
    out = whole(x, weight, label, block)
    first = jnp.arange(out.shape[0]) < out.shape[0] // 2
    return jnp.where(first, out, jax.lax.stop_gradient(out))
lm.lm_head_ce = half
""",
    # the delta rule's products at a lower precision than check (b) states:
    # float32 operands rounded to bfloat16 (the timed path's are already)
    "bfloat16_kda_core": """
from mxnet_tpu.ops import lm
import jax.numpy as jnp
exact = lm.kda_chunked
def rounded(q, k, v, g, beta, **kw):
    if q.dtype == jnp.float32:
        q, k, v = (a.astype(jnp.bfloat16).astype(jnp.float32)
                   for a in (q, k, v))
    return exact(q, k, v, g, beta, **kw)
lm.kda_chunked = rounded
""",
    "float8_reference": "",
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
    ("half_batch_gradient", [],
     "change of the parameters over the first dispatch",
     "first step's per-token losses"),
    ("bfloat16_kda_core", [], "float32 l1_", "change of the parameters"),
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


def test_operation_counts_against_issue_26(config):
    close = lambda got, want: abs(got - want) <= 0.005 * want + 5e4
    assert close(flops.kda_mixer_params(config), 39.5e6)
    assert close(flops.mla_mixer_params(config), 29.1e6)
    assert close(flops.expert_params(config), 7.08e6)
    assert close(flops.router_params(config), 0.59e6)
    assert close(flops.dense_mlp_params(config), 63.7e6)
    assert close(flops.total_params(config), 603e6)
    # 16 B a parameter: 9.65 GB, 60% of the chip
    assert 9.6e9 < 16 * flops.total_params(config) < 9.7e9
    macs = flops.macs_by_mechanism(config)
    assert close(macs["mla"], 71e6) and close(macs["head"], 47.2e6)
    assert 100e6 < macs["mlp"] < 102e6 and 160e6 < macs["kda"] < 170e6
    assert 18e12 < flops.train_flops_per_sequence(config) < 20e12


def _message(*fields):
    """A protocol-buffer message of (number, int | bytes) fields."""
    def varint(v):
        out = b""
        while True:
            out += bytes([(v & 0x7F) | (0x80 if v > 0x7F else 0)])
            v >>= 7
            if not v:
                return out
    out = b""
    for number, value in fields:
        if isinstance(value, int):
            out += varint(number << 3) + varint(value)
        else:
            out += varint(number << 3 | 2) + varint(len(value)) + value
    return out


def test_scope_reader_finds_the_metadata_of_an_xspace():
    from reduce import op_scopes
    op = b"%fusion.7 = bf16[8]{0} fusion(%p), kind=kLoop"
    path = b"jit(multi)/while/body/transpose(jvp(mx.kda))/mx.kda.core/mul"
    meta = _message((1, 7), (2, op), (5, _message((1, 26), (5, path))),
                    (5, _message((1, 3), (3, 12345))))
    # a second operation names its path by reference to a stat_metadata id
    op2 = b"%fusion.8 = bf16[8]{0} fusion(%p), kind=kLoop"
    meta2 = _message((1, 8), (2, op2), (5, _message((1, 26), (7, 40))))
    plane = _message((1, 2), (2, b"/device:TPU:0"), (3, b""),
                     (4, _message((1, 7), (2, meta))),
                     (4, _message((1, 8), (2, meta2))),
                     (5, _message((1, 40), (2, _message(
                         (1, 40), (2, b"jit(multi)/mx.optimizer/add"))))))
    host = _message((2, b"/host:CPU"), (4, _message((1, 1), (2, _message(
        (2, b"other"), (5, _message((5, b"mx.optimizer"))))))))
    texts = op_scopes.metadata_texts(_message((1, host), (1, plane)))
    assert texts == {op.decode(): [path.decode()],
                     op2.decode(): ["jit(multi)/mx.optimizer/add"]}
    assert op_scopes.scope_of(texts[op.decode()]) == ("mx.kda.core", True)


def test_scope_reader_on_synthetic_operations():
    from reduce import op_scopes
    name = "jit(multi)/while/body/transpose(jvp(mx.kda))/mx.kda.core/dot"
    assert op_scopes.scope_of(["", name]) == ("mx.kda.core", True)
    assert op_scopes.scope_of(["%fusion.3 = f32[8]{0} fusion(...)"]) == \
        (op_scopes.UNSCOPED, False)
    # the TPU compiler's own kernels for jax.lax.ragged_dot carry no scope
    assert op_scopes.scope_of(
        ["ragged-dot-none", "%ragged-dot-none.7 = f32[16384,1024]{1,0} "
         "custom-call(...)"]) == ("mx.moe.experts.matmul", False)
    ops = [["mx.kda", 0.0, 1.0, "f"],            # a while around the next
           ["mx.kda.core", 0.2, 0.5, "b"],
           ["mx.optimizer", 1.0, 0.5, "f"],
           [op_scopes.UNSCOPED, 1.5, 0.5, "f"]]
    scopes = op_scopes.Scopes(ops, (0.0, 2.0))
    assert scopes.unscoped_top == [("", 0.5)]
    assert scopes.busy_s == pytest.approx(2.0)
    assert scopes.seconds("mx.kda") == pytest.approx(1.0)
    assert scopes.seconds("mx.kda.core") == pytest.approx(0.5)
    assert scopes.share_of_busy("mx.optimizer") == pytest.approx(0.25)
    assert not op_scopes.Scopes([[op_scopes.UNSCOPED, 0, 1, "f"]], (0, 1))
