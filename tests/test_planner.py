"""Unified N-D parallelism planner (mxnet_tpu.parallel.planner,
ISSUE 19): MXNET_PLAN grammar, knob auto-tune ("auto unless set"),
deterministic auto-selection, HBM-prefilter pruning BEFORE any
compilation (via the MXNET_DEVSTATS_HBM_BYTES env path), fp32 bitwise
parity of planner-built degenerate trainers against the directly
constructed legacy trainers, and cross-plan checkpoint resume."""
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError
from mxnet_tpu.parallel import DataParallelTrainer, ZeroTrainer
from mxnet_tpu.parallel import planner
from mxnet_tpu.parallel.planner import (AUTO_KNOB_VARS, ModelSpec, Plan,
                                        make_trainer, parse_plan,
                                        plan_auto, _small_model)

N_DEV = 8


@pytest.fixture(autouse=True)
def _restore_knob_env():
    """make_trainer writes the chosen plan's knobs into os.environ
    ("auto unless set"). Left there, MXNET_ZERO_STAGE turns the next
    test file's plain DataParallelTrainer into a ZeroTrainer."""
    saved = {k: os.environ.get(k) for k in AUTO_KNOB_VARS}
    yield
    for k, v in saved.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v


def _data(batch, dim, nclass, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.normal(size=(batch, dim)).astype(np.float32)
    y = rng.randint(0, nclass, size=(batch,)).astype(np.float32)
    return x, y


def _run(tr, model, steps, seed=0):
    batch, dim = model.shape_kwargs["data"]
    nclass = model.shape_kwargs.get("nclass", 8)
    params, states, aux = tr.init_state(dict(model.shape_kwargs))
    x, y = _data(batch, dim, 8, seed)
    inputs = tr.shard_inputs([x, y])
    losses = []
    for _ in range(steps):
        params, states, aux, loss, _ = tr.step(params, states, aux,
                                               inputs)
        losses.append(float(np.asarray(loss)))
    return params, states, aux, losses


def _host(tr, params):
    if hasattr(tr, "host_params"):
        return tr.host_params(params)
    return {n: np.asarray(p) for n, p in zip(tr.param_names, params)}


# -- grammar / knobs (no compilation) ---------------------------------------

def test_parse_plan_grammar():
    """MXNET_PLAN grammar: every documented spec form parses to the
    mesh/stage/layout it names; junk raises MXNetError."""
    model, batch, dim, nclass = _small_model()
    p = parse_plan("dp", N_DEV, model)
    assert p.axes == {"data": N_DEV} and p.zero_stage == 0 \
        and p.param_specs is None
    p = parse_plan("zero2", N_DEV, model)
    assert p.axes == {"data": N_DEV} and p.zero_stage == 2
    p = parse_plan("dp2.tp4", N_DEV, model)
    assert p.axes == {"data": 2, "model": 4} and p.zero_stage == 0 \
        and p.param_specs            # GSPMD layout present
    p = parse_plan("dp2.tp4+zero2", N_DEV, model)
    assert p.axes == {"data": 2, "model": 4} and p.zero_stage == 2 \
        and p.param_specs is None    # joint-axis zero, not GSPMD
    p = parse_plan("tp4", N_DEV, model)
    assert p.axes == {"data": 2, "model": 4} or \
        p.axes == {"data": 1, "model": 4}
    for bad in ("dp3.tp5", "dp2.tp9", "pp2", "zero3", "banana"):
        with pytest.raises(MXNetError):
            parse_plan(bad, N_DEV, model)


def test_knobs_auto_unless_set(monkeypatch):
    """Plan.apply_env writes each of the six knobs ONLY when the env
    leaves it unset: an explicit user setting always wins."""
    for k in AUTO_KNOB_VARS:
        monkeypatch.delenv(k, raising=False)
    monkeypatch.setenv("MXNET_ZERO_BUCKET_MB", "7")   # user-pinned
    model, _, _, _ = _small_model()
    plan = parse_plan("zero2", N_DEV, model)
    planner._finalize_knobs(plan, model)
    plan.apply_env()
    import os
    assert os.environ["MXNET_ZERO_STAGE"] == "2"
    assert os.environ["MXNET_ZERO_BUCKET_MB"] == "7"   # untouched
    assert os.environ["MXNET_DEVICE_FEED_DEPTH"] == "2"
    for k in AUTO_KNOB_VARS:
        assert os.environ.get(k) not in (None, ""), k


# -- pruning: the env-var budget path, zero compiles ------------------------

def test_pruning_env_budget_rejects_all_without_compiling(monkeypatch):
    """A 16 KB MXNET_DEVSTATS_HBM_BYTES budget (resolved through
    devstats.hbm_budget(), i.e. the env path — the selftest covers the
    explicit-budget arg) is below every candidate's analytic lower
    bound, so plan_auto must reject everything in the prefilter and
    build ZERO executables."""
    monkeypatch.setenv("MXNET_DEVSTATS_HBM_BYTES", str(1 << 14))
    model, _, _, _ = _small_model()
    with pytest.raises(MXNetError) as ei:
        plan_auto(model, n_dev=N_DEV)
    report = getattr(ei.value, "report", None)
    assert report is not None
    assert report.compiled == 0
    assert report.budget == 1 << 14
    statuses = {e.get("status") for e in report.entries}
    assert statuses <= {"rejected_hbm", "unsupported"}
    assert "rejected_hbm" in statuses


# -- deterministic auto-selection -------------------------------------------

def test_plan_auto_deterministic():
    """Two planner runs over the same model agree on the choice AND on
    the full (name, cost) candidate table — argmin over (cost_s, name)
    with AOT costs is reproducible, so MXNET_PLAN=auto never flaps."""
    model, _, _, _ = _small_model()
    r1 = plan_auto(model, n_dev=N_DEV, max_tp=2)
    r2 = plan_auto(model, n_dev=N_DEV, max_tp=2)
    assert r1.chosen.name == r2.chosen.name
    t1 = [(e["plan"].name, round(e["cost_s"], 15)) for e in r1.entries
          if "cost_s" in e]
    t2 = [(e["plan"].name, round(e["cost_s"], 15)) for e in r2.entries
          if "cost_s" in e]
    assert t1 == t2 and len(t1) >= 3


@pytest.mark.parametrize("tflops,gbps", [(None, None), ("197", "819")])
def test_score_prices_compute_only_with_named_peaks(monkeypatch, tflops,
                                                    gbps):
    """The CPU has no peaks row: a candidate is priced by its wire alone
    and says so (compute_s None). Naming a chip's peaks through the
    devstats overrides adds the roofline term; no chip is assumed."""
    for var, val in (("MXNET_DEVSTATS_PEAK_TFLOPS", tflops),
                     ("MXNET_DEVSTATS_PEAK_GBPS", gbps)):
        if val is None:
            monkeypatch.delenv(var, raising=False)
        else:
            monkeypatch.setenv(var, val)
    model, _, _, _ = _small_model()
    rec = planner.score_plan(model, parse_plan("dp", N_DEV, model),
                             wire_bw=25e9)
    wire_s = rec["wire_bytes_hlo"] / 25e9
    assert wire_s > 0
    if tflops is None:
        assert rec["compute_s"] is None
        assert rec["cost_s"] == pytest.approx(wire_s, rel=1e-6)
    else:
        assert rec["compute_s"] == pytest.approx(
            max(rec["flops"] / 197e12, rec["bytes"] / 819e9))
        assert rec["cost_s"] == pytest.approx(rec["compute_s"] + wire_s,
                                              rel=1e-6)


# -- degenerate parity: planner-built vs direct legacy trainers -------------

def _sym_and_kw():
    from mxnet_tpu.parallel.zero import _wide_sym
    batch, dim, nclass = 16, 32, 8
    sym = _wide_sym(dim=dim, hidden=64, nclass=nclass)
    shapes = {"data": (batch, dim), "softmax_label": (batch,)}
    kw = {"optimizer": "sgd", "learning_rate": 0.1, "momentum": 0.9,
          "rescale_grad": 1.0 / batch}
    return sym, shapes, kw, batch, dim, nclass


def test_planner_dp_bitwise_vs_direct():
    """plan='dp' constructs the EXACT legacy DataParallelTrainer: fp32
    params after 10 steps are bitwise identical to a directly
    constructed one."""
    import jax
    from mxnet_tpu.parallel import data_parallel_mesh
    sym, shapes, kw, batch, dim, nclass = _sym_and_kw()
    tr_p = make_trainer(sym, shapes, plan="dp", n_dev=N_DEV,
                        apply_knobs=False, **kw)
    assert type(tr_p) is DataParallelTrainer
    mesh = data_parallel_mesh(N_DEV, jax.devices()[:N_DEV])
    tr_d = DataParallelTrainer(sym, mesh, **kw)
    model = ModelSpec(sym, shapes, **kw)
    pp, *_ = _run(tr_p, model, 10)
    pd, *_ = _run(tr_d, model, 10)
    hp, hd = _host(tr_p, pp), _host(tr_d, pd)
    for n in hp:
        assert np.array_equal(hp[n], hd[n]), n


def test_planner_zero2_bitwise_vs_direct():
    """plan='zero2' is a stage-2 ZeroTrainer; with the bucket size
    matched to the planner's auto-tuned value the two runs are the same
    program — bitwise identical params."""
    import jax
    from mxnet_tpu.parallel import data_parallel_mesh
    sym, shapes, kw, batch, dim, nclass = _sym_and_kw()
    tr_p = make_trainer(sym, shapes, plan="zero2", n_dev=N_DEV,
                        apply_knobs=False, **kw)
    assert isinstance(tr_p, ZeroTrainer) and tr_p._zero_stage == 2
    model = ModelSpec(sym, shapes, **kw)
    mesh = data_parallel_mesh(N_DEV, jax.devices()[:N_DEV])
    tr_d = ZeroTrainer(sym, mesh, zero_stage=2,
                       zero_bucket_mb=planner._auto_bucket_mb(model),
                       **kw)
    pp, *_ = _run(tr_p, model, 10)
    pd, *_ = _run(tr_d, model, 10)
    hp, hd = _host(tr_p, pp), _host(tr_d, pd)
    for n in hp:
        assert np.array_equal(hp[n], hd[n]), n


# -- cross-plan checkpoint resume -------------------------------------------

def test_cross_plan_resume_dp_to_zero1_bitwise():
    """Train under plan='dp', export, import the snapshot into a
    plan='zero1' trainer and keep training: because ZeRO-1 is bitwise
    with dp in fp32 (docs/ZERO.md), the resumed cross-plan run must
    match the uninterrupted dp run bitwise — a checkpoint is
    plan-portable, not a lock-in."""
    sym, shapes, kw, batch, dim, nclass = _sym_and_kw()
    model = ModelSpec(sym, shapes, **kw)

    tr_a = make_trainer(sym, shapes, plan="dp", n_dev=N_DEV,
                        apply_knobs=False, **kw)
    pa, sa, xa, _ = _run(tr_a, model, 4)
    arrays, meta = tr_a.export_training_state(pa, sa, xa)

    # uninterrupted reference: 4 more dp steps on the same data
    x, y = _data(batch, dim, nclass)
    inp_a = tr_a.shard_inputs([x, y])
    ref_l = []
    for _ in range(4):
        pa, sa, xa, loss, _ = tr_a.step(pa, sa, xa, inp_a)
        ref_l.append(float(np.asarray(loss)))

    tr_b = make_trainer(sym, shapes, plan="zero1", n_dev=N_DEV,
                        apply_knobs=False, **kw)
    assert isinstance(tr_b, ZeroTrainer) and tr_b._zero_stage == 1
    pb, sb, xb = tr_b.import_training_state(arrays, meta)
    inp_b = tr_b.shard_inputs([x, y])
    res_l = []
    for _ in range(4):
        pb, sb, xb, loss, _ = tr_b.step(pb, sb, xb, inp_b)
        res_l.append(float(np.asarray(loss)))

    assert res_l == ref_l
    ha, hb = _host(tr_a, pa), _host(tr_b, pb)
    assert ha.keys() == hb.keys()
    for n in ha:
        assert np.array_equal(ha[n], hb[n]), n
