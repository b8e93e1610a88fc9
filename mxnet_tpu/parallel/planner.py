"""Cost-model-driven sharding planner over dp/zero/tp (mx.parallel.planner).

The reference exposes ONE parallelism (executor-group data parallelism)
and leaves composition to the user; this module is the TPU-native
unification the ROADMAP's N-D story builds toward: a single ``Plan``
names a mesh shape over the shared axis vocabulary (mesh.AXIS_NAMES), a
per-parameter layout, and the runtime knob settings — and a planner
picks one by MEASURED compiled cost instead of folklore:

  candidates   dp, ZeRO-1, ZeRO-2 (1-D data mesh), dpK.tpT (GSPMD
               param shardings on a data×model mesh), dpK.tpT+zero2
               (masters/opt-state sharded 1/(D·T) jointly over BOTH
               axes — the new composition this PR adds). pp appears in
               the explain listing but is never auto-selected: a
               generic Symbol carries no stage partition map
               (docs/PLANNER.md "candidate space").
  prefilter    an analytic per-device HBM lower bound per candidate is
               checked against telemetry.devstats.hbm_budget() BEFORE
               any compilation (devstats.preflight); a plan whose
               lower bound alone overflows is rejected without ever
               building an executable.
  scoring      each survivor's training step is AOT-lowered and
               compiled (never executed); XLA's own cost/memory
               analysis (devstats.extract: per-device flops, bytes,
               peak) lands on the devstats roofline peak table, and
               collective wire bytes are read out of the compiled
               module's HLO (hloaudit.collectives_in_text under ring
               accounting):

                 cost_s = max(flops/peak_flops, bytes/peak_bw)
                        + wire_bytes/wire_bw          (docs/PLANNER.md)

               wire_bw is MXNET_PLAN_WIRE_GBPS (default 25 GB/s — a
               conservative ICI figure; override per fabric). A device
               without a devstats peaks row (the CPU) ranks by the wire
               term alone. A compiled peak over the HBM budget rejects
               the plan too.
  selection    deterministic argmin over (cost_s, name); ties break
               lexicographically so two runs always agree.

``MXNET_PLAN=auto|dp|zero1|zero2|dpK.tpT[+zero2]|tpT[+zero2]`` selects
the plan (auto = run the planner); the chosen plan auto-tunes the six
runtime knobs — MXNET_ZERO_STAGE, MXNET_ZERO_BUCKET_MB,
MXNET_GRAD_COMPRESS, MXNET_DEVICE_FEED, MXNET_DEVICE_FEED_DEPTH,
MXNET_FUSED_K — each only when the user has not set it ("auto unless
set", docs/env_vars.md).

Degenerate plans (pure dp, pure zero) construct the EXACT legacy
trainers, so fp32 training under the planner is bit-identical to the
single-mode paths (tests/test_planner.py asserts this).

CLI: ``--selftest`` (determinism, pruning-before-compile, degenerate
parity, ZeRO-over-dp×tp trajectory — tools/ci.sh quick), ``--explain``
(the per-candidate score table), ``--bench`` (auto vs hand-picked plans
on 8 virtual CPU devices),
``--hlo-audit`` (hloaudit's fit_step_plan subprocess body).
"""
from __future__ import annotations

import os

import numpy as _np

from ..base import MXNetError
from .mesh import build_mesh

__all__ = ["Plan", "PlanReport", "ModelSpec", "parse_plan",
           "resolve_plan", "enumerate_candidates", "tp_param_specs",
           "plan_auto", "make_trainer", "resolve_wire_bw",
           "AUTO_KNOB_VARS"]

# the six runtime knobs a chosen plan auto-tunes ("auto unless set"):
# Plan.apply_env writes each ONLY when the process env leaves it unset,
# so an explicit user setting always wins (docs/PLANNER.md knob table)
AUTO_KNOB_VARS = ("MXNET_ZERO_STAGE", "MXNET_ZERO_BUCKET_MB",
                  "MXNET_GRAD_COMPRESS", "MXNET_DEVICE_FEED",
                  "MXNET_DEVICE_FEED_DEPTH", "MXNET_FUSED_K")


def resolve_plan(value=None):
    """Plan spec string: explicit arg wins, else MXNET_PLAN, else auto."""
    if value is None:
        from .. import config
        value = config.get("MXNET_PLAN", "auto")
    spec = str(value or "auto").strip().lower()
    return spec or "auto"


def resolve_wire_bw(value=None):
    """Cross-device wire bandwidth in bytes/s for the cost model
    (MXNET_PLAN_WIRE_GBPS, default 25 GB/s)."""
    if value is None:
        from .. import config
        value = config.get("MXNET_PLAN_WIRE_GBPS", "25")
    try:
        bw = float(value) * 1e9
    except (TypeError, ValueError):
        raise MXNetError(
            f"MXNET_PLAN_WIRE_GBPS must be a number, got {value!r}")
    if bw <= 0:
        raise MXNetError(
            f"MXNET_PLAN_WIRE_GBPS must be > 0, got {value!r}")
    return bw


class Plan:
    """One point in the planner's composition space: a named mesh shape
    plus the sharding mode and knob settings that make a trainer.

    ``axes`` is an ordered {axis_name: size} over mesh.AXIS_NAMES
    ("data" first, "model" when tensor parallelism is on);
    ``zero_stage`` > 0 shards masters/optimizer state jointly over ALL
    mesh axes (parallel/zero.py); ``param_specs`` (name ->
    PartitionSpec) is the GSPMD tensor-parallel layout for stage-0
    plans. The knob fields feed apply_env().
    """

    def __init__(self, name, axes, zero_stage=0, param_specs=None,
                 compress="none", bucket_mb=None, fused_k=None,
                 feed_depth=2):
        self.name = str(name)
        self.axes = dict(axes)
        self.zero_stage = int(zero_stage)
        self.param_specs = dict(param_specs) if param_specs else None
        self.compress = compress
        self.bucket_mb = bucket_mb
        self.fused_k = fused_k
        self.feed_depth = int(feed_depth)
        if "data" not in self.axes:
            raise MXNetError(f"plan {name!r}: no data axis in {axes}")
        if self.zero_stage and self.param_specs:
            raise MXNetError(
                f"plan {name!r}: ZeRO plans shard masters jointly over "
                "the mesh and keep compute model-replicated; GSPMD "
                "param_specs only apply to stage-0 plans "
                "(docs/PLANNER.md)")

    @property
    def n_devices(self):
        n = 1
        for s in self.axes.values():
            n *= int(s)
        return n

    @property
    def model_factor(self):
        return self.n_devices // int(self.axes["data"])

    def mesh(self, devices=None):
        return build_mesh(self.axes, devices=devices)

    def knobs(self):
        """The auto-tuned knob values (docs/PLANNER.md knob table)."""
        return {
            "MXNET_ZERO_STAGE": str(self.zero_stage),
            "MXNET_ZERO_BUCKET_MB": str(self.bucket_mb
                                        if self.bucket_mb else 4),
            "MXNET_GRAD_COMPRESS": str(self.compress),
            "MXNET_DEVICE_FEED": "1",
            "MXNET_DEVICE_FEED_DEPTH": str(self.feed_depth),
            "MXNET_FUSED_K": str(self.fused_k if self.fused_k else 8),
        }

    def apply_env(self):
        """Write the knob values into os.environ — each only when the
        user has NOT set it ("auto unless set"). Returns the dict of
        vars actually written."""
        applied = {}
        for k, v in self.knobs().items():
            if os.environ.get(k) in (None, ""):
                os.environ[k] = v
                applied[k] = v
        return applied

    def to_dict(self):
        return {"name": self.name, "axes": dict(self.axes),
                "zero_stage": self.zero_stage,
                "tp_params": sorted(self.param_specs)
                if self.param_specs else [],
                "knobs": self.knobs()}

    def __repr__(self):
        return f"Plan({self.name!r}, axes={self.axes}, " \
               f"zero_stage={self.zero_stage})"


def _divisors(n):
    return [d for d in range(1, n + 1) if n % d == 0]


def parse_plan(spec, n_dev, model=None):
    """Parse a non-auto MXNET_PLAN spec into a Plan.

    Grammar: ``dp`` | ``zero1`` | ``zero2`` | ``dpK.tpT`` | ``tpT``,
    optionally ``+zero1``/``+zero2`` after a tp form. K·T must equal
    the device count (K inferred when the dp factor is omitted).
    ``model`` (a ModelSpec) supplies the tp layout; required for tp
    plans.
    """
    spec = str(spec).strip().lower()
    if not spec or spec == "auto":
        raise MXNetError("parse_plan: 'auto' is resolved by plan_auto")
    stage = 0
    base = spec
    if "+" in spec:
        base, suffix = spec.split("+", 1)
        if suffix not in ("zero1", "zero2"):
            raise MXNetError(f"MXNET_PLAN: unknown suffix +{suffix} "
                             f"in {spec!r} (want +zero1|+zero2)")
        stage = int(suffix[-1])
    if base == "dp":
        if stage:
            return Plan(spec, {"data": n_dev}, zero_stage=stage)
        return Plan("dp", {"data": n_dev})
    if base in ("zero1", "zero2"):
        if stage:
            raise MXNetError(f"MXNET_PLAN: {spec!r} names zero twice")
        return Plan(base, {"data": n_dev}, zero_stage=int(base[-1]))
    # dpK.tpT / tpT
    dp_k, tp_t = None, None
    for tok in base.split("."):
        if tok.startswith("dp") and tok[2:].isdigit():
            dp_k = int(tok[2:])
        elif tok.startswith("tp") and tok[2:].isdigit():
            tp_t = int(tok[2:])
        else:
            raise MXNetError(
                f"MXNET_PLAN: cannot parse {tok!r} in {spec!r} (want "
                "auto|dp|zero1|zero2|dpK.tpT[+zero1|+zero2]|tpT[...])")
    if tp_t is None:
        raise MXNetError(f"MXNET_PLAN: no tp factor in {spec!r}")
    if dp_k is None:
        if n_dev % tp_t:
            raise MXNetError(
                f"MXNET_PLAN: tp{tp_t} does not divide {n_dev} devices")
        dp_k = n_dev // tp_t
    if dp_k * tp_t != n_dev:
        raise MXNetError(
            f"MXNET_PLAN: {spec!r} spans {dp_k * tp_t} devices but the "
            f"mesh has {n_dev}")
    name = f"dp{dp_k}.tp{tp_t}" + (f"+zero{stage}" if stage else "")
    axes = {"data": dp_k, "model": tp_t}
    if stage:
        return Plan(name, axes, zero_stage=stage)
    if model is None:
        raise MXNetError(
            f"MXNET_PLAN: {spec!r} needs a model spec for the tp "
            "layout (construct through planner.make_trainer)")
    specs, sharded, total = tp_param_specs(model.param_names,
                                           model.param_shapes, tp_t)
    if not specs:
        raise MXNetError(
            f"MXNET_PLAN: {spec!r} — no parameter dimension divides by "
            f"tp={tp_t}; pick a divisor of the layer widths")
    return Plan(name, axes, param_specs=specs)


def tp_param_specs(param_names, param_shapes, t):
    """Megatron-style layout heuristic over a generic Symbol's params.

    2-D weights alternate column-parallel / row-parallel in declaration
    order — mxnet FullyConnected stores weight as (num_hidden, in_dim)
    and computes x @ W.T, so column-parallel (shard the OUTPUT features)
    is P("model", None) and row-parallel (shard the input features) is
    P(None, "model"); a column-parallel layer's 1-D bias shards with its
    output features. Dims that t does not divide stay replicated (GSPMD
    keeps any mix correct; the alternation only minimizes resharding).
    Returns (specs dict, sharded_bytes, total_bytes).
    """
    from jax.sharding import PartitionSpec as P
    specs, col_next = {}, True
    sharded = total = 0
    bias_of = {}        # "<prefix>_bias" -> col-sharded?
    for n, s in zip(param_names, param_shapes):
        sz = 4 * max(1, int(_np.prod(s)) if s else 1)
        total += sz
        if len(s) == 2:
            if col_next and s[0] % t == 0:
                specs[n] = P("model", None)
                if n.endswith("_weight"):
                    bias_of[n[:-len("_weight")] + "_bias"] = True
                sharded += sz
                col_next = False
            elif not col_next and s[1] % t == 0:
                specs[n] = P(None, "model")
                sharded += sz
                col_next = True
        elif len(s) == 1 and bias_of.get(n) and s[0] % t == 0:
            specs[n] = P("model")
            sharded += sz
    return specs, sharded, total


class ModelSpec:
    """Everything the planner needs to size and build a trainer for one
    Symbol: inferred parameter shapes, optimizer state width, the batch
    geometry, and the trainer kwargs forwarded to construction."""

    def __init__(self, symbol, shape_kwargs, data_names=("data",),
                 label_names=("softmax_label",), optimizer="sgd",
                 dtype="float32", **trainer_kwargs):
        from .dp import _OPT_OPS
        from ..ops.registry import get_op
        self.symbol = symbol
        self.shape_kwargs = dict(shape_kwargs)
        self.data_names = tuple(data_names)
        self.label_names = tuple(label_names)
        self.optimizer = optimizer
        self.dtype = dtype
        self.trainer_kwargs = dict(trainer_kwargs)
        arg_names = symbol.list_arguments()
        input_names = set(self.data_names) | set(self.label_names)
        arg_shapes, _, aux_shapes = symbol.infer_shape(**shape_kwargs)
        shapes = dict(zip(arg_names, arg_shapes))
        self.param_names = [n for n in arg_names if n not in input_names]
        self.param_shapes = [tuple(shapes[n]) for n in self.param_names]
        self.aux_shapes = [tuple(s) for s in aux_shapes]
        self.input_shapes = [tuple(shapes[n]) for n in arg_names
                             if n in input_names]
        self.batch = int(shape_kwargs[self.data_names[0]][0])
        opt_op = _OPT_OPS.get(optimizer)
        if opt_op is None:
            raise MXNetError(f"planner: no fused op for {optimizer!r}")
        hp = dict(trainer_kwargs)
        opname = opt_op(hp) if callable(opt_op) else opt_op
        self.n_states = len(get_op(opname).input_names) - 2
        self.param_elems = sum(max(1, int(_np.prod(s)) if s else 1)
                               for s in self.param_shapes)
        self.param_bytes = 4 * self.param_elems

    def compute_itemsize(self):
        return 2 if self.dtype in ("bfloat16", "float16") else 4


# -- analytic estimates (prefilter + the audit's wire cross-check) -----------

def estimate_hbm_bytes(model, plan):
    """Analytic per-device HBM LOWER BOUND of one training step under
    `plan` — masters + optimizer state at their sharded residency, one
    compute-dtype param copy + one gradient (the live set at the
    backward/update boundary), and the local batch. Deliberately a
    lower bound (no activation model for a generic Symbol): a plan
    rejected on it alone can never fit, while survivors still face the
    compiled-peak check (docs/PLANNER.md "HBM prefilter")."""
    pb = model.param_bytes
    ci = model.compute_itemsize()
    n = plan.n_devices
    t = plan.model_factor
    if plan.zero_stage > 0:
        master_opt = pb * (1 + model.n_states) / n
    elif plan.param_specs:
        # tp: listed params shard 1/T, the rest replicate
        _, sharded, total = tp_param_specs(model.param_names,
                                           model.param_shapes, t)
        shard_b = sharded / t + (total - sharded)
        master_opt = shard_b * (1 + model.n_states)
    else:
        master_opt = pb * (1 + model.n_states)
    # one gathered/cast compute copy + one gradient, at compute width
    live = 2 * pb * ci / 4
    if plan.param_specs:
        live /= t
    batch_local = 0
    for s in model.input_shapes:
        elems = max(1, int(_np.prod(s)) if s else 1)
        batch_local += 4 * elems / int(plan.axes["data"])
    return int(master_opt + live + batch_local)


def estimate_wire_bytes(model, plan, bucket_bytes=None):
    """Analytic per-device collective wire bytes of one step — the
    number the fit_step_plan audit holds the compiled HLO to within
    10%. ZeRO plans reuse ZeroLayout's ring accounting (gather +
    reduce over the JOINT axis ring); stage-0 dp is one all-reduce of
    the full gradient. Stage-0 tp has no closed form for a generic
    Symbol (activation collectives depend on the layer graph) — None
    means "score from the compiled HLO only"."""
    ci = model.compute_itemsize()
    n = plan.n_devices
    if plan.zero_stage > 0:
        from .zero import ZeroLayout, _resolve_bucket_bytes
        bb = bucket_bytes if bucket_bytes is not None \
            else _resolve_bucket_bytes(plan.bucket_mb)
        lay = ZeroLayout(model.param_shapes, n, bb)
        return lay.wire_bytes_per_step(plan.zero_stage, ci, ci)
    if plan.param_specs:
        return None
    return int(2 * (n - 1) / n * model.param_bytes * ci / 4)


# -- candidate space ---------------------------------------------------------

def enumerate_candidates(model, n_dev, max_tp=8):
    """The planner's candidate compositions for one model at one device
    count: [(plan_or_None, reject_reason_or_None)]. Deterministic
    order. pp rides along as an explained rejection — a generic Symbol
    has no stage partition map, so the planner never selects it."""
    out = [(Plan("dp", {"data": n_dev}), None)]
    if n_dev > 1:
        out.append((Plan("zero1", {"data": n_dev}, zero_stage=1), None))
        out.append((Plan("zero2", {"data": n_dev}, zero_stage=2), None))
    for t in _divisors(n_dev):
        if t == 1 or t == n_dev or t > max_tp:
            continue
        k = n_dev // t
        specs, sharded, total = tp_param_specs(model.param_names,
                                               model.param_shapes, t)
        if not specs:
            out.append((None, (f"dp{k}.tp{t}: no parameter dimension "
                               f"divides by tp={t}")))
            continue
        out.append((Plan(f"dp{k}.tp{t}", {"data": k, "model": t},
                         param_specs=specs), None))
        out.append((Plan(f"dp{k}.tp{t}+zero2", {"data": k, "model": t},
                         zero_stage=2), None))
    if n_dev > 1:
        out.append((None, f"pp{n_dev}: generic Symbol has no stage "
                          "partition map (use parallel.pp directly)"))
    return out


# -- trainer construction ----------------------------------------------------

def _auto_bucket_mb(model):
    """Bucket threshold targeting ~4 gradient buckets, clamped to
    [1, 32] MB (docs/PLANNER.md knob table)."""
    mb = model.param_bytes / (1 << 20)
    return max(1, min(32, int(round(mb / 4)) or 1))


def _auto_fused_k(model):
    """Small-step models amortize dispatch deeper: K=16 under 8 MB of
    params, the dp default K=8 above."""
    return 16 if model.param_bytes < (8 << 20) else 8


def _finalize_knobs(plan, model):
    if plan.bucket_mb is None:
        plan.bucket_mb = _auto_bucket_mb(model)
    if plan.fused_k is None:
        plan.fused_k = _auto_fused_k(model)
    return plan


def build_trainer(model, plan, devices=None):
    """Construct the trainer a Plan describes. Degenerate plans call
    the EXACT legacy constructors (bitwise parity with the single-mode
    paths); tp plans hand dp the GSPMD param_specs; any zero_stage>0
    plan builds a ZeroTrainer over the plan's (possibly N-D) mesh."""
    from .dp import DataParallelTrainer
    from .zero import ZeroTrainer
    _finalize_knobs(plan, model)
    mesh = plan.mesh(devices)
    kw = dict(model.trainer_kwargs, optimizer=model.optimizer,
              dtype=model.dtype, data_names=model.data_names,
              label_names=model.label_names)
    if plan.zero_stage > 0:
        tr = ZeroTrainer(model.symbol, mesh, zero_stage=plan.zero_stage,
                         grad_compress=plan.compress,
                         zero_bucket_mb=plan.bucket_mb, **kw)
    else:
        tr = DataParallelTrainer(model.symbol, mesh, zero_stage=0,
                                 param_specs=plan.param_specs, **kw)
    tr._plan = plan
    return tr


# -- AOT scoring -------------------------------------------------------------

def _abstract_args(model, tr):
    """ShapeDtypeStructs for one single-step dispatch of `tr` — metadata
    only, so scoring never allocates training state."""
    import jax
    import jax.numpy as jnp
    from .. import random as _random
    f32 = jnp.float32
    sds = jax.ShapeDtypeStruct
    key = _random.next_key()
    rng = sds(key.shape, key.dtype)
    scalar = sds((), f32)
    inputs = tuple(sds(s, f32) for s in model.input_shapes)
    aux = tuple(sds(s, f32) for s in model.aux_shapes)
    from .zero import ZeroTrainer
    if isinstance(tr, ZeroTrainer):
        L = tr._ensure_layout(model.param_shapes)
        masters = tuple(sds((L.padded[b],), f32)
                        for b in range(L.n_buckets))
        states = tuple(tuple(sds((L.padded[b],), f32)
                             for _ in range(tr._n_states))
                       for b in range(L.n_buckets))
        resid = () if tr._wire_dtype is None else tuple(
            sds((tr._n_dev, L.padded[b]), f32)
            for b in range(L.n_buckets))
        tr._build_zero_step()
        return tr._zstep, (masters, states, resid, aux, inputs, rng,
                           scalar, scalar)
    params = tuple(sds(s, f32) for s in model.param_shapes)
    states = tuple(tuple(sds(s, f32) for _ in range(tr._n_states))
                   for s in model.param_shapes)
    return tr._step, (params, states, aux, inputs, rng, scalar, scalar)


def score_plan(model, plan, devices=None, wire_bw=None):
    """AOT-compile one candidate's step and price it: returns the
    record dict (never executes the step). The compiled peak is
    re-checked against the HBM budget here — the prefilter is a lower
    bound, this is XLA's own number."""
    from ..telemetry import devstats
    from ..analysis.hloaudit import (collectives_in_text,
                                     collective_wire_bytes)
    wire_bw = wire_bw or resolve_wire_bw()
    tr = build_trainer(model, plan, devices)
    fn, args = _abstract_args(model, tr)
    compiled = fn.lower(*args).compile()
    stats = devstats.extract(compiled)
    colls = collectives_in_text(compiled.as_text())
    wires = collective_wire_bytes(colls, plan.n_devices)
    wire = float(sum(wires.values()))
    pf, pb, _ = devstats.peaks()
    # a device without a peaks row (the CPU) cannot price compute: its
    # candidates rank by wire alone. To rank for a chip from a CPU mesh,
    # name that chip's peaks with MXNET_DEVSTATS_PEAK_TFLOPS / _GBPS
    compute = max(stats["flops"] / pf, stats["bytes_accessed"] / pb) \
        if pf else None
    cost = (compute or 0.0) + wire / wire_bw
    est = estimate_wire_bytes(model, plan,
                              bucket_bytes=getattr(tr, "_bucket_bytes",
                                                   None))
    return {"plan": plan, "trainer": tr, "compiled": compiled,
            "flops": stats["flops"], "bytes": stats["bytes_accessed"],
            "peak_bytes": stats["peak_bytes"],
            "wire_bytes_hlo": int(wire),
            "wire_bytes_estimate": est,
            "collectives": {k: len(v) for k, v in colls.items()},
            "compute_s": compute, "cost_s": cost}


class PlanReport:
    """The planner's full decision record: the chosen Plan plus one
    entry per candidate — scored (cost_s ...), rejected_hbm (the
    prefilter said it cannot fit; never compiled), rejected_peak (XLA's
    compiled peak overflowed), or unsupported (no layout). `compiled`
    counts executables actually built — the pruning test pins it."""

    def __init__(self, chosen, entries, compiled, budget):
        self.chosen = chosen
        self.entries = entries
        self.compiled = compiled
        self.budget = budget

    def to_dict(self):
        return {"chosen": self.chosen.name if self.chosen else None,
                "budget_bytes": self.budget,
                "compiled": self.compiled,
                "candidates": [
                    {k: v for k, v in e.items()
                     if k not in ("plan", "trainer", "compiled")}
                    | {"name": e["plan"].name if e.get("plan") else
                       e.get("name")}
                    for e in self.entries]}


def plan_auto(model, n_dev=None, devices=None, budget=None,
              wire_bw=None, max_tp=8):
    """Enumerate → prefilter → compile+score → argmin. Returns a
    PlanReport whose `chosen` plan minimizes (cost_s, name); raises
    MXNetError when every candidate is rejected."""
    import jax
    from ..telemetry import devstats
    if devices is None and n_dev is not None:
        devices = jax.devices()[:n_dev]
    if devices is not None:
        n_dev = len(devices)
    if n_dev is None:
        n_dev = len(jax.devices())
    if budget is None:
        budget = devstats.hbm_budget()
    entries, compiled_n = [], 0
    for plan, reason in enumerate_candidates(model, n_dev, max_tp):
        if plan is None:
            entries.append({"name": reason.split(":")[0],
                            "status": "unsupported", "reason": reason})
            continue
        _finalize_knobs(plan, model)
        need = estimate_hbm_bytes(model, plan)
        try:
            devstats.preflight(plan.name, need, budget=budget,
                               what="plan")
        except devstats.HBMPreflightError as e:
            entries.append({"plan": plan, "status": "rejected_hbm",
                            "need_bytes": need, "reason": str(e)})
            continue
        rec = score_plan(model, plan, devices, wire_bw)
        compiled_n += 1
        if budget is not None and rec["peak_bytes"] > budget:
            rec |= {"status": "rejected_peak",
                    "reason": f"compiled peak {rec['peak_bytes']} over "
                              f"budget {budget}"}
        else:
            rec["status"] = "scored"
        entries.append(rec)
    scored = [e for e in entries if e.get("status") == "scored"]
    if not scored:
        # carry the full record out on the error so callers (and the
        # pruning test) can see that nothing was compiled
        err = MXNetError(
            "planner: no feasible plan — every candidate was rejected "
            f"({[e.get('reason') for e in entries]})")
        err.report = PlanReport(None, entries, compiled_n, budget)
        raise err
    best = min(scored, key=lambda e: (e["cost_s"], e["plan"].name))
    best["status"] = "selected"
    return PlanReport(best["plan"], entries, compiled_n, budget)


def make_trainer(symbol, shape_kwargs, plan=None, devices=None,
                 n_dev=None, data_names=("data",),
                 label_names=("softmax_label",), optimizer="sgd",
                 dtype="float32", apply_knobs=True, budget=None,
                 **trainer_kwargs):
    """The MXNET_PLAN front door: build the trainer the (possibly
    auto-)selected plan describes. `plan` overrides the env spec;
    "auto" runs the planner. The chosen plan's knob values land in the
    environment ("auto unless set") unless apply_knobs=False. The
    trainer carries `_plan` (and `_plan_report` under auto)."""
    import jax
    model = ModelSpec(symbol, shape_kwargs, data_names=data_names,
                      label_names=label_names, optimizer=optimizer,
                      dtype=dtype, **trainer_kwargs)
    if devices is None and n_dev is not None:
        devices = jax.devices()[:n_dev]
    n = len(devices) if devices is not None else len(jax.devices())
    spec = resolve_plan(plan)
    report = None
    if spec == "auto":
        report = plan_auto(model, n_dev=n, devices=devices,
                           budget=budget)
        chosen = report.chosen
        # the scoring trainer is the real trainer — reuse it, its jit
        # cache already holds the compiled step
        tr = next(e["trainer"] for e in report.entries
                  if e.get("status") == "selected")
    else:
        chosen = parse_plan(spec, n, model)
        tr = build_trainer(model, chosen, devices)
    if apply_knobs:
        chosen.apply_env()
    tr._plan_report = report
    return tr


# ============================================================================
# CLI: --selftest / --explain / --bench / --hlo-audit
# ============================================================================

def _bench_sym(dim=256, hidden=2048, nclass=16):
    """The transformer-scale bench arm: wide FC stack whose parameter
    gather/reduce wire dwarfs the tiny per-device batch compute."""
    from .zero import _wide_sym
    return _wide_sym(dim=dim, hidden=hidden, nclass=nclass)


def _small_model(batch=16, dim=32, hidden=64, nclass=8,
                 optimizer="sgd"):
    from .zero import _wide_sym
    sym = _wide_sym(dim=dim, hidden=hidden, nclass=nclass)
    kw = {"learning_rate": 0.1, "rescale_grad": 1.0 / batch}
    if optimizer == "sgd":
        kw["momentum"] = 0.9
    return ModelSpec(sym, {"data": (batch, dim),
                           "softmax_label": (batch,)},
                     optimizer=optimizer, **kw), batch, dim, nclass


def selftest(devices=8):
    """tools/ci.sh quick body — one planner_selftest JSON line:

      1. determinism: two plan_auto runs agree on the choice AND the
         full (name, cost) candidate ordering;
      2. pruning: a 1 MB budget rejects every candidate BEFORE any
         executable is built (report.compiled == 0 via the raised
         no-feasible-plan error's report-free path — asserted with a
         probe run at a budget only dp fits);
      3. degenerate construction: plan="dp" is a plain
         DataParallelTrainer, plan="zero2" a stage-2 ZeroTrainer;
      4. ZeRO over dp×tp: dpK.tp2+zero2 trains the selftest model with
         an fp32 loss trajectory within 8 ULP of pure dp after 10
         steps, and its masters shard 1/(D·T).
    """
    import json
    from mxnet_tpu.config import pin_cpu
    pin_cpu(devices)
    import jax
    n_dev = min(devices, len(jax.devices()))
    model, batch, dim, nclass = _small_model()
    results = {"metric": "planner_selftest", "devices": n_dev}

    # 1) determinism
    r1 = plan_auto(model, n_dev=n_dev, budget=None)
    r2 = plan_auto(model, n_dev=n_dev, budget=None)
    key = lambda r: [(e["plan"].name, round(e["cost_s"], 15))
                     for e in r.entries if "cost_s" in e]
    results["auto_choice"] = r1.chosen.name
    results["deterministic"] = bool(r1.chosen.name == r2.chosen.name
                                    and key(r1) == key(r2))
    results["candidates_scored"] = r1.compiled

    # 2) pruning before compile: 16 KB is below every candidate's
    # analytic lower bound, so all reject in the prefilter and the
    # report must show ZERO executables built
    try:
        plan_auto(model, n_dev=n_dev, budget=1 << 14)
        results["pruned_all"] = False
        results["pruned_compiles"] = -1
    except MXNetError as e:
        rep = getattr(e, "report", None)
        results["pruned_all"] = bool(rep is not None and all(
            x.get("status") == "rejected_hbm"
            for x in rep.entries if x.get("plan") is not None))
        results["pruned_compiles"] = rep.compiled if rep else -1

    # 3) degenerate plans construct the exact legacy trainers
    from .dp import DataParallelTrainer
    from .zero import ZeroTrainer
    tr_dp = make_trainer(model.symbol, model.shape_kwargs, plan="dp",
                         n_dev=n_dev, apply_knobs=False,
                         optimizer=model.optimizer,
                         **model.trainer_kwargs)
    tr_z2 = make_trainer(model.symbol, model.shape_kwargs, plan="zero2",
                         n_dev=n_dev, apply_knobs=False,
                         optimizer=model.optimizer,
                         **model.trainer_kwargs)
    results["degenerate_dp"] = bool(
        type(tr_dp) is DataParallelTrainer)
    results["degenerate_zero2"] = bool(
        isinstance(tr_z2, ZeroTrainer) and tr_z2._zero_stage == 2)

    # 4) ZeRO over dp×tp vs pure dp (fp32, 10 steps)
    rng = _np.random.RandomState(0)
    x = rng.normal(size=(batch, dim)).astype(_np.float32)
    y = rng.randint(0, nclass, size=(batch,)).astype(_np.float32)

    def _train(tr, steps=10):
        params, states, aux = tr.init_state(model.shape_kwargs)
        inputs = tr.shard_inputs([x, y])
        losses = []
        for _ in range(steps):
            params, states, aux, loss, _ = tr.step(params, states, aux,
                                                   inputs)
            losses.append(float(loss))
        return tr.host_params(params) if hasattr(tr, "host_params") \
            else {n: _np.asarray(p)
                  for n, p in zip(tr.param_names, params)}, losses

    t = 2 if n_dev % 2 == 0 and n_dev > 2 else 1
    if t > 1:
        tr_tz = make_trainer(model.symbol, model.shape_kwargs,
                             plan=f"dp{n_dev // t}.tp{t}+zero2",
                             n_dev=n_dev, apply_knobs=False,
                             optimizer=model.optimizer,
                             **model.trainer_kwargs)
        h_dp, l_dp = _train(tr_dp)
        h_tz, l_tz = _train(tr_tz)
        ulp = max(float(_np.abs(h_dp[n] - h_tz[n]).max())
                  / (float(_np.abs(h_dp[n]).max()) * 2.0 ** -23 + 1e-30)
                  for n in h_dp)
        results["zero_tp_param_ulp"] = round(ulp, 3)
        results["zero_tp_close"] = bool(ulp <= 8.0)
        results["zero_tp_loss_close"] = bool(all(
            abs(a - b) <= 8 * 2.0 ** -23 * max(abs(a), 1.0)
            for a, b in zip(l_dp, l_tz)))
        results["zero_tp_model_factor"] = tr_tz._model_factor
    else:
        results["zero_tp_close"] = True
        results["zero_tp_loss_close"] = True

    ok = (results["deterministic"] and results["pruned_all"]
          and results["pruned_compiles"] == 0
          and results["degenerate_dp"] and results["degenerate_zero2"]
          and results["zero_tp_close"]
          and results["zero_tp_loss_close"])
    results["ok"] = bool(ok)
    print(json.dumps(results), flush=True)
    return 0 if ok else 1


def explain(plan_spec="auto", devices=8):
    """Print the per-candidate score table (the --explain CLI) plus one
    planner_explain JSON line."""
    import json
    from mxnet_tpu.config import pin_cpu
    pin_cpu(devices)
    import jax
    n_dev = min(devices, len(jax.devices()))
    model, _, _, _ = _small_model(batch=32, dim=64, hidden=256,
                                  nclass=16, optimizer="adam")
    report = plan_auto(model, n_dev=n_dev)
    rows = []
    for e in report.entries:
        name = e["plan"].name if e.get("plan") else e["name"]
        if "cost_s" in e:
            rows.append((name, e["status"], e["cost_s"],
                         e["flops"], e["wire_bytes_hlo"],
                         e["peak_bytes"]))
            print(f"{name:>16}  {e['status']:>13}  "
                  f"cost={e['cost_s'] * 1e3:8.3f}ms  "
                  f"flops={e['flops'] / 1e6:8.1f}M  "
                  f"wire={e['wire_bytes_hlo'] / 1e6:7.2f}MB  "
                  f"peak={e['peak_bytes'] / 1e6:7.1f}MB")
        else:
            rows.append((name, e["status"], None, None, None, None))
            print(f"{name:>16}  {e['status']:>13}  {e['reason']}")
    print(f"{'-' * 72}\nselected: {report.chosen.name}  "
          f"knobs: {report.chosen.knobs()}")
    if any(e.get("compute_s", 0) is None for e in report.entries):
        print("no peaks row for this device: cost is the wire term alone")
    rec = {"metric": "planner_explain", "devices": n_dev}
    rec.update(report.to_dict())
    print(json.dumps(rec), flush=True)
    return 0


def bench(devices=8, steps=8):
    """`--bench`: MXNET_PLAN=auto vs hand-picked dp
    and zero2 on the transformer-scale arm (wide FC stack, small batch,
    adam — parameter gather/reduce wire and de-replicated update work
    dominate). Reports measured steps/s per arm, the planner's decision
    and its predicted cost ranking; one plan_bench JSON line."""
    import json
    import time
    from mxnet_tpu.config import pin_cpu
    pin_cpu(devices)
    import jax
    n_dev = min(devices, len(jax.devices()))
    batch, dim, nclass, hidden = 16, 256, 16, 1024
    sym = _bench_sym(dim=dim, hidden=hidden, nclass=nclass)
    shape_kwargs = {"data": (batch, dim), "softmax_label": (batch,)}
    kw = dict(optimizer="adam", learning_rate=1e-3,
              rescale_grad=1.0 / batch)
    model = ModelSpec(sym, shape_kwargs, **kw)
    rng = _np.random.RandomState(0)
    x = rng.normal(size=(batch, dim)).astype(_np.float32)
    y = rng.randint(0, nclass, size=(batch,)).astype(_np.float32)

    report = plan_auto(model, n_dev=n_dev)
    predicted = sorted(
        ((e["plan"].name, e["cost_s"]) for e in report.entries
         if "cost_s" in e), key=lambda kv: (kv[1], kv[0]))

    def _measure(plan_spec):
        tr = make_trainer(sym, shape_kwargs, plan=plan_spec,
                          n_dev=n_dev, apply_knobs=False, **kw)
        params, states, aux = tr.init_state(shape_kwargs)
        inputs = tr.shard_inputs([x, y])
        for _ in range(2):
            params, states, aux, loss, _ = tr.step(params, states, aux,
                                                   inputs)
        float(loss)
        rates = []
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(steps):
                params, states, aux, loss, _ = tr.step(params, states,
                                                       aux, inputs)
            float(loss)
            rates.append(steps / (time.perf_counter() - t0))
        return sorted(rates)[1]

    arms = {"dp": _measure("dp"), "zero2": _measure("zero2"),
            "auto": _measure(report.chosen.name)}
    measured = sorted(arms.items(), key=lambda kv: (-kv[1], kv[0]))
    best_hand = max(arms["dp"], arms["zero2"])
    rec = {"metric": "plan_bench", "devices": n_dev,
           "params": int(model.param_elems), "optimizer": "adam",
           "batch": batch, "steps_per_window": steps,
           "auto_choice": report.chosen.name,
           "predicted_rank": [n for n, _ in predicted],
           "predicted_cost_s": {n: round(c, 6) for n, c in predicted},
           "dp_steps_per_s": round(arms["dp"], 2),
           "zero2_steps_per_s": round(arms["zero2"], 2),
           "auto_steps_per_s": round(arms["auto"], 2),
           "measured_rank": [n for n, _ in measured],
           "auto_beats_hand": bool(arms["auto"] >= 0.95 * best_hand),
           "speedup_vs_dp": round(arms["auto"] / arms["dp"], 3)}
    print(json.dumps(rec), flush=True)
    return 0


def hlo_audit(devices=8):
    """hloaudit's fit_step_plan subprocess body: compile the planner's
    dp×tp+ZeRO-2 composition on an 8-device virtual mesh and report the
    invariants — reduce-scatter + all-gather present, no gradient-sized
    all-reduce, full donation, HLO wire bytes within 10% of the
    planner's analytic estimate. One planner_hlo_audit JSON line."""
    import json
    from mxnet_tpu.config import pin_cpu
    pin_cpu(devices)
    import jax
    from ..telemetry import devstats
    from ..analysis.hloaudit import (collectives_in_text,
                                     collective_wire_bytes,
                                     donated_param_indices,
                                     collective_pairing_ok, has_f64,
                                     convert_count, allreduce_counts,
                                     _elems)
    n_dev = min(devices, len(jax.devices()))
    t = 2 if n_dev % 2 == 0 and n_dev > 1 else 1
    model, batch, dim, nclass = _small_model(batch=16, dim=64,
                                             hidden=256, nclass=16)
    plan = parse_plan(f"dp{n_dev // t}.tp{t}+zero2", n_dev, model)
    tr = build_trainer(model, plan)
    fn, args = _abstract_args(model, tr)
    compiled = fn.lower(*args).compile()
    hlo = compiled.as_text()
    colls = collectives_in_text(hlo)
    wires = collective_wire_bytes(colls, n_dev)
    # small all-reduces ride every plan (loss/finite scalars, and the
    # pmax that proves loss/outputs/aux replicated over the model axis);
    # one the size of a gradient BUCKET means the joint reduce-scatter
    # regressed to dp
    L = tr._layout
    grad_ars = [c for c in colls["all-reduce"]
                if _elems(c[1]) >= min(L.padded)]
    wire_hlo = sum(wires.values())
    est = estimate_wire_bytes(model, plan,
                              bucket_bytes=tr._bucket_bytes)
    donated = donated_param_indices(hlo)
    expected = L.n_buckets * (1 + tr._n_states)   # masters + opt shards
    within = bool(est and abs(wire_hlo - est) <= 0.10 * est)
    n_sync, n_async = allreduce_counts(hlo)
    rec = {"metric": "planner_hlo_audit", "devices": n_dev,
           "plan": plan.name, "buckets": L.n_buckets,
           "allreduce_sync": n_sync, "allreduce_async": n_async,
           "reduce_scatter": len(colls["reduce-scatter"]),
           "all_gather": len(colls["all-gather"]),
           "grad_allreduce_nonscalar": len(grad_ars),
           "wire_bytes_hlo": int(wire_hlo),
           "wire_bytes_estimate": int(est),
           "wire_within_10pct": within,
           "donated": sorted(donated), "donate_expected": expected,
           "pairing_ok": collective_pairing_ok(hlo),
           "has_f64": has_f64(hlo),
           "convert_count": convert_count(hlo),
           "recompiles": 1,
           "cost": {k: devstats.extract(compiled)[k]
                    for k in ("flops", "bytes_accessed",
                              "argument_bytes", "peak_bytes")}}
    rec["ok"] = bool(rec["reduce_scatter"] and rec["all_gather"]
                     and not grad_ars and within
                     and len(donated) >= expected)
    print(json.dumps(rec), flush=True)
    return 0 if rec["ok"] else 1


def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser(
        prog="python -m mxnet_tpu.parallel.planner")
    ap.add_argument("--selftest", action="store_true",
                    help="determinism/pruning/parity (ci.sh quick)")
    ap.add_argument("--explain", action="store_true",
                    help="per-candidate score table for the auto plan")
    ap.add_argument("--bench", action="store_true",
                    help="auto vs hand dp/zero2 on a virtual CPU mesh")
    ap.add_argument("--hlo-audit", action="store_true",
                    help="fit_step_plan subprocess body (hloaudit)")
    ap.add_argument("--plan", default="auto")
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--steps", type=int, default=8)
    args = ap.parse_args(argv)
    if args.hlo_audit:
        return hlo_audit(args.devices)
    if args.bench:
        return bench(devices=args.devices, steps=args.steps)
    if args.explain:
        return explain(args.plan, args.devices)
    if args.selftest:
        return selftest(args.devices)
    ap.print_help()
    return 2


if __name__ == "__main__":
    import sys
    sys.exit(main())
