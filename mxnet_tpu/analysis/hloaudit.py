"""hloaudit — compiled-program invariant auditor.

Where tracelint/locklint read the *source*, this pass compiles a matrix
of representative programs and asserts properties of the *artifact* —
the post-SPMD / optimized HLO the partitioner actually emits:

  - ``fit_step_fp32`` / ``fit_step_bf16``  the fused K=2 training step
    (``DataParallelTrainer._multi_step_fn``) on a 2-device cpu mesh:
    gradient all-reduce present and (where async) start/done paired,
    params+optimizer-states donated, no f64, convert count and
    recompile count within the per-program budget;
  - ``serving_bucket``  one bucketed serving plan
    (``ServingEngine._plan``): no f64, convert/recompile budgets;
  - the PR-4 amp wire invariant: the bf16 gradient all-reduce moves
    EXACTLY half the wire bytes of the fp32 one (two
    ``python -m mxnet_tpu.amp --hlo-check`` subprocess runs).

The compile half runs in a fresh subprocess (``--audit-programs``):
device pinning and XLA dump flags are consumed once at backend init,
so the auditing process must own its backend from birth — the parent
only parses the JSON report. The text helpers below are the single
home of the repo's HLO-matching code; ``__graft_entry__`` and
``mxnet_tpu.amp.__main__`` import them rather than re-growing regexes.

Budgets come from ``hlo_budget(baseline, program)`` — the shipped
defaults in ``analysis.DEFAULT_HLO_BUDGETS``, overridable key-by-key in
``tools/analysis_baseline.json`` under ``hlo_budgets``.
"""
from __future__ import annotations

import glob
import json
import os
import re
import subprocess
import sys

from . import Finding, hlo_budget, package_root

__all__ = ["allreduce_counts", "allreduce_pairing_ok", "has_f64",
           "convert_count", "donated_param_indices", "spmd_allreduces",
           "spmd_collectives", "collectives_in_text", "collective_counts",
           "collective_pairing_ok", "collective_wire_bytes",
           "async_pair_stats", "async_interleave_ok",
           "wire_bytes", "parse_last_metric", "audit_findings",
           "findings_from_report", "amp_wire_findings", "run",
           "ITEMSIZE", "PROGRAMS", "COLLECTIVE_KINDS"]

ITEMSIZE = {"f32": 4, "bf16": 2, "f16": 2, "f64": 8,
            "f8e4m3fn": 1, "f8e5m2": 1}

PROGRAMS = ("fit_step_fp32", "fit_step_bf16", "fit_step_zero",
            "fit_step_embedding", "serving_bucket", "fit_decode",
            "fit_step_plan")

# the cross-device data-movement ops the ZeRO lane audits. "-start"
# suffixed async forms are matched alongside the synchronous spelling;
# "-done" halves are never counted (one transfer, two instructions).
COLLECTIVE_KINDS = ("all-reduce", "reduce-scatter", "all-gather")

# where each audited program's defining code lives (finding file field)
_PROGRAM_FILE = {
    "fit_step_fp32": "parallel/dp.py",
    "fit_step_bf16": "parallel/dp.py",
    "fit_step_zero": "parallel/zero.py",
    "fit_step_embedding": "parallel/embedding.py",
    "serving_bucket": "serving/engine.py",
    "fit_decode": "serving/decode.py",
    "fit_step_plan": "parallel/planner.py",
}


def pin_cpu_with_spmd_dump(devices, prefix):
    """Set-up shared by the fresh-process entry points that read the
    post-SPMD HLO dump (``--hlo-check``, ``--bench``): ask XLA for the
    dump (the flag is read once, at backend start), select `devices`
    CPU devices, and turn the persistent compilation cache off for this
    process — a cache hit compiles nothing, so it dumps nothing.
    Returns the dump directory (the caller removes it)."""
    import tempfile
    import jax
    from mxnet_tpu.config import pin_cpu
    dump = tempfile.mkdtemp(prefix=prefix)
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_dump_to={dump} --xla_dump_hlo_as_text"
        + " --xla_dump_hlo_pass_re=.*spmd.*")
    pin_cpu(devices)
    jax.config.update("jax_enable_compilation_cache", False)
    return dump


# -- pure HLO-text helpers ---------------------------------------------------
# (no jax imports: unit-testable on strings, importable everywhere)

def allreduce_counts(hlo):
    """(n_sync, n_async) all-reduces in one HLO module text. Async pairs
    (all-reduce-start/-done) are how TPU/GPU backends hide the collective
    behind compute; the cpu backend lowers the synchronous form."""
    return hlo.count("all-reduce("), hlo.count("all-reduce-start")


def allreduce_pairing_ok(hlo):
    """Every all-reduce-start has a matching all-reduce-done."""
    return hlo.count("all-reduce-done") == hlo.count("all-reduce-start")


def has_f64(hlo):
    """Any f64 tensor anywhere in the module — the framework is fp32/
    half-precision only; f64 means a silent numpy float64 leaked in."""
    return re.search(r"\bf64\[", hlo) is not None


def convert_count(hlo):
    """Number of convert ops — the dtype-cast traffic amp is supposed to
    keep fused and bounded."""
    return len(re.findall(r"\bconvert\(", hlo))


def donated_param_indices(hlo):
    """Parameter indices donated to outputs, from the HloModule header's
    ``input_output_alias={ {out}: (param, {}, may-alias), ... }`` map.
    Balanced-brace scan: the map's values nest braces, so a regex over
    the whole header would stop at the first ``}``."""
    start = hlo.find("input_output_alias={")
    if start < 0:
        return set()
    i = hlo.index("{", start)
    depth, j = 0, i
    while j < len(hlo):
        if hlo[j] == "{":
            depth += 1
        elif hlo[j] == "}":
            depth -= 1
            if depth == 0:
                break
        j += 1
    blob = hlo[i:j + 1]
    return {int(m.group(1)) for m in re.finditer(r"\(\s*(\d+)\s*,", blob)}


def spmd_allreduces(dump_dir, module_substr="jit_step"):
    """[(dtype, "d0,d1,...")] for every all-reduce in the POST-SPMD-
    PARTITIONING dump of modules matching ``module_substr``. This is the
    pass that inserts the collectives; later backend legalization may
    re-widen them (cpu promotes bf16 to f32), so only this dump shows
    the wire dtype the partitioner chose."""
    ars = []
    pat = os.path.join(dump_dir,
                       f"*{module_substr}*after_spmd-partitioning*")
    for f in sorted(glob.glob(pat)):
        with open(f, encoding="utf-8") as fh:
            text = fh.read()
        for m in re.finditer(r"=\s*(\w+)\[([\d,]*)\][^=]*?all-reduce\(",
                             text):
            ars.append([m.group(1), m.group(2)])
    return ars


def wire_bytes(ars):
    """Total bytes moved by [(dtype, shape-csv)] collectives."""
    total = 0
    for dt, shape in ars:
        n = 1
        for d in shape.split(","):
            if d:
                n *= int(d)
        total += ITEMSIZE.get(dt, 4) * n
    return total


def collective_counts(hlo):
    """kind -> (n_sync, n_async) over COLLECTIVE_KINDS in one module
    text. The "(?:-start)?\\(" tail keeps "all-reduce-start(" from being
    double-counted by the bare spelling and never matches "-done("."""
    out = {}
    for kind in COLLECTIVE_KINDS:
        out[kind] = (len(re.findall(re.escape(kind) + r"\(", hlo)),
                     len(re.findall(re.escape(kind) + r"-start\(", hlo)))
    return out


def collective_pairing_ok(hlo):
    """Every async collective start has a matching done, per kind."""
    return all(
        hlo.count(f"{kind}-start") == hlo.count(f"{kind}-done")
        for kind in COLLECTIVE_KINDS)


# one collective instruction: its result type — one array, or the tuple
# XLA's all-reduce combiner makes of several — then the op name
_COLL_RX = re.compile(
    r"=\s*(\([^()\n]*\)|\w+\[[\d,]*\][^=\n]*?)\s*"
    rf"({'|'.join(re.escape(k) for k in COLLECTIVE_KINDS)})"
    r"(?:-start)?\(")
_ARRAY_RX = re.compile(r"(\w+)\[([\d,]*)\]")


def collectives_in_text(hlo):
    """kind -> [(dtype, "d0,d1,...")] for every array a collective
    moves in ONE module text (a Compiled's as_text(), or a dumped
    module); a combined (tuple-typed) collective contributes each of its
    arrays. Caveat: backend legalization may have re-widened dtypes in
    the final module (cpu promotes bf16), so use that for shape/count
    structure, the post-SPMD dump for wire-dtype questions."""
    colls = {kind: [] for kind in COLLECTIVE_KINDS}
    for m in _COLL_RX.finditer(hlo):
        colls[m.group(2)].extend(
            [dt, shape] for dt, shape in _ARRAY_RX.findall(m.group(1)))
    return colls


def spmd_collectives(dump_dir, module_substr="jit_step"):
    """collectives_in_text over the post-SPMD dump of the modules
    matching ``module_substr``. Same dump stage as spmd_allreduces (the
    wire dtype the partitioner chose); reduce-scatter's dumped OUTPUT
    shape is the per-device SHARD — collective_wire_bytes re-globalizes
    it with n_dev."""
    colls = {kind: [] for kind in COLLECTIVE_KINDS}
    pat = os.path.join(dump_dir,
                       f"*{module_substr}*after_spmd-partitioning*")
    for f in sorted(glob.glob(pat)):
        with open(f, encoding="utf-8") as fh:
            for kind, found in collectives_in_text(fh.read()).items():
                colls[kind].extend(found)
    return colls


def _elems(shape_csv):
    n = 1
    for d in shape_csv.split(","):
        if d:
            n *= int(d)
    return n


def collective_wire_bytes(colls, n_dev):
    """kind -> per-device wire bytes under ring-collective accounting:
    an all-gather / reduce-scatter of a GLOBAL buffer of S bytes moves
    (N-1)/N * S per device; an all-reduce moves twice that (it IS a
    reduce-scatter + all-gather). Dumped output shapes are global for
    all-reduce/all-gather and the 1/N shard for reduce-scatter."""
    frac = (n_dev - 1) / n_dev
    out = {}
    for kind in COLLECTIVE_KINDS:
        total = 0.0
        for dt, shape in colls.get(kind, []):
            size = ITEMSIZE.get(dt, 4) * _elems(shape)
            if kind == "reduce-scatter":
                size *= n_dev
            mult = 2.0 if kind == "all-reduce" else 1.0
            total += mult * frac * size
        out[kind] = int(total)
    return out


# async start/done interleave: the latency-hiding proof. A start opens a
# window; any sizable compute op issued before its done means the
# scheduler actually overlapped the collective with computation.
_ASYNC_START_RX = re.compile(
    r"(\S+)\s*=\s*[^=\n]*?\b((?:all-reduce|reduce-scatter|all-gather|"
    r"collective-permute)-start)\(")
_ASYNC_DONE_RX = re.compile(
    r"\b(?:all-reduce|reduce-scatter|all-gather|collective-permute)"
    r"-done\(\s*(\S+?)[\s,)]")
# ops that represent real computation (NOT bookkeeping like bitcast/
# tuple/parameter, and NOT a substring of "all-reduce(")
_COMPUTE_RX = re.compile(
    r"\b(?:fusion|dot|convolution|custom-call|while)\(")


def async_pair_stats(hlo):
    """{"pairs": n, "interleaved": k}: of n async collective start/done
    pairs, k had at least one compute op (fusion/dot/convolution/
    custom-call/while) issued between start and done in program order.
    Line scanner over the module text: HLO instruction order inside a
    computation IS the scheduler's issue order in dumped optimized
    modules."""
    open_starts = {}            # result var -> compute seen since start
    pairs = interleaved = 0
    for line in hlo.splitlines():
        m = _ASYNC_START_RX.search(line)
        if m:
            open_starts[m.group(1).lstrip("%")] = False
            continue
        m = _ASYNC_DONE_RX.search(line)
        if m:
            var = m.group(1).lstrip("%")
            if var in open_starts:
                pairs += 1
                if open_starts.pop(var):
                    interleaved += 1
            continue
        if open_starts and _COMPUTE_RX.search(line):
            for var in open_starts:
                open_starts[var] = True
    return {"pairs": pairs, "interleaved": interleaved}


def async_interleave_ok(stats):
    """Vacuously true with no async pairs (cpu lowers sync collectives);
    with pairs present, at least one must bracket compute."""
    return stats["pairs"] == 0 or stats["interleaved"] > 0


def parse_last_metric(stdout, metric):
    """Last JSON line in ``stdout`` whose "metric" field matches, or {}.
    Selftest CLIs print exactly one such line; anything else on stdout
    (warnings, progress) is skipped."""
    for line in reversed((stdout or "").strip().splitlines()):
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        if rec.get("metric") == metric:
            return rec
    return {}


# -- the compile half (fresh-subprocess body) --------------------------------

def _audit_programs():
    """Compile the program matrix and print ONE ``hlo_audit`` JSON line.
    Must run in a process whose jax backend it owns (``config.pin_cpu``
    before the first backend use)."""
    from mxnet_tpu.amp.__main__ import _mlp_sym, _trainer
    from mxnet_tpu.config import pin_cpu
    pin_cpu(2)
    import numpy as np
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu.parallel import data_parallel_mesh

    # devstats.extract is the single home of executable introspection:
    # the audit report carries each program's XLA cost/memory analytics
    # ("cost" key) from the same Compiled whose HLO text is budgeted
    from mxnet_tpu.telemetry import devstats

    def _cost(compiled):
        s = devstats.extract(compiled)
        return {k: s[k] for k in ("flops", "bytes_accessed",
                                  "argument_bytes", "peak_bytes")}

    out = {"metric": "hlo_audit", "programs": {}}
    mesh = data_parallel_mesh(2, jax.devices()[:2])
    # stacked (K=2, batch, ...) blocks for the fused step
    xk = np.zeros((2, 16, 8), np.float32)
    yk = np.zeros((2, 16), np.float32)

    for name, dtype in (("fit_step_fp32", "float32"),
                        ("fit_step_bf16", "bfloat16")):
        tr = _trainer(dtype, mesh)
        params, states, aux = tr.init_state({"data": (16, 8),
                                             "softmax_label": (16,)})
        stacked = tr.shard_inputs([xk, yk], stacked=True)
        tr._ensure_dev_state(None)
        fn = tr._multi_step_fn(2, "none")
        compiled = fn.lower(params, states, aux, stacked, tr._rng_dev,
                            tr._lr_dev, tr._t_dev).compile()
        hlo = compiled.as_text()
        n_sync, n_async = allreduce_counts(hlo)
        donated = donated_param_indices(hlo)
        # donate_argnums=(0, 1): every params + optimizer-state leaf
        # must be aliased to an output or the fused loop double-buffers
        n_leaves = len(jax.tree_util.tree_leaves((params, states)))
        # recompile check: two same-shape dispatches, ONE executable
        p2, s2, a2, _, _ = tr.step_k(params, states, aux, stacked)
        tr.step_k(p2, s2, a2, tr.shard_inputs([xk, yk], stacked=True))
        out["programs"][name] = {
            "allreduce_sync": n_sync,
            "allreduce_async": n_async,
            "pairing_ok": allreduce_pairing_ok(hlo),
            "has_f64": has_f64(hlo),
            "convert_count": convert_count(hlo),
            "donated": sorted(donated),
            "donate_expected": n_leaves,
            "recompiles": int(fn._cache_size()),
            "cost": _cost(compiled),
        }

    # fit_step_zero: the ZeRO-2 K=2 fused step, tiny bucket threshold so
    # the layout is multi-bucket (one reduce-scatter per bucket is the
    # overlap structure the interleave assertion is about)
    from mxnet_tpu.parallel.zero import ZeroTrainer
    trz = ZeroTrainer(_mlp_sym(), mesh, zero_stage=2, optimizer="sgd",
                      learning_rate=0.1, momentum=0.9,
                      rescale_grad=1.0 / 16, zero_bucket_mb=0.0005)
    params, states, aux = trz.init_state({"data": (16, 8),
                                          "softmax_label": (16,)})
    stacked = trz.shard_inputs([xk, yk], stacked=True)
    trz._ensure_dev_state(None)
    fnz = trz._zero_multi_fn(2, "none")
    compiled_z = fnz.lower(params, states, trz._resid_dev, aux, stacked,
                           trz._rng_dev, trz._lr_dev,
                           trz._t_dev).compile()
    hlo = compiled_z.as_text()
    cc = collective_counts(hlo)
    grad_ars = [m for m in re.finditer(
        r"=\s*(\w+)\[([\d,]*)\][^=\n]*?all-reduce\(", hlo)
        if m.group(2)]          # non-scalar = gradient-sized
    donated = donated_param_indices(hlo)
    n_leaves = len(jax.tree_util.tree_leaves((params, states)))
    p2, s2, a2, _, _ = trz.step_k(params, states, aux, stacked)
    trz.step_k(p2, s2, a2, trz.shard_inputs([xk, yk], stacked=True))
    out["programs"]["fit_step_zero"] = {
        "allreduce_sync": cc["all-reduce"][0],
        "allreduce_async": cc["all-reduce"][1],
        "reduce_scatter": sum(cc["reduce-scatter"]),
        "all_gather": sum(cc["all-gather"]),
        "grad_allreduce_nonscalar": len(grad_ars),
        "buckets": trz._layout.n_buckets,
        "async": async_pair_stats(hlo),
        "pairing_ok": collective_pairing_ok(hlo),
        "has_f64": has_f64(hlo),
        "convert_count": convert_count(hlo),
        "donated": sorted(donated),
        "donate_expected": n_leaves,
        "recompiles": int(fnz._cache_size()),
        "cost": _cost(compiled_z),
    }

    # fit_step_embedding: the row-sparse embedding exchange. Compile the
    # SAME step at two vocab sizes (touched rows held fixed) plus the
    # dense baseline, and take collective wire bytes straight from the
    # optimized modules: the exchange payload must not move when only
    # the vocab grows, and must undercut the dense all-reduce.
    from mxnet_tpu.parallel.embedding import EmbeddingTrainer

    def _embed_compile(vocab, exchange):
        tr = EmbeddingTrainer(mesh, vocab=vocab, embed_dim=16, n_slots=2,
                              mlp_hidden=(32,), optimizer="sgd",
                              learning_rate=0.1, exchange=exchange,
                              compress="none", batch_size=16,
                              rescale_grad=1.0 / 16)
        state = tr.init_state(16)
        rng = np.random.RandomState(0)
        inp = tr.shard_inputs([rng.randint(0, vocab, (16, 2)),
                               np.zeros((16, 0), np.float32),
                               rng.randint(0, 2, (16,)).astype(
                                   np.float32)])
        tr._ensure_layout(16 // 2 * 2)
        tr._build_step()
        compiled = tr._step_fn.lower(*state, *inp).compile()
        return tr, state, inp, compiled

    tre, state_e, inp_e, compiled_e = _embed_compile(256, "sparse")
    hlo = compiled_e.as_text()
    wire_sp = sum(collective_wire_bytes(
        collectives_in_text(hlo), 2).values())
    _, _, _, c_big = _embed_compile(1024, "sparse")
    wire_sp_big = sum(collective_wire_bytes(
        collectives_in_text(c_big.as_text()), 2).values())
    _, _, _, c_dn = _embed_compile(256, "dense")
    wire_dn = sum(collective_wire_bytes(
        collectives_in_text(c_dn.as_text()), 2).values())
    cc = collective_counts(hlo)
    donated = donated_param_indices(hlo)
    n_leaves = len(jax.tree_util.tree_leaves(state_e))
    # recompile check: two same-shape dispatches, ONE executable
    s2, _, _ = tre.step(state_e, inp_e)
    tre.step(s2, inp_e)
    out["programs"]["fit_step_embedding"] = {
        "allreduce_sync": cc["all-reduce"][0],
        "allreduce_async": cc["all-reduce"][1],
        "all_gather": sum(cc["all-gather"]),
        "reduce_scatter": sum(cc["reduce-scatter"]),
        "wire_bytes_sparse": wire_sp,
        "wire_bytes_sparse_big_vocab": wire_sp_big,
        "wire_bytes_dense": wire_dn,
        "pairing_ok": collective_pairing_ok(hlo),
        "has_f64": has_f64(hlo),
        "convert_count": convert_count(hlo),
        "donated": sorted(donated),
        "donate_expected": n_leaves,
        "recompiles": int(tre._step_fn._cache_size()),
        "cost": _cost(compiled_e),
    }

    sym = _mlp_sym()
    mod = mx.mod.Module(sym, context=mx.cpu(0))
    mod.bind(data_shapes=[("data", (8, 8))],
             label_shapes=[("softmax_label", (8,))])
    mod.init_params(mx.init.Xavier())
    args, auxs = mod.get_params()
    from mxnet_tpu.serving import ServingEngine
    eng = ServingEngine.from_symbol(sym, args, auxs, {"data": (8, 8)},
                                    warmup=False)
    bucket = eng.buckets[0]          # smallest bucket: pad-and-slice plan
    arrays = [np.zeros((bucket, 8), np.float32)]
    # plans are AOT Compiled objects (serving/engine.py): the executable
    # the requests run IS the one audited — no second lower/compile
    plan = eng._plan(bucket)
    hlo = plan.as_text()
    eng.infer(arrays[0])
    eng.infer(arrays[0])
    out["programs"]["serving_bucket"] = {
        "allreduce_sync": hlo.count("all-reduce("),
        "allreduce_async": hlo.count("all-reduce-start"),
        "pairing_ok": allreduce_pairing_ok(hlo),
        "has_f64": has_f64(hlo),
        "convert_count": convert_count(hlo),
        "donated": [],
        "donate_expected": 0,        # serving plans donate nothing
        # AOT plans cannot recompile by construction; the audited count
        # is the engine's cache-miss counter for this one bucket
        "recompiles": int(eng.plan_compiles),
        "cost": _cost(plan),
    }

    # fit_decode: the continuous-batching invariants (PR 18). ONE step
    # executable regardless of session occupancy, KV-cache buffers
    # donated between steps (steady-state decode holds one pool), and
    # the calibrated int8 weights survive fusion as s8 dot operands.
    from mxnet_tpu.serving.decode import DecodeEngine, DecodeModel
    from mxnet_tpu.contrib.quantization import calibrate_weights
    dmodel = DecodeModel(vocab=32, layers=2, d_model=32, heads=2,
                         kv_heads=1, d_ff=64, max_len=32)
    qparams, _ = calibrate_weights(dmodel.init_params(seed=3), "int8")
    deng = DecodeEngine(dmodel, qparams, num_slots=4, warmup=True,
                        name="audit-decode")
    try:
        # occupancy 1, then 3 concurrent: the plan must not re-key
        deng.generate([1, 2, 3], max_new_tokens=4)
        sess = [deng.submit([4 + i, 5], max_new_tokens=6)
                for i in range(3)]
        for s in sess:
            s.result()
        hlo = deng._step_plan.as_text()
        donated = donated_param_indices(hlo)
        out["programs"]["fit_decode"] = {
            "allreduce_sync": hlo.count("all-reduce("),
            "allreduce_async": hlo.count("all-reduce-start"),
            "pairing_ok": allreduce_pairing_ok(hlo),
            "has_f64": has_f64(hlo),
            "convert_count": convert_count(hlo),
            "donated": sorted(donated),
            # one (K, V) cache buffer per layer, all donated
            "donate_expected": 2 * dmodel.layers,
            # occupancy changed 1 -> 3 across the run; a second
            # executable here is the recompile storm the issue forbids
            "recompiles": int(deng.step_compiles),
            "int8_operands": "s8[" in hlo,
            "step_executions": int(deng.step_executions),
            "cost": _cost(deng._step_plan),
        }
    finally:
        deng.close(drain=False)

    # fit_step_plan: the planner's chosen dp×tp+ZeRO-2 composition on
    # an 8-device virtual mesh (parallel/planner.py --hlo-audit). This
    # process is pinned to 2 cpu devices above, so the 8-device compile
    # runs in its own subprocess and its record merges here; a dead
    # subprocess reports zeroed collectives, which the findings rules
    # flag loudly (missing reduce-scatter/all-gather are P0s).
    proc = _sub(["mxnet_tpu.parallel.planner", "--hlo-audit"], 600)
    prec = parse_last_metric(proc.stdout, "planner_hlo_audit")
    if proc.returncode != 0 or not prec:
        out["programs"]["fit_step_plan"] = {
            "error": f"rc={proc.returncode}: "
                     f"{(proc.stderr or proc.stdout or '')[-300:]}",
            "allreduce_sync": 0, "allreduce_async": 0,
            "reduce_scatter": 0, "all_gather": 0,
            "grad_allreduce_nonscalar": 0, "wire_within_10pct": False,
            "wire_bytes_hlo": 0, "wire_bytes_estimate": 0,
            "pairing_ok": True, "has_f64": False, "convert_count": 0,
            "donated": [], "donate_expected": 0, "recompiles": 0,
            "cost": {}}
    else:
        prec.pop("metric", None)
        out["programs"]["fit_step_plan"] = prec
    print(json.dumps(out), flush=True)
    return 0


# -- host-side driver: subprocess -> findings --------------------------------

def _sub(args, timeout):
    return subprocess.run(
        [sys.executable, "-m"] + args, capture_output=True, text=True,
        timeout=timeout, cwd=os.path.dirname(package_root()))


def audit_findings(baseline=None, timeout=900):
    """Run the program-matrix audit in a fresh subprocess and map its
    report onto findings. One P1 ``hlo-audit-error`` if the subprocess
    itself dies (an unbuildable program is a finding, not a crash)."""
    proc = _sub(["mxnet_tpu.analysis.hloaudit", "--audit-programs"],
                timeout)
    rec = parse_last_metric(proc.stdout, "hlo_audit")
    if proc.returncode != 0 or not rec.get("programs"):
        return [Finding(
            "hlo-audit-error", "P1", "analysis/hloaudit.py", 0,
            f"program audit subprocess failed rc={proc.returncode}: "
            f"{(proc.stderr or proc.stdout or '')[-400:]}",
            scope="audit-programs")]
    return findings_from_report(rec, baseline)


def findings_from_report(rec, baseline=None):
    """Map one ``hlo_audit`` report onto findings (separated from the
    subprocess plumbing so tests can feed synthetic reports)."""
    baseline = baseline or {}
    findings = []
    for prog in sorted(rec["programs"]):
        r = rec["programs"][prog]
        bud = hlo_budget(baseline, prog)
        file = _PROGRAM_FILE.get(prog, "analysis/hloaudit.py")
        n_ar = r["allreduce_sync"] + r["allreduce_async"]
        if prog.startswith("fit_step") and prog != "fit_step_zero" \
                and n_ar == 0:
            findings.append(Finding(
                "hlo-missing-allreduce", "P0", file, 0,
                f"{prog}: no gradient all-reduce in the compiled "
                f"2-device step — data parallelism is not happening",
                scope=prog))
        if prog == "fit_step_zero":
            # the ZeRO-2 invariants: grads move via reduce-scatter (a
            # grad-sized all-reduce means sharding regressed to dp), and
            # where the backend emits async pairs they must bracket
            # compute (the bucketed-overlap proof; cpu lowers sync
            # collectives, so pairs==0 passes vacuously)
            if not r.get("reduce_scatter"):
                findings.append(Finding(
                    "hlo-zero-missing-reduce-scatter", "P0", file, 0,
                    f"{prog}: no reduce-scatter in the compiled ZeRO-2 "
                    f"step — gradient sharding is not happening",
                    scope=prog))
            if r.get("grad_allreduce_nonscalar"):
                findings.append(Finding(
                    "hlo-zero-grad-allreduce", "P1", file, 0,
                    f"{prog}: {r['grad_allreduce_nonscalar']} "
                    f"gradient-sized all-reduce(s) in the ZeRO-2 step — "
                    f"grads should move via reduce-scatter only",
                    scope=prog))
            stats = r.get("async")
            if stats and not async_interleave_ok(stats):
                findings.append(Finding(
                    "hlo-zero-async-interleave", "P1", file, 0,
                    f"{prog}: {stats['pairs']} async collective pairs, "
                    f"none bracketing compute — bucketed comm/compute "
                    f"overlap is not being scheduled", scope=prog))
        if prog == "fit_step_embedding":
            # the row-sparse exchange invariants: wire bytes track
            # touched rows (identical batch at 4x the vocab must move
            # identical bytes), and the sparse program must beat the
            # dense table-sized all-reduce it replaces
            w1 = r.get("wire_bytes_sparse")
            w2 = r.get("wire_bytes_sparse_big_vocab")
            wd = r.get("wire_bytes_dense")
            if not r.get("all_gather"):
                findings.append(Finding(
                    "hlo-embed-missing-allgather", "P0", file, 0,
                    f"{prog}: no all-gather in the compiled sparse "
                    f"exchange step — the row exchange is not happening",
                    scope=prog))
            if w1 is not None and w2 is not None and w2 != w1:
                findings.append(Finding(
                    "hlo-embed-wire-scales-with-vocab", "P1", file, 0,
                    f"{prog}: sparse exchange moved {w1} wire bytes at "
                    f"vocab 256 but {w2} at vocab 1024 with the same "
                    f"batch — payload must scale with touched rows, "
                    f"not the table", scope=prog))
            if w1 is not None and wd is not None and w1 >= wd:
                findings.append(Finding(
                    "hlo-embed-sparse-not-smaller", "P1", file, 0,
                    f"{prog}: sparse exchange moves {w1} wire bytes "
                    f"vs the dense baseline's {wd} — the row-sparse "
                    f"path lost its reason to exist", scope=prog))
        if prog == "fit_step_plan":
            # the planner-composition invariants (ZeRO-2 over a dp×tp
            # mesh): grads move via a JOINT-axis reduce-scatter, params
            # re-materialize via a joint all-gather, and the compiled
            # wire bytes must agree with the planner's analytic
            # estimate — the number its cost model ranked plans with
            if not r.get("reduce_scatter"):
                findings.append(Finding(
                    "hlo-plan-missing-reduce-scatter", "P0", file, 0,
                    f"{prog}: no reduce-scatter in the compiled "
                    f"dp×tp+ZeRO-2 step — joint-axis gradient sharding "
                    f"is not happening", scope=prog))
            if not r.get("all_gather"):
                findings.append(Finding(
                    "hlo-plan-missing-allgather", "P0", file, 0,
                    f"{prog}: no all-gather in the compiled "
                    f"dp×tp+ZeRO-2 step — sharded masters are never "
                    f"re-materialized for compute", scope=prog))
            if r.get("grad_allreduce_nonscalar"):
                findings.append(Finding(
                    "hlo-plan-grad-allreduce", "P1", file, 0,
                    f"{prog}: {r['grad_allreduce_nonscalar']} "
                    f"gradient-sized all-reduce(s) — the joint sharding "
                    f"regressed to replicated dp", scope=prog))
            if not r.get("wire_within_10pct"):
                findings.append(Finding(
                    "hlo-plan-wire-estimate", "P1", file, 0,
                    f"{prog}: compiled HLO moves "
                    f"{r.get('wire_bytes_hlo')} wire bytes but the "
                    f"planner's estimate was "
                    f"{r.get('wire_bytes_estimate')} (>10% apart) — "
                    f"the cost model is ranking plans on bad numbers",
                    scope=prog))
        if prog == "fit_decode" and not r.get("int8_operands"):
            # the quantized-matmul invariant: calibrated int8 weights
            # must reach the fused dot as s8 operands — a convert back
            # to f32 before fusion means the bandwidth win evaporated
            findings.append(Finding(
                "hlo-decode-no-int8-operands", "P1", file, 0,
                f"{prog}: no s8 operands in the fused decode-step HLO — "
                f"quantized weights are being dequantized outside the "
                f"matmul fusion", scope=prog))
        if not r["pairing_ok"]:
            findings.append(Finding(
                "hlo-allreduce-pairing", "P0", file, 0,
                f"{prog}: unpaired all-reduce-start in optimized HLO",
                scope=prog))
        if r["has_f64"]:
            findings.append(Finding(
                "hlo-f64", "P1", file, 0,
                f"{prog}: f64 tensor in the compiled program (a numpy "
                f"float64 leaked into the trace)", scope=prog))
        if r["donate_expected"] and \
                len(r["donated"]) < r["donate_expected"]:
            findings.append(Finding(
                "hlo-donation", "P1", file, 0,
                f"{prog}: only {len(r['donated'])} of "
                f"{r['donate_expected']} params/opt-state buffers "
                f"donated — the fused step is double-buffering weights",
                scope=prog))
        cmax = bud.get("convert_max")
        if cmax is not None and r["convert_count"] > cmax:
            findings.append(Finding(
                "hlo-convert-budget", "P1", file, 0,
                f"{prog}: {r['convert_count']} convert ops, budget "
                f"{cmax} (tools/analysis_baseline.json hlo_budgets)",
                scope=prog))
        rmax = bud.get("recompile_max")
        if rmax is not None and r["recompiles"] > rmax:
            findings.append(Finding(
                "hlo-recompile-budget", "P1", file, 0,
                f"{prog}: {r['recompiles']} compiled executables for "
                f"one input shape, budget {rmax}", scope=prog))
    return findings


def amp_wire_findings(timeout=600):
    """PR-4 invariant: the bf16 gradient all-reduce moves EXACTLY half
    the wire bytes of fp32's. Two ``mxnet_tpu.amp --hlo-check``
    subprocesses (each owns its backend: the post-SPMD dump flags are
    read once at init)."""
    recs = {}
    for dt in ("float32", "bfloat16"):
        proc = _sub(["mxnet_tpu.amp", "--hlo-check", "--dtype", dt],
                    timeout)
        recs[dt] = parse_last_metric(proc.stdout, "amp_hlo_check")
        recs[dt].setdefault("_stderr", (proc.stderr or "")[-300:])
    f32, b16 = recs["float32"], recs["bfloat16"]
    if not f32.get("ok") or not b16.get("ok"):
        bad = {d: r for d, r in recs.items() if not r.get("ok")}
        return [Finding(
            "hlo-amp-width", "P1", "amp/__init__.py", 0,
            f"amp --hlo-check failed: {bad}", scope="amp_wire")]
    fb = f32["grad_allreduce_bytes_per_step"]
    bb = b16["grad_allreduce_bytes_per_step"]
    if bb * 2 != fb:
        return [Finding(
            "hlo-amp-width", "P1", "amp/__init__.py", 0,
            f"bf16 grad all-reduce moves {bb} wire bytes/step, want "
            f"exactly half of fp32's {fb} — amp is not halving the "
            f"collective", scope="amp_wire")]
    return []


def run(baseline=None, amp_wire=True, timeout=900):
    """The full auditor: program matrix + (optionally) the amp wire
    invariant. Returns findings; [] is a clean bill."""
    findings = audit_findings(baseline, timeout=timeout)
    if amp_wire:
        findings += amp_wire_findings(timeout=timeout)
    return findings


def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser(
        prog="python -m mxnet_tpu.analysis.hloaudit")
    ap.add_argument("--audit-programs", action="store_true",
                    help="subprocess body: compile the program matrix "
                         "and print the hlo_audit JSON line")
    args = ap.parse_args(argv)
    if args.audit_programs:
        return _audit_programs()
    from . import load_baseline
    findings = run(load_baseline())
    for f in findings:
        print(f)
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
