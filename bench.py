"""Benchmark: ResNet-50 ImageNet-shape training throughput on the chip.

Prints ONE JSON line {"metric", "value", "unit", "vs_baseline", ...}.

Flagship config (BASELINE.md): ResNet-50, 224x224, training step =
fwd + bwd + SGD-momentum update fused into one XLA program over a 1-chip
mesh (mxnet_tpu.parallel.DataParallelTrainer — the same engine Module uses
for multi-context training).

Baselines (all published in the reference repo,
example/image-classification/README.md):
  - K80 ResNet-50 *inference* batch 32: 109 img/s  (:154)
  - K80 ResNet-152 *train* per GPU:     20.08 img/s (:311)
vs_baseline is train-throughput / 109 — our TRAINING img/s against the
reference chip's INFERENCE img/s on the same model, i.e. a conservative
lower bound (training is ~3x the FLOPs of inference). The exact
inference-vs-inference ratio is reported as `inference_vs_baseline`.

MFU accounting: model FLOPs are read from XLA's own cost analysis of the
compiled step executable, via telemetry.devstats.extract — the framework's
single home of executable introspection, which also hands each lane its
plan-memory columns (peak / argument / accessed bytes, `plan_memory` in
the summary and on the lane lines) — NOT a hand-maintained constant. ResNet-50 fwd is 4.09 GMACs = 8.18 GFLOPs/img
(2 FLOPs per MAC); a full training step measures ~23.8 GFLOP/img (fwd +
grad-weights + grad-activations; the data tensor gets no gradient). The
peak denominator is the telemetry.devstats.PEAKS row of the device the run
is on (keyed by device_kind); a CPU run has no row and reports no MFU.

Platform: the run needs the chip and fails without one. BENCH_PLATFORM=cpu
asks for the CPU by name (cpu-sized profile, counts and CPU rates only).
Every child process a lane starts is held to the CPU and says so in its
record: the parent holds the chip, and a chip belongs to one process.
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

TRAIN_BATCH = 128
INFER_BATCH = 32
TRAIN_IMG = 224

# -- run budget --------------------------------------------------------------
# BENCH_BUDGET_S bounds the whole run; secondary lanes are shed (reported
# "skipped: budget") once the remaining budget can't cover them, so the
# canonical invocation always exits cleanly WITH its JSON line instead of
# being killed mid-lane. --quick additionally trims iteration counts for a
# fast sanity pass. The flagship lanes always run.
BENCH_BUDGET_S = float(os.environ.get("BENCH_BUDGET_S", "780"))
QUICK = False                  # set by main() from --quick
# A run that asks for the cpu (BENCH_PLATFORM=cpu) drops to a cpu-sized
# profile (batch 8, 32x32 images, 8-step windows): the flagship ResNet-50
# b128 step takes seconds per step there. The six chip-sized lanes are
# skipped outright with the reason in the summary — the harness still
# exercises every lane path that is meaningful off-chip. BENCH_CPU_SCALE=0
# restores chip sizing on cpu (debug only).
CPU_SCALE = False              # set by main() when the run pins cpu
_T_START = time.monotonic()


class _BudgetExceeded(RuntimeError):
    """A secondary lane was shed to keep the run inside BENCH_BUDGET_S."""


class _ChipOnly(RuntimeError):
    """Lane sized for the chip — skipped when the run is cpu-pinned."""


SKIP_CPU = "skipped: cpu-scale (chip-sized lane)"


def _budget_left():
    return BENCH_BUDGET_S - (time.monotonic() - _T_START)


def _emit(lane, payload):
    """Stream one JSON line the moment a lane completes (flushed), so a
    driver that kills the run mid-lane (rc=124)
    still finds every finished lane's numbers on stdout. The final
    summary line (keyed "metric") is unchanged and still last."""
    rec = {"lane": lane}
    rec.update(payload)
    print(json.dumps(rec), flush=True)


def _heartbeat(name, event, **extra):
    """Flushed per-lane liveness line ({"lane": name, "event":
    "lane_start"/"lane_end", ...}): a future rc=124 names its last-live
    lane on stdout, and the telemetry watchdog's last-beat label matches
    (the deadline stack dump armed in main() covers the rest)."""
    _emit(name, {"event": event,
                 "elapsed_s": round(time.monotonic() - _T_START, 1),
                 **extra})
    try:
        from mxnet_tpu.telemetry import watchdog
        watchdog.beat(f"bench:{name}")
    except Exception:
        pass


_REPO = os.path.dirname(os.path.abspath(__file__))
# the one compile-cache directory of a run that finds no
# JAX_COMPILATION_CACHE_DIR (fixed: the path is part of the cache's key)
CACHE_DIR = os.path.join(_REPO, ".jax_cache")


def _fresh_dir(name):
    """Empty scratch directory at a fixed path inside the checkout."""
    import shutil
    path = os.path.join(_REPO, ".bench_scratch", name)
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    return path


def _platform():
    """The platform this run is for: the chip, unless BENCH_PLATFORM=cpu
    asks for the CPU by name (two host devices, so the multi-device lanes
    get a real mesh). A run that then finds another platform fails — a
    benchmark never falls back to the CPU on its own."""
    plat = os.environ.get("BENCH_PLATFORM", "tpu").strip().lower()
    if plat not in ("tpu", "cpu"):
        raise SystemExit(f"bench: BENCH_PLATFORM must be tpu or cpu, "
                         f"got {plat!r}")
    if plat == "cpu":
        from mxnet_tpu.config import pin_cpu
        pin_cpu(2)
    import jax
    found = jax.devices()[0].platform
    if found != plat:
        raise SystemExit(
            f"bench: this run is for {plat!r} but jax's default backend "
            f"is {found!r} (BENCH_PLATFORM=cpu asks for the CPU by name)")
    return plat


def _cpu_child(args, timeout=420, drop_env=()):
    """Run `python -m <args>` held to the CPU and return the finished
    process. The parent holds the chip and a chip belongs to one
    process, so no child may need it; whatever a child times is a CPU
    time, and _child_record labels it so."""
    import subprocess
    import sys
    env = os.environ.copy()
    for k in ("XLA_FLAGS", "JAX_NUM_CPU_DEVICES") + tuple(drop_env):
        env.pop(k, None)
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "-m"] + list(args), capture_output=True,
        text=True, timeout=timeout, env=env, cwd=_REPO)


def _child_record(proc, lane, metric):
    """The child's last JSON line whose "metric" is `metric`, labelled
    "platform": "cpu"; an error with the end of its stderr if none."""
    from mxnet_tpu.analysis.hloaudit import parse_last_metric
    rec = parse_last_metric(proc.stdout, metric)
    if not rec:
        raise RuntimeError(
            f"{lane} bench subprocess rc={proc.returncode}: "
            f"{(proc.stderr or '').strip()[-300:]}")
    rec.pop("metric")
    rec["platform"] = "cpu"
    return rec


def _mfu(rate_per_unit, flops_per_unit):
    """Model-FLOPs utilization against the devstats.PEAKS row of the
    device in use; None where there is no row (the CPU) or no FLOPs."""
    from mxnet_tpu.telemetry import devstats
    if not flops_per_unit:
        return None
    frac = devstats.mfu(rate_per_unit * flops_per_unit)
    return None if frac is None else round(frac, 4)


def _median(rates):
    return sorted(rates)[len(rates) // 2]
RN50_FWD_FLOPS_PER_IMG = 8.18e9   # fallback only: 2 FLOPs x 4.09 GMACs
TRAIN_FLOPS_PER_IMG = 2.9 * RN50_FWD_FLOPS_PER_IMG  # fallback only

K80_RN50_INFER_B32 = 109.0        # README.md:154
K80_RN152_TRAIN = 20.08           # README.md:311


def _resnet50_symbol():
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import vision
    net = vision.resnet50_v1()
    data = mx.sym.Variable("data")
    return mx.sym.SoftmaxOutput(net(data), name="softmax")


def _resnet152_symbol():
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import vision
    net = vision.resnet152_v1()
    data = mx.sym.Variable("data")
    return mx.sym.SoftmaxOutput(net(data), name="softmax")


def _train_ips_quick(sym, mesh, dtype, batch, steps=10):
    """Secondary-lane throughput (resnet-152): median-of-3 windows with
    the step executable's model FLOPs from XLA cost analysis, so every
    reported rate carries MFU context. Returns (img/s, flops/image)."""
    from mxnet_tpu.parallel import DataParallelTrainer
    trainer = DataParallelTrainer(sym, mesh, optimizer="sgd",
                                  learning_rate=0.05, momentum=0.9,
                                  rescale_grad=1.0 / batch, dtype=dtype)
    params, states, aux = trainer.init_state(
        {"data": (batch, 3, 224, 224), "softmax_label": (batch,)})
    rng = np.random.RandomState(0)
    x = rng.uniform(0, 1, size=(batch, 3, 224, 224)).astype(np.float32)
    y = rng.randint(0, 1000, size=(batch,)).astype(np.float32)
    inputs = trainer.shard_inputs([x, y])
    for _ in range(2):
        params, states, aux, loss, _ = trainer.step(params, states, aux,
                                                    inputs)
    float(loss)
    flops = _cost_flops(trainer._step, params, states, aux, inputs,
                        trainer._rng_dev, trainer._lr_dev, trainer._t_dev,
                        lane="train_resnet152")
    if QUICK:
        steps = min(steps, 3)
    rates = []
    for _ in range(1 if QUICK else 3):
        t0 = time.perf_counter()
        for _ in range(steps):
            params, states, aux, loss, _ = trainer.step(params, states,
                                                        aux, inputs)
        float(loss)
        rates.append(steps * batch / (time.perf_counter() - t0))
    return _median(rates), flops / batch if flops else None  # per img


def _lstm_tokens_per_sec(mesh, batch=32, seq=64, hidden=512, vocab=10000,
                         layers=2, k=16, unroll=2):
    """LSTM LM training throughput (BASELINE config 4 role: bucketing
    LSTM): fused RNN symbol, full fwd+bwd+update, steps_per_dispatch=16
    via step_k (unroll=2). Returns (tokens/sec median-of-3, flops/token
    from XLA cost analysis, single-dispatch tokens/sec).

    This lane is why the multi-step driver exists: the step is short, so
    the fixed host cost of each python dispatch shows; K=16 fused steps
    pay it once, and unroll=2 halves the outer-scan loop overhead XLA
    adds around the RNN's inner while loops. The single-dispatch rate is
    reported alongside so the dispatch cost stays visible."""
    import mxnet_tpu as mx
    from mxnet_tpu.parallel import DataParallelTrainer
    data = mx.sym.Variable("data")
    emb = mx.sym.Embedding(data, input_dim=vocab, output_dim=hidden,
                           name="emb")
    emb_t = mx.sym.transpose(emb, axes=(1, 0, 2))  # TNC for fused RNN
    # initial states enter BATCH-major (batch, layers, hidden) so the
    # data-parallel axis-0 sharding of shard_inputs splits the batch, not
    # the layers axis; transposed to the RNN op's (layers, batch, hidden)
    state_bf = mx.sym.Variable("state")
    cell_bf = mx.sym.Variable("state_cell")
    rnn = mx.sym.RNN(emb_t, mx.sym.Variable("rnn_params"),
                     mx.sym.transpose(state_bf, axes=(1, 0, 2)),
                     mx.sym.transpose(cell_bf, axes=(1, 0, 2)),
                     state_size=hidden, num_layers=layers, mode="lstm",
                     name="lstm")
    out = mx.sym.transpose(rnn, axes=(1, 0, 2))
    logits = mx.sym.FullyConnected(mx.sym.reshape(out, shape=(-1, hidden)),
                                   num_hidden=vocab, name="dec")
    sym = mx.sym.SoftmaxOutput(logits, name="softmax", multi_output=False)

    trainer = DataParallelTrainer(
        sym, mesh, data_names=("data", "state", "state_cell"),
        label_names=("softmax_label",), optimizer="sgd", learning_rate=0.1,
        rescale_grad=1.0 / (batch * seq), dtype="bfloat16")
    rng = np.random.RandomState(0)
    shapes = {"data": (batch, seq), "state": (batch, layers, hidden),
              "state_cell": (batch, layers, hidden),
              "softmax_label": (batch * seq,)}
    params, states, aux = trainer.init_state(shapes)
    x = rng.randint(0, vocab, (batch, seq)).astype(np.float32)
    h0 = np.zeros((batch, layers, hidden), np.float32)
    y = rng.randint(0, vocab, (batch * seq,)).astype(np.float32)
    inputs = trainer.shard_inputs([x, h0, h0.copy(), y])
    xs = rng.randint(0, vocab, (k, batch, seq)).astype(np.float32)
    h0s = np.zeros((k, batch, layers, hidden), np.float32)
    ys = rng.randint(0, vocab, (k, batch * seq)).astype(np.float32)
    inputs_k = trainer.shard_inputs([xs, h0s, h0s.copy(), ys], stacked=True)
    # compile + warm both paths
    params, states, aux, losses, _ = trainer.step_k(params, states, aux,
                                                    inputs_k, unroll=unroll)
    float(np.asarray(losses)[-1])
    for _ in range(2):
        params, states, aux, loss, _ = trainer.step(params, states, aux,
                                                    inputs)
    float(loss)
    flops = _cost_flops(trainer._step, params, states, aux, inputs,
                        trainer._rng_dev, trainer._lr_dev, trainer._t_dev,
                        lane="lstm_lm")
    n_disp, rates = 64 // k, []
    n_single = 3 if QUICK else 10
    for _ in range(1 if QUICK else 3):
        t0 = time.perf_counter()
        for _ in range(n_disp):
            params, states, aux, losses, _ = trainer.step_k(
                params, states, aux, inputs_k, unroll=unroll)
        float(np.asarray(losses)[-1])
        rates.append(n_disp * k * batch * seq / (time.perf_counter() - t0))
    # single-dispatch comparison
    t0 = time.perf_counter()
    for _ in range(n_single):
        params, states, aux, loss, _ = trainer.step(params, states, aux,
                                                    inputs)
    float(loss)
    single_tps = n_single * batch * seq / (time.perf_counter() - t0)
    return _median(rates), \
        flops / (batch * seq) if flops else None, single_tps   # per token


PLAN_MEM = {}        # lane -> plan-memory columns (devstats extraction)
LANE_TIMES = {}      # lane -> {est_s, actual_s, err_s} (budget accounting)


def _plan_stats(lane, jitted, *args):
    """XLA cost/memory analytics of a compiled lane executable via
    telemetry.devstats.extract (the single home of executable
    introspection). Side effect: PLAN_MEM[lane] gets the lane's
    plan-memory columns (peak / argument / accessed bytes) for the lane
    line and the summary. Returns model FLOPs, or None if the backend
    doesn't support cost analysis."""
    try:
        from mxnet_tpu.telemetry import devstats
        stats = devstats.extract(jitted.lower(*args).compile())
        PLAN_MEM[lane] = {
            "plan_peak_bytes": int(stats["peak_bytes"]),
            "plan_argument_bytes": int(stats["argument_bytes"]),
            "plan_bytes_accessed": int(stats["bytes_accessed"]),
        }
        return float(stats["flops"]) or None
    except Exception:
        return None


def _cost_flops(jitted, *args, lane=None):
    """Model FLOPs of a compiled executable, from XLA's cost analysis.
    Returns None if the backend doesn't support it."""
    return _plan_stats(lane or "unnamed", jitted, *args)


def _train_ips(sym, mesh, dtype, want_flops=False, k=4):
    """Flagship train lane: steps_per_dispatch=k via step_k (K fused
    steps per jitted lax.scan dispatch), timed over 80-step windows: a
    window ends in one host fetch of the loss, a fixed cost that a long
    window makes small against the steps it times (how large it is on
    this runtime is not measured). Median of 3 windows. The
    single-dispatch path is reported alongside as `single_step_ips`."""
    from mxnet_tpu.parallel import DataParallelTrainer
    trainer = DataParallelTrainer(sym, mesh, optimizer="sgd",
                                  learning_rate=0.05, momentum=0.9,
                                  rescale_grad=1.0 / TRAIN_BATCH, dtype=dtype)
    params, states, aux = trainer.init_state(
        {"data": (TRAIN_BATCH, 3, TRAIN_IMG, TRAIN_IMG),
         "softmax_label": (TRAIN_BATCH,)})
    rng = np.random.RandomState(0)
    x = rng.uniform(0, 1, size=(TRAIN_BATCH, 3, TRAIN_IMG, TRAIN_IMG)) \
        .astype(np.float32)
    y = rng.randint(0, 1000, size=(TRAIN_BATCH,)).astype(np.float32)
    xs = rng.uniform(0, 1, size=(k, TRAIN_BATCH, 3, TRAIN_IMG, TRAIN_IMG)) \
        .astype(np.float32)
    ys = rng.randint(0, 1000, size=(k, TRAIN_BATCH)).astype(np.float32)
    inputs_k = trainer.shard_inputs([xs, ys], stacked=True)
    inputs1 = trainer.shard_inputs([x, y])
    # compile + warmup (the single-step path only where it gets used:
    # flops source + the comparison lane of the flagship call)
    params, states, aux, loss, _ = trainer.step_k(params, states, aux,
                                                  inputs_k)
    float(np.asarray(loss)[-1])
    if want_flops:
        for _ in range(2):
            params, states, aux, loss1, _ = trainer.step(params, states,
                                                         aux, inputs1)
        float(loss1)
    step_flops = None
    if want_flops:
        # from the SINGLE-step executable: XLA's cost analysis counts a
        # scan body once (not x trip count), so the K-step program would
        # under-report by K
        step_flops = _cost_flops(trainer._step, params, states, aux,
                                 inputs1, trainer._rng_dev,
                                 trainer._lr_dev, trainer._t_dev,
                                 lane="train_resnet50")
    # median of 3 trials: resists a single slow window without the upward
    # bias of best-of
    n_steps = 16 if QUICK else (8 if CPU_SCALE else 80)
    n_disp, rates = n_steps // k, []
    for _ in range(1 if QUICK else 3):
        t0 = time.perf_counter()
        for _ in range(n_disp):
            params, states, aux, loss, _ = trainer.step_k(
                params, states, aux, inputs_k)
        float(np.asarray(loss)[-1])  # block on the chain
        rates.append(n_disp * k * TRAIN_BATCH / (time.perf_counter() - t0))
    # single-dispatch comparison lane (one 80-step window) — flagship
    # (want_flops) call only; the fp32 fill lane skips it
    single_ips = None
    if want_flops:
        t0 = time.perf_counter()
        for _ in range(n_steps):
            params, states, aux, loss1, _ = trainer.step(params, states,
                                                         aux, inputs1)
        float(loss1)
        single_ips = n_steps * TRAIN_BATCH / (time.perf_counter() - t0)
    return (_median(rates), step_flops, trainer, params, aux, x, y,
            single_ips)


def _infer_ips(run, argv, aux, key, want_flops=False):
    """Median-of-3 timed inference loops over a prebuilt jitted runner."""
    import jax
    infer = jax.jit(lambda a, s, r: run(a, s, r)[0][0])
    jax.block_until_ready(infer(argv, aux, key))
    # cost_analysis pays a second AOT compile — only when asked for
    flops = _cost_flops(infer, argv, aux, key,
                        lane="inference_resnet50") if want_flops else None
    n_inf, inf_rates = (10 if (QUICK or CPU_SCALE) else 50), []
    for _ in range(1 if QUICK else 3):
        t0 = time.perf_counter()
        out = None
        for _ in range(n_inf):
            out = infer(argv, aux, key)
        jax.block_until_ready(out)
        inf_rates.append(n_inf * INFER_BATCH / (time.perf_counter() - t0))
    return _median(inf_rates), flops


def _flash_attention_tokens_per_sec(batch=8, heads=8, seq=4096, dim=128):
    """Long-context lane: attention train-direction throughput at seq 4096
    — Pallas flash FORWARD + Pallas recompute-based flash BACKWARD
    (ops/attention.py _flash_pallas_bwd; O(S) activation memory, the
    (S, S) score matrix never exists in either direction). Returns
    (tokens/sec median-of-3, flops/token): XLA's cost analysis cannot
    see inside pallas_call, so flops are the closed-form causal
    attention model count (see inline note)."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.attention import flash_attention

    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    shape = (batch, heads, seq, dim)
    q = jax.random.normal(kq, shape, jnp.bfloat16)
    k = jax.random.normal(kk, shape, jnp.bfloat16)
    v = jax.random.normal(kv, shape, jnp.bfloat16)

    @jax.jit
    def step(q, k, v):
        def loss(q, k, v):
            out = flash_attention(q, k, v, causal=True)
            return jnp.sum(out.astype(jnp.float32) ** 2)
        l, grads = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
        return l, grads

    l, _ = step(q, k, v)
    float(l)
    rates, n_steps = [], (3 if QUICK else 10)
    for _ in range(1 if QUICK else 3):
        t0 = time.perf_counter()
        out = None
        for _ in range(n_steps):
            out = step(q, k, v)
        float(out[0])
        rates.append(n_steps * batch * seq / (time.perf_counter() - t0))
    # MODEL flops (MFU convention: algorithmic work, recompute excluded):
    # 6 S^2xD matmuls — fwd QK^T + PV; bwd dV + dP + dQ + dK (the count
    # a dense backward with stored P would execute) — at 2 FLOPs/MAC;
    # causal halves them. The flash kernels actually execute 3 more
    # (S recomputed in both passes, dP twice), which MFU does not credit.
    flops = 6 * 2 * batch * heads * seq * seq * dim / 2
    return _median(rates), flops / (batch * seq)   # per token


def _quantized_serving_lane():
    """End-to-end quantized serving A/B (ISSUE 18): the same MLP
    exported twice — bf16 weights vs int8 weight-only calibration baked
    into the `.mxa` manifest — both served through ServingEngine, so
    the measured delta includes the whole path the artifact actually
    runs (container load, scale-companion params, fused dequant
    matmul). Replaces the parked XLA-conv int8 lane (docs/int8_r04.md,
    a round-4 record): weight-only serving is the int8 shape this
    codebase ships, and it runs on every backend, so the lane is no
    longer chip-gated."""
    import mxnet_tpu as mx
    from mxnet_tpu.contrib.export import export_model
    from mxnet_tpu.serving import ServingEngine

    rng = np.random.RandomState(0)
    d_in, d_h, d_out, batch = 256, 1024, 256, 32
    net = mx.sym.FullyConnected(mx.sym.Variable("data"),
                                num_hidden=d_h, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=d_h, name="fc2")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=d_out, name="fc3")
    sym = mx.sym.SoftmaxOutput(net, name="softmax")
    shapes = {"data": (batch, d_in), "softmax_label": (batch,)}
    arg_shapes, _, _ = sym.infer_shape(**shapes)
    args = {n: mx.nd.array(rng.normal(0, 0.05, s).astype(np.float32))
            for n, s in zip(sym.list_arguments(), arg_shapes)
            if n not in ("data", "softmax_label")}
    x = rng.uniform(-1, 1, (batch, d_in)).astype(np.float32)

    def _serve_ips(path):
        eng = ServingEngine(path, buckets=(batch,))
        try:
            out = np.asarray(eng.infer(x))
            iters = 20 if QUICK else 60
            rates = []
            for _ in range(3):
                t0 = time.perf_counter()
                for _ in range(iters):
                    last = eng.infer(x)
                np.asarray(last)        # host-fetch barrier
                rates.append(iters * batch
                             / (time.perf_counter() - t0))
            return _median(rates), out
        finally:
            eng.close() if hasattr(eng, "close") else None

    res = {"batch": batch}
    td = _fresh_dir("int8_serving")
    p16 = os.path.join(td, "mlp_bf16.mxa")
    p8 = os.path.join(td, "mlp_int8.mxa")
    export_model(p16, sym, args, {}, {"data": (batch, d_in)},
                 dtype="bfloat16")
    export_model(p8, sym, args, {}, {"data": (batch, d_in)},
                 dtype="bfloat16", quantize="int8")
    import zipfile
    with zipfile.ZipFile(p8) as z:
        quant = json.loads(
            z.read("MANIFEST.json")).get("quant") or {}
    bf16_ips, out16 = _serve_ips(p16)
    int8_ips, out8 = _serve_ips(p8)
    res.update({
        "bf16_ips": round(bf16_ips, 1),
        "int8_ips": round(int8_ips, 1),
        "int8_vs_bf16": round(int8_ips / bf16_ips, 3),
        # softmax outputs: the quantization error the artifact ships
        "max_abs_err": float(np.abs(out16 - out8).max()),
        "quantized_params": len(quant.get("params", []))})
    return res


def _decode_lane():
    """Continuous-batching decode (ISSUE 18): one DecodeEngine, its ONE
    compiled step plan advancing whatever sessions are live — measured
    at 1/8/32 concurrent sessions. Reports aggregate tokens/s, p50/p99
    per-token latency seen by a session (submit→done wall over tokens
    emitted: queueing + prefill + its share of every packed step), and
    the KV-pool occupancy the wave actually reached."""
    from mxnet_tpu.serving.decode import DecodeEngine, DecodeModel

    rng = np.random.RandomState(7)
    model = DecodeModel(vocab=256, layers=2, d_model=128, heads=4,
                        kv_heads=2, d_ff=256, max_len=128)
    params = model.init_params(seed=0)
    eng = DecodeEngine(model, params, num_slots=32,
                       name="bench-decode", warmup=True)
    new_tokens = 16 if QUICK else 32
    res = {"num_slots": eng.num_slots, "max_len": eng.max_len,
           "new_tokens": new_tokens, "levels": {}}
    try:
        # warm BOTH prefill buckets the prompt lengths below hit (8 and
        # 16), so no level pays a first-compile mid-wave
        eng.generate(list(rng.randint(1, 256, 8)), max_new_tokens=2)
        eng.generate(list(rng.randint(1, 256, 12)), max_new_tokens=2)
        for conc in (1, 8, 32):
            prompts = [list(map(int, rng.randint(1, 256,
                                                 8 + (i % 5))))
                       for i in range(conc)]
            t0 = time.perf_counter()
            sess = [eng.submit(p, max_new_tokens=new_tokens)
                    for p in prompts]
            # peak occupancy while the wave is in flight: how full the
            # continuous batch actually ran
            occ = 0
            while not all(s.future.done() for s in sess):
                occ = max(occ, eng.pool.occupancy())
                time.sleep(0.001)
            outs = [s.result() for s in sess]
            wall = time.perf_counter() - t0
            per_tok = sorted((s.t_done - s.t_submit) / len(o)
                             for s, o in zip(sess, outs))
            n_tok = sum(len(o) for o in outs)
            res["levels"][str(conc)] = {
                "tokens_per_s": round(n_tok / wall, 1),
                "per_token_p50_ms": round(
                    per_tok[len(per_tok) // 2] * 1e3, 3),
                "per_token_p99_ms": round(
                    per_tok[min(len(per_tok) - 1,
                                int(len(per_tok) * 0.99))] * 1e3, 3),
                "kv_occupancy": occ}
        s1 = res["levels"]["1"]["tokens_per_s"]
        s32 = res["levels"]["32"]["tokens_per_s"]
        res["batching_speedup_32v1"] = round(s32 / s1, 2)
        res["step_executions"] = eng.step_executions
        res["plan_compiles"] = eng.plan_compiles
        res["kv_cache_bytes"] = eng.cache_bytes
    finally:
        eng.close(drain=False)
    return res


SYNTH_REC = os.path.join(_REPO, ".bench_scratch", "synth_imagenet.rec")


def _build_synth_rec(n=2560, size=256, seed=0):
    """Synthetic ImageNet-shaped recordio (256x256 JPEGs, 1000-class
    labels), built once and cached (role of the reference's im2rec'd
    val set for its e2e iterator benchmarks, tools/im2rec.py)."""
    import cv2
    from mxnet_tpu import recordio
    if os.path.exists(SYNTH_REC):
        return SYNTH_REC
    rng = np.random.RandomState(seed)
    # build to a temp path + atomic rename: an interrupted build must not
    # leave a truncated file that later runs silently treat as the cache
    os.makedirs(os.path.dirname(SYNTH_REC), exist_ok=True)
    tmp = SYNTH_REC + ".build"
    rec = recordio.MXRecordIO(tmp, "w")
    for i in range(n):
        # low-freq content + light noise: realistic JPEG size/decode cost
        base = rng.randint(0, 255, (8, 8, 3), np.uint8)
        img = cv2.resize(base, (size, size),
                         interpolation=cv2.INTER_CUBIC)
        img = np.clip(img.astype(np.int16)
                      + rng.randint(-10, 10, img.shape),
                      0, 255).astype(np.uint8)
        ok, buf = cv2.imencode(".jpg", img,
                               [cv2.IMWRITE_JPEG_QUALITY, 90])
        assert ok
        hdr = recordio.IRHeader(0, float(rng.randint(0, 1000)), i, 0)
        rec.write(recordio.pack(hdr, buf.tobytes()))
    rec.close()
    os.replace(tmp, SYNTH_REC)
    return SYNTH_REC


def _e2e_data_lane(sym, mesh, steps=None):
    if steps is None:
        steps = 5 if QUICK else 20
    """End-to-end train lane: ResNet-50 fed by ImageRecordIter (native
    JPEG decode + rand_crop/mirror + in-engine prefetch) instead of
    device-resident arrays. Uses the TPU-native input regime — uint8
    payloads (4x less host->device traffic) normalized INSIDE the
    compiled step (input_preproc). Returns (e2e img/s, standalone
    pipeline img/s).

    e2e converges to min(pipeline, synthetic-step) by construction
    (decode threads + async device_put overlap the device step)."""
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu.parallel import DataParallelTrainer
    from mxnet_tpu.image.image import (IMAGENET_DEFAULT_MEAN,
                                       IMAGENET_DEFAULT_STD)
    rec = _build_synth_rec()
    it = mx.io.ImageRecordIter(
        path_imgrec=rec, data_shape=(3, 224, 224),
        batch_size=TRAIN_BATCH, shuffle=True, rand_crop=True,
        rand_mirror=True, preprocess_threads=4, prefetch_buffer=3,
        output_dtype="uint8")

    def get():
        while True:
            try:
                return it.next()
            except StopIteration:
                it.reset()

    # standalone pipeline throughput (host-side only)
    for _ in range(3):
        get()
    t0 = time.perf_counter()
    for _ in range(steps):
        get()
    pipe_ips = steps * TRAIN_BATCH / (time.perf_counter() - t0)

    mean = np.asarray(IMAGENET_DEFAULT_MEAN, np.float32) \
        .reshape(1, 3, 1, 1)
    stdinv = (1.0 / np.asarray(IMAGENET_DEFAULT_STD, np.float32)) \
        .reshape(1, 3, 1, 1)

    def preproc(name, v):
        if name == "data":
            return (v.astype(jnp.float32) - mean) * stdinv
        return v

    trainer = DataParallelTrainer(
        sym, mesh, optimizer="sgd", learning_rate=0.05, momentum=0.9,
        rescale_grad=1.0 / TRAIN_BATCH, dtype="bfloat16",
        input_preproc=preproc)
    params, states, aux = trainer.init_state(
        {"data": (TRAIN_BATCH, 3, 224, 224),
         "softmax_label": (TRAIN_BATCH,)})
    for _ in range(3):
        b = get()
        inputs = trainer.shard_inputs([b.data[0], b.label[0]])
        params, states, aux, loss, _ = trainer.step(params, states, aux,
                                                    inputs)
    float(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        b = get()
        inputs = trainer.shard_inputs([b.data[0], b.label[0]])
        params, states, aux, loss, _ = trainer.step(params, states, aux,
                                                    inputs)
    float(loss)
    e2e_ips = steps * TRAIN_BATCH / (time.perf_counter() - t0)
    if hasattr(it, "close"):
        it.close()   # join the native decode workers before later lanes
    return e2e_ips, pipe_ips


ACC_TARGET = 0.97


def _accuracy_lane():
    """End-to-end convergence on the chip: LeNet on sklearn's bundled
    handwritten digits (the zero-egress stand-in for the reference's MNIST
    trainer-integration tier, tests/python/train/test_conv.py; same models
    asserted >0.97 in tests/test_train_accuracy.py on CPU). Returns the
    held-out accuracy actually reached on the TPU.

    The lane is SEEDED: unseeded, np.random state inherited from whatever
    ran before in bench.py decided the Xavier draws and shuffle order, and
    an unlucky draw lands below the bar. It runs two extra epochs of
    margin, and ASSERTS the target instead of just reporting (a silent
    sub-bar number is a regression, not a result)."""
    import mxnet_tpu as mx
    from sklearn.datasets import load_digits
    np.random.seed(0)
    mx.random.seed(0)
    d = load_digits()
    x = (d.data.astype(np.float32) / 16.0)
    y = d.target.astype(np.float32)
    rng = np.random.RandomState(7)
    idx = rng.permutation(len(y))
    x, y = x[idx], y[idx]
    img = np.kron(x.reshape(-1, 8, 8),
                  np.ones((1, 4, 4), np.float32))[:, None]
    xt, yt, xv, yv = img[:1437], y[:1437], img[1437:], y[1437:]

    data = mx.sym.Variable("data")
    net = mx.sym.Convolution(data, kernel=(5, 5), num_filter=20, name="c1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.Pooling(net, pool_type="max", kernel=(2, 2), stride=(2, 2))
    net = mx.sym.Convolution(net, kernel=(5, 5), num_filter=50, name="c2")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.Pooling(net, pool_type="max", kernel=(2, 2), stride=(2, 2))
    net = mx.sym.FullyConnected(mx.sym.Flatten(net), num_hidden=256,
                                name="f1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=10, name="f2")
    sym = mx.sym.SoftmaxOutput(net, name="softmax")

    it = mx.io.NDArrayIter(xt, yt, batch_size=64, shuffle=True,
                           label_name="softmax_label")
    vit = mx.io.NDArrayIter(xv, yv, batch_size=64,
                            label_name="softmax_label")
    mod = mx.mod.Module(sym, context=mx.tpu(0))
    mod.fit(it, num_epoch=14, optimizer="sgd",
            optimizer_params={"learning_rate": 0.05, "momentum": 0.9},
            initializer=mx.init.Xavier())
    vit.reset()
    acc = float(dict(mod.score(vit, mx.metric.Accuracy()))["accuracy"])
    if acc < ACC_TARGET:
        raise AssertionError(
            f"accuracy lane FAILED: {acc:.4f} < {ACC_TARGET} "
            "(seeded config; see _accuracy_lane docstring)")
    return acc


def _pipeline_lane():
    """Async device-feed A/B (mxnet_tpu.pipeline): the same gluon
    fused_fit run twice over a deliberately host-bound data source —
    each batch costs ~one device-step of host-side wait (I/O stand-in:
    time.sleep, which yields the core like the decode/read stalls the
    feed exists to hide) — with MXNET_DEVICE_FEED on vs off.

    fused_fit is the consumer loop with an honest per-block sync point
    (it reads the K-step loss on the host every dispatch), so the sync
    arm pays host + device serially; Module.fit's per-batch loop hides
    most host time behind async dispatch already and would understate
    the feed. Epoch 0 pays the XLA compile in both arms, so steps/s is
    measured over epochs 1..N. Reports both rates, the ratio
    (acceptance: >= 1.15x), and the feed's overlap_frac counter for the
    on-arm."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn
    from mxnet_tpu import pipeline as pl

    batches, batch, dim, k = (12 if (QUICK or CPU_SCALE) else 24), 128, 1024, 4
    epochs = 3
    rng = np.random.RandomState(0)
    xs = rng.uniform(-1, 1, (batches, batch, dim)).astype(np.float32)
    ys = rng.randint(0, 10, (batches, batch)).astype(np.float32)

    class _SlowData:
        """Re-iterable (x, y) source with a fixed host cost per batch."""

        def __init__(self, host_s):
            self.host_s = host_s

        def __iter__(self):
            def gen():
                for i in range(batches):
                    if self.host_s:
                        time.sleep(self.host_s)
                    yield mx.nd.array(xs[i]), mx.nd.array(ys[i])
            return gen()

    def _fit_arm(feed_on, host_s):
        prev = os.environ.get("MXNET_DEVICE_FEED")
        os.environ["MXNET_DEVICE_FEED"] = "1" if feed_on else "0"
        try:
            net = nn.HybridSequential()
            with net.name_scope():
                net.add(nn.Dense(dim, activation="relu"))
                net.add(nn.Dense(dim, activation="relu"))
                net.add(nn.Dense(10))
            net.initialize(mx.init.Xavier())
            loss = gluon.loss.SoftmaxCrossEntropyLoss()
            marks = []
            gluon.trainer.fused_fit(
                net, loss, _SlowData(host_s), num_epoch=epochs,
                optimizer="sgd", optimizer_params={"learning_rate": 0.05},
                steps_per_dispatch=k,
                epoch_callback=lambda *a: marks.append(time.perf_counter()))
            steady_s = marks[-1] - marks[0]     # epochs 1..N (0 compiles)
            return (epochs - 1) * batches / steady_s
        finally:
            if prev is None:
                os.environ.pop("MXNET_DEVICE_FEED", None)
            else:
                os.environ["MXNET_DEVICE_FEED"] = prev

    # calibrate the host cost to ~1 steady device step (measured with a
    # free source, feed off) so the A/B has real work to hide
    step_s = 1.0 / _fit_arm(False, 0.0)
    host_s = max(step_s, 2e-3)
    sync_sps = _fit_arm(False, host_s)
    base = pl.stats()
    feed_sps = _fit_arm(True, host_s)
    delta = pl.stats()
    stage_us = delta["feed_stage_us"] - base["feed_stage_us"]
    wait_us = delta["feed_wait_us"] - base["feed_wait_us"]
    overlap = (max(0.0, 1.0 - wait_us / stage_us) if stage_us else 0.0)
    return {"device_feed_steps_per_sec": round(feed_sps, 2),
            "sync_steps_per_sec": round(sync_sps, 2),
            "speedup": round(feed_sps / sync_sps, 3),
            "overlap_frac": round(overlap, 4),
            "host_cost_ms_per_batch": round(host_s * 1e3, 3),
            "steps_per_dispatch": k}


def _compile_cache_lane():
    """Persistent-compile-cache cold vs warm
    (config.enable_compile_cache): point JAX's disk cache at an emptied
    directory of this lane's own, time bind+first-step cold (compiles,
    writes entries), drop the
    in-process executable caches with jax.clear_caches(), rebuild the
    identical module and time the same first step warm — it deserializes
    from disk instead of recompiling. Reports both times + entry count;
    warm << cold is the acceptance signal."""
    import glob
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu.config import enable_compile_cache

    # a cache placed from outside is not moved by code, and cannot be
    # emptied for a cold reading
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return {"status": "skipped: cache placed by "
                          "JAX_COMPILATION_CACHE_DIR"}
    cache_dir = enable_compile_cache(_fresh_dir("compile_cache_lane"))

    batch, dim = 32, 256
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=dim, name="ccfc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=dim, name="ccfc2")
    sym = mx.sym.SoftmaxOutput(net, name="softmax")
    x = np.zeros((batch, dim), np.float32)
    y = np.zeros((batch,), np.float32)

    def _first_step_s():
        mod = mx.mod.Module(sym, context=mx.tpu(0))
        mod.bind(data_shapes=[("data", (batch, dim))],
                 label_shapes=[("softmax_label", (batch,))])
        mod.init_params(mx.init.Uniform(0.01))
        t0 = time.perf_counter()
        mod.forward(mx.io.DataBatch(data=[mx.nd.array(x)],
                                    label=[mx.nd.array(y)]), is_train=True)
        mod.backward()
        for o in mod.get_outputs():
            o.asnumpy()
        return time.perf_counter() - t0

    try:
        cold_s = _first_step_s()
        jax.clear_caches()          # drop in-process executables only —
        warm_s = _first_step_s()    # disk cache survives and serves this
        entries = len(glob.glob(os.path.join(cache_dir, "*")))
    finally:
        enable_compile_cache(CACHE_DIR)     # back to the run's own cache
    return {"cold_first_step_s": round(cold_s, 3),
            "warm_first_step_s": round(warm_s, 3),
            "warm_over_cold": round(warm_s / cold_s, 3) if cold_s else None,
            "cache_entries": entries,
            "cache_dir": cache_dir}


def _amp_lane():
    """Mixed-precision train A/B (mxnet_tpu.amp, ISSUE 4): the same
    matmul-heavy MLP stepped fp32 vs bf16 on a 2-device data-parallel
    mesh (steps/s, median-of-3), plus the gradient all-reduce wire
    bytes/step for both dtypes read from the post-SPMD-partitioning HLO
    by `python -m mxnet_tpu.amp --hlo-check` in a fresh subprocess —
    the XLA dump flags are consumed once at backend init, and on cpu the
    FINAL optimized HLO re-widens bf16 collectives (backend
    legalization, not a program property; see amp/__main__.py)."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu.analysis.hloaudit import parse_last_metric
    from mxnet_tpu.parallel import DataParallelTrainer, data_parallel_mesh

    n = min(2, len(jax.devices()))
    mesh = data_parallel_mesh(n, jax.devices()[:n])
    batch, dim, hidden = 256, 1024, 2048
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=hidden, name="ampfc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=hidden, name="ampfc2")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=64, name="ampfc3")
    sym = mx.sym.SoftmaxOutput(net, name="softmax")
    rng = np.random.RandomState(0)
    x = rng.uniform(-1, 1, (batch, dim)).astype(np.float32)
    y = rng.randint(0, 64, (batch,)).astype(np.float32)
    steps = 5 if QUICK else (10 if CPU_SCALE else 20)

    def _sps(dtype):
        tr = DataParallelTrainer(sym, mesh, optimizer="sgd",
                                 learning_rate=0.05, momentum=0.9,
                                 rescale_grad=1.0 / batch, dtype=dtype)
        params, states, aux = tr.init_state(
            {"data": (batch, dim), "softmax_label": (batch,)})
        inputs = tr.shard_inputs([x, y])
        for _ in range(2):
            params, states, aux, loss, _ = tr.step(params, states, aux,
                                                   inputs)
        float(loss)
        rates = []
        for _ in range(1 if QUICK else 3):
            t0 = time.perf_counter()
            for _ in range(steps):
                params, states, aux, loss, _ = tr.step(params, states,
                                                       aux, inputs)
            float(loss)
            rates.append(steps / (time.perf_counter() - t0))
        return _median(rates)

    fp32_sps = _sps("float32")
    bf16_sps = _sps("bfloat16")

    def _hlo(dtype):
        return parse_last_metric(_cpu_child(
            ["mxnet_tpu.amp", "--hlo-check", "--dtype", dtype],
            timeout=240).stdout, "amp_hlo_check")

    hlo32, hlo16 = _hlo("float32"), _hlo("bfloat16")
    return {"fp32_steps_per_sec": round(fp32_sps, 2),
            "bf16_steps_per_sec": round(bf16_sps, 2),
            "speedup": round(bf16_sps / fp32_sps, 3),
            "allreduce_bytes_per_step_fp32":
                hlo32.get("grad_allreduce_bytes_per_step"),
            "allreduce_bytes_per_step_bf16":
                hlo16.get("grad_allreduce_bytes_per_step"),
            "hlo_check_ok": bool(hlo16.get("ok")),
            "hlo_check_platform": "cpu",
            "devices": n}


def _zero_lane():
    """ZeRO-sharded dp A/B (mxnet_tpu.parallel.zero, ISSUE 10): dp fp32
    vs ZeRO-1 vs ZeRO-2 vs ZeRO-2+fp8 on an 8-virtual-device cpu mesh —
    steps/s plus per-step collective wire bytes read from each arm's
    post-SPMD HLO dump. Runs `python -m mxnet_tpu.parallel.zero --bench`
    in a fresh subprocess: the 8-device backend and the XLA dump flags
    must be pinned before jax initializes, and this process already
    consumed both."""
    return _child_record(_cpu_child(
        ["mxnet_tpu.parallel.zero", "--bench", "--devices", "8",
         "--steps", "6" if QUICK else "12"]), "zero", "zero_bench")


def _plan_lane():
    """Sharding-planner A/B (mxnet_tpu.parallel.planner, ISSUE 19):
    MXNET_PLAN=auto vs hand-picked dp and zero2 on the transformer-scale
    arm (wide FC stack, small per-device batch, adam — parameter
    gather/reduce wire and de-replicated update work dominate) on an
    8-virtual-device cpu mesh. Reports measured steps/s per arm, the
    planner's decision and its predicted cost ranking. Runs `python -m
    mxnet_tpu.parallel.planner --bench` in a fresh subprocess: the
    8-device backend must be pinned before jax initializes, and this
    process already consumed it."""
    return _child_record(_cpu_child(
        ["mxnet_tpu.parallel.planner", "--bench", "--devices", "8",
         "--steps", "4" if QUICK else "8"]), "plan", "plan_bench")


def _dlrm_lane():
    """Row-sparse embedding exchange A/B (mxnet_tpu.parallel.embedding,
    ISSUE 16): a DLRM-style step — sharded 65k-row table, deduped
    touched-row exchange (plus the fp8-wire arm) vs the dense
    replicated-table all-reduce — on an 8-virtual-device cpu mesh;
    steps/s plus per-step collective wire bytes read from each arm's
    post-SPMD HLO dump. Runs `python -m mxnet_tpu.parallel.embedding
    --bench` in a fresh subprocess: the 8-device backend and the XLA
    dump flags must be pinned before jax initializes, and this process
    already consumed both."""
    return _child_record(_cpu_child(
        ["mxnet_tpu.parallel.embedding", "--bench", "--devices", "8",
         "--steps", "6" if QUICK else "10"]), "dlrm", "embed_bench")


def _dist_recovery_lane():
    """Distributed-runtime recovery (mxnet_tpu.cluster, ISSUEs 12/20): a
    real 3-process jax.distributed gang on the Gloo CPU backend —
    barrier latency, an injected SIGKILL pre-barrier timed from victim
    death to the survivors' DistRankFailure exits (detect_s, partial-
    gang survival at N=3), then a kill mid-cooperative-commit healed by
    the auto-restart SUPERVISOR with no human step: mttr_s is victim
    death → first post-restart training step, and restarts_total /
    shrink_events come from the supervisor's own accounting. Runs
    `python -m mxnet_tpu.cluster --bench` in a fresh subprocess: each
    rank needs its own 1-device backend pinned before jax initializes,
    and this process already consumed an 8-device mesh."""
    rec = _child_record(_cpu_child(
        ["mxnet_tpu.cluster", "--bench", "--nprocs", "3"],
        drop_env=("MXNET_CLUSTER_INJECT", "MXNET_CLUSTER_HOSTS")),
        "dist_recovery", "dist_recovery")
    if rec.pop("skipped", None):
        rec["status"] = "skipped: no gloo CPU collectives"
    elif not rec.get("ok"):
        raise RuntimeError(
            f"dist_recovery selftest failed: {rec.get('error')}")
    return rec


def _checkpoint_lane():
    """Checkpoint overhead A/B (mxnet_tpu.checkpoint, ISSUE 5): the amp
    lane's MLP stepped with NO checkpoints, with SYNCHRONOUS full-state
    commits every 8 steps, and with ASYNC (saver-thread) commits on the
    same cadence — steps/s each, so the overhead the async design buys
    back is on record — plus restore latency and bytes per commit. The
    cadence is sized so ~8 steps of compute cover one serialize+fsync
    (the manager holds ONE in-flight job; a cadence shorter than the
    save degenerates to blocking for both modes)."""
    import shutil
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu.parallel import DataParallelTrainer, data_parallel_mesh
    from mxnet_tpu.checkpoint import CheckpointManager, TrainingState

    n = min(2, len(jax.devices()))
    mesh = data_parallel_mesh(n, jax.devices()[:n])
    batch, dim, hidden = 256, 1024, 512
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=hidden, name="ckfc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=hidden, name="ckfc2")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=64, name="ckfc3")
    sym = mx.sym.SoftmaxOutput(net, name="softmax")
    rng = np.random.RandomState(0)
    x = rng.uniform(-1, 1, (batch, dim)).astype(np.float32)
    y = rng.randint(0, 64, (batch,)).astype(np.float32)
    steps = 16 if QUICK else 32
    save_every = 8
    root = _fresh_dir("ckpt")
    out = {}
    try:
        def _run(mode):
            tr = DataParallelTrainer(sym, mesh, optimizer="sgd",
                                     learning_rate=0.05, momentum=0.9,
                                     rescale_grad=1.0 / batch,
                                     dtype="float32")
            params, states, aux = tr.init_state(
                {"data": (batch, dim), "softmax_label": (batch,)})
            inputs = tr.shard_inputs([x, y])
            for _ in range(2):
                params, states, aux, loss, _ = tr.step(params, states,
                                                       aux, inputs)
            float(loss)
            mgr = None
            if mode != "none":
                mgr = CheckpointManager(os.path.join(root, mode),
                                        async_save=(mode == "async"),
                                        keep_last_n=2)
            rates = []
            gstep = 0
            for _ in range(3):
                t0 = time.perf_counter()
                for _ in range(steps):
                    params, states, aux, loss, _ = tr.step(params, states,
                                                           aux, inputs)
                    gstep += 1
                    if mgr is not None and gstep % save_every == 0:
                        arrays, tmeta = tr.export_training_state(
                            params, states, aux)
                        mgr.save(TrainingState(arrays=arrays, meta={
                            "kind": "bench", "epoch": 0, "batch": gstep,
                            "step": gstep, "trainer": tmeta}), step=gstep)
                float(loss)
                if mgr is not None:
                    mgr.wait()
                rates.append(steps / (time.perf_counter() - t0))
            sps = _median(rates)
            restore_ms = None
            counters = {}
            if mgr is not None:
                t0 = time.perf_counter()
                assert mgr.restore() is not None
                restore_ms = (time.perf_counter() - t0) * 1e3
                counters = mgr.counters()
                mgr.close()
            return sps, restore_ms, counters

        base_sps, _, _ = _run("none")
        sync_sps, sync_restore_ms, sync_c = _run("sync")
        async_sps, _, async_c = _run("async")
        commits = max(1, async_c.get("ckpt_commits", 1))
        out = {
            "baseline_steps_per_sec": round(base_sps, 2),
            "sync_steps_per_sec": round(sync_sps, 2),
            "async_steps_per_sec": round(async_sps, 2),
            "sync_overhead_pct": round(
                (base_sps / sync_sps - 1.0) * 100, 1),
            "async_overhead_pct": round(
                (base_sps / async_sps - 1.0) * 100, 1),
            "ckpt_bytes_per_commit": int(
                async_c.get("ckpt_bytes", 0) // commits),
            "ckpt_save_ms": round(
                async_c.get("ckpt_save_us", 0) / commits / 1e3, 1),
            "overlap_frac": async_c.get("ckpt_overlap_frac"),
            "restore_ms": round(sync_restore_ms, 1),
            "devices": n,
        }
    finally:
        shutil.rmtree(root, ignore_errors=True)
    return out


def _elastic_ckpt_lane():
    """Topology-elastic restore (ISSUE 8): save the checkpoint lane's
    MLP state sharded as if 8 devices owned it (num_shards=8), then
    restore and reshard onto the CURRENT (smaller) mesh — the
    preemption-then-shrink path. Reports save/restore wall time, the
    bytes reassembled+resharded, and proves the roundtrip is bitwise
    lossless (state_sha256 before == after)."""
    import shutil
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu.parallel import DataParallelTrainer, data_parallel_mesh
    from mxnet_tpu.checkpoint import (CheckpointManager, TrainingState,
                                      state_sha256)

    save_shards, restore_devices = 8, min(4, len(jax.devices()))
    mesh = data_parallel_mesh(restore_devices,
                              jax.devices()[:restore_devices])
    batch, dim, hidden = 256, 1024, 512
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=hidden, name="elfc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=64, name="elfc2")
    sym = mx.sym.SoftmaxOutput(net, name="softmax")
    rng = np.random.RandomState(0)
    x = rng.uniform(-1, 1, (batch, dim)).astype(np.float32)
    y = rng.randint(0, 64, (batch,)).astype(np.float32)
    tr = DataParallelTrainer(sym, mesh, optimizer="sgd",
                             learning_rate=0.05, momentum=0.9,
                             rescale_grad=1.0 / batch, dtype="float32")
    params, states, aux = tr.init_state(
        {"data": (batch, dim), "softmax_label": (batch,)})
    inputs = tr.shard_inputs([x, y])
    for _ in range(4):
        params, states, aux, loss, _ = tr.step(params, states, aux,
                                               inputs)
    float(loss)
    arrays, tmeta = tr.export_training_state(params, states, aux)
    st = TrainingState(arrays=arrays, meta={
        "kind": "bench", "epoch": 0, "batch": 4, "step": 4,
        "trainer": tmeta})
    sha_before = state_sha256(st)
    root = _fresh_dir("elastic_ckpt")
    try:
        mgr = CheckpointManager(os.path.join(root, "ckpt"),
                                async_save=False, keep_last_n=0,
                                num_shards=save_shards)
        t0 = time.perf_counter()
        mgr.save(st, step=4)
        save_ms = (time.perf_counter() - t0) * 1e3
        t0 = time.perf_counter()
        back = mgr.restore()
        restore_ms = (time.perf_counter() - t0) * 1e3
        reshard_bytes = sum(
            np.asarray(v).nbytes for v in back.arrays.values())
        # reshard onto the current mesh: device_put in import is the
        # elastic step — the saved shard layout never constrains it
        t0 = time.perf_counter()
        tr.import_training_state(back.arrays, back.meta["trainer"])
        reshard_ms = (time.perf_counter() - t0) * 1e3
        out = {
            "saved_shards": save_shards,
            "restore_devices": restore_devices,
            "save_ms": round(save_ms, 1),
            "restore_ms": round(restore_ms, 1),
            "reshard_ms": round(reshard_ms, 1),
            "reshard_bytes": int(reshard_bytes),
            "bit_identical": state_sha256(back) == sha_before,
        }
        mgr.close()
    finally:
        shutil.rmtree(root, ignore_errors=True)
    return out


def _telemetry_lane():
    """Step-telemetry overhead A/B (mxnet_tpu.telemetry, ISSUE 6): the
    checkpoint lane's MLP stepped with NO recorder vs with a live
    StepLogger (registry histogram + counters per step) — steps/s each,
    so the always-on observability cost is a measured number, not a
    promise. Also times one /metrics scrape against the in-process
    exporter while the registry is hot."""
    import urllib.request
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu.parallel import DataParallelTrainer, data_parallel_mesh
    from mxnet_tpu.telemetry import StepLogger, start_server

    n = min(2, len(jax.devices()))
    mesh = data_parallel_mesh(n, jax.devices()[:n])
    batch, dim, hidden = 256, 1024, 512
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=hidden, name="tlfc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=64, name="tlfc2")
    sym = mx.sym.SoftmaxOutput(net, name="softmax")
    rng = np.random.RandomState(0)
    x = rng.uniform(-1, 1, (batch, dim)).astype(np.float32)
    y = rng.randint(0, 64, (batch,)).astype(np.float32)
    steps = 32 if QUICK else 64

    def _run(with_telemetry):
        tr = DataParallelTrainer(sym, mesh, optimizer="sgd",
                                 learning_rate=0.05, momentum=0.9,
                                 rescale_grad=1.0 / batch,
                                 dtype="float32")
        params, states, aux = tr.init_state(
            {"data": (batch, dim), "softmax_label": (batch,)})
        inputs = tr.shard_inputs([x, y])
        for _ in range(2):
            params, states, aux, loss, _ = tr.step(params, states, aux,
                                                   inputs)
        float(loss)
        slog = StepLogger("bench_telemetry") if with_telemetry else None
        rates = []
        try:
            for _ in range(3):
                t0 = time.perf_counter()
                for _ in range(steps):
                    params, states, aux, loss, _ = tr.step(
                        params, states, aux, inputs)
                    if slog is not None:
                        slog.step(samples=batch)
                float(loss)
                rates.append(steps / (time.perf_counter() - t0))
        finally:
            if slog is not None:
                slog.close()
        return _median(rates)

    base_sps = _run(False)
    tele_sps = _run(True)
    srv = start_server(0)
    t0 = time.perf_counter()
    body = urllib.request.urlopen(srv.url + "/metrics",
                                  timeout=10).read().decode()
    scrape_ms = (time.perf_counter() - t0) * 1e3
    return {"baseline_steps_per_sec": round(base_sps, 2),
            "telemetry_steps_per_sec": round(tele_sps, 2),
            "overhead_pct": round((base_sps / tele_sps - 1.0) * 100, 2),
            "scrape_ms": round(scrape_ms, 2),
            "scrape_lines": body.count("\n"),
            "devices": n}


def _tracing_lane():
    """Span-tracing overhead A/B + shard-merge latency
    (mxnet_tpu.telemetry.tracing, ISSUE 13). The same gluon fused_fit
    run with MXNET_TRACE off vs on — steps/s each, so the tracing tax on
    the fused hot loop is a measured number (acceptance: < 2%). The
    traced arms also write a steplog JSONL, from which the measured
    feed-vs-compute and comm-vs-compute overlap fractions are pulled for
    a plain-dp arm and a ZeRO-1 arm (MXNET_ZERO_STAGE=1). Finally an
    8-rank synthetic shard set (per-rank clock offsets/skews) is merged
    into one timeline, timed."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.telemetry import tracing

    batches, batch, dim, k = (8 if (QUICK or CPU_SCALE) else 16), 128, 512, 4
    epochs = 3
    rng = np.random.RandomState(0)
    xs = rng.uniform(-1, 1, (batches, batch, dim)).astype(np.float32)
    ys = rng.randint(0, 10, (batches, batch)).astype(np.float32)

    class _Data:
        def __iter__(self):
            return ((mx.nd.array(xs[i]), mx.nd.array(ys[i]))
                    for i in range(batches))

    _ENV = ("MXNET_TRACE", "MXNET_TELEMETRY_LOG", "MXNET_ZERO_STAGE")

    def _fit_arm(trace_on, log_path=None, zero=False, ndev=1):
        prev = {v: os.environ.get(v) for v in _ENV}
        os.environ["MXNET_TRACE"] = "1" if trace_on else "0"
        if log_path:
            os.environ["MXNET_TELEMETRY_LOG"] = log_path
        else:
            os.environ.pop("MXNET_TELEMETRY_LOG", None)
        if zero:
            os.environ["MXNET_ZERO_STAGE"] = "1"
        else:
            os.environ.pop("MXNET_ZERO_STAGE", None)
        try:
            net = nn.HybridSequential()
            with net.name_scope():
                net.add(nn.Dense(dim, activation="relu"))
                net.add(nn.Dense(10))
            net.initialize(mx.init.Xavier())
            loss = gluon.loss.SoftmaxCrossEntropyLoss()
            marks = []
            gluon.trainer.fused_fit(
                net, loss, _Data(), num_epoch=epochs,
                optimizer="sgd", optimizer_params={"learning_rate": 0.05},
                steps_per_dispatch=k,
                contexts=[mx.cpu(i) for i in range(ndev)],
                epoch_callback=lambda *a: marks.append(time.perf_counter()))
            return (epochs - 1) * batches / (marks[-1] - marks[0])
        finally:
            for v, val in prev.items():
                if val is None:
                    os.environ.pop(v, None)
                else:
                    os.environ[v] = val

    def _overlap_fields(log_path):
        """Last step record's measured overlap fractions."""
        fields = None
        with open(log_path, encoding="utf-8") as f:
            for line in f:
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if rec.get("event") == "step" and \
                        "feed_compute_overlap_frac" in rec:
                    fields = rec
        if fields is None:
            raise RuntimeError(f"no traced step records in {log_path}")
        return {"feed_compute_overlap_frac":
                fields["feed_compute_overlap_frac"],
                "comm_compute_overlap_frac":
                fields["comm_compute_overlap_frac"],
                "feed_us": fields["feed_us"],
                "compute_us": fields["compute_us"],
                "comm_us": fields["comm_us"]}

    root = _fresh_dir("trace")
    ndev = min(2, len(jax.devices()))
    base_sps = _fit_arm(False)
    dp_log = os.path.join(root, "dp.jsonl")
    trace_sps = _fit_arm(True, log_path=dp_log)
    zero_log = os.path.join(root, "zero.jsonl")
    _fit_arm(True, log_path=zero_log, zero=True, ndev=ndev)

    shard_dir = os.path.join(root, "shards")
    tracing.synth_shards(shard_dir, ranks=8, steps=5,
                         base_wall=time.time())
    t0 = time.perf_counter()
    merged, summary = tracing.merge(shard_dir)
    merge_ms = (time.perf_counter() - t0) * 1e3
    return {"baseline_steps_per_sec": round(base_sps, 2),
            "traced_steps_per_sec": round(trace_sps, 2),
            "overhead_pct": round((base_sps / trace_sps - 1.0) * 100, 2),
            "dp": _overlap_fields(dp_log),
            "zero": _overlap_fields(zero_log),
            "merge_ranks": 8,
            "merge_events": summary["events"],
            "merge_ms": round(merge_ms, 2)}


def _serving_net_lane():
    """Network serving tier closed-loop (mxnet_tpu.serving.frontend,
    ISSUE 17): a subprocess HTTP/1.1 server (ThreadingHTTPServer over a
    ModelRouter with 2 hot models × 2 engine replicas) driven by 64
    concurrent urllib client threads over real sockets — QPS, p50/p99
    end-to-end latency, and the shed fraction under mixed
    interactive/batch admission classes. Subprocess because the server
    pins its own cpu device set before jax initializes."""
    return _child_record(_cpu_child(
        ["mxnet_tpu.serving.frontend", "--bench",
         "--requests", "384" if QUICK else "768", "--concurrency", "64"]),
        "serving_net", "serving_net")


def _analysis_lane():
    """Static-analysis gate as a measured lane (mxnet_tpu.analysis,
    ISSUE 9): one `python -m mxnet_tpu.analysis --strict --json`
    subprocess — the same command ci.sh quick runs — timed wall-clock,
    with the finding counts on record. The strict gate passing inside
    the bench run proves the analysis invariants hold on the EXACT tree
    being benchmarked."""
    from mxnet_tpu.analysis.hloaudit import parse_last_metric

    t0 = time.perf_counter()
    proc = _cpu_child(["mxnet_tpu.analysis", "--strict", "--json"],
                      timeout=600)
    wall_s = time.perf_counter() - t0
    rec = parse_last_metric(proc.stdout, "analysis")
    return {"strict_ok": proc.returncode == 0,
            "platform": "cpu",
            "wall_s": round(wall_s, 1),
            "counts": rec.get("counts"),
            # per-pass-family wall time + finding counts, so a pass
            # whose cost regresses shows up in the bench series
            "families": rec.get("families"),
            "suppressed": rec.get("suppressed"),
            "strict_failures": rec.get("strict_failures")}


def main(argv=None):
    import argparse

    global QUICK, _T_START, CPU_SCALE, TRAIN_BATCH, INFER_BATCH, TRAIN_IMG
    ap = argparse.ArgumentParser(description="canonical perf JSON bench")
    ap.add_argument("--quick", action="store_true",
                    help="trim iteration counts (fast sanity pass; "
                         "numbers carry quick=true)")
    args = ap.parse_args(argv)
    QUICK = args.quick
    _T_START = time.monotonic()

    # the FIRST flushed JSON line lands on stdout before any jax
    # import/backend start, so a run killed mid-init still parses (and
    # the platform asked for is on record)
    _emit("bench_start", {"platform": os.environ.get(
        "BENCH_PLATFORM", "tpu").strip().lower(),
        "quick": QUICK, "budget_s": BENCH_BUDGET_S})
    plat = _platform()
    from mxnet_tpu.config import enable_compile_cache
    cache_dir = enable_compile_cache(CACHE_DIR)
    if plat == "cpu" and os.environ.get(
            "BENCH_CPU_SCALE", "1").strip().lower() not in ("0", "false",
                                                            "off"):
        CPU_SCALE = True
        TRAIN_BATCH, INFER_BATCH, TRAIN_IMG = 8, 8, 32
        _emit("cpu_scale", {
            "train_batch": TRAIN_BATCH, "infer_batch": INFER_BATCH,
            "train_img": TRAIN_IMG,
            "note": "cpu-pinned run: cpu-sized lanes; chip-sized lanes "
                    "skipped (see SKIP_CPU markers)"})

    import jax
    import jax.numpy as jnp
    from mxnet_tpu.parallel import data_parallel_mesh
    device = {"platform": jax.devices()[0].platform,
              "kind": jax.devices()[0].device_kind,
              "count": len(jax.devices())}
    _emit("device", {**device, "compile_cache_dir": cache_dir})
    # a driver kill at the budget should leave
    # all-thread stacks on stderr, not rc=124 with zero evidence — arm
    # one deadline dump just inside BENCH_BUDGET_S (cancelled on clean
    # exit below)
    from mxnet_tpu.telemetry import watchdog as _watchdog
    _watchdog.dump_after(max(BENCH_BUDGET_S - 10.0, 30.0))

    def _gated(name, est_s, fn, *fargs, **fkw):
        """Run a secondary lane only when the remaining BENCH_BUDGET_S
        covers its estimated cost; shed (with the reason on record)
        instead of letting the driver's timeout kill the whole run.
        Emits flushed lane_start/lane_end heartbeats so a killed run
        names its last-live lane."""
        if _budget_left() < est_s:
            raise _BudgetExceeded(
                f"budget: {_budget_left():.0f}s left < {est_s}s estimate")
        _heartbeat(name, "lane_start", est_s=est_s)
        t0 = time.monotonic()
        try:
            out = fn(*fargs, **fkw)
        except BaseException as e:
            lane_s = round(time.monotonic() - t0, 1)
            LANE_TIMES[name] = {"est_s": est_s, "actual_s": lane_s,
                                "err_s": round(lane_s - est_s, 1)}
            _heartbeat(name, "lane_end", ok=False,
                       error=type(e).__name__, lane_s=lane_s)
            raise
        lane_s = round(time.monotonic() - t0, 1)
        # estimate-vs-actual error feeds the summary's budget accounting
        # (a lane whose estimate drifts is what sheds later lanes)
        LANE_TIMES[name] = {"est_s": est_s, "actual_s": lane_s,
                            "err_s": round(lane_s - est_s, 1)}
        _heartbeat(name, "lane_end", ok=True, lane_s=lane_s)
        return out

    sym = _resnet50_symbol()
    mesh = data_parallel_mesh(1, jax.devices())

    # -- training: bf16 multi-precision is the flagship lane (fp32 master
    # params, bf16 compute — the reference trains its fp16 configs the same
    # way, SURVEY §7); fp32 reported alongside ---------------------------------
    _heartbeat("train_resnet50", "lane_start")
    fp32_ips = None if QUICK else _train_ips(sym, mesh, "float32")[0]
    (bf16_ips, step_flops, trainer, params, aux, x, y,
     single_step_ips) = _train_ips(sym, mesh, "bfloat16", want_flops=True)
    train_ips = bf16_ips
    train_flops_img = (step_flops / TRAIN_BATCH if step_flops
                       else TRAIN_FLOPS_PER_IMG)
    mfu = _mfu(train_ips, train_flops_img)
    _emit("train_resnet50", {"bf16_ips": round(train_ips, 2),
                             "mfu": mfu,
                             "fp32_ips": round(fp32_ips, 2)
                             if fp32_ips is not None else None,
                             **PLAN_MEM.get("train_resnet50", {})})

    # -- inference (exact baseline config: batch 32), fp32 and bf16 ----------
    _heartbeat("inference_resnet50", "lane_start")
    from mxnet_tpu.executor import _build_runner
    run = _build_runner(sym, is_train=False)
    arg_names = sym.list_arguments()
    pmap = dict(zip(trainer.param_names, params))
    xi, yi, key = trainer.replicate_inputs(
        [x[:INFER_BATCH], y[:INFER_BATCH], jax.random.PRNGKey(0)])
    argv = tuple(pmap[n] if n in pmap else (xi if n == "data" else yi)
                 for n in arg_names)
    infer_ips, _ = _infer_ips(run, argv, aux, key)
    # bf16 inference: weights + data in bf16, vector params (gamma/beta/
    # bias) and BN running stats stay fp32 — ops cast at use sites
    argv16 = tuple(v.astype(jnp.bfloat16) if v.ndim > 1 and
                   jnp.issubdtype(v.dtype, jnp.floating) else v
                   for v in argv)
    infer16_ips, infer16_flops = _infer_ips(run, argv16, aux, key,
                                            want_flops=True)
    infer_flops_img = (infer16_flops / INFER_BATCH if infer16_flops
                       else RN50_FWD_FLOPS_PER_IMG)
    infer_mfu = _mfu(infer16_ips, infer_flops_img)
    _emit("inference_resnet50", {"fp32_b32_ips": round(infer_ips, 2),
                                 "bf16_b32_ips": round(infer16_ips, 2),
                                 "bf16_mfu": infer_mfu,
                                 **PLAN_MEM.get("inference_resnet50", {})})

    # secondary lanes, each guarded: failures must not discard the
    # flagship numbers measured above. Every lane reports its model
    # FLOPs + MFU so no throughput number is unitless.
    try:
        # apples-to-apples with the published K80 ResNet-152 row
        # (README.md:311, batch/GPU 32 — we use 64 for lane fill)
        if CPU_SCALE:
            raise _ChipOnly()
        rn152_ips, rn152_unit_flops = _gated(
            "train_resnet152", 90, _train_ips_quick, _resnet152_symbol(),
            mesh, "bfloat16", batch=64)
        rn152_ips = round(rn152_ips, 2)
        rn152_mfu = _mfu(rn152_ips, rn152_unit_flops)
    except _ChipOnly:
        rn152_ips, rn152_mfu = SKIP_CPU, None
    except _BudgetExceeded:
        rn152_ips, rn152_mfu = "skipped: budget", None
    except Exception as e:
        rn152_ips, rn152_mfu = f"unavailable: {type(e).__name__}", None
    _emit("train_resnet152", {"ips_b64": rn152_ips, "mfu": rn152_mfu,
                              **PLAN_MEM.get("train_resnet152", {})})
    try:
        if CPU_SCALE:   # bf16 LSTM is software-emulated on cpu — chip lane
            raise _ChipOnly()
        lstm_tps, lstm_unit_flops, lstm_single_tps = _gated(
            "lstm_lm", 60, _lstm_tokens_per_sec, mesh)
        lstm_tps = round(lstm_tps, 0)
        lstm_single_tps = round(lstm_single_tps, 0)
        lstm_mfu = _mfu(lstm_tps, lstm_unit_flops)
    except _ChipOnly:
        lstm_tps, lstm_mfu, lstm_single_tps = SKIP_CPU, None, None
    except _BudgetExceeded:
        lstm_tps, lstm_mfu, lstm_single_tps = "skipped: budget", None, None
    except Exception as e:
        lstm_tps, lstm_mfu = f"unavailable: {type(e).__name__}", None
        lstm_single_tps = None
    _emit("lstm_lm", {"tokens_per_sec": lstm_tps, "mfu": lstm_mfu,
                      **PLAN_MEM.get("lstm_lm", {})})
    try:
        if CPU_SCALE:   # ~5 TFLOP/step Pallas kernel — chip lane
            raise _ChipOnly()
        fa_tps, fa_unit_flops = _gated("flash_attention_seq4096", 45,
                                       _flash_attention_tokens_per_sec)
        fa_tps = round(fa_tps, 0)
        fa_mfu = _mfu(fa_tps, fa_unit_flops)
    except _ChipOnly:
        fa_tps, fa_mfu = SKIP_CPU, None
    except _BudgetExceeded:
        fa_tps, fa_mfu = "skipped: budget", None
    except Exception as e:
        fa_tps, fa_mfu = f"unavailable: {type(e).__name__}", None
    _emit("flash_attention_seq4096", {"tokens_per_sec": fa_tps,
                                      "mfu": fa_mfu})
    try:
        # long-context lane: seq 8192, auto 512-blocks
        # (tools/attention_sweep.py sweeps the curve through 32k)
        if CPU_SCALE:
            raise _ChipOnly()
        fa8_tps, fa8_unit_flops = _gated(
            "flash_attention_seq8192", 45, _flash_attention_tokens_per_sec,
            batch=2, heads=8, seq=8192, dim=128)
        fa8_tps = round(fa8_tps, 0)
        fa8_mfu = _mfu(fa8_tps, fa8_unit_flops)
    except _ChipOnly:
        fa8_tps, fa8_mfu = SKIP_CPU, None
    except _BudgetExceeded:
        fa8_tps, fa8_mfu = "skipped: budget", None
    except Exception as e:
        fa8_tps, fa8_mfu = f"unavailable: {type(e).__name__}", None
    _emit("flash_attention_seq8192", {"tokens_per_sec": fa8_tps,
                                      "mfu": fa8_mfu})
    # int8 lane, un-parked (ISSUE 18): end-to-end quantized serving
    # (bf16 vs int8 .mxa through ServingEngine) replaces the chip-gated
    # XLA-conv measurement — weight-only serving runs on every backend
    try:
        int8_lane = _gated("int8_serving", 90, _quantized_serving_lane)
    except _BudgetExceeded:
        int8_lane = {"status": "skipped: budget"}
    except Exception as e:
        int8_lane = {"status": f"unavailable: {type(e).__name__}"}
    _emit("int8_serving", int8_lane)
    # continuous-batching decode at 1/8/32 concurrent sessions
    try:
        decode_lane = _gated("decode", 120, _decode_lane)
    except _BudgetExceeded:
        decode_lane = {"status": "skipped: budget"}
    except Exception as e:
        decode_lane = {"status": f"unavailable: {type(e).__name__}"}
    _emit("decode", decode_lane)
    try:
        if CPU_SCALE:   # 224px JPEG decode -> resnet50 b128 — chip lane
            raise _ChipOnly()
        e2e_ips, pipe_ips = _gated("e2e_data", 120, _e2e_data_lane, sym,
                                   mesh)
        e2e_ips, pipe_ips = round(e2e_ips, 1), round(pipe_ips, 1)
    except _ChipOnly:
        e2e_ips, pipe_ips = SKIP_CPU, None
    except _BudgetExceeded:
        e2e_ips, pipe_ips = "skipped: budget", None
    except Exception as e:
        e2e_ips, pipe_ips = f"unavailable: {type(e).__name__}", None
    _emit("e2e_data", {"train_e2e_ips": e2e_ips,
                       "pipeline_standalone_ips": pipe_ips})
    # device-feed A/B + persistent-compile-cache lanes (ISSUE 3); cheap,
    # but gated like every secondary lane so a tight budget sheds them
    # with the reason on record instead of eating the driver timeout
    try:
        pipeline_lane = _gated("pipeline", 90, _pipeline_lane)
    except _BudgetExceeded:
        pipeline_lane = {"status": "skipped: budget"}
    except Exception as e:
        pipeline_lane = {"status": f"unavailable: {type(e).__name__}"}
    _emit("pipeline", pipeline_lane)
    try:
        cache_lane = _gated("compile_cache", 60, _compile_cache_lane)
    except _BudgetExceeded:
        cache_lane = {"status": "skipped: budget"}
    except Exception as e:
        cache_lane = {"status": f"unavailable: {type(e).__name__}"}
    _emit("compile_cache", cache_lane)
    # mixed-precision A/B + half-width all-reduce wire bytes (ISSUE 4)
    try:
        amp_lane = _gated("amp", 90, _amp_lane)
    except _BudgetExceeded:
        amp_lane = {"status": "skipped: budget"}
    except Exception as e:
        amp_lane = {"status": f"unavailable: {type(e).__name__}"}
    _emit("amp", amp_lane)
    # ZeRO-sharded dp: stage 0/1/2 (+fp8 wire compression) steps/s and
    # post-SPMD collective wire bytes at 8 devices (ISSUE 10)
    try:
        zero_lane = _gated("zero", 180, _zero_lane)
    except _BudgetExceeded:
        zero_lane = {"status": "skipped: budget"}
    except Exception as e:
        zero_lane = {"status": f"unavailable: {type(e).__name__}"}

    _emit("zero", zero_lane)
    # cost-model sharding planner: MXNET_PLAN=auto vs hand-picked dp /
    # zero2 on the transformer-scale arm at 8 devices (ISSUE 19)
    try:
        plan_lane = _gated("plan", 240, _plan_lane)
    except _BudgetExceeded:
        plan_lane = {"status": "skipped: budget"}
    except Exception as e:
        plan_lane = {"status": f"unavailable: {type(e).__name__}"}
    _emit("plan", plan_lane)
    # DLRM-style sharded embedding: row-sparse deduped exchange (+fp8
    # wire) vs dense replicated-table all-reduce at 8 devices (ISSUE 16)
    try:
        dlrm_lane = _gated("dlrm", 240, _dlrm_lane)
    except _BudgetExceeded:
        dlrm_lane = {"status": "skipped: budget"}
    except Exception as e:
        dlrm_lane = {"status": f"unavailable: {type(e).__name__}"}
    _emit("dlrm", dlrm_lane)
    # fault-tolerant checkpointing A/B: none vs sync vs async commit
    # cadence, restore latency, bytes per commit (ISSUE 5)
    try:
        ckpt_lane = _gated("checkpoint", 90, _checkpoint_lane)
    except _BudgetExceeded:
        ckpt_lane = {"status": "skipped: budget"}
    except Exception as e:
        ckpt_lane = {"status": f"unavailable: {type(e).__name__}"}
    _emit("checkpoint", ckpt_lane)
    # topology-elastic restore: 8-shard save resharded onto the current
    # mesh, bitwise-lossless (ISSUE 8)
    try:
        elastic_lane = _gated("elastic_ckpt", 60, _elastic_ckpt_lane)
    except _BudgetExceeded:
        elastic_lane = {"status": "skipped: budget"}
    except Exception as e:
        elastic_lane = {"status": f"unavailable: {type(e).__name__}"}
    _emit("elastic_ckpt", elastic_lane)
    # distributed-runtime recovery: 3-process gang barrier latency,
    # injected-kill detection latency, supervised self-healing MTTR +
    # restarts_total (ISSUEs 12/20)
    try:
        dist_lane = _gated("dist_recovery", 120, _dist_recovery_lane)
    except _BudgetExceeded:
        dist_lane = {"status": "skipped: budget"}
    except Exception as e:
        dist_lane = {"status": f"unavailable: {type(e).__name__}"}
    _emit("dist_recovery", dist_lane)
    # step-telemetry overhead A/B + /metrics scrape latency (ISSUE 6)
    try:
        tele_lane = _gated("telemetry", 60, _telemetry_lane)
    except _BudgetExceeded:
        tele_lane = {"status": "skipped: budget"}
    except Exception as e:
        tele_lane = {"status": f"unavailable: {type(e).__name__}"}
    _emit("telemetry", tele_lane)
    # span-tracing overhead A/B + 8-rank shard-merge latency (ISSUE 13)
    try:
        tracing_lane = _gated("tracing", 90, _tracing_lane)
    except _BudgetExceeded:
        tracing_lane = {"status": "skipped: budget"}
    except Exception as e:
        tracing_lane = {"status": f"unavailable: {type(e).__name__}"}
    _emit("tracing", tracing_lane)
    # static-analysis strict gate, timed (ISSUE 9)
    try:
        analysis_lane = _gated("analysis", 150, _analysis_lane)
    except _BudgetExceeded:
        analysis_lane = {"status": "skipped: budget"}
    except Exception as e:
        analysis_lane = {"status": f"unavailable: {type(e).__name__}"}
    _emit("analysis", analysis_lane)
    # network serving tier: HTTP closed-loop at concurrency 64 (ISSUE 17)
    try:
        serving_net_lane = _gated("serving_net", 120, _serving_net_lane)
    except _BudgetExceeded:
        serving_net_lane = {"status": "skipped: budget"}
    except Exception as e:
        serving_net_lane = {"status": f"unavailable: {type(e).__name__}"}
    _emit("serving_net", serving_net_lane)
    acc_fail = None
    try:
        # the accuracy lane ASSERTS its target — never shed silently in a
        # canonical run; --quick skips it by name (it is a convergence
        # check, not a throughput number, and dominates quick runtime)
        if QUICK:
            acc_lane = "skipped: quick"
        else:
            acc_lane = round(_gated("accuracy", 180, _accuracy_lane), 4)
    except _BudgetExceeded:
        acc_lane = "skipped: budget"
    except AssertionError as e:
        # below-target accuracy FAILS the bench (nonzero exit after the
        # JSON line) instead of being silently recorded
        acc_lane = str(e)
        acc_fail = str(e)
    except Exception as e:
        acc_lane = f"unavailable: {type(e).__name__}"
    _emit("accuracy", {"lenet_digits_val_acc": acc_lane})

    print(json.dumps({
        "metric": "resnet50_train_throughput",
        "value": round(train_ips, 2),
        "unit": "images/sec",
        "vs_baseline": round(train_ips / K80_RN50_INFER_B32, 2),
        "mfu": mfu,
        "train_flops_per_img": round(train_flops_img / 1e9, 2),
        "flops_source": "xla_cost_analysis" if step_flops else "fallback",
        "train_batch": TRAIN_BATCH,
        "train_img": TRAIN_IMG,
        "infer_batch": INFER_BATCH,
        "platform": plat,
        "device": device,
        # cpu-sized profile (see CPU_SCALE comment at top): rates here
        # are CPU rates; chip-sized lanes carry SKIP_CPU markers
        "cpu_scale": CPU_SCALE,
        "train_dtype": "bfloat16(mp)",
        # K fused steps per dispatch; the 1-step-per-dispatch rate is
        # kept alongside
        "steps_per_dispatch": 4,
        "single_dispatch_ips": round(single_step_ips, 2),
        "fp32_train_ips": round(fp32_ips, 2) if fp32_ips is not None
        else "skipped: quick",
        # budget accounting: lanes shed to fit
        # BENCH_BUDGET_S say so above; --quick also trims window sizes
        "quick": QUICK,
        "budget_s": BENCH_BUDGET_S,
        "elapsed_s": round(time.monotonic() - _T_START, 1),
        # what was left of BENCH_BUDGET_S at summary time (negative =
        # the run overran; the driver's kill margin is visible here)
        "budget_headroom_s": round(_budget_left(), 1),
        # per-lane estimate-vs-actual duration error for the gated
        # lanes: positive err_s means the lane ran past its estimate —
        # the drift that sheds later lanes
        "lane_duration_error_s": {
            name: t["err_s"] for name, t in sorted(LANE_TIMES.items())},
        "lane_times_s": LANE_TIMES,
        # per-lane plan-memory columns (devstats extraction of each
        # lane's compiled executable; also on the lane lines above)
        "plan_memory": PLAN_MEM,
        "inference_b32_ips": round(infer_ips, 2),
        "inference_bf16_b32_ips": round(infer16_ips, 2),
        "inference_bf16_mfu": infer_mfu,
        # fp32-vs-fp32 like round 2 (the K80 baseline is fp32); the bf16
        # ratio is reported separately so cross-round series stay honest
        "inference_vs_baseline": round(infer_ips / K80_RN50_INFER_B32, 2),
        "inference_bf16_vs_baseline": round(
            infer16_ips / K80_RN50_INFER_B32, 2),
        # int8 lane un-parked as end-to-end quantized serving (bf16 vs
        # int8 .mxa through ServingEngine)
        "int8_serving": int8_lane,
        # continuous-batching decode: tokens/s + per-token p50/p99 +
        # kv occupancy at 1/8/32 concurrent sessions
        "decode": decode_lane,
        # end-to-end lane: ImageRecordIter (native JPEG decode, uint8
        # payloads, on-device normalize) feeding the train step
        "resnet50_train_e2e_ips": e2e_ips,
        "data_pipeline_standalone_ips": pipe_ips,
        "resnet152_train_ips_b64": rn152_ips,
        "resnet152_vs_k80": round(rn152_ips / K80_RN152_TRAIN, 2)
        if isinstance(rn152_ips, float) else None,
        "resnet152_mfu": rn152_mfu,
        "lstm_lm_train_tokens_per_sec": lstm_tps,
        "lstm_lm_steps_per_dispatch": 16,
        "lstm_lm_single_dispatch_tokens_per_sec": lstm_single_tps,
        "lstm_lm_mfu": lstm_mfu,
        "attention_seq4096_flash_fwd_bwd_tokens_per_sec": fa_tps,
        "attention_mfu_model_flops": fa_mfu,
        "attention_seq8192_flash_fwd_bwd_tokens_per_sec": fa8_tps,
        "attention_seq8192_mfu_model_flops": fa8_mfu,
        "accuracy_lane_lenet_digits_val_acc": acc_lane,
        # async device-feed A/B + persistent compile cache (ISSUE 3;
        # full per-lane payloads streamed above as "lane" JSON lines)
        "device_feed_speedup": pipeline_lane.get("speedup",
                                                 pipeline_lane.get("status")),
        "device_feed_overlap_frac": pipeline_lane.get("overlap_frac"),
        "compile_cache_cold_s": cache_lane.get("cold_first_step_s",
                                               cache_lane.get("status")),
        "compile_cache_warm_s": cache_lane.get("warm_first_step_s"),
        # mixed precision (ISSUE 4): fp32-vs-bf16 step A/B + the grad
        # all-reduce wire bytes from the post-SPMD HLO (full payload
        # streamed above as the "amp" lane line)
        "amp_bf16_vs_fp32_speedup": amp_lane.get(
            "speedup", amp_lane.get("status")),
        "amp_allreduce_bytes_per_step_bf16": amp_lane.get(
            "allreduce_bytes_per_step_bf16"),
        "amp_allreduce_bytes_per_step_fp32": amp_lane.get(
            "allreduce_bytes_per_step_fp32"),
        # ZeRO-sharded dp (ISSUE 10): de-replicated optimizer update +
        # reduce-scatter/all-gather wire at 8 devices (full payload
        # streamed above as the "zero" lane line)
        "zero2_vs_dp_speedup": zero_lane.get(
            "speedup_zero2", zero_lane.get("status")),
        "zero2_fp8_vs_dp_speedup": zero_lane.get("speedup_zero2_fp8"),
        "zero_wire_bytes_per_step_dp": zero_lane.get(
            "wire_bytes_per_step_dp"),
        "zero_wire_bytes_per_step_zero2": zero_lane.get(
            "wire_bytes_per_step_zero2"),
        "zero_wire_bytes_per_step_zero2_fp8": zero_lane.get(
            "wire_bytes_per_step_zero2_fp8"),
        "zero_devices": zero_lane.get("devices"),
        # sharding planner (ISSUE 19): the auto-selected composition and
        # whether it held up against the hand-tuned single modes (full
        # payload streamed above as the "plan" lane line)
        "plan_auto_choice": plan_lane.get(
            "auto_choice", plan_lane.get("status")),
        "plan_auto_steps_per_s": plan_lane.get("auto_steps_per_s"),
        "plan_dp_steps_per_s": plan_lane.get("dp_steps_per_s"),
        "plan_zero2_steps_per_s": plan_lane.get("zero2_steps_per_s"),
        "plan_auto_beats_hand": plan_lane.get("auto_beats_hand"),
        # DLRM sharded embedding (ISSUE 16): deduped row exchange vs
        # dense table all-reduce at 8 devices (full payload streamed
        # above as the "dlrm" lane line)
        "dlrm_sparse_vs_dense_speedup": dlrm_lane.get(
            "speedup_sparse", dlrm_lane.get("status")),
        "dlrm_sparse_fp8_vs_dense_speedup": dlrm_lane.get(
            "speedup_sparse_fp8"),
        "dlrm_touched_row_frac": dlrm_lane.get("touched_frac"),
        "dlrm_wire_bytes_per_step_dense": dlrm_lane.get(
            "wire_bytes_per_step_dense"),
        "dlrm_wire_bytes_per_step_sparse": dlrm_lane.get(
            "wire_bytes_per_step_sparse"),
        "dlrm_wire_bytes_per_step_sparse_fp8": dlrm_lane.get(
            "wire_bytes_per_step_sparse_fp8"),
        # checkpointing (ISSUE 5): save-every-3-steps overhead vs no-ckpt
        # baseline, sync vs saver-thread async, plus restore latency
        "checkpoint_sync_overhead_pct": ckpt_lane.get(
            "sync_overhead_pct", ckpt_lane.get("status")),
        "checkpoint_async_overhead_pct": ckpt_lane.get(
            "async_overhead_pct"),
        "checkpoint_restore_ms": ckpt_lane.get("restore_ms"),
        "checkpoint_bytes_per_commit": ckpt_lane.get(
            "ckpt_bytes_per_commit"),
        # elastic checkpointing (ISSUE 8): 8-shard save restored +
        # resharded onto the current mesh, bitwise lossless
        "elastic_ckpt_restore_ms": elastic_lane.get(
            "restore_ms", elastic_lane.get("status")),
        "elastic_ckpt_reshard_bytes": elastic_lane.get("reshard_bytes"),
        "elastic_ckpt_bit_identical": elastic_lane.get("bit_identical"),
        # distributed recovery (ISSUE 12): 2-process gang barrier
        # latency, SIGKILL-to-DistRankFailure detection latency, and
        # kill-mid-commit restart-resume MTTR (full payload streamed
        # above as the "dist_recovery" lane line)
        "dist_barrier_us_mean": dist_lane.get(
            "barrier_us_mean", dist_lane.get("status")),
        "dist_kill_detect_s": dist_lane.get("detect_s"),
        "dist_restart_mttr_s": dist_lane.get("mttr_s"),
        # step telemetry (ISSUE 6): recorder-on overhead vs bare loop +
        # /metrics scrape latency (full payload streamed above)
        "telemetry_overhead_pct": tele_lane.get(
            "overhead_pct", tele_lane.get("status")),
        "telemetry_scrape_ms": tele_lane.get("scrape_ms"),
        # network serving tier (ISSUE 17): HTTP closed-loop at
        # concurrency 64 against 2 hot models x 2 replicas (full
        # payload streamed above as the "serving_net" lane line)
        "serving_net_qps": serving_net_lane.get(
            "qps", serving_net_lane.get("status")),
        "serving_net_p50_ms": serving_net_lane.get("p50_ms"),
        "serving_net_p99_ms": serving_net_lane.get("p99_ms"),
        "serving_net_shed_frac": serving_net_lane.get("shed_frac"),
        "timing": ("median-of-3x8-steps (2 dispatches x K=4, cpu-scale)"
                   if CPU_SCALE
                   else "median-of-3x80-steps (20 dispatches x K=4)"),
        "secondary_lane_timing": ("chip-sized secondary lanes skipped "
                                  "(cpu-scale)" if CPU_SCALE else
                                  "median-of-3 windows: rn152 10 steps, "
                                  "lstm 64 steps (4xK=16), attn 10 steps"),
    }))
    _watchdog.cancel_deadline()
    if acc_fail:
        raise SystemExit(f"bench FAILED: {acc_fail}")


if __name__ == "__main__":
    main()
