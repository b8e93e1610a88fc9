#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof the system still starts on the chip.

Drives the train -> export -> serve path once through the entry points a
user calls, at the full width of the flagship model (ResNet-50 v1, 1000
classes, 224x224, batch 128), on ONE TPU chip, in ONE process:

  device   jax must report a TPU, else the run stops here, non-zero
  train    Module.fit under amp bf16: fused K=4 dispatches, then per-batch
  serve    export_model -> .mxa -> ServingEngine + DynamicBatcher vs Predictor
  parity   the same fp32 forward on the chip and on this process's CPU backend
  kernels  every Pallas kernel a user path reaches, picked by force=None,
           found in the compiled program, compared with its XLA spelling

Each phase prints one JSON line (seconds, compile seconds, what it checked)
and any failure ends the run non-zero. The last line of a passing run is

  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

`--chips 4` runs ONLY the multi-chip phase (Module.fit over four contexts
against the same steps on one, then ZeRO-2) and ends with "count": 4.
`--rehearse` shrinks every size so the control flow can be walked on the
CPU (Pallas kernels under the interpreter); it is not a CPU mode: it can
never print the final line and always exits non-zero.

Weights and data come from --seed; nothing is downloaded, no child process
needs the chip, and every thread this starts is joined before the end.
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
CACHE_DIR = os.path.join(HERE, ".jax_cache")       # when nothing places it
WORK_DIR = os.path.join(HERE, ".chip_smoke")       # exported artifacts

# tolerances, fixed before any run
BF16_REL = 3e-2     # relative Frobenius error between two bf16 computations
PARITY_REL = 5e-2   # fp32-on-MXU (bf16 passes) vs fp32-on-CPU, 50 layers deep
# 4-chip vs 1-chip cross-entropy (bf16, psum order), first dispatch and
# later ones: the same parameters meet the first step, after which rounding
# differences compound through train-mode BatchNorm (fp32 alone drifts 3% by
# the fourth step at batch 32 on the CPU); a gradient summed twice moves the
# first dispatch by half
LOSS_RTOL = (2e-2, 1e-1)


class Sizes:
    """Real sizes, and the toy ones of --rehearse."""

    def __init__(self, rehearse):
        self.batch, self.image = (8, 32) if rehearse else (128, 224)
        self.fused_k = 4
        self.fused_batches = 8          # two K=4 dispatches
        self.step_batches = 3           # three per-batch steps
        self.serve_batch = 8
        self.parity_batch = 2 if rehearse else 8
        # flash attention (b, h, s, d) with h and 2 kv heads
        self.flash = (1, 4, 256, 128) if rehearse else (8, 8, 4096, 128)
        self.flash_kv = 2
        # decode model: head_dim 128 at every size
        self.dec = dict(vocab=512, layers=2, d_model=256, heads=2,
                        max_len=64) if rehearse else \
            dict(vocab=8192, layers=2, d_model=2048, heads=16, max_len=256)
        self.dec_sessions, self.dec_new = (3, 4) if rehearse else (5, 6)
        # block_until_ready probe: (matrix side, chained matmuls)
        self.sync = (256, 20) if rehearse else (4096, 200)
        self.multi_k, self.multi_batches = 2, 4     # --chips 4: two dispatches
        # BatchNorm over a toy batch of 8 amplifies rounding far more
        self.loss_rtol = (0.3, 0.6) if rehearse else LOSS_RTOL


# -- compile accounting (jax.monitoring) -------------------------------------

class CompileMeter:
    """Seconds and counts of XLA backend compiles, from JAX's own events:
    every compile request (cache hit or not) fires the duration event, and
    the persistent cache fires hit/miss events."""

    def __init__(self):
        import jax.monitoring as mon
        self.seconds = 0.0
        self.requests = 0
        self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.requests += 1

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return (self.seconds, self.requests, self.cache_hits)


def run_phase(name, meter, fn):
    """Run one phase, print its JSON line. A failure prints what failed and
    propagates: nothing here lets the run end in 0."""
    t0 = time.perf_counter()
    s0, r0, h0 = meter.snapshot()
    try:
        checked = fn()
    except BaseException as e:
        print(json.dumps({"phase": name, "ok": False,
                          "seconds": round(time.perf_counter() - t0, 1),
                          "error": f"{type(e).__name__}: {e}"[:2000]}),
              flush=True)
        raise
    s1, r1, h1 = meter.snapshot()
    print(json.dumps({"phase": name, "ok": True,
                      "seconds": round(time.perf_counter() - t0, 1),
                      "compile_seconds": round(s1 - s0, 1),
                      "compile_requests": r1 - r0,
                      "compile_cache_hits": h1 - h0,
                      "checked": checked}), flush=True)


def check(cond, what):
    if not cond:
        raise AssertionError(what)


def rel_err(a, b):
    """Relative Frobenius error of a against the reference b, in float64."""
    import numpy as np
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def on_platform(arr, platform):
    """True when every shard of a jax array lives on `platform`."""
    return all(d.platform == platform for d in arr.devices())


# -- the model ---------------------------------------------------------------

def resnet50(softmax=True):
    """The flagship symbol: gluon model-zoo ResNet-50 v1, full depth and
    widths, under a fixed prefix so every phase names its weights alike."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import vision
    out = vision.resnet50_v1(prefix="resnetv1_")(mx.sym.Variable("data"))
    return mx.sym.SoftmaxOutput(out, name="softmax") if softmax else out


def seeded_iter(sz, n_batches, seed):
    import numpy as np
    import mxnet_tpu as mx
    rng = np.random.RandomState(seed)
    n = n_batches * sz.batch
    data = rng.standard_normal((n, 3, sz.image, sz.image)).astype(np.float32)
    label = rng.randint(0, 1000, (n,)).astype(np.float32)
    return mx.io.NDArrayIter(data, label, batch_size=sz.batch,
                             label_name="softmax_label")


def xavier():
    import mxnet_tpu as mx
    return mx.init.Xavier(rnd_type="gaussian", factor_type="in", magnitude=2)


class StepWatch:
    """batch_end_callback: after every dispatch, the cross-entropy of that
    dispatch alone, JAX's compile-request count, and where the training
    state lives — read from the loop's own `locals`, as Speedometer does."""

    def __init__(self, meter, platform):
        self.meter, self.platform = meter, platform
        self.ce, self.compiles, self.placed = [], [], []
        self.seen = (0.0, 0)
        self.locals = None

    def __call__(self, param):
        import jax
        m = param.eval_metric
        d_sum = m.sum_metric - self.seen[0]
        d_n = m.num_inst - self.seen[1]
        self.seen = (m.sum_metric, m.num_inst)
        self.ce.append(float(d_sum / d_n))
        self.compiles.append(self.meter.requests)
        self.locals = loc = param.locals
        if "trainer" in loc:        # fused loop: the trainer's device state
            arrays = list(loc["params"]) + \
                [s for st in loc["states"] for s in st]
        else:                       # per-batch loop: executor + updater
            mod = loc["self"]
            updater = mod._updater or mod._kvstore._updater
            arrays = [a._data for a in jax.tree_util.tree_leaves(
                ([mod._exec.arg_dict[n] for n in mod._param_names],
                 list(updater.states.values())),
                is_leaf=lambda a: hasattr(a, "_data"))]
        self.placed.append(all(on_platform(a, self.platform)
                               for a in arrays))
        self.n_arrays = len(arrays)


# -- phases ------------------------------------------------------------------

def phase_device(args, sz, meter, state):
    import jax
    dev = jax.devices()[0]
    found = {"platform": dev.platform, "kind": dev.device_kind,
             "count": len(jax.devices()), "jax": jax.__version__}
    if dev.platform != "tpu" and not args.rehearse:
        raise SystemExit(f"chip_smoke: needs a TPU, jax found {found}")
    check(found["count"] >= args.chips,
          f"--chips {args.chips} but jax reports {found['count']} devices")
    state.update(found)
    # the package is imported only now: without it (a directory that holds
    # this script alone) the run ends here, non-zero
    from mxnet_tpu import _native, config
    found["compile_cache_dir"] = config.enable_compile_cache(CACHE_DIR)
    found["compile_cache_placed_by"] = (
        "JAX_COMPILATION_CACHE_DIR" if
        os.environ.get("JAX_COMPILATION_CACHE_DIR") else "chip_smoke")
    found["native_runtime"] = ("c++" + ("+jpeg" if _native.has_jpeg()
                                        else "")) \
        if _native.lib() is not None else "python fallback"
    return found


def phase_train(args, sz, meter, state):
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu.telemetry import devstats
    platform = state["platform"]
    np.random.seed(args.seed)
    mx.random.seed(args.seed)
    mx.amp.init("bfloat16")             # bf16 compute, fp32 masters
    sym = resnet50()
    mod = mx.mod.Module(sym, context=mx.tpu(0))
    it = seeded_iter(sz, sz.fused_batches, args.seed)
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(xavier())
    before = {n: v.asnumpy().copy()
              for n, v in mod.get_params()[0].items()}
    opt = {"learning_rate": 0.01, "momentum": 0.9, "wd": 1e-4}

    fused = StepWatch(meter, platform)
    mod.fit(it, num_epoch=1, optimizer="sgd", optimizer_params=opt,
            eval_metric="ce", batch_end_callback=fused,
            steps_per_dispatch=sz.fused_k)
    check(len(fused.ce) == sz.fused_batches // sz.fused_k >= 2,
          f"fused path made {len(fused.ce)} dispatches")
    check("trainer" in fused.locals, "steps_per_dispatch fell back to "
          "the per-batch loop")

    steps = StepWatch(meter, platform)
    it2 = seeded_iter(sz, sz.step_batches, args.seed + 1)
    mod.fit(it2, begin_epoch=1, num_epoch=2, optimizer="sgd",
            optimizer_params=opt, eval_metric="ce",
            batch_end_callback=steps)
    check(len(steps.ce) == sz.step_batches >= 2,
          f"per-batch path made {len(steps.ce)} steps")
    check("trainer" not in steps.locals, "per-batch fit took the fused loop")

    for w in (fused, steps):
        check(all(np.isfinite(w.ce)), f"non-finite loss: {w.ce}")
        check(all(w.placed), f"training state off the {platform}: "
                             f"{w.placed}")
        # the first dispatch of a path compiles; no later one may
        check(w.compiles[-1] == w.compiles[0],
              f"compiles after the first dispatch: {w.compiles}")
    sentinel = devstats.counters()["recompiles"]
    check(not any(sentinel.values()), f"recompile sentinel: {sentinel}")

    after = mod.get_params()
    same = [n for n in before
            if not np.any(after[0][n].asnumpy() != before[n])]
    # a conv bias in front of a BatchNorm starts at zero and gets a zero
    # gradient (the norm removes it): those, and only those, may stay put
    check(all(n.endswith("_bias") for n in same) and
          len(same) < len(before) // 4, f"parameters unchanged: {same}")
    changed = len(before) - len(same)
    state["params"] = after
    return {"model": "resnet50_v1", "batch": sz.batch, "image": sz.image,
            "amp": "bfloat16", "fused_dispatches": len(fused.ce),
            "steps_per_dispatch": sz.fused_k,
            "per_batch_steps": len(steps.ce),
            "cross_entropy_fused": [round(x, 4) for x in fused.ce],
            "cross_entropy_per_batch": [round(x, 4) for x in steps.ce],
            "params_changed": [changed, len(before)],
            "state_arrays_on_" + platform: [fused.n_arrays, steps.n_arrays],
            "compiles_after_first_dispatch": 0,
            "devstats_programs": sorted(devstats.program_stats())}


def phase_serve(args, sz, meter, state):
    import threading
    from concurrent.futures import ThreadPoolExecutor
    import jax
    import numpy as np
    from mxnet_tpu.contrib.export import export_model
    from mxnet_tpu.predictor import Predictor
    from mxnet_tpu.serving import DynamicBatcher, ServingEngine
    platform = state["platform"]
    arg_params, aux_params = state["params"]
    os.makedirs(WORK_DIR, exist_ok=True)
    path = os.path.join(WORK_DIR, "resnet50.mxa")
    shape = (sz.serve_batch, 3, sz.image, sz.image)
    export_model(path, resnet50(), arg_params, aux_params, {"data": shape},
                 dtype="bfloat16")
    pred = Predictor(path)
    check(platform in pred.manifest["platforms"],
          f"artifact lowered for {pred.manifest['platforms']} only")
    engine = ServingEngine(path)
    batcher = DynamicBatcher(engine, max_wait_us=5000)
    rng = np.random.RandomState(args.seed + 2)
    # a burst of small concurrent requests (coalesced into big buckets),
    # then lone ones (small buckets)
    burst = [rng.standard_normal((int(rng.randint(1, 3)),) + shape[1:])
             .astype(np.float32) for _ in range(24)]
    lone = [rng.standard_normal((r,) + shape[1:]).astype(np.float32)
            for r in (1, 3, 1, 2, 3, 1)]
    try:
        with ThreadPoolExecutor(max_workers=len(burst)) as pool:
            barrier = threading.Barrier(len(burst))

            def ask(x):
                barrier.wait(timeout=60)
                return batcher.infer(x, timeout_ms=120_000)[0]
            answers = list(pool.map(ask, burst))
        answers += [batcher.infer(x, timeout_ms=120_000)[0] for x in lone]
        snap = batcher.metrics.snapshot()
    finally:
        batcher.close()
    requests = burst + lone
    check(len(answers) == len(requests), "a request went unanswered")
    worst = 0.0
    for x, got in zip(requests, answers):
        want = pred.forward(x)[0]
        check(got.shape == want.shape == (x.shape[0], 1000),
              f"output shape {got.shape}")
        check(np.isfinite(got).all(), "non-finite serving output")
        worst = max(worst, rel_err(got, want))
    check(worst <= BF16_REL, f"ServingEngine vs Predictor: {worst}")
    buckets = sorted({engine.bucket_for(int(rows))
                      for rows in snap["batch_hist"]})
    check(len(buckets) >= 2, f"requests landed in buckets {buckets} only")
    check(all(on_platform(a, platform) for a in pred._state),
          f"served parameters off the {platform}")
    for b, plan in engine._plans.items():
        devs = {d.platform
                for s in jax.tree_util.tree_leaves(plan.input_shardings)
                for d in s.device_set}
        check(devs == {platform}, f"plan b{b} compiled for {devs}")
    return {"artifact": os.path.relpath(path, HERE),
            "platforms": pred.manifest["platforms"],
            "requests": len(requests), "answered": len(answers),
            "batch_hist": snap["batch_hist"], "buckets_used": buckets,
            "plans_on_" + platform: sorted(engine._plans),
            "max_rel_err_vs_predictor": round(worst, 6),
            "tolerance": BF16_REL}


def phase_parity(args, sz, meter, state):
    import numpy as np
    import mxnet_tpu as mx
    mx.amp.disable()                    # fp32 on both backends
    arg_params, aux_params = state["params"]
    rng = np.random.RandomState(args.seed + 3)
    x = rng.standard_normal((sz.parity_batch, 3, sz.image, sz.image)) \
        .astype(np.float32)
    logits = {}
    for name, ctx in (("chip", mx.tpu(0)), ("cpu", mx.cpu(0))):
        mod = mx.mod.Module(resnet50(softmax=False), context=ctx,
                            label_names=None)
        mod.bind(data_shapes=[("data", x.shape)], for_training=False)
        mod.set_params(arg_params, aux_params, allow_extra=True)
        mod.forward(mx.io.DataBatch(data=[mx.nd.array(x, ctx=ctx)]),
                    is_train=False)
        out = mod.get_outputs()[0]
        check(on_platform(out._data, state["platform"] if name == "chip"
                          else "cpu"), f"{name} logits on "
                                       f"{out._data.devices()}")
        logits[name] = out.asnumpy()
    check(logits["chip"].shape == (sz.parity_batch, 1000),
          f"logits shape {logits['chip'].shape}")
    check(np.isfinite(logits["chip"]).all(), "non-finite logits")
    err = rel_err(logits["chip"], logits["cpu"])
    check(err <= PARITY_REL, f"chip vs cpu logits: {err}")
    return {"batch": sz.parity_batch, "dtype": "float32",
            "rel_err_chip_vs_cpu": round(err, 6), "tolerance": PARITY_REL}


def _kernel_calls(compiled):
    """(all Pallas kernels, those with an int8 operand) in a compiled
    program's text — a kernel that quietly gave way to its XLA reference
    leaves no tpu_custom_call behind."""
    lines = [ln for ln in compiled.as_text().splitlines()
             if "tpu_custom_call" in ln and "custom-call(" in ln]
    return len(lines), sum("s8[" in ln for ln in lines)


def _flash_check(args, sz, h_kv, force, on_chip):
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.attention import flash_attention
    b, h, s, d = sz.flash
    keys = jax.random.split(jax.random.PRNGKey(args.seed + h_kv), 4)
    q = jax.random.normal(keys[0], (b, h, s, d), jnp.bfloat16)
    k = jax.random.normal(keys[1], (b, h_kv, s, d), jnp.bfloat16)
    v = jax.random.normal(keys[2], (b, h_kv, s, d), jnp.bfloat16)
    g = jax.random.normal(keys[3], (b, h, s, d), jnp.bfloat16)

    def programs(force, q, k, v, g):
        def fwd(q, k, v):
            return flash_attention(q, k, v, causal=True, force=force)

        def loss(q, k, v, g):
            return (fwd(q, k, v).astype(jnp.float32)
                    * g.astype(jnp.float32)).sum()
        return (jax.jit(fwd).lower(q, k, v).compile(),
                jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
                .lower(q, k, v, g).compile())

    fwd, grad = programs(force, q, k, v, g)
    n_fwd, n_grad = _kernel_calls(fwd)[0], _kernel_calls(grad)[0]
    if on_chip:
        check(n_fwd >= 1 and n_grad >= 3,
              f"flash kernels missing from the compiled programs: "
              f"forward {n_fwd}, gradient {n_grad}")
    out, grads = fwd(q, k, v), grad(q, k, v, g)
    # the XLA spelling keeps (b, h, s, s) scores: take it half a batch at a
    # time (rows are independent) so it fits beside everything else
    half = max(b // 2, 1)
    rfwd, rgrad = programs("xla", q[:half], k[:half], v[:half], g[:half])
    errs = {"out": 0.0, "dq": 0.0, "dk": 0.0, "dv": 0.0}
    for lo in range(0, b, half):
        sl = slice(lo, lo + half)
        errs["out"] = max(errs["out"],
                          rel_err(out[sl], rfwd(q[sl], k[sl], v[sl])))
        for name, got, want in zip(
                ("dq", "dk", "dv"), grads,
                rgrad(q[sl], k[sl], v[sl], g[sl])):
            errs[name] = max(errs[name], rel_err(got[sl], want))
    check(max(errs.values()) <= BF16_REL,
          f"flash attention (kv heads {h_kv}) vs XLA: {errs}")
    return {"shape": [b, h, s, d], "kv_heads": h_kv, "dtype": "bfloat16",
            "kernels_forward": n_fwd, "kernels_gradient": n_grad,
            "rel_err": {k: round(v, 5) for k, v in errs.items()}}


def _decode_check(args, sz, force, on_chip):
    import jax
    import numpy as np
    from mxnet_tpu.contrib.export import export_decode_model
    from mxnet_tpu.serving.decode import DecodeEngine, DecodeModel
    model = DecodeModel(**sz.dec)
    check(model.head_dim == 128, f"head_dim {model.head_dim}")
    os.makedirs(WORK_DIR, exist_ok=True)
    path = os.path.join(WORK_DIR, "decoder_int8.mxa")
    export_decode_model(path, model.config(),
                        model.init_params(seed=args.seed), quantize="int8")
    rng = np.random.RandomState(args.seed + 4)
    prompts = [rng.randint(0, model.vocab, int(n)).tolist()
               for n in rng.randint(3, 30, sz.dec_sessions)]

    def streams(attention, matmul):
        eng = DecodeEngine(path, num_slots=8, attention=attention,
                           matmul=matmul)
        try:
            sessions = [eng.submit(p, max_new_tokens=sz.dec_new)
                        for p in prompts]
            toks = [s.result(timeout=600) for s in sessions]
            check(eng.step_compiles == 1,
                  f"{eng.step_compiles} decode step plans compiled")
            plans = [eng._step_plan] + list(eng._prefill_plans.values())
            return toks, [_kernel_calls(p) for p in plans], eng.stats()
        finally:
            eng.close()

    # both engines at full f32 matmul precision: greedy streams of random
    # weights part ways on a rounding difference, and the comparison is
    # about the kernels' arithmetic, not the MXU's default pass count
    jax.config.update("jax_default_matmul_precision", "highest")
    try:
        got, calls, stats = streams(force, force)
        want, ref_calls, _ = streams("xla", "xla")
    finally:
        jax.config.update("jax_default_matmul_precision", None)
    check(all(len(t) == sz.dec_new for t in got), f"short streams: {got}")
    check(got == want, f"token streams differ from the XLA spellings: "
                       f"{got} vs {want}")
    if on_chip:
        lay = model.layers
        for n_all, n_int8 in calls:
            # per plan: one attention kernel per layer, and six int8
            # matmuls per layer plus the vocabulary head
            check(n_all - n_int8 >= lay and n_int8 >= 6 * lay + 1,
                  f"decode kernels missing from a plan: {calls}")
        check(not any(n for n, _ in ref_calls),
              f"XLA spellings compiled kernels: {ref_calls}")
    return {"model": model.config(), "weights": "int8 (export_decode_model)",
            "sessions": len(prompts), "new_tokens": sz.dec_new,
            "streams_equal_to_xla": True,
            "plans": len(calls),
            "kernels_per_plan[all,int8]": [list(c) for c in calls],
            "step_executions": stats["step_executions"],
            "prefill_executions": stats["prefill_executions"]}


def phase_kernels(args, sz, meter, state):
    on_chip = state["platform"] == "tpu"
    # None is the auto pick under test; the CPU rehearsal walks the same
    # kernels under the Pallas interpreter
    force = None if on_chip else "interpret"
    out = {"force": force,
           "flash_mha": _flash_check(args, sz, sz.flash[1], force, on_chip),
           "flash_gqa": _flash_check(args, sz, sz.flash_kv, force, on_chip),
           "decode": _decode_check(args, sz, force, on_chip)}
    # nothing on this path may have run under the interpreter on the chip
    from mxnet_tpu import rtc
    check(not on_chip or not rtc.PallasModule(
        "def k(x_ref, o_ref):\n    o_ref[...] = x_ref[...]\n")._interpret,
        "rtc would interpret its kernels on a TPU")
    out["block_until_ready_waits"] = _sync_check(*sz.sync)
    return out


def _sync_check(side, n_matmuls):
    """Does block_until_ready wait for the device? Time a long chain of
    matmuls to its block, then the host fetch of its scalar result: a
    fetch that still takes long means the block returned early."""
    import jax
    import jax.numpy as jnp
    x = jnp.ones((side, side), jnp.bfloat16)

    @jax.jit
    def chain(x):
        return jax.lax.fori_loop(
            0, n_matmuls, lambda i, a: (a @ x) * 1e-4, x)[0, 0]
    float(chain(x))                             # compile + warm
    t0 = time.perf_counter()
    y = jax.block_until_ready(chain(x))
    t_block = time.perf_counter() - t0
    t0 = time.perf_counter()
    float(y)
    t_fetch = time.perf_counter() - t0
    check(t_fetch < max(0.25 * t_block, 0.02),
          f"block_until_ready returned early: block {t_block:.4f}s, "
          f"then fetch {t_fetch:.4f}s")
    return {"block_s": round(t_block, 4), "fetch_after_s": round(t_fetch, 5)}


# -- the four-chip phase -----------------------------------------------------

def _fit_on(args, sz, contexts, meter, platform):
    """Module.fit(steps_per_dispatch=K) over `contexts`; returns the watch
    (cross-entropies, loop locals) — same seed, so same weights and data."""
    import numpy as np
    import mxnet_tpu as mx
    np.random.seed(args.seed)
    mx.random.seed(args.seed)
    mod = mx.mod.Module(resnet50(), context=contexts)
    watch = StepWatch(meter, platform)
    mod.fit(seeded_iter(sz, sz.multi_batches, args.seed), num_epoch=1,
            optimizer="sgd", initializer=xavier(), eval_metric="ce",
            optimizer_params={"learning_rate": 0.01, "momentum": 0.9},
            batch_end_callback=watch, steps_per_dispatch=sz.multi_k)
    check("trainer" in watch.locals, "fit fell back to the per-batch loop")
    check(all(np.isfinite(watch.ce)), f"non-finite loss: {watch.ce}")
    return watch


def _step_program(loc):
    """The compiled K-step program of a fused fit, re-lowered from the
    loop's own arrays (a persistent-cache hit), for its text and memory."""
    import jax
    from mxnet_tpu.parallel.zero import ZeroTrainer
    tr, k = loc["trainer"], loc["n_blk"]
    tail = (loc["aux"], loc["inputs"], tr._rng_dev, tr._lr_dev, tr._t_dev)
    if isinstance(tr, ZeroTrainer):
        fn = tr._zero_multi_fn(k, "all")
        call = (loc["params"], loc["states"], tr._resid_dev) + tail
    else:
        fn = tr._multi_step_fn(k, "all")
        call = (loc["params"], loc["states"]) + tail
    sds = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=a.sharding), call)
    return fn.lower(*sds).compile()


def _spread(arrays, n_dev):
    """Shard shapes of each array, checking it spans n_dev devices."""
    shapes = []
    for a in arrays:
        shards = a.addressable_shards
        check(len({s.device for s in shards}) == n_dev,
              f"array on {len({s.device for s in shards})} devices")
        shapes.append([list(a.shape), list(shards[0].data.shape)])
    return shapes


def _collectives(compiled):
    """kind -> collective instructions (sync or async) in the program."""
    from mxnet_tpu.analysis.hloaudit import collective_counts
    return {kind: sum(n) for kind, n in
            collective_counts(compiled.as_text()).items()}


def phase_multichip(args, sz, meter, state):
    import jax
    import mxnet_tpu as mx
    platform, n = state["platform"], args.chips
    mx.amp.init("bfloat16")
    four = _fit_on(args, sz, [mx.tpu(i) for i in range(n)], meter, platform)
    one = _fit_on(args, sz, mx.tpu(0), meter, platform)
    diffs = [abs(a - b) / abs(b) for a, b in zip(four.ce, one.ce)]
    tols = [sz.loss_rtol[0]] + [sz.loss_rtol[1]] * (len(diffs) - 1)
    check(len(four.ce) == len(one.ce) >= 2 and
          all(d <= t for d, t in zip(diffs, tols)),
          f"{n}-chip vs 1-chip cross-entropy: {four.ce} vs {one.ce}, "
          f"relative {diffs}, allowed {tols}")

    loc = four.locals
    dp_prog = _step_program(loc)
    dp_colls = _collectives(dp_prog)
    check(dp_colls["all-reduce"] >= 1, f"no all-reduce in the dp step: "
                                       f"{dp_colls}")
    batch_shards = _spread([loc["inputs"][0]], n)
    check(batch_shards[0][1][1] * n == batch_shards[0][0][1],
          f"batch not split {n} ways: {batch_shards}")
    _spread(list(loc["params"])[:3], n)     # dp params: replicated over all
    dp_arg_bytes = dp_prog.memory_analysis().argument_size_in_bytes

    os.environ["MXNET_ZERO_STAGE"] = "2"
    try:
        zero = _fit_on(args, sz, [mx.tpu(i) for i in range(n)], meter,
                       platform)
    finally:
        del os.environ["MXNET_ZERO_STAGE"]
    zloc = zero.locals
    from mxnet_tpu.parallel.zero import ZeroTrainer
    check(isinstance(zloc["trainer"], ZeroTrainer),
          f"MXNET_ZERO_STAGE=2 built a {type(zloc['trainer']).__name__}")
    z_prog = _step_program(zloc)
    z_colls = _collectives(z_prog)
    # the TPU compiler may spell a reduce-scatter as all-reduce + slice or
    # all-gather + reduce: any gradient collective plus the param all-gather
    check(z_colls["reduce-scatter"] + z_colls["all-reduce"] >= 1
          and z_colls["all-gather"] >= 1,
          f"ZeRO-2 step collectives: {z_colls}")
    state_shards = _spread([s for st in zloc["states"] for s in st], n)
    check(all(full[0] == part[0] * n for full, part in state_shards),
          f"optimizer state not split {n} ways: {state_shards}")
    z_arg_bytes = z_prog.memory_analysis().argument_size_in_bytes
    check(z_arg_bytes < dp_arg_bytes,
          f"ZeRO-2 holds {z_arg_bytes} argument bytes per device, dp "
          f"{dp_arg_bytes}")
    # the CPU backend of a rehearsal keeps no memory statistics
    in_use = [d.memory_stats()["bytes_in_use"] for d in jax.devices()[:n]] \
        if platform == "tpu" else None
    check(in_use is None or all(b > 0 for b in in_use),
          f"a device holds nothing: {in_use}")
    return {"model": "resnet50_v1", "global_batch": sz.batch,
            "steps_per_dispatch": sz.multi_k, "dispatches": len(four.ce),
            "cross_entropy_%d_chips" % n: [round(x, 4) for x in four.ce],
            "cross_entropy_1_chip": [round(x, 4) for x in one.ce],
            "rel_diff": [round(d, 5) for d in diffs], "tolerance": tols,
            "dp_collectives": dp_colls,
            "batch_[global,per_device]": batch_shards[0],
            "dp_argument_bytes_per_device": dp_arg_bytes,
            "zero2_cross_entropy": [round(x, 4) for x in zero.ce],
            "zero2_collectives": z_colls,
            "zero2_state_[global,per_device]": state_shards[:2],
            "zero2_argument_bytes_per_device": z_arg_bytes,
            "bytes_in_use_per_device": in_use}


# -- main --------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of every weight and input")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the multi-chip phase")
    ap.add_argument("--rehearse", action="store_true",
                    help="toy sizes for a CPU walk-through; never prints "
                         "the final line, always exits non-zero")
    args = ap.parse_args(argv)
    sz = Sizes(args.rehearse)

    meter = CompileMeter()
    state = {}      # what the device phase found; the trained parameters

    def phase(name, fn):
        run_phase(name, meter, lambda: fn(args, sz, meter, state))

    phase("device", phase_device)
    if args.chips == 4:
        phase("multichip", phase_multichip)
    else:
        phase("train", phase_train)
        phase("serve", phase_serve)
        phase("parity", phase_parity)
        phase("kernels", phase_kernels)

    if args.rehearse:
        print(json.dumps({"ok": False, "rehearsal": True,
                          "note": "toy sizes; not a result"}), flush=True)
        return 2
    # count: the chips this run used (the device phase saw that jax reports
    # at least as many, and printed how many it reports)
    print(json.dumps({"ok": True, "device": {
        "platform": state["platform"], "kind": state["kind"],
        "count": args.chips}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
