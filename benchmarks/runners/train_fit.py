"""Runner `train_fit`: `Module.fit(steps_per_dispatch=K)` for --seconds.

One `fit` call, one epoch, over a DataIter of the benchmark's own that
cycles a pool of host batches made from the seed and ends the epoch when
told to — only ever after a multiple of K batches, because `_fit_fused`
compiles a shorter scan for a short tail block. Everything is timed from the
`batch_end_callback`, which the fused loop calls once per dispatch after its
metric update has fetched that dispatch's outputs: the clock never stops on
work the device still owes.

  dispatches 1..W          warm-up (the first compiles); set-up ends at the
                           callback of dispatch W
  W+1..E                   the window: E is the first dispatch whose callback
                           comes --seconds or more after that of W
  E+1..E+1+T  (--trace 1)  the profiler's window, between two annotations
                           (bench.window_start/_end), T dispatches long

The feed stages blocks ahead of the loop, so a few more dispatches run after
the epoch was told to end; they are outside every window.
"""
import time

from common import check, emit, memory_stats, peak_memory_bytes, rel_err


class PoolIter:
    """Cycles `pool` (DataBatch objects) for ever; raises StopIteration
    once `stop` is set and a multiple of `k` batches has been given out."""

    def __init__(self, pool, provide_data, provide_label, batch_size, k,
                 annotate):
        self.pool, self.k, self.annotate = pool, k, annotate
        self.provide_data, self.provide_label = provide_data, provide_label
        self.batch_size = batch_size
        self.given = 0
        self.stop = False

    def __iter__(self):
        return self

    def reset(self):
        pass

    def next(self):
        with self.annotate("bench.data_next"):
            if self.stop and self.given % self.k == 0:
                raise StopIteration
            batch = self.pool[self.given % len(self.pool)]
            self.given += 1
            return batch

    __next__ = next


class Watch:
    """batch_end_callback: the clock, the loss of each dispatch alone, the
    compile and feed counters, and the switches of the windows."""

    def __init__(self, env, it):
        from mxnet_tpu import pipeline
        self.env, self.it = env, it
        self.feed_stats = pipeline.stats
        self.annotate = env.annotate
        self.profile_dir = None
        tr = env.traffic
        self.warm = int(tr["warmup_dispatches"])
        self.traced = int(tr["traced_dispatches"])
        self.t, self.ce, self.compiles, self.feed_wait_us = [], [], [], []
        self.seen = (0.0, 0)
        self.i0 = self.i1 = self.i_trace_end = None
        self.setup_s = None
        self.setup_meter = None
        self.locals = None

    def __call__(self, param):
        with self.annotate("bench.batch_end"):
            now = time.perf_counter()
            m = param.eval_metric
            d_sum, d_n = m.sum_metric - self.seen[0], m.num_inst - self.seen[1]
            self.seen = (m.sum_metric, m.num_inst)
            self.t.append(now)
            self.ce.append(float(d_sum / d_n))
            self.compiles.append(self.env.meter.requests)
            self.feed_wait_us.append(self.feed_stats()["feed_wait_us"])
            self.locals = param.locals
            i = len(self.t) - 1
            if i + 1 == self.warm:
                self.i0 = i
                self.setup_s = self.env.since_start()
                self.setup_meter = self.env.meter.snapshot()
            elif self.i0 is not None and self.i1 is None and \
                    now - self.t[self.i0] >= self.env.seconds:
                self.i1 = i
                if self.env.trace:
                    self.profile_dir = self.env.start_trace()
                else:
                    self.it.stop = True
            elif self.i_trace_end is None and self.i1 is not None and \
                    self.env.trace:
                # the dispatch after the profiler started is left out (the
                # start itself stalls the host); then `traced` dispatches
                if i == self.i1 + 1:
                    with self.annotate("bench.window_start"):
                        pass
                elif i == self.i1 + 1 + self.traced:
                    with self.annotate("bench.window_end"):
                        pass
                    self.i_trace_end = i
                    self.it.stop = True


def make_pool(env, batch, image, classes, n_batches):
    import mxnet_tpu as mx
    rng = env.rng(1)
    pool = []
    for _ in range(n_batches):
        data = rng.standard_normal((batch, 3, image, image), dtype="float32")
        label = rng.integers(0, classes, (batch,)).astype("float32")
        pool.append(mx.io.DataBatch(
            data=[mx.nd.array(data, ctx=mx.cpu(0))],
            label=[mx.nd.array(label, ctx=mx.cpu(0))], pad=0))
    return pool


def parity(env, model, cfg, params, faults):
    """chip_smoke's parity check: the fp32 forward of the trained weights
    on a few seeded images, on the chip and on this process's CPU backend."""
    import mxnet_tpu as mx
    mx.amp.disable()
    arg_params, aux_params = params
    n, image = int(env.traffic["parity_batch"]), cfg["image_size"]
    x = env.rng(3).standard_normal((n, 3, image, image), dtype="float32")
    logits = {}
    for name, ctx in (("chip", mx.tpu(0)), ("cpu", mx.cpu(0))):
        mod = mx.mod.Module(model.build(cfg, softmax=False), context=ctx,
                            label_names=None)
        mod.bind(data_shapes=[("data", x.shape)], for_training=False)
        mod.set_params(arg_params, aux_params, allow_extra=True)
        mod.forward(mx.io.DataBatch(data=[mx.nd.array(x, ctx=ctx)]),
                    is_train=False)
        logits[name] = mod.get_outputs()[0].asnumpy()
    err = rel_err(logits["chip"], logits["cpu"])
    tol = float(env.traffic["parity_tolerance"])
    check(err == err and err <= tol,
          f"fp32 forward, chip against CPU backend: {err} > {tol}", faults)
    return err


def spread(env, loc, batch, faults):
    """Over several chips: every parameter and optimizer-state array lives
    on all of them, and the batch is split as many ways."""
    n = env.chips
    arrays = list(loc["params"]) + [s for st in loc["states"] for s in st]
    on = {len({s.device for s in a.addressable_shards}) for a in arrays}
    check(on == {n}, f"training state spans {sorted(on)} devices, not {n}",
          faults)
    x = loc["inputs"][0]                # (K, batch, ...)
    per = x.addressable_shards[0].data.shape[1]
    check(per * n == x.shape[1] == batch,
          f"batch {x.shape[1]} split as {per} a device over {n}", faults)
    return {"state_arrays": len(arrays), "batch_per_device": per}


def run(env):
    import jax
    import numpy as np
    import mxnet_tpu as mx
    cfg, tr = env.config, env.traffic
    model = env.load("models", cfg["model"])
    k = int(tr["steps_per_dispatch"])
    batch = int(cfg["per_chip_batch"]) * env.chips
    image, classes = cfg["image_size"], cfg["num_classes"]
    t_import = env.since_start()

    np.random.seed(env.seed % (2**31 - 1))
    mx.random.seed(env.seed % (2**31 - 1))
    mx.amp.init(cfg["compute_dtype"])
    contexts = [mx.tpu(i) for i in range(env.chips)]
    mod = mx.mod.Module(model.build(cfg),
                        context=contexts if env.chips > 1 else contexts[0])
    pool = make_pool(env, batch, image, classes, int(tr["pool_batches"]))
    it = PoolIter(pool,
                  [mx.io.DataDesc("data", (batch, 3, image, image))],
                  [mx.io.DataDesc("softmax_label", (batch,))], batch, k,
                  env.annotate)
    watch = Watch(env, it)
    t_build = env.since_start()

    opt = dict(cfg["optimizer"])
    try:
        mod.fit(it, num_epoch=1, optimizer=opt.pop("name"),
                optimizer_params=opt, initializer=model.initializer(),
                eval_metric="ce", batch_end_callback=watch,
                steps_per_dispatch=k)
    finally:
        if watch.profile_dir:
            jax.profiler.stop_trace()

    faults = []
    i0, i1 = watch.i0, watch.i1
    if not check(i0 is not None and i1 is not None and i1 > i0,
                 f"the window never closed: {len(watch.t)} dispatches",
                 faults):
        return {"correct": False, "attempted": len(watch.t), "failed": 0,
                "faults": faults, "setup_s": watch.setup_s or 0.0,
                "end_to_end": {}, "host": {}}
    window_s = watch.t[i1] - watch.t[i0]
    n_disp = i1 - i0
    rate = n_disp * k * batch / window_s / env.chips
    gaps_ms = [(b - a) * 1e3 for a, b in zip(watch.t[i0:i1], watch.t[i0 + 1:i1 + 1])]
    compiles_in_window = watch.compiles[i1] - watch.compiles[i0]
    feed_wait_s = (watch.feed_wait_us[i1] - watch.feed_wait_us[i0]) / 1e6

    span = int(tr["loss_span_dispatches"])
    check("trainer" in watch.locals, "fit fell back to the per-batch loop",
          faults)
    bad = int(np.sum(~np.isfinite(watch.ce)))
    check(bad == 0, f"{bad} dispatches with a non-finite loss", faults)
    first, last = np.mean(watch.ce[:span]), np.mean(watch.ce[-span:])
    check(last < first, f"loss did not fall: first {span} dispatches "
          f"{first:.4f}, last {span} {last:.4f}", faults)
    check(compiles_in_window == 0,
          f"{compiles_in_window} compiles inside the window", faults)
    placed = spread(env, watch.locals, batch, faults) \
        if env.chips > 1 else None
    memory_peak = peak_memory_bytes(env.devices)
    emit("memory", stats=memory_stats(env.devices))
    err = parity(env, model, cfg, mod.get_params(), faults)

    emit("setup", import_s=t_import, build_s=t_build - t_import,
         compile_and_warmup_s=watch.setup_s - t_build,
         compile_seconds=watch.setup_meter["seconds"],
         compile_requests=watch.setup_meter["requests"],
         compile_cache_hits=watch.setup_meter["cache_hits"])
    emit("train", dispatches=len(watch.t), window_dispatches=n_disp,
         window_s=window_s, steps_per_dispatch=k, global_batch=batch,
         loss_first=float(first), loss_last=float(last),
         dispatch_ms=[round(g, 1) for g in gaps_ms],
         compiles_in_window=compiles_in_window,
         parity_rel_err=err, placed=placed,
         trace_window_dispatches=(watch.traced if watch.i_trace_end else 0))
    return {
        "correct": not faults, "faults": faults,
        "attempted": n_disp, "failed": bad,
        "setup_s": watch.setup_s,
        "memory_peak_bytes": memory_peak,
        "end_to_end": {"train_rate": rate},
        "host": {"dispatch_gaps_ms": gaps_ms, "window_s": window_s,
                 "compiles_in_window": compiles_in_window,
                 "feed_wait_s": feed_wait_s,
                 "setup_compile_s": watch.setup_meter["seconds"]},
        "profile_dir": watch.profile_dir if watch.i_trace_end else None,
    }
