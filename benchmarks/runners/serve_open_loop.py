"""Runner `serve_open_loop`: `ServingEngine` + `DynamicBatcher` under an
open loop of requests at a rate fixed in the traffic file.

Set-up exports the seeded weights (`export_model`), loads the artifact
(`Predictor`, `ServingEngine`, which warms every bucket plan), and sends a
short warm-up burst through the batcher. The window then offers a FIXED
schedule: `rate_per_s` x --seconds requests whose gaps are the quantiles of
the exponential distribution (Poisson arrivals) and whose sizes are the mix's
shares of that count — the seed permutes both, so every seed does the same
work in another order. One thread sends each request at its due time
(`submit` does not block), one collects the answers in the order sent (the
batcher answers in that order), and a request's latency runs from the
instant it was DUE to its whole answer on the host. A request that is shed,
fails or misses its deadline is counted in `failed` and enters the latency
sample at its deadline or what it took, whichever is more.

`knee_sweep` in the traffic file (used once, by hand, when the cell is
defined) replaces the window by a few seconds at each of several rates and
prints one line per rate.
"""
import math
import os
import queue
import threading
import time

from common import check, emit, memory_stats, peak_memory_bytes, quantile, \
    rel_err


def schedule(env, rate, seconds, sizes, stream):
    """(due seconds, rows) of every request of a window, from the seed."""
    import numpy as np
    n = max(int(round(rate * seconds)), 1)
    gaps = -np.log(1.0 - (np.arange(n) + 0.5) / n) / rate
    rows = np.concatenate([
        np.full(int(round(share * n)), int(size))
        for size, share in sorted(sizes.items(), key=lambda kv: int(kv[0]))])
    rows = np.resize(rows, n)           # rounding may leave one short
    rng = env.rng(stream)
    due = np.cumsum(rng.permutation(gaps)) - gaps.min()
    return due, rng.permutation(rows)


class Load:
    """One window of offered load: a sender and a collector thread."""

    def __init__(self, batcher, pool, due, rows, offsets, deadline_ms,
                 annotate):
        self.batcher, self.pool = batcher, pool
        self.due, self.rows, self.offsets = due, rows, offsets
        self.deadline_ms, self.annotate = deadline_ms, annotate
        n = len(due)
        self.sent = [0.0] * n           # actual send, seconds after start
        self.done = [math.nan] * n      # answer on the host, same clock
        self.ok = [False] * n
        self.answers = [None] * n
        self.keep = set()
        self.errors = {}                # exception name -> requests
        self._q = queue.Queue()

    def inputs(self, i):
        o = self.offsets[i]
        return self.pool[o:o + self.rows[i]]

    def _send(self):
        t0 = self.t0
        for i, due in enumerate(self.due):
            wait = t0 + due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            self.sent[i] = time.perf_counter() - t0
            try:
                with self.annotate("bench.request"):
                    fut = self.batcher.submit(self.inputs(i),
                                              timeout_ms=self.deadline_ms)
            except Exception as e:      # shed at the door
                fut = e
            self._q.put((i, fut))
        self._q.put(None)

    def _collect(self):
        t0 = self.t0
        while True:
            item = self._q.get()
            if item is None:
                return
            i, fut = item
            try:
                if isinstance(fut, Exception):
                    raise fut
                out = fut.result()[0]
                self.done[i] = time.perf_counter() - t0
                self.ok[i] = out.shape[0] == self.rows[i]
                if i in self.keep:
                    self.answers[i] = out
            except Exception as e:      # shed, timed out, or failed: counted
                self.done[i] = time.perf_counter() - t0
                kind = type(e).__name__
                self.errors[kind] = self.errors.get(kind, 0) + 1

    def run(self):
        threads = [threading.Thread(target=self._send, name="bench-send"),
                   threading.Thread(target=self._collect,
                                    name="bench-collect")]
        self.t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return self

    def latencies_ms(self):
        """Per request, from due time; a failed one at no less than its
        deadline. And whether each came back right and in time."""
        lat, good = [], []
        for due, done, ok in zip(self.due, self.done, self.ok):
            ms = (done - due) * 1e3
            in_time = ok and ms <= self.deadline_ms
            lat.append(ms if in_time else max(ms, self.deadline_ms))
            good.append(in_time)
        return lat, good


class TimedEngine:
    """The engine as the batcher sees it, with the benchmark's own clock
    (and annotation) around each `infer`: host padding, the copy in, the
    plan, the answer on the host. The program's `serve.compute` span ends
    when the plan is dispatched, before the answer is waited for, so it
    cannot stand for the engine's time."""

    def __init__(self, engine, annotate):
        self._engine, self._annotate = engine, annotate
        self.calls = []                 # (start, seconds, rows)

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def infer(self, *arrays):
        t0 = time.perf_counter()
        with self._annotate("bench.engine_infer"):
            out = self._engine.infer(*arrays)
        self.calls.append((t0, time.perf_counter() - t0,
                           int(arrays[0].shape[0])))
        return out


def build_artifact(env, model, cfg, max_batch):
    """Seeded, untrained weights -> .mxa in the run's work directory. The
    artifact answers with logits, not probabilities: through 50 untrained
    layers the softmax saturates to one-hot rows, which would leave the
    comparison with Predictor nothing to compare but an argmax."""
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu.contrib.export import export_model
    np.random.seed(env.seed % (2**31 - 1))
    mx.random.seed(env.seed % (2**31 - 1))
    image = cfg["image_size"]
    sym = model.build(cfg, softmax=False)
    mod = mx.mod.Module(sym, context=mx.cpu(0), label_names=None)
    mod.bind(data_shapes=[("data", (1, 3, image, image))],
             for_training=False)
    mod.init_params(model.initializer())
    arg_params, aux_params = mod.get_params()
    os.makedirs(env.work_dir, exist_ok=True)
    path = os.path.join(env.work_dir, "model.mxa")
    export_model(path, sym, arg_params, aux_params,
                 {"data": (max_batch, 3, image, image)},
                 dtype=cfg["compute_dtype"])
    return path


def run(env):
    import numpy as np
    from mxnet_tpu import profiler as mx_profiler
    from mxnet_tpu.predictor import Predictor
    from mxnet_tpu.serving import DynamicBatcher, ServingEngine
    cfg, tr = env.config, env.traffic
    model = env.load("models", cfg["model"])
    max_batch, image = int(cfg["serve_max_batch"]), cfg["image_size"]
    sizes = {int(s): float(p) for s, p in tr["request_rows"].items()}
    deadline_ms = float(tr["deadline_ms"])
    rate = float(tr["rate_per_s"])
    t_import = env.since_start()

    path = build_artifact(env, model, cfg, max_batch)
    t_export = env.since_start()
    pred = Predictor(path)
    annotate = env.annotate
    engine = ServingEngine(path, buckets=tr.get("buckets"))
    timed = TimedEngine(engine, annotate)
    batcher = DynamicBatcher(timed, **tr["batcher"])
    t_plans = env.since_start()
    pool = env.rng(2).standard_normal(
        (int(tr["pool_images"]), 3, image, image), dtype="float32")
    span = len(pool) - max(sizes) + 1

    def load(seconds, stream, at_rate=rate):
        due, rows = schedule(env, at_rate, seconds, sizes, stream)
        offsets = env.rng(stream + 1).integers(0, span, len(due))
        return Load(batcher, pool, due, rows, offsets, deadline_ms, annotate)

    faults = []
    try:
        load(float(tr["warmup_seconds"]), 10).run()
        setup_s = env.since_start()
        setup_meter = env.meter.snapshot()
        emit("setup", import_s=t_import, export_s=t_export - t_import,
             plans_s=t_plans - t_export, warmup_s=setup_s - t_plans,
             compile_seconds=setup_meter["seconds"],
             compile_requests=setup_meter["requests"],
             compile_cache_hits=setup_meter["cache_hits"],
             buckets=engine.buckets)

        if tr.get("knee_sweep"):
            for j, r in enumerate(tr["knee_sweep"]["rates_per_s"]):
                w = load(float(tr["knee_sweep"]["seconds"]), 100 + 2 * j,
                         at_rate=float(r)).run()
                lat, good = w.latencies_ms()
                third = len(lat) // 3
                emit("knee", rate_per_s=r, requests=len(lat),
                     met_deadline_share=sum(good) / len(lat),
                     p50_ms=quantile(lat, 0.5), p95_ms=quantile(lat, 0.95),
                     # a backlog that grows shows as the last third of the
                     # requests waiting longer than the middle third
                     p50_ms_middle_third=quantile(lat[third:2 * third], 0.5),
                     p50_ms_last_third=quantile(lat[2 * third:], 0.5),
                     errors=w.errors,
                     images_per_s=float(sum(w.rows)) / max(w.done))

        snap0 = batcher.metrics.snapshot()
        compiles0 = env.meter.requests
        win = load(env.seconds, 20)
        check_n = min(int(tr["check_requests"]), len(win.due))
        win.keep = set(env.rng(4).choice(len(win.due), check_n,
                                         replace=False).tolist())
        calls0 = len(timed.calls)
        win.run()
        calls1 = len(timed.calls)
        snap1 = batcher.metrics.snapshot()
        compiles_in_window = env.meter.requests - compiles0

        traced = None
        if env.trace:
            import jax
            os.environ["MXNET_TRACE"] = "1"     # the program's span ring
            mx_profiler.clear_events()
            profile_dir = env.start_trace()
            try:
                with annotate("bench.window_start"):
                    pass
                traced = load(float(tr["traced_seconds"]), 30).run()
                with annotate("bench.window_end"):
                    pass
            finally:
                jax.profiler.stop_trace()
                os.environ["MXNET_TRACE"] = "0"
        spans = {}
        for ev in (mx_profiler.events_snapshot() if env.trace else ()):
            if ev.get("ph") == "X":
                spans.setdefault(ev["name"], []).append(ev["dur"] / 1e3)
    finally:
        batcher.close()

    lat, good = win.latencies_ms()
    n = len(lat)
    failed = n - sum(good)
    rows_good = int(sum(r for r, g in zip(win.rows, good) if g))
    lag_ms = [(s - d) * 1e3 for s, d in zip(win.sent, win.due)]

    # outside the window: the kept answers against Predictor.forward on the
    # same inputs, in one padded call
    kept = sorted(i for i in win.keep if win.answers[i] is not None)
    check(len(kept) >= max(1, check_n // 2),
          f"only {len(kept)} of {check_n} sampled requests were answered",
          faults)
    err = math.nan
    if kept:
        fit = np.cumsum([win.rows[i] for i in kept]) <= max_batch
        kept = kept[:max(1, int(fit.sum()))]
        want = pred.forward(np.concatenate([win.inputs(i) for i in kept]))[0]
        got = np.concatenate([win.answers[i] for i in kept])
        check(got.shape == want.shape and bool(np.isfinite(got).all())
              and float(np.ptp(got)) > 0,
              f"answers of shape {got.shape}, finite "
              f"{bool(np.isfinite(got).all())}, range {np.ptp(got)}", faults)
        err = rel_err(got, want)
        tol = float(tr["check_tolerance"])
        check(err <= tol, f"ServingEngine vs Predictor: {err} > {tol}",
              faults)
    check(compiles_in_window == 0,
          f"{compiles_in_window} compiles inside the window", faults)
    check(failed <= float(tr["max_failed_share"]) * n,
          f"{failed} of {n} requests failed or missed {deadline_ms} ms",
          faults)

    emit("memory", stats=memory_stats(env.devices))
    batches = snap1["batches"] - snap0["batches"]
    rows_batched = snap1["batched_rows"] - snap0["batched_rows"]
    hist = sorted(lat)
    emit("serve", requests=n, failed=failed, rate_per_s=rate,
         deadline_ms=deadline_ms, window_s=env.seconds,
         drained_s=max(win.done), latency_ms_quantiles={
             q: quantile(hist, float(q)) for q in
             ("0.1", "0.25", "0.5", "0.75", "0.9", "0.95", "0.99", "1.0")},
         batches=batches, batched_rows=rows_batched,
         shed=snap1["shed"] - snap0["shed"],
         timeouts=snap1["timeouts"] - snap0["timeouts"],
         batch_hist=snap1["batch_hist"], check_requests=len(kept),
         check_rel_err=err, check_answer_rms=float(np.sqrt(np.mean(
             np.square(got, dtype=np.float64)))) if kept else None,
         compiles_in_window=compiles_in_window,
         errors=win.errors,
         engine_calls_ms_rows=[[round(c[1] * 1e3), c[2]]
                               for c in timed.calls[calls0:calls1]],
         padded_rows=engine.padded_rows)
    return {
        "correct": not faults, "faults": faults,
        "attempted": n, "failed": failed,
        "setup_s": setup_s,
        "memory_peak_bytes": peak_memory_bytes(env.devices),
        "end_to_end": {"serve_p50_ms": quantile(lat, 0.5),
                       "serve_p95_ms": quantile(lat, 0.95),
                       "serve_rate": rows_good / env.seconds},
        "host": {"window_s": env.seconds,
                 "compiles_in_window": compiles_in_window,
                 "setup_compile_s": setup_meter["seconds"],
                 "batches": batches, "batched_rows": rows_batched,
                 "generator_lag_ms": lag_ms,
                 "engine_infer_ms": [c[1] * 1e3
                                     for c in timed.calls[calls0:calls1]],
                 "spans_ms": spans},
        "profile_dir": profile_dir if traced else None,
    }
