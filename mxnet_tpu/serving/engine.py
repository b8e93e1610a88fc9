"""ServingEngine — bucketed compiled-plan cache over an exported .mxa.

The inference artifact binds ONE batch shape at export time (the
MXPredCreate contract, contrib/export.py). Under serving load the
request batch is whatever the micro-batcher coalesced this tick — and
XLA recompiles per shape, so naively executing each distinct batch size
would either thrash the compile cache or waste the MXU padding
everything to the export batch on the host.

The engine takes the middle path the serving literature converged on
(Clipper-style adaptive batching over fixed-shape accelerators):

  - a ladder of power-of-two batch *buckets* up to the export batch
    (read from MANIFEST.json's `serving` block when present, derived
    otherwise);
  - one compiled plan per bucket, built lazily and cached: an
    ahead-of-time compiled (``jit(fn).lower(specs).compile()``) program
    that zero-pads the bucket batch up to the export batch ON DEVICE,
    calls the exported StableHLO module, and slices outputs back to the
    bucket — pad and slice are fused into the XLA program, so the host
    only ever pads request->bucket (cheap numpy). The AOT ``Compiled``
    object is the plan: dispatch never consults the jit cache (no
    shape/commitment re-keying) and its cost/memory analytics feed
    telemetry.devstats — per-plan FLOPs/bytes gauges on /metrics, a
    total-resident-bytes account of the plan cache (`plan_resident_bytes`,
    the eviction input), and an HBM preflight that rejects a bucket whose
    estimated footprint will not fit the device memory budget *before*
    it is admitted;
  - `warmup()` pre-compiles every bucket so no request pays a compile.

Thread-safe: plan creation and device execution are serialized with an
internal lock (one device stream; the DynamicBatcher drives it from a
single worker thread anyway, but direct `infer` from many threads is
safe too).
"""
from __future__ import annotations

import threading

import numpy as np

from ..predictor import Predictor
from ..telemetry import tracing as _tracing


def _pow2_buckets(max_batch):
    buckets, b = [], 1
    while b < max_batch:
        buckets.append(b)
        b *= 2
    buckets.append(int(max_batch))
    return buckets


class ServingEngine:
    """Load a .mxa artifact (or wrap an existing Predictor) and serve
    any request batch <= the export batch through bucketed compiled
    plans."""

    def __init__(self, model, device=None, buckets=None, warmup=True):
        self._pred = model if isinstance(model, Predictor) \
            else Predictor(model, device=device)
        man = self._pred.manifest
        serving = man.get("serving", {})
        # compute dtype baked into the artifact (mxnet_tpu.amp); request
        # and response I/O are fp32 either way — the casts are fused
        # inside each bucket's jitted plan (exp.call carries them)
        self.amp_dtype = serving.get("amp_dtype") \
            or man.get("dtype", "float32")
        self.batch_axis = int(serving.get("batch_axis", 0))
        if self.batch_axis != 0:
            raise ValueError("ServingEngine: only batch_axis 0 artifacts "
                             "are supported")
        self.max_batch = self._pred.export_batch
        ladder = buckets or serving.get("buckets") \
            or _pow2_buckets(self.max_batch)
        ladder = sorted({int(b) for b in ladder if 1 <= int(b)})
        if any(b > self.max_batch for b in ladder):
            raise ValueError(f"ServingEngine: bucket larger than the "
                             f"export batch {self.max_batch}")
        if not ladder or ladder[-1] != self.max_batch:
            ladder.append(self.max_batch)
        self.buckets = ladder
        self.input_names = list(self._pred._input_names)
        self.output_names = list(self._pred.output_names)
        # per-model metrics label (serving/metrics.py): recorded by
        # contrib.export when the artifact was built with a name
        self.model_name = str(man.get("model_name")
                              or serving.get("model") or "model")
        self._plans = {}
        self.plan_bytes = {}            # bucket -> resident-bytes estimate
        self.plan_peak_bytes = {}       # bucket -> est. execution footprint
        self.plan_resident_bytes = 0    # sum over cached plans (eviction input)
        self._lock = threading.RLock()
        self.plan_compiles = 0          # bucket plans built (cache misses)
        self.executions = 0             # compiled-plan invocations
        self.padded_rows = 0            # host-side request->bucket padding
        if warmup:
            self.warmup()

    @classmethod
    def from_symbol(cls, symbol, arg_params, aux_params, data_shapes,
                    path=None, **kwargs):
        """Export `symbol` through contrib.export and serve the artifact
        — the one-call train->serve bridge (uses the same _build_runner
        lowering the Executor runs)."""
        import tempfile
        import os
        from ..contrib.export import export_model
        if path is None:
            path = os.path.join(tempfile.mkdtemp(prefix="mxa_serve_"),
                                "model.mxa")
        export_model(path, symbol, arg_params, aux_params, data_shapes)
        return cls(path, **kwargs)

    # -- plan cache ---------------------------------------------------------

    def bucket_for(self, n):
        """Smallest bucket >= n (the plan that serves an n-row batch)."""
        if n < 1 or n > self.max_batch:
            raise ValueError(f"batch {n} outside [1, {self.max_batch}]")
        for b in self.buckets:
            if b >= n:
                return b
        return self.max_batch          # unreachable (ladder ends at max)

    def _plan(self, bucket):
        plan = self._plans.get(bucket)
        if plan is not None:
            return plan
        import jax
        import jax.numpy as jnp
        exp = self._pred._exp
        B = self.max_batch

        def fn(inputs, state, rng):
            feed = []
            for x in inputs:
                if x.ndim > 0 and x.shape[0] == bucket and bucket < B:
                    pad = jnp.zeros((B - bucket,) + x.shape[1:], x.dtype)
                    x = jnp.concatenate([x, pad], axis=0)
                feed.append(x)
            outs = exp.call(*feed, *state, rng)
            return tuple(o[:bucket]
                         if getattr(o, "ndim", 0) and o.shape[0] == B
                         else o for o in outs)

        # AOT: lower against this bucket's exact specs and keep the
        # Compiled object itself as the plan. Compiled is directly
        # callable, so dispatch pays no jit-cache keying — and the same
        # executable yields cost/memory analytics for free.
        from ..telemetry import devstats
        in_specs = tuple(jax.ShapeDtypeStruct(
            (bucket,) + tuple(self._pred._input_shapes[n][1:]),
            jnp.float32) for n in self.input_names)
        state_specs = tuple(jax.ShapeDtypeStruct(s.shape, s.dtype)
                            for s in self._pred._state)
        rng_spec = jax.ShapeDtypeStruct(self._pred._rng.shape,
                                        self._pred._rng.dtype)
        compiled = jax.jit(fn).lower(in_specs, state_specs,
                                     rng_spec).compile()
        resident = peak = 0
        if devstats.enabled():
            name = "serving.b%d" % bucket
            stats = devstats.record_program(name, compiled=compiled,
                                            kind="serving")
            # resident = what keeping the plan cached pins (the
            # executable); cpu reports no code size — fall back to the
            # I/O footprint so the account is never silently zero
            resident = int(stats["generated_code_bytes"]
                           or (stats["argument_bytes"]
                               + stats["output_bytes"]))
            peak = int(stats["peak_bytes"])
            # shed the bucket BEFORE admitting it to the cache: a sized
            # HBMPreflightError beats a runtime OOM mid-request
            devstats.preflight(name, peak,
                               resident_bytes=self.plan_resident_bytes,
                               what="serving bucket plan")
            devstats.note_compile(name)
        self._plans[bucket] = compiled
        self.plan_bytes[bucket] = resident
        self.plan_peak_bytes[bucket] = peak
        self.plan_resident_bytes = sum(self.plan_bytes.values())
        self.plan_compiles += 1
        return compiled

    def warmup(self):
        """Compile every bucket plan up front (serving must not pay XLA
        compiles on the request path). Bucket b+1's dummy inputs are
        built on the async device feed's thread (pipeline.py) while
        bucket b compiles; with MXNET_COMPILE_CACHE set, re-runs load
        every bucket plan from the disk cache instead of recompiling.

        The dummies stay host-side numpy (the shape requests arrive in);
        plans are AOT Compiled objects, so input commitment cannot key a
        fresh compile either way."""
        from ..pipeline import feed_or_inline, close_feed

        def _stage(b):
            return b, [np.zeros((b,) + tuple(
                self._pred._input_shapes[n][1:]), np.float32)
                for n in self.input_names]

        feed = feed_or_inline(iter(self.buckets), _stage,
                              name="serving_warmup")
        try:
            with self._lock:
                for b, staged in feed:
                    self._run(b, staged)
        finally:
            close_feed(feed)

    # -- request path -------------------------------------------------------

    def _run(self, bucket, arrays):
        plan = self._plan(bucket)
        outs = plan(tuple(arrays), tuple(self._pred._state),
                    self._pred._rng)
        self.executions += 1
        return outs

    def infer(self, *arrays):
        """Run one already-coalesced batch (n rows, 1 <= n <= max_batch,
        batch axis 0). Returns a list of numpy arrays sliced to n."""
        # one parent over the whole call; its three children cover it:
        # serve.pad (host conversion, checks and the padding
        # concatenations), serve.compute (lock wait + plan run; returns
        # before the device ends) and serve.fetch (the answer's copy to
        # the host, which waits for the device)
        with _tracing.span("serve.infer"):
            with _tracing.span("serve.pad"):
                arrays = [np.asarray(getattr(a, "_data", a), np.float32)
                          for a in arrays]
                if len(arrays) != len(self.input_names):
                    raise ValueError(
                        f"expected {len(self.input_names)} inputs "
                        f"{self.input_names}, got {len(arrays)}")
                n = int(arrays[0].shape[0])
                for name, a in zip(self.input_names, arrays):
                    want = self._pred._input_shapes[name]
                    if a.shape[0] != n or \
                            tuple(a.shape[1:]) != tuple(want[1:]):
                        raise ValueError(
                            f"input {name!r}: shape {tuple(a.shape)} is not "
                            f"(n<= {self.max_batch},)+{tuple(want[1:])}")
                bucket = self.bucket_for(n)
                if bucket != n:
                    arrays = [np.concatenate(
                        [a, np.zeros((bucket - n,) + a.shape[1:], a.dtype)],
                        axis=0) for a in arrays]
            with _tracing.span("serve.compute", phase="serve",
                               bucket=bucket, rows=n):
                with self._lock:
                    # padding accounting under the lock: infer() runs
                    # concurrently on batcher-worker and direct-caller
                    # threads, and += on a bare attribute loses updates
                    # under that interleaving
                    if bucket != n:
                        self.padded_rows += bucket - n
                    outs = self._run(bucket, arrays)
            with _tracing.span("serve.fetch", bucket=bucket, rows=n):
                return [np.asarray(o)[:n]
                        if getattr(o, "ndim", 0)
                        and np.asarray(o).shape[0] == bucket
                        else np.asarray(o) for o in outs]

    def stats(self):
        return {"buckets": list(self.buckets),
                "max_batch": self.max_batch,
                "amp_dtype": self.amp_dtype,
                "model": self.model_name,
                "plan_compiles": self.plan_compiles,
                "plans": len(self._plans),
                "plan_bytes": dict(self.plan_bytes),
                "plan_peak_bytes": dict(self.plan_peak_bytes),
                "plan_resident_bytes": self.plan_resident_bytes,
                "executions": self.executions,
                "padded_rows": self.padded_rows}
