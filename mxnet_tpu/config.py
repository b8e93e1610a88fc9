"""MXNET_* environment-variable config surface.

Parity target: docs/faq/env_var.md — the reference reads ~29 `MXNET_*` env
vars via dmlc::GetEnv at use sites (engine threads
threaded_engine_perdevice.cc:77-78, bulk exec graph_executor.cc:1351-1354,
mem pool pooled_storage_manager.h:54, kvstore bound kvstore_dist.h:58).

Here every documented var is *accepted* and surfaced through `get()`; vars
with a live TPU-stack meaning act (table below), the rest are recorded
no-ops because XLA/PJRT owns the concern:

  MXNET_ENGINE_TYPE            -> engine.set_engine_type (NaiveEngine = sync)
  MXNET_PROFILER_AUTOSTART     -> profiler.set_state('run') at import
  MXNET_EXEC_BULK_EXEC_*       -> engine.set_bulk_size hint (XLA fuses anyway)
  MXNET_KVSTORE_BIGARRAY_BOUND -> recorded only: keys are never sharded
                                  across servers here (no ps-lite analog)
  MXNET_ENFORCE_DETERMINISM    -> jax default; recorded
  MXNET_CPU_WORKER_NTHREADS /
  MXNET_GPU_WORKER_NTHREADS    -> XLA owns threading; recorded
  MXNET_GPU_MEM_POOL_RESERVE   -> PJRT preallocation owns HBM; recorded
  MXNET_EXEC_INPLACE_GRAD_SUM_CAP, MXNET_CUDNN_AUTOTUNE_DEFAULT, ...
                               -> absorbed by XLA buffer assignment/autotune
"""
from __future__ import annotations

import os

_DOCUMENTED = {
    "MXNET_ENGINE_TYPE": "ThreadedEnginePerDevice",
    "MXNET_CPU_WORKER_NTHREADS": 1,
    "MXNET_CPU_PRIORITY_NTHREADS": 4,
    "MXNET_CPU_NNPACK_NTHREADS": 4,
    "MXNET_GPU_WORKER_NTHREADS": 2,
    "MXNET_GPU_COPY_NTHREADS": 1,
    "MXNET_OMP_MAX_THREADS": None,
    "MXNET_EXEC_NUM_TEMP": 1,
    "MXNET_EXEC_INPLACE_GRAD_SUM_CAP": 8,
    "MXNET_EXEC_BULK_EXEC_INFERENCE": 1,
    "MXNET_EXEC_BULK_EXEC_TRAIN": 1,
    "MXNET_EXEC_BULK_EXEC_MAX_NODE_TRAIN": 15,
    "MXNET_GPU_MEM_POOL_RESERVE": 5,
    "MXNET_GPU_MEM_POOL_TYPE": "Naive",
    "MXNET_ENFORCE_DETERMINISM": 0,
    "MXNET_KVSTORE_REDUCTION_NTHREADS": 4,
    "MXNET_KVSTORE_BIGARRAY_BOUND": 1000000,
    "MXNET_KVSTORE_USETREE": 0,
    "MXNET_ENABLE_GPU_P2P": 1,
    "MXNET_UPDATE_ON_KVSTORE": 1,
    "MXNET_CUDNN_AUTOTUNE_DEFAULT": 1,
    "MXNET_CUDNN_LIB_CHECKING": 1,
    "MXNET_MKLDNN_ENABLED": 1,
    "MXNET_MKLDNN_CACHE_NUM": -1,
    "MXNET_PROFILER_AUTOSTART": 0,
    "MXNET_PROFILER_MODE": 0,
    "MXNET_DUMP_PROFILE": 0,
    "MXNET_BACKWARD_DO_MIRROR": 0,
    "MXNET_USE_FUSION": 1,
    # native-runtime knobs (TPU build additions, docs/env_vars.md)
    "MXNET_TPU_DISABLE_NATIVE": 0,
    "MXNET_TPU_DISABLE_NATIVE_ITER": 0,
    "MXNET_TPU_NATIVE_DIR": None,
    "MXIO_PIPE_DEBUG": 0,
    # async device-feed pipeline + persistent compile cache
    # (docs/PIPELINE.md): MXNET_DEVICE_FEED=0 restores the synchronous
    # per-step device_put path; MXNET_COMPILE_CACHE=<dir> points JAX's
    # persistent XLA compilation cache at <dir> so executor bind, Gluon
    # CachedOp and serving bucket plans hit disk on re-runs
    "MXNET_DEVICE_FEED": 1,
    "MXNET_DEVICE_FEED_DEPTH": 2,
    "MXNET_COMPILE_CACHE": None,
    # mixed precision (mxnet_tpu.amp, docs/AMP.md): MXNET_AMP=1 turns on
    # framework-wide autocast at import; MXNET_AMP_DTYPE picks the
    # compute dtype — bfloat16 (default, no loss scaling needed) or
    # float16 (DynamicLossScaler engages in the fused dp step). Unset /
    # MXNET_AMP=0 leaves every program bit-identical to fp32.
    "MXNET_AMP": 0,
    "MXNET_AMP_DTYPE": "bfloat16",
    # fault-tolerant checkpointing (mxnet_tpu.checkpoint,
    # docs/CHECKPOINT.md): MXNET_CHECKPOINT_ASYNC=0 makes every
    # CheckpointManager.save commit synchronously on the training
    # thread; MXNET_CHECKPOINT_KEEP is the keep-last-N retention
    # default (<=0 keeps everything); MXNET_CHECKPOINT_BEST_K
    # additionally retains the best k steps by the save metric
    # elastic sharding (PR: topology-elastic checkpoints):
    # MXNET_CHECKPOINT_SHARDS=<n> fixes the shard count of the sharded
    # layout (<=0 = auto = the device count the executor mesh spans);
    # MXNET_CHECKPOINT_RETRIES / MXNET_CHECKPOINT_BACKOFF_S (float
    # seconds, exponential) bound the retry loop around transient shard
    # I/O failures
    "MXNET_CHECKPOINT_ASYNC": 1,
    "MXNET_CHECKPOINT_KEEP": 3,
    "MXNET_CHECKPOINT_BEST_K": 0,
    "MXNET_CHECKPOINT_SHARDS": 0,
    "MXNET_CHECKPOINT_RETRIES": 2,
    "MXNET_CHECKPOINT_BACKOFF_S": "0.5",
    # crash/IO fault injection for the durability tests (CI only):
    # MXNET_CHECKPOINT_INJECT_CRASH=<pre-rename|post-rename>:<step>
    # os._exit()s mid-commit; MXNET_CHECKPOINT_INJECT_IO_FAIL=<n> makes
    # the first n shard writes raise OSError (exercises the retry loop)
    "MXNET_CHECKPOINT_INJECT_CRASH": None,
    "MXNET_CHECKPOINT_INJECT_IO_FAIL": 0,
    # gluon model zoo (gluon/model_zoo): MXNET_HOME relocates the
    # pretrained-weight cache (default ~/.mxnet); MXNET_GLUON_REPO
    # points model_store downloads at a mirror of the apache repo
    "MXNET_HOME": None,
    "MXNET_GLUON_REPO": None,
    # unified telemetry (mxnet_tpu.telemetry, docs/TELEMETRY.md):
    # MXNET_TELEMETRY=0 disables step recording (watchdog beats remain);
    # MXNET_TELEMETRY_PORT=<port> starts the /metrics + /healthz HTTP
    # exporter at import; MXNET_TELEMETRY_LOG=<path> appends JSONL
    # run_start/step/run_end records; MXNET_TELEMETRY_STALL_S=<seconds>
    # (float string — default unset) arms the stall watchdog that dumps
    # all-thread stacks when no training step lands for that long;
    # MXNET_TELEMETRY_STALL_PATH additionally appends dumps to a file
    "MXNET_TELEMETRY": 1,
    "MXNET_TELEMETRY_PORT": None,
    "MXNET_TELEMETRY_LOG": None,
    # MXNET_TELEMETRY_HTTP_LOG=1 re-enables the BaseHTTPRequestHandler
    # per-request stderr lines the /metrics exporter silences by default
    "MXNET_TELEMETRY_HTTP_LOG": None,
    "MXNET_TELEMETRY_STALL_S": None,
    "MXNET_TELEMETRY_STALL_PATH": None,
    # ZeRO-sharded data parallelism (mxnet_tpu.parallel.zero,
    # docs/ZERO.md): MXNET_ZERO_STAGE=1|2 makes DataParallelTrainer(...)
    # construct a ZeroTrainer that shards fp32 masters + optimizer state
    # across the dp axis (1 = all-reduce + update own shard, 2 =
    # reduce-scatter); MXNET_ZERO_BUCKET_MB sizes the gradient buckets
    # whose reduce-scatter overlaps the next bucket's backward;
    # MXNET_GRAD_COMPRESS=bf16|fp8 casts gradients to a narrow wire
    # dtype with an error-feedback residual carried in the step state
    "MXNET_ZERO_STAGE": 0,
    "MXNET_ZERO_BUCKET_MB": "4",
    "MXNET_GRAD_COMPRESS": "none",
    # unified N-D parallelism planner (mxnet_tpu.parallel.planner,
    # docs/PLANNER.md): MXNET_PLAN picks the sharding composition —
    # auto (cost-model argmin over dp/zero1/zero2/dpK.tpT[+zero2]
    # candidates), or an explicit spec. The chosen plan auto-tunes
    # MXNET_ZERO_STAGE / MXNET_ZERO_BUCKET_MB / MXNET_GRAD_COMPRESS /
    # MXNET_DEVICE_FEED / MXNET_DEVICE_FEED_DEPTH / MXNET_FUSED_K,
    # each only when the user left it unset ("auto unless set").
    # MXNET_PLAN_WIRE_GBPS is the cross-device bandwidth (GB/s) the
    # cost model prices collective wire bytes with; MXNET_FUSED_K is
    # gluon fused_fit's steps-per-dispatch default (0 = auto = 8)
    "MXNET_PLAN": "auto",
    "MXNET_PLAN_WIRE_GBPS": "25",
    "MXNET_FUSED_K": 0,
    # sharded-embedding row-sparse exchange (mxnet_tpu.parallel.
    # embedding, docs/SPARSE.md): MXNET_EMBED_EXCHANGE picks how
    # embedding gradients cross the wire (sparse = deduped touched rows,
    # dense = table-sized all-reduce baseline); MXNET_EMBED_UNIQUE_CAP
    # bounds the static unique-row slot count per device (0 = auto =
    # the per-device id count, lossless); MXNET_EMBED_COMPRESS casts the
    # exchanged row values to a narrow wire dtype (fp8 adds per-row
    # max-abs scales; no error-feedback residual — see docs/SPARSE.md)
    "MXNET_EMBED_EXCHANGE": "sparse",
    "MXNET_EMBED_UNIQUE_CAP": "0",
    "MXNET_EMBED_COMPRESS": "none",
    # multi-process cluster harness + distributed-runtime hardening
    # (mxnet_tpu.cluster + dist.py, docs/CLUSTER.md):
    # MXNET_DIST_TIMEOUT_S (float-string seconds) bounds every
    # dist.barrier()/collective wait — past it the runtime dumps
    # all-thread stacks and raises DistRankFailure naming the missing
    # rank(s); MXNET_DIST_RETRIES re-waits a timed-out barrier with
    # exponential backoff first (transient stragglers; all surviving
    # ranks retry in lockstep); MXNET_CLUSTER_NPROCS is the launcher's
    # default gang size; MXNET_CLUSTER_INJECT=
    # <kill|hang|exit>@<point>[:rank][@<n>] arms the fault-injection
    # plane (selftests/CI only — see the point table in docs/CLUSTER.md)
    # MXNET_COORDINATOR=<host:port> overrides the jax distributed
    # coordinator address init_process_group derives from the launcher
    "MXNET_COORDINATOR": None,
    "MXNET_DIST_TIMEOUT_S": "60",
    "MXNET_DIST_RETRIES": 1,
    "MXNET_CLUSTER_NPROCS": 2,
    "MXNET_CLUSTER_INJECT": None,
    # self-healing supervisor + multi-host gangs (cluster/supervisor.py,
    # cluster/launcher.py, docs/CLUSTER.md): MXNET_CLUSTER_HOSTS=
    # host1:4,host2:4 assigns ranks to hosts in order (non-local hosts
    # run over ssh; rank 0's host is the coordinator);
    # MXNET_SUPERVISE_MAX_RESTARTS bounds consecutive gang relaunches
    # without a new sealed checkpoint commit before the supervisor gives
    # up with exit 44; MXNET_SUPERVISE_BACKOFF_S (float-string seconds)
    # is the base of the exponential backoff between no-progress
    # relaunches
    "MXNET_CLUSTER_HOSTS": None,
    "MXNET_SUPERVISE_MAX_RESTARTS": 3,
    "MXNET_SUPERVISE_BACKOFF_S": "1",
    # distributed span tracing (telemetry/tracing.py, docs/TELEMETRY.md):
    # MXNET_TRACE=1 records host-side phase spans (feed/compute/comm/
    # ckpt/serve) into the shared profiler event ring and writes this
    # rank's trace-rank-K.json shard at exit; MXNET_TRACE_DIR places the
    # shards; MXNET_TRACE_FLUSH_S (float-string seconds, 0 = exit-only)
    # additionally snapshots the shard periodically so SIGKILL'd ranks
    # leave a recent one; MXNET_TRACE_MAX_EVENTS bounds the shared
    # chrome-event ring (profiler ops + spans; evictions are counted)
    "MXNET_TRACE": 0,
    "MXNET_TRACE_DIR": None,
    "MXNET_TRACE_FLUSH_S": "0",
    "MXNET_TRACE_MAX_EVENTS": 200000,
    # crash flight recorder (telemetry/flightrec.py): MXNET_FLIGHTREC=0
    # disables the always-on in-memory ring of recent spans/events;
    # MXNET_FLIGHTREC_EVENTS sizes it; MXNET_FLIGHTREC_DIR makes crash
    # triggers (DistRankFailure, uncaught exception, SIGTERM) and the
    # periodic flusher write flightrec-rank-K.json black boxes there;
    # MXNET_FLIGHTREC_FLUSH_S is the flusher interval
    "MXNET_FLIGHTREC": 1,
    "MXNET_FLIGHTREC_EVENTS": 4096,
    "MXNET_FLIGHTREC_DIR": None,
    "MXNET_FLIGHTREC_FLUSH_S": "0.5",
    # static analysis (mxnet_tpu.analysis, docs/ANALYSIS.md):
    # MXNET_ANALYSIS_BASELINE=<path> points the finding-suppression
    # baseline somewhere other than tools/analysis_baseline.json;
    # MXNET_ANALYSIS_STRICT=1 makes `python -m mxnet_tpu.analysis`
    # strict by default (exit non-zero on unsuppressed P0/P1)
    "MXNET_ANALYSIS_BASELINE": None,
    "MXNET_ANALYSIS_STRICT": 0,
    # device-efficiency observability (telemetry/devstats.py,
    # docs/TELEMETRY.md): MXNET_DEVSTATS=0 disables XLA cost/memory
    # extraction, MFU/roofline step fields, HBM preflight and the
    # recompile sentinel (default on; off is bit-identical);
    # _PEAK_TFLOPS/_PEAK_GBPS override the per-device_kind peak table
    # MFU/roofline divide by; _HBM_BYTES pins the device memory
    # budget the preflight checks against (autodetected from PJRT
    # memory_stats where the backend exposes it — cpu does not);
    # _RECOMPILE_LIMIT is the per-program compile count past which the
    # sentinel warns + flight-records a recompile storm (<=0 disables)
    "MXNET_DEVSTATS": 1,
    "MXNET_DEVSTATS_PEAK_TFLOPS": None,
    "MXNET_DEVSTATS_PEAK_GBPS": None,
    "MXNET_DEVSTATS_HBM_BYTES": None,
    "MXNET_DEVSTATS_RECOMPILE_LIMIT": 32,
    # network serving tier (mxnet_tpu.serving.frontend, docs/SERVING.md):
    # MXNET_SERVING_PORT=<port> is the HTTP front-door default bind;
    # MXNET_SERVING_REPLICAS sets the EnginePool replica count per model;
    # MXNET_SERVING_HBM_BUDGET=<bytes> caps the ModelRouter's summed
    # plan-cache footprint (admission preflight + LRU eviction; unset
    # falls back to MXNET_DEVSTATS_HBM_BYTES / the PJRT bytes_limit);
    # MXNET_SERVING_MAX_MODELS bounds the hot-model table (0 = unbounded)
    "MXNET_SERVING_PORT": None,
    "MXNET_SERVING_REPLICAS": 1,
    "MXNET_SERVING_HBM_BUDGET": None,
    "MXNET_SERVING_MAX_MODELS": 0,
    # decode-mode serving (mxnet_tpu.serving.decode, docs/SERVING.md):
    # _SLOTS is the KV-pool session capacity (one preallocated max_len
    # cache block per slot; the decode step is compiled once for this
    # width); _MAX_LEN is the default per-session cache length (prompt +
    # generated tokens) when the model/artifact doesn't pin one;
    # _MAX_NEW is the per-request generation budget when the request
    # omits max_new_tokens
    "MXNET_DECODE_SLOTS": 8,
    "MXNET_DECODE_MAX_LEN": 256,
    "MXNET_DECODE_MAX_NEW": 32,
    # post-training weight quantization (contrib.quantization
    # calibrate_weights / the export CLI): default target dtype for
    # weight-only quantization — "int8" or "fp8" (float8_e4m3fn)
    "MXNET_QUANT_DTYPE": "int8",
}


def get(name, default=None):
    """Read an MXNET_* var with its documented default."""
    if default is None:
        default = _DOCUMENTED.get(name)
    raw = os.environ.get(name)
    if raw is None:
        return default
    if isinstance(default, int):
        try:
            return int(raw)
        except ValueError:
            return default
    return raw


def flag(name):
    """Boolean env flag with forgiving parsing: unset/''/'0'/'false'/'off'/
    'no' (any case, whitespace ignored) are False — plain truthiness would
    treat the string '0' as enabled."""
    return os.environ.get(name, "").strip().lower() not in (
        "", "0", "false", "off", "no")


def list_vars():
    """All documented vars with their effective values."""
    return {k: get(k) for k in sorted(_DOCUMENTED)}


def enable_compile_cache(path):
    """Turn on JAX's persistent XLA compilation cache, so every jit/bind
    in this process — executor programs, Gluon CachedOp, serving bucket
    plans — is written to and re-loaded from disk across process
    restarts. Returns the directory in use.

    Where JAX_COMPILATION_CACHE_DIR is set, JAX itself has placed the
    cache there and `path` is ignored: no code of this repo moves a
    cache that was placed from outside (the directory is part of a
    deployment, and of the cache's key). Otherwise the cache goes to
    `path` (created), with the min-compile-time/min-entry-size
    thresholds zeroed so small programs cache too, and without a size
    limit: JAX_COMPILATION_CACHE_MAX_SIZE bounds the directory a
    deployment placed, and here it would evict (a step program of a few
    hundred MB pushes out every other entry of a 192 MiB cache, and the
    next process hits nothing: `kimi_linear.train` on the v5e, PR 26)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return jax.config.jax_compilation_cache_dir
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_compilation_cache_max_size", -1)
    # jax latches its cache handle at the first compile: if any program
    # compiled before the dir was set, the cache sits initialized-with-
    # no-dir and silently writes nothing — re-initialize so the new dir
    # takes effect mid-process
    compilation_cache.reset_cache()
    return str(path)


def pin_cpu(n):
    """Select the CPU backend with `n` virtual devices, before the first
    backend use. For the `--selftest`/`--hlo-check`/`--bench` entry
    points that assert on a CPU mesh of a stated size whatever the
    machine has; the config values win over JAX_PLATFORMS,
    JAX_NUM_CPU_DEVICES and XLA_FLAGS inherited from a parent."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", int(n))


def _apply_startup():
    """Honor vars that have a live meaning (called at package import)."""
    from . import engine
    engine.set_engine_type(get("MXNET_ENGINE_TYPE"))
    engine.set_bulk_size(get("MXNET_EXEC_BULK_EXEC_MAX_NODE_TRAIN"))
    cache_dir = get("MXNET_COMPILE_CACHE")
    if cache_dir:
        enable_compile_cache(cache_dir)
    if get("MXNET_AMP"):
        from . import amp
        amp.init(get("MXNET_AMP_DTYPE") or "bfloat16")
    if get("MXNET_PROFILER_AUTOSTART"):
        from . import profiler
        profiler.set_state("run")
    port = get("MXNET_TELEMETRY_PORT")
    if port not in (None, ""):
        from . import telemetry
        try:
            telemetry.start_server(int(port))
        except (ValueError, OSError):
            pass                      # bad port / port in use: no exporter
    if get("MXNET_TELEMETRY_STALL_S") not in (None, ""):
        from .telemetry import watchdog
        watchdog.install()
    if get("MXNET_TRACE"):
        from .telemetry import tracing
        tracing.arm_autodump()
        from . import profiler as _prof
        _prof.set_max_events(get("MXNET_TRACE_MAX_EVENTS"))
    # flight-recorder crash triggers: armed whenever a dump dir is
    # configured or this process is a gang member (the launcher sets
    # MXNET_FLIGHTREC_DIR for every rank; the in-memory ring itself
    # records regardless)
    if get("MXNET_FLIGHTREC") and (
            get("MXNET_FLIGHTREC_DIR")
            or int(os.environ.get("DMLC_NUM_WORKER", "1")) > 1):
        from .telemetry import flightrec
        flightrec.install()
    # Join the distributed job NOW if launched by tools/launch.py:
    # jax.distributed.initialize must run before any XLA backend use, and
    # user scripts create arrays long before they reach
    # kvstore.create('dist_*').
    if int(os.environ.get("DMLC_NUM_WORKER", "1")) > 1:
        from . import dist
        dist.init_process_group()
