"""Device-efficiency observability: XLA cost/memory analytics, MFU and
roofline attainment, HBM preflight, and a recompile sentinel.

The registry/steplog/tracing stack measures wall-clock phases — what the
*host* did. This module records what *XLA* knows about each program it
compiled: per-program FLOPs and bytes moved (``compiled.cost_analysis()``),
argument/output/temp/generated-code sizes and the peak-memory estimate
(``compiled.memory_analysis()``). Every compile funnel reports here —
the fused-fit trainers (``parallel/dp.py``, ``parallel/zero.py``),
``ServingEngine._plan`` (AOT bucket plans), and ``contrib.export`` — and
the numbers surface three ways:

- **/metrics gauges** — the ``devstats`` profiler hook renders per-program
  ``mxnet_devstats_<stat>{bucket="<program>"}`` series plus the native
  ``mxnet_recompiles_total`` counter and ``mxnet_devstats_mfu`` /
  ``mxnet_devstats_roofline_frac`` gauges;
- **per-step MFU/roofline** — trainers publish the step program's
  FLOPs/bytes per step; ``StepLogger`` calls :func:`step_sample` so each
  JSONL row carries ``mfu`` (achieved FLOP/s over the device's published
  peak, ``PEAKS`` keyed by ``device_kind``; null on the CPU) and
  ``roofline_frac`` (over the bandwidth-aware roofline ceiling);
- **HBM preflight** — when a device memory budget is known
  (``MXNET_DEVSTATS_HBM_BYTES``, or autodetected via PJRT
  ``memory_stats``), a plan whose estimated footprint does not fit
  raises :class:`HBMPreflightError` *before* dispatch — a sized,
  actionable error instead of a runtime OOM.

The **recompile sentinel** counts compiles per program at dispatch time
(``fn._cache_size()`` deltas) and, past ``MXNET_DEVSTATS_RECOMPILE_LIMIT``
compiles of one program, warns once and drops a ``recompile_storm`` event
into the crash flight recorder — the production generalization of
hloaudit's static ``recompile_max`` budget.

Hot-path cost: one cache-size read and a dict lookup per fused dispatch.
Extraction itself (an AOT ``lower().compile()`` of the same program) runs
on a daemon worker thread, memoized per program signature — except when a
memory budget is known, where the first dispatch pays a synchronous
compile so the preflight verdict lands before any device allocation.
``MXNET_DEVSTATS=0`` makes every entry point inert; the selftest proves
on/off fits bit-identical with overhead under the 2% gate:

    python -m mxnet_tpu.telemetry.devstats --selftest
"""

import json
import logging
import os
import queue
import threading
import time

from .. import config
from . import flightrec, tracing
from .registry import counter as _counter, gauge as _gauge

__all__ = [
    "HBMPreflightError", "enabled", "extract", "record_program",
    "program_stats", "on_dispatch", "drain", "counters", "peaks", "mfu",
    "roofline_frac", "set_step_costs", "step_costs", "step_sample",
    "fit_summary",
    "hbm_budget", "preflight", "note_compile", "note_compiles",
    "recompile_limit", "reset",
]

log = logging.getLogger("mxnet_tpu.devstats")

_LOCK = threading.RLock()
_PROGRAMS = {}       # name -> stats dict (extract() output + "kind")
_COMPILES = {}       # name -> compiles observed (sentinel input)
_STORMED = set()     # programs whose storm already fired
_STORMS = [0]
_CACHE_SIZES = {}    # name -> last fn._cache_size() seen at dispatch
_SIGS = {}           # name -> aval signature of the extracted program
_PENDING = set()     # names with an extraction in flight
_STEP = {"name": None, "flops": 0.0, "bytes": 0.0}   # per-step costs
_HOOKED = [False]
_AUTO_BUDGET = ["unset"]   # cached PJRT memory_stats autodetection
_QUEUE = None
_WORKER = None

# Published peaks of one chip, keyed by jax's `device_kind`: (bf16
# FLOP/s, HBM bytes/s). Source: Google Cloud documentation, "TPU v5e"
# (197 TFLOP/s bf16, 819 GB/s). The package's one peaks table (the planner
# reads it; the benchmark keeps its own, benchmarks/peaks.json). A TPU kind that is not here is an error, not a
# default; the CPU has no row, so a CPU run reports no MFU. Override with
# MXNET_DEVSTATS_PEAK_TFLOPS / MXNET_DEVSTATS_PEAK_GBPS.
PEAKS = {
    "TPU v5 lite": (197.0e12, 819.0e9),
}


class HBMPreflightError(RuntimeError):
    """A compiled plan's estimated HBM footprint exceeds the device
    memory budget. Raised before dispatch, with sizes in the message."""


def enabled():
    """Live MXNET_DEVSTATS flag (default on; ``0`` is fully inert)."""
    return bool(config.get("MXNET_DEVSTATS"))


def recompile_limit():
    """Sentinel threshold: compiles of one program past this warn +
    flight-record (``MXNET_DEVSTATS_RECOMPILE_LIMIT``, <=0 disables)."""
    return int(config.get("MXNET_DEVSTATS_RECOMPILE_LIMIT"))


# ---------------------------------------------------------------- extraction

def extract(compiled):
    """Cost/memory analytics of a jax ``Compiled`` as a plain dict.

    Defensive against backend/version variance: ``cost_analysis()`` may
    return a dict or a one-element list; ``memory_analysis()`` fields are
    read via getattr with 0 defaults; anything that raises contributes
    zeros. ``peak_bytes`` is the max of the backend's own peak estimate
    and the args+outputs+temps+code sum net of donation aliasing."""
    out = {"flops": 0.0, "bytes_accessed": 0.0, "argument_bytes": 0,
           "output_bytes": 0, "temp_bytes": 0, "generated_code_bytes": 0,
           "alias_bytes": 0, "peak_bytes": 0}
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        if isinstance(ca, dict):
            out["flops"] = float(ca.get("flops", 0.0) or 0.0)
            out["bytes_accessed"] = float(
                ca.get("bytes accessed", 0.0) or 0.0)
    except Exception:
        pass
    peak = 0
    try:
        ma = compiled.memory_analysis()
        for key, attr in (
                ("argument_bytes", "argument_size_in_bytes"),
                ("output_bytes", "output_size_in_bytes"),
                ("temp_bytes", "temp_size_in_bytes"),
                ("generated_code_bytes", "generated_code_size_in_bytes"),
                ("alias_bytes", "alias_size_in_bytes")):
            try:
                out[key] = int(getattr(ma, attr, 0) or 0)
            except Exception:
                pass
        try:
            peak = int(getattr(ma, "peak_memory_in_bytes", 0) or 0)
        except Exception:
            peak = 0
    except Exception:
        pass
    footprint = (out["argument_bytes"] + out["output_bytes"]
                 + out["temp_bytes"] + out["generated_code_bytes"]
                 - out["alias_bytes"])
    out["peak_bytes"] = max(peak, footprint, 0)
    return out


def record_program(name, compiled=None, stats=None, kind="program"):
    """Record one program's analytics under `name`; returns the stats
    dict. Idempotent last-write-wins; registers the /metrics hook."""
    if stats is None:
        stats = extract(compiled)
    with _LOCK:
        _PROGRAMS[name] = dict(stats, kind=kind)
    _ensure_hook()
    return stats


def program_stats(name=None):
    """Snapshot of recorded program analytics (one dict, or all)."""
    with _LOCK:
        if name is not None:
            s = _PROGRAMS.get(name)
            return dict(s) if s else None
        return {k: dict(v) for k, v in _PROGRAMS.items()}


# -------------------------------------------------------- recompile sentinel

def note_compiles(name, total):
    """Sample an absolute compile count (e.g. ``fn._cache_size()``) for
    `name`; ticks the sentinel with the delta since the last sample."""
    with _LOCK:
        prev = _CACHE_SIZES.get(name, 0)
        _CACHE_SIZES[name] = max(prev, int(total))
        delta = int(total) - prev
    if delta > 0:
        note_compile(name, delta)


def _rec_counter():
    # registry get-or-create is thread-safe; never cached here so there
    # is no bare shared write and no devstats-lock -> registry-lock hold
    return _counter("mxnet_recompiles_total",
                    "XLA compiles beyond the first per traced program")


def note_compile(name, n=1):
    """Count `n` compiles of program `name`; warn + flight-record once
    when the per-program total crosses the sentinel limit."""
    if n <= 0:
        return
    _ensure_hook()
    _rec_counter().inc(n)
    limit = recompile_limit()
    storm = False
    with _LOCK:
        c = _COMPILES.get(name, 0) + n
        _COMPILES[name] = c
        if 0 < limit < c and name not in _STORMED:
            _STORMED.add(name)
            _STORMS[0] += 1
            storm = True
    if storm:
        log.warning(
            "devstats: recompile storm — program %r compiled %d times "
            "(limit %d). Shape/dtype churn is defeating the jit cache; "
            "pad or bucket inputs. (MXNET_DEVSTATS_RECOMPILE_LIMIT)",
            name, c, limit)
        flightrec.record("devstats", "recompile_storm", program=name,
                         compiles=c, limit=limit)


# ----------------------------------------------------------- peaks, MFU

def peaks():
    """(peak FLOP/s, peak bytes/s, source) of the device in use: the
    ``PEAKS`` row of its ``device_kind`` (source ``table:<kind>``), with
    ``MXNET_DEVSTATS_PEAK_TFLOPS`` / ``MXNET_DEVSTATS_PEAK_GBPS``
    overriding (source ``env``). The CPU has no row: (None, None,
    "none") unless both are set. An accelerator whose kind is not in the
    table raises — dividing by another chip's peak is a wrong number."""
    import jax
    dev = jax.devices()[0]
    if dev.device_kind in PEAKS:
        pf, pb = PEAKS[dev.device_kind]
        src = "table:%s" % dev.device_kind
    elif dev.platform == "cpu":
        pf, pb, src = None, None, "none"
    else:
        raise KeyError(
            f"devstats: no peaks row for device_kind {dev.device_kind!r} "
            f"(known: {sorted(PEAKS)}); add its published peaks to "
            f"devstats.PEAKS")
    tf = os.environ.get("MXNET_DEVSTATS_PEAK_TFLOPS")
    gb = os.environ.get("MXNET_DEVSTATS_PEAK_GBPS")
    if tf:
        pf, src = float(tf) * 1e12, "env"
    if gb:
        pb, src = float(gb) * 1e9, "env"
    if pf is None or pb is None:
        return None, None, "none"
    return pf, pb, src


def mfu(flops_per_s):
    """Model FLOPs utilization: achieved FLOP/s over the device's peak;
    None where the device has no peaks row (the CPU)."""
    pf, _, _ = peaks()
    return flops_per_s / pf if pf else None


def roofline_frac(flops_per_s, flops_per_step, bytes_per_step):
    """Attainment against the roofline ceiling for this program's
    arithmetic intensity: min(peak_flops, intensity * peak_bw); None
    where the device has no peaks row (the CPU)."""
    pf, pb, _ = peaks()
    if not pf:
        return None
    ceiling = pf
    if bytes_per_step > 0 and flops_per_step > 0:
        ceiling = min(pf, (flops_per_step / bytes_per_step) * pb)
    return flops_per_s / ceiling


def set_step_costs(name, flops_per_step, bytes_per_step):
    """Publish the active training-step program's per-step FLOPs/bytes
    (what StepLogger turns into MFU each step)."""
    with _LOCK:
        _STEP.update(name=name, flops=float(flops_per_step),
                     bytes=float(bytes_per_step))


def step_costs():
    with _LOCK:
        return dict(_STEP)


def fit_summary():
    """Run-end devstats digest for the fused trainers: the step
    program's identity, its per-step XLA costs, and the peak table in
    force — splatted into StepLogger.close(**fit_summary()) so the JSONL
    run_end record says what program the MFU numbers were measured
    against. {} when devstats is off or no step program was extracted
    (extraction is async; a very short fit may end before it lands)."""
    if not enabled():
        return {}
    costs = step_costs()
    if not costs.get("name") or costs.get("flops", 0.0) <= 0:
        return {}
    pf, pb, src = peaks()
    return {"devstats_program": costs["name"],
            "devstats_flops_per_step": costs["flops"],
            "devstats_bytes_per_step": costs["bytes"],
            "devstats_peak_flops_per_s": pf,
            "devstats_peak_bytes_per_s": pb,
            "devstats_peak_source": src}


def step_sample(wall_s, steps):
    """Per-step MFU/roofline fields for StepLogger, or None when off or
    no step program has been extracted yet. Host floats only; also sets
    the mxnet_devstats_mfu / _roofline_frac gauges."""
    if not enabled():
        return None
    with _LOCK:
        f, b = _STEP["flops"], _STEP["bytes"]
    if f <= 0 or wall_s <= 0 or steps <= 0:
        return None
    fps = f * steps / wall_s
    _ensure_hook()
    _gauge("mxnet_devstats_model_flops_per_s",
           "achieved model FLOP/s").set(fps)
    m = mfu(fps)
    if m is None:       # no peaks row (the CPU): a rate, never an MFU
        return {"mfu": None, "roofline_frac": None,
                "model_flops_per_s": fps}
    rf = roofline_frac(fps, f, b)
    _gauge("mxnet_devstats_mfu",
           "achieved FLOP/s over device peak").set(m)
    _gauge("mxnet_devstats_roofline_frac",
           "achieved FLOP/s over roofline ceiling").set(rf)
    return {"mfu": round(m, 6), "roofline_frac": round(rf, 6),
            "model_flops_per_s": fps}


# ----------------------------------------------------------- HBM preflight

def hbm_budget():
    """Device memory budget in bytes: ``MXNET_DEVSTATS_HBM_BYTES`` if
    set, else PJRT ``memory_stats()['bytes_limit']`` where the backend
    exposes it (TPU/GPU do; cpu does not → None, preflight inert)."""
    raw = os.environ.get("MXNET_DEVSTATS_HBM_BYTES")
    if raw:
        try:
            return int(float(raw))
        except ValueError:
            pass
    with _LOCK:
        cached = _AUTO_BUDGET[0]
    if cached != "unset":
        return cached
    val = None
    try:
        import jax
        for d in jax.local_devices():
            ms = d.memory_stats()
            if ms and ms.get("bytes_limit"):
                val = int(ms["bytes_limit"])
                break
    except Exception:
        val = None
    with _LOCK:
        _AUTO_BUDGET[0] = val
    return val


def _mib(n):
    n = float(n)
    for unit, width in (("GiB", 1024.0 ** 3), ("MiB", 1024.0 ** 2),
                        ("KiB", 1024.0)):
        if abs(n) >= width:
            return "%.1f %s" % (n / width, unit)
    return "%d B" % int(n)


def preflight(name, need_bytes, resident_bytes=0, budget=None, what="plan"):
    """Check an estimated footprint against the HBM budget *before*
    dispatch. Returns headroom bytes (or None when no budget is known);
    raises :class:`HBMPreflightError` — sized and actionable — when the
    plan does not fit."""
    if budget is None:
        budget = hbm_budget()
    if budget is None:
        return None
    total = int(need_bytes) + int(resident_bytes)
    if total > budget:
        raise HBMPreflightError(
            "HBM preflight: %s %r needs %s (estimated peak %s + %s "
            "already resident) but the device memory budget is %s — "
            "over by %s. Shrink the batch/bucket, evict cached plans, "
            "or raise MXNET_DEVSTATS_HBM_BYTES if the budget is wrong."
            % (what, name, _mib(total), _mib(need_bytes),
               _mib(resident_bytes), _mib(budget), _mib(total - budget)))
    return budget - total


# ------------------------------------------------- dispatch-funnel wiring

def _sds_of(args):
    """ShapeDtypeStructs mirroring `args` (metadata only — never holds
    buffers, safe to capture across donation)."""
    import jax

    def one(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype)
    return jax.tree_util.tree_map(one, args)


def _sig_of(sds):
    import jax
    leaves = jax.tree_util.tree_leaves(sds)
    return tuple((tuple(l.shape), str(l.dtype)) for l in leaves)


def on_dispatch(name, fn, args, steps=None, kind="fit"):
    """Trainer hot-path hook, called once per fused dispatch just before
    ``fn(*args)``. Cost when already recorded: one ``_cache_size()``
    read + a dict compare. On the first dispatch of a program (or after
    a recompile) it snapshots ShapeDtypeStructs and extracts analytics —
    asynchronously, unless a memory budget is known, in which case the
    compile+preflight runs synchronously so HBMPreflightError lands
    before any device allocation. Never raises anything else."""
    try:
        if not enabled():
            return
        try:
            cache = int(fn._cache_size())
        except Exception:
            cache = None
        fresh = False
        with _LOCK:
            if cache is None:
                fresh = name not in _SIGS and name not in _PENDING
            else:
                prev = _CACHE_SIZES.get(name)
                if prev is None:
                    # first dispatch: it will compile once — pre-credit
                    # that compile so steady state never re-extracts and
                    # "recompiles" means compiles beyond the first
                    _CACHE_SIZES[name] = cache + 1
                    fresh = True
                elif cache > prev:
                    _CACHE_SIZES[name] = cache
                    fresh = True
            if fresh and name in _PENDING:
                fresh = False
            elif fresh:
                _PENDING.add(name)
        if cache is not None:
            with _LOCK:
                counted = _COMPILES.get(name, 0)
            delta = cache - 1 - counted   # first compile is pre-credited
            if delta > 0:
                note_compile(name, delta)
        if not fresh:
            return
        try:
            sds = _sds_of(args)
        except Exception:
            with _LOCK:
                _PENDING.discard(name)
            return
        if hbm_budget() is not None:
            try:
                _run_extraction(name, fn, sds, steps, kind,
                                do_preflight=True)
            finally:
                with _LOCK:
                    _PENDING.discard(name)
        else:
            _submit((name, fn, sds, steps, kind))
    except HBMPreflightError:
        raise
    except Exception:
        log.debug("devstats.on_dispatch failed for %r", name, exc_info=True)


def _run_extraction(name, fn, sds, steps, kind, do_preflight=False):
    sig = _sig_of(sds)
    with _LOCK:
        if _SIGS.get(name) == sig and not do_preflight:
            return
    # the span tells this second trace, lowering and compile-or-load of
    # the program (its `compile.*` children) from the jit call's own
    with tracing.span("devstats.extract", program=name):
        compiled = fn.lower(*sds).compile()
        stats = record_program(name, compiled=compiled, kind=kind)
    with _LOCK:
        _SIGS[name] = sig
    if steps:
        set_step_costs(name, stats["flops"] / steps,
                       stats["bytes_accessed"] / steps)
    if do_preflight:
        preflight(name, stats["peak_bytes"], what="fused %s plan" % kind)


def _worker_loop():
    while True:
        task = _QUEUE.get()
        try:
            _run_extraction(*task)
        except Exception:
            log.debug("devstats extraction failed for %r", task[0],
                      exc_info=True)
        finally:
            with _LOCK:
                _PENDING.discard(task[0])
            _QUEUE.task_done()


def _submit(task):
    global _QUEUE, _WORKER
    with _LOCK:
        if _QUEUE is None:
            _QUEUE = queue.Queue()
        if _WORKER is None or not _WORKER.is_alive():
            _WORKER = threading.Thread(target=_worker_loop, daemon=True,
                                       name="mxnet-devstats")
            _WORKER.start()
    _QUEUE.put(task)


def drain(timeout=30.0):
    """Block until pending async extractions finish (tests/selftest).
    Returns True when the queue drained inside the deadline."""
    if _QUEUE is None:
        return True
    deadline = time.time() + timeout
    while time.time() < deadline:
        with _LOCK:
            busy = bool(_PENDING)
        if _QUEUE.unfinished_tasks == 0 and not busy:
            return True
        time.sleep(0.01)
    return _QUEUE.unfinished_tasks == 0


# ------------------------------------------------------------ /metrics hook

def counters():
    """The ``devstats`` profiler-hook payload: flattened by the registry
    into ``mxnet_devstats_<stat>`` gauges, per-program dicts becoming
    ``{bucket="<program>"}`` labeled series."""
    pf, pb, _ = peaks()
    with _LOCK:
        progs = {k: dict(v) for k, v in _PROGRAMS.items()}
        compiles = dict(_COMPILES)
        storms = _STORMS[0]
    out = {
        "programs": len(progs),
        "recompile_storms": storms,
        "hbm_budget_bytes": hbm_budget() or 0,
        "peak_flops_per_s": pf or 0,
        "peak_bytes_per_s": pb or 0,
        "recompiles": compiles,
    }
    for stat in ("flops", "bytes_accessed", "peak_bytes", "argument_bytes",
                 "output_bytes", "temp_bytes", "generated_code_bytes"):
        series = {n: s.get(stat, 0) for n, s in progs.items()}
        if series:
            out[stat] = series
    return out


def _ensure_hook():
    with _LOCK:
        if _HOOKED[0]:
            return
        _HOOKED[0] = True
    _rec_counter()
    try:
        from .. import profiler
        profiler.register_counter_export("devstats", counters)
    except Exception:
        pass


def reset():
    """Test support: forget programs/compiles/step costs (native counters
    are monotonic and stay)."""
    with _LOCK:
        _PROGRAMS.clear()
        _COMPILES.clear()
        _STORMED.clear()
        _STORMS[0] = 0
        _CACHE_SIZES.clear()
        _SIGS.clear()
        _PENDING.clear()
        _STEP.update(name=None, flops=0.0, bytes=0.0)
        _AUTO_BUDGET[0] = "unset"


# ---------------------------------------------------------------- selftest

def _selftest(max_overhead_pct=2.0):
    """See module docstring; one JSON line + DEVSTATS-SELFTEST-OK/FAIL."""
    import numpy as np

    from . import devstats as ds     # canonical module (not __main__)
    from .registry import get_registry

    results = {}
    failures = []

    def check(ok, what):
        results[what] = bool(ok)
        if not ok:
            failures.append(what)

    import jax
    import jax.numpy as jnp

    # 1 — extraction matches hand-computed FLOPs on a known matmul
    n = 192
    f = jax.jit(lambda a, b: a @ b)
    sds = jax.ShapeDtypeStruct((n, n), jnp.float32)
    stats = ds.record_program("selftest.matmul",
                              compiled=f.lower(sds, sds).compile())
    hand = 2.0 * n * n * n
    ratio = stats["flops"] / hand if hand else 0.0
    results["matmul_flops_ratio"] = round(ratio, 4)
    check(0.5 <= ratio <= 1.5, "matmul_flops_within_tolerance")
    check(stats["argument_bytes"] == 2 * n * n * 4, "argument_bytes_exact")

    # 2 — MFU/roofline arithmetic under pinned env peaks
    os.environ["MXNET_DEVSTATS_PEAK_TFLOPS"] = "1.0"
    os.environ["MXNET_DEVSTATS_PEAK_GBPS"] = "100.0"
    try:
        pf, pb, src = ds.peaks()
        check(pf == 1.0e12 and pb == 1.0e11 and src == "env",
              "peaks_env_override")
        ds.set_step_costs("selftest.step", 5.0e9, 1.0e9)
        s = ds.step_sample(wall_s=0.01, steps=2)
        # fps = 5e9*2/0.01 = 1e12 → mfu 1.0; ceiling = min(1e12, 5*1e11)
        check(s and abs(s["mfu"] - 1.0) < 1e-6, "mfu_arithmetic")
        check(s and abs(s["roofline_frac"] - 2.0) < 1e-6,
              "roofline_arithmetic")
    finally:
        os.environ.pop("MXNET_DEVSTATS_PEAK_TFLOPS", None)
        os.environ.pop("MXNET_DEVSTATS_PEAK_GBPS", None)

    # 3 — preflight accepts under budget, rejects over it, sized message
    ok_headroom = ds.preflight("small", 1000, budget=4096)
    rejected = False
    msg = ""
    try:
        ds.preflight("big", 8192, resident_bytes=1024, budget=4096)
    except ds.HBMPreflightError as e:
        rejected = True
        msg = str(e)
    check(ok_headroom == 3096, "preflight_accepts_under_budget")
    check(rejected and "9.0 KiB" in msg and "over by" in msg
          and "MXNET_DEVSTATS_HBM_BYTES" in msg,
          "preflight_rejects_with_sized_error")

    # 4 — sentinel fires on a forced shape-churn loop
    os.environ["MXNET_DEVSTATS_RECOMPILE_LIMIT"] = "4"
    try:
        churn = jax.jit(lambda x: x * 2.0)
        for i in range(1, 9):
            churn(np.zeros((i,), np.float32))
            ds.note_compiles("selftest.churn", int(churn._cache_size()))
        snap = ds.counters()
        check(snap["recompiles"].get("selftest.churn", 0) >= 8,
              "sentinel_counted_churn_compiles")
        check(snap["recompile_storms"] >= 1, "sentinel_storm_fired")
        ev = [e for e in flightrec.snapshot()
              if e.get("name") == "recompile_storm"]
        check(len(ev) == 1 and ev[0].get("program") == "selftest.churn",
              "sentinel_flightrec_event_once")
    finally:
        os.environ.pop("MXNET_DEVSTATS_RECOMPILE_LIMIT", None)

    # 5 — fit funnel: gauges + per-step MFU appear after a fused fit
    net, data = _build_fit()
    snap0 = _snap_params(net)
    params_on = _fit_once(net, data, snap0)
    ds.drain(60.0)
    # second fit: extraction has landed, so every step samples MFU
    params_on = _fit_once(net, data, snap0)
    text = get_registry().render_prometheus()
    check('mxnet_devstats_flops{bucket="dp.step' in text,
          "fit_program_gauges_on_metrics")
    check("mxnet_recompiles_total" in text, "recompiles_counter_on_metrics")
    check("mxnet_devstats_model_flops_per_s" in text,
          "model_flops_gauge_on_metrics")
    costs = ds.step_costs()
    check(costs["flops"] > 0, "fit_step_costs_published")

    # 6 — serving funnel: AOT plan gauges + resident-bytes accounting,
    #     then a tiny synthetic budget rejects the next bucket admit
    serving = _serve_once(ds, check)
    results.update(serving)

    # 7 — on/off bit-identical, overhead under the gate (min-of-N:
    # the minimum over 4 runs per arm hides the once-per-process async
    # extraction compile; 3 attempts ride out host noise)
    params_off = None
    overhead_pct = None
    for _ in range(3):
        on_t, off_t = [], []
        for _ in range(4):
            t0 = time.perf_counter()
            params_on = _fit_once(net, data, snap0)
            on_t.append(time.perf_counter() - t0)
            os.environ["MXNET_DEVSTATS"] = "0"
            try:
                t0 = time.perf_counter()
                params_off = _fit_once(net, data, snap0)
                off_t.append(time.perf_counter() - t0)
            finally:
                os.environ.pop("MXNET_DEVSTATS", None)
        ds.drain(60.0)
        overhead_pct = 100.0 * (min(on_t) - min(off_t)) / min(off_t)
        if overhead_pct <= max_overhead_pct:
            break
    results["overhead_pct"] = round(overhead_pct, 3)
    check(overhead_pct <= max_overhead_pct, "overhead_under_gate")
    same = (sorted(params_on) == sorted(params_off)
            and all(np.array_equal(params_on[k], params_off[k])
                    for k in params_on))
    check(same, "on_off_bit_identical")

    results["failures"] = failures
    results["ok"] = not failures
    print(json.dumps(results, sort_keys=True))
    print("DEVSTATS-SELFTEST-%s" % ("OK" if not failures else
                                    "FAIL: %s" % ", ".join(failures)))
    return 0 if not failures else 1


def _build_fit():
    """Tiny deterministic gluon net + loader for the A/B fit arms."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.ndarray.ndarray import array as nd_array

    rng = np.random.RandomState(7)
    x = rng.uniform(-1, 1, (256, 8)).astype(np.float32)
    y = rng.randint(0, 4, (256,)).astype(np.float32)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(16, activation="relu"))
    net.add(gluon.nn.Dense(4))
    net.initialize(mx.init.Xavier())
    net(nd_array(x[:32]))       # finish deferred init
    data = gluon.data.DataLoader(gluon.data.ArrayDataset(x, y),
                                 batch_size=32, shuffle=False)
    return net, data


def _snap_params(net):
    import numpy as np
    return {n: np.asarray(p.data().asnumpy()).copy()
            for n, p in net.collect_params().items()}


def _fit_once(net, data, snap0):
    """One fused fit from the snapshotted initial params; returns the
    final params as host arrays (the bit-identical A/B payload)."""
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.trainer import fused_fit
    from mxnet_tpu.ndarray.ndarray import array as nd_array

    for n, p in net.collect_params().items():
        p.set_data(nd_array(snap0[n]))
    loss = gluon.loss.SoftmaxCrossEntropyLoss()
    fused_fit(net, loss, data, num_epoch=1, optimizer="sgd",
              optimizer_params={"learning_rate": 0.05},
              steps_per_dispatch=4)
    return _snap_params(net)


def _serve_once(ds, check):
    """Admit two serving buckets, verify devstats gauges + engine
    resident-bytes accounting, then force a preflight rejection with a
    256-byte synthetic budget."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.serving import ServingEngine

    out = {}
    sym = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=4,
                              name="fc"), name="softmax")
    mod = mx.mod.Module(sym, context=mx.cpu(0))
    mod.bind(data_shapes=[("data", (8, 8))],
             label_shapes=[("softmax_label", (8,))])
    mod.init_params(mx.init.Xavier())
    args, auxs = mod.get_params()
    eng = ServingEngine.from_symbol(sym, args, auxs, {"data": (8, 8)},
                                    warmup=False)
    eng.infer(np.zeros((3, 8), np.float32))      # admits bucket 4
    eng.infer(np.zeros((7, 8), np.float32))      # admits bucket 8
    st = eng.stats()
    check(st.get("plan_resident_bytes", 0) > 0 and st.get("plans") == 2
          and st["plan_resident_bytes"] == sum(st["plan_bytes"].values()),
          "serving_resident_bytes_accounted")
    snap = ds.counters()
    serve_progs = [k for k in snap.get("flops", {})
                   if k.startswith("serving.")]
    check(len(serve_progs) >= 2, "serving_program_gauges")
    out["serving_plans"] = st.get("plans")
    out["serving_resident_bytes"] = st.get("plan_resident_bytes")
    # an oversized plan (vs a 256-byte synthetic budget) is shed with a
    # sized error before it is admitted to the cache
    os.environ["MXNET_DEVSTATS_HBM_BYTES"] = "256"
    try:
        eng2 = ServingEngine.from_symbol(sym, args, auxs,
                                         {"data": (8, 8)}, warmup=False)
        rejected = False
        msg = ""
        try:
            eng2.infer(np.zeros((2, 8), np.float32))
        except ds.HBMPreflightError as e:
            rejected = True
            msg = str(e)
        check(rejected and "256 B" in msg and "over by" in msg,
              "serving_preflight_rejects_oversized_plan")
        check(not eng2._plans and eng2.plan_resident_bytes == 0,
              "rejected_plan_not_admitted")
    finally:
        os.environ.pop("MXNET_DEVSTATS_HBM_BYTES", None)
    return out


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser(prog="mxnet_tpu.telemetry.devstats")
    ap.add_argument("--selftest", action="store_true")
    ap.add_argument("--max-overhead-pct", type=float, default=2.0)
    ns = ap.parse_args(argv)
    if not ns.selftest:
        ap.print_help()
        return 0
    config.pin_cpu(2)     # like the other telemetry selftests
    from mxnet_tpu.telemetry import devstats as canonical
    return canonical._selftest(max_overhead_pct=ns.max_overhead_pct)


if __name__ == "__main__":
    raise SystemExit(main())
