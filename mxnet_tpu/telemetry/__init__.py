"""mxnet_tpu.telemetry — unified observability for the whole framework.

Beyond-reference subsystem (docs/TELEMETRY.md). Four pieces:

  - **registry** (registry.py): always-on Counter/Gauge/Histogram store,
    host-side only (no device syncs), that additionally absorbs every
    `profiler.register_counter_export` hook — serving, device_feed,
    checkpoint, amp — so all subsystem counters flow through one place.
    `profiler.dump()` keeps embedding the merged snapshot (the registry
    exports itself back as the "telemetry" hook).
  - **exporter** (exporter.py): stdlib HTTP server; Prometheus text
    exposition at `/metrics`, JSON `/healthz`.
    `telemetry.start_server(port)` or `MXNET_TELEMETRY_PORT=<port>`.
  - **step telemetry** (steplog.py): `StepLogger` threaded through
    BaseModule.fit / Module._fit_fused / gluon fused_fit — per-step wall
    time, samples/s, loss, amp scale/skips, DeviceFeed overlap,
    checkpoint save/wait time; JSONL event log via
    `MXNET_TELEMETRY_LOG=<path>`; `MXNET_TELEMETRY=0` turns recording off.
  - **hang diagnostics** (watchdog.py): stall watchdog
    (`MXNET_TELEMETRY_STALL_S`) dumping all-thread stacks when a step
    stalls, SIGUSR1 on-demand dumps, and deadline dumps for budgeted
    harnesses. Stall dumps append the flight-recorder tail.
  - **span tracing** (tracing.py): `MXNET_TRACE=1` host-side spans over
    feed/compute/comm/ckpt/serve phases, per-rank `trace-rank-K.json`
    chrome-trace shards with clock metadata, and `--merge` fusing a
    gang's shards into one pod timeline with a critical-path summary.
  - **flight recorder** (flightrec.py): always-on bounded ring of recent
    spans/events dumped as a per-rank black box on DistRankFailure,
    watchdog stall, uncaught exception, or SIGTERM; the cluster launcher
    collects the boxes and names the rank that went quiet first.
  - **device efficiency** (devstats.py): XLA cost/memory analytics from
    every compile funnel (fused trainers, serving bucket plans, export)
    as `mxnet_devstats_*` gauges; per-step MFU/roofline attainment in
    the steplog; an HBM preflight that rejects oversized plans with a
    sized error before dispatch; and a recompile sentinel
    (`mxnet_recompiles_total`, flight-recorder storm events).
    `MXNET_DEVSTATS=0` turns it off (bit-identical either way).

Selftest: `python -m mxnet_tpu.telemetry --selftest` runs a short fit
with the server up, scrapes itself, asserts every subsystem's counters
appear, A/B-checks telemetry-on vs -off overhead (< 2%) with bit-identical
params, and proves the stall watchdog dumps stacks.
"""
from __future__ import annotations

from .registry import (Counter, Gauge, Histogram, Registry, counter, gauge,
                       get_registry, histogram)
from .exporter import TelemetryServer, get_server, start_server, stop_server
from .steplog import StepLogger, enabled, log_event, maybe_step_logger
from . import watchdog
from . import tracing
from . import flightrec
from . import devstats
from .watchdog import install as install_watchdog
from .tracing import span, traced

__all__ = ["Counter", "Gauge", "Histogram", "Registry", "counter", "gauge",
           "histogram", "get_registry", "TelemetryServer", "start_server",
           "stop_server", "get_server", "StepLogger", "maybe_step_logger",
           "enabled", "log_event", "watchdog", "install_watchdog",
           "tracing", "flightrec", "devstats", "span", "traced"]
