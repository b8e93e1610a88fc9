#!/usr/bin/env bash
# CI entry point (role of the reference's Jenkinsfile stages: sanity,
# build, unit tests, nightly).
#
#   tools/ci.sh quick    — install + 30s cross-subsystem smoke tier
#   tools/ci.sh full     — install + full CPU-mesh suite (~15 min)
#   tools/ci.sh tpu      — real-chip lane (needs a TPU backend)
#
# All stages run on the 8-device virtual CPU mesh except tpu.
set -euo pipefail
cd "$(dirname "$0")/.."

stage="${1:-quick}"

echo "== install (editable, offline-safe)"
pip install -e . --no-deps --no-build-isolation -q

echo "== compile check (native runtime + package import)"
python - <<'EOF'
import mxnet_tpu as mx
from mxnet_tpu import _native
print("package:", mx.__name__, "| native lib:",
      "ok" if _native.lib() is not None else "python-fallback")
EOF

case "$stage" in
  quick)
    python -m pytest tests/ -m quick -q
    echo "== serving smoke (dynamic-batching selftest, tiny convnet)"
    JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
      python -m mxnet_tpu.serving --selftest --requests 128
    echo "== serving frontend smoke (HTTP tier: 64 clients, shed order, LRU)"
    JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
      python -m mxnet_tpu.serving.frontend --selftest --requests 192
    echo "== device-feed smoke (async pipeline overlap selftest)"
    JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
      python -m mxnet_tpu.pipeline --selftest
    echo "== amp smoke (autocast no-op / bf16 convergence / fp16 scaler)"
    JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
      python -m mxnet_tpu.amp --selftest
    echo "== checkpoint smoke (crash injection: SIGKILL mid-commit, resume)"
    JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
      python -m mxnet_tpu.checkpoint --selftest
    echo "== elastic checkpoint smoke (SIGKILL at 4 devices, resume at 2)"
    JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
      python -m mxnet_tpu.checkpoint --selftest --elastic \
        --devices-a 4 --devices-b 2
    echo "== telemetry smoke (registry/scrape/JSONL/overhead/watchdog)"
    JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
      python -m mxnet_tpu.telemetry --selftest
    echo "== tracing smoke (spans/ring/shard merge/flight recorder)"
    JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
      python -m mxnet_tpu.telemetry.tracing --selftest
    echo "== devstats smoke (XLA cost/memory, MFU, preflight, sentinel)"
    JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
      python -m mxnet_tpu.telemetry.devstats --selftest
    echo "== cluster smoke (2-proc gang: barrier, kill injection, resume)"
    JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
      python -m mxnet_tpu.cluster --selftest --nprocs 2
    echo "== supervisor smoke (self-healing at N=3: SIGKILL'd rank + coordinator auto-restart, shrink, give-up)"
    JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
      python -m mxnet_tpu.cluster --selftest --supervise
    echo "== zero smoke (ZeRO-1 bitwise parity, fp8 convergence, HLO wire)"
    JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
      python -m mxnet_tpu.parallel.zero --selftest
    echo "== embedding smoke (row-sparse exchange parity, resume, HLO wire)"
    JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
      python -m mxnet_tpu.parallel.embedding --selftest
    echo "== decode smoke (continuous batching: 8 staggered sessions, bit-identical, faster than sequential)"
    JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
      python -m mxnet_tpu.serving.decode --selftest
    echo "== planner smoke (determinism, HBM pruning, degenerate parity, ZeRO-over-dp×tp)"
    JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
      python -m mxnet_tpu.parallel.planner --selftest
    echo "== static analysis (tracelint/locklint/commlint/leaklint/configlint/hloaudit, --strict gate)"
    JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
      python -m mxnet_tpu.analysis --strict ;;
  full)
    python -m pytest tests/ -q ;;
  tpu)
    # needs the chip: each is its own process, one after the other
    python chip_smoke.py
    python -m pytest tests_tpu/ -q ;;
  *)
    echo "unknown stage: $stage (quick|full|tpu)" >&2; exit 2 ;;
esac
echo "== ci stage '$stage' green"
