"""telemetry.devstats: XLA cost/memory extraction, registry gauge
shapes, HBM preflight boundaries, the recompile sentinel, MFU/roofline
arithmetic, and serving plan-cache resident-bytes accounting."""
import os
import tempfile

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.telemetry import devstats, flightrec
from mxnet_tpu.telemetry.registry import get_registry


@pytest.fixture(autouse=True)
def _fresh_devstats(monkeypatch):
    monkeypatch.setenv("MXNET_DEVSTATS", "1")
    devstats.reset()
    yield
    devstats.reset()


def test_extract_matmul_flops_and_registry_gauge_shapes():
    n = 64
    f = jax.jit(lambda a, b: a @ b)
    sds = jax.ShapeDtypeStruct((n, n), jnp.float32)
    stats = devstats.record_program(
        "test.matmul", compiled=f.lower(sds, sds).compile())
    # XLA's own count of an n*n matmul is 2n^3 (tolerance for fusion)
    assert 0.5 <= stats["flops"] / (2.0 * n ** 3) <= 1.5
    assert stats["argument_bytes"] == 2 * n * n * 4
    assert stats["peak_bytes"] >= stats["argument_bytes"]
    # the devstats hook renders per-program labeled gauge series plus
    # the native recompile counter
    text = get_registry().render_prometheus()
    assert 'mxnet_devstats_flops{bucket="test.matmul"}' in text
    assert 'mxnet_devstats_peak_bytes{bucket="test.matmul"}' in text
    assert 'mxnet_devstats_argument_bytes{bucket="test.matmul"}' in text
    assert "mxnet_recompiles_total" in text
    assert "mxnet_devstats_hbm_budget_bytes" in text


def test_preflight_accept_reject_boundaries():
    # exactly at budget: accepted, zero headroom
    assert devstats.preflight("fit", 4096, budget=4096) == 0
    assert devstats.preflight("fit", 3000, resident_bytes=1096,
                              budget=4096) == 0
    assert devstats.preflight("fit", 1000, budget=4096) == 3096
    # one byte over: rejected with a sized, actionable message
    with pytest.raises(devstats.HBMPreflightError) as ei:
        devstats.preflight("big", 4097, budget=4096)
    msg = str(ei.value)
    assert "over by" in msg and "MXNET_DEVSTATS_HBM_BYTES" in msg
    with pytest.raises(devstats.HBMPreflightError) as ei:
        devstats.preflight("big", 8192, resident_bytes=1024, budget=4096)
    assert "9.0 KiB" in str(ei.value)
    # no budget known (cpu: no PJRT bytes_limit) -> preflight is inert
    assert devstats.preflight("anything", 1 << 40, budget=None) is None


def test_hbm_budget_env_override(monkeypatch):
    monkeypatch.setenv("MXNET_DEVSTATS_HBM_BYTES", "12345")
    assert devstats.hbm_budget() == 12345
    monkeypatch.setenv("MXNET_DEVSTATS_HBM_BYTES", "2e9")
    assert devstats.hbm_budget() == 2_000_000_000


def test_recompile_sentinel_threshold(monkeypatch):
    monkeypatch.setenv("MXNET_DEVSTATS_RECOMPILE_LIMIT", "3")
    monkeypatch.setenv("MXNET_FLIGHTREC", "1")
    flightrec.reset()
    # at the limit: counted, no storm yet
    devstats.note_compile("test.churn", 3)
    snap = devstats.counters()
    assert snap["recompiles"]["test.churn"] == 3
    assert snap["recompile_storms"] == 0
    # crossing the limit: exactly one storm + one flight-recorder event,
    # however many more compiles follow
    devstats.note_compile("test.churn")
    devstats.note_compile("test.churn", 5)
    snap = devstats.counters()
    assert snap["recompiles"]["test.churn"] == 9
    assert snap["recompile_storms"] == 1
    evs = [e for e in flightrec.snapshot()
           if e.get("name") == "recompile_storm"]
    assert len(evs) == 1 and evs[0]["program"] == "test.churn"
    # absolute cache-size sampling converts to deltas
    devstats.note_compiles("test.abs", 2)
    devstats.note_compiles("test.abs", 5)
    devstats.note_compiles("test.abs", 5)     # no growth, no tick
    assert devstats.counters()["recompiles"]["test.abs"] == 5


def test_mfu_and_roofline_arithmetic(monkeypatch):
    monkeypatch.setenv("MXNET_DEVSTATS_PEAK_TFLOPS", "1.0")
    monkeypatch.setenv("MXNET_DEVSTATS_PEAK_GBPS", "100.0")
    pf, pb, src = devstats.peaks()
    assert (pf, pb, src) == (1.0e12, 1.0e11, "env")
    assert devstats.mfu(5.0e11) == pytest.approx(0.5)
    # intensity 1 FLOP/byte -> ceiling is bandwidth-bound at 1e11 FLOP/s
    assert devstats.roofline_frac(5.0e10, 100.0, 100.0) \
        == pytest.approx(0.5)
    # compute-bound program: ceiling is the FLOP peak
    assert devstats.roofline_frac(5.0e11, 1000.0, 1.0) \
        == pytest.approx(0.5)
    # step_sample: 5 GFLOP/step x 2 steps / 10 ms = 1e12 FLOP/s
    devstats.set_step_costs("test.step", 5.0e9, 1.0e9)
    s = devstats.step_sample(wall_s=0.01, steps=2)
    assert s["mfu"] == pytest.approx(1.0)
    assert s["model_flops_per_s"] == pytest.approx(1.0e12)
    # fit_summary mirrors the published step costs for run_end records
    summ = devstats.fit_summary()
    assert summ["devstats_program"] == "test.step"
    assert summ["devstats_flops_per_step"] == pytest.approx(5.0e9)
    assert summ["devstats_peak_source"] == "env"


class _Dev:
    def __init__(self, platform, device_kind):
        self.platform, self.device_kind = platform, device_kind


@pytest.mark.parametrize("platform,kind,want", [
    ("tpu", "TPU v5 lite", (197.0e12, 819.0e9, "table:TPU v5 lite")),
    ("cpu", "cpu", (None, None, "none")),
    ("tpu", "TPU v9 imaginary", KeyError),
])
def test_peaks_keyed_by_device_kind(monkeypatch, platform, kind, want):
    """One table keyed by device_kind: the v5e row for a v5e, no row
    (and so no MFU) for the CPU, an error for a TPU it does not know."""
    import jax
    monkeypatch.setattr(jax, "devices", lambda *a: [_Dev(platform, kind)])
    if want is KeyError:
        with pytest.raises(KeyError, match="TPU v9 imaginary"):
            devstats.peaks()
        return
    assert devstats.peaks() == want
    if want[0] is None:
        assert devstats.mfu(1.0e12) is None
        assert devstats.roofline_frac(1.0e12, 1.0, 1.0) is None
        devstats.set_step_costs("test.cpu", 5.0e9, 1.0e9)
        s = devstats.step_sample(wall_s=0.01, steps=2)
        assert s["mfu"] is None and s["roofline_frac"] is None
        assert s["model_flops_per_s"] == pytest.approx(1.0e12)


def test_step_sample_off_and_without_costs(monkeypatch):
    assert devstats.step_sample(0.01, 1) is None      # no program yet
    devstats.set_step_costs("p", 1e9, 1e9)
    monkeypatch.setenv("MXNET_DEVSTATS", "0")
    assert devstats.step_sample(0.01, 1) is None      # master gate off
    assert devstats.fit_summary() == {}


def _tiny_engine(tmp_dir, budget_env=None, buckets=(4, 8)):
    from mxnet_tpu import symbol as sym
    from mxnet_tpu.serving.engine import ServingEngine
    data = sym.Variable("data")
    net = sym.FullyConnected(data, name="fc1", num_hidden=8)
    net = sym.SoftmaxOutput(net, name="softmax")
    rng = np.random.default_rng(0)
    arg = {"fc1_weight": rng.standard_normal((8, 6), dtype=np.float32),
           "fc1_bias": np.zeros(8, np.float32)}
    path = os.path.join(tmp_dir, "tinynet.mxa")
    return ServingEngine.from_symbol(net, arg, {}, {"data": (8, 6)},
                                     path=path, buckets=buckets,
                                     warmup=False)


def test_serving_resident_bytes_accounting_across_admits(tmp_path):
    eng = _tiny_engine(str(tmp_path))
    assert eng.model_name == "tinynet"
    assert eng.plan_resident_bytes == 0
    x = np.zeros((3, 6), np.float32)
    eng.infer(x)                       # admits bucket 4
    assert set(eng.plan_bytes) == {4}
    after_one = eng.plan_resident_bytes
    assert after_one == sum(eng.plan_bytes.values()) > 0
    eng.infer(np.zeros((6, 6), np.float32))   # admits bucket 8
    assert set(eng.plan_bytes) == {4, 8}
    assert eng.plan_resident_bytes == sum(eng.plan_bytes.values()) \
        > after_one
    eng.infer(x)                       # cached plan: no growth
    assert eng.plan_resident_bytes == sum(eng.plan_bytes.values())
    st = eng.stats()
    assert st["model"] == "tinynet"
    assert st["plan_resident_bytes"] == eng.plan_resident_bytes
    assert st["plans"] == 2
    # per-plan gauges on /metrics under the serving.b<bucket> programs
    text = get_registry().render_prometheus()
    assert 'mxnet_devstats_peak_bytes{bucket="serving.b4"}' in text
    assert 'mxnet_devstats_peak_bytes{bucket="serving.b8"}' in text


def test_serving_preflight_rejects_oversized_bucket(tmp_path, monkeypatch):
    # a budget below the smallest plan's peak: nothing gets admitted,
    # the cache stays empty, and the error names sizes + the knob
    monkeypatch.setenv("MXNET_DEVSTATS_HBM_BYTES", "256")
    eng = _tiny_engine(str(tmp_path))
    with pytest.raises(devstats.HBMPreflightError) as ei:
        eng.infer(np.zeros((3, 6), np.float32))
    msg = str(ei.value)
    assert "256 B" in msg and "over by" in msg
    assert eng.plan_bytes == {} and eng.plan_resident_bytes == 0


def test_batcher_labels_metrics_with_model_and_plan_bytes(tmp_path):
    from mxnet_tpu.serving.batcher import DynamicBatcher
    eng = _tiny_engine(str(tmp_path))
    b = DynamicBatcher(eng, max_wait_us=0)
    try:
        out = b.infer(np.zeros((3, 6), np.float32))
        assert out[0].shape == (3, 8)
        b._sync_plan_bytes()
        snap = b.metrics.snapshot()
        assert snap["model"] == "tinynet"
        assert snap["plan_resident_bytes"] == eng.plan_resident_bytes > 0
        assert snap["plans"] == len(eng.plan_bytes)
        text = get_registry().render_prometheus()
        line = [ln for ln in text.splitlines()
                if ln.startswith("mxnet_serving")
                and "plan_resident_bytes{" in ln]
        assert line and 'model="tinynet"' in line[0]
    finally:
        b.close()


def test_export_manifest_carries_model_name_and_devstats(tmp_path):
    import json
    import zipfile
    from mxnet_tpu import symbol as sym
    from mxnet_tpu.contrib.export import export_model
    data = sym.Variable("data")
    net = sym.FullyConnected(data, name="fc1", num_hidden=4)
    net = sym.SoftmaxOutput(net, name="softmax")
    rng = np.random.default_rng(0)
    arg = {"fc1_weight": rng.standard_normal((4, 6), dtype=np.float32),
           "fc1_bias": np.zeros(4, np.float32)}
    path = os.path.join(str(tmp_path), "exported.mxa")
    export_model(path, net, arg, {}, {"data": (8, 6)})
    with zipfile.ZipFile(path) as zf:
        man = json.loads(zf.read("MANIFEST.json"))
    assert man["model_name"] == "exported"
    ds = man.get("devstats")
    assert ds and ds["flops"] > 0 and ds["argument_bytes"] > 0
