"""mxnet_tpu.telemetry.tracing + flightrec — distributed span tracing,
cross-rank timeline merge, and the crash flight recorder (ISSUE 13).

Quick tier: span nesting/thread exactness, the bounded chrome-event
ring's drop accounting, synthetic 8-rank shard merge (clock alignment,
quiet/slowest rank naming, valid chrome JSON), steplog per-step phase
fields + overlap fractions, flight-recorder ring/dump/tail — all
jax-free or cheap.

Full tier adds: MXNET_TRACE=0 vs =1 bit-identical Module.fit (tracing
must never perturb numerics), the excepthook auto-dump, and the
watchdog dump carrying the flight tail; and (ISSUE 24) the spans of a
fused fit read back from a JAX profiler trace — name, thread, parent
and block number of each — with the counters and the serve.infer split
that came with them.

Slow tier (-m slow, Gloo backend): a real 2-rank gang with an injected
SIGKILL — every rank leaves a black box, the launcher's triage and the
merged trace timeline both name the victim.
"""
import json
import os
import sys
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import profiler
from mxnet_tpu.cluster import ClusterLauncher, cpu_collectives_available
from mxnet_tpu.telemetry import flightrec, tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

needs_gloo = pytest.mark.skipif(
    not cpu_collectives_available(),
    reason="jaxlib lacks the Gloo CPU cross-process collectives backend")


@pytest.fixture(autouse=True)
def _clean_slate():
    """Every test starts and ends with empty rings and phase totals, and
    leaves the process-wide ring capacity at its default."""
    profiler.clear_events()
    flightrec.reset()
    tracing.reset_phase_totals()
    yield
    profiler.set_max_events(200000)
    profiler.clear_events()
    flightrec.reset()
    tracing.reset_phase_totals()


def _trace_events():
    return [e for e in profiler.events_snapshot()
            if e.get("cat", "").startswith("trace:")]


# -- span core ---------------------------------------------------------------

def test_span_nesting_and_thread_stacks(monkeypatch):
    monkeypatch.setenv("MXNET_TRACE", "1")
    seen = {}

    def worker():
        with tracing.span("outer.t2", phase="compute"):
            seen["t2"] = tracing.current_stack()

    with tracing.span("outer", phase="compute", k=3):
        with tracing.span("inner", phase="feed"):
            seen["nested"] = tracing.current_stack()
        t = threading.Thread(target=worker)
        t.start()
        t.join()
    assert tracing.current_stack() == ()
    assert seen["nested"] == ("outer", "inner")
    # the worker thread's stack never saw this thread's open spans
    assert seen["t2"] == ("outer.t2",)

    byname = {e["name"]: e for e in _trace_events()}
    assert set(byname) == {"outer", "inner", "outer.t2"}
    outer, inner = byname["outer"], byname["inner"]
    assert outer["ph"] == "X" and outer["cat"] == "trace:compute"
    # child interval nests inside the parent's (1µs float slack)
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1.0
    assert byname["outer.t2"]["tid"] != outer["tid"]
    assert byname["outer"]["args"]["k"] == 3
    # exact phase accounting: 2 compute spans, 1 feed span
    assert tracing.phase_counts() == {"compute": 2, "feed": 1}
    totals = tracing.phase_totals()
    assert totals["compute"] > 0 and totals["feed"] > 0


def test_span_records_error_name_on_exception(monkeypatch):
    monkeypatch.setenv("MXNET_TRACE", "1")
    with pytest.raises(ValueError):
        with tracing.span("doomed", phase="compute"):
            raise ValueError("boom")
    (ev,) = _trace_events()
    assert ev["args"]["error"] == "ValueError"
    assert tracing.current_stack() == ()      # stack popped on the error


def test_trace_off_is_a_shared_noop(monkeypatch):
    monkeypatch.setenv("MXNET_TRACE", "0")
    monkeypatch.setenv("MXNET_FLIGHTREC", "0")
    s = tracing.span("ghost", phase="compute")
    assert s is tracing.span("ghost2")        # one shared null instance
    with s:
        assert tracing.current_stack() == ()
    tracing.event("ghost3", time.perf_counter(), phase="feed")
    assert _trace_events() == []
    assert tracing.phase_totals() == {}
    assert flightrec.stats()["total"] == 0


def test_retrospective_event_spans_interval(monkeypatch):
    monkeypatch.setenv("MXNET_TRACE", "1")
    t0 = time.perf_counter()
    time.sleep(0.002)
    tracing.event("queue.wait", t0, phase="serve", rows=4)
    (ev,) = _trace_events()
    assert ev["name"] == "queue.wait" and ev["cat"] == "trace:serve"
    assert ev["dur"] >= 1500.0                # at least ~1.5ms of the 2ms
    assert ev["args"]["rows"] == 4


# -- bounded event ring ------------------------------------------------------

def test_event_ring_bound_and_drop_accounting(monkeypatch):
    monkeypatch.setenv("MXNET_TRACE", "1")
    profiler.set_max_events(16)
    profiler.clear_events()
    for i in range(50):
        with tracing.span(f"burst{i}", phase="compute"):
            pass
    snap = profiler.events_snapshot()
    assert len(snap) == 16
    assert profiler.dropped_events() == 34
    # the survivors are the NEWEST events
    assert snap[-1]["name"] == "burst49"


def test_shard_dump_metadata(monkeypatch, tmp_path):
    monkeypatch.setenv("MXNET_TRACE", "1")
    with tracing.span("real.step", phase="compute"):
        time.sleep(0.001)
    p = tracing.dump(path=str(tmp_path / "trace-rank-0.json"))
    shard = json.loads(open(p, encoding="utf-8").read())
    meta = shard["metadata"]
    assert meta["rank"] == 0 and meta["version"] == 1
    assert "clock_offset_us" in meta and "phase_totals_us" in meta
    assert meta["dropped_events"] == 0
    names = [e["name"] for e in shard["traceEvents"]]
    assert "process_name" in names and "real.step" in names


# -- merge -------------------------------------------------------------------

def test_merge_aligns_clocks_and_names_victims(monkeypatch, tmp_path):
    d = str(tmp_path / "shards")
    tracing.synth_shards(d, ranks=8, steps=5, quiet_rank=3,
                         quiet_after_step=1, slow_rank=5)
    out, summary = tracing.merge(d)
    m = json.loads(open(out, encoding="utf-8").read())
    evs = m["traceEvents"]
    assert isinstance(evs, list) and evs
    # valid chrome-trace JSON: every event has ph+pid; complete events
    # carry ts/dur/tid and normalized non-negative timestamps
    assert all("ph" in e and "pid" in e for e in evs)
    xs = [e for e in evs if e["ph"] == "X"]
    assert xs and all(
        e["ts"] >= 0 and "dur" in e and "tid" in e for e in xs)
    assert sorted({e["pid"] for e in evs}) == list(range(8))
    # per-rank clock offset (100s+17s/rank) and skew (1ms/rank) undone:
    # the same step's feed spans land within 1µs across all 8 ranks
    step0 = [e for e in xs
             if (e.get("args") or {}).get("step") == 0
             and e["cat"] == "trace:feed"]
    assert len(step0) == 8
    assert max(e["ts"] for e in step0) - min(e["ts"] for e in step0) < 1.0
    assert summary["quiet_first"]["rank"] == 3
    assert summary["slowest_rank_per_phase"]["compute"]["rank"] == 5
    assert any(w["rank"] == 5 and w["phase"] == "compute"
               for w in summary["critical_path"])
    # the merge CLI (python -m mxnet_tpu.telemetry.tracing --merge /
    # tools/trace_merge.py) drives the same path
    assert tracing.main(["--merge", d,
                         "--out", str(tmp_path / "cli.json")]) == 0
    assert os.path.exists(tmp_path / "cli.json")


def test_merge_skew_correction_uses_metadata(tmp_path):
    # two ranks, same true timeline; rank 1's shard carries 1ms skew —
    # merge must subtract it, not average it away
    d = str(tmp_path / "two")
    tracing.synth_shards(d, ranks=2, steps=1)
    out, summary = tracing.merge(d)
    assert summary["ranks"] == [0, 1]
    assert summary["events"] == 6             # 3 phases x 2 ranks
    assert summary["dropped_events"] == 0


def test_merge_survives_missing_and_torn_shards(tmp_path):
    # post-mortem reality: rank 2 died before dumping (no shard), rank 3
    # was killed mid-write (truncated JSON) — merge the survivors and
    # say so, instead of raising on the first bad shard
    d = str(tmp_path / "wreck")
    tracing.synth_shards(d, ranks=4, steps=3)
    os.remove(os.path.join(d, "trace-rank-2.json"))
    p3 = os.path.join(d, "trace-rank-3.json")
    raw = open(p3, encoding="utf-8").read()
    open(p3, "w", encoding="utf-8").write(raw[: len(raw) // 2])
    out, summary = tracing.merge(d)
    assert summary["ranks"] == [0, 1]
    assert summary["missing_ranks"] == [2]
    assert [t["rank"] for t in summary["torn_shards"]] == [3]
    assert "JSONDecodeError" in summary["torn_shards"][0]["error"]
    # survivors fully merged (3 phases x 3 steps x 2 ranks)
    assert summary["events"] == 18
    m = json.loads(open(out, encoding="utf-8").read())
    assert sorted({e["pid"] for e in m["traceEvents"]}) == [0, 1]
    assert m["metadata"]["merged_from"] == 2
    txt = tracing.format_summary(summary)
    assert "MISSING" in txt and "[2]" in txt and "TORN" in txt
    # a clean merge reports no damage
    d2 = str(tmp_path / "clean")
    tracing.synth_shards(d2, ranks=2, steps=1)
    _, clean = tracing.merge(d2)
    assert clean["missing_ranks"] == [] and clean["torn_shards"] == []
    # zero readable shards is still an error
    d3 = str(tmp_path / "allgone")
    for p in tracing.synth_shards(d3, ranks=2, steps=1):
        open(p, "w", encoding="utf-8").write("{torn")
    with pytest.raises(FileNotFoundError):
        tracing.merge(d3)


# -- steplog integration -----------------------------------------------------

def test_steplog_phase_fields_and_overlap_fracs(monkeypatch, tmp_path):
    from mxnet_tpu.telemetry import StepLogger
    from mxnet_tpu.telemetry.registry import get_registry
    log = tmp_path / "steps.jsonl"
    monkeypatch.setenv("MXNET_TRACE", "1")
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    monkeypatch.setenv("MXNET_TELEMETRY_LOG", str(log))
    slog = StepLogger("tracing_test")
    with tracing.span("feed.wait", phase="feed"):
        time.sleep(0.004)
    with tracing.span("step.fused_dispatch", phase="compute"):
        time.sleep(0.002)
    with tracing.span("dist.allreduce", phase="comm"):
        time.sleep(0.001)
    slog.step(samples=8)
    slog.close()

    recs = [json.loads(line) for line in
            open(log, encoding="utf-8").read().splitlines()]
    (start,) = [r for r in recs if r["event"] == "run_start"]
    (step,) = [r for r in recs if r["event"] == "step"]
    assert start["trace_id"] == slog.trace_id
    assert step["trace_id"] == slog.trace_id
    # per-step phase breakdown, measured not estimated
    assert step["feed_us"] >= 3000
    assert step["compute_us"] >= 1500
    assert step["comm_us"] >= 500
    assert step["ckpt_us"] == 0
    for k in ("feed_compute_overlap_frac", "comm_compute_overlap_frac"):
        assert 0.0 <= step[k] <= 1.0
    # the step blocked ~4ms on feed out of ~7ms wall: overlap well < 1
    assert step["feed_compute_overlap_frac"] < 1.0
    # the same fractions ride /metrics as gauges
    reg = get_registry()
    g = reg.get("mxnet_trace_feed_compute_overlap_frac")
    assert g is not None and \
        g.value() == step["feed_compute_overlap_frac"]
    # spans closing during the run carried the run's trace id
    ev = [e for e in _trace_events() if e["name"] == "feed.wait"][0]
    assert ev["args"]["trace_id"] == slog.trace_id


def test_steplog_no_trace_fields_when_off(monkeypatch, tmp_path):
    from mxnet_tpu.telemetry import StepLogger
    log = tmp_path / "steps.jsonl"
    monkeypatch.setenv("MXNET_TRACE", "0")
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    monkeypatch.setenv("MXNET_TELEMETRY_LOG", str(log))
    slog = StepLogger("tracing_off")
    slog.step(samples=8)
    slog.close()
    (step,) = [json.loads(line) for line in
               open(log, encoding="utf-8").read().splitlines()
               if '"step"' in line and '"event": "step"' in line]
    assert "feed_us" not in step and "trace_id" not in step


# -- bit-identical fit -------------------------------------------------------

def _mlp_sym():
    data = mx.sym.Variable("data")
    fc1 = mx.sym.FullyConnected(data, name="fc1", num_hidden=16)
    act1 = mx.sym.Activation(fc1, act_type="relu")
    fc2 = mx.sym.FullyConnected(act1, name="fc2", num_hidden=4)
    return mx.sym.SoftmaxOutput(fc2, name="softmax")


def _fit_params(trace_flag):
    os.environ["MXNET_TRACE"] = trace_flag
    try:
        mx.random.seed(7)
        np.random.seed(7)
        rng = np.random.RandomState(0)
        X = rng.uniform(-1, 1, (160, 8)).astype(np.float32)
        Y = rng.randint(0, 4, (160,)).astype(np.float32)
        it = mx.io.NDArrayIter(X, Y, batch_size=40, shuffle=False)
        mod = mx.mod.Module(_mlp_sym(), context=mx.cpu(0))
        mod.fit(it, num_epoch=2, optimizer="sgd",
                optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
                initializer=mx.init.Xavier())
        args, _ = mod.get_params()
        return {n: a.asnumpy() for n, a in args.items()}
    finally:
        os.environ.pop("MXNET_TRACE", None)


def test_fit_bit_identical_trace_on_vs_off():
    """Tracing must never perturb numerics: params after fit with
    MXNET_TRACE=1 equal the MXNET_TRACE=0 run bit-for-bit (spans are
    host-side wall-clock reads only — no device syncs, no extra
    dispatches)."""
    profiler.clear_events()
    off = _fit_params("0")
    n_off = len(_trace_events())
    on = _fit_params("1")
    assert n_off == 0                         # off -> zero trace events
    assert len(_trace_events()) > 0           # on -> the fit was traced
    assert set(on) == set(off)
    for n in on:
        np.testing.assert_array_equal(on[n], off[n], err_msg=n)


# -- the program's spans in the profiler's trace (ISSUE 24) -------------------

K, FUSED_BATCH, FUSED_ROWS, FUSED_FEATURES = 4, 10, 160, 8
FUSED_DISPATCHES = FUSED_ROWS // (K * FUSED_BATCH)
LOOP_SPANS = ("feed.wait", "step.fused_dispatch", "step.enqueue",
              "step.metric_update", "step.log", "step.callbacks",
              "step.checkpoint")
FEEDER_SPANS = ("feed.stage", "feed.pull", "feed.stack", "feed.put",
                "feed.enqueue")
SETUP_SPANS = ("fit.bind", "fit.init_params", "fit.trainer_init",
               "fit.init_state")
PARENT = {"step.enqueue": "step.fused_dispatch",
          "step.metric_update": "step.fused_dispatch",
          "feed.pull": "feed.stage", "feed.stack": "feed.stage",
          "feed.put": "feed.stage"}


def _fused_fit(checkpoint_dir=None):
    """One epoch of a tiny fused fit (K=4, 4 dispatches); returns the
    trained arguments."""
    mx.random.seed(7)
    np.random.seed(7)
    rng = np.random.RandomState(0)
    X = rng.uniform(-1, 1, (FUSED_ROWS, FUSED_FEATURES)).astype(np.float32)
    Y = rng.randint(0, 4, (FUSED_ROWS,)).astype(np.float32)
    it = mx.io.NDArrayIter(X, Y, batch_size=FUSED_BATCH, shuffle=False)
    mod = mx.mod.Module(_mlp_sym(), context=mx.cpu(0))
    mod.fit(it, num_epoch=1, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
            initializer=mx.init.Xavier(), steps_per_dispatch=K,
            batch_end_callback=lambda param: None,
            checkpoint_dir=checkpoint_dir,
            checkpoint_period=K if checkpoint_dir else None)
    args, _ = mod.get_params()
    return {n: a.asnumpy() for n, a in args.items()}


@pytest.fixture(scope="module")
def profiled_fit(tmp_path_factory):
    """{span name: [(line, start_ns, end_ns, stats)]} of the `mx.*` events
    a profiler session recorded over one tiny fused fit that checkpoints
    after every dispatch, read back with jax.profiler.ProfileData."""
    import glob
    import jax
    from jax.profiler import ProfileData
    tmp = tmp_path_factory.mktemp("profiled_fit")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp / "profile"), profiler_options=opts)
    try:
        _fused_fit(checkpoint_dir=str(tmp / "ckpt"))
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp / "profile" / "**" / "*.xplane.pb"),
                        recursive=True)
    spans = {}
    for plane in ProfileData.from_file(path).planes:
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("mx."):
                    spans.setdefault(ev.name[3:], []).append(
                        ((plane.name, i), ev.start_ns,
                         ev.start_ns + ev.duration_ns, dict(ev.stats)))
    return spans


@pytest.mark.parametrize("name", LOOP_SPANS + FEEDER_SPANS + SETUP_SPANS)
def test_span_in_profiler_trace(profiled_fit, name):
    """Every span of the table is in the profiler's own trace, on the
    thread it belongs to, inside its parent, and numbered by block."""
    assert name in profiled_fit, sorted(profiled_fit)
    events = profiled_fit[name]
    loop_line = profiled_fit["step.fused_dispatch"][0][0]
    feeder_line = profiled_fit["feed.stage"][0][0]
    assert loop_line != feeder_line
    want_line = feeder_line if name in FEEDER_SPANS else loop_line
    assert {line for line, *_ in events} == {want_line}
    if name in SETUP_SPANS:
        assert len(events) == 1
        first_wait = min(s for _, s, _, _ in profiled_fit["feed.wait"])
        assert events[0][2] <= first_wait     # set-up ends before the loop
        return
    # one event per block (the feed's last wait/stage/pull find the end)
    seqs = sorted(st["seq"] for *_, st in events)
    assert seqs[:FUSED_DISPATCHES] == list(range(FUSED_DISPATCHES))
    assert len(seqs) <= FUSED_DISPATCHES + 1
    if name in PARENT:
        parents = {st["seq"]: (s, e)
                   for _, s, e, st in profiled_fit[PARENT[name]]}
        for _, s, e, st in events:
            ps, pe = parents[st["seq"]]
            assert ps <= s and e <= pe, (name, st)


def test_profiler_trace_joins_a_block_by_seq(profiled_fit):
    """Block n is staged, then waited for, then dispatched: the three
    spans that carry seq n lie in that order, across two threads."""
    def by_seq(name):
        return {st["seq"]: (s, e) for _, s, e, st in profiled_fit[name]}
    stage, wait, disp = (by_seq("feed.stage"), by_seq("feed.wait"),
                         by_seq("step.fused_dispatch"))
    for n in range(FUSED_DISPATCHES):
        assert stage[n][1] <= wait[n][1] <= disp[n][0]
    # the leaves of the loop thread do not overlap one another
    leaves = sorted((s, e) for name in LOOP_SPANS
                    if name != "step.fused_dispatch"
                    for _, s, e, _ in profiled_fit[name])
    assert all(a[1] <= b[0] for a, b in zip(leaves, leaves[1:]))


def test_child_spans_add_no_phase_total(monkeypatch, tmp_path):
    """The children of step.fused_dispatch and feed.stage carry no phase:
    StepLogger's compute_us is the parent's time, counted once."""
    log = tmp_path / "steps.jsonl"
    monkeypatch.setenv("MXNET_TRACE", "1")
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    monkeypatch.setenv("MXNET_TELEMETRY_LOG", str(log))
    _fused_fit()
    counts = tracing.phase_counts()
    assert counts["compute"] == FUSED_DISPATCHES
    assert counts["feed"] == FUSED_DISPATCHES + 1       # + the end's wait
    assert counts["feed_stage"] == FUSED_DISPATCHES + 1
    assert set(counts) <= {"compute", "feed", "feed_stage", "ckpt", "comm"}
    evs = _trace_events()
    by_cat = {}
    for e in evs:
        by_cat.setdefault(e["cat"], set()).add(e["name"])
    assert by_cat["trace:compute"] == {"step.fused_dispatch"}
    assert {"step.enqueue", "step.metric_update", "step.log", "feed.pull",
            "feed.stack", "feed.put", "feed.enqueue"} <= by_cat["trace:span"]
    # children lie inside their parent (1µs float slack)
    disp = {e["args"]["seq"]: e for e in evs
            if e["name"] == "step.fused_dispatch"}
    for e in evs:
        if e["name"] in ("step.enqueue", "step.metric_update"):
            p = disp[e["args"]["seq"]]
            assert p["ts"] <= e["ts"] and \
                e["ts"] + e["dur"] <= p["ts"] + p["dur"] + 1.0
    steps = [json.loads(line) for line in
             open(log, encoding="utf-8").read().splitlines()]
    steps = [r for r in steps if r["event"] == "step"]
    assert len(steps) == FUSED_DISPATCHES
    for n, rec in enumerate(steps):
        assert {"feed_us", "compute_us", "comm_us", "ckpt_us",
                "feed_compute_overlap_frac",
                "comm_compute_overlap_frac"} <= set(rec)
        assert rec["compute_us"] == pytest.approx(disp[n]["dur"], abs=2.0)


def test_fused_fit_bit_identical_and_span_sites_counted(monkeypatch):
    """No profiler session, MXNET_TRACE=0: the fused fit with its span
    sites timed (flight recorder on) equals the one with every site a
    no-op, bit for bit; fewer than 16 span sites run per dispatch."""
    monkeypatch.setenv("MXNET_TRACE", "0")
    monkeypatch.setenv("MXNET_FLIGHTREC", "0")
    off = _fused_fit()
    assert flightrec.stats()["total"] == 0 and _trace_events() == []
    monkeypatch.setenv("MXNET_FLIGHTREC", "1")
    on = _fused_fit()
    assert _trace_events() == []
    assert set(on) == set(off)
    for n in on:
        np.testing.assert_array_equal(on[n], off[n], err_msg=n)
    spans = [e for e in flightrec.snapshot() if e["kind"] == "span"]
    per_block = {}
    for e in spans:
        # dispatch 0 holds set-up's spans (the compile pipeline's,
        # devstats' extraction): every dispatch after it is counted
        if e["name"] not in SETUP_SPANS + ("devstats.extract",) and \
                not e["name"].startswith("compile."):
            per_block[e["seq"]] = per_block.get(e["seq"], 0) + 1
    assert set(range(FUSED_DISPATCHES)) <= set(per_block)
    assert all(5 <= n < 16 for s, n in per_block.items()
               if 0 < s < FUSED_DISPATCHES), per_block


def test_setup_spans_and_first_dispatch_in_flightrec(monkeypatch):
    monkeypatch.setenv("MXNET_TRACE", "0")
    _fused_fit()
    spans = [e for e in flightrec.snapshot() if e["kind"] == "span"]
    names = [e["name"] for e in spans]
    assert [n for n in names if n.startswith("fit.")] == list(SETUP_SPANS)
    first = names.index("step.fused_dispatch")
    assert first > names.index("fit.init_state")
    assert spans[first]["seq"] == 0 and spans[first]["dur_us"] > 0
    assert all(e["dur_us"] >= 0 for e in spans)


def test_feed_staged_bytes_counts_the_blocks_staged():
    from mxnet_tpu import pipeline
    pipeline.reset_stats()
    _fused_fit()
    st = pipeline.stats()
    assert st["feed_batches"] == FUSED_DISPATCHES
    # a block: K batches of float32 features and labels
    block = K * FUSED_BATCH * (FUSED_FEATURES + 1) * 4
    assert st["feed_staged_bytes"] == FUSED_DISPATCHES * block


def test_stopwatch_times_with_every_sink_off(monkeypatch):
    """DeviceFeed's counters read the span's own clock: a stopwatch is
    timed even when nothing records it."""
    monkeypatch.setenv("MXNET_TRACE", "0")
    monkeypatch.setenv("MXNET_FLIGHTREC", "0")
    with tracing.stopwatch("feed.wait", phase="feed") as sw:
        time.sleep(0.002)
        assert tracing.current_stack() == ()
    assert sw.dur_us >= 1500.0
    assert flightrec.stats()["total"] == 0 and tracing.phase_totals() == {}
    monkeypatch.setenv("MXNET_FLIGHTREC", "1")
    with tracing.stopwatch("feed.wait", phase="feed", seq=3) as sw:
        with tracing.span("feed.inner"):
            pass
    (inner, outer) = flightrec.snapshot()
    assert outer["dur_us"] == int(sw.dur_us)
    assert inner["seq"] == 3                  # taken over from the parent


# -- a record that can be put on a timeline, and the compile pipeline ---------
# (ISSUE 34)

def _ring_spans():
    return [e for e in flightrec.snapshot() if e["kind"] == "span"]


def test_ring_record_carries_start_id_and_parent(monkeypatch):
    """Every span record holds `t0_us` (its start on perf_counter), `id`
    and `parent`; a child's parent is the enclosing span's id, whether the
    child is a span, a stopwatch or a retrospective event."""
    monkeypatch.setenv("MXNET_TRACE", "0")
    before = time.perf_counter()
    with tracing.span("outer", seq=5) as outer:
        with tracing.span("a.span") as a:
            with tracing.stopwatch("a.stopwatch") as b:
                t_ev = time.perf_counter()
                time.sleep(0.001)
                tracing.event("an.event", t_ev, note="x")
        tracing.event("late.event", before)
    tracing.event("orphan.event", before)
    after = time.perf_counter()
    recs = {e["name"]: e for e in _ring_spans()}
    assert set(recs) == {"outer", "a.span", "a.stopwatch", "an.event",
                         "late.event", "orphan.event"}
    for e in recs.values():
        assert isinstance(e["t0_us"], int) and isinstance(e["id"], int)
        assert before * 1e6 - 1 <= e["t0_us"] <= after * 1e6
        assert e["t0_us"] + e["dur_us"] <= after * 1e6 + 1
    assert len({e["id"] for e in recs.values()}) == len(recs)
    assert recs["outer"]["parent"] is None
    assert recs["outer"]["id"] == outer.id
    assert recs["a.span"]["parent"] == outer.id
    assert recs["a.stopwatch"]["parent"] == a.id
    assert recs["an.event"]["parent"] == b.id
    assert recs["an.event"]["t0_us"] == int(t_ev * 1e6)
    assert recs["an.event"]["dur_us"] >= 1000
    # an event's start may lie before its parent's: the parent is the span
    # that was open when the event was recorded
    assert recs["late.event"]["parent"] == outer.id
    assert recs["late.event"]["t0_us"] < recs["outer"]["t0_us"]
    assert recs["orphan.event"]["parent"] is None
    # a child lies inside its parent on the timeline
    child, parent = recs["a.span"], recs["outer"]
    assert parent["t0_us"] <= child["t0_us"]
    assert child["t0_us"] + child["dur_us"] <= \
        parent["t0_us"] + parent["dur_us"] + 1


def _compiles(under=None):
    return [(e["name"], e["fun"]) for e in _ring_spans()
            if e["name"].startswith("compile.")
            and (under is None or e["parent"] == under)]


def test_compile_pipeline_spans_once_a_program(monkeypatch):
    """A fresh jit function called under span("x") leaves exactly one
    compile.trace, compile.lower and compile.backend naming it under x; the
    jit nested inside it, the helpers its lowering traces and a second call
    add none."""
    import jax
    import jax.numpy as jnp
    monkeypatch.setenv("MXNET_TRACE", "0")
    x = jnp.ones((4,))          # whatever building it compiles: before x
    x.block_until_ready()

    @jax.jit
    def nested_helper(v):
        return jnp.sin(v) + 1.0

    def fresh_program(v):
        # a scan: its lowering traces helpers of its own
        def body(c, row):
            return c + nested_helper(row).sum(), c
        return jax.lax.scan(body, 0.0, jnp.stack([v, v]))[0]

    fn = jax.jit(fresh_program)
    flightrec.reset()
    with tracing.span("x") as sp:
        fn(x).block_until_ready()
    assert _compiles(under=sp.id) == [
        ("compile.trace", "fresh_program"),
        ("compile.lower", "jit(fresh_program)"),
        ("compile.backend", "jit(fresh_program)")]
    assert _compiles() == _compiles(under=sp.id)
    (backend,) = [e for e in _ring_spans() if e["name"] == "compile.backend"]
    assert backend["cache"] in ("off", "miss", "hit")
    x_rec = [e for e in _ring_spans() if e["name"] == "x"][0]
    for e in _ring_spans():
        assert x_rec["t0_us"] <= e["t0_us"] and e["t0_us"] + e["dur_us"] \
            <= x_rec["t0_us"] + x_rec["dur_us"] + 1000, e
    flightrec.reset()
    with tracing.span("x"):
        fn(x).block_until_ready()
    assert _compiles() == []


def test_compile_backend_span_says_whether_the_cache_answered(
        monkeypatch, tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setenv("MXNET_TRACE", "0")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes",
             "jax_compilation_cache_max_size")
    saved = {n: getattr(jax.config, n) for n in names}
    x = jnp.arange(6.0)
    x.block_until_ready()

    def cached_program(v):
        return jnp.cos(v) * 3.0 - v

    def backend_spans():
        return [e for e in _ring_spans() if e["name"] == "compile.backend"
                and e["fun"] == "jit(cached_program)"]
    try:
        flightrec.reset()
        jax.jit(cached_program)(x).block_until_ready()
        (off,) = backend_spans()
        if not saved["jax_compilation_cache_dir"]:
            assert off["cache"] == "off" and "retrieval_s" not in off
        mx.config.enable_compile_cache(str(tmp_path / "cache"))
        states = []
        for _ in range(2):
            jax.clear_caches()
            flightrec.reset()
            jax.jit(cached_program)(x).block_until_ready()
            (rec,) = backend_spans()
            states.append(rec)
        assert [r["cache"] for r in states] == ["miss", "hit"]
        assert "retrieval_s" not in states[0]
        assert 0 <= states[1]["retrieval_s"] <= states[1]["dur_us"] / 1e6
    finally:
        for n, v in saved.items():
            jax.config.update(n, v)
        compilation_cache.reset_cache()


def test_fit_tells_extraction_from_the_jit_calls_own_compile(monkeypatch):
    """A fused fit: devstats' extraction runs under `devstats.extract`, the
    jit call's own trace, lowering and compile under `step.enqueue` of
    dispatch 0; no later dispatch has a compile.* descendant."""
    from mxnet_tpu.telemetry import devstats
    monkeypatch.setenv("MXNET_TRACE", "0")
    devstats.reset()
    _fused_fit()
    assert devstats.drain()
    spans = _ring_spans()
    by_id = {e["id"]: e for e in spans}

    def chain(e):
        out = []
        while e["parent"] is not None:
            e = by_id[e["parent"]]
            out.append(e)
        return out

    step = [e for e in spans if e["name"].startswith("compile.")
            and e["fun"] in ("multi", "jit(multi)")]
    under = {}
    for e in step:
        under.setdefault(chain(e)[0]["name"], []).append(e["name"])
    assert set(under) == {"devstats.extract", "step.enqueue"}, under
    for names in under.values():
        assert sorted(names) == ["compile.backend", "compile.lower",
                                 "compile.trace"]
    (extract,) = [e for e in spans if e["name"] == "devstats.extract"]
    assert extract["program"] == "dp.step_k%d" % K
    for e in spans:
        if e["name"].startswith("compile."):
            dispatches = [a for a in chain(e)
                          if a["name"] == "step.fused_dispatch"]
            assert all(a["seq"] == 0 for a in dispatches), (e, dispatches)
    # the jit call's own compile is dispatch 0's
    enqueue = [a for e in step for a in chain(e)
               if a["name"] == "step.enqueue"]
    assert enqueue and all(a["seq"] == 0 for a in enqueue)


def test_head_keeps_the_first_records(monkeypatch):
    """The process's first 2,048 records outlive any number of later ones:
    snapshot() is head + tail, stats() counts both."""
    monkeypatch.setenv("MXNET_FLIGHTREC", "1")
    assert flightrec.HEAD_EVENTS == 2048
    n = flightrec.HEAD_EVENTS + 6000
    for i in range(n):
        flightrec.record("event", "beat", i=i)
    st = flightrec.stats()
    assert st["head"] == 2048 and st["tail"] == st["capacity"] == 4096
    assert st["events"] == 2048 + 4096 and st["total"] == n
    assert st["dropped"] == n - st["events"]
    got = [e["i"] for e in flightrec.snapshot()]
    assert got == list(range(2048)) + list(range(n - 4096, n))
    assert "beat" in flightrec.tail_text(n=3)
    flightrec.reset()
    assert flightrec.stats()["events"] == 0 and flightrec.snapshot() == []


def test_compile_listener_is_registered_once():
    import importlib
    from jax._src import monitoring

    def ours(callbacks):
        return [cb for cb in callbacks if type(getattr(
            cb, "__self__", None)).__name__ == "_CompileListener"]

    importlib.reload(tracing)
    importlib.reload(mx.telemetry)
    tracing._listen_to_compiles()
    assert len(ours(monitoring.get_event_duration_listeners())) == 1
    assert len(ours(monitoring.get_event_listeners())) == 1
    assert len(ours(monitoring.get_scalar_listeners())) == 1


def test_startup_spans_recorded_once(monkeypatch):
    monkeypatch.setenv("MXNET_TRACE", "0")
    monkeypatch.setitem(tracing._startup, "recorded", False)
    t_import = time.perf_counter()
    tracing.record_startup(t_import)
    tracing.record_startup(t_import)         # a reload of the package
    recs = _ring_spans()
    assert [e["name"] for e in recs] == ["process.start", "import.mxnet_tpu"]
    start, imp = recs
    assert start["parent"] is None and imp["parent"] is None
    # the process is older than this test, and process.start ends where
    # the import begins
    assert start["dur_us"] > 0
    assert abs(start["t0_us"] + start["dur_us"] - imp["t0_us"]) <= 1
    assert imp["t0_us"] == int(t_import * 1e6)


def test_import_records_the_time_before_the_programs_spans():
    """In a fresh process: `process.start` from the operating system's
    record of the process's start to the import's first line, then
    `import.mxnet_tpu`, which is the ring's last record once the import
    has returned."""
    import subprocess
    code = ("import time, json; t0 = time.perf_counter(); "
            "import mxnet_tpu; t1 = time.perf_counter(); "
            "from mxnet_tpu.telemetry import flightrec; "
            "print(json.dumps({'t0': t0, 't1': t1, "
            "'ring': flightrec.snapshot()}))")
    env = dict(os.environ, JAX_PLATFORMS="cpu", MXNET_FLIGHTREC="1",
               PYTHONPATH=REPO)
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    ring = [e for e in got["ring"] if e["kind"] == "span"]
    assert ring[-1]["name"] == "import.mxnet_tpu"
    imp = ring[-1]
    assert got["t0"] * 1e6 <= imp["t0_us"]
    assert imp["t0_us"] + imp["dur_us"] <= got["t1"] * 1e6 + 1
    # the import took nearly all of the time the caller measured around it
    assert imp["dur_us"] >= 0.9 * (got["t1"] - got["t0"]) * 1e6
    (start,) = [e for e in ring if e["name"] == "process.start"]
    assert start["t0_us"] < got["t0"] * 1e6
    assert abs(start["t0_us"] + start["dur_us"] - imp["t0_us"]) <= 1
    # the interpreter's start-up, not minutes
    assert 0 < start["dur_us"] < 60e6


def test_serve_infer_children_cover_infer(monkeypatch, tmp_path):
    """serve.infer spans the whole of ServingEngine.infer; serve.pad,
    serve.compute and serve.fetch lie inside it, in that order, and
    leave none of it uncovered but the hops between them."""
    from mxnet_tpu.contrib.export import export_model
    from mxnet_tpu.serving import ServingEngine
    sym = _mlp_sym()
    mod = mx.mod.Module(sym, context=mx.cpu(0))
    mod.bind(data_shapes=[("data", (8, 8))],
             label_shapes=[("softmax_label", (8,))])
    mod.init_params(mx.init.Xavier())
    args, auxs = mod.get_params()
    path = str(tmp_path / "model.mxa")
    export_model(path, sym, args, auxs, {"data": (8, 8)})
    engine = ServingEngine(path)
    engine.warmup()
    monkeypatch.setenv("MXNET_TRACE", "1")
    profiler.clear_events()
    t0 = time.perf_counter()
    (out,) = engine.infer(np.ones((3, 8), np.float32))
    wall_us = (time.perf_counter() - t0) * 1e6
    assert out.shape == (3, 4)
    evs = {e["name"]: e for e in _trace_events()}
    assert set(evs) == {"serve.infer", "serve.pad", "serve.compute",
                        "serve.fetch"}
    parent = evs["serve.infer"]
    assert evs["serve.compute"]["cat"] == "trace:serve"
    assert parent["cat"] == "trace:span"      # no phase: counted once
    kids = [evs[n] for n in ("serve.pad", "serve.compute", "serve.fetch")]
    assert parent["ts"] <= kids[0]["ts"]
    for a, b in zip(kids, kids[1:]):
        assert a["ts"] + a["dur"] <= b["ts"] + 1.0
    assert kids[-1]["ts"] + kids[-1]["dur"] <= \
        parent["ts"] + parent["dur"] + 1.0
    assert evs["serve.compute"]["args"]["bucket"] == engine.bucket_for(3)
    assert evs["serve.fetch"]["args"]["rows"] == 3
    covered = sum(k["dur"] for k in kids)
    assert covered <= parent["dur"] + 1.0 <= wall_us + 1.0
    assert parent["dur"] - covered < 200.0 + 0.1 * parent["dur"]


# -- flight recorder ---------------------------------------------------------

def test_flightrec_ring_dump_and_tail(monkeypatch, tmp_path):
    monkeypatch.setenv("MXNET_FLIGHTREC", "1")
    monkeypatch.setenv("MXNET_FLIGHTREC_EVENTS", "32")
    monkeypatch.setattr(flightrec, "HEAD_EVENTS", 0)    # the tail alone
    for i in range(50):
        flightrec.record("event", f"beat{i}", step=i)
    st = flightrec.stats()
    assert st["events"] == 32 and st["total"] == 50
    assert st["dropped"] == 18 and st["capacity"] == 32
    assert st["head"] == 0 and st["tail"] == 32
    p = flightrec.dump(path=str(tmp_path / "fr.json"), reason="test")
    box = json.loads(open(p, encoding="utf-8").read())
    assert box["reason"] == "test" and box["rank"] == 0
    assert len(box["events"]) == 32 and box["dropped"] == 18
    assert box["last_event_t"] == box["events"][-1]["t"]
    tail = flightrec.tail_text(n=5)
    assert "beat49" in tail and "beat44" not in tail


def test_flightrec_disabled_records_nothing(monkeypatch, tmp_path):
    monkeypatch.setenv("MXNET_FLIGHTREC", "0")
    flightrec.record("event", "ghost")
    assert flightrec.stats()["total"] == 0
    assert flightrec.dump(path=str(tmp_path / "no.json")) is None
    assert not (tmp_path / "no.json").exists()


def test_flightrec_excepthook_dumps_blackbox(monkeypatch, tmp_path):
    monkeypatch.setenv("MXNET_FLIGHTREC", "1")
    prev_hook = sys.excepthook
    assert flightrec.install(directory=str(tmp_path))
    try:
        flightrec.record("event", "last_breath")
        try:
            raise RuntimeError("simulated crash")
        except RuntimeError:
            sys.excepthook(*sys.exc_info())
        box_path = tmp_path / "flightrec-rank-0.json"
        assert box_path.exists()
        box = json.loads(box_path.read_text(encoding="utf-8"))
        assert box["reason"].startswith("uncaught exception: RuntimeError")
        names = [e["name"] for e in box["events"]]
        assert "last_breath" in names
        assert "uncaught:RuntimeError" in names
    finally:
        flightrec.uninstall()
    assert sys.excepthook is prev_hook


def test_periodic_flush_never_takes_a_crash_box(monkeypatch, tmp_path):
    """A flush that was due when a crash hook dumped must not land over
    the crash's box and take its reason (the flusher and the hook race)."""
    monkeypatch.setenv("MXNET_FLIGHTREC", "1")
    path = str(tmp_path / "box.json")

    def reason():
        with open(path, encoding="utf-8") as f:
            return json.load(f)["reason"]

    flightrec.record("event", "beat")
    try:
        flightrec.dump(path=path, reason="periodic-flush")
        assert reason() == "periodic-flush"
        flightrec.dump(path=path, reason="SIGTERM")
        flightrec.record("event", "later")
        assert flightrec.dump(path=path, reason="periodic-flush") == path
        assert reason() == "SIGTERM"
    finally:
        flightrec.uninstall()               # forgets the crash boxes
    flightrec.dump(path=path, reason="periodic-flush")
    assert reason() == "periodic-flush"


def test_watchdog_dump_carries_flight_tail(monkeypatch, tmp_path):
    from mxnet_tpu.telemetry import watchdog
    monkeypatch.setenv("MXNET_FLIGHTREC", "1")
    flightrec.record("span", "ckpt.seal", dur_us=1234, step=7)
    out = tmp_path / "dump.txt"
    with open(out, "w", encoding="utf-8") as f:
        watchdog.dump_now(reason="test-stall", file=f)
    text = out.read_text(encoding="utf-8")
    # faulthandler stacks show where threads ARE; the flight tail shows
    # what they were DOING
    assert "watchdog: test-stall" in text
    assert "flight recorder tail" in text
    assert "ckpt.seal" in text and "1.234ms" in text


# -- launcher triage (no jax: black boxes are plain JSON) --------------------

def _fake_box(rank, t_last, n=5):
    return {"version": 1, "rank": rank, "pid": 1000 + rank,
            "reason": "periodic-flush", "wall_time": t_last,
            "events": [{"t": t_last - (n - 1 - i) * 0.1,
                        "thr": "MainThread", "kind": "span",
                        "name": f"r{rank}.ev{i}", "dur_us": 42}
                       for i in range(n)],
            "dropped": 0, "total": n, "last_event_t": t_last}


def test_cluster_result_quiet_rank_and_triage(tmp_path):
    base = 1700000000.0
    boxes = {0: _fake_box(0, base + 10.0),
             1: _fake_box(1, base + 4.0),     # went quiet 6s earlier
             2: _fake_box(2, base + 9.8)}
    launcher = ClusterLauncher(nprocs=3, blackbox_dir=str(tmp_path))
    for r, b in boxes.items():
        (tmp_path / f"flightrec-rank-{r}.json").write_text(
            json.dumps(b), encoding="utf-8")
    collected = launcher.collect_blackboxes()
    assert sorted(collected) == [0, 1, 2]
    from mxnet_tpu.cluster.launcher import ClusterResult

    class _RP:
        def __init__(self, rank, rc):
            self.rank, self.exit_rc, self.exit_t = rank, rc, 1.0
            self.reaped = False

        def log_text(self):
            return ""

    ranks = [_RP(0, 1), _RP(1, -9), _RP(2, 1)]
    res = ClusterResult(ranks, 12.0, False, 0.5, 0.0,
                        blackboxes=collected,
                        blackbox_dir=str(tmp_path))
    assert res.quiet_rank == 1
    text = res.triage(last_s=20.0)
    assert "rank 1 went quiet FIRST" in text
    assert "r0.ev4" in text and "r1.ev4" in text
    # interleaved and time-ordered: rank 1's newest event prints before
    # rank 0's newest (it is 6s older)
    assert text.index("r1.ev4") < text.index("r0.ev4")


# -- the real thing: 2-rank gang, injected SIGKILL ---------------------------

_TRACED_WORKER = r"""
import os, time
import mxnet_tpu as mx
from mxnet_tpu import dist

rank = int(os.environ.get("DMLC_WORKER_ID", "0"))
assert dist.is_initialized()
for i in range(6):
    dist.barrier(f"traced_{i}")
    time.sleep(0.3)      # give the 0.5s flushers time to land a snapshot
print("worker done", rank, flush=True)
"""


@pytest.mark.slow
@needs_gloo
def test_two_rank_kill_leaves_blackboxes_and_merged_timeline(tmp_path):
    """End-to-end DistRankFailure postmortem: rank 1 is SIGKILLed at its
    3rd barrier; the survivor aborts with a named DistRankFailure; BOTH
    ranks leave flight-recorder black boxes; the launcher triage and the
    merged span timeline each name rank 1 as the one that went quiet."""
    trace_dir = str(tmp_path / "trace")
    victim = 1
    launcher = ClusterLauncher(
        nprocs=2, deadline_s=90.0, dist_timeout_s=5.0, dist_retries=0,
        inject=f"kill@pre-barrier:{victim}@3", stream=False,
        blackbox_dir=str(tmp_path / "blackbox"),
        env={"PYTHONPATH": REPO + os.pathsep
             + os.environ.get("PYTHONPATH", ""),
             "MXNET_TELEMETRY": "0",
             "MXNET_TRACE": "1", "MXNET_TRACE_DIR": trace_dir,
             "MXNET_TRACE_FLUSH_S": "0.5"})
    res = launcher.launch_python(_TRACED_WORKER)
    assert not res.ok
    assert not res.deadline_fired, res.describe()
    assert res.returncodes[victim] == -9
    assert "DistRankFailure" in res.tails[0] \
        or "JAX distributed service detected fatal errors" in res.tails[0]
    # every rank's black box was collected; the victim is the quiet one
    assert sorted(res.blackboxes) == [0, 1], res.describe()
    assert res.quiet_rank == victim
    assert f"rank {victim} went quiet FIRST" in res.triage()
    # the per-rank shards merge into one valid timeline naming the victim
    out, summary = tracing.merge(trace_dir)
    merged = json.loads(open(out, encoding="utf-8").read())
    assert isinstance(merged["traceEvents"], list)
    assert all("ph" in e and "pid" in e for e in merged["traceEvents"])
    assert summary["quiet_first"]["rank"] == victim
