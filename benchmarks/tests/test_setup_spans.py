"""reduce/setup_spans.py and the six `setup_*_s` readers over it, checked
without a chip (`python -m pytest benchmarks/tests -q`; outside tier-1's
tests/).

The timeline's arithmetic runs on a hand-made ring; the readers are shown
to give nothing, without a fault, for a ring from before PR 34 (no
`t0_us`); and every train cell is rehearsed with a trace, as the driver
runs it, to print the `setup_spans` line and the six metrics.
"""
import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.join(HERE, "tests")]

from reduce import setup_spans                          # noqa: E402
from test_harness import ROOT, load_run, rehearse       # noqa: E402

S = 1_000_000       # a second of the ring's microseconds
T0 = 5_000 * S      # perf_counter's epoch is arbitrary


def rec(ident, name, start_s, dur_s, parent=None, thr="MainThread", **args):
    return {"t": 0.0, "thr": thr, "kind": "span", "name": name,
            "dur_us": int(dur_s * S), "t0_us": T0 + int(start_s * S),
            "id": ident, "parent": parent, **args}


def ring():
    """W = 2. The process starts at 0 and the import runs 3–4. The harness
    works 4–10 under no span, with one compile of its own 5–6. `fit.bind`
    10–12 holds a compile triple 10.5–11.7. Dispatch 0 runs 14–30: its
    `step.enqueue` 14–26 holds the synchronous extraction 15–20 (trace,
    lower, load) and the jit call's own trace 20–22, lower 22–23 and load
    23–25; its `step.metric_update` 26–30 holds one small compile 29–29.5.
    The callbacks of dispatch 0 (30.2–31) record an event that began at
    29.8: before `step.log` (30–30.2), their sibling, had closed. Dispatch 1
    runs 31–32, its callbacks close the window at 32.5. A feeder thread
    stages 13–15 and 16–33, and compiles 13.2–13.4 on its own."""
    main = [
        rec(1, "process.start", 0, 3),
        rec(2, "import.mxnet_tpu", 3, 1),
        rec(3, "compile.trace", 5, 0.2, fun="pool"),
        rec(4, "compile.lower", 5.2, 0.3, fun="jit(pool)"),
        rec(5, "compile.backend", 5.5, 0.5, fun="jit(pool)", cache="hit",
            retrieval_s=0.1),
        rec(7, "compile.trace", 10.5, 0.2, 6, fun="bind"),
        rec(8, "compile.lower", 10.7, 0.4, 6, fun="jit(bind)"),
        rec(9, "compile.backend", 11.1, 0.6, 6, fun="jit(bind)",
            cache="miss"),
        rec(6, "fit.bind", 10, 2),
        rec(13, "compile.trace", 15.0, 2.0, 12, fun="multi"),
        rec(14, "compile.lower", 17.0, 1.0, 12, fun="jit(multi)"),
        rec(15, "compile.backend", 18.0, 1.5, 12, fun="jit(multi)",
            cache="hit", retrieval_s=1.0),
        rec(12, "devstats.extract", 15, 5, 11, seq=0, program="dp.step_k4"),
        rec(16, "compile.trace", 20, 2, 11, fun="multi"),
        rec(17, "compile.lower", 22, 1, 11, fun="jit(multi)"),
        rec(18, "compile.backend", 23, 2, 11, fun="jit(multi)", cache="hit",
            retrieval_s=1.5),
        rec(11, "step.enqueue", 14, 12, 10, seq=0),
        rec(20, "compile.trace", 29.0, 0.1, 19, fun="reshape"),
        rec(21, "compile.lower", 29.1, 0.1, 19, fun="jit(reshape)"),
        rec(22, "compile.backend", 29.2, 0.3, 19, fun="jit(reshape)",
            cache="off"),
        rec(19, "step.metric_update", 26, 4, 10, seq=0),
        rec(10, "step.fused_dispatch", 14, 16, seq=0, k=4),
        rec(23, "step.log", 30, 0.2, seq=0),
        rec(25, "host.copy", 29.8, 0.9, 24),          # retrospective
        rec(24, "step.callbacks", 30.2, 0.8, seq=0),
        rec(27, "step.enqueue", 31, 0.25, 26, seq=1),
        rec(28, "step.metric_update", 31.25, 0.75, 26, seq=1),
        rec(26, "step.fused_dispatch", 31, 1, seq=1, k=4),
        rec(29, "step.log", 32, 0.1, seq=1),
        rec(30, "step.callbacks", 32.1, 0.4, seq=1),
        rec(31, "step.fused_dispatch", 33, 1, seq=2, k=4)]
    feeder = [
        rec(41, "compile.backend", 13.2, 0.2, 40, thr="feeder",
            fun="jit(stack)", cache="miss"),
        rec(40, "feed.stage", 13, 2, thr="feeder", seq=0),
        rec(42, "feed.stage", 16, 17, thr="feeder", seq=1)]
    # as the ring holds them: by the time they closed
    return sorted(main + feeder, key=lambda e: e["t0_us"] + e["dur_us"])


def test_self_seconds_and_unattributed_add_up_to_the_window():
    tl = setup_spans.Timeline(ring(), 2)
    assert tl and tl.starts_with == "process.start"
    assert tl.thread == "MainThread"
    assert tl.window == (T0, T0 + int(32.5 * S))
    # to the microsecond, as integers
    assert sum(tl.own.values()) + tl.free == 32_500_000
    out = tl.summary()
    assert abs(out["self_sum_s"] + out["unattributed_s"]
               - out["window_s"]) < 1e-6
    rows = out["spans"]
    assert abs(sum(r["self_s"] for r in rows.values()
                   if r["thread"] == "loop")
               + out["unattributed_s"] - 32.5) < 1e-6
    # 4–5, 6–10 and 12–14 lie under no span
    assert out["unattributed_s"] == pytest.approx(1 + 4 + 2)
    assert rows["process.start"]["self_s"] == pytest.approx(3)
    assert rows["fit.bind"] == {"thread": "loop", "count": 1,
                                "total_s": pytest.approx(2),
                                "self_s": pytest.approx(0.8)}
    # 14–15 and 25–26: the Python around extraction and the jit call
    assert rows["step.enqueue"]["self_s"] == pytest.approx(2 + 0.25)
    assert rows["devstats.extract"]["self_s"] == pytest.approx(0.5)
    assert rows["step.fused_dispatch"]["self_s"] == pytest.approx(0)
    assert rows["step.fused_dispatch"]["count"] == 2
    # the event began at 29.8 under the first run and under step.log, which
    # started later than it: it owns 29.8–30 and, once the callbacks that
    # recorded it have started (30.2, later still), nothing more
    assert rows["host.copy"]["total_s"] == pytest.approx(0.9)
    assert rows["host.copy"]["self_s"] == pytest.approx(0.2)
    assert rows["step.log"]["self_s"] == pytest.approx(0.2 + 0.1)
    assert rows["step.callbacks"]["self_s"] == pytest.approx(0.8 + 0.4)
    # the other thread's spans run beside the loop's: no self time
    assert rows["feed.stage@feeder"] == {
        "thread": "feeder", "count": 2, "total_s": pytest.approx(2 + 16.5),
        "self_s": None}
    assert rows["compile.backend@feeder"]["count"] == 1
    assert out["cache"] == {"hit": 3, "miss": 2, "off": 1}
    longest = out["compile_longest"]
    assert len(longest) == 10
    assert longest[0] == {"name": "compile.trace", "fun": "multi", "s": 2.0,
                          "under": "devstats.extract", "thread": "loop"}
    assert {"name": "compile.backend", "fun": "jit(multi)", "s": 2.0,
            "cache": "hit", "retrieval_s": 1.5, "under": "step.enqueue",
            "thread": "loop"} in longest


def test_metrics_and_the_first_dispatch_split():
    tl = setup_spans.Timeline(ring(), 2)
    m = tl.metrics()
    assert set(m) == set(setup_spans.METRICS)
    assert m["setup_import_s"] == pytest.approx(1)
    # 7 s under no span at all and 3 s before the import
    assert m["setup_unattributed_s"] == pytest.approx(7 + 3)
    assert m["setup_extract_s"] == pytest.approx(5)
    # not under the extraction: the harness's 0.5, bind's 0.6, the jit
    # call's 3, the first run's 0.2 (the feeder's are no part of the loop)
    assert m["setup_trace_lower_s"] == pytest.approx(0.5 + 0.6 + 3 + 0.2)
    assert m["setup_program_load_s"] == pytest.approx(0.5 + 0.6 + 2 + 0.3)
    # 26–30 less its compile of 0.5 and the 0.2 that the callbacks' event,
    # which began under it, owns
    assert m["setup_first_run_s"] == pytest.approx(4 - 0.5 - 0.2)
    first = tl.first_dispatch()
    assert first == {"total_s": 16.0,
                     "enqueue_self_s": pytest.approx(2),
                     "extract_s": pytest.approx(5),
                     "first_run_s": pytest.approx(3.3),
                     "other_s": pytest.approx(0.2),
                     "program_load_s": pytest.approx(2 + 0.3),
                     "trace_lower_s": pytest.approx(3 + 0.2),
                     "unattributed_s": 0.0}
    assert sum(v for k, v in first.items() if k != "total_s") == \
        pytest.approx(first["total_s"])
    rows = tl.dispatches()
    assert [r["seq"] for r in rows] == [0, 1]
    assert rows[0]["fused_dispatch_s"] == pytest.approx(16)
    assert rows[0]["callbacks_s"] == pytest.approx(0.8)
    assert rows[1]["enqueue_self_s"] == pytest.approx(0.25)
    assert rows[1]["metric_update_s"] == pytest.approx(0.75)


def test_window_starts_at_the_import_without_a_process_record():
    records = [e for e in ring() if e["name"] != "process.start"]
    tl = setup_spans.Timeline(records, 2)
    assert tl.starts_with == "import.mxnet_tpu"
    assert tl.window == (T0 + 3 * S, T0 + int(32.5 * S))
    assert sum(tl.own.values()) + tl.free == 29_500_000
    assert tl.metrics()["setup_unattributed_s"] == pytest.approx(7)


def context(warm):
    return types.SimpleNamespace(traffic={"warmup_dispatches": warm},
                                 cell={"name": "test_setup_spans"})


@pytest.mark.parametrize("records", [
    pytest.param([{k: v for k, v in e.items()
                   if k not in ("t0_us", "id", "parent")} for e in ring()],
                 id="a-ring-from-before-PR-34"),
    pytest.param([e for e in ring() if not e["name"].startswith("step.")],
                 id="the-fit-never-ran"),
    pytest.param([], id="an-empty-ring")])
def test_readers_give_nothing_and_do_not_raise(monkeypatch, capsys, records):
    from mxnet_tpu.telemetry import flightrec
    monkeypatch.setattr(flightrec, "snapshot", lambda last_s=None: records)
    setup_spans._of_ring.cache_clear()
    load = load_run().load_file_module
    try:
        for name in setup_spans.METRICS:
            assert load("layer_metrics", name).compute(context(2)) is None
    finally:
        setup_spans._of_ring.cache_clear()
    assert "setup_spans" not in capsys.readouterr().out


def test_readers_read_the_ring_and_print_one_line(monkeypatch, capsys):
    from mxnet_tpu.telemetry import flightrec
    monkeypatch.setattr(flightrec, "snapshot", lambda last_s=None: ring())
    setup_spans._of_ring.cache_clear()
    load = load_run().load_file_module
    try:
        got = {name: load("layer_metrics", name).compute(context(2))
               for name in setup_spans.METRICS}
    finally:
        setup_spans._of_ring.cache_clear()
    assert got == setup_spans.Timeline(ring(), 2).metrics()
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    (line,) = [ln for ln in lines if ln["line"] == "setup_spans"]
    assert line["metrics"] == got and "ring" in line
    # 4–5 and 6–10: what the caller did before it called fit
    assert line["unattributed_before_fit_s"] == pytest.approx(5)
    with open(line["box"], encoding="utf-8") as f:
        box = json.load(f)
    os.remove(line["box"])
    assert box["reason"] == "setup_spans" and box["events"] == ring()


def test_entries_are_appended_for_the_train_cells_only():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    train = [w["name"] for w in bench["workloads"]
             if w["name"].split(".")[-1] in ("train", "dp4")]
    tail = bench["per_layer"][-len(setup_spans.METRICS):]
    assert [m["name"] for m in tail] == list(setup_spans.METRICS)
    for m in tail:
        assert m["workloads"] == train and m["moves"] == "setup_s"
        assert (m["unit"], m["better"], m["source"]) == \
            ("s", "lower", "program_span")
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert [m["layer"] for m in tail] == [
        "start-up", "start-up", "compile cache", "compile cache",
        "compile cache", "device"]


@pytest.mark.parametrize("cell", [
    "resnet50.train", "resnet152.train", "resnet152.dp4",
    "kimi_linear.train", "kanana2.train", "lfm2_moe.train"])
def test_traced_rehearsal_prints_the_line_and_the_six_metrics(cell):
    # (`correct` is the cells' own tests': from a cold cache a rehearsal can
    # read "1 compiles inside the window", ROADMAP S9 (a))
    out, lines = rehearse(cell, 1)
    (line,) = [ln for ln in lines if ln["line"] == "setup_spans"]
    for name in setup_spans.METRICS:
        value = out["metrics"][name]
        assert value["unit"] == "s" and value["value"] >= 0
        assert value["value"] == line["metrics"][name]
    assert line["metrics"]["setup_extract_s"] > 0
    assert line["metrics"]["setup_first_run_s"] > 0
    # the line's rule: the window is accounted for to the microsecond
    assert abs(line["self_sum_s"] + line["unattributed_s"]
               - line["window_s"]) < 1e-6
    # and it is the run's set-up, with the interpreter's start before
    # run.py's clock and the last callback's own time after it
    (setup,) = [ln for ln in lines if ln["line"] == "setup"]
    setup_s = setup["import_s"] + setup["build_s"] + \
        setup["compile_and_warmup_s"]
    assert 0 <= line["window_s"] - line["before_t_start_s"] - setup_s < 0.5
    assert line["ring"]["head"] > 0
    first = line["first_dispatch"]
    assert first["total_s"] == out["metrics"]["setup_first_dispatch_s"][
        "value"]
    # the traced window's spans are the steady state's: none of set-up's
    (spans,) = [ln for ln in lines if ln["line"] == "program_spans"]
    assert not [n for n in spans["spans"]
                if "compile." in n or "devstats.extract" in n]
