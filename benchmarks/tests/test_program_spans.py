"""reduce/program_spans.py and the readers over it, checked without a chip
(`python -m pytest benchmarks/tests -q`; outside tier-1's tests/).

The interval arithmetic runs on hand-made events, the reduction on the
`mx.*`, `bench.*` and device-0 events of a trace taken on the chip
(fixtures/<name>.spans.json.gz, with what the reader gave when it was
looked at by hand in <name>.spans.expect.json), and the two cells this
reader came with are rehearsed end to end.
"""
import gzip
import json
import os
import sys
import time

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.join(HERE, "tests")]

from reduce import program_spans, xplane        # noqa: E402
from test_harness import ROOT, named, rehearse  # noqa: E402

FIXTURES = os.path.join(HERE, "fixtures")


def synthetic():
    """A window of 10 s. The device runs 1–4 and 6–9, so it idles 0–1, 4–6
    and 9–10. The loop waits for the feed 0–1.5, dispatches 1.5–5 (enqueue
    1.5–2, metric update 2–4.5, half a second of its own after it), logs
    5–5.2, is in no span 5.2–6, waits again 6–9.5 and dispatches 9.5–12,
    past the window's end. The feeder stages 0.2–1.4 and 5.5–9.4."""
    device = [["fusion.1", 1.0, 3.0, "fusion:Loop"],
              ["fusion.2", 6.0, 3.0, "fusion:Loop"]]
    loop = [["bench.window_start", 0.0, 0.0, {}],
            ["mx.feed.wait", 0.0, 1.5, {"seq": 0}],
            ["mx.step.fused_dispatch", 1.5, 3.5, {"seq": 0, "k": 4}],
            ["mx.step.enqueue", 1.5, 0.5, {"seq": 0}],
            ["mx.step.metric_update", 2.0, 2.5, {"seq": 0}],
            ["mx.step.log", 5.0, 0.2, {"seq": 0}],
            ["mx.feed.wait", 6.0, 3.5, {"seq": 1}],
            ["mx.step.fused_dispatch", 9.5, 2.5, {"seq": 1, "k": 4}],
            ["mx.step.enqueue", 9.5, 2.5, {"seq": 1}],
            ["bench.window_end", 10.0, 0.0, {}]]
    feeder = [["mx.feed.stage", 0.2, 1.2, {"seq": 0}],
              ["mx.feed.put", 0.4, 1.0, {"seq": 0}],
              ["mx.feed.stage", 5.5, 3.9, {"seq": 1}],
              ["mx.feed.put", 6.0, 3.0, {"seq": 1}]]
    return {"planes": [
        {"name": "/device:TPU:0",
         "lines": [{"name": "XLA Ops", "events": device}]},
        {"name": "/host:CPU",
         "lines": [{"name": "python", "events": loop},
                   {"name": "python", "events": feeder}]}]}


def test_idle_seconds_add_up_to_the_idle_time():
    spans = program_spans.of_events(synthetic())
    assert spans.window == (0.0, 10.0)
    assert spans.thread(spans.loop) == "loop"
    assert spans.thread(spans.feeder) == "feeder"
    idle = spans.loop_idle()
    assert idle == pytest.approx({
        "mx.feed.wait": 1.0 + 0.5,              # 0–1 and 9–9.5
        "mx.step.enqueue": 0.5,                 # 9.5–10 (1.5–2 is busy)
        "mx.step.metric_update": 0.5,           # 4–4.5
        "mx.step.fused_dispatch": 0.5,          # its own 4.5–5
        "mx.step.log": 0.2,
        program_spans.UNATTRIBUTED: 0.8})       # 5.2–6
    assert sum(idle.values()) == pytest.approx(4.0)
    assert sum(idle.values()) == pytest.approx(
        xplane.Reduced(synthetic()).idle_share() * spans.window_s)


def test_table_counts_where_a_span_starts_and_clips_its_seconds():
    table = program_spans.of_events(synthetic()).table()
    enqueue = table["mx.step.enqueue"]
    assert enqueue["thread"] == "loop" and enqueue["count"] == 2
    assert enqueue["total_s"] == pytest.approx(1.0)     # 0.5 + 0.5 of 2.5
    assert enqueue["median_s"] == pytest.approx(1.5)    # whole durations
    stage = table["mx.feed.stage"]
    assert stage["thread"] == "feeder" and stage["count"] == 2
    assert stage["median_s"] == pytest.approx(2.55)
    assert stage["idle_s"] == pytest.approx(0.8 + 0.5 + 0.4)    # 9–9.4 too
    assert table["mx.feed.put"]["median_s"] == pytest.approx(2.0)
    assert table["mx.step.fused_dispatch"]["idle_s"] == pytest.approx(1.5)


def test_without_a_device_trace_there_are_spans_and_no_idle_figure():
    events = synthetic()
    events["planes"] = events["planes"][1:]             # a CPU rehearsal
    spans = program_spans.Spans(events)
    assert spans and spans.window == (0.0, 10.0)
    assert spans.loop_idle() is None
    assert spans.table()["mx.feed.wait"]["idle_s"] is None
    assert spans.durations("mx.feed.stage", spans.feeder) == \
        pytest.approx([1.2, 3.9])
    assert not program_spans.Spans({"planes": []})


def test_arguments_left_in_the_name_are_decoded():
    assert program_spans.split_name("mx.feed.wait#feed=fit,seq=3#") == \
        ("mx.feed.wait", {"feed": "fit", "seq": 3})
    assert program_spans.split_name("mx.step.log", [("seq", 7)]) == \
        ("mx.step.log", {"seq": 7})
    assert program_spans.split_name("bench.batch_end") == \
        ("bench.batch_end", {})


def test_a_trace_from_before_this_process_is_not_read(tmp_path, monkeypatch):
    old = tmp_path / "t.xplane.pb"
    old.write_bytes(b"")
    main = sys.modules["__main__"]
    monkeypatch.setattr(main, "T_START", time.perf_counter(), raising=False)
    assert program_spans._this_run_wrote(str(old))
    os.utime(old, (time.time() - 3600, time.time() - 3600))
    assert not program_spans._this_run_wrote(str(old))
    monkeypatch.delattr(main, "T_START")
    assert program_spans._this_run_wrote(str(old))      # by hand: no check


def fixture_names():
    return sorted(f[:-len(".spans.json.gz")] for f in os.listdir(FIXTURES)
                  if f.endswith(".spans.json.gz"))


def test_a_trace_was_recorded_on_the_chip():
    assert fixture_names(), "no recorded spans in fixtures/"


@pytest.mark.parametrize("name", fixture_names())
def test_reduction_on_the_recorded_spans(name):
    """fixtures/<name>.spans.json.gz is read_events() of a traced run on
    the chip cut to its window; <name>.spans.expect.json is what the
    reader gave when the fixture was recorded and looked at by hand."""
    base = os.path.join(FIXTURES, name)
    with gzip.open(base + ".spans.json.gz", "rt") as f:
        events = json.load(f)
    with open(base + ".spans.expect.json") as f:
        want = json.load(f)
    spans = program_spans.of_events(events)
    got = spans.summary()
    assert got["window_s"] == pytest.approx(want["window_s"])
    assert got["device_idle_s"] == pytest.approx(want["device_idle_s"])
    assert got["loop_idle_s"] == pytest.approx(want["loop_idle_s"])
    # the loop's self times and what lies under none add up to the idle time
    assert sum(got["loop_idle_s"].values()) == \
        pytest.approx(got["device_idle_s"])
    assert set(got["spans"]) == set(want["spans"])
    for span, row in want["spans"].items():
        assert got["spans"][span]["thread"] == row["thread"]
        assert got["spans"][span]["count"] == row["count"]
        for key in ("total_s", "median_s", "idle_s"):
            assert got["spans"][span][key] == pytest.approx(row[key]), \
                (span, key)
    # every span of one block carries the block's number
    for line in spans.by_line:
        assert all("seq" in args for _, _, _, args in line)


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("cell", ["resnet152.train"])
def test_new_cell_rehearses_with_the_program_span_metrics(bench, cell):
    out, lines = rehearse(cell, 1)
    assert out["correct"] is True and out["device"]["platform"] == "cpu"
    assert out["device"]["count"] == \
        [w for w in bench["workloads"] if w["name"] == cell][0]["chips"]
    want = named(bench, "per_layer", cell)
    from_spans = {n for n, m in want.items()
                  if m["source"] == "program_span"}
    assert from_spans == {"feed_stage_ms.train", "feed_put_ms.train",
                          "setup_fit_prepare_s", "setup_first_dispatch_s"}
    assert from_spans <= set(out["metrics"]) <= set(want)
    for name in from_spans:
        assert out["metrics"][name]["value"] > 0
    # a CPU has no device trace: the idle shares are left out, the table
    # is printed whole all the same, once
    assert not [n for n in out["metrics"] if n.startswith("idle_")]
    (table,) = [ln for ln in lines if ln["line"] == "program_spans"]
    assert table["loop_idle_s"] is None
    traced = int(json.load(open(os.path.join(
        HERE, "traffic", "fit_fused_k4_synthetic.json")))["traced_dispatches"])
    for span in ("mx.feed.wait", "mx.step.fused_dispatch", "mx.step.enqueue",
                 "mx.step.metric_update", "mx.step.log", "mx.step.callbacks"):
        assert table["spans"][span]["thread"] == "loop"
        assert table["spans"][span]["count"] == traced
    for span in ("mx.feed.stage", "mx.feed.pull", "mx.feed.stack",
                 "mx.feed.put", "mx.feed.enqueue"):
        assert table["spans"][span]["thread"] == "feeder"
        assert table["spans"][span]["count"] >= traced - 1
