"""Start-up as one timeline, from the program's flight recorder.

No profiler session runs during set-up, so its spans live only in the ring
of `mxnet_tpu/telemetry/flightrec.py`, whose head keeps the process's first
2,048 records. Since PR 34 a span's record holds `t0_us` (its start on
`time.perf_counter`, the clock of `run.py`'s `T_START`), `dur_us`, `id` and
`parent`, and JAX's compile pipeline is in there as `compile.trace`,
`compile.lower` and `compile.backend` spans with `fun` (and `cache` on the
last). This reader lays them on one timeline:

  the window   [start of `process.start` (else of `import.mxnet_tpu`),
               close of the `step.callbacks` span of dispatch W], W the
               traffic file's `warmup_dispatches`: what `setup_s` times,
               with the interpreter's start before `T_START` added
  self time    every microsecond of the window belongs to ONE span of the
               loop's thread: of those that cover it, the one that started
               last (a child starts after its parent; a retrospective
               event may start before it, and then owns only what no
               later span covers), or to none: `unattributed_s`. Self
               seconds and `unattributed_s` add up to the window exactly
               (integers of microseconds)

and prints one `setup_spans` line: per span name count, total and self
seconds; what the persistent cache answered; the ten longest `compile.*`
spans by `fun`; each warm-up dispatch's parts; the first dispatch split
into extraction, trace and lower, program load, first run and the Python
of `step.enqueue` around them. The six `setup_*_s` readers in
layer_metrics/ are one call each into `metric`. Every one of them is None,
and nothing is printed, where the ring's records carry no `t0_us` (a
program from before PR 34) or the fused fit never ran.

  python benchmarks/reduce/setup_spans.py <flightrec box .json> [W]

prints the summary of a dumped black box (`flightrec.dump`).
"""
import functools
import json
import os
import sys

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]

from common import emit              # noqa: E402

START, IMPORT = "process.start", "import.mxnet_tpu"
EXTRACT = "devstats.extract"
TRACE_LOWER = ("compile.trace", "compile.lower")
BACKEND = "compile.backend"
DISPATCH, ENQUEUE, FIRST_RUN, CLOSE = ("step.fused_dispatch", "step.enqueue",
                                       "step.metric_update", "step.callbacks")
UNATTRIBUTED = "unattributed"
METRICS = ("setup_import_s", "setup_unattributed_s", "setup_extract_s",
           "setup_trace_lower_s", "setup_program_load_s", "setup_first_run_s")


def timed(records):
    """The ring's span records that can be put on a timeline."""
    return [e for e in records if e.get("kind") == "span" and "t0_us" in e
            and "dur_us" in e and "id" in e]


def partition(spans, lo, hi):
    """{id: microseconds} of [lo, hi) owned by each of `spans` (records of
    ONE thread), and the microseconds no span covers. The owner of an
    instant is the covering span that started last (the later record of
    two that start together: the inner one)."""
    cut = []
    for e in spans:
        a, b = max(lo, e["t0_us"]), min(hi, e["t0_us"] + e["dur_us"])
        if b > a:
            cut.append((a, b, (e["t0_us"], e["id"]), e["id"]))
    opens = sorted(cut, key=lambda c: c[0])
    edges = sorted({lo, hi} | {c[0] for c in cut} | {c[1] for c in cut})
    own, free, active, nxt = {}, 0, {}, 0
    for a, b in zip(edges, edges[1:]):
        while nxt < len(opens) and opens[nxt][0] <= a:
            c = opens[nxt]
            active[c[3]] = c
            nxt += 1
        for ident in [i for i, c in active.items() if c[1] <= a]:
            del active[ident]
        if active:
            owner = max(active.values(), key=lambda c: c[2])[3]
            own[owner] = own.get(owner, 0) + (b - a)
        else:
            free += b - a
    return own, free


class Timeline:
    """The set-up window of one process's ring. False where there is no
    timeline to read."""

    def __init__(self, records, warmup_dispatches):
        self.all = timed(records)
        self.by_id = {e["id"]: e for e in self.all}
        self.warm = int(warmup_dispatches)
        self.window = self.thread = self.starts_with = None
        close = [e for e in self.all if e["name"] == CLOSE
                 and e.get("seq") == self.warm - 1]
        begin = next((e for name in (START, IMPORT) for e in self.all
                      if e["name"] == name), None)
        if begin is None or not close:
            return
        self.starts_with = begin["name"]
        self.thread = close[0]["thr"]
        self.window = (begin["t0_us"], close[0]["t0_us"] + close[0]["dur_us"])
        self.main = [e for e in self.all if e["thr"] == self.thread]
        self.own, self.free = partition(self.main, *self.window)

    def __bool__(self):
        return self.window is not None and self.window[1] > self.window[0]

    # -- the tree -------------------------------------------------------------

    def ancestors(self, e):
        """The spans above `e`, nearest first (as far as the ring has
        them)."""
        seen = set()
        while e.get("parent") in self.by_id and e["parent"] not in seen:
            seen.add(e["parent"])
            e = self.by_id[e["parent"]]
            yield e

    def under(self, e, name, seq=None):
        """`e` is, or lies under, a span `name` (of dispatch `seq`)."""
        return any(a["name"] == name and (seq is None or a.get("seq") == seq)
                   for a in [e, *self.ancestors(e)])

    def kind(self, e):
        """The part of set-up that the self time of `e` belongs to."""
        if self.under(e, EXTRACT):
            return "extract"
        if e["name"] in TRACE_LOWER:
            return "trace_lower"
        if e["name"] == BACKEND:
            return "program_load"
        if self.under(e, FIRST_RUN, seq=0):
            return "first_run"
        if e["name"] == ENQUEUE and e.get("seq") == 0:
            return "enqueue_self"
        return "other"

    # -- seconds --------------------------------------------------------------

    def clipped_us(self, e):
        lo, hi = self.window
        return max(0, min(hi, e["t0_us"] + e["dur_us"]) - max(lo, e["t0_us"]))

    def in_window(self, e):
        lo, hi = self.window
        return lo <= e["t0_us"] < hi

    def self_us(self, keep):
        return sum(us for ident, us in self.own.items()
                   if keep(self.by_id[ident]))

    def parts(self, lo, hi):
        """{kind: seconds} of [lo, hi) by the owner of each microsecond of
        the loop's thread; what no span owns is `unattributed`."""
        own, free = partition(self.main, lo, hi)
        out = {UNATTRIBUTED: free / 1e6}
        for ident, us in own.items():
            k = self.kind(self.by_id[ident])
            out[k] = out.get(k, 0.0) + us / 1e6
        return out

    def table(self):
        """{name: {thread, count, total_s, self_s}}: the loop thread's
        spans under their names (self seconds from the partition), other
        threads' as `name@thread` (no self time: they run beside it)."""
        rows = {}
        for e in self.all:
            us = self.clipped_us(e)
            if not us and not self.in_window(e):
                continue
            main = e["thr"] == self.thread
            key = e["name"] if main else f"{e['name']}@{e['thr']}"
            row = rows.setdefault(key, {
                "thread": "loop" if main else e["thr"], "count": 0,
                "total_s": 0.0, "self_s": 0.0 if main else None})
            row["count"] += self.in_window(e)
            row["total_s"] += us / 1e6
            if main:
                row["self_s"] += self.own.get(e["id"], 0) / 1e6
        return rows

    def dispatches(self):
        """The warm-up dispatches, one row each: the wait for the feed
        before it, its `step.fused_dispatch`, `step.enqueue`'s self time
        (the Python around extraction and the jit call), the sync on its
        outputs, the log and the callbacks."""
        columns = {"feed.wait": "feed_wait_s", DISPATCH: "fused_dispatch_s",
                   FIRST_RUN: "metric_update_s", "step.log": "log_s",
                   CLOSE: "callbacks_s"}
        rows = []
        for seq in range(self.warm):
            row = {"seq": seq, **dict.fromkeys(columns.values(), 0.0)}
            for e in self.main:
                if e.get("seq") == seq and e["name"] in columns:
                    row[columns[e["name"]]] += self.clipped_us(e) / 1e6
            row["enqueue_self_s"] = self.self_us(
                lambda e: e["name"] == ENQUEUE and e.get("seq") == seq) / 1e6
            rows.append(row)
        return rows

    def first_dispatch(self):
        """The first `step.fused_dispatch` split by kind; None without."""
        first = [e for e in self.main if e["name"] == DISPATCH
                 and e.get("seq") == 0]
        if not first:
            return None
        lo = first[0]["t0_us"]
        parts = self.parts(lo, lo + first[0]["dur_us"])
        return {"total_s": first[0]["dur_us"] / 1e6,
                **{k + "_s": v for k, v in sorted(parts.items())}}

    def compiles(self):
        """(what the cache answered, the ten longest `compile.*` spans)
        of every thread in the window."""
        spans = [e for e in self.all if e["name"].startswith("compile.")
                 and self.in_window(e)]
        cache = {}
        for e in spans:
            if e["name"] == BACKEND:
                answer = e.get("cache", "off")
                cache[answer] = cache.get(answer, 0) + 1
        longest = sorted(spans, key=lambda e: -e["dur_us"])[:10]
        return cache, [{
            "name": e["name"], "fun": e.get("fun"), "s": e["dur_us"] / 1e6,
            **{k: e[k] for k in ("cache", "retrieval_s") if k in e},
            "under": next((a["name"] for a in self.ancestors(e)), None),
            "thread": "loop" if e["thr"] == self.thread else e["thr"]}
            for e in longest]

    def metrics(self):
        """The six per-layer metrics, in seconds."""
        def named(*names):
            return lambda e: e["name"] in names and not self.under(e, EXTRACT)
        return {
            "setup_import_s": sum(
                self.clipped_us(e) for e in self.main
                if e["name"] == IMPORT) / 1e6,
            # under no span of the program's own: before the import
            # (`process.start`: the interpreter, what the caller imported
            # and started first) and around the program's spans
            "setup_unattributed_s": (self.free + self.self_us(
                lambda e: e["name"] == START)) / 1e6,
            # whole, on whichever thread it ran
            "setup_extract_s": sum(
                self.clipped_us(e) for e in self.all
                if e["name"] == EXTRACT) / 1e6,
            "setup_trace_lower_s": self.self_us(named(*TRACE_LOWER)) / 1e6,
            "setup_program_load_s": self.self_us(named(BACKEND)) / 1e6,
            "setup_first_run_s": self.self_us(
                lambda e: self.kind(e) == "first_run") / 1e6}

    def summary(self):
        lo, hi = self.window
        table = self.table()
        cache, longest = self.compiles()
        fit = [e["t0_us"] for e in self.main if e["name"].startswith("fit.")]
        return {"window_s": (hi - lo) / 1e6, "starts_with": self.starts_with,
                "warmup_dispatches": self.warm, "thread": self.thread,
                "unattributed_s": self.free / 1e6,
                # the caller's own work before it calls fit (the rest lies
                # between the fit's spans)
                "unattributed_before_fit_s": partition(
                    self.main, lo, min(fit))[1] / 1e6 if fit else None,
                "self_sum_s": sum(self.own.values()) / 1e6,
                "spans": table, "cache": cache, "compile_longest": longest,
                "dispatches": self.dispatches(),
                "first_dispatch": self.first_dispatch(),
                "metrics": self.metrics()}


# -- what the per-layer readers call ------------------------------------------

@functools.lru_cache(maxsize=None)
def _of_ring(warmup_dispatches, box=None):
    from mxnet_tpu.telemetry import flightrec
    timeline = Timeline(flightrec.snapshot(), warmup_dispatches)
    if not timeline:
        return None
    extra = {"ring": flightrec.stats()}
    if box:
        # the whole ring beside the run's trace, for `main` below
        extra["box"] = flightrec.dump(path=box, reason="setup_spans")
    t_start = getattr(sys.modules.get("__main__"), "T_START", None)
    if t_start is not None:
        # what the window holds before the clock `setup_s` is counted on
        extra["before_t_start_s"] = t_start - timeline.window[0] / 1e6
    emit("setup_spans", **timeline.summary(), **extra)
    return timeline.metrics()


def metric(ctx, name):
    """One of METRICS for this run (the timeline is built once a process
    and printed as one `setup_spans` line), or None where the ring cannot
    be put on a timeline. Never raises: a reader's fault is no run's."""
    try:
        here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        values = _of_ring(int(ctx.traffic["warmup_dispatches"]), os.path.join(
            here, ".bench_scratch", ctx.cell["name"], "flightrec.json"))
        return None if values is None else values[name]
    except Exception:
        return None


# -- by hand ------------------------------------------------------------------

def main(argv):
    with open(argv[1], encoding="utf-8") as f:
        box = json.load(f)
    timeline = Timeline(box["events"], int(argv[2]) if len(argv) > 2 else 3)
    print(json.dumps(timeline.summary() if timeline else None, indent=1))


if __name__ == "__main__":
    main(sys.argv)
