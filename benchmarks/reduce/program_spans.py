"""The program's own spans, read out of the profiler's trace.

`mxnet_tpu/telemetry/tracing.py` enters a `jax.profiler.TraceAnnotation`
named `"mx." + name` for every span it times, so a traced run's .xplane.pb
holds them on the host planes, on the clock of the device operations. This
reader takes them from the same file the run's `Reduced` came from and puts
the device's idle time down to the host work that lay under it.

  read_events(path)   .xplane.pb -> the plain dict of reduce/xplane.py's
                      read_events (device operation lines, host lines),
                      with the `mx.*` events kept beside the `bench.*`
                      ones: a host event is [name, start_s, duration_s,
                      arguments], arguments as the annotation carried them
                      ({"seq": 3}); one entry of "lines" per thread
  Spans(events)       -> per span name: thread, count, total and median
                      seconds in the traced window, and the seconds of it
                      in which device 0 ran nothing

Threads are told apart by line, never by line name (both are `python`):
the loop is the line that holds `mx.step.fused_dispatch`, the feeder the
one that holds `mx.feed.stage`. The loop's spans nest (`step.enqueue` in
`step.fused_dispatch`), so each gets the idle seconds of its SELF time; the
self times do not overlap, and with the idle seconds under no span of the
loop they add up to the device's idle time exactly.

A span counts (count, median) in the window in which it STARTS; its
seconds (total, idle) are the part of it inside the window.

  python benchmarks/reduce/program_spans.py <trace dir or .xplane.pb> [out.spans.json.gz]

prints the table and, with a second argument, writes the events cut to the
window (what fixtures/*.spans.json.gz are).
"""
import functools
import gzip
import json
import os
import re
import sys
import time

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]

from common import emit, quantile    # noqa: E402
from reduce import xplane            # noqa: E402

PREFIX = "mx."
LOOP_MARK, FEEDER_MARK = "mx.step.fused_dispatch", "mx.feed.stage"
SETUP_SPANS = ("fit.bind", "fit.init_params", "fit.trainer_init",
               "fit.init_state")
UNATTRIBUTED = "unattributed"
# an annotation whose arguments the profiler left in the name:
# "mx.feed.wait#feed=x,seq=3#"
_ENCODED = re.compile(r"^([^#]*)#(.*)#$")


def _number(v):
    if isinstance(v, (int, float)):
        return v
    try:
        return int(v)
    except (TypeError, ValueError):
        return str(v)


def split_name(name, stats=()):
    """(bare name, arguments) of a host event."""
    args = {k: _number(v) for k, v in stats}
    m = _ENCODED.match(name)
    if m:
        name = m.group(1)
        for item in filter(None, m.group(2).split(",")):
            k, _, v = item.partition("=")
            args.setdefault(k, _number(v))
    return name, args


def read_events(path):
    """As reduce/xplane.py's read_events, with the program's spans kept.
    None where there is no trace to read."""
    path = xplane.find_xplane(path)
    if path is None:
        return None
    from jax.profiler import ProfileData
    planes = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith(xplane.DEVICE_PLANE)
        lines = []
        for line in plane.lines:
            if device and line.name not in xplane.OPS_LINES:
                continue
            events = []
            for ev in line.events:
                if device:
                    name, extra = xplane.short_op(ev.name)
                elif ev.name.startswith((PREFIX, xplane.HOST_PREFIX)):
                    name, extra = split_name(ev.name, ev.stats)
                else:
                    continue
                events.append([name, ev.start_ns / 1e9,
                               ev.duration_ns / 1e9, extra])
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def window_of(events):
    """The traced window as reduce/xplane.py's Reduced takes it from the
    benchmark's two annotations; None without them."""
    starts, ends = [], []
    for p in events["planes"]:
        for line in p["lines"]:
            for name, start, _, _ in line["events"]:
                if name == xplane.WINDOW_START:
                    starts.append(start)
                elif name == xplane.WINDOW_END:
                    ends.append(start)
    if starts and ends and max(ends) > min(starts):
        return min(starts), max(ends)
    return None


class Spans:
    """The `mx.*` spans of one trace inside `window`. `idle` is device 0's
    idle intervals in that window (a Reduced's `devices[0].gaps`), or None
    where no device was traced: then every idle figure is None."""

    def __init__(self, events, window=None, idle=None):
        self.window = window or window_of(events)
        self.idle = idle
        self.by_line = []           # per host line: [(name, start, end, args)]
        for p in events["planes"]:
            if p["name"].startswith(xplane.DEVICE_PLANE):
                continue
            for line in p["lines"]:
                spans = [(n, s, s + d, a or {}) for n, s, d, a in
                         line["events"] if n.startswith(PREFIX)]
                if spans:
                    self.by_line.append(
                        sorted(spans, key=lambda e: (e[1], -e[2])))
        self.loop = self._line_of(LOOP_MARK)
        self.feeder = self._line_of(FEEDER_MARK)

    def __bool__(self):
        return bool(self.by_line) and self.window is not None

    @property
    def window_s(self):
        return self.window[1] - self.window[0]

    def _line_of(self, mark):
        for i, spans in enumerate(self.by_line):
            if any(n == mark for n, *_ in spans):
                return i
        return None

    def thread(self, line):
        return {self.loop: "loop", self.feeder: "feeder"}.get(
            line, f"line{line}")

    def intervals(self, name, line=None):
        """The window's part of every span `name` (of one line, or all)."""
        lines = self.by_line if line is None else [self.by_line[line]]
        return xplane.clip(xplane.union(
            [(s, e) for spans in lines for n, s, e, _ in spans
             if n == name]), *self.window)

    def durations(self, name, line=None):
        """Whole durations of the spans `name` that start in the window."""
        lines = self.by_line if line is None else [self.by_line[line]]
        lo, hi = self.window
        return [e - s for spans in lines for n, s, e, _ in spans
                if n == name and lo <= s < hi]

    def idle_s(self, intervals):
        if self.idle is None:
            return None
        return xplane.total(xplane.subtract(
            intervals, xplane.subtract(intervals, self.idle)))

    def self_intervals(self, line):
        """{name: the parts of its spans that no span nested in them
        covers}, for one thread's spans, clipped to the window."""
        out, stack = {}, []     # stack of [name, end, cursor, pieces]

        def close(top):
            name, end, cur, pieces = top
            if end > cur:
                pieces.append((cur, end))
            out.setdefault(name, []).extend(pieces)

        for name, s, e, _ in self.by_line[line]:
            while stack and stack[-1][1] <= s:
                close(stack.pop())
            if stack:
                top = stack[-1]
                if s > top[2]:
                    top[3].append((top[2], s))
                top[2] = max(top[2], e)
            stack.append([name, e, s, []])
        while stack:
            close(stack.pop())
        return {n: xplane.clip(xplane.union(iv), *self.window)
                for n, iv in out.items()}

    def loop_idle(self):
        """{span name of the loop thread: idle seconds under its self
        time} and, under UNATTRIBUTED, the idle seconds under none of
        them: they add up to the device's idle time. None without a
        device trace or a loop thread."""
        if self.idle is None or self.loop is None:
            return None
        selfs = self.self_intervals(self.loop)
        out = {n: self.idle_s(iv) for n, iv in selfs.items()}
        covered = xplane.union([iv for v in selfs.values() for iv in v])
        out[UNATTRIBUTED] = xplane.total(
            xplane.subtract(self.idle, covered))
        return out

    def table(self):
        """{name: {thread, count, total_s, median_s, idle_s}} over every
        span name and thread; a name on two threads gets `name@thread`
        for the second."""
        rows = {}
        for line, spans in enumerate(self.by_line):
            for name in sorted({n for n, *_ in spans}):
                iv = self.intervals(name, line)
                durs = self.durations(name, line)
                if not iv and not durs:
                    continue
                key = name if name not in rows else \
                    f"{name}@{self.thread(line)}"
                rows[key] = {
                    "thread": self.thread(line), "count": len(durs),
                    "total_s": xplane.total(iv),
                    "median_s": quantile(durs, 0.5) if durs else None,
                    "idle_s": self.idle_s(iv)}
        return rows

    def summary(self):
        idle = self.loop_idle()
        return {"window_s": self.window_s,
                "device_idle_s": None if self.idle is None
                else xplane.total(self.idle),
                "loop_idle_s": idle, "spans": self.table()}


# -- what the per-layer readers call ------------------------------------------

def _this_run_wrote(path):
    """False for a trace that was on disk before this process began (an
    earlier run's, where this one never started the profiler)."""
    t_start = getattr(sys.modules.get("__main__"), "T_START", None)
    if t_start is None:
        return True
    began = time.time() - (time.perf_counter() - t_start)
    return os.path.getmtime(path) >= began - 1.0


@functools.lru_cache(maxsize=None)
def _of_profile(profile_dir, reduced):
    path = xplane.find_xplane(profile_dir)
    if path is None or not _this_run_wrote(path):
        return None
    events = read_events(path)
    spans = Spans(events, window=reduced.window if reduced else None,
                  idle=reduced.devices[0].gaps if reduced else None)
    if not spans:
        return None
    emit("program_spans", **spans.summary())
    return spans


def of_run(ctx):
    """The Spans of this run's trace (read once a process; the table is
    printed as one `program_spans` line), or None where the run wrote no
    trace or the program no span into it."""
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return _of_profile(os.path.join(here, ".bench_scratch",
                                    ctx.cell["name"], "profile"),
                       ctx.trace or None)


def idle_share(ctx, name):
    """Percent of the traced window in which device 0 ran nothing while
    the loop thread was in the self time of span `name` (UNATTRIBUTED: in
    no span at all). None without a device trace."""
    spans = of_run(ctx)
    idle = spans.loop_idle() if spans else None
    if idle is None:
        return None
    return 100.0 * idle.get(name, 0.0) / spans.window_s


def feeder_median_ms(ctx, name):
    """Median milliseconds of the feeder thread's spans `name` that start
    in the window."""
    spans = of_run(ctx)
    if not spans or spans.feeder is None:
        return None
    durs = spans.durations(name, spans.feeder)
    return 1e3 * quantile(durs, 0.5) if durs else None


def ring_spans():
    """The spans of the program's flight recorder (on by default; it holds
    the process's first events for as long as nothing was dropped), oldest
    first: [{"name", "dur_us", ...}]."""
    from mxnet_tpu.telemetry import flightrec
    return [e for e in flightrec.snapshot() if e.get("kind") == "span"]


def setup_fit_prepare_s():
    """Seconds in the four set-up steps of the fused fit, or None where
    the run never called it (or the ring has dropped them)."""
    durs = {e["name"]: e["dur_us"] for e in reversed(ring_spans())
            if e["name"] in SETUP_SPANS}
    if set(durs) != set(SETUP_SPANS):
        return None
    return sum(durs.values()) / 1e6


def setup_first_dispatch_s():
    """Seconds of the process's first fused dispatch: program load or
    compile, devstats' extraction, the first run."""
    from mxnet_tpu.telemetry import flightrec
    spans = ring_spans()
    names = [e["name"] for e in spans]
    if "step.fused_dispatch" not in names:
        return None
    # the first one in the ring is the process's first only if nothing
    # older was dropped, or set-up's last span is still ahead of it
    if flightrec.stats()["dropped"] and "fit.init_state" not in names:
        return None
    return spans[names.index("step.fused_dispatch")]["dur_us"] / 1e6


# -- by hand ------------------------------------------------------------------

def cut_to_window(events):
    """The events that overlap the traced window, times counted from its
    start: what a fixture keeps."""
    lo, hi = window_of(events)
    planes = []
    for p in events["planes"]:
        lines = []
        for line in p["lines"]:
            kept = [[n, s - lo, d, a] for n, s, d, a in line["events"]
                    if s + d >= lo and s <= hi]
            if kept:
                lines.append({"name": line["name"], "events": kept})
        if lines:
            planes.append({"name": p["name"], "lines": lines})
    return {"planes": planes}


def of_events(events):
    """Spans of a plain events dict over its own window and device 0."""
    red = xplane.Reduced(events)
    return Spans(events, window=red.window if red else None,
                 idle=red.devices[0].gaps if red else None)


def main(argv):
    events = read_events(argv[1])
    if len(argv) > 2:
        events = cut_to_window(events)
        with gzip.open(argv[2], "wt") as f:
            json.dump(events, f)
    print(json.dumps(of_events(events).summary(), indent=1))


if __name__ == "__main__":
    main(sys.argv)
