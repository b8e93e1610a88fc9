"""The one reduction from a profiler trace to numbers.

Two steps, so that the second can be checked against a small recorded
trace (fixtures/) without a chip:

  read_events(path)   .xplane.pb -> plain dict of planes, lines and events
                      (via jax.profiler.ProfileData; nothing else needed)
  Reduced(events)     -> the traced window, per device the busy intervals
                      and idle gaps, time per op name and per category,
                      and what the host was doing in each long gap

Times are seconds, taken from the trace's own clock. Device operations are
the events of the lines named in OPS_LINES on the planes whose name starts
with DEVICE_PLANE. An operation that encloses others (a `while` around the
steps of a scan) counts for its self time only in the per-op totals, and in
full in the busy union, which is a union.

  python benchmarks/reduce/xplane.py <trace dir or .xplane.pb> [out.json]

prints what a trace holds (planes, lines, top operations, gaps): look at one
by hand before trusting a pattern below.
"""
import glob
import json
import os
import re
import sys

DEVICE_PLANE = "/device:TPU:"
OPS_LINES = ("XLA Ops",)
# the benchmark's own host annotations (jax.profiler.TraceAnnotation)
WINDOW_START, WINDOW_END = "bench.window_start", "bench.window_end"
HOST_PREFIX = "bench."
IN_PROGRAM = "in_program"

# category of a device operation: first pattern that matches
# "<opcode[:fusion kind]>|<op name>", both read by short_op() from the HLO
# instruction text that the profiler gives as the operation's name (this
# runtime records no category of its own). Kept in this one table.
CATEGORIES = (
    ("collective", re.compile(
        r"all-reduce|all-gather|reduce-scatter|collective-permute|"
        r"all-to-all|collective", re.I)),
    # on the TPU every convolution sits in an output fusion (kind=kOutput:
    # the convolution with what was fused onto its result)
    ("convolution fusion", re.compile(r"convolution|fusion:Output", re.I)),
    ("copy/transfer", re.compile(
        r"copy|infeed|outfeed|send|recv|transfer|data formatting|"
        r"dynamic-update-slice|dynamic-slice|bitcast|transpose", re.I)),
    ("other fusion", re.compile(r"fusion", re.I)),
)
OTHER = "other"


def categorize(name, opcode=""):
    key = f"{opcode}|{name}"
    for cat, pat in CATEGORIES:
        if pat.search(key):
            return cat
    return OTHER


# -- step 1: the trace file -> plain events ----------------------------------

def find_xplane(path):
    if os.path.isfile(path):
        return path
    files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    return files[-1] if files else None


_OPCODE = re.compile(r"(?<![\w.%-])([a-z][a-z0-9-]*)\(")
_KIND = re.compile(r"kind=k(\w+)")


def short_op(text):
    """("%fusion.12", "fusion:Loop") from the HLO instruction text the
    profiler gives as a device operation's name: the instruction's own
    name, and its opcode with a fusion's kind. The text after " = " is the
    result shape (tilings such as T(8,128) are upper case) and then the
    opcode, the first lower-case word before a parenthesis."""
    name, _, rest = text.partition(" = ")
    m = _OPCODE.search(rest)
    op = m.group(1) if m else ""
    kind = _KIND.search(rest) if op == "fusion" else None
    return name.strip(), f"{op}:{kind.group(1)}" if kind else op


def read_events(path):
    """{"planes": [{"name", "lines": [{"name", "events":
    [[name, start_s, duration_s, opcode], ...]}]}]} of the device
    planes' operation lines and of every host line that holds one of the
    benchmark's annotations. None where there is no trace to read."""
    path = find_xplane(path)
    if path is None:
        return None
    from jax.profiler import ProfileData
    planes = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith(DEVICE_PLANE)
        lines = []
        for line in plane.lines:
            if device and line.name not in OPS_LINES:
                continue
            events = []
            for ev in line.events:
                if device:
                    name, cat = short_op(ev.name)
                elif ev.name.startswith(HOST_PREFIX):
                    name, cat = ev.name, ""
                else:
                    continue
                events.append([name, ev.start_ns / 1e9,
                               ev.duration_ns / 1e9, cat])
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


# -- step 2: events -> numbers -----------------------------------------------

def union(intervals):
    """Merged, sorted list of (start, end) from any list of them."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        elif e > s:
            out.append((s, e))
    return out


def total(intervals):
    return sum(e - s for s, e in intervals)


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a, b):
    """The parts of the merged intervals `a` that no interval of the merged
    list `b` covers."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def self_times(events):
    """[(name, category, self seconds, start, end, is_leaf)] of one
    device's events: an event's time less that of the events it encloses
    (a `while` around the steps of a scan encloses every op of them)."""
    out, stack = [], []          # stack of [end, index into out]
    for name, start, dur, cat in sorted(events, key=lambda e: (e[1], -e[2])):
        end = start + dur
        # what ended, or only overlaps this event, does not enclose it
        while stack and stack[-1][0] + 1e-12 < end:
            stack.pop()
        if stack:
            parent = out[stack[-1][1]]
            parent[2] -= dur
            parent[5] = False
        out.append([name, cat, dur, start, end, True])
        stack.append([end, len(out) - 1])
    return [(n, c, max(t, 0.0), s, e, leaf) for n, c, t, s, e, leaf in out]


class Device:
    def __init__(self, name, events, lo, hi):
        self.name = name
        inside = [e for e in events if e[1] + e[2] > lo and e[1] < hi]
        spans = [(e[1], e[1] + e[2]) for e in inside]
        self.busy = clip(union(spans), lo, hi)
        self.busy_s = total(self.busy)
        self.gaps = subtract([(lo, hi)], self.busy)     # (start, end)
        by_cat = {}
        self.op_s, self.cat_s, self.op_cat = {}, {}, {}
        for name_, cat, t, s, e, leaf in self_times(inside):
            c = categorize(name_, cat)
            self.op_s[name_] = self.op_s.get(name_, 0.0) + t
            self.cat_s[c] = self.cat_s.get(c, 0.0) + t
            self.op_cat[name_] = c
            if leaf:
                by_cat.setdefault(c, []).append((s, e))
        self.cat_intervals = {c: clip(union(v), lo, hi)
                              for c, v in by_cat.items()}

    def exposed_s(self, category):
        """Seconds in which an operation of `category` runs on this device
        and no operation of another category does (enclosing operations
        such as a `while` left out)."""
        mine = self.cat_intervals.get(category, [])
        others = union([iv for c, v in self.cat_intervals.items()
                        if c != category for iv in v])
        return total(subtract(mine, others))


class Reduced:
    """What the metrics read. `devices` are in the order of their names."""

    def __init__(self, events):
        dev_planes = sorted((p for p in events["planes"]
                             if p["name"].startswith(DEVICE_PLANE)),
                            key=lambda p: (len(p["name"]), p["name"]))
        self.host = []          # (name, start, end) of our annotations
        for p in events["planes"]:
            if p["name"].startswith(DEVICE_PLANE):
                continue
            for line in p["lines"]:
                self.host += [(e[0], e[1], e[1] + e[2])
                              for e in line["events"]]
        dev_events = {p["name"]: [e for line in p["lines"]
                                  for e in line["events"]]
                      for p in dev_planes}
        starts = [s for n, s, _ in self.host if n == WINDOW_START]
        ends = [s for n, s, _ in self.host if n == WINDOW_END]
        every = [e for evs in dev_events.values() for e in evs]
        if starts and ends and max(ends) > min(starts):
            self.window = (min(starts), max(ends))
            self.window_from = "annotations"
        elif every:
            self.window = (min(e[1] for e in every),
                           max(e[1] + e[2] for e in every))
            self.window_from = "device events"
        else:
            self.window = (0.0, 0.0)
            self.window_from = "nothing"
        self.window_s = self.window[1] - self.window[0]
        self.devices = [Device(n, dev_events[n], *self.window)
                        for n in dev_events if dev_events[n]]

    def __bool__(self):
        return bool(self.devices) and self.window_s > 0

    @property
    def busy_s(self):
        """Mean over the devices of the seconds an operation ran."""
        return sum(d.busy_s for d in self.devices) / len(self.devices)

    def idle_share(self, device=0):
        return 1.0 - self.devices[device].busy_s / self.window_s

    def category_share_of_busy(self, category, device=0):
        d = self.devices[device]
        return d.cat_s.get(category, 0.0) / sum(d.cat_s.values())

    def host_activity(self, start, end):
        """Which of the benchmark's annotations covers most of (start,
        end); IN_PROGRAM where none covers any of it."""
        cover = {}
        for name, s, e in self.host:
            if name in (WINDOW_START, WINDOW_END):
                continue
            o = min(e, end) - max(s, start)
            if o > 0:
                cover[name] = cover.get(name, 0.0) + o
        if not cover:
            return IN_PROGRAM
        name, o = max(cover.items(), key=lambda kv: kv[1])
        return name if o >= 0.5 * (end - start) else IN_PROGRAM

    def breakdown(self, n_ops=10, n_gaps=5, device=0):
        d = self.devices[device]
        ops = sorted(d.op_s.items(), key=lambda kv: -kv[1])[:n_ops]
        gaps = sorted(d.gaps, key=lambda g: g[0] - g[1])[:n_gaps]
        return {
            "device_ops": [[f"{name} [{d.op_cat[name]}]", t]
                           for name, t in ops],
            "idle_gaps": [[self.host_activity(s, e), e - s]
                          for s, e in gaps],
        }

    def summary(self, device=0):
        d = self.devices[device]
        return {"window_s": self.window_s, "window_from": self.window_from,
                "devices": [x.name for x in self.devices],
                "busy_s": [x.busy_s for x in self.devices],
                "category_s": d.cat_s,
                "n_gaps": len(d.gaps),
                "gap_s_by_host_activity": self._gaps_by_activity(d)}

    def _gaps_by_activity(self, d):
        out = {}
        for s, e in d.gaps:
            k = self.host_activity(s, e)
            out[k] = out.get(k, 0.0) + (e - s)
        return out


def reduce_trace(path):
    """Reduced of the newest trace under `path`, or None."""
    events = read_events(path)
    if not events:
        return None
    red = Reduced(events)
    return red if red else None


def _describe(path):
    """Planes, lines, and per line the events that took most time with
    their statistics, as the profiler wrote them (no filter): what to look
    at by hand."""
    from jax.profiler import ProfileData
    for plane in ProfileData.from_file(find_xplane(path)).planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            if not evs:
                continue
            lo = min(e.start_ns for e in evs)
            hi = max(e.start_ns + e.duration_ns for e in evs)
            print(f"  LINE {line.name!r}: {len(evs)} events from "
                  f"{lo / 1e9:.6f} s to {hi / 1e9:.6f} s")
            by_name = {}
            for ev in evs:
                rec = by_name.setdefault(ev.name, [0.0, 0, ev])
                rec[0] += ev.duration_ns / 1e6
                rec[1] += 1
            top = sorted(by_name.items(), key=lambda kv: -kv[1][0])[:12]
            for name, (ms, n, ev) in top:
                stats = {k: str(v)[:60] for k, v in list(ev.stats)[:12]}
                print(f"    {ms:10.3f} ms x{n:<5d} {short_op(name)} "
                      f"{name[:70]!r} {stats}")


def main(argv):
    path = argv[1]
    _describe(path)
    events = read_events(path)
    if len(argv) > 2:
        with open(argv[2], "w") as f:
            json.dump(events, f)
    red = Reduced(events)
    print(json.dumps(red.summary(), indent=1))
    if red:
        print(json.dumps(red.breakdown(), indent=1))


if __name__ == "__main__":
    main(sys.argv)
