"""Crash flight recorder — the always-on black box.

A bounded ring of the most recent spans/events (`record()` is a dict
build + deque append, ~µs, no I/O, no device syncs), beside a head of the
process's first `HEAD_EVENTS` records that is never evicted (a start-up
timeline outlives a long run), dumped to a postmortem JSON file when
something dies:

  - `dist.DistRankFailure` (dist._fail calls `dump()` on its exit ramp),
  - a watchdog stall/deadline dump (`watchdog.dump_now` appends
    `tail_text()` next to the faulthandler stacks),
  - an uncaught exception (`install()` chains sys.excepthook),
  - SIGTERM (preemption — `install()` chains the handler, dumps, then
    re-delivers the prior disposition).

SIGKILL cannot be caught, so when `MXNET_FLIGHTREC_DIR` is set a flusher
daemon snapshots the ring to disk every `MXNET_FLIGHTREC_FLUSH_S`
seconds (atomic tmp+rename — a reader never sees a torn file). A
kill -9'd rank therefore leaves a black box at most one flush interval
stale; `cluster/launcher.py` collects every rank's file after a failed
run and names the rank that went quiet first (earliest last-event
timestamp — survivors keep recording while they wait on the corpse).

Gating: `MXNET_FLIGHTREC=0` turns recording off entirely. The ring is
host-side only and never touches device state, so it cannot perturb
numerics — "always on" is safe.
"""
from __future__ import annotations

import json
import os
import signal
import sys
import threading
import time
from collections import deque

__all__ = ["enabled", "record", "snapshot", "dump", "tail_lines",
           "tail_text", "install", "uninstall", "default_path", "rank",
           "stats", "reset"]

# analysis/locklint: record() mutates the ring under _lock (uncontended
# acquire is ~100ns — well inside the µs budget); _installed flags are
# flipped from install/uninstall only
__analysis_thread_safe__ = {"_installed"}

_lock = threading.Lock()
_dump_lock = threading.RLock()   # re-entrant: SIGTERM may land mid-dump
_PERIODIC = "periodic-flush"     # the flusher's reason
_crash_boxes = set()             # paths a crash trigger has written
HEAD_EVENTS = 2048    # the process's first records, kept for good
_head = []            # filled first; then records go to the ring
_ring = None          # deque (the tail), created lazily at first record
_total = 0            # appended since reset
_installed = {
    "excepthook": None,     # prev sys.excepthook when chained
    "sigterm": None,        # prev SIGTERM handler when chained
    "flusher": None,        # (thread, stop_event)
    "dir": None,            # where auto-dumps land
}


def enabled():
    """MXNET_FLIGHTREC master gate (default ON — the recorder is the
    always-on black box; the env dict lookup keeps the off-path cheap)."""
    return os.environ.get("MXNET_FLIGHTREC", "1") not in ("0", "false", "")


def _capacity():
    from .. import config
    try:
        return max(16, int(config.get("MXNET_FLIGHTREC_EVENTS", 4096)))
    except (TypeError, ValueError):
        return 4096


def rank():
    try:
        return int(os.environ.get("DMLC_WORKER_ID", "0") or 0)
    except ValueError:
        return 0


def record(kind, name, dur_us=None, **fields):
    """Append one event to the ring. kind is a short class ("span",
    "event", "error"); extra fields must be JSON-serializable scalars."""
    if not enabled():
        return
    ev = {"t": time.time(), "thr": threading.current_thread().name,
          "kind": kind, "name": name}
    if dur_us is not None:
        ev["dur_us"] = int(dur_us)
    if fields:
        ev.update(fields)
    global _ring, _total
    with _lock:
        if _ring is None:
            _ring = deque(maxlen=_capacity())
        if len(_head) < HEAD_EVENTS:
            _head.append(ev)
        else:
            _ring.append(ev)
        _total += 1


def snapshot(last_s=None):
    """Copy of the buffered events, head then tail (oldest first; what
    the tail dropped lay between them), optionally only the last `last_s`
    seconds (relative to the newest event, not the wall clock — a long
    stall should not empty the tail)."""
    with _lock:
        evs = _head + list(_ring) if _ring is not None else []
    if last_s is not None and evs:
        cutoff = evs[-1]["t"] - float(last_s)
        evs = [e for e in evs if e["t"] >= cutoff]
    return evs


def stats():
    """`events` buffered = `head` (never evicted) + `tail` (the ring, of
    `capacity`); `dropped` were evicted from between the two."""
    with _lock:
        head = len(_head)
        tail = len(_ring) if _ring is not None else 0
        cap = _ring.maxlen if _ring is not None else _capacity()
        return {"events": head + tail, "head": head, "tail": tail,
                "total": _total, "dropped": max(0, _total - head - tail),
                "capacity": cap}


def reset():
    """Drop all buffered events (tests)."""
    global _ring, _total
    with _lock:
        del _head[:]
        _ring = None
        _total = 0


def default_path(directory=None):
    from .. import config
    d = directory or _installed["dir"] or \
        config.get("MXNET_FLIGHTREC_DIR") or "."
    return os.path.join(str(d), f"flightrec-rank-{rank()}.json")


def dump(path=None, reason="on-demand", last_s=None):
    """Write the black box (atomic tmp+rename). Returns the path, or
    None when recording is disabled. Never raises — this runs on crash
    paths where a secondary failure must not mask the primary."""
    if not enabled():
        return None
    try:
        path = path or default_path()
        # one dump at a time, snapshot to rename: the flusher's box must
        # not share a crash hook's tmp file, nor land over its box (a
        # flush that was due when the crash came would take its reason)
        with _dump_lock:
            if reason != _PERIODIC:
                _crash_boxes.add(path)
            elif path in _crash_boxes:
                return path
            st = stats()
            box = {"version": 1, "rank": rank(), "pid": os.getpid(),
                   "reason": str(reason), "wall_time": time.time(),
                   "events": snapshot(last_s=last_s),
                   "dropped": st["dropped"], "total": st["total"]}
            if box["events"]:
                box["last_event_t"] = box["events"][-1]["t"]
            d = os.path.dirname(path)
            if d:
                os.makedirs(d, exist_ok=True)
            tmp = f"{path}.tmp.{os.getpid()}"
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump(box, f)
            os.replace(tmp, path)
        return path
    except Exception:                    # pragma: no cover
        return None


def tail_lines(n=50, last_s=None):
    """The last events formatted one per line — what watchdog.dump_now
    appends under the faulthandler stacks so a hang dump shows what the
    threads were DOING, not just where they are."""
    evs = snapshot(last_s=last_s)[-int(n):]
    out = []
    for e in evs:
        extra = {k: v for k, v in e.items()
                 if k not in ("t", "thr", "kind", "name", "dur_us")}
        dur = f" {e['dur_us'] / 1000.0:.3f}ms" if "dur_us" in e else ""
        out.append(f"  [{time.strftime('%H:%M:%S', time.localtime(e['t']))}"
                   f".{int((e['t'] % 1) * 1000):03d} {e['thr']}] "
                   f"{e['kind']} {e['name']}{dur}"
                   f"{' ' + json.dumps(extra) if extra else ''}")
    return out


def tail_text(n=50, last_s=None):
    lines = tail_lines(n=n, last_s=last_s)
    st = stats()
    head = (f"flight recorder tail ({len(lines)} of {st['events']} "
            f"buffered, {st['dropped']} dropped):")
    return "\n".join([head] + lines) if lines else \
        "flight recorder: no events buffered"


# -- crash triggers ----------------------------------------------------------

def _excepthook(exc_type, exc, tb):
    record("error", f"uncaught:{exc_type.__name__}", msg=str(exc)[:200])
    dump(reason=f"uncaught exception: {exc_type.__name__}: "
                f"{str(exc)[:200]}")
    prev = _installed["excepthook"]
    (prev or sys.__excepthook__)(exc_type, exc, tb)


def _sigterm(signum, frame):
    record("event", "SIGTERM")
    dump(reason="SIGTERM")
    prev = _installed["sigterm"]
    if callable(prev):
        prev(signum, frame)      # e.g. checkpoint's preemption hook
    elif prev == signal.SIG_DFL:
        signal.signal(signal.SIGTERM, signal.SIG_DFL)
        os.kill(os.getpid(), signal.SIGTERM)


def _flush_interval():
    from .. import config
    try:
        return float(config.get("MXNET_FLIGHTREC_FLUSH_S", "0.5") or 0)
    except (TypeError, ValueError):
        return 0.5


def _flusher(stop, directory):
    # first dump immediately (not one interval in): a rank SIGKILLed
    # inside its first flush interval must still leave a black box
    last_total = None
    path = default_path(directory)
    while True:
        with _lock:
            total = _total
        if total != last_total:
            last_total = total
            dump(path=path, reason=_PERIODIC)
        if stop.wait(_flush_interval() or 0.5):
            return


def install(directory=None):
    """Arm the auto-dump triggers: excepthook + SIGTERM chains, and —
    when a dump directory is configured — the periodic flusher that
    keeps an on-disk snapshot fresh for SIGKILL/OOM deaths. Idempotent;
    config._apply_startup calls this for every gang member."""
    if not enabled():
        return False
    from .. import config
    directory = directory or config.get("MXNET_FLIGHTREC_DIR") or None
    if directory and _installed["flusher"] is None:
        # baseline event: even a rank killed before its first span leaves
        # a box with a last_event_t, so quiet-rank triage can order it
        record("event", "flightrec.armed", pid=os.getpid())
    with _lock:
        if _installed["dir"] is None:
            _installed["dir"] = directory
        if _installed["excepthook"] is None and \
                sys.excepthook is not _excepthook:
            _installed["excepthook"] = sys.excepthook
            sys.excepthook = _excepthook
        if _installed["sigterm"] is None:
            try:
                prev = signal.getsignal(signal.SIGTERM)
                if prev is not _sigterm:
                    _installed["sigterm"] = prev
                    signal.signal(signal.SIGTERM, _sigterm)
            except (ValueError, OSError):    # non-main thread / platform
                pass
        if directory and _installed["flusher"] is None and \
                _flush_interval() > 0:
            stop = threading.Event()
            t = threading.Thread(target=_flusher, args=(stop, directory),
                                 name="flightrec-flusher", daemon=True)
            t.start()
            _installed["flusher"] = (t, stop)
    return True


def uninstall():
    """Restore chained hooks and stop the flusher (tests)."""
    with _lock:
        if _installed["excepthook"] is not None:
            if sys.excepthook is _excepthook:
                sys.excepthook = _installed["excepthook"]
            _installed["excepthook"] = None
        if _installed["sigterm"] is not None:
            try:
                if signal.getsignal(signal.SIGTERM) is _sigterm:
                    signal.signal(signal.SIGTERM, _installed["sigterm"])
            except (ValueError, OSError):
                pass
            _installed["sigterm"] = None
        flusher, _installed["flusher"] = _installed["flusher"], None
        _installed["dir"] = None
    with _dump_lock:
        _crash_boxes.clear()
    if flusher is not None:
        t, stop = flusher
        stop.set()
        t.join(timeout=2.0)
