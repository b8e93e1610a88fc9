"""What every runner shares: the run's environment, compile accounting,
the error norm of the correctness checks, the lines a run prints."""
import contextlib
import json
import os
import shutil
import time


class CompileMeter:
    """Seconds and counts of XLA backend compiles, from JAX's own events
    (copied from chip_smoke.py): every compile request, cache hit or not,
    fires the duration event, and the persistent cache fires hit events."""

    def __init__(self):
        import jax.monitoring as mon
        self.seconds = 0.0
        self.requests = 0
        self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.requests += 1

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return {"seconds": self.seconds, "requests": self.requests,
                "cache_hits": self.cache_hits}


class Env:
    """One run: the cell and its files, the arguments of the command, the
    clock that started with the process, and where scratch files go."""

    def __init__(self, cell, config, traffic, args, t_start, meter, devices,
                 work_dir, load):
        self.cell, self.config, self.traffic = cell, config, traffic
        self.seed, self.seconds = args.seed, float(args.seconds)
        self.trace, self.rehearse = bool(args.trace), args.rehearse
        self.chips = int(cell["chips"])
        self.t_start, self.meter = t_start, meter
        self.devices = devices          # the jax devices this cell uses
        self.work_dir = work_dir
        self.load = load                # load(directory, name) -> module

    def since_start(self):
        return time.perf_counter() - self.t_start

    def annotate(self, name):
        """A host span in the profiler's own trace (TraceAnnotation) in a
        traced run, nothing otherwise: what the host was doing in a gap."""
        if not self.trace:
            return contextlib.nullcontext()
        from jax.profiler import TraceAnnotation
        return TraceAnnotation(name)

    def start_trace(self):
        """Start the profiler, writing under the run's work directory
        (returned). Host spans are TraceMe's; Python's own tracer is off,
        it slows the host and swells the trace."""
        import jax
        profile_dir = os.path.join(self.work_dir, "profile")
        shutil.rmtree(profile_dir, ignore_errors=True)  # an earlier run's
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(profile_dir, profiler_options=opts)
        return profile_dir

    def rng(self, stream):
        """A numpy Generator for one named use of the seed, so that uses
        do not share draws. --seed may be any whole number."""
        import numpy as np
        return np.random.default_rng([int(self.seed), int(stream)])


def emit(kind, **fields):
    """One JSON line of what is worth reading besides the result."""
    print(json.dumps({"line": kind, **fields},
                     default=lambda o: o.item()), flush=True)   # numpy scalars


def check(cond, what, faults):
    """Record a failed correctness check; the run goes on to its end."""
    if not cond:
        faults.append(what)
    return bool(cond)


def rel_err(a, b):
    """Relative Frobenius error of a against the reference b, in float64."""
    import numpy as np
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def quantile(values, q):
    """Linear-interpolated quantile of a non-empty list."""
    v = sorted(values)
    pos = q * (len(v) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def memory_stats(devices):
    """What the runtime says of each device's memory, whole."""
    return [d.memory_stats() or {} for d in devices]


def peak_memory_bytes(devices):
    """Peak bytes on the fullest device, as the runtime reports them: its
    peak of live buffers (`peak_bytes_in_use`: parameters, staged inputs,
    outputs) plus its peak reservation for running programs
    (`peak_bytes_reserved`: XLA's temporaries, which the first counter
    leaves out — a probe program with 2 GiB of temporaries moved only the
    second; PERF.md, PR 23). The two peaks need not fall together, so this
    is an upper bound, a close one. 0 where the backend keeps no statistics
    (the CPU of a rehearsal)."""
    return int(max(s.get("peak_bytes_in_use", 0) +
                   s.get("peak_bytes_reserved", 0)
                   for s in memory_stats(devices)))
