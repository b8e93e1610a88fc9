"""Which mechanism a device operation belongs to, read from the trace.

The program runs its mechanisms under `jax.named_scope`s (`mx.kda`,
`mx.kda.core`, `mx.mla`, `mx.flash_attention`, `mx.moe.route`,
`mx.moe.experts`, `mx.moe.experts.matmul`, `mx.moe.shared`, `mx.mlp`,
`mx.lm_head`, `mx.optimizer`); XLA carries the scope in an operation's
metadata (`op_name`), forward and backward, and the profiler files it with
the operation's event metadata (`jit(multi)/.../transpose(jvp(mx.kda))/
mx.kda.core/dot_general`; seen in a v5e trace, PR 26). `reduce/xplane.py`
keeps an operation's name and opcode only, so this reader goes back to the
same .xplane.pb for the scope.

  Scopes(path, window)   per innermost scope the self seconds of device 0's
                         operations inside the window (an enclosing `while`
                         counts for its own time only), split into forward
                         and backward (`transpose(` or a rematerialised
                         computation in the operation's path)

One mechanism loses its scope on the way: the TPU compiler turns
`jax.lax.ragged_dot` into kernels of its own (`%ragged-dot-none.N`, with a
`%ragged-dot-metadata.N` beside them; op_name `ragged-dot-none`, seen in the
program compiled for a v5e and in its trace, PR 26), outside every scope.
Only the held experts' grouped products are ragged, so such an operation is
filed under `mx.moe.experts.matmul`, where the program had put it.

A trace without scopes (a program that has none) gives an empty table; the
readers then return None and their metrics are left out of the line.

  python benchmarks/reduce/op_scopes.py <trace dir or .xplane.pb>
"""
import functools
import json
import os
import re
import sys

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]

from common import emit              # noqa: E402
from reduce import xplane            # noqa: E402

SCOPE = re.compile(r"mx\.[a-z_]+(?:\.[a-z_]+)*")
BACKWARD = re.compile(r"transpose\(|rematted_computation")
RAGGED = re.compile(r"ragged-dot")
RAGGED_SCOPE = "mx.moe.experts.matmul"
UNSCOPED = "unscoped"


def scope_of(texts):
    """(innermost `mx.*` scope, is_backward) of an operation, from its name
    text and its string statistics; (UNSCOPED, False) where none names
    one. The path of scopes reads outermost first, so the last match of the
    first text that has one is the innermost."""
    for text in texts:
        found = SCOPE.findall(text)
        if found:
            return found[-1], bool(BACKWARD.search(text))
    if any(RAGGED.search(text) for text in texts):
        return RAGGED_SCOPE, False
    return UNSCOPED, False


# -- the trace file's own metadata ----------------------------------------------
# jax.profiler.ProfileData gives an event's name and its own statistics; the
# scope sits in the statistics of the event's METADATA (XEventMetadata.stats,
# the string the profiler files under `tf_op`), which ProfileData leaves out.
# So the file is read once more as what it is, a protocol buffer (XSpace,
# tsl/profiler/protobuf/xplane.proto), with the few fields that are needed:
#   XSpace.planes = 1;  XPlane.name = 2, .event_metadata = 4 and
#   .stat_metadata = 5 (maps: key 1, value 2);  XEventMetadata.name = 2,
#   .display_name = 4, .stats = 5;  XStat.str_value = 5, .ref_value = 7 (the
#   id of a stat_metadata entry whose NAME is the string);
#   XStatMetadata.id = 1, .name = 2

def _varint(buf, i):
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if not b & 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of a message's fields: an int for a varint,
    bytes for a length-delimited field; fixed-width fields are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield number, value
        elif wire == 2:
            size, i = _varint(buf, i)
            yield number, buf[i:i + size]
            i += size
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")


def metadata_texts(raw):
    """{operation's name: [the strings among its metadata's statistics]}
    for the first TPU device plane of a serialized XSpace."""
    planes = {}
    for number, plane in _fields(raw):
        if number != 1:
            continue
        name = next((v.decode("utf-8", "replace") for n, v in _fields(plane)
                     if n == 2 and isinstance(v, bytes)), "")
        if name.startswith(xplane.DEVICE_PLANE):
            planes[name] = plane
    out = {}
    if not planes:
        return out
    plane = planes[min(planes, key=lambda n: (len(n), n))]
    entries = {4: [], 5: []}
    for number, entry in _fields(plane):
        if number in entries:
            entries[number].append(
                next((v for n, v in _fields(entry) if n == 2), b""))
    referred = {}
    for meta in entries[5]:
        fields = dict(_fields(meta))
        if isinstance(fields.get(2), bytes):
            referred[fields.get(1, 0)] = fields[2].decode("utf-8", "replace")
    for meta in entries[4]:
        names, texts = [], []
        for n, v in _fields(meta):
            if n in (2, 4) and isinstance(v, bytes):
                names.append(v.decode("utf-8", "replace"))
            elif n == 5 and isinstance(v, bytes):
                for m, s in _fields(v):
                    if m == 5 and isinstance(s, bytes):
                        texts.append(s.decode("utf-8", "replace"))
                    elif m == 7 and s in referred:
                        texts.append(referred[s])
        for name in names:
            out[name] = texts
    return out


def read_ops(path):
    """[[scope, start_s, duration_s, "b"|"f"]] of device 0's operations."""
    path = xplane.find_xplane(path)
    if path is None:
        return []
    with open(path, "rb") as f:
        texts = metadata_texts(f.read())
    from jax.profiler import ProfileData
    planes = sorted((p for p in ProfileData.from_file(path).planes
                     if p.name.startswith(xplane.DEVICE_PLANE)),
                    key=lambda p: (len(p.name), p.name))
    out, scope_of_name = [], {}
    for line in (planes[0].lines if planes else ()):
        if line.name not in xplane.OPS_LINES:
            continue
        for ev in line.events:
            found = scope_of_name.get(ev.name)
            if found is None:
                found = scope_of_name[ev.name] = scope_of(
                    texts.get(ev.name, []) + [ev.name])
            out.append([found[0], ev.start_ns / 1e9, ev.duration_ns / 1e9,
                        "b" if found[1] else "f", ev.name])
    return out


class Scopes:
    def __init__(self, ops, window):
        lo, hi = window
        inside = [e for e in ops if e[1] + e[2] > lo and e[1] < hi]
        self.self_s = {}            # scope -> {"f": s, "b": s}
        unscoped = {}               # operation -> self seconds
        for (scope, op), way, t, _, _, _ in xplane.self_times(
                [[(e[0], e[4] if len(e) > 4 else ""), e[1], e[2], e[3]]
                 for e in inside]):
            row = self.self_s.setdefault(scope, {"f": 0.0, "b": 0.0})
            row[way] += t
            if scope == UNSCOPED:
                unscoped[op] = unscoped.get(op, 0.0) + t
        self.busy_s = sum(v["f"] + v["b"] for v in self.self_s.values())
        # what no scope names, by operation: a rule that `scope_of` lacks
        # is read off this list
        self.unscoped_top = sorted(unscoped.items(),
                                   key=lambda kv: -kv[1])[:8]

    def __bool__(self):
        return any(s != UNSCOPED for s in self.self_s)

    def seconds(self, prefix):
        """Self seconds, forward and backward, of every scope that is
        `prefix` or lies under it."""
        return sum(v["f"] + v["b"] for s, v in self.self_s.items()
                   if s == prefix or s.startswith(prefix + "."))

    def share_of_busy(self, prefix):
        return self.seconds(prefix) / self.busy_s if self.busy_s else None

    def summary(self):
        return {"busy_self_s": self.busy_s, "scopes": {
            s: {"forward_s": v["f"], "backward_s": v["b"]}
            for s, v in sorted(self.self_s.items())},
            "unscoped_largest": [[name[:80], round(seconds, 6)]
                                 for name, seconds in self.unscoped_top]}


@functools.lru_cache(maxsize=None)
def _of_profile(profile_dir, window):
    scopes = Scopes(read_ops(profile_dir), window)
    if not scopes:
        return None
    emit("op_scopes", **scopes.summary())
    return scopes


def of_run(ctx):
    """The Scopes of this run's trace (read once a process, printed as one
    `op_scopes` line), or None without a device trace or without scopes."""
    if not ctx.trace:
        return None
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return _of_profile(os.path.join(here, ".bench_scratch",
                                    ctx.cell["name"], "profile"),
                       tuple(ctx.trace.window))


def share(ctx, prefix):
    """Percent of device 0's busy (self) time under scope `prefix`."""
    scopes = of_run(ctx)
    value = scopes.share_of_busy(prefix) if scopes else None
    return None if value is None else 100.0 * value


def traced_steps(ctx):
    return int(ctx.traffic["traced_dispatches"]) * \
        int(ctx.host["steps_per_dispatch"])


def roofline_share(ctx, prefix, flops, nbytes):
    """Percent: the least time the chip could take for `flops` and `nbytes`
    a step (the larger of operations over the bf16 peak and bytes over the
    memory's rate) over the seconds a step spends under scope `prefix`."""
    scopes = of_run(ctx)
    seconds = scopes.seconds(prefix) if scopes else 0.0
    if not seconds:
        return None
    least = max(flops / ctx.peaks["bf16_flops_per_s"],
                nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least * traced_steps(ctx) / seconds


def main(argv):
    ops = read_ops(argv[1])
    red = xplane.reduce_trace(argv[1])
    window = red.window if red else (min(o[1] for o in ops),
                                     max(o[1] + o[2] for o in ops))
    print(json.dumps(Scopes(ops, window).summary(), indent=1))


if __name__ == "__main__":
    main(sys.argv)
