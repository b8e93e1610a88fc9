#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json, in a process of its own.

  python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the chip or exits non-zero (no CPU fallback), sets up, warms every
shape of the cell, measures for --seconds, checks correctness outside the
window, and prints the contract's one JSON line LAST. Earlier lines, one
JSON object each, carry what else is worth reading (set-up split, compile
requests and cache hits, losses, latency quantiles, the trace summary).

Everything is found by name: the cell in BENCHMARK.json, its configuration
in the file the cell's `config` entry names, its traffic mix in
traffic/<mix>.json, the mix's runner in runners/<runner>.py, the model's
builder in models/<model>.py, and each per-layer metric in
layer_metrics/<metric>.py. Nothing here branches on any of those names; a
later PR adds files and entries and edits nothing (README.md).

`--rehearse` (CPU rehearsals only) applies the `rehearse` overrides of the
configuration and traffic files (toy sizes) on as many virtual CPU devices
as the cell has chips; its result line says "platform": "cpu".
"""
import time
T_START = time.perf_counter()       # set-up is counted from here

import argparse                     # noqa: E402
import importlib.util               # noqa: E402
import json                         # noqa: E402
import os                           # noqa: E402
import sys                          # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE_DIR = os.path.join(HERE, ".jax_cache")     # fixed: part of the key
WORK_DIR = os.path.join(HERE, ".bench_scratch")  # artifacts, traces


def load_json(path):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def by_name(entries, name, what):
    found = [e for e in entries if e["name"] == name]
    if len(found) != 1:
        raise SystemExit(f"run.py: {what} {name!r} is not in BENCHMARK.json "
                         f"(has {[e['name'] for e in entries]})")
    return found[0]


def load_file_module(directory, name):
    """The module in benchmarks/<directory>/<name>.py (a metric's name may
    hold dots, so the file is loaded by path)."""
    path = os.path.join(HERE, directory, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"{directory}.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def with_rehearsal(doc, rehearse):
    """The file's `rehearse` overrides laid over it, for a CPU rehearsal."""
    doc = dict(doc)
    toy = doc.pop("rehearse", {})
    if rehearse:
        doc.update(toy)
    return doc


def peaks_for(kind, rehearse=False):
    """The row of peaks.json for a device kind. A kind that is not there is
    an error, not a default; only a rehearsal's CPU borrows the row that
    peaks.json names for it."""
    table = load_json(os.path.join(HERE, "peaks.json"))
    if isinstance(table.get(kind), dict):
        return table[kind]
    if rehearse:
        return table[table["rehearse_as"]]
    raise SystemExit(f"run.py: no peaks for device kind {kind!r} in "
                     "peaks.json; add its row with a source")


def metric_applies(metric, cell_name):
    return "workloads" not in metric or cell_name in metric["workloads"]


class Context:
    """What a per-layer metric's reader is given."""

    def __init__(self, env, result, trace, peaks):
        self.cell, self.config, self.traffic = \
            env.cell, env.config, env.traffic
        self.chips = env.chips
        self.host = result.get("host", {})
        self.end_to_end = result.get("end_to_end", {})
        self.memory_peak_bytes = result.get("memory_peak_bytes", 0)
        self.trace, self.peaks = trace, peaks


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--traffic-set", action="append", default=[],
                    metavar="KEY=JSON", help="by hand only: lay a value over "
                    "the traffic file (a knee sweep, another rate)")
    args = ap.parse_args(argv)

    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    if args.seconds is None:
        args.seconds = bench["run_seconds"]
    cell = by_name(bench["workloads"], args.workload, "workload")
    entry = by_name(bench["configs"], cell["config"], "configuration")
    config = with_rehearsal(load_json(os.path.join(ROOT, entry["file"])),
                            args.rehearse)
    traffic = with_rehearsal(load_json(os.path.join(
        HERE, "traffic", cell["traffic"] + ".json")), args.rehearse)
    for item in args.traffic_set:
        key, _, value = item.partition("=")
        traffic[key] = json.loads(value)

    # the cache lives in the checkout, at a fixed path, whatever the
    # machine's own variable says (config.enable_compile_cache would obey it)
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            f" --xla_force_host_platform_device_count={cell['chips']}")
    sys.path[:0] = [HERE, ROOT]

    import jax
    devices = jax.devices()
    if not args.rehearse and devices[0].platform != "tpu":
        raise SystemExit(f"run.py: needs a TPU, jax found {devices[0]}; "
                         "there is no CPU mode (--rehearse is a rehearsal)")
    if len(devices) < cell["chips"]:
        raise SystemExit(f"run.py: {cell['name']} needs {cell['chips']} "
                         f"chips, jax reports {len(devices)}")
    devices = devices[:cell["chips"]]
    kind = devices[0].device_kind
    peaks = peaks_for(kind, args.rehearse)

    from mxnet_tpu import config as mx_config
    import common
    cache_dir = mx_config.enable_compile_cache(CACHE_DIR)
    meter = common.CompileMeter()
    env = common.Env(cell, config, traffic, args, T_START, meter, devices,
                     os.path.join(WORK_DIR, cell["name"]), load_file_module)
    common.emit("run", workload=cell["name"], seed=args.seed,
                seconds=args.seconds, trace=args.trace,
                rehearse=args.rehearse, compile_cache_dir=cache_dir,
                jax=jax.__version__, device_kind=kind,
                devices=len(jax.devices()))

    result = load_file_module("runners", traffic["runner"]).run(env)
    for fault in result.get("faults", ()):
        common.emit("fault", what=fault)

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": result.get("memory_peak_bytes", 0)}
    out = {"correct": bool(result["correct"]),
           "attempted": int(result["attempted"]),
           "failed": int(result["failed"])}
    values = dict(result["end_to_end"], setup_s=result["setup_s"])
    if args.trace:
        from reduce import xplane
        trace = xplane.reduce_trace(result["profile_dir"]) \
            if result.get("profile_dir") else None
        ctx = Context(env, result, trace, peaks)
        wanted, values = bench["per_layer"], {}
        for metric in wanted:
            if metric_applies(metric, cell["name"]):
                value = load_file_module(
                    "layer_metrics", metric["name"]).compute(ctx)
                if value is not None:
                    values[metric["name"]] = value
        if trace:
            device["busy_s"] = trace.busy_s
            device["window_s"] = trace.window_s
            out["breakdown"] = trace.breakdown()
            common.emit("trace", **trace.summary())
    else:
        wanted = bench["end_to_end"]
    out["metrics"] = {
        m["name"]: {"value": float(values[m["name"]]), "unit": m["unit"]}
        for m in wanted
        if metric_applies(m, cell["name"]) and m["name"] in values}
    out["device"] = device
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
