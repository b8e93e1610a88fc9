"""Checkpoint selftest CLI — crash-injection proof of the commit protocol.

    python -m mxnet_tpu.checkpoint --selftest

Two layers, one JSON line, exit 0 iff everything holds:

  1. in-process protocol checks: atomic save/restore roundtrip,
     keep-last-N + best-k retention, corrupt-latest falls back to the
     previous committed step, counters exported;
  2. crash injection: fork a seeded MLP `Module.fit(checkpoint_dir=...)`
     victim, SIGKILL it at an exact instant of the step-15 commit
     (`MXNET_CHECKPOINT_INJECT_CRASH`), prove the newest COMMITTED
     checkpoint is still restorable, then `fit(..., resume=True)` and
     prove the final params are bit-identical (sha256) to an
     uninterrupted run on the same seed.

`--fused` runs the same matrix through the steps_per_dispatch>1 fused
path (DataParallelTrainer carries). `--victim` is the internal
subprocess entry point.

  3. `--elastic`: the topology-elasticity lane (ci.sh quick runs it at
     4->2). SIGKILL a victim mid-save at topology A (N simulated CPU
     devices via jax_num_cpu_devices), re-gather the newest committed
     state in a subprocess pinned to topology B and prove it sha256-
     identical to the uninterrupted baseline's checkpoint at the SAME
     step (the save->shard->reshard->restore cycle is bitwise
     lossless; training itself is not bitwise comparable across device
     counts — psum reduction order differs), then resume=True at B and
     prove the run completes and commits to the final step; finally
     delete one shard file and prove restore falls back a step.
     `--gather` is the internal re-gather subprocess entry point.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import subprocess
import sys
import tempfile

from mxnet_tpu.config import pin_cpu


def _mlp_sym():
    import mxnet_tpu as mx
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=16,
                                name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _params_sha256(mod):
    import numpy as np
    args, auxs = mod.get_params()
    h = hashlib.sha256()
    for d in (args, auxs):
        for name in sorted(d):
            h.update(name.encode("utf-8"))
            h.update(np.ascontiguousarray(d[name].asnumpy()).tobytes())
    return h.hexdigest()


# 5 batches/epoch x 6 epochs -> epoch-boundary commits at steps
# 5,10,15,20,25,30; the selftest injects its crash at the step-15 commit
_SAMPLES, _BATCH, _EPOCHS, _CRASH_STEP = 40, 8, 6, 15


def victim(args):
    """Subprocess entry point: seeded deterministic training run that
    commits a checkpoint at every epoch boundary and prints the sha256
    of the final params. `--ndev N` pins an N-device virtual CPU
    topology (the elastic lane's A/B sizes)."""
    ndev = max(1, int(getattr(args, "ndev", 0) or 1))
    pin_cpu(ndev)
    import numpy as np
    import mxnet_tpu as mx
    np.random.seed(0)
    mx.random.seed(0)
    rng = np.random.RandomState(42)
    X = rng.normal(size=(_SAMPLES, 8)).astype(np.float32)
    Y = rng.randint(0, 4, size=(_SAMPLES,)).astype(np.float32)
    it = mx.io.NDArrayIter(X, Y, batch_size=_BATCH, shuffle=False)
    ctx = [mx.cpu(i) for i in range(ndev)] if ndev > 1 else mx.cpu(0)
    mod = mx.mod.Module(_mlp_sym(), context=ctx)
    mod.fit(it, num_epoch=args.epochs, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
            initializer=mx.init.Xavier(rnd_type="gaussian"),
            eval_metric="acc",
            steps_per_dispatch=2 if args.fused else 1,
            checkpoint_dir=args.victim, resume=args.resume)
    print(json.dumps({"metric": "checkpoint_victim",
                      "sha256": _params_sha256(mod), "ok": True}),
          flush=True)
    return 0


def gather(args):
    """Subprocess entry point for the elastic lane: pin topology B,
    restore the newest (or exact) committed step, round-trip every
    array through a device_put onto THIS topology's mesh, and print the
    state's content hash — proving the saved shards reassemble and
    reshard losslessly at a device count the save never saw."""
    pin_cpu(max(1, int(args.ndev or 1)))
    import numpy as np
    import jax
    from mxnet_tpu.checkpoint import CheckpointManager, state_sha256
    from mxnet_tpu.parallel.mesh import data_parallel_mesh, put_replicated
    mgr = CheckpointManager(args.gather)
    st = mgr.restore(step=None if args.step < 0 else args.step)
    if st is None:
        print(json.dumps({"metric": "checkpoint_gather", "ok": False}),
              flush=True)
        return 1
    mesh = data_parallel_mesh()
    st.arrays = {k: np.asarray(put_replicated(v, mesh))
                 for k, v in st.arrays.items()}
    print(json.dumps({
        "metric": "checkpoint_gather", "ok": True, "step": st.step,
        "sha256": state_sha256(st), "devices": int(jax.device_count()),
        "saved_devices":
            (st.meta.get("topology") or {}).get("device_count")}),
        flush=True)
    return 0


def _run_victim(ckpt_dir, resume=False, fused=False, crash=None,
                ndev=None, extra_env=None):
    env = dict(os.environ)
    env.pop("MXNET_CHECKPOINT_INJECT_CRASH", None)
    if crash:
        env["MXNET_CHECKPOINT_INJECT_CRASH"] = crash
    if extra_env:
        env.update(extra_env)
    cmd = [sys.executable, "-m", "mxnet_tpu.checkpoint",
           "--victim", ckpt_dir, "--epochs", str(_EPOCHS)]
    if ndev:
        cmd += ["--ndev", str(ndev)]
    if resume:
        cmd.append("--resume")
    if fused:
        cmd.append("--fused")
    return subprocess.run(cmd, capture_output=True, text=True, env=env,
                          timeout=600)


def _run_gather(ckpt_dir, ndev, step=-1):
    env = dict(os.environ)
    env.pop("MXNET_CHECKPOINT_INJECT_CRASH", None)
    cmd = [sys.executable, "-m", "mxnet_tpu.checkpoint",
           "--gather", ckpt_dir, "--ndev", str(ndev), "--step", str(step)]
    return subprocess.run(cmd, capture_output=True, text=True, env=env,
                          timeout=600)


def _json_rec(proc, metric):
    for line in reversed(proc.stdout.strip().splitlines()):
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        if rec.get("metric") == metric:
            return rec
    return None


def _victim_sha(proc):
    rec = _json_rec(proc, "checkpoint_victim")
    return rec["sha256"] if rec else None


def _payload_file(step_dir):
    """Some shard's arrays payload inside a committed step dir — the
    file the corruption/missing-shard checks target."""
    for root, _, files in sorted(os.walk(step_dir)):
        for f in sorted(files):
            if f.startswith("arrays"):
                return os.path.join(root, f)
    raise FileNotFoundError(f"no arrays payload under {step_dir}")


def _protocol_checks(tmp, results):
    """Fast in-process checks of the manager itself (numpy payloads —
    no mesh/training needed)."""
    import numpy as np
    from mxnet_tpu.checkpoint import CheckpointManager, TrainingState

    mgr = CheckpointManager(os.path.join(tmp, "proto"), keep_last_n=2,
                            keep_best_k=1, async_save=True)
    for s, m in [(1, 0.1), (2, 0.5), (3, 0.3), (4, 0.2), (5, 0.4)]:
        mgr.save(TrainingState(
            arrays={"param:w": np.full((4,), s, np.float32)},
            meta={"epoch": s, "batch": 0, "step": s}), step=s, metric=m)
    mgr.wait()
    # last 2 by recency (4, 5) plus best 1 by metric (2, metric 0.5)
    results["retention_kept"] = mgr.steps()
    results["retention_ok"] = mgr.steps() == [2, 4, 5]
    st = mgr.restore()
    results["roundtrip_ok"] = bool(
        st is not None and st.step == 5
        and np.array_equal(st.arrays["param:w"],
                           np.full((4,), 5, np.float32)))
    # corrupt the newest payload (inside its shard dir): restore must
    # fall back to step 4
    with open(_payload_file(os.path.join(mgr.directory,
                                         mgr._step_dirname(5))),
              "r+b") as f:
        f.write(b"garbage")
    st = mgr.restore()
    results["corrupt_falls_back"] = bool(st is not None and st.step == 4)
    mgr.close()
    c = mgr.counters()
    results["counters_ok"] = bool(c["ckpt_commits"] == 5
                                  and c["ckpt_bytes"] > 0
                                  and c["ckpt_save_us"] > 0)
    return (results["retention_ok"] and results["roundtrip_ok"]
            and results["corrupt_falls_back"] and results["counters_ok"])


def selftest(points, fused=False):
    pin_cpu(1)
    results = {"metric": "checkpoint_selftest", "fused": bool(fused)}
    ok = True
    with tempfile.TemporaryDirectory(prefix="ckpt_selftest_") as tmp:
        ok &= _protocol_checks(tmp, results)

        base = _run_victim(os.path.join(tmp, "baseline"), fused=fused)
        base_sha = _victim_sha(base)
        results["baseline_ok"] = bool(base.returncode == 0 and base_sha)
        if not results["baseline_ok"]:
            results["baseline_stderr"] = base.stderr[-2000:]
            results["ok"] = False
            print(json.dumps(results), flush=True)
            return 1

        from mxnet_tpu.checkpoint import CheckpointManager
        for point in points:
            tag = point.replace("-", "_")
            d = os.path.join(tmp, tag)
            crashed = _run_victim(d, fused=fused,
                                  crash=f"{point}@{_CRASH_STEP}")
            killed = crashed.returncode in (-9, 137)
            results[f"{tag}_killed"] = bool(killed)
            mgr = CheckpointManager(d)
            latest = mgr.latest_step()
            # pre-rename/mid-arrays die before the step-15 commit lands:
            # newest committed is 10; post-rename dies after: 15
            want = _CRASH_STEP if point == "post-rename" \
                else _CRASH_STEP - 5
            results[f"{tag}_latest"] = latest
            restorable = mgr.restore() is not None
            results[f"{tag}_restorable"] = bool(restorable)
            resumed = _run_victim(d, resume=True, fused=fused)
            sha = _victim_sha(resumed)
            results[f"{tag}_resume_ok"] = bool(resumed.returncode == 0
                                               and sha)
            results[f"{tag}_bit_identical"] = bool(sha == base_sha)
            point_ok = (killed and latest == want and restorable
                        and sha == base_sha)
            if not point_ok and resumed.stderr:
                results[f"{tag}_stderr"] = resumed.stderr[-2000:]
            ok &= point_ok
    results["ok"] = bool(ok)
    print(json.dumps(results), flush=True)
    return 0 if ok else 1


def elastic_selftest(dev_a, dev_b, fused=False):
    """Topology-elasticity proof (4 subprocesses):

      1. baseline victim at topology A commits every epoch (retention
         off so early steps survive);
      2. crash victim at A is SIGKILLed mid-arrays at the step-15
         commit -> newest committed must be step 10;
      3. a gather subprocess pinned to topology B restores step 10,
         device-round-trips every array on B's mesh, and its content
         hash must equal the BASELINE's step-10 hash (bitwise-lossless
         save->shard->reshard->restore; training beyond this point is
         not bitwise comparable across device counts — psum reduction
         order differs);
      4. the crashed run resumes at B and must complete and commit the
         final step; then one shard file of the newest commit is
         deleted and restore must fall back one step.
    """
    pin_cpu(1)
    results = {"metric": "checkpoint_elastic_selftest",
               "fused": bool(fused), "devices_a": int(dev_a),
               "devices_b": int(dev_b)}
    ok = True
    keep0 = {"MXNET_CHECKPOINT_KEEP": "0"}
    pre_step = _CRASH_STEP - 5
    final_step = _EPOCHS * 5
    with tempfile.TemporaryDirectory(prefix="ckpt_elastic_") as tmp:
        base = _run_victim(os.path.join(tmp, "baseline"), fused=fused,
                           ndev=dev_a, extra_env=keep0)
        results["baseline_ok"] = bool(base.returncode == 0
                                      and _victim_sha(base))
        if not results["baseline_ok"]:
            results["baseline_stderr"] = base.stderr[-2000:]
            results["ok"] = False
            print(json.dumps(results), flush=True)
            return 1
        from mxnet_tpu.checkpoint import CheckpointManager, state_sha256
        base_pre = CheckpointManager(
            os.path.join(tmp, "baseline")).restore(step=pre_step)
        results["baseline_prestep_ok"] = base_pre is not None
        sha_pre = state_sha256(base_pre) if base_pre is not None else None
        ok &= base_pre is not None

        d = os.path.join(tmp, "crash")
        crashed = _run_victim(d, fused=fused, ndev=dev_a,
                              crash=f"mid-arrays@{_CRASH_STEP}",
                              extra_env=keep0)
        results["killed"] = bool(crashed.returncode in (-9, 137))
        mgr = CheckpointManager(d)
        results["latest_after_crash"] = mgr.latest_step()
        ok &= results["killed"] and mgr.latest_step() == pre_step

        g = _run_gather(d, ndev=dev_b, step=pre_step)
        grec = _json_rec(g, "checkpoint_gather") or {}
        results["gather_ok"] = bool(grec.get("ok"))
        results["gather_devices"] = grec.get("devices")
        results["gather_saved_devices"] = grec.get("saved_devices")
        results["gather_bit_identical"] = bool(
            sha_pre and grec.get("sha256") == sha_pre)
        gather_ok = (results["gather_ok"]
                     and grec.get("devices") == int(dev_b)
                     and results["gather_bit_identical"])
        if not gather_ok and g.stderr:
            results["gather_stderr"] = g.stderr[-2000:]
        ok &= gather_ok

        resumed = _run_victim(d, resume=True, fused=fused, ndev=dev_b,
                              extra_env=keep0)
        mgr = CheckpointManager(d)
        results["resume_rc"] = resumed.returncode
        results["resume_latest"] = mgr.latest_step()
        resume_ok = (resumed.returncode == 0
                     and _victim_sha(resumed) is not None
                     and mgr.latest_step() == final_step)
        results["resume_completed"] = bool(resume_ok)
        if not resume_ok and resumed.stderr:
            results["resume_stderr"] = resumed.stderr[-2000:]
        ok &= resume_ok

        # degradation: a deleted shard file must not fail the job — the
        # newest commit is skipped for the previous good step
        try:
            os.remove(_payload_file(
                os.path.join(d, mgr._step_dirname(mgr.latest_step()))))
            st = mgr.restore()
            results["missing_shard_falls_back"] = bool(
                st is not None and st.step == final_step - 5)
            results["fallback_counter"] = \
                mgr.counters().get("ckpt_fallback_total")
            ok &= results["missing_shard_falls_back"] and \
                results["fallback_counter"] >= 1
        except Exception as e:                   # pragma: no cover
            results["missing_shard_error"] = repr(e)
            ok = False
    results["ok"] = bool(ok)
    print(json.dumps(results), flush=True)
    return 0 if ok else 1


def main(argv=None):
    ap = argparse.ArgumentParser(prog="python -m mxnet_tpu.checkpoint")
    ap.add_argument("--selftest", action="store_true",
                    help="run protocol + crash-injection checks "
                         "(ci.sh quick)")
    ap.add_argument("--points", default="mid-arrays,post-rename",
                    help="comma-separated crash points for --selftest "
                         "(mid-arrays, pre-rename, post-rename)")
    ap.add_argument("--fused", action="store_true",
                    help="run the victim through the fused "
                         "steps_per_dispatch>1 path")
    ap.add_argument("--elastic", action="store_true",
                    help="with --selftest: run ONLY the topology-"
                         "elasticity lane (crash at --devices-a, "
                         "re-gather + resume at --devices-b)")
    ap.add_argument("--devices-a", type=int, default=4,
                    help="elastic lane: simulated device count at save "
                         "time (default 4)")
    ap.add_argument("--devices-b", type=int, default=2,
                    help="elastic lane: simulated device count at "
                         "restore time (default 2)")
    ap.add_argument("--victim", metavar="DIR",
                    help="(internal) run the training victim with "
                         "checkpoint_dir=DIR")
    ap.add_argument("--gather", metavar="DIR",
                    help="(internal) restore DIR at --ndev devices and "
                         "print the state content hash")
    ap.add_argument("--ndev", type=int, default=0,
                    help="(internal) pin this many virtual CPU devices")
    ap.add_argument("--step", type=int, default=-1,
                    help="(internal) exact step for --gather")
    ap.add_argument("--epochs", type=int, default=_EPOCHS)
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args(argv)
    if args.gather:
        return gather(args)
    if args.victim:
        return victim(args)
    if not args.selftest:
        ap.print_help()
        return 2
    if args.elastic:
        return elastic_selftest(args.devices_a, args.devices_b,
                                fused=args.fused)
    return selftest([p.strip() for p in args.points.split(",")
                     if p.strip()], fused=args.fused)


if __name__ == "__main__":
    sys.exit(main())
