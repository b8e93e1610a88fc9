"""Model-parallel path microbench: per-stage jitted segments vs the
earlier eager per-op walk.

Both paths execute the SAME 4-stage group2ctx MLP training step (fwd +
bwd + BN aux) over 4 CPU devices. The eager baseline reconstructs the
r4 execution model exactly: un-jitted _build_runner walk (one python/jax
dispatch per op) + a fresh jax.vjp retrace every step. The segmented
path is what Executor now does: one cached jitted fwd fn + one cached
jitted bwd fn per stage, explicit device_put at stage boundaries.

Run: python tools/mp_bench.py [--stages 4] [--hidden 256] [--steps 30]
"""
from __future__ import annotations

import argparse
import os
import sys
import time

os.environ.setdefault("JAX_NUM_CPU_DEVICES", "4")
import jax  # noqa: E402

jax.config.update("jax_num_cpu_devices",
                  int(os.environ["JAX_NUM_CPU_DEVICES"]))
jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np  # noqa: E402

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu.executor import _SegmentedRunner  # noqa: E402


def staged_sym(stages, hidden):
    x = mx.sym.Variable("data")
    for s in range(stages):
        with mx.AttrScope(ctx_group=f"stage{s}"):
            x = mx.sym.FullyConnected(x, num_hidden=hidden, name=f"fc{s}")
            x = mx.sym.BatchNorm(x, name=f"bn{s}")
            x = mx.sym.Activation(x, act_type="relu")
    with mx.AttrScope(ctx_group=f"stage{stages - 1}"):
        x = mx.sym.FullyConnected(x, num_hidden=3, name="head")
    return mx.sym.SoftmaxOutput(x, name="softmax")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--stages", type=int, default=4)
    ap.add_argument("--hidden", type=int, default=256)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--steps", type=int, default=30)
    a = ap.parse_args()

    sym = staged_sym(a.stages, a.hidden)
    devs = jax.local_devices(backend="cpu")
    g2d = {f"stage{s}": devs[s % len(devs)] for s in range(a.stages)}
    arg_names = sym.list_arguments()
    aux_names = sym.list_auxiliary_states()
    shapes = dict(zip(arg_names, sym.infer_shape(
        data=(a.batch, 32), softmax_label=(a.batch,))[0]))
    aux_shapes = dict(zip(aux_names, sym.infer_shape(
        data=(a.batch, 32), softmax_label=(a.batch,))[2]))
    rng = np.random.RandomState(0)
    args = tuple(jax.device_put(
        rng.normal(0, 0.1, shapes[n]).astype(np.float32), devs[0])
        for n in arg_names)
    aux = tuple(jax.device_put(np.zeros(aux_shapes[n], np.float32)
                               if "mean" in n else
                               np.ones(aux_shapes[n], np.float32),
                               devs[0]) for n in aux_names)
    key = jax.device_put(jax.random.PRNGKey(0), devs[0])
    diff_pos = [i for i, n in enumerate(arg_names)
                if n not in ("data", "softmax_label")]

    # -- r4 eager baseline: per-op walk + per-step vjp retrace ------------
    # (reconstructed from the r4 Executor's group2ctx path, with per-op
    # input placement added so weights parked on dev0 reach later stages
    # — the r4 walk only moved OUTPUTS, so a >2-stage chain would mix
    # devices; the fix doesn't change what's being measured: one python
    # dispatch per op per step plus a fresh vjp trace per step)
    from mxnet_tpu.ops.registry import OpCtx
    from mxnet_tpu.executor import _node_group_dev
    topo = sym._topo()
    args_nodes, aux_nodes = sym._input_vars()
    arg_of = {id(n): i for i, n in enumerate(args_nodes)}
    aux_of = {id(n): i for i, n in enumerate(aux_nodes)}
    node_pos = {id(n): i for i, n in enumerate(topo)}
    out_entries = [(node_pos[id(n)], i) for (n, i) in sym._outputs]

    def eager_run(arg_values, aux_values, rng_key):
        vals = [None] * len(topo)
        for pos, node in enumerate(topo):
            if node.op is None:
                v = aux_values[aux_of[id(node)]] if id(node) in aux_of \
                    else arg_values[arg_of[id(node)]]
                vals[pos] = (v,)
                continue
            dev = _node_group_dev(node, g2d) or devs[0]
            parsed = node.op.parse_attrs(node.attrs)
            ins = [jax.device_put(vals[node_pos[id(n2)]][i2], dev)
                   for (n2, i2) in node.inputs]
            res = node.op.fcompute(
                parsed, OpCtx(is_train=True, platform="cpu"), *ins)
            if not isinstance(res, tuple):
                res = (res,)
            vals[pos] = tuple(jax.device_put(r, dev) for r in res)
        return tuple(vals[p][i] for (p, i) in out_entries)

    def eager_step():
        def loss_fn(diff_vals):
            full = list(args)
            for p, v in zip(diff_pos, diff_vals):
                full[p] = v
            return eager_run(tuple(full), aux, key)
        diff_vals = tuple(args[p] for p in diff_pos)
        outputs, vjp_fn = jax.vjp(loss_fn, diff_vals)
        (grads,) = vjp_fn(tuple(jax.numpy.ones_like(o) for o in outputs))
        return outputs, grads

    def timed(fn, steps):
        out = fn()                      # warm
        jax.block_until_ready(out[0])
        t0 = time.perf_counter()
        for _ in range(steps):
            out = fn()
        jax.block_until_ready(out[0])
        return (time.perf_counter() - t0) / steps

    eager_s = timed(eager_step, a.steps)

    # -- segmented path ---------------------------------------------------
    seg = _SegmentedRunner(sym, True, g2d, devs[0], diff_arg_pos=diff_pos)

    def seg_step():
        outputs, new_aux, arg_grads = seg.forward_backward(args, aux, key)
        return outputs, arg_grads

    seg_s = timed(seg_step, a.steps)

    print(f"stages={a.stages} hidden={a.hidden} batch={a.batch} "
          f"steps={a.steps}")
    print(f"eager per-op walk + per-step vjp : {eager_s * 1e3:8.2f} ms/step")
    print(f"per-stage jitted segments        : {seg_s * 1e3:8.2f} ms/step")
    print(f"speedup: {eager_s / seg_s:.1f}x  (stages traced: "
          f"{seg.trace_counts})")
    return eager_s / seg_s


if __name__ == "__main__":
    main()
