"""Runner `train_fit_tokens_dense`: `Module.fit(steps_per_dispatch=K)` of a
language model without a mixture over token ids, for --seconds.

Everything is `train_fit_tokens`'s (loaded by name: its pool, its
`FirstDispatch`, its reference's Adam, checks (a) and (u), the window, the
memory and set-up lines, the checks for no compile in the window and no
dense attention fallback) but what assumes a mixture: there is no stats
output and no routing. The symbol's second output is the model's exit
distribution p (batch, sequence, passes) where the model has one; check
(b) runs on the pool's first sequence (no discrete decision lies near a
tie) and compares the last pass's logits, every p_t and the gradients of
the traffic file's `checked_parameters` with the reference's, amp off and
at `highest` precision.

The reference module of the configuration's model gives, besides what
`train_fit_tokens` reads, `forward(cfg, params, tokens, labels, q_block)`
-> (last logits, per-position losses, p).

Check (u)'s leaf limit and check (b)'s gradient limit take each of the
traffic file's `leaves_joined` groups as one leaf (a layer's weight and its
bias of one element); (u)'s limit over all parameters sees every element as
it is.

By hand only, the controls each limit has to refuse (`--traffic-set`):
`reference_rounding='"float8_e4m3fn"'` rounds the reference's weights (a),
(u); `fp32_check_precision='"default"'` runs the system's side of (b) at
the chip's default matrix precision.

A sample of `train_rate` is one sequence.
"""
import gc
import os

import numpy as np

from common import check, emit, memory_stats, peak_memory_bytes, rel_err


class MemoryLog:
    """After each dispatch: the device's memory peak so far (at the end of
    the window it is the training loop's own)."""

    def __init__(self, devices):
        self._devices = devices
        self.rows = []

    def __call__(self, param):
        self.rows.append(peak_memory_bytes(self._devices))


def reference_class(tokens_runner):
    class Reference(tokens_runner.Reference):
        """`train_fit_tokens`' reference, whose forward program gives the
        per-position losses of the model's own objective and p."""

        def __init__(self, env, model, ref_params):
            super().__init__(env, model, ref_params)
            import jax
            ref, cfg = self.ref, self.cfg
            q_block = int(env.traffic["reference_q_block"])
            self._forward = jax.jit(lambda p, tokens, labels: ref.forward(
                cfg, p, tokens, labels, q_block=q_block))

        def forward(self, batch):
            """(per-position losses (B, S) of `batch`; the logits and p of
            its first sequence)."""
            losses, first = [], None
            for tokens, labels in self.sequences(batch):
                lg, ls, p = self._forward(self.params, tokens, labels)
                losses.append(np.asarray(ls)[0])
                if first is None:
                    first = (np.asarray(lg), np.asarray(p))
                del lg
            return np.stack(losses), first[0], first[1]
    return Reference


def joined_leaves(groups, prefix, *trees):
    """Each tree with every group of leaves (names without the model's
    prefix) flattened into one leaf under the group's first name. The
    elements are the same, so (u)'s error over all parameters does not
    move; a layer's parameters are compared together, a bias of one
    element with its weight: its gradient is one sum over every position,
    and where that sum lies near zero the relative error of the one
    element (and of Adam's first step, about the sign of it) grows without
    bound, whatever the program. A joined leaf's relative error lies
    between its parts' least and greatest."""
    out = [dict(tree) for tree in trees]
    for group in groups:
        names = [prefix + n for n in group]
        for tree in out:
            tree[names[0]] = np.concatenate(
                [np.ravel(np.asarray(tree.pop(n), np.float64))
                 for n in names])
    return out


def check_fp32(env, model, sys_params, tokens, labels, want_logits, want_p,
               want_grads, faults):
    """(b): the last pass's logits, p and the named gradients, amp off,
    one sequence."""
    import jax
    import mxnet_tpu as mx
    names = list(env.traffic["checked_parameters"])
    ctx = mx.tpu(0)
    mx.amp.disable()
    with jax.default_matmul_precision(env.traffic["fp32_check_precision"]):
        args = {k: mx.nd.array(v, ctx=ctx) for k, v in sys_params.items()}
        args[model.DATA] = mx.nd.array(tokens, ctx=ctx, dtype="int32")
        sym = model.build(env.config, softmax=False)
        exe = sym.bind(ctx, {k: args[k] for k in sym.list_arguments()},
                       grad_req="null")
        logits = exe.forward(is_train=False)[0].asnumpy()
        del exe
        args[model.LABEL] = mx.nd.array(labels, ctx=ctx, dtype="int32")
        wanted = {model.PREFIX + n for n in names}
        grads = {k: mx.nd.zeros(args[k].shape, ctx=ctx) for k in wanted}
        exe = model.build(env.config).bind(
            ctx, args, args_grad=grads,
            grad_req={k: "write" if k in wanted else "null" for k in args})
        out = exe.forward(is_train=True)
        p = out[1].asnumpy()
        exe.backward([mx.nd.ones(out[0].shape, ctx=ctx) / out[0].size,
                      mx.nd.zeros(out[1].shape, ctx=ctx)])
        got = {k: g.asnumpy() for k, g in grads.items()}
        del exe, args, grads, out
        gc.collect()

    got, want = joined_leaves(
        env.traffic.get("leaves_joined", ()), model.PREFIX, got,
        model.reference().system_params(want_grads, model.PREFIX))
    errs = {"logits": rel_err(logits, want_logits),
            "exit_probs": rel_err(p, want_p)}
    errs.update({k[len(model.PREFIX):]: rel_err(got[k], want[k])
                 for k in sorted(want)})
    tol_l = float(env.traffic["fp32_logits_tolerance"])
    tol_g = float(env.traffic["fp32_gradient_tolerance"])
    for name, err in errs.items():
        tol = tol_l if name in ("logits", "exit_probs") else tol_g
        check(err == err and err <= tol,
              f"float32 {name}, system against reference: {err} > {tol}",
              faults)
    return errs


def run(env):
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu.ops import attention
    base = env.load("runners", "train_fit")
    tokens_runner = env.load("runners", "train_fit_tokens")
    cfg, tr = env.config, env.traffic
    model = env.load("models", cfg["model"])
    k = int(tr["steps_per_dispatch"])
    batch = int(cfg["per_chip_batch"]) * env.chips
    seq, vocab = int(cfg["sequence_length"]), int(cfg["vocab_size"])
    t_import = env.since_start()

    np.random.seed(env.seed % (2**31 - 1))
    mx.random.seed(env.seed % (2**31 - 1))
    if cfg.get("rematerialise"):
        os.environ["MXNET_BACKWARD_DO_MIRROR"] = "1"
    mx.amp.init(cfg["compute_dtype"])
    ref_params, sys_params = model.seeded_params(cfg, env.seed)
    sym = model.build(cfg)
    loss_name = sym.list_outputs()[0]
    contexts = [mx.tpu(i) for i in range(env.chips)]
    mod = mx.mod.Module(sym, data_names=[model.DATA],
                        label_names=[model.LABEL],
                        context=contexts if env.chips > 1 else contexts[0])
    pool = tokens_runner.make_pool(env, batch, seq, vocab,
                                   int(tr["pool_batches"]))
    it = base.PoolIter(
        pool, [mx.io.DataDesc(model.DATA, (batch, seq), dtype="int32")],
        [mx.io.DataDesc(model.LABEL, (batch, seq), dtype="int32")],
        batch, k, env.annotate)
    watch = base.Watch(env, it)
    first, memory = tokens_runner.FirstDispatch(), MemoryLog(env.devices)
    from mxnet_tpu.telemetry import registry

    def fallbacks():
        metric = registry.get_registry().get(
            attention.DENSE_FALLBACK_COUNTER)
        return metric.value() if metric is not None else 0.0
    fallbacks_before = fallbacks()

    def let_go(param):
        # Watch keeps the loop's locals, and with them the trainer's state
        # past the fit's end, where the module's arrays return to the device
        watch.locals = {n: v for n, v in param.locals.items()
                        if n == "trainer"}
    t_build = env.since_start()

    opt = dict(cfg["optimizer"])
    opt["rescale_grad"] = 1.0 / (batch * seq)     # the MEAN position loss
    try:
        mod.fit(it, num_epoch=1, optimizer=opt.pop("name"),
                optimizer_params=opt, initializer=model.initializer(),
                arg_params={n: mx.nd.array(v, ctx=mx.cpu(0))
                            for n, v in sys_params.items()},
                eval_metric=mx.metric.Loss(output_names=[loss_name]),
                batch_end_callback=[first, memory, watch, let_go],
                steps_per_dispatch=k)
    finally:
        if watch.profile_dir:
            jax.profiler.stop_trace()

    faults = []
    i0, i1 = watch.i0, watch.i1
    if not check(i0 is not None and i1 is not None and i1 > i0,
                 f"the window never closed: {len(watch.t)} dispatches",
                 faults):
        return {"correct": False, "attempted": len(watch.t), "failed": 0,
                "faults": faults, "setup_s": watch.setup_s or 0.0,
                "end_to_end": {}, "host": {}}
    window_s = watch.t[i1] - watch.t[i0]
    n_disp = i1 - i0
    rate = n_disp * k * batch / window_s / env.chips
    gaps_ms = [(b - a) * 1e3
               for a, b in zip(watch.t[i0:i1], watch.t[i0 + 1:i1 + 1])]
    compiles_in_window = watch.compiles[i1] - watch.compiles[i0]
    feed_wait_s = (watch.feed_wait_us[i1] - watch.feed_wait_us[i0]) / 1e6
    dense_attention = fallbacks() - fallbacks_before

    span = int(tr["loss_span_dispatches"])
    check("trainer" in watch.locals, "fit fell back to the per-batch loop",
          faults)
    bad = int(np.sum(~np.isfinite(watch.ce)))
    check(bad == 0, f"{bad} dispatches with a non-finite loss", faults)
    first_loss, last_loss = (np.mean(watch.ce[:span]),
                             np.mean(watch.ce[-span:]))
    check(last_loss < first_loss, f"loss did not fall: first {span} "
          f"dispatches {first_loss:.4f}, last {span} {last_loss:.4f}", faults)
    check(compiles_in_window == 0,
          f"{compiles_in_window} compiles inside the window", faults)
    check(dense_attention == 0, f"{dense_attention} attention calls traced "
          "onto the dense (S, S) path", faults)
    memory_peak = memory.rows[i1]
    emit("memory", stats=memory_stats(env.devices),
         peak_at_window_end=memory_peak)

    emit("setup", import_s=t_import, build_s=t_build - t_import,
         compile_and_warmup_s=watch.setup_s - t_build,
         compile_seconds=watch.setup_meter["seconds"],
         compile_requests=watch.setup_meter["requests"],
         compile_cache_hits=watch.setup_meter["cache_hits"])
    emit("train", dispatches=len(watch.t), window_dispatches=n_disp,
         window_s=window_s, train_rate=rate, steps_per_dispatch=k,
         global_batch=batch, sequence_length=seq,
         loss_first=float(first_loss), loss_last=float(last_loss),
         dispatch_ms=[round(g, 1) for g in gaps_ms],
         compiles_in_window=compiles_in_window,
         dense_attention_calls=dense_attention,
         trace_window_dispatches=(watch.traced if watch.i_trace_end else 0))

    # free the trainer's state before the reference takes the chip, and
    # the step's traces and programs before it takes the host
    watch.locals = None
    del mod
    jax.clear_caches()
    gc.collect()
    t = env.since_start()
    reference = reference_class(tokens_runner)(env, model, ref_params)
    want_losses, want_logits, want_p = reference.forward(pool[0])
    t = tokens_runner.emit_check("first_step", t, env,
                                 tokens_runner.check_first_step(
                                     env, want_losses, first.losses, faults))
    tokens = pool[0].data[0].asnumpy()[:1]
    labels = pool[0].label[0].asnumpy()[:1]
    want_grads = reference.gradients(tokens, labels,
                                     list(tr["checked_parameters"]))
    change = reference.adam(pool[:k], cfg["optimizer"])
    reference.free()
    t = tokens_runner.emit_check("update", t, env, tokens_runner.check_update(
        env, *joined_leaves(tr.get("leaves_joined", ()),
                            model.PREFIX, sys_params, first.params, change),
        faults))
    del change
    first.params = None
    tokens_runner.emit_check("fp32", t, env, dict(
        check_fp32(env, model, sys_params, tokens, labels, want_logits,
                   want_p, want_grads, faults),
        fp32_sequence=[0, 0],
        exit_probs_mean_reference=[float(x) for x in
                                   want_p.mean(axis=(0, 1))]))
    return {
        "correct": not faults, "faults": faults,
        "attempted": n_disp, "failed": bad,
        "setup_s": watch.setup_s,
        "memory_peak_bytes": memory_peak,
        "end_to_end": {"train_rate": rate},
        "host": {"dispatch_gaps_ms": gaps_ms, "window_s": window_s,
                 "compiles_in_window": compiles_in_window,
                 "feed_wait_s": feed_wait_s,
                 "setup_compile_s": watch.setup_meter["seconds"],
                 "steps_per_dispatch": k, "sequences_per_step": batch},
        "profile_dir": watch.profile_dir if watch.i_trace_end else None,
    }
