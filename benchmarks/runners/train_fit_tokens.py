"""Runner `train_fit_tokens`: `Module.fit(steps_per_dispatch=K)` of a
language model over token ids, for --seconds.

The loop, the clock and the windows are `train_fit`'s (its `PoolIter` and
`Watch`): one `fit` call over a pool of host batches made from the seed,
everything timed from the `batch_end_callback`. What differs is the data
(token ids, labels the next token), the head (the symbol's first output is
the per-token loss, its second the mixture layers' load counts), and what
decides `correct`: the plain float32 reference of the configuration's
model file, computed on this chip at `highest` precision after the window.

  (a) the per-token losses the FIRST dispatch of the timed path returned
      for its first step (the seeded weights, the pool's first batch, the
      configuration's compute dtype) against the reference's, as the
      relative error of the two vectors after each has its mean taken off;
  (u) the change of every parameter over that first dispatch (K steps of
      the timed path's own backward and optimizer, read from the trainer's
      state at the dispatch's callback) against the change the reference's
      plain Adam makes over the same K batches: the norm of the difference
      over the norm of the reference's change, over all parameters and for
      the worst of them. A state left unchanged reads 1;
  (b) with amp off and at `highest` precision, the system's logits and the
      gradients of the traffic file's `checked_parameters` against the
      reference's, on that sequence of the pool's first
      `fp32_candidate_batches` whose routing decisions are the least close
      (the reference's `route_margin`: top-k is a step, and two float32
      programs agree on a choice only where the scores differ by more than
      their rounding);
  (c) finite losses, a loss that falls over the run, no compile in the
      window, no token-expert pair dropped, no attention call on the dense
      (S, S) path.

The reference runs one forward and one gradient program, each over one
sequence at a time; its Adam runs on the host.

By hand only, the controls each limit has to refuse (`--traffic-set`):
`reference_rounding='"float8_e4m3fn"'` rounds the reference's weights (a);
`fp32_check_precision='"default"'` runs the system's side of (b) at the
chip's default matrix precision.

A sample of `train_rate` is one sequence.
"""
import gc
import os
import resource

import numpy as np

from common import check, emit, memory_stats, peak_memory_bytes, rel_err


class FirstDispatch:
    """Keeps, of the first dispatch: the losses it returned for its first
    step, and the parameters its K steps left (host copies)."""

    def __init__(self):
        self.losses = self.params = None

    def __call__(self, param):
        if self.losses is None:
            loc = param.locals
            self.losses = np.asarray(loc["outputs"][0])[0]
            self.params = loc["trainer"].host_params(loc["params"])


class CounterLog:
    """After each dispatch: the registry's counters of the new mechanisms,
    the pairs that went through the grouped products (from the step's own
    statistics: a layer-step on the dense path multiplies none), and the
    device's memory peak."""

    NAMES = ("moe_tokens_routed_total", "moe_tokens_dropped_total",
             "moe_dense_fallback_total", "moe_expert_load_max",
             "moe_expert_load_mean")

    def __init__(self, devices, stats_output=1):
        from mxnet_tpu.telemetry import registry
        self._get = registry.get_registry().get
        self._devices, self._output = devices, stats_output
        self.rows = []

    def read(self, name):
        metric = self._get(name)
        return metric.value() if metric is not None else 0.0

    def __call__(self, param):
        row = {n: self.read(n) for n in self.NAMES}
        stats = np.asarray(param.locals["outputs"][self._output], np.int64)
        row["grouped_pairs"] = int(np.sum(stats[..., -3] *
                                          (1 - stats[..., -1])))
        row["layer_steps"] = int(stats[..., -1].size)
        # the peak so far: at the end of the window it is the training
        # loop's own (at the fit's end the module's arrays come back to
        # the device beside the trainer's state: not what a step needs)
        row["memory_peak_bytes"] = peak_memory_bytes(self._devices)
        self.rows.append(row)


def make_pool(env, batch, seq, vocab, n_batches):
    """`n_batches` DataBatches of (batch, seq) int32 ids: ranks drawn
    Zipf(exponent) over a seeded permutation of the vocabulary slice; the
    label of a position is the next id of the same draw."""
    import mxnet_tpu as mx
    rng = env.rng(1)
    p = 1.0 / np.arange(1, vocab + 1) ** float(env.traffic["zipf_exponent"])
    ids_of_rank = rng.permutation(vocab)
    pool = []
    for _ in range(n_batches):
        ids = ids_of_rank[rng.choice(vocab, size=(batch, seq + 1),
                                     p=p / p.sum())].astype(np.int32)
        pool.append(mx.io.DataBatch(
            data=[mx.nd.array(ids[:, :-1], ctx=mx.cpu(0), dtype="int32")],
            label=[mx.nd.array(ids[:, 1:], ctx=mx.cpu(0), dtype="int32")],
            pad=0))
    return pool


def emit_check(name, t0, env, values):
    """One `against_reference` line a check, as it ends (a run that dies in
    a later one has said what it knew), with the seconds it took and the
    process's peak of resident memory."""
    emit("against_reference", check=name, seconds=env.since_start() - t0,
         host_peak_rss_gb=resource.getrusage(
             resource.RUSAGE_SELF).ru_maxrss / 2**20, **values)
    return env.since_start()


def centred_rel_err(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return rel_err(a - a.mean(), b - b.mean())


class Reference:
    """The plain reference on this chip, at `highest` precision: ONE forward
    program (logits, per-token losses, each mixture layer's routing margin)
    and ONE gradient program (every parameter, each layer rematerialised),
    both over one sequence; the optimizer's arithmetic runs on the host."""

    def __init__(self, env, model, ref_params):
        import jax
        import jax.numpy as jnp
        self.ref = ref = model.reference()
        self.cfg = cfg = model.model_config(env.config)
        self.prefix = model.PREFIX
        q_block = int(env.traffic["reference_q_block"])
        self.host, self.chip = jax.devices("cpu")[0], env.devices[0]
        rounding = env.traffic.get("reference_rounding")
        self.params = {
            k: jnp.asarray(v) if not rounding else
            jnp.asarray(v).astype(rounding).astype(jnp.float32)
            for k, v in ref_params.items()}
        # where Adam starts, on the host: the seeded arrays themselves
        self.start = ref_params if not rounding else \
            {k: np.asarray(v) for k, v in self.params.items()}

        def forward(p, tokens, labels):
            margins = []
            lg = ref.logits(cfg, p, tokens, q_block=q_block, margins=margins)
            return lg, ref.losses_of_logits(lg, labels), jnp.stack(margins)
        self._forward = jax.jit(forward)
        self._grads = jax.jit(lambda p, tokens, labels: ref.loss_and_grads(
            cfg, p, tokens, labels, q_block=q_block, remat=True)[1])

    @staticmethod
    def sequences(batch):
        import jax.numpy as jnp
        tokens = jnp.asarray(batch.data[0].asnumpy(), jnp.int32)
        labels = jnp.asarray(batch.label[0].asnumpy(), jnp.int32)
        return [(tokens[i:i + 1], labels[i:i + 1])
                for i in range(tokens.shape[0])]

    def forward(self, batches):
        """(per-token losses (B, S) of the first batch; the smallest routing
        margin of each sequence of `batches`; the (batch, row) of the
        sequence whose smallest is largest, and its logits)."""
        losses, margins, best = [], [], None
        for b, batch in enumerate(batches):
            for i, (tokens, labels) in enumerate(self.sequences(batch)):
                lg, ls, mg = self._forward(self.params, tokens, labels)
                if b == 0:
                    losses.append(np.asarray(ls)[0])
                margins.append(float(np.min(np.asarray(mg))))
                if best is None or margins[-1] > best[0]:
                    best = (margins[-1], (b, i), np.asarray(lg))
                del lg
        return np.stack(losses), margins, best[1], best[2]

    def gradients(self, tokens, labels, names):
        """The reference's gradients named in `names`, on one sequence."""
        grads = self._grads(self.params, tokens, labels)
        return {n: np.asarray(grads[n]) for n in names}

    def adam(self, batches, optimizer):
        """The parameters' change over plain Adam on `batches`, a step
        each, under the system's names. The reference's weights leave the
        chip for it (the gradient program needs 13.4 GiB of the 15.75
        beside nothing but one copy of them: compile, PR 26)."""
        import jax
        adam = {"lr": optimizer["learning_rate"],
                "beta1": optimizer["beta1"], "beta2": optimizer["beta2"],
                "eps": optimizer["epsilon"]}
        theta = dict(self.start)
        on_chip, self.params = self.params, None
        m, v = {}, {}
        for t, batch in enumerate(batches, start=1):
            if on_chip is None:
                on_chip = jax.device_put(theta, self.chip)
            total = {}
            seqs = self.sequences(batch)
            for tokens, labels in seqs:
                # to the host a leaf at a time, all of it before the next
                # call: the gradients' 2.2 GiB on the chip are that call's
                # margin, and the host holds them once
                g = self._grads(on_chip, tokens, labels)
                for name in list(g):
                    leaf = jax.block_until_ready(
                        jax.device_put(g.pop(name), self.host))
                    total[name] = total[name] + leaf if name in total \
                        else leaf
            on_chip = g = None
            # a leaf at a time: the machine's 40 GiB hold five copies of
            # the parameters already
            for name in list(total):
                one, (m1, v1) = self.ref.adam_update(
                    {name: theta[name]}, {name: total.pop(name) / len(seqs)},
                    ({name: m[name]}, {name: v[name]}) if name in m else None,
                    t, **adam)
                theta[name], m[name], v[name] = \
                    one[name], m1[name], v1[name]
        return self.ref.system_params(
            {k: np.asarray(theta.pop(k)) - self.start[k]
             for k in list(theta)}, self.prefix)

    def free(self):
        self.params = self.start = self._forward = self._grads = None
        gc.collect()


def check_first_step(env, want, got, faults):
    """(a): the timed path's own first losses against the reference's."""
    err = centred_rel_err(got, want)
    tol = float(env.traffic["first_step_loss_tolerance"])
    check(err == err and err <= tol,
          f"first step's per-token losses, {env.config['compute_dtype']} "
          f"program against the float32 reference: {err} > {tol}", faults)
    return {"first_step_loss_rel_err": err,
            "first_step_loss_mean": float(np.mean(got)),
            "first_step_loss_mean_reference": float(np.mean(want)),
            "first_step_loss_spread_reference": float(np.std(want))}


def check_update(env, before, got, want, faults):
    """(u): the parameters' change over the first dispatch (`got` less
    `before`), the timed path against the reference's Adam (`want`)."""
    num = den = 0.0
    leaves = {}
    for name in sorted(want):
        d_want = np.asarray(want[name], np.float64)
        d_got = np.asarray(got[name], np.float64) - before[name]
        leaves[name] = rel_err(d_got, d_want)
        num += float(np.sum((d_got - d_want) ** 2))
        den += float(np.sum(d_want ** 2))
    err = (num / max(den, 1e-300)) ** 0.5
    worst = max(leaves, key=leaves.get)
    tol = float(env.traffic["update_tolerance"])
    tol_leaf = float(env.traffic["update_leaf_tolerance"])
    check(err == err and err <= tol,
          f"change of the parameters over the first dispatch, timed path "
          f"against the reference's Adam: {err} > {tol}", faults)
    check(leaves[worst] == leaves[worst] and leaves[worst] <= tol_leaf,
          f"change of {worst} over the first dispatch: {leaves[worst]} > "
          f"{tol_leaf}", faults)
    return {"update_rel_err": err, "update_rel_err_worst": leaves[worst],
            "update_worst_parameter": worst,
            "update_rel_err_by_parameter": {
                k: round(v, 4) for k, v in leaves.items()}}


def check_fp32(env, model, sys_params, tokens, labels, want_logits,
               want_grads, faults):
    """(b): logits and named gradients, amp off, one sequence."""
    import jax
    import mxnet_tpu as mx
    names = list(env.traffic["checked_parameters"])
    ctx = mx.tpu(0)
    mx.amp.disable()
    with jax.default_matmul_precision(env.traffic["fp32_check_precision"]):
        args = {k: mx.nd.array(v, ctx=ctx) for k, v in sys_params.items()}
        args[model.DATA] = mx.nd.array(tokens, ctx=ctx, dtype="int32")
        exe = model.build(env.config, softmax=False).bind(
            ctx, dict(args), grad_req="null")
        logits = exe.forward(is_train=False)[0].asnumpy()
        del exe
        args[model.LABEL] = mx.nd.array(labels, ctx=ctx, dtype="int32")
        wanted = {model.PREFIX + n for n in names}
        grads = {k: mx.nd.zeros(args[k].shape, ctx=ctx) for k in wanted}
        exe = model.build(env.config).bind(
            ctx, args, args_grad=grads,
            grad_req={k: "write" if k in wanted else "null" for k in args})
        out = exe.forward(is_train=True)
        exe.backward([mx.nd.ones(out[0].shape, ctx=ctx) / out[0].size,
                      mx.nd.zeros(out[1].shape, ctx=ctx, dtype="int32")])
        got = {k: g.asnumpy() for k, g in grads.items()}
        del exe, args, grads, out
        gc.collect()

    want = model.reference().system_params(want_grads, model.PREFIX)
    errs = {"logits": rel_err(logits, want_logits)}
    errs.update({k[len(model.PREFIX):]: rel_err(got[k], want[k])
                 for k in sorted(want)})
    tol_l = float(env.traffic["fp32_logits_tolerance"])
    tol_g = float(env.traffic["fp32_gradient_tolerance"])
    for name, err in errs.items():
        tol = tol_l if name == "logits" else tol_g
        check(err == err and err <= tol,
              f"float32 {name}, system against reference: {err} > {tol}",
              faults)
    return errs


def run(env):
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu.ops import attention
    base = env.load("runners", "train_fit")
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
    pool = make_pool(env, batch, seq, vocab, int(tr["pool_batches"]))
    it = base.PoolIter(
        pool, [mx.io.DataDesc(model.DATA, (batch, seq), dtype="int32")],
        [mx.io.DataDesc(model.LABEL, (batch, seq), dtype="int32")],
        batch, k, env.annotate)
    watch = base.Watch(env, it)
    first, counters = FirstDispatch(), CounterLog(env.devices)
    fallbacks_before = counters.read(attention.DENSE_FALLBACK_COUNTER)

    def let_go(param):
        # Watch keeps the loop's locals, and with them the trainer's state
        # past the fit's end, where the module's arrays return to the device
        watch.locals = {n: v for n, v in param.locals.items()
                        if n == "trainer"}
    t_build = env.since_start()

    opt = dict(cfg["optimizer"])
    opt["rescale_grad"] = 1.0 / (batch * seq)     # the MEAN token loss
    try:
        mod.fit(it, num_epoch=1, optimizer=opt.pop("name"),
                optimizer_params=opt, initializer=model.initializer(),
                arg_params={n: mx.nd.array(v, ctx=mx.cpu(0))
                            for n, v in sys_params.items()},
                eval_metric=mx.metric.Loss(output_names=[loss_name]),
                batch_end_callback=[first, mx.callback.ExpertLoadCounters(1),
                                    counters, watch, let_go],
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
    moe = {n: counters.rows[i1][n] - counters.rows[i0][n]
           for n in CounterLog.NAMES if n.endswith("_total")}
    in_window = counters.rows[i0 + 1:i1 + 1]
    moe["expert_load_max"] = max(r["moe_expert_load_max"] for r in in_window)
    moe["expert_load_mean"] = float(np.mean(
        [r["moe_expert_load_mean"] for r in in_window]))
    moe["window_steps"] = n_disp * k
    moe["layer_steps"] = sum(r["layer_steps"] for r in in_window)
    # the profiler's window: the dispatches between its two annotations
    moe["traced_grouped_pairs"] = sum(
        r["grouped_pairs"] for r in counters.rows[i1 + 2:watch.i_trace_end + 1]
    ) if watch.i_trace_end else 0
    dense_attention = counters.read(attention.DENSE_FALLBACK_COUNTER) \
        - fallbacks_before

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
    dropped = counters.rows[-1]["moe_tokens_dropped_total"]
    check(dropped == 0, f"{dropped} token-expert pairs dropped", faults)
    check(counters.rows[-1]["moe_tokens_routed_total"] > 0,
          "no token reached a held expert", faults)
    check(dense_attention == 0, f"{dense_attention} attention calls traced "
          "onto the dense (S, S) path", faults)
    memory_peak = counters.rows[i1]["memory_peak_bytes"]
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
         compiles_in_window=compiles_in_window, moe=moe,
         dense_attention_calls=dense_attention,
         trace_window_dispatches=(watch.traced if watch.i_trace_end else 0))

    # free the trainer's state before the reference takes the chip, and
    # the step's traces and programs before it takes the host (a cold run
    # peaks at 35.7 of the machine's 40 GiB: PERF.md section 5)
    watch.locals = None
    del mod
    jax.clear_caches()
    gc.collect()
    t = env.since_start()
    reference = Reference(env, model, ref_params)
    want_losses, margins, (b, row), want_logits = reference.forward(
        pool[:int(tr["fp32_candidate_batches"])])
    t = emit_check("first_step", t, env, check_first_step(
        env, want_losses, first.losses, faults))
    tokens = pool[b].data[0].asnumpy()[row:row + 1]
    labels = pool[b].label[0].asnumpy()[row:row + 1]
    want_grads = reference.gradients(tokens, labels,
                                     list(tr["checked_parameters"]))
    change = reference.adam(pool[:k], cfg["optimizer"])
    reference.free()
    t = emit_check("update", t, env, check_update(
        env, sys_params, first.params, change, faults))
    del change
    first.params = None
    emit_check("fp32", t, env, dict(
        check_fp32(env, model, sys_params, tokens, labels, want_logits,
                   want_grads, faults),
        routing_margins=margins, fp32_sequence=[b, row]))
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
                 "moe": moe, "steps_per_dispatch": k,
                 "sequences_per_step": batch},
        "profile_dir": watch.profile_dir if watch.i_trace_end else None,
    }
