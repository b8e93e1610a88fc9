"""ZeRO-sharded data parallelism (mx.parallel.zero).

Role of the reference's reserved ``KVStore.SetGradientCompression`` +
multi-device optimizer sharding (PAPER.md §6), built TPU-native on the
fused dp step: instead of every device holding fp32 master weights and
optimizer state for EVERY parameter and all-reducing full fp32
gradients (parallel/dp.py), each device owns a 1/N slice of flat
per-bucket master/optimizer buffers:

  stage 1  grads are psum'd (same wire as dp), but each device applies
           the optimizer to ITS shard only — optimizer state is
           sharded, the update work drops N-fold, and fp32 stage-1
           training is BIT-IDENTICAL to the unsharded baseline (same
           reduction, same elementwise update per element);
  stage 2  the psum becomes a reduce-scatter: each device receives only
           its gradient shard ((N-1)/N of the all-reduce wire), then
           all-gathers the updated compute-dtype params.

Parameters are packed into flat fp32 buckets of ``MXNET_ZERO_BUCKET_MB``
bytes (padded to a multiple of N, sharded over the dp axis); every fused
optimizer op in dp's ``_OPT_OPS`` is elementwise, so the update applies
directly to the flat 1-D shards. Bucketing bounds peak gather/scatter
buffer size and — because each bucket's reduce-scatter depends only on
that bucket's gradients — lets XLA's latency-hiding scheduler start
bucket k's collective while the backward for bucket k+1 is still
computing (asserted post-SPMD by analysis/hloaudit's ``fit_step_zero``
program; the cpu backend lowers synchronous collective forms, so the
async-interleave assertion binds where async pairs exist, i.e. on TPU).

On-wire gradient compression (``MXNET_GRAD_COMPRESS=fp8|bf16``) casts
the bucketed gradient to the wire dtype before the reduce, with a
per-device error-feedback residual (Lin et al., Deep Gradient
Compression) carried across steps — and through the fused K-step scan —
so the quantization error is re-injected instead of lost. This is WHY
the step is an explicit `shard_map` program rather than dp's implicit
GSPMD sharding: error feedback needs the per-device PARTIAL gradient
before the reduction, which the partitioner-inserted psum never exposes
at trace level.

Semantics deltas vs dp (documented in docs/ZERO.md): under shard_map
the forward runs per-device, so BatchNorm batch statistics are LOCAL to
each device's batch shard (the reference's cross-device BN semantics);
aux running stats are pmean'd back to replicated each step.

Env surface: ``MXNET_ZERO_STAGE=0|1|2`` (0 = plain dp; >0 reroutes
``DataParallelTrainer(...)`` construction here), ``MXNET_ZERO_BUCKET_MB``
(default 4), ``MXNET_GRAD_COMPRESS=none|bf16|fp8``.

CLI: ``python -m mxnet_tpu.parallel.zero --selftest`` (2-device A/B:
bitwise stage-1 parity, fp8 convergence, HLO wire-byte reduction),
``--hlo-check`` (post-SPMD collective report), ``--bench`` (8 virtual
CPU devices: dp vs ZeRO-1 vs ZeRO-2 vs +fp8, wire bytes a step; its
steps/s are a CPU mesh's, not the chip's).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as _np

from ..base import MXNetError
from .dp import DataParallelTrainer
from .mesh import shard_map

__all__ = ["ZeroTrainer", "ZeroLayout", "counters", "resolve_stage",
           "resolve_compress", "WIRE_DTYPES"]

# wire dtypes for MXNET_GRAD_COMPRESS; fp8 e4m3 keeps the most mantissa
# of the fp8 encodings (gradients after loss rescale sit well inside its
# range; the residual carries what the 3-bit mantissa drops)
WIRE_DTYPES = {
    "bf16": jnp.bfloat16,
    "fp8": getattr(jnp, "float8_e4m3fn", jnp.bfloat16),
}


def resolve_stage(value=None):
    """ZeRO stage: explicit arg wins, else MXNET_ZERO_STAGE, else the
    stage a pure-zero MXNET_PLAN names (so ``MXNET_PLAN=zero2`` reroutes
    plain trainer construction without going through the planner), else
    0."""
    if value is None:
        import os
        from .. import config
        # unset/empty collapses to the declared default 0; an explicit
        # "0" is the truthy string "0" here, so it still wins over plan
        value = os.environ.get("MXNET_ZERO_STAGE") or 0
        if not value:
            plan = str(config.get("MXNET_PLAN", "auto")).strip().lower()
            if plan in ("zero1", "zero2"):
                return int(plan[-1])
    try:
        stage = int(value)
    except (TypeError, ValueError):
        raise MXNetError(f"MXNET_ZERO_STAGE must be 0|1|2, got {value!r}")
    if stage not in (0, 1, 2):
        raise MXNetError(f"MXNET_ZERO_STAGE must be 0|1|2, got {stage}")
    return stage


def resolve_compress(value=None):
    """Wire-compression mode: none|bf16|fp8 (MXNET_GRAD_COMPRESS)."""
    if value is None:
        from .. import config
        value = config.get("MXNET_GRAD_COMPRESS", "none")
    mode = str(value or "none").strip().lower()
    if mode in ("", "0", "none", "off"):
        return "none"
    if mode not in WIRE_DTYPES:
        raise MXNetError(
            f"MXNET_GRAD_COMPRESS must be none|bf16|fp8, got {value!r}")
    return mode


def _resolve_bucket_bytes(mb=None):
    if mb is None:
        from .. import config
        mb = config.get("MXNET_ZERO_BUCKET_MB", 4)
    try:
        b = int(float(mb) * (1 << 20))
    except (TypeError, ValueError):
        raise MXNetError(f"MXNET_ZERO_BUCKET_MB must be a number, got {mb!r}")
    return max(b, 1)


class ZeroLayout:
    """Flat-bucket layout of the parameter set over N devices.

    Parameters are packed in declaration order into buckets of at most
    ``bucket_bytes`` fp32 bytes (a parameter never splits across
    buckets; a single parameter larger than the threshold gets its own
    bucket). Each bucket's flat length is padded to a multiple of
    ``n_dev`` so the P("data") shard is even; padding is zeros and the
    elementwise optimizer update on zero grads leaves it zeros.
    """

    def __init__(self, shapes, n_dev, bucket_bytes):
        self.shapes = [tuple(s) for s in shapes]
        self.n_dev = int(n_dev)
        self.sizes = [max(1, int(_np.prod(s))) if s else 1
                      for s in self.shapes]
        self.buckets = []
        cur, cur_bytes = [], 0
        for i, sz in enumerate(self.sizes):
            if cur and cur_bytes + 4 * sz > bucket_bytes:
                self.buckets.append(cur)
                cur, cur_bytes = [], 0
            cur.append(i)
            cur_bytes += 4 * sz
        if cur:
            self.buckets.append(cur)
        self.offsets, self.totals, self.padded, self.shard_len = \
            [], [], [], []
        for idxs in self.buckets:
            offs, o = [], 0
            for i in idxs:
                offs.append(o)
                o += self.sizes[i]
            self.offsets.append(offs)
            self.totals.append(o)
            p = o + (-o % self.n_dev)
            self.padded.append(p)
            self.shard_len.append(p // self.n_dev)

    @property
    def n_buckets(self):
        return len(self.buckets)

    def flatten_host(self, arrays, b):
        """Host numpy (padded,) fp32 flat buffer of bucket b."""
        flat = _np.zeros(self.padded[b], _np.float32)
        for a, i, off in zip(arrays, self.buckets[b], self.offsets[b]):
            flat[off:off + self.sizes[i]] = \
                _np.asarray(a, _np.float32).ravel()
        return flat

    def flatten_traced(self, parts, b):
        """Traced flat (padded,) buffer from bucket b's per-param
        tensors (keeps their dtype; pads with zeros)."""
        flat = jnp.concatenate([p.reshape(-1) for p in parts])
        pad = self.padded[b] - self.totals[b]
        if pad:
            flat = jnp.concatenate(
                [flat, jnp.zeros((pad,), flat.dtype)])
        return flat

    def unflatten_traced(self, flat, b):
        """[(param_index, tensor)] views of bucket b's flat buffer."""
        out = []
        for i, off in zip(self.buckets[b], self.offsets[b]):
            out.append((i, jax.lax.dynamic_slice_in_dim(
                flat, off, self.sizes[i]).reshape(self.shapes[i])))
        return out

    def unflatten_host(self, flat, b):
        out = []
        for i, off in zip(self.buckets[b], self.offsets[b]):
            out.append((i, _np.asarray(
                flat[off:off + self.sizes[i]]).reshape(self.shapes[i])))
        return out

    def owner(self, i):
        """Device owning parameter i's shard (by its start offset) —
        the checkpoint ownership map, so cooperative sharded commits
        write exactly the optimizer shards a rank owns."""
        b = next(k for k, idxs in enumerate(self.buckets) if i in idxs)
        off = self.offsets[b][self.buckets[b].index(i)]
        return min(off // self.shard_len[b], self.n_dev - 1)

    def wire_bytes_breakdown(self, stage, compute_itemsize, wire_itemsize):
        """(param all-gather bytes, grad-reduce bytes) per device per step
        (ring collective accounting: all-gather/reduce-scatter move
        (N-1)/N of the global buffer per device, all-reduce twice that).
        The per-stage split telemetry.devstats pairs with the step
        program's FLOPs for roofline accounting."""
        n = self.n_dev
        frac = (n - 1) / n
        ag = red = 0.0
        for p in self.padded:
            ag += p * frac * compute_itemsize               # all-gather
            r = p * frac * wire_itemsize                    # grad reduce
            red += r if stage >= 2 else 2 * r               # ar = 2x rs
        return int(ag), int(red)

    def wire_bytes_per_step(self, stage, compute_itemsize, wire_itemsize):
        """Analytic per-device wire bytes of one step — the breakdown's
        sum. The HLO-measured numbers come from hloaudit.spmd_collectives;
        this feeds the live `zero_wire_bytes` telemetry counter without a
        device sync."""
        ag, red = self.wire_bytes_breakdown(stage, compute_itemsize,
                                            wire_itemsize)
        return ag + red

    def overlap_frac(self):
        """Fraction of grad-reduce bytes whose bucket collective can
        start before the full backward finishes: every bucket except
        the one whose gradients complete last (bucket 0 — the
        input-side params, last out of the backward). Structural
        headroom; the HLO interleave assertion is the proof."""
        tot = sum(self.padded)
        if self.n_buckets < 2 or not tot:
            return 0.0
        return round(1.0 - self.padded[0] / tot, 4)

    def ownership(self, param_names, n_states):
        own = {}
        for i, n in enumerate(param_names):
            k = self.owner(i)
            own[f"param:{n}"] = k
            for j in range(n_states):
                own[f"opt:{n}:{j}"] = k
        return own


# -- live counter export (profiler hook "zero", scraped by telemetry) --------

_COUNTERS = {"zero_wire_bytes": 0, "zero_steps": 0,
             "zero_wire_allgather_bytes": 0, "zero_wire_reduce_bytes": 0,
             "zero_flops_per_step": 0.0,
             "zero_overlap_frac": 0.0, "zero_stage": 0,
             "zero_buckets": 0, "zero_compress_bits": 32}
_HOOKED = False


def counters():
    """Host-side ZeRO counters (no device sync): cumulative analytic
    wire bytes, steps, current stage/bucket/overlap configuration."""
    return dict(_COUNTERS)


def _ensure_hook():
    global _HOOKED
    if not _HOOKED:
        from .. import profiler
        profiler.register_counter_export("zero", counters)
        _HOOKED = True


class ZeroTrainer(DataParallelTrainer):
    """DataParallelTrainer with ZeRO-sharded masters/optimizer state.

    Drop-in: same constructor surface plus ``zero_stage`` /
    ``zero_bucket_mb`` / ``grad_compress`` (env-defaulted), same
    step/step_k/init_state/export/import contract. The params/states
    tuples it hands back are per-BUCKET flat fp32 shards instead of
    per-parameter replicas — opaque to every fused-fit loop, which
    round-trips them through the trainer; host access goes through
    ``host_params``/``export_training_state`` (which return the usual
    per-parameter arrays, so checkpoints interchange with plain dp and
    ``MXNET_ZERO_STAGE`` can change across a resume).
    """

    def __init__(self, symbol, mesh, data_names=("data",),
                 label_names=("softmax_label",), optimizer="sgd",
                 learning_rate=0.01, momentum=0.0, wd=0.0,
                 rescale_grad=None, clip_gradient=None, loss_index=0,
                 dtype="float32", input_preproc=None, loss_scaler=None,
                 zero_stage=None, zero_bucket_mb=None, grad_compress=None,
                 **opt_kwargs):
        stage = resolve_stage(zero_stage)
        if stage == 0:
            # direct construction is an explicit opt-in: default to
            # stage 1 when neither arg nor env picked one
            stage = 1
        super().__init__(symbol, mesh, data_names=data_names,
                         label_names=label_names, optimizer=optimizer,
                         learning_rate=learning_rate, momentum=momentum,
                         wd=wd, rescale_grad=rescale_grad,
                         clip_gradient=clip_gradient,
                         loss_index=loss_index, dtype=dtype,
                         input_preproc=input_preproc,
                         loss_scaler=loss_scaler, **opt_kwargs)
        self._zero_stage = stage
        self._bucket_bytes = _resolve_bucket_bytes(zero_bucket_mb)
        self._compress = resolve_compress(grad_compress)
        self._wire_dtype = (None if self._compress == "none"
                            else WIRE_DTYPES[self._compress])
        self._n_dev = int(self._mesh.devices.size)
        self._n_outputs = len(symbol.list_outputs())
        # N-D meshes (the planner's dp×tp+ZeRO composition): masters,
        # optimizer state and the gather/scatter collectives shard JOINTLY
        # over every mesh axis — 1/(D·T) per device — while the batch
        # stays sharded over the data axis only, so the T model replicas
        # of a data rank compute identical forwards/grads and the joint
        # reduce needs a 1/T rescale (docs/PLANNER.md "ZeRO over dp×tp").
        # A 1-D mesh keeps the scalar axis spelling so its programs stay
        # bit-identical to the single-mode trainer.
        axis_names = tuple(self._mesh.axis_names)
        self._shard_axes = (self._data_axis if len(axis_names) == 1
                            else axis_names)
        self._axis_sizes = tuple(int(self._mesh.shape[a])
                                 for a in axis_names)
        self._model_factor = (self._n_dev
                              // int(self._mesh.shape[self._data_axis]))
        self._layout = None
        self._resid_dev = ()
        self._zstep = None
        self._zero_multi = {}
        self._compute_itemsize = (
            _np.dtype(self._compute_dtype).itemsize
            if self._compute_dtype is not None else 4)
        self._wire_itemsize = (
            _np.dtype(self._wire_dtype).itemsize
            if self._wire_dtype is not None else self._compute_itemsize)
        # distinct jit names per config: the post-SPMD dump is matched
        # by module substring, and no tag may be a prefix of another
        suffix = {"none": "n", "bf16": "b16", "fp8": "f8"}[self._compress]
        if self._model_factor > 1:
            self._program_tag = \
                f"zstep_t{self._model_factor}s{stage}{suffix}"
        else:
            self._program_tag = f"zstep_s{stage}{suffix}"
        _ensure_hook()

    # -- layout / sharded placement ------------------------------------------

    def _ensure_layout(self, shapes):
        if self._layout is None:
            self._layout = ZeroLayout(shapes, self._n_dev,
                                      self._bucket_bytes)
            from jax.sharding import NamedSharding, PartitionSpec as P
            self._zshard = NamedSharding(self._mesh, P(self._shard_axes))
            self._rshard = NamedSharding(self._mesh,
                                         P(self._shard_axes, None))
        return self._layout

    def _pack_from_host(self, host_params, host_states):
        """Flatten per-parameter host arrays into sharded flat buckets;
        (re)initialize the compression residual to zeros."""
        L = self._ensure_layout([p.shape for p in host_params])
        masters, zstates = [], []
        for b, idxs in enumerate(L.buckets):
            masters.append(jax.device_put(
                L.flatten_host([host_params[i] for i in idxs], b),
                self._zshard))
            zstates.append(tuple(jax.device_put(
                L.flatten_host([host_states[i][j] for i in idxs], b),
                self._zshard) for j in range(self._n_states)))
        self._reset_residual()
        self._build_zero_step()
        return tuple(masters), tuple(zstates)

    def _reset_residual(self):
        if self._wire_dtype is None:
            self._resid_dev = ()
            return
        L = self._layout
        self._resid_dev = tuple(jax.device_put(
            _np.zeros((self._n_dev, L.padded[b]), _np.float32),
            self._rshard) for b in range(L.n_buckets))

    def init_state(self, shape_kwargs, initializer=None, seed=0,
                   arg_params=None, aux_params=None):
        params, states, aux = super().init_state(
            shape_kwargs, initializer=initializer, seed=seed,
            arg_params=arg_params, aux_params=aux_params)
        masters, zstates = self._pack_from_host(
            [_np.asarray(p) for p in params],
            [[_np.asarray(s) for s in st] for st in states])
        return masters, zstates, aux

    # -- the sharded step program --------------------------------------------

    def _zero_impl(self):
        """Per-device step body (runs under shard_map): all-gather
        compute-dtype params from the master shards, local fwd/bwd,
        per-bucket error-feedback compress + reduce(-scatter), update
        the owned master/state shards. Closures mirror dp._step_impl."""
        from ..ops.registry import AttrDict, OpCtx
        L = self._layout
        ax = self._data_axis
        # joint shard axes: scalar data axis on a 1-D mesh (bit-identical
        # legacy program), the full axis tuple on the planner's N-D
        # meshes. model replicas (non-data axes) compute identical grads,
        # so the joint psum over-counts by T — the 1/T rescale below is
        # EXACT for power-of-two T (an fp32 exponent decrement).
        axes = self._shard_axes
        axis_names = tuple(self._mesh.axis_names)
        axis_sizes = self._axis_sizes
        replica_axes = tuple(a for a in axis_names if a != ax)
        model_scale = (1.0 / self._model_factor
                       if self._model_factor > 1 else None)
        stage = self._zero_stage
        wire_dt = self._wire_dtype
        run, n_args = self._run, len(self._arg_names)
        param_pos, input_pos = list(self._param_pos), list(self._input_pos)
        loss_index = self._loss_index
        fcompute, attrs = self._fcompute, self._attrs
        has_t, is_adam = self._has_t, self._is_adam
        compute_dtype, has_ls = self._compute_dtype, self._has_ls
        scaler = self._scaler
        cast_input, preproc_names = self._cast_input, self._preproc_names
        input_preproc = self._input_preproc
        n_aux = len(self._aux_names)
        B = L.n_buckets

        def impl(masters, states, resid, aux, inputs, rng, lr, t, ls):
            rng, next_rng = jax.random.split(rng)
            scale = ls[0] if has_ls else None
            # [1] masters -> full compute-dtype params. The cast happens
            # on the SHARD, before the gather, so the param all-gather
            # moves half-width words under amp (the gather-side analogue
            # of dp's half-width grad all-reduce); the cast is
            # elementwise, so cast-then-gather == gather-then-cast.
            cparams = [None] * len(param_pos)
            for b in range(B):
                m = masters[b]
                if compute_dtype is not None:
                    m = m.astype(compute_dtype)
                full = jax.lax.all_gather(m, axes, tiled=True)
                for i, arr in L.unflatten_traced(full, b):
                    cparams[i] = arr
            cparams = tuple(cparams)

            def loss_fn(cparams):
                args = [None] * n_args
                for p, v in zip(param_pos, cparams):
                    args[p] = v
                for p, v, cast, nm in zip(input_pos, inputs, cast_input,
                                          preproc_names):
                    if input_preproc is not None:
                        v = input_preproc(nm, v)
                    args[p] = jnp.asarray(v, compute_dtype) \
                        if compute_dtype is not None and cast and \
                        jnp.issubdtype(v.dtype, jnp.floating) else v
                outputs, new_aux = run(tuple(args), aux, rng)
                # LOCAL batch-shard sum; the explicit psum below makes
                # the reported loss match dp's global-batch sum
                loss = outputs[loss_index].sum().astype(jnp.float32)
                obj = loss * scale if has_ls else loss
                return obj, (new_aux, outputs, loss)

            if has_ls:
                from .. import amp as _amp
                _amp._set_trace_loss_scale(scale)
            try:
                (_, (new_aux, outputs, loss)), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(cparams)
            finally:
                if has_ls:
                    from .. import amp as _amp
                    _amp._set_trace_loss_scale(None)

            # [2] per bucket: error feedback + wire cast + reduce. Each
            # bucket's collective depends only on that bucket's grads —
            # the dataflow slack the latency-hiding scheduler uses to
            # overlap bucket k's reduce with bucket k+1's backward.
            gshards, new_resid = [], []
            finite = jnp.asarray(True)
            for b in range(B):
                g = L.flatten_traced([grads[i] for i in L.buckets[b]], b)
                if model_scale is not None:
                    g = g * jnp.asarray(model_scale, g.dtype)
                if wire_dt is not None:
                    r = resid[b][0]                 # (padded,) local f32
                    acc = g.astype(jnp.float32) + r
                    c = acc.astype(wire_dt)
                    new_resid.append(acc - c.astype(jnp.float32))
                    g = c
                if stage >= 2:
                    gs = jax.lax.psum_scatter(g, axes, scatter_dimension=0,
                                              tiled=True)
                else:
                    gfull = jax.lax.psum(g, axes)
                    # joint linear rank in P(axes) tiling order (row-major
                    # over the mesh axes; == axis_index(ax) on 1-D)
                    k = jax.lax.axis_index(axis_names[0])
                    for a, s in zip(axis_names[1:], axis_sizes[1:]):
                        k = k * s + jax.lax.axis_index(a)
                    gs = jax.lax.dynamic_slice_in_dim(
                        gfull, k * L.shard_len[b], L.shard_len[b])
                g32 = gs.astype(jnp.float32)
                if has_ls:
                    finite = jnp.logical_and(
                        finite, jnp.all(jnp.isfinite(g32)))
                gshards.append(g32)

            if has_ls:
                # stage-2 shards are distinct per device: the skip
                # decision must be GLOBAL or replicas diverge
                bad = jax.lax.psum(
                    jnp.where(finite, 0, 1).astype(jnp.float32), axes)
                finite = bad == 0
                t = t + jnp.where(finite, 1.0, 0.0)
                inv_scale = 1.0 / scale
            else:
                t = t + 1.0
            eff_lr = lr
            if is_adam:
                b1, b2 = attrs["beta1"], attrs["beta2"]
                eff_lr = lr * jnp.sqrt(1.0 - b2 ** t) / (1.0 - b1 ** t)
            a2 = AttrDict(attrs)
            a2["lr"] = eff_lr
            if has_t:
                a2["t"] = t
            octx = OpCtx(is_train=True)

            # [3] elementwise optimizer update on the OWNED 1/N shard
            new_masters, new_states = [], []
            for b in range(B):
                g32 = gshards[b]
                if has_ls:
                    g32 = g32 * inv_scale
                res = fcompute(a2, octx, masters[b], g32, *states[b])
                if has_ls:
                    new_masters.append(
                        jnp.where(finite, res[0], masters[b]))
                    new_states.append(tuple(
                        jnp.where(finite, s, s0)
                        for s, s0 in zip(res[1:], states[b])))
                else:
                    new_masters.append(res[0])
                    new_states.append(tuple(res[1:]))
            if wire_dt is not None:
                if has_ls:
                    # a skipped step applied nothing: the residual must
                    # not absorb the overflowed gradient either
                    new_resid = [jnp.where(finite, nr, resid[b][0])
                                 for b, nr in enumerate(new_resid)]
                new_resid = tuple(nr[None] for nr in new_resid)
            else:
                new_resid = ()

            if has_ls:
                new_aux = tuple(jnp.where(finite, a, a0)
                                for a, a0 in zip(new_aux, aux))
            if n_aux:
                # local-BN statistics averaged back to replicated (the
                # out_spec asserts replication; exact for means, a
                # shard-average for variances — docs/ZERO.md)
                new_aux = tuple(jax.lax.pmean(a, ax) for a in new_aux)
            loss = jax.lax.psum(loss, ax)
            if replica_axes:
                # the model replicas of a data rank hold EQUAL loss/
                # outputs/aux, but the joint all-gather types them as
                # varying over the non-data axes; pmax of equal values is
                # exact and gives them the replication the out_specs claim
                new_aux, loss, outputs = jax.tree_util.tree_map(
                    lambda x: jax.lax.pmax(x, replica_axes),
                    (new_aux, loss, outputs))
            if has_ls:
                new_ls = scaler.update_state(ls, finite)
                return (tuple(new_masters), tuple(new_states), new_resid,
                        new_aux, loss, outputs, next_rng, t, new_ls)
            return (tuple(new_masters), tuple(new_states), new_resid,
                    new_aux, loss, outputs, next_rng, t)

        return impl

    def _zero_specs(self, stacked=False):
        from jax.sharding import PartitionSpec as P
        ax = self._data_axis
        axes = self._shard_axes      # joint masters/state/resid sharding
        ispec = P(None, ax) if stacked else P(ax)
        in_specs = (P(axes), P(axes), P(axes, None), P(), ispec,
                    P(), P(), P())
        out_core = (P(axes), P(axes), P(axes, None), P())
        return in_specs, out_core

    def _build_zero_step(self):
        if self._zstep is not None:
            return
        from jax.sharding import NamedSharding, PartitionSpec as P
        impl = self._zero_impl()
        self._zimpl = impl
        has_ls = self._has_ls
        ax = self._data_axis
        mesh = self._mesh

        if has_ls:
            def zstep(masters, states, resid, aux, inputs, rng, lr, t,
                      ls):
                return impl(masters, states, resid, aux, inputs, rng,
                            lr, t, ls)
        else:
            def zstep(masters, states, resid, aux, inputs, rng, lr, t):
                return impl(masters, states, resid, aux, inputs, rng,
                            lr, t, None)
        zstep.__name__ = self._program_tag

        in_specs, out_core = self._zero_specs()
        ls_extra = (P(),) if has_ls else ()
        out_specs = out_core + (P(), P(ax), P(), P()) + ls_extra
        sm = shard_map(zstep, mesh=mesh, in_specs=in_specs + ls_extra,
                       out_specs=out_specs)
        try:
            sm.__name__ = self._program_tag
        except AttributeError:      # pragma: no cover
            pass
        ns = lambda spec: NamedSharding(mesh, spec)
        self._zstep = jax.jit(
            sm,
            in_shardings=tuple(ns(s) for s in in_specs)
            + tuple(ns(s) for s in ls_extra),
            out_shardings=tuple(ns(s) for s in out_specs),
            donate_argnums=(0, 1, 2))

    def _zero_multi_fn(self, k, outputs_mode):
        key = (int(k), outputs_mode)
        fn = self._zero_multi.get(key)
        if fn is not None:
            return fn
        from jax.sharding import NamedSharding, PartitionSpec as P
        impl = self._zimpl
        has_ls = self._has_ls
        ax = self._data_axis
        mesh = self._mesh

        if has_ls:
            def multi(masters, states, resid, aux, inputs, rng, lr, t,
                      ls):
                def body(carry, xs):
                    masters, states, resid, aux, rng, t, ls = carry
                    (masters, states, resid, aux, loss, outputs, rng, t,
                     ls) = impl(masters, states, resid, aux, xs, rng,
                                lr, t, ls)
                    ys = (loss, outputs) if outputs_mode == "all" \
                        else loss
                    return (masters, states, resid, aux, rng, t, ls), ys

                (masters, states, resid, aux, rng, t, ls), ys = \
                    jax.lax.scan(body,
                                 (masters, states, resid, aux, rng, t,
                                  ls), inputs, length=key[0])
                losses, outputs = ys if outputs_mode == "all" \
                    else (ys, ())
                return (masters, states, resid, aux, losses, outputs,
                        rng, t, ls)
        else:
            def multi(masters, states, resid, aux, inputs, rng, lr, t):
                def body(carry, xs):
                    masters, states, resid, aux, rng, t = carry
                    (masters, states, resid, aux, loss, outputs, rng,
                     t) = impl(masters, states, resid, aux, xs, rng,
                               lr, t, None)
                    ys = (loss, outputs) if outputs_mode == "all" \
                        else loss
                    return (masters, states, resid, aux, rng, t), ys

                (masters, states, resid, aux, rng, t), ys = jax.lax.scan(
                    body, (masters, states, resid, aux, rng, t), inputs,
                    length=key[0])
                losses, outputs = ys if outputs_mode == "all" \
                    else (ys, ())
                return (masters, states, resid, aux, losses, outputs,
                        rng, t)
        multi.__name__ = self._program_tag.replace("zstep", "zstepk")

        in_specs, out_core = self._zero_specs(stacked=True)
        ls_extra = (P(),) if has_ls else ()
        out_specs = out_core + (
            P(), P(None, ax) if outputs_mode == "all" else P(),
            P(), P()) + ls_extra
        sm = shard_map(multi, mesh=mesh, in_specs=in_specs + ls_extra,
                       out_specs=out_specs)
        ns = lambda spec: NamedSharding(mesh, spec)
        fn = jax.jit(
            sm,
            in_shardings=tuple(ns(s) for s in in_specs)
            + tuple(ns(s) for s in ls_extra),
            out_shardings=tuple(ns(s) for s in out_specs),
            donate_argnums=(0, 1, 2))
        self._zero_multi[key] = fn
        return fn

    # -- public step surface (dp contract) -----------------------------------

    def _tick_counters(self, k):
        L = self._layout
        ag, red = L.wire_bytes_breakdown(self._zero_stage,
                                         self._compute_itemsize,
                                         self._wire_itemsize)
        _COUNTERS["zero_wire_bytes"] += (ag + red) * int(k)
        _COUNTERS["zero_wire_allgather_bytes"] += ag * int(k)
        _COUNTERS["zero_wire_reduce_bytes"] += red * int(k)
        _COUNTERS["zero_steps"] += int(k)
        _COUNTERS["zero_overlap_frac"] = L.overlap_frac()
        _COUNTERS["zero_stage"] = self._zero_stage
        _COUNTERS["zero_buckets"] = L.n_buckets
        _COUNTERS["zero_compress_bits"] = self._wire_itemsize * 8
        # XLA-reported FLOPs of the active zero step program (devstats
        # async extraction; 0 until the first extraction lands)
        from ..telemetry import devstats
        costs = devstats.step_costs()
        if costs["flops"] > 0 and str(costs["name"]).startswith("zero"):
            _COUNTERS["zero_flops_per_step"] = costs["flops"]

    def step(self, params, states, aux, inputs, rng=None):
        if self._zstep is None:
            raise MXNetError("ZeroTrainer.step before init_state/"
                             "import_training_state")
        self._ensure_dev_state(rng)
        from ..telemetry import devstats
        name = "zero%d.step" % self._zero_stage
        if self._has_ls:
            args = (params, states, self._resid_dev, aux, inputs,
                    self._rng_dev, self._lr_dev, self._t_dev,
                    self._ls_dev)
            devstats.on_dispatch(name, self._zstep, args, steps=1)
            out = self._zstep(*args)
            self._ls_dev = out[8]
        else:
            args = (params, states, self._resid_dev, aux, inputs,
                    self._rng_dev, self._lr_dev, self._t_dev)
            devstats.on_dispatch(name, self._zstep, args, steps=1)
            out = self._zstep(*args)
        self._resid_dev = out[2]
        self._rng_dev, self._t_dev = out[6], out[7]
        self._tick_counters(1)
        return out[0], out[1], out[3], out[4], out[5]

    def step_k(self, params, states, aux, inputs, rng=None,
               outputs_mode="none"):
        if self._zstep is None:
            raise MXNetError("ZeroTrainer.step_k before init_state/"
                             "import_training_state")
        self._ensure_dev_state(rng)
        k = int(inputs[0].shape[0])
        fn = self._zero_multi_fn(k, outputs_mode)
        from ..telemetry import devstats
        name = "zero%d.step_k%d" % (self._zero_stage, k)
        if self._has_ls:
            args = (params, states, self._resid_dev, aux, inputs,
                    self._rng_dev, self._lr_dev, self._t_dev,
                    self._ls_dev)
            devstats.on_dispatch(name, fn, args, steps=k)
            out = fn(*args)
            self._ls_dev = out[8]
        else:
            args = (params, states, self._resid_dev, aux, inputs,
                    self._rng_dev, self._lr_dev, self._t_dev)
            devstats.on_dispatch(name, fn, args, steps=k)
            out = fn(*args)
        self._resid_dev = out[2]
        self._rng_dev, self._t_dev = out[6], out[7]
        self._tick_counters(k)
        return out[0], out[1], out[3], out[4], out[5]

    # -- host views / checkpoint round-trip ----------------------------------

    def host_params(self, params):
        """name -> full per-parameter fp32 host arrays (np.asarray of a
        sharded global array materializes the gather)."""
        L = self._layout
        out = {}
        for b, m in enumerate(params):
            flat = _np.asarray(m)
            for i, arr in L.unflatten_host(flat, b):
                out[self._param_names[i]] = arr
        return out

    def export_training_state(self, params, states, aux):
        """Same per-parameter array names as dp (param:/opt:/aux:), so
        ZeRO checkpoints restore into plain dp and vice versa — an
        MXNET_ZERO_STAGE change across a resume is just a repack. Adds
        the zero meta block (stage/compress/ownership) and, under
        compression, the per-device error-feedback residuals."""
        L = self._layout
        arrays = {}
        for n, a in self.host_params(params).items():
            arrays[f"param:{n}"] = a
        for b in range(L.n_buckets):
            for j in range(self._n_states):
                flat = _np.asarray(states[b][j])
                for i, arr in L.unflatten_host(flat, b):
                    arrays[f"opt:{self._param_names[i]}:{j}"] = arr
        for n, a in zip(self._aux_names, aux):
            arrays[f"aux:{n}"] = _np.asarray(a)
        meta = self._export_meta()
        meta["zero"] = {
            "stage": self._zero_stage,
            "compress": self._compress,
            "bucket_bytes": self._bucket_bytes,
            "ownership": L.ownership(self._param_names, self._n_states),
        }
        if self._wire_dtype is not None:
            for b, r in enumerate(self._resid_dev):
                arrays[f"zero_resid:{b}"] = _np.asarray(r)
        return arrays, meta

    def import_training_state(self, arrays, meta):
        hp = [_np.asarray(arrays[f"param:{n}"], _np.float32)
              for n in self._param_names]
        hs = [[_np.asarray(arrays[f"opt:{n}:{j}"], _np.float32)
               for j in range(self._n_states)]
              for n in self._param_names]
        masters, zstates = self._pack_from_host(hp, hs)
        put = lambda v: jax.device_put(_np.asarray(v), self._repl)
        aux = tuple(put(arrays[f"aux:{n}"]) for n in self._aux_names)
        self._import_scalar_state(meta)
        if self._wire_dtype is not None:
            L = self._layout
            resid = []
            compat = True
            for b in range(L.n_buckets):
                r = arrays.get(f"zero_resid:{b}")
                if r is None or tuple(_np.asarray(r).shape) != \
                        (self._n_dev, L.padded[b]):
                    compat = False
                    break
                resid.append(jax.device_put(
                    _np.asarray(r, _np.float32), self._rshard))
            if compat and resid:
                self._resid_dev = tuple(resid)
            # else: _pack_from_host already zeroed them — an elastic
            # restore at a different device count or from a plain-dp
            # checkpoint drops the residual (a bounded one-step
            # compression-error loss, not a correctness loss)
        return masters, zstates, aux


# ============================================================================
# CLI: --selftest / --hlo-check / --bench  (tools/ci.sh quick)
# ============================================================================

def _wide_sym(dim=64, hidden=256, nclass=16):
    import mxnet_tpu as mx
    net = mx.sym.FullyConnected(mx.sym.Variable("data"),
                                num_hidden=hidden, name="zfc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=hidden, name="zfc2")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=nclass, name="zfc3")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _make_trainer(sym, mesh, stage, compress="none", dtype="float32",
                  batch=16, optimizer="sgd", bucket_mb=0.002, **kw):
    """stage 0 -> plain dp baseline; >0 -> ZeroTrainer. The tiny default
    bucket threshold forces multi-bucket layouts on the selftest MLPs."""
    from mxnet_tpu.parallel import DataParallelTrainer as DP
    common = dict(optimizer=optimizer, learning_rate=0.1,
                  rescale_grad=1.0 / batch, dtype=dtype, **kw)
    if optimizer == "sgd":
        common["momentum"] = 0.9
    if stage == 0:
        return DP(sym, mesh, zero_stage=0, **common)
    return ZeroTrainer(sym, mesh, zero_stage=stage,
                       grad_compress=compress, zero_bucket_mb=bucket_mb,
                       **common)


def _ce_of(outs, y, n):
    p = _np.asarray(outs[0], _np.float32)
    return float(-_np.log(p[_np.arange(n), y.astype(int)] + 1e-8).mean())


def selftest(argv_devices=2):
    """2-device A/B vs the unsharded baseline, printed as ONE
    zero_selftest JSON line (tools/ci.sh quick):

      1. stage-1 fp32: BIT-identical trained params after 20 steps;
      2. stage-1 bf16: fp32 masters within a few bf16 ULP of dp's and
         bit-identical across two ZeRO runs (XLA elides one bf16
         rounding point inside dp's weight-grad dot+all-reduce chain
         that an explicit shard_map psum cannot reproduce — docs/ZERO.md
         "bf16 parity"; the wire stays half-width either way);
      3. stage-2 fp32: numerically equal (reduce-scatter may reassociate
         the sum) and loss trace close;
      4. stage-2 + fp8 error feedback: CE decreases over 60 steps and
         the carried residual is non-zero;
      5. wire bytes: two --hlo-check subprocesses prove the stage-2
         reduce-scatter exists and the fp8 grad-reduce moves less than
         1/4 of the fp32 all-reduce's bytes (post-SPMD HLO).
    """
    import json
    import subprocess
    import sys
    from mxnet_tpu.config import pin_cpu
    pin_cpu(argv_devices)
    import jax as _jax
    from mxnet_tpu.parallel import data_parallel_mesh

    n_dev = min(argv_devices, len(_jax.devices()))
    mesh = data_parallel_mesh(n_dev, _jax.devices()[:n_dev])
    batch, dim, nclass = 16, 64, 16
    rng = _np.random.RandomState(0)
    x = rng.normal(size=(batch, dim)).astype(_np.float32)
    y = rng.randint(0, nclass, size=(batch,)).astype(_np.float32)
    sym = _wide_sym(dim=dim, nclass=nclass)
    results = {"metric": "zero_selftest", "devices": n_dev}

    def _train(stage, compress="none", dtype="float32", steps=20,
               optimizer="sgd"):
        tr = _make_trainer(sym, mesh, stage, compress=compress,
                           dtype=dtype, batch=batch, optimizer=optimizer)
        params, states, aux = tr.init_state(
            {"data": (batch, dim), "softmax_label": (batch,)})
        inputs = tr.shard_inputs([x, y])
        ces = []
        for _ in range(steps):
            params, states, aux, loss, outs = tr.step(params, states,
                                                      aux, inputs)
            ces.append(_ce_of(outs, y, batch))
        return tr, params, ces

    # 1) stage-1 fp32 bitwise parity
    tr0, p0, ce0 = _train(0)
    tr1, p1, ce1 = _train(1)
    h0 = {n: _np.asarray(p) for n, p in zip(tr0.param_names, p0)}
    h1 = tr1.host_params(p1)
    results["stage1_fp32_bitwise"] = bool(
        all((h0[n] == h1[n]).all() for n in h0))

    # 2) stage-1 bf16: masters track dp at bf16-ULP scale, and ZeRO
    # itself is run-to-run deterministic (bitwise)
    tr0b, p0b, _ = _train(0, dtype="bfloat16")
    tr1b, p1b, _ = _train(1, dtype="bfloat16")
    tr1c, p1c, _ = _train(1, dtype="bfloat16")
    h0b = {n: _np.asarray(p) for n, p in zip(tr0b.param_names, p0b)}
    h1b = tr1b.host_params(p1b)
    h1c = tr1c.host_params(p1c)
    # Closeness is measured in units of the bf16 mantissa step at each
    # tensor's own scale: XLA elides one bf16 rounding point in dp's
    # fused weight-grad chain that shard_map cannot reproduce (see
    # docs/ZERO.md "bf16 parity"), so the two programs drift by O(ULP)
    # per step.  Measured worst case at 2 devices / 20 steps: 2.1 ULP.
    ulp = 2.0 ** -8        # bf16 mantissa step
    results["stage1_bf16_close"] = bool(all(
        float(_np.abs(h0b[n] - h1b[n]).max())
        <= 8 * ulp * max(float(_np.abs(h0b[n]).max()), 1e-6)
        for n in h0b))
    results["stage1_bf16_deterministic"] = bool(
        all((h1b[n] == h1c[n]).all() for n in h1b))

    # 3) stage-2 fp32: allclose (reduce-scatter reassociates)
    tr2, p2, ce2 = _train(2)
    h2 = tr2.host_params(p2)
    results["stage2_fp32_allclose"] = bool(
        all(_np.allclose(h0[n], h2[n], rtol=1e-5, atol=1e-6)
            for n in h0))
    results["stage2_ce_last"] = ce2[-1]

    # 4) fp8 + error feedback converges; residual is live
    tr8, p8, ce8 = _train(2, compress="fp8", steps=60)
    first, last = ce8[0], ce8[-1]
    resid_norm = float(sum(
        _np.abs(_np.asarray(r)).sum() for r in tr8._resid_dev))
    results["fp8_ce_first"] = first
    results["fp8_ce_last"] = last
    results["fp8_converges"] = bool(_np.isfinite(last) and last < first)
    results["fp8_residual_nonzero"] = bool(resid_norm > 0)

    # 5) wire bytes from the post-SPMD HLO (fresh subprocesses: the
    # dump flags are consumed once at backend init)
    def _hlo(stage, compress):
        proc = subprocess.run(
            [sys.executable, "-m", "mxnet_tpu.parallel.zero",
             "--hlo-check", "--stage", str(stage),
             "--compress", compress],
            capture_output=True, text=True, timeout=300)
        from mxnet_tpu.analysis.hloaudit import parse_last_metric
        rec = parse_last_metric(proc.stdout, "zero_hlo_check")
        rec.setdefault("_stderr", (proc.stderr or "")[-300:])
        return rec

    h_base = _hlo(0, "none")
    h_z2 = _hlo(2, "none")
    h_f8 = _hlo(2, "fp8")
    base_bytes = h_base.get("grad_reduce_bytes_per_step") or 0
    z2_bytes = h_z2.get("grad_reduce_bytes_per_step") or 0
    f8_bytes = h_f8.get("grad_reduce_bytes_per_step") or 0
    results["hlo_base_grad_reduce_bytes"] = base_bytes
    results["hlo_zero2_grad_reduce_bytes"] = z2_bytes
    results["hlo_zero2_fp8_grad_reduce_bytes"] = f8_bytes
    results["hlo_zero2_has_reduce_scatter"] = bool(
        h_z2.get("has_reduce_scatter"))
    # stage-2 halves the grad-reduce wire (rs = half an all-reduce);
    # fp8 cuts the remaining bytes 4x vs f32
    results["hlo_wire_reduced"] = bool(
        base_bytes and z2_bytes and f8_bytes
        and z2_bytes < base_bytes and f8_bytes * 4 <= base_bytes)

    ok = (results["stage1_fp32_bitwise"]
          and results["stage1_bf16_close"]
          and results["stage1_bf16_deterministic"]
          and results["stage2_fp32_allclose"]
          and results["fp8_converges"]
          and results["fp8_residual_nonzero"]
          and results["hlo_zero2_has_reduce_scatter"]
          and results["hlo_wire_reduced"])
    results["ok"] = bool(ok)
    print(json.dumps(results), flush=True)
    return 0 if ok else 1


def hlo_check(stage, compress="none", dtype="float32", devices=2):
    """Compile one (multi-bucket) step on a fresh pinned backend and
    report its post-SPMD collectives + ring wire bytes. stage 0 audits
    the plain dp baseline for the A/B."""
    import json
    from mxnet_tpu.analysis.hloaudit import pin_cpu_with_spmd_dump
    dump = pin_cpu_with_spmd_dump(devices, "zero_hlo_")
    import jax as _jax
    from mxnet_tpu.parallel import data_parallel_mesh

    mesh = data_parallel_mesh(devices, _jax.devices()[:devices])
    batch, dim, nclass = 16, 64, 16
    sym = _wide_sym(dim=dim, nclass=nclass)
    tr = _make_trainer(sym, mesh, stage, compress=compress, dtype=dtype,
                       batch=batch)
    params, states, aux = tr.init_state(
        {"data": (batch, dim), "softmax_label": (batch,)})
    x = _np.zeros((batch, dim), _np.float32)
    y = _np.zeros((batch,), _np.float32)
    params, states, aux, _, _ = tr.step(
        params, states, aux, tr.shard_inputs([x, y]))

    from mxnet_tpu.analysis.hloaudit import (spmd_collectives,
                                             collective_wire_bytes)
    tag = "jit_step" if stage == 0 else f"jit_{tr._program_tag}"
    colls = spmd_collectives(dump, tag)
    wires = collective_wire_bytes(colls, devices)
    # non-scalar all-reduces = gradient (or compressed-gradient) tensors;
    # scalar ones are the loss/finite reductions
    grad_ars = [c for c in colls["all-reduce"] if c[1]]
    rec = {"metric": "zero_hlo_check", "stage": stage,
           "compress": compress, "dtype": dtype, "devices": devices,
           "buckets": getattr(tr, "_layout", None).n_buckets
           if getattr(tr, "_layout", None) else 1,
           "collectives": {k: len(v) for k, v in colls.items()},
           "has_reduce_scatter": bool(colls["reduce-scatter"]),
           "grad_allreduce_nonscalar": len(grad_ars),
           "grad_reduce_bytes_per_step":
               wires["reduce-scatter"] + collective_wire_bytes(
                   {"all-reduce": grad_ars,
                    "reduce-scatter": [], "all-gather": []},
                   devices)["all-reduce"],
           "gather_bytes_per_step": wires["all-gather"],
           "wire_bytes_per_step": sum(wires.values())}
    rec["ok"] = bool(colls["all-reduce"] or colls["reduce-scatter"]) \
        and (stage == 0 or (rec["has_reduce_scatter"]
                            and not grad_ars) or stage == 1)
    print(json.dumps(rec), flush=True)
    return 0 if rec["ok"] else 1


def bench(devices=8, steps=12, hidden=1024, batch=16):
    """`--bench`: dp fp32 vs ZeRO-1 vs ZeRO-2 vs
    ZeRO-2+fp8 on an N-virtual-device cpu mesh, one big-parameter Adam
    MLP (optimizer-update work dominates, which is exactly the work
    ZeRO de-replicates: dp updates ALL params on EVERY device; ZeRO
    updates 1/N per device). Wire bytes per step come from the
    post-SPMD dump of each arm's distinctly-named module. Prints one
    zero_bench JSON line."""
    import json
    import time
    from mxnet_tpu.analysis.hloaudit import pin_cpu_with_spmd_dump
    dump = pin_cpu_with_spmd_dump(devices, "zero_bench_hlo_")
    import jax as _jax
    from mxnet_tpu.parallel import data_parallel_mesh
    from mxnet_tpu.analysis.hloaudit import (spmd_collectives,
                                             collective_wire_bytes)

    n_dev = min(devices, len(_jax.devices()))
    mesh = data_parallel_mesh(n_dev, _jax.devices()[:n_dev])
    dim, nclass = 256, 16
    sym = _wide_sym(dim=dim, hidden=hidden, nclass=nclass)
    rng = _np.random.RandomState(0)
    x = rng.normal(size=(batch, dim)).astype(_np.float32)
    y = rng.randint(0, nclass, size=(batch,)).astype(_np.float32)

    def _arm(stage, compress):
        tr = _make_trainer(sym, mesh, stage, compress=compress,
                           batch=batch, optimizer="adam",
                           bucket_mb=1.0)
        params, states, aux = tr.init_state(
            {"data": (batch, dim), "softmax_label": (batch,)})
        inputs = tr.shard_inputs([x, y])
        for _ in range(2):
            params, states, aux, loss, _ = tr.step(params, states, aux,
                                                   inputs)
        float(loss)
        rates = []
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(steps):
                params, states, aux, loss, _ = tr.step(params, states,
                                                       aux, inputs)
            float(loss)
            rates.append(steps / (time.perf_counter() - t0))
        tag = "jit_step" if stage == 0 else f"jit_{tr._program_tag}"
        wires = collective_wire_bytes(spmd_collectives(dump, tag),
                                      n_dev)
        return sorted(rates)[1], sum(wires.values()), wires

    n_params = sum(
        max(1, int(_np.prod(s))) for n, s in zip(
            sym.list_arguments(),
            sym.infer_shape(data=(batch, dim),
                            softmax_label=(batch,))[0])
        if n not in ("data", "softmax_label"))
    dp_sps, dp_wire, _ = _arm(0, "none")
    z1_sps, z1_wire, _ = _arm(1, "none")
    z2_sps, z2_wire, _ = _arm(2, "none")
    z8_sps, z8_wire, _ = _arm(2, "fp8")
    rec = {"metric": "zero_bench", "devices": n_dev,
           "params": int(n_params), "optimizer": "adam",
           "batch": batch, "steps_per_window": steps,
           "dp_steps_per_s": round(dp_sps, 2),
           "zero1_steps_per_s": round(z1_sps, 2),
           "zero2_steps_per_s": round(z2_sps, 2),
           "zero2_fp8_steps_per_s": round(z8_sps, 2),
           "speedup_zero1": round(z1_sps / dp_sps, 3),
           "speedup_zero2": round(z2_sps / dp_sps, 3),
           "speedup_zero2_fp8": round(z8_sps / dp_sps, 3),
           "wire_bytes_per_step_dp": int(dp_wire),
           "wire_bytes_per_step_zero1": int(z1_wire),
           "wire_bytes_per_step_zero2": int(z2_wire),
           "wire_bytes_per_step_zero2_fp8": int(z8_wire),
           "wire_source": "post_spmd_hlo"}
    print(json.dumps(rec), flush=True)
    return 0


def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser(
        prog="python -m mxnet_tpu.parallel.zero")
    ap.add_argument("--selftest", action="store_true",
                    help="2-device A/B vs unsharded dp (ci.sh quick)")
    ap.add_argument("--hlo-check", action="store_true",
                    help="post-SPMD collective/wire-byte report")
    ap.add_argument("--bench", action="store_true",
                    help="dp vs ZeRO-1/2/fp8 steps/s + wire bytes")
    ap.add_argument("--stage", type=int, default=2)
    ap.add_argument("--compress", default="none",
                    choices=["none", "bf16", "fp8"])
    ap.add_argument("--dtype", default="float32",
                    choices=["float32", "bfloat16", "float16"])
    ap.add_argument("--devices", type=int, default=2)
    ap.add_argument("--steps", type=int, default=12)
    args = ap.parse_args(argv)
    if args.hlo_check:
        return hlo_check(args.stage, args.compress, args.dtype,
                         args.devices)
    if args.bench:
        return bench(devices=args.devices, steps=args.steps)
    if args.selftest:
        return selftest(args.devices)
    ap.print_help()
    return 2


if __name__ == "__main__":
    import sys
    sys.exit(main())
