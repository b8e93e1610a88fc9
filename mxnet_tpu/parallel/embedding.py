"""Row-sharded embedding training with row-sparse gradient exchange
(mx.parallel.embedding).

Role of the reference's row_sparse recommender stack — `Embedding` over a
row_sparse weight, `KVStore.PullRowSparse`, and the sparse optimizer
kernels (PAPER.md §3/§6) — composed TPU-native into one shard_map step.
A vocab-size table cannot replicate per device ("millions of users" is
the ROADMAP's recommender scenario), and a dense gradient exchange moves
the WHOLE table every step even though a batch touches a sliver of it.
Here:

  placement   the (V, D) table is row-sharded 1/N per device over the dp
              mesh axis (padded so the shard is even); optimizer state
              for the table is sharded identically, so memory AND update
              cost drop N-fold.
  lookup      each device dedups its local batch's flat ids
              (ops/sparse_ops.unique_rows — static-shape jnp.unique),
              all-gathers the per-device unique id lists, serves the rows
              it owns (non-owned slots contribute zeros), and a
              psum-scatter returns exactly each device's unique rows —
              a gather whose wire scales with TOUCHED rows, not vocab.
  backward    the loss is differentiated wrt the gathered unique ROWS
              (never the table — autodiff would materialize a dense
              (V/N, D) cotangent), and the (rows, vals) pairs are
              exchanged as-is: one all-gather of the per-row gradients,
              a second dedup + segment-sum on the receiver, then the
              lazy `rows_*` scatter kernels update only owned touched
              rows. Out-of-shard slots map one-past-the-shard and the
              kernels' mode="drop" scatters discard them.
  dense MLP   the non-embedding parameters keep the normal dp path:
              replicated, gradient psum, same fused update formulas.

``MXNET_EMBED_EXCHANGE=dense`` keeps the table replicated and all-reduces
the dense (V, D) gradient — the paper-baseline A/B the bench lane and
`hloaudit.fit_step_embedding` measure against. With every row touched
(fp32) the two exchanges are BIT-identical: same forward values, same
per-row scatter-add sums, same `rows_*` update kernels.

``MXNET_EMBED_COMPRESS=bf16|fp8`` casts the backward (rows, vals)
exchange to a narrow wire dtype (fp8 adds a per-row max-abs scale
exchanged alongside). Unlike parallel/zero.py's bucket compression there
is NO error-feedback residual: a residual needs stable coordinates
across steps, and a row's slot in the per-step unique list is not one —
the honest alternative would be a per-device table-sized residual,
defeating the sharding. Per-row scaling bounds the relative error at the
wire dtype's mantissa step instead; convergence is asserted by the
selftest (docs/SPARSE.md "wire compression").

Env surface: ``MXNET_EMBED_EXCHANGE=sparse|dense``,
``MXNET_EMBED_UNIQUE_CAP`` (per-device unique-row slots, 0 = auto =
local ids per step, always lossless), ``MXNET_EMBED_COMPRESS``.

CLI: ``python -m mxnet_tpu.parallel.embedding --selftest`` (tiny-DLRM
convergence, dense-vs-sparse bit-identity when every row is touched,
checkpoint resume across sharding changes, wire proof), ``--hlo-check``
(post-SPMD collective/wire report at a given vocab), ``--bench``
(8 virtual CPU devices: sparse vs dense wire bytes at ≤5% touched rows;
its steps/s are a CPU mesh's, not the chip's).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as _np

from ..base import MXNetError
from .mesh import shard_map

__all__ = ["EmbeddingTrainer", "EmbeddingLayout", "counters",
           "resolve_exchange", "resolve_compress", "resolve_unique_cap"]

# wire dtypes for MXNET_EMBED_COMPRESS (same encodings as
# zero.WIRE_DTYPES; fp8 e4m3 keeps the most mantissa)
WIRE_DTYPES = {
    "bf16": jnp.bfloat16,
    "fp8": getattr(jnp, "float8_e4m3fn", jnp.bfloat16),
}
# fp8 per-row scale target: e4m3 tops out at 448; scaling row maxima to
# 240 leaves headroom for the decode multiply to stay finite
_FP8_AMAX = 240.0


def resolve_exchange(value=None):
    """Exchange mode: explicit arg wins, else MXNET_EMBED_EXCHANGE,
    else sparse."""
    if value is None:
        from .. import config
        value = config.get("MXNET_EMBED_EXCHANGE", "sparse")
    mode = str(value or "sparse").strip().lower()
    if mode not in ("sparse", "dense"):
        raise MXNetError(
            f"MXNET_EMBED_EXCHANGE must be sparse|dense, got {value!r}")
    return mode


def resolve_compress(value=None):
    """Wire-compression mode: none|bf16|fp8 (MXNET_EMBED_COMPRESS)."""
    if value is None:
        from .. import config
        value = config.get("MXNET_EMBED_COMPRESS", "none")
    mode = str(value or "none").strip().lower()
    if mode in ("", "0", "none", "off"):
        return "none"
    if mode not in WIRE_DTYPES:
        raise MXNetError(
            f"MXNET_EMBED_COMPRESS must be none|bf16|fp8, got {value!r}")
    return mode


def resolve_unique_cap(value=None):
    """Per-device unique-row slots per step (0 = auto = the local id
    count, which can never drop a row). A positive cap bounds the
    exchange size; it must cover the worst-case per-device unique count
    or over-cap rows lose their gradient (jnp.unique keeps the smallest
    ids) — docs/SPARSE.md "unique cap"."""
    if value is None:
        from .. import config
        value = config.get("MXNET_EMBED_UNIQUE_CAP", 0)
    try:
        cap = int(value)
    except (TypeError, ValueError):
        raise MXNetError(
            f"MXNET_EMBED_UNIQUE_CAP must be an int, got {value!r}")
    if cap < 0:
        raise MXNetError(
            f"MXNET_EMBED_UNIQUE_CAP must be >= 0, got {cap}")
    return cap


class EmbeddingLayout:
    """Row-shard layout of a (vocab, dim) table over N devices plus the
    analytic wire accounting of one training step.

    The vocab is padded to a multiple of N so the P("data") row shard is
    even; pad rows can never be looked up (ids are validated < vocab)
    and the one-past-the-pad sentinel marks unique-list slack. Ring
    collective accounting matches ZeroLayout: all-gather/reduce-scatter
    move (N-1)/N of the global buffer per device, all-reduce twice that.
    """

    def __init__(self, vocab, dim, n_dev, unique, n_states):
        self.vocab = int(vocab)
        self.dim = int(dim)
        self.n_dev = int(n_dev)
        self.unique = int(unique)           # per-device unique slots U
        self.n_states = int(n_states)
        self.padded_vocab = self.vocab + (-self.vocab % self.n_dev)
        self.rows_per_dev = self.padded_vocab // self.n_dev
        self.sentinel = self.padded_vocab   # fill id: owned by no shard

    def wire_bytes_per_step(self, exchange, wire_itemsize, mlp_bytes):
        """Analytic per-device wire bytes of one step (feeds the live
        `embed_wire_bytes` counter without a device sync; the measured
        numbers come from hloaudit.spmd_collectives). Sparse exchange:
        id all-gather + row psum-scatter forward, value all-gather (+
        fp8 scales) backward — every term scales with N*U, none with
        vocab. Dense exchange: one table-sized fp32 all-reduce."""
        n = self.n_dev
        frac = (n - 1) / n
        mlp = 2.0 * frac * mlp_bytes                    # grad all-reduce
        if exchange == "dense":
            return int(mlp + 2.0 * frac
                       * self.padded_vocab * self.dim * 4)
        nu = n * self.unique
        table = (nu * 4                                 # fwd id gather
                 + nu * self.dim * 4                    # fwd row scatter
                 + nu * self.dim * wire_itemsize)       # bwd val gather
        if wire_itemsize == 1:
            table += nu * 4                             # fp8 row scales
        return int(mlp + frac * table)

    def ownership(self, mlp_names):
        """{array name: owning dp rank} for checkpoint shard placement
        (checkpoint/state.to_shard_files ownership=): the table and its
        optimizer rows live row-sharded on every rank — rank 0 seals
        them (it already owns the leading rows); replicated MLP arrays
        round-robin so no single shard carries the whole dense tail."""
        own = {"param:embed": 0}
        for j in range(self.n_states):
            own[f"opt:embed:{j}"] = 0
        for i, n in enumerate(mlp_names):
            k = i % self.n_dev
            own[f"param:{n}"] = k
            for j in range(self.n_states):
                own[f"opt:{n}:{j}"] = k
        return own


# -- live counter export (profiler hook "embed", scraped by telemetry) -------

_COUNTERS = {"embed_wire_bytes": 0, "embed_steps": 0,
             "embed_unique_rows": 0, "embed_touched_frac": 0.0,
             "embed_vocab_rows": 0, "embed_sparse": 1,
             "embed_compress_bits": 32}
# last step's device-resident global-unique-row count: materialized at
# scrape time (counters()), never on the step path — the dispatch loop
# must not sync on a scalar
_LAST_NNZ = {"dev": None, "vocab": 0}
_HOOKED = False


def counters():
    """Host-side embedding-exchange counters: cumulative analytic wire
    bytes, steps, and the last step's touched-row stats. Reading the
    touched-row count materializes one device scalar (scrape-time only;
    by then the step that produced it has long retired)."""
    dev, vocab = _LAST_NNZ["dev"], _LAST_NNZ["vocab"]
    if dev is not None and vocab:
        try:
            nnz = int(dev)
        except Exception:           # pragma: no cover - mid-teardown
            nnz = 0
        _COUNTERS["embed_unique_rows"] = nnz
        _COUNTERS["embed_touched_frac"] = round(nnz / vocab, 6)
    return dict(_COUNTERS)


def _ensure_hook():
    global _HOOKED
    if not _HOOKED:
        from .. import profiler
        profiler.register_counter_export("embed", counters)
        _HOOKED = True


def _bce_logits(logit, y):
    """Numerically stable sum of binary cross-entropy with logits."""
    z = logit.astype(jnp.float32)
    return jnp.sum(jnp.maximum(z, 0.0) - z * y
                   + jnp.log1p(jnp.exp(-jnp.abs(z))))


class EmbeddingTrainer:
    """One-table DLRM-style trainer: a row-sharded embedding over S
    categorical slots + an optional dense-feature input, concatenated
    into a replicated MLP ending in one click logit (sum-BCE loss).

    The whole step — sparse lookup exchange, fwd/bwd, row-sparse
    gradient exchange, lazy table update, MLP psum + update — is ONE
    shard_map program per config (distinctly named for the post-SPMD
    HLO audit). State is an opaque tuple the step round-trips (dp
    contract); host access goes through ``host_params`` /
    ``export_training_state``, which return full topology-independent
    per-parameter arrays so checkpoints interchange across device
    counts, unique caps, and MXNET_EMBED_EXCHANGE changes.
    """

    def __init__(self, mesh, vocab, embed_dim, n_slots, dense_dim=0,
                 mlp_hidden=(32,), optimizer="sgd", learning_rate=0.05,
                 momentum=0.0, wd=0.0, rescale_grad=1.0,
                 clip_gradient=None, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, exchange=None, compress=None,
                 unique_cap=None, batch_size=None, program_tag=None):
        if optimizer not in ("sgd", "adam"):
            raise MXNetError(
                f"EmbeddingTrainer supports sgd|adam, got {optimizer!r}")
        self._mesh = mesh
        self._ax = mesh.axis_names[0]
        self._n_dev = int(mesh.devices.size)
        self.vocab = int(vocab)
        self.dim = int(embed_dim)
        self.n_slots = int(n_slots)
        self.dense_dim = int(dense_dim)
        self.mlp_hidden = tuple(int(h) for h in mlp_hidden)
        self.optimizer = optimizer
        self._lr = float(learning_rate)
        self._momentum = float(momentum)
        self._wd = float(wd)
        self._rescale = float(rescale_grad)
        self._clip = -1.0 if clip_gradient is None else float(clip_gradient)
        self._beta1, self._beta2, self._eps = \
            float(beta1), float(beta2), float(epsilon)
        self.exchange = resolve_exchange(exchange)
        self.compress = resolve_compress(compress)
        self._wire_dtype = (None if self.compress == "none"
                            else WIRE_DTYPES[self.compress])
        self._wire_itemsize = (4 if self._wire_dtype is None else
                               _np.dtype(self._wire_dtype).itemsize)
        cap = resolve_unique_cap(unique_cap)
        if batch_size is not None and int(batch_size) % self._n_dev:
            raise MXNetError(
                f"global batch {batch_size} must divide over "
                f"{self._n_dev} devices")
        self._batch = None if batch_size is None else int(batch_size)
        self._cap = cap
        n_states = {"sgd": (1 if self._momentum else 0), "adam": 2}[
            optimizer]
        self._n_states = n_states
        # U is only known once the per-device id count is (first step)
        self._layout = None
        self._step_fn = None
        # distinct jit names per config; no tag a prefix of another
        # (hloaudit matches the dump by module substring)
        suffix = {"none": "n", "bf16": "b", "fp8": "f"}[self.compress]
        mode = {"sparse": "sp", "dense": "dn"}[self.exchange]
        self._program_tag = (program_tag or f"estep_{mode}{suffix}")
        self._t = 0.0
        from jax.sharding import NamedSharding, PartitionSpec as P
        self._P = P
        self._repl = NamedSharding(mesh, P())
        self._bshard = NamedSharding(mesh, P(self._ax))
        self._tshard = (NamedSharding(mesh, P(self._ax, None))
                        if self.exchange == "sparse" else self._repl)
        _ensure_hook()

    # -- parameter surface ---------------------------------------------------

    @property
    def mlp_names(self):
        names = []
        for i in range(len(self.mlp_hidden) + 1):
            names += [f"mlp_w{i}", f"mlp_b{i}"]
        return names

    @property
    def param_names(self):
        return ["embed"] + self.mlp_names

    def _mlp_shapes(self):
        dims = ([self.n_slots * self.dim + self.dense_dim]
                + list(self.mlp_hidden) + [1])
        shapes = []
        for i in range(len(dims) - 1):
            shapes += [(dims[i], dims[i + 1]), (dims[i + 1],)]
        return shapes

    def _ensure_layout(self, n_local_ids):
        if self._layout is None:
            u = self._cap or int(n_local_ids)
            self._layout = EmbeddingLayout(self.vocab, self.dim,
                                           self._n_dev, u,
                                           self._n_states)
        return self._layout

    # -- state init / placement ----------------------------------------------

    def init_state(self, batch_size=None, seed=0):
        """(table, tstates, mlp, mstates, t) device state. The table is
        placed row-sharded (sparse exchange) or replicated (dense); the
        MLP replicates; `t` is the device-carried update count (adam
        bias correction), restored by import_training_state."""
        b = self._batch if batch_size is None else int(batch_size)
        if b is None:
            raise MXNetError("init_state needs batch_size")
        if b % self._n_dev:
            raise MXNetError(f"global batch {b} must divide over "
                             f"{self._n_dev} devices")
        self._batch = b
        L = self._ensure_layout(b // self._n_dev * self.n_slots)
        rng = _np.random.RandomState(seed)
        table = rng.normal(0.0, 0.01, size=(
            L.padded_vocab, self.dim)).astype(_np.float32)
        table[self.vocab:] = 0.0
        mlp = []
        for s in self._mlp_shapes():
            if len(s) == 2:
                mlp.append(rng.normal(
                    0.0, _np.sqrt(2.0 / s[0]), size=s)
                    .astype(_np.float32))
            else:
                mlp.append(_np.zeros(s, _np.float32))
        return self._place(table, [_np.zeros_like(table)
                                   for _ in range(self._n_states)],
                           mlp, [[_np.zeros_like(p)
                                  for _ in range(self._n_states)]
                                 for p in mlp], 0.0)

    def _place(self, table, tstates, mlp, mstates, t):
        self._t = float(t)
        put_t = lambda a: jax.device_put(
            _np.asarray(a, _np.float32), self._tshard)
        put_r = lambda a: jax.device_put(
            _np.asarray(a, _np.float32), self._repl)
        return (put_t(table), tuple(put_t(s) for s in tstates),
                tuple(put_r(p) for p in mlp),
                tuple(tuple(put_r(s) for s in st) for st in mstates),
                put_r(_np.float32(t)))

    def shard_inputs(self, arrays):
        """[ids (B,S) int, dense (B,F) f32, labels (B,) f32] -> device
        arrays sharded along the batch axis."""
        out = []
        for a in arrays:
            a = _np.asarray(a)
            a = a.astype(_np.int32 if _np.issubdtype(a.dtype, _np.integer)
                         else _np.float32)
            out.append(jax.device_put(a, self._bshard))
        return tuple(out)

    # -- the step program ----------------------------------------------------

    def _optimizer_rows(self, weight, states, rows, grad_rows, lr_t):
        """One lazy row-update: the SAME ops/sparse_ops kernels in every
        mode — sparse exchange hands them the deduped owned rows, the
        dense baseline and the MLP hand them an iota over all rows —
        so cross-mode parity is a data question, never a formula one."""
        from ..ops import sparse_ops as sp
        lr, t = lr_t
        if self.optimizer == "sgd":
            if self._n_states:
                w, m = sp.rows_sgd_mom_update(
                    weight, states[0], rows, grad_rows, lr,
                    self._momentum, wd=self._wd,
                    rescale_grad=self._rescale, clip_gradient=self._clip)
                return w, (m,)
            w = sp.rows_sgd_update(
                weight, rows, grad_rows, lr, wd=self._wd,
                rescale_grad=self._rescale, clip_gradient=self._clip)
            return w, ()
        eff_lr = lr * jnp.sqrt(1.0 - self._beta2 ** t) \
            / (1.0 - self._beta1 ** t)
        w, m, v = sp.rows_adam_update(
            weight, states[0], states[1], rows, grad_rows, eff_lr,
            self._beta1, self._beta2, self._eps, wd=self._wd,
            rescale_grad=self._rescale, clip_gradient=self._clip)
        return w, (m, v)

    def _mlp_forward(self, mlp, feat):
        h = feat
        n_layers = len(self.mlp_hidden) + 1
        for i in range(n_layers):
            w, b = mlp[2 * i], mlp[2 * i + 1]
            h = h @ w + b
            if i < n_layers - 1:
                h = jax.nn.relu(h)
        return h.reshape(-1)

    def _encode_wire(self, g):
        """Backward wire cast: bf16 is a straight cast (fp32 exponent
        range); fp8 e4m3 rides a per-row max-abs scale exchanged
        alongside (no residual — see module docstring)."""
        if self.compress == "bf16":
            return g.astype(jnp.bfloat16), None
        amax = jnp.max(jnp.abs(g), axis=1, keepdims=True)
        scale = jnp.where(amax > 0, amax / _FP8_AMAX, 1.0)
        return (g / scale).astype(self._wire_dtype), \
            scale[:, 0].astype(jnp.float32)

    def _impl(self):
        L = self._layout
        ax = self._ax
        n_dev, U = self._n_dev, L.unique
        R, Vp, sent = L.rows_per_dev, L.padded_vocab, L.sentinel
        dim, slots, ddim = self.dim, self.n_slots, self.dense_dim
        sparse = self.exchange == "sparse"
        wire_dt = self._wire_dtype
        lr = self._lr
        from ..ops import sparse_ops as sp

        def impl(table, tstates, mlp, mstates, t, ids, dense, labels):
            t = t + 1.0
            flat = ids.reshape(-1).astype(jnp.int32)

            if sparse:
                # [1] dedup local ids, gather every device's unique
                # list, serve owned rows, scatter the sums back: each
                # device ends with ITS unique rows (U, D). Non-owned
                # slots contribute exact zeros to the psum.
                uniq, inv, _ = sp.unique_rows(flat, U, sent)
                all_ids = jax.lax.all_gather(uniq, ax, tiled=True)
                k = jax.lax.axis_index(ax)
                lo = (k * R).astype(jnp.int32)
                owned = (all_ids >= lo) & (all_ids < lo + R)
                loc = jnp.where(owned, all_ids - lo, R)
                contrib = jnp.take(table, loc, axis=0, mode="fill",
                                   fill_value=0.0)
                rows = jax.lax.psum_scatter(
                    contrib, ax, scatter_dimension=0, tiled=True)
            else:
                rows, inv = jax.lax.pcast(table, ax, to="varying"), flat
            # differentiate at device-varying copies of the replicated
            # inputs: shard_map psums the cotangent of a replicated input
            # by itself, and the explicit psums of [2]/[4] below would
            # then count every device's gradient n_dev times
            mlp_v = jax.lax.pcast(mlp, ax, to="varying")

            def loss_fn(rows, mlp):
                emb = jnp.take(rows, inv, axis=0)
                feat = emb.reshape(-1, slots * dim)
                if ddim:
                    feat = jnp.concatenate([feat, dense], axis=1)
                return _bce_logits(self._mlp_forward(mlp, feat), labels)

            loss, (g_rows, g_mlp) = jax.value_and_grad(
                loss_fn, argnums=(0, 1))(rows, mlp_v)

            if sparse:
                # [2] row-sparse gradient exchange: (rows, vals) pairs
                # on the wire, never a table-sized buffer. The id list
                # was already gathered in [1]; only values (+ fp8
                # scales) move here.
                if wire_dt is not None:
                    wire, scales = self._encode_wire(g_rows)
                    vals = jax.lax.all_gather(
                        wire, ax, tiled=True).astype(jnp.float32)
                    if scales is not None:
                        s_all = jax.lax.all_gather(scales, ax,
                                                   tiled=True)
                        vals = vals * s_all[:, None]
                else:
                    vals = jax.lax.all_gather(g_rows, ax, tiled=True)
                # [3] receiver-side dedup: devices sharing a row each
                # contributed a partial sum — segment-sum them, then
                # map to local shard coordinates (one-past-the-shard
                # for non-owned/pad slots; the rows_* kernels drop
                # those writes)
                uniq2, inv2, nnz = sp.unique_rows(all_ids, n_dev * U,
                                                  sent)
                # every device counts the same gathered id list; pmax of
                # equal values is exact and types nnz as replicated
                nnz = jax.lax.pmax(nnz, ax)
                gsum = sp.segment_sum_rows(vals, inv2, n_dev * U)
                owned2 = (uniq2 >= lo) & (uniq2 < lo + R)
                rows2 = jnp.where(owned2, uniq2 - lo, R)
                new_table, new_tstates = self._optimizer_rows(
                    table, tstates, rows2, gsum, (lr, t))
            else:
                g_table = jax.lax.psum(g_rows, ax)
                all_rows = jnp.arange(Vp, dtype=jnp.int32)
                new_table, new_tstates = self._optimizer_rows(
                    table, tstates, all_rows, g_table, (lr, t))
                nnz = jnp.int32(Vp)

            # [4] dense MLP params: the normal dp path — psum'd grads,
            # replicated update (iota rows, same kernels)
            new_mlp, new_mstates = [], []
            for p, st, g in zip(mlp, mstates, g_mlp):
                g = jax.lax.psum(g, ax)
                p2 = p.reshape(p.shape[0], -1)
                w, s2 = self._optimizer_rows(
                    p2, tuple(s.reshape(p2.shape) for s in st),
                    jnp.arange(p2.shape[0], dtype=jnp.int32),
                    g.reshape(p2.shape), (lr, t))
                new_mlp.append(w.reshape(p.shape))
                new_mstates.append(tuple(s.reshape(p.shape)
                                         for s in s2))
            loss = jax.lax.psum(loss, ax)
            return (new_table, tuple(new_tstates), tuple(new_mlp),
                    tuple(new_mstates), t, loss, nnz)

        return impl

    def _build_step(self):
        if self._step_fn is not None:
            return
        from jax.sharding import NamedSharding
        P = self._P
        ax = self._ax
        tspec = P(ax, None) if self.exchange == "sparse" else P()
        impl = self._impl()

        def estep(table, tstates, mlp, mstates, t, ids, dense, labels):
            return impl(table, tstates, mlp, mstates, t, ids, dense,
                        labels)
        estep.__name__ = self._program_tag

        in_specs = (tspec, tspec, P(), P(), P(), P(ax), P(ax), P(ax))
        out_specs = (tspec, tspec, P(), P(), P(), P(), P())
        sm = shard_map(estep, mesh=self._mesh, in_specs=in_specs,
                       out_specs=out_specs)
        try:
            sm.__name__ = self._program_tag
        except AttributeError:          # pragma: no cover
            pass
        ns = lambda spec: NamedSharding(self._mesh, spec)
        self._step_fn = jax.jit(
            sm, in_shardings=tuple(ns(s) for s in in_specs),
            out_shardings=tuple(ns(s) for s in out_specs),
            donate_argnums=(0, 1, 2, 3, 4))

    def _mlp_bytes(self):
        return sum(4 * max(1, int(_np.prod(s)))
                   for s in self._mlp_shapes())

    def _tick_counters(self, nnz_dev):
        L = self._layout
        _COUNTERS["embed_wire_bytes"] += L.wire_bytes_per_step(
            self.exchange, self._wire_itemsize, self._mlp_bytes())
        _COUNTERS["embed_steps"] += 1
        _COUNTERS["embed_vocab_rows"] = self.vocab
        _COUNTERS["embed_sparse"] = int(self.exchange == "sparse")
        _COUNTERS["embed_compress_bits"] = self._wire_itemsize * 8
        _LAST_NNZ["dev"] = nnz_dev
        _LAST_NNZ["vocab"] = self.vocab

    def step(self, state, inputs):
        """One fused train step: (state, inputs) -> (state, loss, nnz)
        where nnz is the global touched-row count (device scalar — only
        telemetry scrape materializes it)."""
        table, tstates, mlp, mstates, t = state
        self._ensure_layout(
            inputs[0].shape[0] // self._n_dev * self.n_slots)
        self._build_step()
        ids, dense, labels = inputs
        from ..telemetry import devstats
        name = f"embed_{self.exchange}.step"
        args = (table, tstates, mlp, mstates, t, ids, dense, labels)
        devstats.on_dispatch(name, self._step_fn, args, steps=1)
        out = self._step_fn(*args)
        self._tick_counters(out[6])
        return out[:5], out[5], out[6]

    # -- host views / checkpoint round-trip ----------------------------------

    def host_params(self, state):
        """name -> full fp32 host arrays; the table is trimmed back to
        (vocab, dim) so the export is topology-independent (pad rows
        are a device-count artifact)."""
        table = _np.asarray(state[0])[:self.vocab]
        out = {"embed": table}
        for n, p in zip(self.mlp_names, state[2]):
            out[n] = _np.asarray(p)
        return out

    def export_training_state(self, state):
        """checkpoint.TrainingState-ready (arrays, meta): the usual
        param:/opt: names with FULL per-parameter arrays, so a resume
        can change device count, MXNET_EMBED_EXCHANGE, or the unique
        cap and restore state_sha256-identical state. meta["embed"]
        carries the layout + the ownership map for sharded commits."""
        # scratch layout, NOT _ensure_layout: only the cap-independent
        # fields (padded_vocab, ownership) are read here, and caching a
        # layout before the first step would freeze the unique cap at a
        # value unrelated to the batch (a fresh trainer that imports a
        # checkpoint before ever stepping would silently truncate its
        # dedup list to n_slots rows)
        L = self._layout or EmbeddingLayout(
            self.vocab, self.dim, self._n_dev,
            self._cap or self.n_slots, self._n_states)
        arrays = {}
        for n, a in self.host_params(state).items():
            arrays[f"param:{n}"] = a
        for j in range(self._n_states):
            arrays[f"opt:embed:{j}"] = \
                _np.asarray(state[1][j])[:self.vocab]
            for n, st in zip(self.mlp_names, state[3]):
                arrays[f"opt:{n}:{j}"] = _np.asarray(st[j])
        meta = {
            "t": float(_np.asarray(state[4])),
            "optimizer": self.optimizer,
            "embed": {
                "exchange": self.exchange,
                "compress": self.compress,
                "vocab": self.vocab, "dim": self.dim,
                "unique_cap": self._cap,
                "ownership": L.ownership(self.mlp_names),
            },
        }
        return arrays, meta

    def import_training_state(self, arrays, meta):
        """Inverse of export: re-pad the table for THIS topology and
        re-place every array under the current exchange mode's
        shardings. The checkpoint's own exchange/unique-cap settings are
        irrelevant — full arrays carry no layout."""
        t = float((meta or {}).get("t", 0.0))
        table = _np.asarray(arrays["param:embed"], _np.float32)
        if table.shape != (self.vocab, self.dim):
            raise MXNetError(
                f"embed table shape {table.shape} != "
                f"{(self.vocab, self.dim)}")
        # scratch layout, NOT _ensure_layout: only the cap-independent
        # fields (padded_vocab, ownership) are read here, and caching a
        # layout before the first step would freeze the unique cap at a
        # value unrelated to the batch (a fresh trainer that imports a
        # checkpoint before ever stepping would silently truncate its
        # dedup list to n_slots rows)
        L = self._layout or EmbeddingLayout(
            self.vocab, self.dim, self._n_dev,
            self._cap or self.n_slots, self._n_states)
        pad = L.padded_vocab - self.vocab

        def _padded(a):
            a = _np.asarray(a, _np.float32)
            return _np.concatenate(
                [a, _np.zeros((pad,) + a.shape[1:], _np.float32)]) \
                if pad else a

        tstates = [_padded(arrays[f"opt:embed:{j}"])
                   for j in range(self._n_states)]
        mlp = [_np.asarray(arrays[f"param:{n}"], _np.float32)
               for n in self.mlp_names]
        mstates = [[_np.asarray(arrays[f"opt:{n}:{j}"], _np.float32)
                    for j in range(self._n_states)]
                   for n in self.mlp_names]
        return self._place(_padded(table), tstates, mlp, mstates, t)


# ============================================================================
# CLI: --selftest / --hlo-check / --bench  (tools/ci.sh quick)
# ============================================================================

def _click_data(vocab, batch, slots, dense_dim, seed=0, structured=True):
    """Synthetic click data with learnable structure: the label is a
    parity-style function of two slots' ids plus a dense margin, so a
    table+MLP that memorizes per-row embeddings can drive the BCE
    down (the convergence assertion has something to converge TO)."""
    rng = _np.random.RandomState(seed)
    ids = rng.randint(0, vocab, size=(batch, slots)).astype(_np.int32)
    dense = rng.normal(size=(batch, dense_dim)).astype(_np.float32) \
        if dense_dim else _np.zeros((batch, 0), _np.float32)
    if structured:
        y = (((ids[:, 0] + ids[:, 1 % slots]) % 2)
             ^ (dense[:, 0] > 0 if dense_dim else 0)).astype(_np.float32)
    else:
        y = rng.randint(0, 2, size=(batch,)).astype(_np.float32)
    return ids, dense, y


def _permutation_data(vocab, batch, slots, dense_dim, seed=0):
    """Every table row touched EXACTLY once globally (ids are a
    permutation of arange(vocab) reshaped to (batch, slots)): each row's
    gradient has a single contribution, so no exchange can reassociate
    a sum and dense-vs-sparse bit-identity is well-posed."""
    assert batch * slots == vocab
    rng = _np.random.RandomState(seed)
    ids = rng.permutation(vocab).astype(_np.int32).reshape(batch, slots)
    dense = rng.normal(size=(batch, dense_dim)).astype(_np.float32) \
        if dense_dim else _np.zeros((batch, 0), _np.float32)
    y = rng.randint(0, 2, size=(batch,)).astype(_np.float32)
    return ids, dense, y


def _mk(mesh, vocab, batch, exchange, compress="none", optimizer="adam",
        lr=0.02, slots=4, dense_dim=4, dim=8, tag=None, cap=None,
        momentum=0.9):
    return EmbeddingTrainer(
        mesh, vocab=vocab, embed_dim=dim, n_slots=slots,
        dense_dim=dense_dim, mlp_hidden=(32,), optimizer=optimizer,
        learning_rate=lr, momentum=momentum if optimizer == "sgd" else 0.0,
        rescale_grad=1.0 / batch, exchange=exchange, compress=compress,
        batch_size=batch, program_tag=tag, unique_cap=cap)


def _run(tr, data, steps, state=None, seed=0):
    if state is None:
        state = tr.init_state(seed=seed)
    inputs = tr.shard_inputs(list(data))
    losses = []
    for _ in range(steps):
        state, loss, nnz = tr.step(state, inputs)
        losses.append(float(loss))
    return state, losses, int(nnz)


def selftest(argv_devices=2):
    """A/B the sparse exchange against the dense baseline on a tiny
    DLRM, printed as ONE embed_selftest JSON line (tools/ci.sh quick):

      1. convergence: sum-BCE falls >30% over 60 adam steps (sparse);
      2. bit-identity: with every row touched exactly once globally
         (fp32, sgd+momentum AND adam), trained table+MLP+optimizer
         state match the dense exchange BIT-for-bit;
      3. wire compression: bf16 stays close to fp32; fp8 (per-row
         scales) still converges;
      4. checkpoint: export -> import across an exchange-mode AND
         unique-cap change -> re-export restores state_sha256-equal
         state, and training continues;
      5. wire: --hlo-check subprocesses prove post-SPMD exchange bytes
         are vocab-INdependent under sparse (equal at V and 2V) and
         vocab-proportional under dense.
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
    results = {"metric": "embed_selftest", "devices": n_dev}

    # 1) convergence on structured clicks
    vocab, batch, slots = 64, 32, 4
    data = _click_data(vocab, batch, slots, 4, seed=1)
    tr, = [_mk(mesh, vocab, batch, "sparse")]
    state, ces, nnz = _run(tr, data, 60)
    results["ce_first"] = round(ces[0], 4)
    results["ce_last"] = round(ces[-1], 4)
    results["touched_rows"] = nnz
    results["converges"] = bool(
        _np.isfinite(ces[-1]) and ces[-1] < 0.7 * ces[0])

    # 2) dense-vs-sparse bit-identity when every row is touched once
    pvocab = batch * slots
    pdata = _permutation_data(pvocab, batch, slots, 4, seed=2)
    bit = {}
    for optimizer in ("sgd", "adam"):
        tr_sp = _mk(mesh, pvocab, batch, "sparse", optimizer=optimizer)
        tr_dn = _mk(mesh, pvocab, batch, "dense", optimizer=optimizer)
        ssp, _, _ = _run(tr_sp, pdata, 10)
        sdn, _, _ = _run(tr_dn, pdata, 10)
        hs, hd = tr_sp.host_params(ssp), tr_dn.host_params(sdn)
        same = all((hs[n] == hd[n]).all() for n in hs)
        # optimizer state must match too (moments only decay on
        # touched rows — here that is EVERY row)
        same = same and all(
            (_np.asarray(a)[:pvocab] == _np.asarray(b)[:pvocab]).all()
            for a, b in zip(ssp[1], sdn[1]))
        bit[optimizer] = bool(same)
    results["bitwise_sgd"] = bit["sgd"]
    results["bitwise_adam"] = bit["adam"]

    # 3) wire compression
    s16, ce16, _ = _run(_mk(mesh, vocab, batch, "sparse",
                            compress="bf16"), data, 60)
    s8, ce8, _ = _run(_mk(mesh, vocab, batch, "sparse",
                          compress="fp8"), data, 60)
    results["bf16_ce_last"] = round(ce16[-1], 4)
    results["fp8_ce_last"] = round(ce8[-1], 4)
    results["bf16_close"] = bool(
        abs(ce16[-1] - ces[-1]) <= 0.15 * ces[0])
    results["fp8_converges"] = bool(
        _np.isfinite(ce8[-1]) and ce8[-1] < 0.7 * ce8[0])

    # 4) checkpoint resume across exchange-mode + unique-cap change
    from mxnet_tpu.checkpoint.state import state_sha256, TrainingState
    arrays, meta = tr.export_training_state(state)
    sha0 = state_sha256(TrainingState(arrays, meta={"trainer": meta}))
    tr_dn = _mk(mesh, vocab, batch, "dense", cap=2 * batch * slots)
    st2 = tr_dn.import_training_state(arrays, meta)
    arrays2, meta2 = tr_dn.export_training_state(st2)
    sha1 = state_sha256(TrainingState(arrays2, meta={"trainer": meta2}))
    results["resume_sha_equal"] = bool(sha0 == sha1)
    _, cont, _ = _run(tr_dn, data, 3, state=st2)
    results["resume_continues"] = bool(_np.isfinite(cont[-1]))

    # 5) wire proof from the post-SPMD HLO (fresh subprocesses: dump
    # flags are consumed once at backend init)
    def _hlo(exchange, vocab_n):
        proc = subprocess.run(
            [sys.executable, "-m", "mxnet_tpu.parallel.embedding",
             "--hlo-check", "--exchange", exchange,
             "--vocab", str(vocab_n), "--devices", str(n_dev)],
            capture_output=True, text=True, timeout=300)
        from mxnet_tpu.analysis.hloaudit import parse_last_metric
        rec = parse_last_metric(proc.stdout, "embed_hlo_check")
        rec.setdefault("_stderr", (proc.stderr or "")[-300:])
        return rec

    v1, v2 = 2048, 4096
    sp1, sp2 = _hlo("sparse", v1), _hlo("sparse", v2)
    dn1, dn2 = _hlo("dense", v1), _hlo("dense", v2)
    b_sp1 = sp1.get("exchange_bytes_per_step") or 0
    b_sp2 = sp2.get("exchange_bytes_per_step") or 0
    b_dn1 = dn1.get("exchange_bytes_per_step") or 0
    b_dn2 = dn2.get("exchange_bytes_per_step") or 0
    results["hlo_sparse_bytes_v1"] = b_sp1
    results["hlo_sparse_bytes_v2"] = b_sp2
    results["hlo_dense_bytes_v1"] = b_dn1
    results["hlo_dense_bytes_v2"] = b_dn2
    results["hlo_wire_scales_with_rows"] = bool(
        b_sp1 and b_sp1 == b_sp2            # vocab-independent
        and b_dn2 > int(1.5 * b_dn1)        # vocab-proportional
        and b_sp1 < b_dn1)                  # and smaller outright

    ok = (results["converges"] and results["bitwise_sgd"]
          and results["bitwise_adam"] and results["bf16_close"]
          and results["fp8_converges"] and results["resume_sha_equal"]
          and results["resume_continues"]
          and results["hlo_wire_scales_with_rows"])
    results["ok"] = bool(ok)
    print(json.dumps(results), flush=True)
    return 0 if ok else 1


def hlo_check(exchange, compress="none", vocab=2048, devices=2,
              batch=32, slots=4):
    """Compile one step on a fresh pinned backend and report its
    post-SPMD collectives + ring wire bytes, split into the embedding
    exchange vs the (vocab-independent) MLP all-reduce."""
    import json
    from mxnet_tpu.analysis.hloaudit import pin_cpu_with_spmd_dump
    dump = pin_cpu_with_spmd_dump(devices, "embed_hlo_")
    import jax as _jax
    from mxnet_tpu.parallel import data_parallel_mesh
    from mxnet_tpu.analysis.hloaudit import (spmd_collectives,
                                             collective_wire_bytes)

    mesh = data_parallel_mesh(devices, _jax.devices()[:devices])
    mode = {"sparse": "sp", "dense": "dn"}[exchange]
    suffix = {"none": "n", "bf16": "b", "fp8": "f"}[compress]
    tag = f"estep_{mode}{suffix}_v{vocab}"
    tr = _mk(mesh, vocab, batch, exchange, compress=compress, tag=tag,
             slots=slots)
    data = _click_data(vocab, batch, slots, 4)
    state, _, _ = _run(tr, data, 1)

    colls = spmd_collectives(dump, f"jit_{tag}")
    import shutil
    shutil.rmtree(dump, ignore_errors=True)
    wires = collective_wire_bytes(colls, devices)
    mlp_ar = 2.0 * (devices - 1) / devices * tr._mlp_bytes()
    total = sum(wires.values())
    # scalar all-reduces (loss) round to 0 wire; the MLP all-reduce is
    # the only other vocab-independent term — everything else IS the
    # embedding exchange
    exch = max(0, int(total - wires["all-reduce"])) \
        if exchange == "sparse" else int(wires["all-reduce"] - mlp_ar)
    rec = {"metric": "embed_hlo_check", "exchange": exchange,
           "compress": compress, "vocab": vocab, "devices": devices,
           "unique_per_dev": tr._layout.unique,
           "collectives": {k: len(v) for k, v in colls.items()},
           "has_reduce_scatter": bool(colls["reduce-scatter"]),
           "exchange_bytes_per_step": exch,
           "mlp_allreduce_bytes": int(mlp_ar),
           "analytic_bytes_per_step": tr._layout.wire_bytes_per_step(
               exchange, tr._wire_itemsize, tr._mlp_bytes()),
           "wire_bytes_per_step": int(total)}
    rec["ok"] = bool(total > 0 and (
        exchange == "dense" or rec["has_reduce_scatter"]))
    print(json.dumps(rec), flush=True)
    return 0 if rec["ok"] else 1


def bench(devices=8, steps=10, vocab=65536, dim=48, batch=256, slots=8):
    """`--bench`: sparse vs dense gradient exchange
    on an N-virtual-device cpu mesh at a ≤5% touched-row fraction (the
    regime the row-sparse exchange exists for). Reports steps/s A/B,
    HLO-measured wire bytes per step for both arms, and the touched-row
    fraction. Prints one embed_bench JSON line."""
    import json
    import time
    from mxnet_tpu.analysis.hloaudit import pin_cpu_with_spmd_dump
    dump = pin_cpu_with_spmd_dump(devices, "embed_bench_hlo_")
    import jax as _jax
    from mxnet_tpu.parallel import data_parallel_mesh
    from mxnet_tpu.analysis.hloaudit import (spmd_collectives,
                                             collective_wire_bytes)

    n_dev = min(devices, len(_jax.devices()))
    mesh = data_parallel_mesh(n_dev, _jax.devices()[:n_dev])
    data = _click_data(vocab, batch, slots, 8, seed=0)
    touched = len(_np.unique(data[0]))

    def _arm(exchange, compress="none"):
        tag = ("estep_sp" if exchange == "sparse" else "estep_dn") + \
            {"none": "n", "bf16": "b", "fp8": "f"}[compress] + "_bench"
        tr = _mk(mesh, vocab, batch, exchange, compress=compress,
                 dim=dim, slots=slots, dense_dim=8, tag=tag)
        state, _, nnz = _run(tr, data, 2)
        inputs = tr.shard_inputs(list(data))
        rates = []
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(steps):
                state, loss, _ = tr.step(state, inputs)
            float(loss)
            rates.append(steps / (time.perf_counter() - t0))
        wires = collective_wire_bytes(
            spmd_collectives(dump, f"jit_{tag}"), n_dev)
        return sorted(rates)[1], int(sum(wires.values())), nnz

    sp_sps, sp_wire, nnz = _arm("sparse")
    f8_sps, f8_wire, _ = _arm("sparse", "fp8")
    dn_sps, dn_wire, _ = _arm("dense")
    import shutil
    shutil.rmtree(dump, ignore_errors=True)
    rec = {"metric": "embed_bench", "devices": n_dev,
           "vocab": vocab, "dim": dim, "batch": batch, "slots": slots,
           "touched_rows": int(touched),
           "touched_frac": round(touched / vocab, 4),
           "steps_per_window": steps,
           "dense_steps_per_s": round(dn_sps, 2),
           "sparse_steps_per_s": round(sp_sps, 2),
           "sparse_fp8_steps_per_s": round(f8_sps, 2),
           "speedup_sparse": round(sp_sps / dn_sps, 3),
           "speedup_sparse_fp8": round(f8_sps / dn_sps, 3),
           "wire_bytes_per_step_dense": dn_wire,
           "wire_bytes_per_step_sparse": sp_wire,
           "wire_bytes_per_step_sparse_fp8": f8_wire,
           "wire_reduction": round(dn_wire / max(1, sp_wire), 1),
           "wire_source": "post_spmd_hlo"}
    rec["ok"] = bool(rec["speedup_sparse"] >= 2.0
                     and rec["touched_frac"] <= 0.05
                     and sp_wire and dn_wire and sp_wire < dn_wire)
    print(json.dumps(rec), flush=True)
    return 0 if rec["ok"] else 1


def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser(
        prog="python -m mxnet_tpu.parallel.embedding")
    ap.add_argument("--selftest", action="store_true",
                    help="tiny-DLRM A/B vs dense exchange (ci.sh quick)")
    ap.add_argument("--hlo-check", action="store_true",
                    help="post-SPMD collective/wire-byte report")
    ap.add_argument("--bench", action="store_true",
                    help="sparse vs dense exchange steps/s + wire bytes")
    ap.add_argument("--exchange", default="sparse",
                    choices=["sparse", "dense"])
    ap.add_argument("--compress", default="none",
                    choices=["none", "bf16", "fp8"])
    ap.add_argument("--vocab", type=int, default=2048)
    ap.add_argument("--devices", type=int, default=2)
    ap.add_argument("--steps", type=int, default=10)
    args = ap.parse_args(argv)
    if args.hlo_check:
        return hlo_check(args.exchange, args.compress, args.vocab,
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
