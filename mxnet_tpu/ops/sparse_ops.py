"""Gather/scatter sparse compute — TPU-native row_sparse/CSR kernels.

Role of the reference's sparse kernels (dot(csr,dense)
src/operator/tensor/dot-inl.h; sparse optimizer kernels
src/operator/optimizer_op.cc). TPU/XLA has no native sparse formats, so
the TPU-first realization is the ELL (padded-row) layout: a CSR matrix
(R, F) with at most K nonzeros per row becomes `val (R, K)` + `idx
(R, K)` device arrays (rows padded with idx=0/val=0). All kernels are
static-shaped gathers/scatters XLA lowers to its native dynamic-gather/
scatter HLOs — compute and memory scale with nnz (R*K), not with the
dense (R, F) / (F, M) sizes. NDArray-level dispatch lives in
ndarray/sparse.py; where sparse beats dense on the chip has not been
measured on this runtime (no benchmark cell runs these kernels).
"""
from __future__ import annotations

import numpy as _np

import jax
import jax.numpy as jnp


def ell_from_csr(data, indices, indptr, pad_to_multiple=8,
                 num_features=None):
    """Host-side CSR -> ELL conversion, vectorized (no per-row python
    loop — construction must scale to million-row matrices). Returns
    (val (R, K), idx (R, K), counts (R,)) with K = max row nnz rounded
    up for lane friendliness; counts preserves the exact nnz structure
    (pad entries are indistinguishable from an explicit zero at column
    0 without it). `num_features` (when known) bounds-checks the column
    indices here on the host — the device gathers/scatters downstream
    CLIP out-of-range indices instead of erroring, which would turn a
    malformed triplet into silently wrong values."""
    data = _np.asarray(data)
    indices = _np.asarray(indices, dtype=_np.int32)
    indptr = _np.asarray(indptr, dtype=_np.int64)
    if len(indices) and (int(indices.min()) < 0 or (
            num_features is not None
            and int(indices.max()) >= num_features)):
        raise ValueError(
            f"ell_from_csr: column index out of range [0, {num_features}) "
            f"(got min {int(indices.min())}, max {int(indices.max())})")
    rows = len(indptr) - 1
    counts = _np.diff(indptr).astype(_np.int32)
    k = int(counts.max()) if rows else 0
    k = max(1, -(-k // pad_to_multiple) * pad_to_multiple)
    val = _np.zeros((rows, k), dtype=data.dtype)
    idx = _np.zeros((rows, k), dtype=_np.int32)
    nnz = len(data)
    if nnz:
        row_of = _np.repeat(_np.arange(rows), counts)
        slot = _np.arange(nnz) - _np.repeat(indptr[:-1], counts)
        val[row_of, slot] = data
        idx[row_of, slot] = indices
    return val, idx, counts


def ell_dot(val, idx, weight):
    """dot(csr, dense): out[r] = sum_j val[r,j] * weight[idx[r,j]].
    Padded entries contribute val=0. out (R, M)."""
    if isinstance(idx, _np.ndarray) and idx.size and \
            int(idx.max()) >= weight.shape[0]:
        raise ValueError(f"ell_dot: column index {int(idx.max())} out of "
                         f"range for weight rows {weight.shape[0]}")
    gathered = jnp.take(weight, idx, axis=0)          # (R, K, M)
    return jnp.einsum("rk,rkm->rm", val.astype(weight.dtype), gathered)


def ell_dot_t(val, idx, dense, num_features):
    """dot(csr.T, dense): out[f] += sum over (r,j) with idx[r,j]==f of
    val[r,j] * dense[r]. The backward/transpose pattern (dW of a linear
    layer over sparse inputs). out (F, M) via XLA scatter-add."""
    if isinstance(idx, _np.ndarray) and idx.size and \
            int(idx.max()) >= num_features:
        raise ValueError(f"ell_dot_t: column index {int(idx.max())} out of "
                         f"range for num_features {num_features}")
    r, k = val.shape
    m = dense.shape[1]
    contrib = (val.astype(dense.dtype)[..., None]
               * dense[:, None, :])                   # (R, K, M)
    out = jnp.zeros((num_features, m), dense.dtype)
    return out.at[idx.reshape(-1)].add(contrib.reshape(r * k, m))


def unique_rows(ids, size, fill):
    """jit-safe static-shape dedup of a flat int row-id vector:
    ``(uniq (size,), inv (len(ids),), count)`` where ``uniq`` is sorted,
    padded with ``fill`` (pick one past the valid row range — a value
    that can never collide with a real id), ``inv`` maps each input
    position to its slot in ``uniq``, and ``count`` is the number of
    live (non-fill) uniques. The building block of the row-sparse
    gradient exchange: dedup happens BEFORE any wire movement, so
    per-step collective payloads scale with touched rows."""
    ids = jnp.asarray(ids).reshape(-1).astype(jnp.int32)
    uniq, inv = jnp.unique(ids, size=size, fill_value=fill,
                           return_inverse=True)
    count = jnp.sum(uniq != fill).astype(jnp.int32)
    return uniq, inv.reshape(-1).astype(jnp.int32), count


def segment_sum_rows(vals, inv, num_segments):
    """Sum value rows that dedup'd to the same unique slot:
    ``out[inv[i]] += vals[i]`` via a single XLA scatter-add (the vector
    form of np.add.at). Pair of unique_rows: (uniq, segment_sum) turns
    per-occurrence gradients into canonical row_sparse (rows, vals)."""
    vals = jnp.asarray(vals)
    out = jnp.zeros((num_segments,) + vals.shape[1:], vals.dtype)
    return out.at[jnp.asarray(inv).reshape(-1)].add(vals)


# The rows_* kernels gather with mode="clip" and scatter with
# mode="drop": an out-of-range row index (>= weight rows) reads row 0's
# values during the update math (harmless — the result is discarded)
# and its write is dropped entirely. This is what lets the sharded
# embedding exchange hand every device the full deduped global row list
# and mask non-owned/padding slots by mapping them to one-past-the-shard
# instead of compacting to a dynamic shape XLA can't compile. In-bounds
# behavior is unchanged (the modes only bind out of range). Negative
# indices must not be used for masking — they wrap before the mode
# applies.

def rows_sgd_update(weight, rows, grad_rows, lr, wd=0.0,
                    rescale_grad=1.0, clip_gradient=-1.0):
    """Row-sparse SGD: touch ONLY the listed rows (reference lazy_update
    sparse kernel semantics — untouched rows skip weight decay too).
    `rows` must be unique among in-bounds entries, the row_sparse format
    invariant (the reference's kernels iterate indices assuming the
    same); out-of-bounds entries are dropped."""
    weight = jnp.asarray(weight)
    g = jnp.asarray(grad_rows).astype(jnp.float32) * rescale_grad
    if clip_gradient > 0:
        g = jnp.clip(g, -clip_gradient, clip_gradient)
    w_rows = jnp.take(weight, rows, axis=0, mode="clip")\
        .astype(jnp.float32)
    upd = -lr * (g + wd * w_rows)
    return weight.at[rows].add(upd.astype(weight.dtype), mode="drop")


def rows_sgd_mom_update(weight, mom, rows, grad_rows, lr, momentum,
                        wd=0.0, rescale_grad=1.0, clip_gradient=-1.0):
    """Row-sparse SGD+momentum: momentum decays ONLY on touched rows
    (reference sgd_mom sparse kernel)."""
    weight, mom = jnp.asarray(weight), jnp.asarray(mom)
    g = jnp.asarray(grad_rows).astype(jnp.float32) * rescale_grad
    if clip_gradient > 0:
        g = jnp.clip(g, -clip_gradient, clip_gradient)
    w_rows = jnp.take(weight, rows, axis=0, mode="clip")\
        .astype(jnp.float32)
    m_rows = jnp.take(mom, rows, axis=0, mode="clip").astype(jnp.float32)
    m_new = momentum * m_rows - lr * (g + wd * w_rows)
    return (weight.at[rows].add(m_new.astype(weight.dtype), mode="drop"),
            mom.at[rows].set(m_new.astype(mom.dtype), mode="drop"))


def rows_adam_update(weight, mean, var, rows, grad_rows, lr, beta1, beta2,
                     epsilon, wd=0.0, rescale_grad=1.0, clip_gradient=-1.0):
    """Row-sparse (lazy) Adam: moments decay ONLY on touched rows
    (reference adam_update sparse kernel, optimizer_op.cc). Adam-family
    prep order: rescale -> +wd*w -> clip (ops/optimizer_ops.py
    _prep_wd_first — decay folds into the grad BEFORE clipping, unlike
    the SGD family)."""
    weight = jnp.asarray(weight)
    mean, var = jnp.asarray(mean), jnp.asarray(var)
    w_rows = jnp.take(weight, rows, axis=0, mode="clip")\
        .astype(jnp.float32)
    g = jnp.asarray(grad_rows).astype(jnp.float32) * rescale_grad \
        + wd * w_rows
    if clip_gradient > 0:
        g = jnp.clip(g, -clip_gradient, clip_gradient)
    m_rows = jnp.take(mean, rows, axis=0, mode="clip").astype(jnp.float32)
    v_rows = jnp.take(var, rows, axis=0, mode="clip").astype(jnp.float32)
    m_new = beta1 * m_rows + (1 - beta1) * g
    v_new = beta2 * v_rows + (1 - beta2) * g * g
    step = -lr * m_new / (jnp.sqrt(v_new) + epsilon)
    return (weight.at[rows].add(step.astype(weight.dtype), mode="drop"),
            mean.at[rows].set(m_new.astype(mean.dtype), mode="drop"),
            var.at[rows].set(v_new.astype(var.dtype), mode="drop"))
