"""Sparse NDArray API (parity surface for python/mxnet/ndarray/sparse.py).

TPU-honest design (SURVEY.md §7 stage 11): TPU/XLA has no native sparse
STORAGE format, so `row_sparse` and `csr` stay *dense-backed views with
sparse metadata* — every dense op keeps working. COMPUTE, however, is
real when the array was built from sparse components: construction from
a (data, indices[, indptr]) triplet retains device-resident ELL
components (ops/sparse_ops.py), and `sparse.dot` / the optimizers'
row_sparse lazy path dispatch to gather/scatter kernels whose work
scales with nnz instead of the dense shape (reference kernels:
src/operator/tensor/dot-inl.h, src/operator/optimizer_op.cc sparse
variants). The crossover against dense on the chip is not measured on
this runtime.
"""
from __future__ import annotations

import numpy as _np

from ..base import MXNetError
from .ndarray import NDArray, array, zeros


class BaseSparseNDArray(NDArray):
    # sparse components (device arrays) when constructed from sparse
    # parts; None when the array is a plain dense-backed view.
    # CSR: (val (R,K) ELL, idx (R,K), counts (R,) nnz per row);
    # row_sparse: (data (N,...), row_indices (N,))
    __slots__ = ("_ell",)

    def __init__(self, data, ell=None):
        super().__init__(data)
        self._ell = ell

    def _rebind(self, data, ag_node=None):
        # any in-place mutation of the dense backing (+=, [:]=, copyto)
        # invalidates the retained components — dropping them demotes
        # the array to the dense-backed slow path instead of letting
        # .data/.indices or the optimizer scatter path read stale values
        self._ell = None
        super()._rebind(data, ag_node)


class CSRNDArray(BaseSparseNDArray):
    __slots__ = ()

    @property
    def stype(self):
        return "csr"

    def _csr_parts(self):
        """(data, indices, indptr) numpy triplet — from the retained
        components when present (explicit zeros preserved, exact
        round-trip), else re-derived from the dense backing."""
        if self._ell is not None:
            val, idx, counts = (_np.asarray(x) for x in self._ell)
            keep = _np.arange(val.shape[1])[None, :] < counts[:, None]
            indptr = _np.concatenate(
                [[0], _np.cumsum(counts)]).astype(_np.int64)
            return val[keep], idx[keep].astype(_np.int64), indptr
        a = self.asnumpy()
        counts = (a != 0).sum(axis=1)
        indptr = _np.concatenate([[0], _np.cumsum(counts)])
        # np.nonzero walks row-major, exactly CSR order
        return a[a != 0], _np.nonzero(a)[1], indptr

    @property
    def indices(self):
        return array(self._csr_parts()[1], dtype="int64")

    @property
    def indptr(self):
        return array(self._csr_parts()[2], dtype="int64")

    @property
    def data(self):
        return array(self._csr_parts()[0])

    def tostype(self, stype):
        if stype == "default":
            return NDArray(self._data)
        if stype == "csr":
            return self
        raise MXNetError(f"cannot convert csr to {stype}")


class RowSparseNDArray(BaseSparseNDArray):
    __slots__ = ()

    @property
    def stype(self):
        return "row_sparse"

    @property
    def indices(self):
        if self._ell is not None:
            # TRUE index list (explicit zero rows preserved — the
            # divergence ops/optimizer_ops.py:_row_mask documents only
            # applies to dense-backed arrays without components)
            return array(_np.asarray(self._ell[1]), dtype="int64")
        a = self.asnumpy().reshape(self.shape[0], -1)
        nz = _np.nonzero((a != 0).any(axis=1))[0]
        return array(nz, dtype="int64")

    @property
    def data(self):
        if self._ell is not None:
            return NDArray(self._ell[0])
        a = self.asnumpy()
        nz = _np.nonzero((a.reshape(a.shape[0], -1) != 0).any(axis=1))[0]
        return array(a[nz])

    def tostype(self, stype):
        if stype == "default":
            return NDArray(self._data)
        if stype == "row_sparse":
            return self
        raise MXNetError(f"cannot convert row_sparse to {stype}")


def csr_matrix(arg1, shape=None, ctx=None, dtype=None):
    """Create a CSRNDArray from (data, indices, indptr) or dense source.
    The triplet form also retains ELL components on device, enabling the
    gather-based `sparse.dot` fast path."""
    from ..ops import sparse_ops as sp
    if isinstance(arg1, tuple) and len(arg1) == 3:
        data, indices, indptr = arg1
        data = _np.asarray(getattr(data, "asnumpy", lambda: data)())
        indices = _np.asarray(getattr(indices, "asnumpy", lambda: indices)(),
                              dtype=_np.int64)
        indptr = _np.asarray(getattr(indptr, "asnumpy", lambda: indptr)(),
                             dtype=_np.int64)
        dense = _np.zeros(shape, dtype=data.dtype if dtype is None else dtype)
        rows = _np.repeat(_np.arange(shape[0]), _np.diff(indptr))
        if len(indices) and (int(indices.min()) < 0
                             or int(indices.max()) >= shape[1]):
            # validate BEFORE the flat dedup key: a negative index would
            # wrap into a positive cell there instead of erroring
            raise MXNetError(
                f"csr_matrix: column index out of range [0, {shape[1]}) "
                f"(min {int(indices.min())}, max {int(indices.max())})")
        key = rows * shape[1] + indices
        uniq, inv = _np.unique(key, return_inverse=True)
        if len(uniq) != len(key):
            # duplicate (row, col) entries: canonicalize by SUMMING them —
            # into the dense backing AND the ELL components — so the
            # gather fast path (which sums every entry) and the dense
            # fallback/tostype('default') agree. Plain dense[r, c] = data
            # would silently keep last-write-wins in one view only.
            summed = _np.zeros(len(uniq), dtype=data.dtype)
            _np.add.at(summed, inv, data)
            data = summed
            rows = (uniq // shape[1]).astype(_np.int64)
            indices = (uniq % shape[1]).astype(_np.int64)
            indptr = _np.concatenate(
                [[0], _np.cumsum(_np.bincount(rows, minlength=shape[0]))]
            ).astype(_np.int64)
        dense[rows, indices] = data
        nd = array(dense, ctx=ctx, dtype=dtype)
        val, idx, counts = sp.ell_from_csr(data, indices, indptr,
                                           num_features=shape[1])
        # components carry the SAME dtype as the dense backing, or the
        # fast paths would compute at a different precision
        ell = (array(val, ctx=ctx, dtype=dtype)._data,
               array(idx, ctx=ctx)._data, counts)
        return CSRNDArray(nd._data, ell)
    nd = array(getattr(arg1, "asnumpy", lambda: arg1)(), ctx=ctx,
               dtype=dtype)
    return CSRNDArray(nd._data)


def row_sparse_array(arg1, shape=None, ctx=None, dtype=None):
    """Create a RowSparseNDArray; the (data, indices) form retains the
    components on device for the scatter-based optimizer fast path."""
    if isinstance(arg1, tuple) and len(arg1) == 2:
        data, indices = arg1
        data = _np.asarray(getattr(data, "asnumpy", lambda: data)())
        indices = _np.asarray(getattr(indices, "asnumpy", lambda: indices)(),
                              dtype=_np.int64)
        if len(_np.unique(indices)) != len(indices):
            # format invariant (also assumed by the scatter kernels):
            # the dense backing keeps last-write-wins while scatter-add
            # would apply every duplicate — refuse loudly
            raise MXNetError("row_sparse_array: duplicate row indices")
        full_shape = shape or ((int(indices.max()) + 1,) + data.shape[1:])
        dense = _np.zeros(full_shape,
                          dtype=data.dtype if dtype is None else dtype)
        dense[indices] = data
        nd = array(dense, ctx=ctx, dtype=dtype)
        comp = (array(data, ctx=ctx, dtype=dtype)._data,
                array(indices.astype(_np.int32), ctx=ctx)._data)
        return RowSparseNDArray(nd._data, comp)
    nd = array(getattr(arg1, "asnumpy", lambda: arg1)(), ctx=ctx,
               dtype=dtype)
    return RowSparseNDArray(nd._data)


def merge_row_sparse(parts, shape=None, ctx=None, dtype=None):
    """Sum row_sparse values (RowSparseNDArray or raw (data, indices)
    pairs) into ONE canonical RowSparseNDArray: indices from every part
    are concatenated, deduplicated, and duplicate rows' values SUMMED
    (np.add.at — the host mirror of ops/sparse_ops.segment_sum_rows).
    This is the reduce step of a row-sparse gradient push (reference
    comm.h Reduce over kRowSparseStorage): the result satisfies the
    unique-row invariant row_sparse_array enforces, so it feeds the
    optimizers' scatter fast path directly."""
    datas, idxs = [], []
    for p in parts:
        if isinstance(p, RowSparseNDArray):
            if shape is None:
                shape = p.shape
            d = p.data.asnumpy()
            i = p.indices.asnumpy().astype(_np.int64).ravel()
        elif isinstance(p, tuple) and len(p) == 2:
            d, i = p
            d = _np.asarray(getattr(d, "asnumpy", lambda: d)())
            i = _np.asarray(getattr(i, "asnumpy", lambda: i)(),
                            dtype=_np.int64).ravel()
        else:
            raise MXNetError(
                "merge_row_sparse: parts must be RowSparseNDArray or "
                f"(data, indices) pairs, got {type(p).__name__}")
        if d.shape[:1] != i.shape:
            raise MXNetError(
                f"merge_row_sparse: {len(i)} indices for "
                f"{d.shape[0] if d.ndim else 0} value rows")
        datas.append(d)
        idxs.append(i)
    if shape is None:
        raise MXNetError("merge_row_sparse: shape= required when no part "
                         "is an NDArray")
    all_idx = (_np.concatenate(idxs) if idxs
               else _np.zeros(0, _np.int64))
    if all_idx.size == 0:
        empty = _np.zeros((0,) + tuple(shape[1:]),
                          _np.float32 if dtype is None else dtype)
        return row_sparse_array((empty, all_idx), shape=shape, ctx=ctx,
                                dtype=dtype)
    if int(all_idx.min()) < 0 or int(all_idx.max()) >= shape[0]:
        raise MXNetError(
            f"merge_row_sparse: row index out of range [0, {shape[0]}) "
            f"(min {int(all_idx.min())}, max {int(all_idx.max())})")
    all_dat = _np.concatenate(datas)
    uniq, inv = _np.unique(all_idx, return_inverse=True)
    summed = _np.zeros((len(uniq),) + all_dat.shape[1:], all_dat.dtype)
    _np.add.at(summed, inv, all_dat)
    return row_sparse_array((summed, uniq), shape=shape, ctx=ctx,
                            dtype=dtype)


def dot(lhs, rhs, transpose_a=False, transpose_b=False):
    """sparse.dot — gather-kernel path for dot(csr, dense) and
    dot(csr.T, dense) when the csr carries ELL components (construction
    from a triplet); falls back to the dense op otherwise. Reference:
    dot-inl.h DotCsrDnsDns / DotCsrTransDnsDns.

    Under autograd recording the dense op path is used uncondition-
    ally: the gather kernel bypasses the tape (it returns a raw device
    computation), and a silently untaped rhs gradient would be worse
    than a slower recorded one."""
    from ..ops import sparse_ops as sp
    from .ndarray import _invoke
    from .. import autograd
    if isinstance(lhs, CSRNDArray) and lhs._ell is not None \
            and not transpose_b and getattr(rhs, "ndim", 0) == 2 \
            and not autograd.is_recording() \
            and rhs.shape[0] == (lhs.shape[0] if transpose_a
                                 else lhs.shape[1]):
        val, idx, _counts = lhs._ell
        if transpose_a:
            out = sp.ell_dot_t(val, idx, rhs._data, lhs.shape[1])
        else:
            out = sp.ell_dot(val, idx, rhs._data)
        return NDArray(out)
    return _invoke("dot", lhs, rhs, transpose_a=transpose_a,
                   transpose_b=transpose_b)


def zeros_sparse(stype, shape, ctx=None, dtype=None):
    nd = zeros(shape, ctx=ctx, dtype=dtype)
    if stype == "csr":
        return CSRNDArray(nd._data)
    if stype == "row_sparse":
        return RowSparseNDArray(nd._data)
    return nd
