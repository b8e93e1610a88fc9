"""Row-sparse embedding stack (mxnet_tpu.parallel.embedding + the
kvstore/ndarray/optimizer row_sparse surface, ISSUE 16): static-shape
dedup + segment-sum building blocks, lazy rows_* kernel parity against
dense updates restricted to the same rows, kvstore row_sparse push
(merge + lazy server-side update) and pull edge cases, layout wire
accounting/ownership, sparse-vs-dense exchange bit-identity, and
checkpoint round-trip across unique-cap changes."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError
from mxnet_tpu.ndarray import sparse as sp
from mxnet_tpu.ndarray.ndarray import array, zeros
from mxnet_tpu.ops import sparse_ops as ops
from mxnet_tpu.parallel import data_parallel_mesh
from mxnet_tpu.parallel.embedding import (EmbeddingLayout,
                                          EmbeddingTrainer,
                                          _permutation_data)


def _mesh(n=8):
    import jax
    return data_parallel_mesh(n, jax.devices()[:n])


# -- static-shape dedup / segment-sum ----------------------------------------

def test_unique_rows_static_shape_and_fill():
    ids = np.array([7, 3, 7, 7, 1], np.int32)
    uniq, inv, count = ops.unique_rows(ids, size=5, fill=99)
    uniq, inv = np.asarray(uniq), np.asarray(inv)
    assert int(count) == 3
    assert list(uniq) == [1, 3, 7, 99, 99]     # sorted, fill-padded
    # inv maps every position back to its slot in uniq
    assert all(uniq[inv[i]] == ids[i] for i in range(len(ids)))


def test_segment_sum_rows_collapses_duplicates():
    ids = np.array([2, 0, 2], np.int32)
    vals = np.array([[1.0, 2.0], [10.0, 20.0], [100.0, 200.0]],
                    np.float32)
    uniq, inv, _ = ops.unique_rows(ids, size=3, fill=5)
    out = np.asarray(ops.segment_sum_rows(vals, inv, 3))
    assert np.array_equal(out[0], [10.0, 20.0])     # row 0
    assert np.array_equal(out[1], [101.0, 202.0])   # row 2 summed


# -- lazy rows_* kernels vs dense update restricted to the same rows ---------

def _dense_sgd(w, rows, g, lr, wd):
    out = w.copy()
    out[rows] -= lr * (g + wd * w[rows])
    return out


def test_rows_sgd_matches_dense_restricted_and_drops_oob():
    rng = np.random.RandomState(0)
    w = rng.normal(size=(6, 3)).astype(np.float32)
    rows = np.array([4, 1, 6], np.int32)           # 6 is out of bounds
    g = rng.normal(size=(3, 3)).astype(np.float32)
    out = np.asarray(ops.rows_sgd_update(w, rows, g, 0.1, wd=0.01))
    exp = _dense_sgd(w, rows[:2], g[:2], 0.1, 0.01)
    assert np.allclose(out, exp, atol=1e-6)
    assert np.array_equal(out[[0, 2, 3, 5]], w[[0, 2, 3, 5]])


def test_rows_adam_matches_dense_restricted():
    rng = np.random.RandomState(1)
    w = rng.normal(size=(5, 2)).astype(np.float32)
    m = rng.normal(size=(5, 2)).astype(np.float32) * 0.1
    v = np.abs(rng.normal(size=(5, 2)).astype(np.float32)) * 0.1
    rows = np.array([3, 0], np.int32)
    g = rng.normal(size=(2, 2)).astype(np.float32)
    lr, b1, b2, eps, wd = 0.01, 0.9, 0.999, 1e-8, 0.02
    w2, m2, v2 = (np.asarray(a) for a in ops.rows_adam_update(
        w, m, v, rows, g, lr, b1, b2, eps, wd=wd))
    # dense reference restricted to the touched rows (adam prep order:
    # rescale -> +wd*w -> clip)
    ge = g + wd * w[rows]
    me = b1 * m[rows] + (1 - b1) * ge
    ve = b2 * v[rows] + (1 - b2) * ge * ge
    we = w[rows] - lr * me / (np.sqrt(ve) + eps)
    assert np.allclose(w2[rows], we, atol=1e-6)
    assert np.allclose(m2[rows], me, atol=1e-6)
    assert np.allclose(v2[rows], ve, atol=1e-6)
    untouched = [1, 2, 4]
    assert np.array_equal(w2[untouched], w[untouched])
    assert np.array_equal(m2[untouched], m[untouched])  # no moment decay


# -- merge_row_sparse --------------------------------------------------------

def test_merge_row_sparse_sums_duplicates_across_parts():
    a = sp.row_sparse_array((np.ones((2, 2), np.float32), [1, 3]),
                            shape=(6, 2))
    b = sp.row_sparse_array((np.full((2, 2), 2.0, np.float32), [3, 5]),
                            shape=(6, 2))
    merged = sp.merge_row_sparse([a, b])
    assert merged.stype == "row_sparse" and merged._ell is not None
    assert list(np.asarray(merged.indices.asnumpy())) == [1, 3, 5]
    dense = merged.asnumpy()
    assert np.array_equal(dense[3], [3.0, 3.0])     # 1 + 2 summed
    # empty merge with an explicit shape yields an nnz=0 sparse array
    empty = sp.merge_row_sparse([], shape=(4, 2))
    assert empty._ell is not None and not empty.asnumpy().any()
    with pytest.raises(MXNetError):
        sp.merge_row_sparse([(np.ones((1, 2), np.float32), [4])],
                            shape=(4, 2))           # row out of range


# -- kvstore row_sparse push/pull --------------------------------------------

def test_kvstore_row_sparse_push_engages_lazy_update():
    rng = np.random.RandomState(2)
    W = rng.normal(size=(8, 4)).astype(np.float32)
    kv = mx.kv.create("local")
    kv.init("emb", array(W))
    kv.set_optimizer(mx.optimizer.SGD(learning_rate=0.5, wd=0.1))
    g1 = sp.row_sparse_array((np.ones((2, 4), np.float32), [1, 3]),
                             shape=(8, 4))
    g2 = sp.row_sparse_array((np.full((2, 4), 2.0, np.float32), [3, 5]),
                             shape=(8, 4))
    kv.push("emb", [g1, g2])
    out = zeros((8, 4))
    kv.pull("emb", out=out)
    o = out.asnumpy()
    untouched = [0, 2, 4, 6, 7]
    # the lazy contract: untouched rows skip weight decay entirely
    assert np.array_equal(o[untouched], W[untouched])
    for r, gv in ((1, 1.0), (3, 3.0), (5, 2.0)):
        assert np.allclose(o[r], W[r] - 0.5 * (gv + 0.1 * W[r]),
                           atol=1e-6)


def test_kvstore_row_sparse_pull_edge_cases():
    W = np.arange(12, dtype=np.float32).reshape(6, 2)
    kv = mx.kv.create("local")
    kv.init("emb", array(W))
    out = zeros((6, 2))
    # duplicate row ids: dedup'd, idempotent mask
    kv.row_sparse_pull("emb", out=out,
                       row_ids=array(np.array([4, 4, 1, 1], np.int64)))
    o = out.asnumpy()
    assert np.array_equal(o[1], W[1]) and np.array_equal(o[4], W[4])
    assert not o[[0, 2, 3, 5]].any()
    # empty id list: a legitimate all-zero pull
    kv.row_sparse_pull("emb", out=out,
                       row_ids=array(np.zeros(0, np.int64)))
    assert not out.asnumpy().any()
    # out-of-range (incl. negative, which must not wrap) raises
    for bad in ([6], [-1]):
        with pytest.raises(MXNetError):
            kv.row_sparse_pull("emb", out=out,
                               row_ids=array(np.array(bad, np.int64)))
    # mismatched key/out/row_ids arity raises
    with pytest.raises(MXNetError):
        kv.row_sparse_pull(["emb"], out=[[out, out]],
                           row_ids=[[array(np.array([1], np.int64))] * 3])
    with pytest.raises(MXNetError):
        kv.row_sparse_pull(["emb", "ghost"], out=[[out], [out]],
                           row_ids=[array(np.array([1], np.int64))])


# -- layout: wire accounting + checkpoint ownership --------------------------

def test_layout_wire_accounting_scales_with_unique_not_vocab():
    small = EmbeddingLayout(100, 8, 4, unique=16, n_states=0)
    big = EmbeddingLayout(100_000, 8, 4, unique=16, n_states=0)
    w_small = small.wire_bytes_per_step("sparse", 4, mlp_bytes=0)
    w_big = big.wire_bytes_per_step("sparse", 4, mlp_bytes=0)
    assert w_small == w_big                        # vocab-independent
    d_small = small.wire_bytes_per_step("dense", 4, mlp_bytes=0)
    d_big = big.wire_bytes_per_step("dense", 4, mlp_bytes=0)
    assert d_big > 500 * d_small                   # table-sized
    # fp8 wire: narrower values + per-row scales, still < fp32 sparse
    w_fp8 = small.wire_bytes_per_step("sparse", 1, mlp_bytes=0)
    assert w_fp8 < w_small


def test_layout_ownership_covers_table_and_mlp():
    lay = EmbeddingLayout(100, 8, 4, unique=16, n_states=2)
    own = lay.ownership(["mlp_w0", "mlp_b0"])
    assert own["param:embed"] == 0
    assert own["opt:embed:0"] == 0 and own["opt:embed:1"] == 0
    assert set(own) == {"param:embed", "opt:embed:0", "opt:embed:1",
                        "param:mlp_w0", "opt:mlp_w0:0", "opt:mlp_w0:1",
                        "param:mlp_b0", "opt:mlp_b0:0", "opt:mlp_b0:1"}
    assert all(0 <= r < 4 for r in own.values())


# -- the fused step: exchange parity + checkpoint round-trip -----------------

def _trainer(exchange, vocab=64, batch=16, slots=4, cap=None, n_dev=8):
    return EmbeddingTrainer(
        _mesh(n_dev), vocab=vocab, embed_dim=8, n_slots=slots, dense_dim=4,
        mlp_hidden=(16,), optimizer="sgd", learning_rate=0.2,
        momentum=0.9, wd=0.01, rescale_grad=1.0 / batch,
        exchange=exchange, compress="none", unique_cap=cap,
        batch_size=batch)


def test_sparse_dense_bit_identity_all_rows_touched():
    """Permutation data (every row touched exactly once globally) makes
    bit-identity well-posed: one contribution per row, exact zeros
    elsewhere, same rows_* kernels in both modes — fp32 states must
    match bit for bit."""
    ids, dense, y = _permutation_data(64, 16, 4, 4, seed=3)
    states, losses = {}, {}
    for mode in ("sparse", "dense"):
        tr = _trainer(mode)
        st = tr.init_state(16, seed=1)
        for _ in range(3):
            st, loss, _ = tr.step(st, tr.shard_inputs([ids, dense, y]))
        states[mode] = tr.export_training_state(st)[0]
        losses[mode] = float(np.asarray(loss))
    assert losses["sparse"] == losses["dense"]
    for name in states["sparse"]:
        assert np.array_equal(states["sparse"][name],
                              states["dense"][name]), name


@pytest.mark.parametrize("mode", ["sparse", "dense"])
def test_step_independent_of_device_count(mode):
    """The global-batch gradient is one sum however many devices share
    it: two steps on 8 devices match the same steps on 1 device. (A
    gradient of a replicated input taken inside shard_map arrives
    already psum'd; summing it again counted it n_dev times.)"""
    ids, dense, y = _permutation_data(64, 16, 4, 4, seed=5)
    states = {}
    for n_dev in (1, 8):
        tr = _trainer(mode, n_dev=n_dev)
        st = tr.init_state(16, seed=1)
        for _ in range(2):
            st, _, _ = tr.step(st, tr.shard_inputs([ids, dense, y]))
        states[n_dev] = tr.export_training_state(st)[0]
    for name, ref in states[1].items():
        np.testing.assert_allclose(states[8][name], ref, rtol=1e-5,
                                   atol=1e-6, err_msg=name)


def test_export_import_roundtrip_across_cap_change():
    from mxnet_tpu.checkpoint.state import TrainingState, state_sha256
    ids, dense, y = _permutation_data(64, 16, 4, 4, seed=4)
    tr = _trainer("sparse")
    st = tr.init_state(16, seed=2)
    st, _, _ = tr.step(st, tr.shard_inputs([ids, dense, y]))
    arrays, meta = tr.export_training_state(st)
    sha0 = state_sha256(TrainingState(arrays, meta={"trainer": meta}))
    # resume under a different unique cap: full arrays carry no layout
    tr2 = _trainer("sparse", cap=32)
    st2 = tr2.import_training_state(arrays, meta)
    arrays2, meta2 = tr2.export_training_state(st2)
    sha1 = state_sha256(TrainingState(arrays2, meta={"trainer": meta2}))
    assert sha0 == sha1
    # the ownership map rides meta for sharded checkpoint commits
    assert meta["embed"]["ownership"]["param:embed"] == 0
    # and the merged-ownership reader picks it up
    from mxnet_tpu.checkpoint.manager import CheckpointManager
    own = CheckpointManager._zero_ownership(
        TrainingState(arrays, meta={"trainer": meta}))
    assert own and own["param:embed"] == 0


def test_import_into_fresh_trainer_then_step_matches():
    """Regression: importing a checkpoint into a trainer that never ran
    init_state must NOT freeze the dedup layout at a tiny unique cap
    (the import-path fallback once cached unique=n_slots, silently
    truncating every later step's touched-row list). The resumed
    trainer's next step must be bit-identical to the original's."""
    ids, dense, y = _permutation_data(64, 16, 4, 4, seed=6)
    tr = _trainer("sparse")
    st = tr.init_state(16, seed=3)
    st, _, _ = tr.step(st, tr.shard_inputs([ids, dense, y]))
    arrays, meta = tr.export_training_state(st)

    tr2 = _trainer("sparse")          # fresh: no init_state before import
    st2 = tr2.import_training_state(arrays, meta)
    # the cap-correct layout is only built at the first step
    ids2, dense2, y2 = _permutation_data(64, 16, 4, 4, seed=7)
    st, loss1, _ = tr.step(st, tr.shard_inputs([ids2, dense2, y2]))
    st2, loss2, _ = tr2.step(st2, tr2.shard_inputs([ids2, dense2, y2]))
    assert float(loss1) == float(loss2)
    a1, _ = tr.export_training_state(st)
    a2, _ = tr2.export_training_state(st2)
    for k in a1:
        assert np.array_equal(np.asarray(a1[k]), np.asarray(a2[k])), k
