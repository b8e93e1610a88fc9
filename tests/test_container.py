"""Reference .params container interop.

Byte-level pinning of the reference NDArray container (magic 0xF993fac9,
src/ndarray/ndarray.cc:1582-1808) plus round-trips: files this framework
writes are loadable by a reference-era reader and vice versa. Since the
reference's C++ loader can't run here, the format is pinned two ways:
(a) hand-assembled byte streams (built field-by-field from the C++
serializer source) load correctly, and (b) written files' headers are
asserted byte-for-byte against the C++-derived layout.
"""
import struct

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.ndarray import container


def _hand_assembled_v2_dense(arr):
    """Bytes the reference NDArray::Save (ndarray.cc:1588-1640) would
    write for a dense cpu float32 array, assembled independently of
    container.py's writer."""
    out = [struct.pack("<I", 0xF993FAC9),        # NDARRAY_V2_MAGIC
           struct.pack("<i", 0)]                 # kDefaultStorage
    out.append(struct.pack("<I", arr.ndim))      # TShape: uint32 ndim
    out.append(np.asarray(arr.shape, "<i8").tobytes())   # int64 dims
    out.append(struct.pack("<ii", 1, 0))         # Context {cpu, 0}
    out.append(struct.pack("<i", 0))             # kFloat32
    out.append(arr.astype("<f4").tobytes())
    return b"".join(out)


def _hand_assembled_file(arrays, names):
    out = [struct.pack("<QQ", 0x112, 0),         # kMXAPINDArrayListMagic
           struct.pack("<Q", len(arrays))]
    out += [_hand_assembled_v2_dense(a) for a in arrays]
    out.append(struct.pack("<Q", len(names)))
    for n in names:
        out.append(struct.pack("<Q", len(n)) + n.encode())
    return b"".join(out)


def test_load_reference_written_file(tmp_path):
    """A byte stream assembled straight from the C++ serializer layout
    (the 'reference-written .params') loads into correct arrays."""
    rng = np.random.RandomState(0)
    w = rng.normal(size=(4, 3)).astype(np.float32)
    b = rng.normal(size=(4,)).astype(np.float32)
    f = tmp_path / "ref.params"
    f.write_bytes(_hand_assembled_file([w, b], ["arg:fc_weight",
                                                "arg:fc_bias"]))
    loaded = mx.nd.load(str(f))
    np.testing.assert_array_equal(loaded["arg:fc_weight"].asnumpy(), w)
    np.testing.assert_array_equal(loaded["arg:fc_bias"].asnumpy(), b)


def test_written_file_is_byte_identical_to_reference_layout(tmp_path):
    """What nd.save writes IS the reference byte layout (not merely
    self-round-trippable)."""
    rng = np.random.RandomState(1)
    w = rng.normal(size=(2, 5)).astype(np.float32)
    f = tmp_path / "ours.params"
    mx.nd.save(str(f), {"w": mx.nd.array(w)})
    assert f.read_bytes() == _hand_assembled_file([w], ["w"])


def test_dense_dtype_roundtrip(tmp_path):
    """Every container type flag the substrate can hold round-trips
    (f64/i64 are not in the set: the jax substrate runs x64-disabled, so
    NDArrays never carry them — reference f64 files still LOAD, value-
    preserved into f32, see test_load_f64_reference_file)."""
    rng = np.random.RandomState(2)
    arrays = {
        "f32": rng.normal(size=(3, 2)).astype(np.float32),
        "f16": rng.normal(size=(2, 2)).astype(np.float16),
        "u8": rng.randint(0, 255, (5,)).astype(np.uint8),
        "i32": rng.randint(-9, 9, (3,)).astype(np.int32),
        "i8": rng.randint(-9, 9, (3,)).astype(np.int8),
    }
    f = str(tmp_path / "all.params")
    mx.nd.save(f, {k: mx.nd.array(v, dtype=v.dtype)
                   for k, v in arrays.items()})
    loaded = mx.nd.load(f)
    for k, v in arrays.items():
        assert loaded[k].asnumpy().dtype == v.dtype, k
        np.testing.assert_array_equal(loaded[k].asnumpy(), v)


def test_load_f64_reference_file(tmp_path):
    """A reference-written float64 blob (type flag 1) loads with values
    intact (held as f32 on the x64-disabled substrate)."""
    arr = np.array([[1.5, -2.25], [0.5, 4.0]])
    blob = (struct.pack("<I", 0xF993FAC9) + struct.pack("<i", 0)
            + struct.pack("<I", 2) + np.asarray([2, 2], "<i8").tobytes()
            + struct.pack("<ii", 1, 0) + struct.pack("<i", 1)  # kFloat64
            + arr.astype("<f8").tobytes())
    f = tmp_path / "f64.params"
    f.write_bytes(struct.pack("<QQQ", 0x112, 0, 1) + blob
                  + struct.pack("<QQ", 1, 1) + b"w")
    loaded = mx.nd.load(str(f))
    np.testing.assert_array_equal(loaded["w"].asnumpy(),
                                  arr.astype(np.float32))


def test_list_form_roundtrip(tmp_path):
    f = str(tmp_path / "list.params")
    mx.nd.save(f, [mx.nd.ones((2, 2)), mx.nd.zeros((3,))])
    loaded = mx.nd.load(f)
    assert isinstance(loaded, list) and len(loaded) == 2
    np.testing.assert_array_equal(loaded[0].asnumpy(), np.ones((2, 2)))


def test_sparse_roundtrip(tmp_path):
    """row_sparse and csr arrays keep the reference aux layout
    (ndarray.cc:1597-1650: storage shape + int64 aux arrays)."""
    from mxnet_tpu.ndarray import sparse
    rs = sparse.row_sparse_array(
        (np.arange(6, dtype=np.float32).reshape(2, 3), np.array([1, 3])),
        shape=(5, 3))
    cs = sparse.csr_matrix(
        (np.array([1.0, 2.0, 3.0], np.float32), np.array([0, 2, 1]),
         np.array([0, 2, 3])), shape=(2, 4))
    f = str(tmp_path / "sparse.params")
    mx.nd.save(f, {"rs": rs, "cs": cs})
    loaded = mx.nd.load(f)
    assert loaded["rs"].stype == "row_sparse"
    assert loaded["cs"].stype == "csr"
    np.testing.assert_array_equal(loaded["rs"].tostype("default").asnumpy(),
                                  rs.tostype("default").asnumpy())
    np.testing.assert_array_equal(loaded["cs"].tostype("default").asnumpy(),
                                  cs.tostype("default").asnumpy())


def test_legacy_v1_and_prev1_load(tmp_path):
    """Pre-V2 blobs: V1 (magic 0xF993fac8, int64 dims) and pre-V1 (magic
    IS ndim, uint32 dims) — ndarray.cc:1655-1697 LegacyLoad."""
    arr = np.arange(6, dtype=np.float32).reshape(2, 3)
    v1 = (struct.pack("<I", 0xF993FAC8) + struct.pack("<I", 2)
          + np.asarray([2, 3], "<i8").tobytes()
          + struct.pack("<ii", 1, 0) + struct.pack("<i", 0)
          + arr.astype("<f4").tobytes())
    pre = (struct.pack("<I", 2) + np.asarray([2, 3], "<u4").tobytes()
           + struct.pack("<ii", 1, 0) + struct.pack("<i", 0)
           + arr.astype("<f4").tobytes())
    for blob, tag in ((v1, "v1"), (pre, "prev1")):
        f = tmp_path / f"{tag}.params"
        f.write_bytes(struct.pack("<QQQ", 0x112, 0, 1) + blob
                      + struct.pack("<Q", 1)
                      + struct.pack("<Q", 1) + b"w")
        loaded = mx.nd.load(str(f))
        np.testing.assert_array_equal(loaded["w"].asnumpy(), arr)


def test_checkpoint_roundtrip_through_module(tmp_path):
    """End-to-end criterion: a symbol-JSON + .params pair
    written by this framework loads back and serves inference — the
    .params being the reference binary container."""
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=4, name="fc")
    sym = mx.sym.SoftmaxOutput(net, name="softmax")
    mod = mx.mod.Module(sym, context=mx.cpu(0))
    it = mx.io.NDArrayIter(np.random.RandomState(0).normal(
        size=(32, 6)).astype(np.float32),
        np.zeros(32, np.float32), batch_size=16,
        label_name="softmax_label")
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(mx.init.Xavier())
    prefix = str(tmp_path / "ckpt")
    mod.save_checkpoint(prefix, 1)
    # the .params file is a genuine reference container
    with open(prefix + "-0001.params", "rb") as fh:
        head = fh.read(8)
    assert container.is_container(head)
    mod2 = mx.mod.Module.load(prefix, 1, context=mx.cpu(0))
    mod2.bind(data_shapes=it.provide_data, label_shapes=it.provide_label,
              for_training=False)
    it.reset()
    out1 = mod.predict(it).asnumpy()
    it.reset()
    out2 = mod2.predict(it).asnumpy()
    np.testing.assert_allclose(out1, out2, rtol=1e-6)


def test_npz_backcompat(tmp_path):
    """Files written by rounds 1-4 (npz) still load."""
    f = str(tmp_path / "old.params")
    np.savez(f, **{"arg:w": np.ones((2, 2), np.float32)})
    import os
    os.replace(f + ".npz", f)
    loaded = mx.nd.load(f)
    np.testing.assert_array_equal(loaded["arg:w"].asnumpy(),
                                  np.ones((2, 2)))


def test_truncated_and_bad_magic_error(tmp_path):
    f = tmp_path / "bad.params"
    f.write_bytes(struct.pack("<QQQ", 0x112, 0, 3))  # claims 3 arrays
    with pytest.raises(mx.MXNetError, match="truncated"):
        mx.nd.load(str(f))


def test_unknown_dtype_flag_errors(tmp_path):
    """A newer-reference dtype flag (bfloat16=12) must fail loudly, not
    misparse as float64 garbage."""
    blob = (struct.pack("<I", 0xF993FAC9) + struct.pack("<i", 0)
            + struct.pack("<I", 1) + np.asarray([2], "<i8").tobytes()
            + struct.pack("<ii", 1, 0) + struct.pack("<i", 12)
            + b"\x00" * 4)
    f = tmp_path / "newdtype.params"
    f.write_bytes(struct.pack("<QQQ", 0x112, 0, 1) + blob
                  + struct.pack("<QQ", 1, 1) + b"w")
    with pytest.raises(mx.MXNetError, match="dtype flag 12"):
        mx.nd.load(str(f))
