"""Native C++ runtime library tests (src/runtime_native.cc via ctypes).

Every native kernel is checked against its pure-python fallback — the
backend-parity discipline of SURVEY.md §4 applied to the host runtime.
"""
import os
import struct
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import _native, recordio
from mxnet_tpu import kvstore as kvs

pytestmark = pytest.mark.skipif(_native.lib() is None,
                                reason="no native toolchain")


def _write_rec(path, payloads):
    rec = recordio.MXRecordIO(str(path), "w")
    for p in payloads:
        rec.write(p)
    rec.close()


def test_scan_records_matches_python(tmp_path):
    payloads = [bytes([i]) * (5 + 7 * i) for i in range(10)]
    f = tmp_path / "a.rec"
    _write_rec(f, payloads)
    offs, lens = _native.scan_records(str(f))
    assert list(lens) == [len(p) for p in payloads]
    # python fallback agrees
    os.environ["MXNET_TPU_DISABLE_NATIVE"] = "1"
    try:
        import importlib
        # direct python walk (scan_record_positions falls through when
        # native is disabled in a fresh process; here compare via struct)
        poffs, plens = [], []
        with open(f, "rb") as fp:
            while True:
                pos = fp.tell()
                hdr = fp.read(8)
                if len(hdr) < 8:
                    break
                magic, lrec = struct.unpack("<II", hdr)
                assert magic == 0xced7230a
                n = lrec & ((1 << 29) - 1)
                poffs.append(pos + 8)
                plens.append(n)
                fp.seek((n + 3) & ~3, 1)
        assert list(offs) == poffs and list(lens) == plens
    finally:
        os.environ.pop("MXNET_TPU_DISABLE_NATIVE", None)


def test_read_records(tmp_path):
    payloads = [b"hello", b"world!!", b"x" * 100]
    f = tmp_path / "b.rec"
    _write_rec(f, payloads)
    offs, lens = _native.scan_records(str(f))
    got = _native.read_records(str(f), offs, lens)
    assert got == payloads
    # gather a subset out of order
    got2 = _native.read_records(str(f), offs[[2, 0]], lens[[2, 0]])
    assert got2 == [payloads[2], payloads[0]]


def test_scan_corrupt_raises(tmp_path):
    f = tmp_path / "bad.rec"
    f.write_bytes(b"\x00" * 32)
    with pytest.raises(IOError):
        _native.scan_records(str(f))


def test_indexed_recordio_without_idx(tmp_path):
    """MXIndexedRecordIO builds its seek table by scanning when no .idx."""
    payloads = [b"rec%d" % i for i in range(6)]
    f = tmp_path / "c.rec"
    _write_rec(f, payloads)
    rio = recordio.MXIndexedRecordIO(None, str(f), "r")
    assert rio.keys == list(range(6))
    assert rio.read_idx(4) == payloads[4]
    assert rio.read_idx(0) == payloads[0]


def test_native_2bit_matches_python():
    rng = np.random.RandomState(0)
    arr = rng.normal(0, 1, 999).astype(np.float32)
    res = rng.normal(0, 0.2, 999).astype(np.float32)
    thr = 0.5
    p_native, r_native = kvs.quantize_2bit(arr, res.copy(), thr)
    # force the numpy path
    os.environ["MXNET_TPU_DISABLE_NATIVE"] = "1"
    try:
        code = (
            "import numpy as np, os\n"
            "from mxnet_tpu import kvstore as kvs\n"
            "import sys\n"
            "arr = np.load(sys.argv[1])['arr']\n"
            "res = np.load(sys.argv[1])['res']\n"
            "p, r = kvs.quantize_2bit(arr, res, 0.5)\n"
            "d = kvs.dequantize_2bit(p, arr.size, 0.5)\n"
            "np.savez(sys.argv[2], p=p.view(np.uint32), r=r, d=d)\n"
        )
        import tempfile
        with tempfile.TemporaryDirectory() as td:
            inp = os.path.join(td, "in.npz")
            outp = os.path.join(td, "out.npz")
            np.savez(inp, arr=arr, res=res)
            env = dict(os.environ, JAX_PLATFORMS="cpu",
                       PYTHONPATH=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
            subprocess.run([sys.executable, "-c", code, inp, outp],
                           check=True, env=env, timeout=240)
            ref = np.load(outp)
            np.testing.assert_array_equal(p_native.view(np.uint32), ref["p"])
            np.testing.assert_allclose(r_native.ravel(), ref["r"].ravel(),
                                       rtol=1e-6)
            d_native = kvs.dequantize_2bit(p_native, arr.size, thr)
            np.testing.assert_array_equal(d_native, ref["d"])
    finally:
        os.environ.pop("MXNET_TPU_DISABLE_NATIVE", None)


def test_hwc_to_chw():
    rng = np.random.RandomState(1)
    img = rng.randint(0, 255, (7, 9, 3), np.uint8)
    mean = np.array([10.0, 20.0, 30.0], np.float32)
    std = np.array([2.0, 4.0, 8.0], np.float32)
    out = _native.hwc_u8_to_chw_f32(img, mean, std)
    want = (img.astype(np.float32) - mean) / std
    np.testing.assert_allclose(out, np.transpose(want, (2, 0, 1)),
                               rtol=1e-6)
    plain = _native.hwc_u8_to_chw_f32(img)
    np.testing.assert_allclose(plain,
                               np.transpose(img.astype(np.float32),
                                            (2, 0, 1)))


_jpeg = pytest.mark.skipif(not _native.has_jpeg(),
                           reason="native lib built without libjpeg")


def _write_img_rec(tmp_path, n=10, size=(40, 50), fmt=".jpg", label_width=1):
    rec_path = tmp_path / "d.rec"
    idx_path = tmp_path / "d.idx"
    rec = recordio.MXIndexedRecordIO(str(idx_path), str(rec_path), "w")
    yy = np.arange(size[0])[:, None, None]
    xx = np.arange(size[1])[None, :, None]
    cc = np.arange(3)[None, None, :]
    for i in range(n):
        # smooth gradients: JPEG decoders/resizers agree closely on these,
        # so parity tolerances stay tight (noise images would amplify
        # legitimate IDCT/bilinear implementation differences)
        img = ((yy * 3 + xx * 2 + cc * 40 + i * 17) % 256).astype(np.uint8)
        if label_width == 1:
            hdr = recordio.IRHeader(0, float(i), i, 0)
        else:
            hdr = recordio.IRHeader(label_width,
                                    np.arange(label_width, dtype=np.float32)
                                    + i, i, 0)
        rec.write_idx(i, recordio.pack_img(hdr, img, quality=95,
                                           img_fmt=fmt))
    rec.close()
    return str(rec_path), str(idx_path)


@_jpeg
def test_native_jpeg_decode_matches_python(tmp_path):
    rec_path, _ = _write_img_rec(tmp_path, n=1)
    raw = recordio.MXRecordIO(rec_path, "r").read()
    _, payload = recordio.unpack(raw)
    native = _native.jpeg_decode(payload)
    ref = recordio._decode_img(payload)
    if recordio.USES_CV2:
        ref = ref[..., ::-1]  # cv2 decodes BGR
    assert native.shape == ref.shape
    # different IDCT implementations may differ by a couple of levels
    assert np.abs(native.astype(int) - ref.astype(int)).mean() < 2.0


@_jpeg
def test_native_image_record_iter_matches_python(tmp_path):
    from mxnet_tpu.image.io import (ImageRecordIter, _NativeImageRecordIter,
                                    _RawImageRecordIter)
    rec_path, idx_path = _write_img_rec(tmp_path, n=10)
    it = ImageRecordIter(rec_path, (3, 32, 32), 4, path_imgidx=idx_path,
                         resize=36, preprocess_threads=2)
    assert isinstance(it, _NativeImageRecordIter)
    py = _RawImageRecordIter(path_imgrec=rec_path, path_imgidx=idx_path,
                             data_shape=(3, 32, 32), batch_size=4,
                             resize=36)
    for bi in range(3):
        nb = it.next()
        pb = py.next()
        assert nb.pad == pb.pad
        keep = 4 - nb.pad  # pad rows differ by design: native wraps to the
        # epoch head (reference round_batch), python repeats tail records
        np.testing.assert_allclose(nb.label[0].asnumpy()[:keep],
                                   pb.label[0].asnumpy()[:keep])
        nd_, pd_ = nb.data[0].asnumpy(), pb.data[0].asnumpy()
        assert nd_.shape == pd_.shape == (4, 3, 32, 32)
        # decoder + bilinear kernels differ slightly; compare content
        assert np.abs(nd_[:keep] - pd_[:keep]).mean() < 4.0
    for obj in (it, py):
        try:
            obj.close()
        except AttributeError:
            pass


@_jpeg
def test_native_iter_shuffle_deterministic(tmp_path):
    from mxnet_tpu.image.io import ImageRecordIter, _NativeImageRecordIter
    rec_path, idx_path = _write_img_rec(tmp_path, n=8, size=(32, 32))
    def labels_of(seed):
        it = ImageRecordIter(rec_path, (3, 32, 32), 4, shuffle=True,
                             seed=seed)
        assert isinstance(it, _NativeImageRecordIter)
        out = []
        for b in it:
            out.extend(b.label[0].asnumpy().tolist())
        it.close()
        return out
    a, b = labels_of(3), labels_of(3)
    assert a == b
    assert sorted(a) == list(range(8))
    assert labels_of(4) != a or labels_of(5) != a


@_jpeg
def test_native_iter_multilabel_and_parts(tmp_path):
    from mxnet_tpu.image.io import ImageRecordIter, _NativeImageRecordIter
    rec_path, _ = _write_img_rec(tmp_path, n=8, size=(32, 32),
                                 label_width=3)
    it = ImageRecordIter(rec_path, (3, 32, 32), 2, label_width=3,
                         num_parts=2, part_index=1)
    assert isinstance(it, _NativeImageRecordIter)
    batch = it.next()
    assert batch.label[0].shape == (2, 3)
    np.testing.assert_allclose(batch.label[0].asnumpy()[0],
                               [4.0, 5.0, 6.0])
    it.close()


@_jpeg
def test_non_jpeg_falls_back_to_python(tmp_path):
    from mxnet_tpu.image.io import ImageRecordIter, _NativeImageRecordIter
    rec_path, idx_path = _write_img_rec(tmp_path, n=4, size=(32, 32),
                                        fmt=".png")
    it = ImageRecordIter(rec_path, (3, 32, 32), 2, path_imgidx=idx_path)
    assert not isinstance(it, _NativeImageRecordIter)
    batch = it.next()
    assert batch.data[0].shape == (2, 3, 32, 32)


@_jpeg
def test_native_pipe_more_workers_than_buffers(tmp_path):
    # regression: workers used to claim a batch seq BEFORE acquiring a
    # buffer; with every buffer holding a batch ahead of the in-order
    # delivery point the pipeline deadlocked (buffers < workers makes the
    # out-of-order window easy to hit). A slow consumer widens it.
    import time
    rec_path, _ = _write_img_rec(tmp_path, n=40, size=(32, 32))
    offs, lens = _native.scan_records(rec_path)
    pipe = _native.NativeImagePipe(rec_path, offs, lens, batch=2,
                                   data_shape=(3, 32, 32), nthreads=4,
                                   depth=2, seed=0)
    for epoch in range(2):
        pipe.reset(np.arange(40))
        seen = 0
        while True:
            out = pipe.next()
            if out is None:
                break
            seen += 1
            time.sleep(0.005)
        assert seen == 20
    pipe.close()


def test_cpp_unit_harness(tmp_path):
    """Build and run the native-side unit tests (tests/cpp tier of the
    reference, SURVEY.md §4) — exercises the C ABI from C++ with no
    python in the loop."""
    src_dir = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    exe = tmp_path / "native_test"
    cmd = ["g++", "-O2", "-std=c++17", "-DMXIO_HAS_JPEG",
           os.path.join(src_dir, "runtime_native_test.cc"),
           os.path.join(src_dir, "runtime_native.cc"),
           "-ljpeg", "-lpthread", "-o", str(exe)]
    build = subprocess.run(cmd, capture_output=True, text=True, timeout=180)
    if build.returncode != 0:
        cmd = [c for c in cmd if c not in ("-DMXIO_HAS_JPEG", "-ljpeg")]
        build = subprocess.run(cmd, capture_output=True, text=True,
                               timeout=180)
    assert build.returncode == 0, build.stderr[-2000:]
    run = subprocess.run([str(exe), str(tmp_path)], capture_output=True,
                         text=True, timeout=120)
    assert run.returncode == 0, (run.stdout + run.stderr)[-2000:]
    assert "ALL NATIVE TESTS PASSED" in run.stdout


@_jpeg
def test_uint8_output_mode_matches_f32(tmp_path):
    """output_dtype='uint8' (beyond-reference, r5): raw CHW bytes equal
    the f32 pipeline's values exactly when no mean/std is applied — the
    4x-smaller payload for the ship-bytes/normalize-on-device regime."""
    from mxnet_tpu.image.io import ImageRecordIter, _NativeImageRecordIter
    rec_path, idx_path = _write_img_rec(tmp_path, n=8)
    u8 = ImageRecordIter(rec_path, (3, 32, 32), 4, resize=36,
                         preprocess_threads=2, output_dtype="uint8")
    f32 = ImageRecordIter(rec_path, (3, 32, 32), 4, resize=36,
                          preprocess_threads=2)
    assert isinstance(u8, _NativeImageRecordIter)
    for _ in range(2):
        bu, bf = u8.next(), f32.next()
        du = bu.data[0].asnumpy()
        assert du.dtype == np.uint8
        np.testing.assert_array_equal(du.astype(np.float32),
                                      bf.data[0].asnumpy())
        np.testing.assert_array_equal(bu.label[0].asnumpy(),
                                      bf.label[0].asnumpy())


@_jpeg
def test_uint8_mode_rejects_host_norm(tmp_path):
    from mxnet_tpu.image.io import ImageRecordIter
    rec_path, _ = _write_img_rec(tmp_path, n=4)
    with pytest.raises(Exception, match="normalize on device"):
        ImageRecordIter(rec_path, (3, 32, 32), 4, mean=True, std=True,
                        output_dtype="uint8")


def test_trainer_input_preproc_device_norm():
    """DataParallelTrainer(input_preproc=...): uint8 batches normalized
    INSIDE the compiled step match host-normalized f32 training."""
    import jax.numpy as jnp
    from mxnet_tpu.parallel import data_parallel_mesh, DataParallelTrainer

    data = mx.sym.Variable("data")
    f1 = mx.sym.FullyConnected(mx.sym.Flatten(data), num_hidden=8,
                               name="fc1")
    sym = mx.sym.SoftmaxOutput(f1, name="softmax")
    mesh = data_parallel_mesh(1)
    rng = np.random.RandomState(0)
    xu8 = rng.randint(0, 255, (8, 3, 4, 4)).astype(np.uint8)
    y = rng.randint(0, 8, (8,)).astype(np.float32)
    mean = np.float32(120.0)
    scale = np.float32(1 / 64.0)

    def preproc(name, v):
        if name == "data":
            return (v.astype(jnp.float32) - mean) * scale
        return v

    import jax
    key = jax.random.PRNGKey(0)
    t1 = DataParallelTrainer(sym, mesh, learning_rate=0.1,
                             rescale_grad=1.0 / 8, input_preproc=preproc)
    p1, s1, a1 = t1.init_state({"data": (8, 3, 4, 4),
                                "softmax_label": (8,)})
    p1, s1, a1, l1, _ = t1.step(p1, s1, a1,
                                t1.shard_inputs([xu8, y]), rng=key)

    t2 = DataParallelTrainer(sym, mesh, learning_rate=0.1,
                             rescale_grad=1.0 / 8)
    p2, s2, a2 = t2.init_state({"data": (8, 3, 4, 4),
                                "softmax_label": (8,)})
    xf = (xu8.astype(np.float32) - 120.0) / 64.0
    p2, s2, a2, l2, _ = t2.step(p2, s2, a2,
                                t2.shard_inputs([xf, y]), rng=key)
    for a, b in zip(p1, p2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
