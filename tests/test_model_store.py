"""Model-store sha1 plumbing + pretrained-zoo interop.

The end-to-end test writes a resnet18_v1 checkpoint in the REFERENCE
binary container format under the store's name-{shorthash} naming,
sha1-registers it, and loads it back through the public
`pretrained=True` path — proving the architecture definitions, the
container codec, and the verified store compose exactly the way a real
reference-pretrained download would.
"""
import hashlib
import logging
import os
import zipfile

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.gluon.model_zoo import vision
from mxnet_tpu.gluon.model_zoo.vision import model_store


def _sha1(path):
    h = hashlib.sha1()
    h.update(open(path, "rb").read())
    return h.hexdigest()


def test_short_hash_published_table():
    assert model_store.short_hash("resnet50_v1") == "c940b1a0"
    with pytest.raises(ValueError):
        model_store.short_hash("not_a_model")


def test_verified_cache_hit(tmp_path, monkeypatch):
    f = tmp_path / "models" / "tiny-00000000.params"
    f.parent.mkdir(parents=True)
    mx.nd.save(str(f), {"w": mx.nd.ones((2,))})
    sha = _sha1(str(f))
    monkeypatch.setitem(model_store._model_sha1, "tiny", sha)
    monkeypatch.setattr(model_store, "short_hash", lambda n: "00000000")
    assert model_store.get_model_file(
        "tiny", root=str(tmp_path / "models")) == str(f)


def test_unverified_local_fallback_warns(tmp_path, caplog):
    root = tmp_path / "models"
    root.mkdir()
    mx.nd.save(str(root / "resnet18_v1.params"), {"w": mx.nd.ones((2,))})
    with caplog.at_level(logging.WARNING):
        path = model_store.get_model_file("resnet18_v1", root=str(root))
    assert path.endswith("resnet18_v1.params")
    assert any("WITHOUT sha1" in r.message for r in caplog.records)


def test_file_repo_download_and_verify(tmp_path, monkeypatch):
    """MXNET_GLUON_REPO=file://... serves the reference zip layout
    offline; the fetched file is sha1-verified."""
    repo = tmp_path / "repo" / "gluon" / "models"
    repo.mkdir(parents=True)
    params = tmp_path / "tiny2-00000000.params"
    mx.nd.save(str(params), {"w": mx.nd.full((3,), 7.0)})
    with zipfile.ZipFile(repo / "tiny2-00000000.zip", "w") as zf:
        zf.write(params, "tiny2-00000000.params")
    sha = _sha1(str(params))
    monkeypatch.setitem(model_store._model_sha1, "tiny2", sha)
    monkeypatch.setattr(model_store, "short_hash", lambda n: "00000000")
    monkeypatch.setenv("MXNET_GLUON_REPO",
                       "file://" + str(tmp_path / "repo") + "/")
    root = tmp_path / "cache" / "models"
    got = model_store.get_model_file("tiny2", root=str(root))
    assert got == str(root / "tiny2-00000000.params")
    loaded = mx.nd.load(got)
    np.testing.assert_allclose(loaded["w"].asnumpy(), np.full((3,), 7.0))


def test_missing_errors_clearly(tmp_path):
    with pytest.raises(mx.MXNetError, match="resnet18_v1-e54b379f"):
        model_store.get_model_file("resnet18_v1",
                                   root=str(tmp_path / "empty"))


def test_pretrained_zoo_roundtrip(tmp_path, monkeypatch):
    """Full pretrained path: reference-container .params under store
    naming -> sha1 verify -> vision.resnet18_v1(pretrained=True) -> same
    logits as the source net."""
    src = vision.resnet18_v1()
    src.initialize(mx.init.Xavier())
    x = mx.nd.array(np.random.RandomState(0)
                    .uniform(0, 1, (1, 3, 32, 32)).astype(np.float32))
    ref_out = src(x).asnumpy()     # also materializes deferred shapes

    root = tmp_path / "models"
    root.mkdir()
    f = root / "resnet18_v1-00000000.params"
    src.save_parameters(str(f))
    # the saved checkpoint is a genuine reference container
    from mxnet_tpu.ndarray import container
    assert container.is_container(open(f, "rb").read(8))
    monkeypatch.setitem(model_store._model_sha1, "resnet18_v1",
                        _sha1(str(f)))
    monkeypatch.setattr(model_store, "short_hash", lambda n: "00000000")

    net = vision.resnet18_v1(pretrained=True, root=str(root))
    out = net(x).asnumpy()
    np.testing.assert_allclose(out, ref_out, rtol=1e-5, atol=1e-6)


def test_file_repo_missing_zip_gets_actionable_error(tmp_path, monkeypatch):
    """A file:// mirror without the zip must surface the curated message,
    not a raw FileNotFoundError."""
    monkeypatch.setitem(model_store._model_sha1, "tiny3", "0" * 40)
    monkeypatch.setattr(model_store, "short_hash", lambda n: "00000000")
    monkeypatch.setenv("MXNET_GLUON_REPO",
                       "file://" + str(tmp_path / "nowhere") + "/")
    with pytest.raises(mx.MXNetError, match="MXNET_GLUON_REPO"):
        model_store.get_model_file("tiny3",
                                   root=str(tmp_path / "models"))
