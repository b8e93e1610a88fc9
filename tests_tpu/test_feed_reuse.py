"""On the chip: a transfer in flight never reads a refilled host buffer.

`pipeline.BlockStager` stacks every block into one of two host buffers it
reuses, and `jax.device_put` returns before the TPU runtime has read the
host array (it linearizes and transfers on threads of its own). Twelve
blocks of `resnet50.train`'s shape (4 x 256 x 3 x 224 x 224 fp32, 616 MB)
go from a source that overwrites its one scratch array at every pull,
through the real `DataParallelTrainer.shard_inputs`, staged back to back
with nothing in between, and come back from the device equal to the true
batches."""
import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import pipeline
from mxnet_tpu.parallel.dp import DataParallelTrainer
from mxnet_tpu.parallel.mesh import mesh_for_contexts

K, BATCH, BLOCKS = 4, 256, 12


def test_back_to_back_blocks_survive_the_round_trip():
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=4,
                              name="fc"), name="softmax")
    trainer = DataParallelTrainer(net, mesh_for_contexts([mx.tpu(0)]))
    rng = np.random.default_rng(25)
    base = rng.standard_normal((K, BATCH, 3, 224, 224), dtype="float32")
    labels = rng.integers(0, 1000, (K, BATCH)).astype("float32")
    scratch_x, scratch_y = np.empty_like(base), np.empty_like(labels)

    def source():
        for n in range(BLOCKS):
            np.add(base, n, out=scratch_x)
            np.add(labels, n, out=scratch_y)
            yield list(scratch_x), list(scratch_y)

    pipeline.reset_stats()
    stager = pipeline.BlockStager(trainer.shard_inputs)
    staged = [stager(columns, stacked=True) for columns in source()]
    scratch_x.fill(np.nan)
    for n, (x, y) in enumerate(staged):
        assert x.devices() == {mx.tpu(0).jax_device()}
        np.testing.assert_array_equal(np.asarray(y), labels + n)
        got = np.asarray(x)
        assert np.array_equal(got, base + np.float32(n)), \
            f"block {n} of {BLOCKS} came back with other bytes"
    stats = pipeline.stats()
    assert stats["feed_stack_allocs"] == 4          # two sets x two columns
    assert stats["feed_stack_reuses"] == 2 * BLOCKS - 4
    assert stats["feed_staged_bytes"] == BLOCKS * (base.nbytes + labels.nbytes)
