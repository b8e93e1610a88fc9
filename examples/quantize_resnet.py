#!/usr/bin/env python
"""INT8 post-training quantization of a model-zoo ResNet.

Role of the reference's quantization example (python/mxnet/contrib/
quantization.py usage): calibrate on sample batches, compare int8 vs fp32
outputs.

  python examples/quantize_resnet.py [--calib naive|entropy|none]
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import mxnet_tpu as mx
from mxnet_tpu.contrib import quantization as qz


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--calib", default="naive",
                    choices=("none", "naive", "entropy"))
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--ctx", default="cpu", choices=("cpu", "tpu"))
    args = ap.parse_args()
    ctx = mx.tpu() if args.ctx == "tpu" else mx.cpu()

    # small conv net (gluon.model_zoo.vision.resnet50_v1 for the full model)
    data = mx.sym.Variable("data")
    net = mx.sym.Convolution(data, kernel=(3, 3), num_filter=16, pad=(1, 1),
                             name="c1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.Pooling(net, kernel=(2, 2), stride=(2, 2), pool_type="max")
    net = mx.sym.Convolution(net, kernel=(3, 3), num_filter=32, pad=(1, 1),
                             name="c2")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.Pooling(net, global_pool=True, pool_type="avg")
    net = mx.sym.FullyConnected(mx.sym.Flatten(net), num_hidden=10,
                                name="fc")
    sym = mx.sym.softmax(net)

    rng = np.random.RandomState(0)
    shape = (args.batch, 3, 32, 32)
    shapes, _, _ = sym.infer_shape(data=shape)
    arg_params = {n: mx.nd.array(rng.normal(0, 0.2, s).astype(np.float32))
                  for n, s in zip(sym.list_arguments(), shapes)
                  if n != "data"}
    x = rng.normal(0, 1, shape).astype(np.float32)
    calib = mx.io.NDArrayIter(x, batch_size=args.batch, label_name=None)

    qsym, qargs, _ = qz.quantize_model(
        sym, arg_params, {}, ctx=ctx, calib_mode=args.calib,
        calib_data=(calib if args.calib != "none" else None),
        num_calib_examples=args.batch)

    def run(s, params):
        ex = s.simple_bind(ctx, grad_req="null", data=shape)
        for kk, vv in params.items():
            if kk in ex.arg_dict:
                ex.arg_dict[kk][:] = vv
        ex.arg_dict["data"][:] = x
        return ex.forward(is_train=False)[0].asnumpy()

    fp = run(sym, arg_params)
    q8 = run(qsym, qargs)
    err = np.abs(fp - q8).max()
    agree = (fp.argmax(1) == q8.argmax(1)).mean()
    nq = sum(1 for n in qsym._topo() if n.op is not None and
             n.op.name.startswith("_contrib_quantized"))
    print(f"{nq} quantized nodes; max prob err {err:.4f}; "
          f"top-1 agreement {agree:.2f}")
    return 0 if err < 0.1 else 1


if __name__ == "__main__":
    sys.exit(main())
