"""gluon model-zoo ResNet v1 (He et al. 2015) traced into a Symbol."""


def build(config, softmax=True):
    """The symbol of `config["zoo_name"]`, full depth and widths, under a
    fixed prefix so that training, export and the checks name its weights
    alike. With `softmax`, the SoftmaxOutput head `Module.fit` trains."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import vision
    net = getattr(vision, config["zoo_name"])(
        classes=config["num_classes"], prefix="resnetv1_")
    out = net(mx.sym.Variable("data"))
    return mx.sym.SoftmaxOutput(out, name="softmax") if softmax else out


def initializer():
    import mxnet_tpu as mx
    return mx.init.Xavier(rnd_type="gaussian", factor_type="in", magnitude=2)
