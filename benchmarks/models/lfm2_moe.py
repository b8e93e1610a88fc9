"""An `lfm2_moe` language model (LFM2-8B-A1B) as the system builds it:
`gluon.nn.DecoderLM` from the configuration's keys (the mixer of each layer
from `layer_types`, the head tied to the embedding), traced into a Symbol
whose first output is the (batch, sequence) loss `Module.fit` trains and
whose second is the mixture layers' stacked load counts. The tests build
the same class."""
import importlib.util
import os

PREFIX = "lfm2_"
DATA, LABEL = "data", "label"


def model_config(config):
    """The keys `DecoderLM` and the reference read: the file's
    `num_experts` is the number held here, the router keeps the published
    width."""
    cfg = {k: v for k, v in config.items()
           if k not in ("assumed", "notes", "rehearse", "deployment")}
    first = int(config["first_expert_held"])
    cfg["experts_held"] = [first, int(config["num_experts"])]
    cfg["num_experts"] = int(config["published"]["num_experts"])
    return cfg


def build(config, softmax=True):
    """Group(loss (B, S), mixture stats (layers, held + 3)); `softmax`
    False gives the logits (B, S, vocabulary) instead."""
    import mxnet_tpu as mx
    net = mx.gluon.nn.DecoderLM(model_config(config), prefix=PREFIX)
    data = mx.sym.Variable(DATA)
    if not softmax:
        return net(data)
    return mx.sym.Group(list(net(data, mx.sym.Variable(LABEL))))


def initializer():
    import mxnet_tpu as mx
    return mx.init.Normal(0.02)


def reference():
    """The plain float32 reference, a copy of
    tests/reference_models/lfm2_moe.py."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "lfm2_moe_reference.py")
    spec = importlib.util.spec_from_file_location("lfm2_moe_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def seeded_params(config, seed):
    """(reference's weights {name: numpy}, the same under the system's
    names and layouts). The symbol is built once first: a program that has
    no such layers says so here, before 2 GB of weights are drawn."""
    build(config)
    ref = reference()
    params = ref.init_params(model_config(config), seed)
    return params, ref.system_params(params, PREFIX)
