"""An `ouro` language model (Ouro-2.6B) as the system builds it:
`gluon.nn.DecoderLM` from the configuration's keys (the stack of layers run
`total_ut_steps` times with the same weights, an exit gate after every pass
but the last; the family's sandwich norms and its attention without q/k
norms from its `model_type`), traced into a Symbol whose first output is
the (batch, sequence) loss `Module.fit` trains, the objective weighted by
where each position exits, and whose second is the exit distribution p
(batch, sequence, passes). The tests build the same class."""
import importlib.util
import os

PREFIX = "ouro_"
DATA, LABEL = "data", "label"
GATE = PREFIX + "exit_gate"


def model_config(config):
    """The keys `DecoderLM` and the reference read."""
    return {k: v for k, v in config.items()
            if k not in ("assumed", "notes", "rehearse", "deployment")}


def build(config, softmax=True):
    """Group(loss (B, S), p (B, S, passes)); `softmax` False gives the last
    pass's logits (B, S, vocabulary) instead. A program that builds no exit
    gate has no loop, and stops here."""
    import mxnet_tpu as mx
    net = mx.gluon.nn.DecoderLM(model_config(config), prefix=PREFIX)
    data = mx.sym.Variable(DATA)
    if not softmax:
        return net(data)
    sym = mx.sym.Group(list(net(data, mx.sym.Variable(LABEL))))
    if GATE not in sym.list_arguments():
        raise SystemExit(f"models/ouro.py: the program built no {GATE!r}: "
                         "this DecoderLM does not run total_ut_steps")
    return sym


def initializer():
    import mxnet_tpu as mx
    return mx.init.Normal(0.02)


def reference():
    """The plain float32 reference, kept beside this file (and imported
    from there by the tests)."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "ouro_reference.py")
    spec = importlib.util.spec_from_file_location("ouro_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def seeded_params(config, seed):
    """(reference's weights {name: numpy}, the same under the system's
    names). The symbol is built once first: a program that has no loop
    says so here, before 2 GB of weights are drawn."""
    build(config)
    ref = reference()
    params = ref.init_params(model_config(config), seed)
    return params, ref.system_params(params, PREFIX)
