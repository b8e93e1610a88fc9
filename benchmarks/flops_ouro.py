"""Operations of an `ouro` model's training step (one stack of dense layers
run `total_ut_steps` times with the same weights: causal multi-head
attention and a SwiGLU a layer; after every pass the final norm, the untied
head and, but for the last pass, the exit gate), from shapes alone (never
from XLA's cost_analysis, and the same whatever implements a mechanism).

Counts are multiply-adds per token of one forward pass unless a name says
otherwise; a training step is forward + backward = 3 x forward, and what a
backward pass recomputes is not counted. A parameter used T times costs its
products T times. `config` is the configuration file as run. The readers of
`mfu_ouro.train`, `loop_share.train` and `exit_share.train` import this
module by name.
"""

# the scopes of the work inside the loop (the passes' norms and residual
# adds, their attention with its rotation and flash kernels, their MLP) and
# of the work after each pass (the final norm, the head's cross-entropy,
# the exit gate and the objective over the passes); a reader sums each
# scope and those under it
LOOP_SCOPES = ("mx.loop", "mx.gqa", "mx.flash_attention", "mx.mlp")
EXIT_SCOPES = ("mx.lm_head", "mx.exit_gate")


def _widths(config):
    return {
        "d": config["hidden_size"], "h": config["num_attention_heads"],
        "hkv": config["num_key_value_heads"], "dh": config["head_dim"],
        "f": config["intermediate_size"], "vocab": config["vocab_size"],
        "seq": config["sequence_length"],
        "layers": config["num_hidden_layers"],
        "passes": config["total_ut_steps"],
    }


# -- parameters -----------------------------------------------------------------

def attention_params(config):
    """wq and wo at every query head, wk and wv at the k/v heads."""
    s = _widths(config)
    return 2 * s["d"] * s["h"] * s["dh"] + 2 * s["d"] * s["hkv"] * s["dh"]


def mlp_params(config):
    s = _widths(config)
    return 3 * s["d"] * s["f"]


def layer_params(config):
    """A layer: attention, the SwiGLU and four norms (before and after each
    sub-layer)."""
    return attention_params(config) + mlp_params(config) + \
        4 * _widths(config)["d"]


def head_params(config):
    """The embedding and the untied head."""
    s = _widths(config)
    return 2 * s["vocab"] * s["d"]


def exit_params(config):
    """The final norm and the exit gate, Linear(hidden -> 1) with bias."""
    s = _widths(config)
    return s["d"] + (s["d"] + 1 if s["passes"] > 1 else 0)


def total_params(config):
    """Parameters held on this chip."""
    return _widths(config)["layers"] * layer_params(config) + \
        head_params(config) + exit_params(config)


# -- multiply-adds per token, forward ---------------------------------------------

def mean_keys(config):
    """Keys a causal query sees on average over the sequence: (S + 1) / 2."""
    return (config["sequence_length"] + 1) / 2


def core_macs(config):
    """Softmax attention per token of one layer application: a score and
    a value of `dh` each, every query head, over `mean_keys`."""
    s = _widths(config)
    return mean_keys(config) * s["h"] * 2 * s["dh"]


def macs_by_mechanism(config):
    """{mechanism: forward multiply-adds per token}: the layers' products
    and attention cores once per (pass, layer) application, the head once
    per pass, the gate once per pass but the last."""
    s = _widths(config)
    applications = s["passes"] * s["layers"]
    return {"attention_products": applications * attention_params(config),
            "attention_cores": applications * core_macs(config),
            "mlp": applications * mlp_params(config),
            "head": s["passes"] * s["vocab"] * s["d"],
            "exit_gate": (s["passes"] - 1) * s["d"]}


def train_flops_per_sequence(config):
    """Floating-point operations of one trained sequence: 2 per
    multiply-add, forward + backward = 3 x forward."""
    per_token = sum(macs_by_mechanism(config).values())
    return 3 * 2 * per_token * config["sequence_length"]
