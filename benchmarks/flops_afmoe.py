"""Operations and bytes of an `afmoe` model's mechanisms (grouped-query
attention over a sliding window in three layers of four and over every
earlier key in the fourth, with an output gate; a sigmoid-scored mixture
with one shared expert; an untied head), from shapes alone (never from
XLA's cost_analysis, and the same whatever implements a mechanism).

Counts are multiply-adds per token of one forward pass unless a name says
otherwise; a training step is forward + backward = 3 x forward, and what a
backward pass recomputes is not counted. `config` is the configuration file
as run (its `num_experts` is the number held here, `published` holds the
uncut counts; `layer_types` and `num_dense_layers` describe the layers that
are kept). Read through `op_tables.table(config)`.
"""

# the scopes of the windowed layers' attention: the operator (its products,
# head norms, rotation and gate) and its flash kernels
WINDOW_SCOPES = ("mx.swa", "mx.flash_attention.window")


def _widths(config):
    kinds = config["layer_types"][:config["num_hidden_layers"]]
    return {
        "d": config["hidden_size"], "h": config["num_attention_heads"],
        "hkv": config["num_key_value_heads"], "dh": config["head_dim"],
        "window": config["sliding_window"],
        "w": config["moe_intermediate_size"],
        "shared": config["num_shared_experts"],
        "f": config["intermediate_size"],
        "held": config["num_experts"],
        "experts": config["published"]["num_experts"],
        "top_k": config["num_experts_per_tok"],
        "vocab": config["vocab_size"], "seq": config["sequence_length"],
        "layers": len(kinds),
        "sliding": kinds.count("sliding_attention"),
        "full": kinds.count("full_attention"),
        "dense": min(config["num_dense_layers"], len(kinds)),
    }


# -- parameters -----------------------------------------------------------------

def attention_params(config):
    """wq, the gate's W_g and wo at every query head, wk and wv at the k/v
    heads, the two head norms' scales."""
    s = _widths(config)
    return (3 * s["d"] * s["h"] * s["dh"] + 2 * s["d"] * s["hkv"] * s["dh"]
            + 2 * s["dh"])


def expert_params(config):
    s = _widths(config)
    return 3 * s["d"] * s["w"]


def shared_params(config):
    return _widths(config)["shared"] * expert_params(config)


def router_params(config):
    """The router's weight and its selection bias."""
    s = _widths(config)
    return s["d"] * s["experts"] + s["experts"]


def dense_mlp_params(config):
    s = _widths(config)
    return 3 * s["d"] * s["f"]


def _norms(config):
    """The four norms of a layer: before and after each sub-layer."""
    return 4 * _widths(config)["d"]


def dense_layer_params(config):
    return attention_params(config) + dense_mlp_params(config) + \
        _norms(config)


def expert_layer_params(config):
    s = _widths(config)
    return (attention_params(config) + router_params(config)
            + shared_params(config) + s["held"] * expert_params(config)
            + _norms(config))


def head_params(config):
    """The embedding, the untied head and the final norm."""
    s = _widths(config)
    return 2 * s["vocab"] * s["d"] + s["d"]


def total_params(config):
    """Parameters held on this chip."""
    s = _widths(config)
    return (s["dense"] * dense_layer_params(config)
            + (s["layers"] - s["dense"]) * expert_layer_params(config)
            + head_params(config))


# -- multiply-adds per token, forward ---------------------------------------------

def mean_keys(config, window):
    """Keys a query sees on average over the sequence when it sees
    min(i + 1, window) of them at position i."""
    s, w = config["sequence_length"], min(window, config["sequence_length"])
    return (w * (w + 1) // 2 + (s - w) * w) / s


def core_macs(config, window):
    """Softmax attention per token: a score and a value of `dh` each, every
    query head, over `mean_keys`."""
    s = _widths(config)
    return mean_keys(config, window) * s["h"] * 2 * s["dh"]


def window_core_macs(config):
    return core_macs(config, _widths(config)["window"])


def full_core_macs(config):
    return core_macs(config, config["sequence_length"])


def attention_product_macs(config):
    """The five products of a layer's attention (the head norms, the
    rotation and the gate's sigmoid are no multiply-adds of a product)."""
    return attention_params(config) - 2 * _widths(config)["dh"]


def routed_expert_macs(config):
    """Per token, on average: top_k of the experts, of which held/experts
    live here."""
    s = _widths(config)
    return s["top_k"] * s["held"] / s["experts"] * expert_params(config)


def macs_by_mechanism(config):
    """{mechanism: forward multiply-adds per token} over the kept layers."""
    s = _widths(config)
    moe_layers = s["layers"] - s["dense"]
    return {"attention_products": s["layers"] * attention_product_macs(config),
            "window_cores": s["sliding"] * window_core_macs(config),
            "full_cores": s["full"] * full_core_macs(config),
            "mlp": s["dense"] * dense_mlp_params(config),
            "shared": moe_layers * shared_params(config),
            "routed": moe_layers * routed_expert_macs(config),
            "router": moe_layers * s["d"] * s["experts"],
            "head": s["vocab"] * s["d"]}


def train_flops_per_sequence(config):
    """Floating-point operations of one trained sequence: 2 per
    multiply-add, forward + backward = 3 x forward."""
    per_token = sum(macs_by_mechanism(config).values())
    return 3 * 2 * per_token * config["sequence_length"]


# -- kernels: operations and bytes of one training step ----------------------------

def _core_step(config, tokens, layers, macs):
    """(flops, bytes) of `layers` attention cores over one step of
    `tokens`: q and the output at every query head, k and v once a group
    (the kernels stream a group's shared head), 2 bytes each, once forward;
    twice that backward."""
    s = _widths(config)
    forward = 2 * s["dh"] * (2 * s["h"] + 2 * s["hkv"])
    return 3 * 2 * macs * tokens * layers, 3 * forward * tokens * layers


def window_core_step(config, tokens):
    """The windowed layers' cores: the keys a query sees are min(i + 1,
    window), not the blocks the kernels visit."""
    return _core_step(config, tokens, _widths(config)["sliding"],
                      window_core_macs(config))


def full_core_step(config, tokens):
    return _core_step(config, tokens, _widths(config)["full"],
                      full_core_macs(config))
