"""Operations and bytes of an `lfm2_moe` model's mechanisms (gated short
convolutions 3 : 1 with grouped-query attention, a sigmoid-scored mixture
without a shared expert, tied embeddings), from shapes alone (never from
XLA's cost_analysis, and the same whatever implements a mechanism).

Counts are multiply-adds per token of one forward pass unless a name says
otherwise; a training step is forward + backward = 3 x forward, and what a
backward pass recomputes is not counted. `config` is the configuration file
as run (its `num_experts` is the number held here, `published` holds the
uncut counts; `layer_types` and `num_dense_layers` describe the layers that
are kept).
"""


def _widths(config):
    d, h = config["hidden_size"], config["num_attention_heads"]
    kinds = config["layer_types"][:config["num_hidden_layers"]]
    return {
        "d": d, "h": h, "hkv": config["num_key_value_heads"], "dh": d // h,
        "kw": config["conv_L_cache"],
        "w": config["moe_intermediate_size"],
        "f": config["intermediate_size"],
        "held": config["num_experts"],
        "experts": config["published"]["num_experts"],
        "top_k": config["num_experts_per_tok"],
        "vocab": config["vocab_size"], "seq": config["sequence_length"],
        "layers": len(kinds),
        "conv": kinds.count("conv"), "attn": kinds.count("full_attention"),
        "dense": min(config["num_dense_layers"], len(kinds)),
    }


# -- parameters -----------------------------------------------------------------

def conv_operator_params(config):
    """in_proj (three chunks), the taps, out_proj."""
    s = _widths(config)
    return 3 * s["d"] * s["d"] + s["d"] * s["kw"] + s["d"] * s["d"]


def gqa_operator_params(config):
    """wq and wo at every query head, wk and wv at the k/v heads, the two
    head norms' scales."""
    s = _widths(config)
    return (2 * s["d"] * s["h"] * s["dh"] + 2 * s["d"] * s["hkv"] * s["dh"]
            + 2 * s["dh"])


def expert_params(config):
    s = _widths(config)
    return 3 * s["d"] * s["w"]


def router_params(config):
    s = _widths(config)
    return s["d"] * s["experts"] + s["experts"]


def dense_mlp_params(config):
    s = _widths(config)
    return 3 * s["d"] * s["f"]


def expert_layer_mlp_params(config):
    s = _widths(config)
    return router_params(config) + s["held"] * expert_params(config)


def total_params(config):
    """Parameters held on this chip; the head is the embedding."""
    s = _widths(config)
    return (s["vocab"] * s["d"] + s["d"]              # embedding, final norm
            + s["layers"] * 2 * s["d"]
            + s["conv"] * conv_operator_params(config)
            + s["attn"] * gqa_operator_params(config)
            + s["dense"] * dense_mlp_params(config)
            + (s["layers"] - s["dense"]) * expert_layer_mlp_params(config))


# -- multiply-adds per token, forward ---------------------------------------------

def conv_operator_macs(config):
    """The two products and the taps (a multiply-add a tap a channel); the
    two gates are no multiply-adds of a product."""
    return conv_operator_params(config)


def gqa_core_macs(config):
    """Causal softmax attention per token at the sequence length: half of
    S keys on average, dh for a score and dh for its value, every query
    head."""
    s = _widths(config)
    return s["seq"] // 2 * s["h"] * 2 * s["dh"]


def gqa_macs(config):
    """An operator: its four products and its core (the head norms and
    the rotation are no multiply-adds of a product)."""
    return gqa_operator_params(config) - 2 * _widths(config)["dh"] \
        + gqa_core_macs(config)


def routed_expert_macs(config):
    """Per token, on average: top_k of the experts, of which held/experts
    live here."""
    s = _widths(config)
    return s["top_k"] * s["held"] / s["experts"] * expert_params(config)


def moe_macs(config):
    s = _widths(config)
    return s["d"] * s["experts"] + routed_expert_macs(config)


def head_macs(config):
    s = _widths(config)
    return s["vocab"] * s["d"]


def macs_by_mechanism(config):
    """{mechanism: forward multiply-adds per token} over the kept layers."""
    s = _widths(config)
    return {"sconv": s["conv"] * conv_operator_macs(config),
            "gqa": s["attn"] * gqa_macs(config),
            "moe": (s["layers"] - s["dense"]) * moe_macs(config),
            "mlp": s["dense"] * dense_mlp_params(config),
            "head": head_macs(config)}


def train_flops_per_sequence(config):
    """Floating-point operations of one trained sequence: 2 per
    multiply-add, forward + backward = 3 x forward."""
    per_token = sum(macs_by_mechanism(config).values())
    return 3 * 2 * per_token * config["sequence_length"]


# -- kernels: operations and bytes of one training step ----------------------------

def short_conv_step(config, tokens):
    """((flops, bytes) forward, (flops, bytes) backward) of the gated
    passes (between the two products) of one step of `tokens`, every
    convolution layer: forward reads the three chunks and writes one,
    backward reads the three chunks and the cotangent and writes three, 2
    bytes each; forward a gate, kw taps and a gate a channel, backward the
    convolution again, its transpose, the taps' gradient and five
    products. Two tuples, because a compiler may fuse either pass into the
    product beside it, where it moves no bytes of its own."""
    s = _widths(config)
    rows = tokens * s["conv"] * s["d"]
    return ((rows * (2 + 2 * s["kw"]), rows * 2 * (3 + 1)),
            (rows * (5 + 6 * s["kw"]), rows * 2 * (4 + 3)))


def gqa_core_step(config, tokens):
    """(flops, bytes) of the attention cores of one step of `tokens`: q and
    the output at every query head, k and v once a group (the kernels
    stream a group's shared head), 2 bytes each, once forward; twice that
    backward."""
    s = _widths(config)
    flops = 3 * 2 * gqa_core_macs(config) * tokens * s["attn"]
    forward = 2 * s["dh"] * (2 * s["h"] + 2 * s["hkv"])
    return flops, 3 * forward * tokens * s["attn"]


def expert_matmul_step(config, pairs):
    """(flops, bytes) of the grouped products over `pairs` token-expert
    pairs summed over the layers of one step: three products a pair; the
    held experts' weights are read forward and backward and their
    gradients written, the pairs' rows in and out in 2 bytes."""
    s = _widths(config)
    flops = 3 * 2 * expert_params(config) * pairs
    weights = 3 * 2 * s["held"] * expert_params(config) \
        * (s["layers"] - s["dense"])
    rows = 3 * 2 * pairs * (2 * s["d"] + 3 * s["w"])
    return flops, weights + rows
