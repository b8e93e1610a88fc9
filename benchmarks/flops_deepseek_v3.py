"""Operations and bytes of a `deepseek_v3` model's mechanisms (latent
attention with a rotary key in every layer, a sigmoid-scored mixture with
shared experts), from shapes alone (never from XLA's cost_analysis, and the
same whatever implements a mechanism).

Counts are multiply-adds per token of one forward pass unless a name says
otherwise; a training step is forward + backward = 3 x forward, and what a
backward pass recomputes is not counted. `config` is the configuration file
as run (its `n_routed_experts` is the number held here, `published` holds
the uncut counts); layers are the published layers 1..num_hidden_layers.
"""


def _widths(config):
    return {
        "d": config["hidden_size"], "h": config["num_attention_heads"],
        "dn": config["qk_nope_head_dim"], "dp": config["qk_rope_head_dim"],
        "dv": config["v_head_dim"], "r": config["kv_lora_rank"],
        "w": config["moe_intermediate_size"],
        "f": config["intermediate_size"],
        "held": config["n_routed_experts"],
        "experts": config["published"]["n_routed_experts"],
        "top_k": config["num_experts_per_tok"],
        "shared": config["n_shared_experts"],
        "vocab": config["vocab_size"], "seq": config["sequence_length"],
        "layers": config["num_hidden_layers"],
        "dense": min(config["first_k_dense_replace"],
                     config["num_hidden_layers"]),
    }


# -- parameters -----------------------------------------------------------------

def mla_mixer_params(config):
    s = _widths(config)
    return (s["d"] * s["h"] * (s["dn"] + s["dp"])
            + s["d"] * (s["r"] + s["dp"]) + s["r"]
            + s["r"] * s["h"] * (s["dn"] + s["dv"])
            + s["h"] * s["dv"] * s["d"])


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
    return router_params(config) + \
        (s["held"] + s["shared"]) * expert_params(config)


def total_params(config):
    """Parameters held on this chip."""
    s = _widths(config)
    return (2 * s["vocab"] * s["d"] + s["d"]          # embedding, head, norm
            + s["layers"] * (2 * s["d"] + mla_mixer_params(config))
            + s["dense"] * dense_mlp_params(config)
            + (s["layers"] - s["dense"]) * expert_layer_mlp_params(config))


# -- multiply-adds per token, forward ---------------------------------------------

def mla_core_macs(config):
    """Causal softmax attention per token at the sequence length: half of
    S keys on average, dn + dp for a score and dv for its value."""
    s = _widths(config)
    return s["seq"] // 2 * s["h"] * (s["dn"] + s["dp"] + s["dv"])


def mla_macs(config):
    """A mixer: its four products and its core (the rotation and the
    latent's norm are no multiply-adds of a product)."""
    return mla_mixer_params(config) - _widths(config)["r"] \
        + mla_core_macs(config)


def routed_expert_macs(config):
    """Per token, on average: top_k of the experts, of which held/experts
    live here."""
    s = _widths(config)
    return s["top_k"] * s["held"] / s["experts"] * expert_params(config)


def moe_macs(config):
    s = _widths(config)
    return (s["d"] * s["experts"] + s["shared"] * expert_params(config)
            + routed_expert_macs(config))


def head_macs(config):
    s = _widths(config)
    return s["vocab"] * s["d"]


def macs_by_mechanism(config):
    """{mechanism: forward multiply-adds per token} over the kept layers."""
    s = _widths(config)
    return {"mla": s["layers"] * mla_macs(config),
            "moe": (s["layers"] - s["dense"]) * moe_macs(config),
            "mlp": s["dense"] * dense_mlp_params(config),
            "head": head_macs(config)}


def train_flops_per_sequence(config):
    """Floating-point operations of one trained sequence: 2 per
    multiply-add, forward + backward = 3 x forward."""
    per_token = sum(macs_by_mechanism(config).values())
    return 3 * 2 * per_token * config["sequence_length"]


# -- kernels: operations and bytes of one training step ----------------------------

def mla_core_step(config, tokens):
    """(flops, bytes) of the attention cores of one step of `tokens`: q and
    k of dn + dp, v and the output of dv, 2 bytes each, once forward; twice
    that backward."""
    s = _widths(config)
    flops = 3 * 2 * mla_core_macs(config) * tokens * s["layers"]
    forward = s["h"] * 2 * (2 * (s["dn"] + s["dp"]) + 2 * s["dv"])
    return flops, 3 * forward * tokens * s["layers"]


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
