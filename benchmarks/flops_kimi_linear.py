"""Operations and bytes of Kimi-Linear's mechanisms, from shapes alone (never
from XLA's cost_analysis, and the same whatever implements a mechanism).

Counts are multiply-adds per token of one forward pass unless a name says
otherwise; a training step is forward + backward = 3 x forward, and what a
backward pass recomputes is not counted. `config` is the configuration file
as run (its `num_experts` is the number held here, `published` holds the
uncut counts); layers are the published layers 1..num_hidden_layers.
"""


def _widths(config):
    lin = config["linear_attn_config"]
    return {
        "d": config["hidden_size"], "h_kda": lin["num_heads"],
        "dk": lin["head_dim"], "kw": lin["short_conv_kernel_size"],
        "h": config["num_attention_heads"],
        "dn": config["qk_nope_head_dim"], "dp": config["qk_rope_head_dim"],
        "dv": config["v_head_dim"], "r": config["kv_lora_rank"],
        "w": config["moe_intermediate_size"],
        "f": config["intermediate_size"],
        "held": config["num_experts"],
        "experts": config["published"]["num_experts"],
        "top_k": config["num_experts_per_token"],
        "shared": config["num_shared_experts"],
        "vocab": config["vocab_size"], "seq": config["sequence_length"],
    }


def layer_kinds(config):
    lin = config["linear_attn_config"]
    return [("kda" if i in lin["kda_layers"] else "mla",
             "dense" if i <= config["first_k_dense_replace"] else "moe")
            for i in range(1, config["num_hidden_layers"] + 1)]


# -- parameters -----------------------------------------------------------------

def kda_mixer_params(config):
    s = _widths(config)
    c, low = s["h_kda"] * s["dk"], s["dk"]
    gate = s["d"] * low + low * c
    return (3 * s["d"] * c + 3 * c * s["kw"]          # q, k, v + convolutions
            + gate + s["h_kda"] + c                  # decay: f, A_log, dt_bias
            + s["d"] * s["h_kda"]                    # beta
            + gate + s["dk"]                         # output gate, head norm
            + c * s["d"])                            # out


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


def total_params(config):
    """Parameters held on this chip."""
    s = _widths(config)
    total = 2 * s["vocab"] * s["d"] + s["d"]          # embedding, head, norm
    for mixer, mlp in layer_kinds(config):
        total += 2 * s["d"]                           # the two norms
        total += kda_mixer_params(config) if mixer == "kda" \
            else mla_mixer_params(config)
        total += dense_mlp_params(config) if mlp == "dense" else \
            (router_params(config)
             + (s["held"] + s["shared"]) * expert_params(config))
    return total


# -- multiply-adds per token, forward ---------------------------------------------

def kda_core_macs(config):
    """The delta rule itself, per token: the state read for the
    prediction (S^T k), its rank-one update and its read for the output
    (S^T q), dk x dv each, per head."""
    s = _widths(config)
    return 3 * s["h_kda"] * s["dk"] * s["dk"]


def kda_macs(config):
    s = _widths(config)
    c = s["h_kda"] * s["dk"]
    products = kda_mixer_params(config) - s["h_kda"] - c - s["dk"]
    return products + kda_core_macs(config)


def mla_core_macs(config):
    """Causal softmax attention per token at the sequence length: half of
    S keys on average, dn + dp for a score and dv for its value."""
    s = _widths(config)
    return s["seq"] // 2 * s["h"] * (s["dn"] + s["dp"] + s["dv"])


def mla_macs(config):
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
    out = {"kda": 0.0, "mla": 0.0, "mlp": 0.0, "head": head_macs(config)}
    for mixer, mlp in layer_kinds(config):
        out[mixer] += kda_macs(config) if mixer == "kda" else mla_macs(config)
        out["mlp"] += dense_mlp_params(config) if mlp == "dense" \
            else moe_macs(config)
    return out


def train_flops_per_sequence(config):
    """Floating-point operations of one trained sequence: 2 per
    multiply-add, forward + backward = 3 x forward."""
    per_token = sum(macs_by_mechanism(config).values())
    return 3 * 2 * per_token * config["sequence_length"]


# -- kernels: operations and bytes of one training step ----------------------------

def _count(config, kind, which=0):
    return sum(1 for k in layer_kinds(config) if k[which] == kind)


def kda_core_step(config, tokens):
    """(flops, bytes) of the KDA cores of one step of `tokens`, forward and
    backward: q, k, v and the output in 2 bytes and the log-decay in 4 per
    channel go through memory once forward; the backward reads them and
    the output's gradient and writes four gradients."""
    s = _widths(config)
    n = _count(config, "kda")
    c = s["h_kda"] * s["dk"]
    flops = 3 * 2 * kda_core_macs(config) * tokens * n
    forward = c * (4 * 2 + 4) + s["h_kda"] * 4
    return flops, 3 * forward * tokens * n


def mla_core_step(config, tokens):
    """(flops, bytes) of the attention cores: q and k of dn + dp, v and
    the output of dv, 2 bytes each, once forward; twice that backward."""
    s = _widths(config)
    n = _count(config, "mla")
    flops = 3 * 2 * mla_core_macs(config) * tokens * n
    forward = s["h"] * 2 * (2 * (s["dn"] + s["dp"]) + 2 * s["dv"])
    return flops, 3 * forward * tokens * n


def expert_matmul_step(config, pairs):
    """(flops, bytes) of the grouped products over `pairs` token-expert
    pairs summed over the layers of one step: three products a pair; the
    held experts' weights are read forward and backward and their
    gradients written, the pairs' rows in and out in 2 bytes."""
    s = _widths(config)
    n = _count(config, "moe", 1)
    flops = 3 * 2 * expert_params(config) * pairs
    weights = 3 * 2 * s["held"] * expert_params(config) * n
    rows = 3 * 2 * pairs * (2 * s["d"] + 3 * s["w"])
    return flops, weights + rows
