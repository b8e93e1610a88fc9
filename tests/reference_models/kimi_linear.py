"""Plain reference of Kimi-Linear-48B-A3B-Instruct's language model.

Source: https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct
(config.json) and the Kimi Linear technical report, arXiv:2510.26692.
Forward, loss and (through `jax.grad`) gradients in straightforward
`jax.numpy`, float32, every product at `highest` precision: a
token-by-token recurrence for Kimi Delta Attention, an explicit softmax for
latent attention, a loop over experts for the mixture. No kernel, no cache,
no batching trick, and nothing imported from the system under test.
`benchmarks/models/kimi_linear_reference.py` is a copy of this file
(`tests/test_kimi_linear.py` holds the two equal).

A chip's share (the `model-configs` guide, section 4): `experts_held =
(first, n)` makes the mixture route over all `num_experts`, renormalise over
all chosen experts, and add only the terms of experts first..first+n-1; the
shared expert is whole. `vocab_size` is the slice the chip holds.

DEPARTURES from the published description, and what it leaves open
(`assumed` in benchmarks/configs/kimi_linear_48b_a3b.json lists the same):
  1. The 64 `pe` dims of latent attention get no rotation
     (`mla_use_nope: true`, `rope_scaling: null`): they are plain extra
     query/key dims, the key's shared by all heads.
  2. q and k of KDA are L2-normalised per head after the convolution and
     SiLU, with eps 1e-6 inside the root; q is then scaled by d_k**-0.5.
  3. The short convolutions have no bias; the projections have none; the
     decay's `dt_bias` is the only additive term of the mixers.
  4. The output norm of KDA is an RMSNorm over each head's 128 values with
     one learned scale of 128 shared by the heads, multiplied by the
     sigmoid of the low-rank gate.
  5. One expert group (`num_expert_group` 1), so grouped top-k is plain
     top-8 of `sigmoid(score) + bias`; the bias takes no gradient.
  6. The weights are drawn normal(0, 0.02) (norm scales 1, `A_log` =
     log of uniform(1, 16), `dt_bias` and the router's bias 0); the
     published checkpoint's own initialiser is not part of config.json.
  7. The loss is the mean next-token cross-entropy over the vocabulary
     slice; Adam without weight decay, bias-corrected, eps 1e-8.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
EPS = 1e-5


def layer_kinds(cfg):
    """[(mixer, mlp)] of the layers that are kept: published layers
    1..num_hidden_layers (1-based, as config.json counts them)."""
    lin = cfg["linear_attn_config"]
    out = []
    for i in range(1, cfg["num_hidden_layers"] + 1):
        mixer = "kda" if i in lin["kda_layers"] else "mla"
        if mixer == "mla" and i not in lin["full_attn_layers"]:
            raise ValueError(f"layer {i} is neither a KDA nor a full layer")
        mlp = "dense" if i <= cfg["first_k_dense_replace"] else "moe"
        out.append((mixer, mlp))
    return out


def param_shapes(cfg):
    """{name: shape} in a fixed order; matrices are (out, in)."""
    d = cfg["hidden_size"]
    lin = cfg["linear_attn_config"]
    h, dk = lin["num_heads"], lin["head_dim"]
    kw = lin["short_conv_kernel_size"]
    ha = cfg["num_attention_heads"]
    dn, dp, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    r = cfg["kv_lora_rank"]
    low = dk                   # the decay's and the gate's low rank
    e_held = cfg["experts_held"][1]
    wi = cfg["moe_intermediate_size"]
    shapes = {"embed": (cfg["vocab_size"], d)}
    for li, (mixer, mlp) in enumerate(layer_kinds(cfg)):
        p = f"l{li}_"
        shapes[p + "norm1"] = (d,)
        if mixer == "kda":
            for n in ("q", "k", "v"):
                shapes[p + f"w{n}"] = (h * dk, d)
                shapes[p + f"conv_{n}"] = (h * dk, kw)
            shapes[p + "w_fa"] = (low, d)
            shapes[p + "w_fb"] = (h * dk, low)
            shapes[p + "A_log"] = (h,)
            shapes[p + "dt_bias"] = (h * dk,)
            shapes[p + "w_b"] = (h, d)
            shapes[p + "w_ga"] = (low, d)
            shapes[p + "w_gb"] = (h * dk, low)
            shapes[p + "o_norm"] = (dk,)
            shapes[p + "wo"] = (d, h * dk)
        else:
            shapes[p + "wq"] = (ha * (dn + dp), d)
            shapes[p + "w_kva"] = (r + dp, d)
            shapes[p + "kv_norm"] = (r,)
            shapes[p + "w_kvb"] = (ha * (dn + dv), r)
            shapes[p + "wo"] = (d, ha * dv)
        shapes[p + "norm2"] = (d,)
        if mlp == "dense":
            f = cfg["intermediate_size"]
            shapes[p + "w_gate"] = (f, d)
            shapes[p + "w_up"] = (f, d)
            shapes[p + "w_down"] = (d, f)
        else:
            shapes[p + "w_r"] = (cfg["num_experts"], d)
            shapes[p + "r_bias"] = (cfg["num_experts"],)
            shapes[p + "e_gate"] = (e_held, wi, d)
            shapes[p + "e_up"] = (e_held, wi, d)
            shapes[p + "e_down"] = (e_held, d, wi)
            shapes[p + "s_gate"] = (wi * cfg["num_shared_experts"], d)
            shapes[p + "s_up"] = (wi * cfg["num_shared_experts"], d)
            shapes[p + "s_down"] = (d, wi * cfg["num_shared_experts"])
    shapes["norm_f"] = (d,)
    shapes["head"] = (cfg["vocab_size"], d)
    return shapes


def init_params(cfg, seed, std=0.02):
    """Seeded weights (numpy, float32): normal(0, std); norm scales 1;
    A_log = log(uniform(1, 16)); dt_bias and the router's bias 0."""
    rng = np.random.default_rng([int(seed), 7])
    out = {}
    for name, shape in param_shapes(cfg).items():
        short = name.split("_", 1)[-1] if name.startswith("l") else name
        if short in ("norm1", "norm2", "kv_norm", "o_norm") or \
                name == "norm_f":
            out[name] = np.ones(shape, np.float32)
        elif short == "A_log":
            out[name] = np.log(rng.uniform(1.0, 16.0, shape)).astype(
                np.float32)
        elif short in ("dt_bias", "r_bias"):
            out[name] = np.zeros(shape, np.float32)
        else:
            out[name] = (std * rng.standard_normal(shape)).astype(np.float32)
    return out


# -- the pieces ---------------------------------------------------------------

def mm(x, w):
    """x (..., in) times w (out, in), transposed, at highest precision."""
    return jnp.einsum("...i,oi->...o", x, w, precision=HIGHEST)


def rms_norm(x, scale, eps=EPS):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def swiglu(x, w_gate, w_up, w_down):
    return mm(jax.nn.silu(mm(x, w_gate)) * mm(x, w_up), w_down)


def short_conv(x, w):
    """Causal depthwise convolution over time. x (B, S, C), w (C, kw):
    y_t = sum_j w[:, j] * x_{t - (kw - 1) + j}, zeros before the start."""
    kw = w.shape[1]
    pad = jnp.pad(x, ((0, 0), (kw - 1, 0), (0, 0)))
    s = x.shape[1]
    return sum(pad[:, j:j + s, :] * w[:, j] for j in range(kw))


def l2_normalize(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + eps)


def kda_recurrence(q, k, v, g, beta, block=64):
    """The gated delta rule, token by token. q, k, g (B, S, H, dk), v
    (B, S, H, dv), beta (B, S, H); g is the log-decay (<= 0). Returns
    (B, S, H, dv). The scan over tokens runs inside a scan over blocks of
    `block` of them under `jax.checkpoint`, so that its backward saves one
    state per block and not one per token (8,192 states of 2 MB a head
    group would not fit); a length that is no multiple of `block` is one
    block."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    if s % block:
        block = s

    def step(state, x):
        qt, kt, vt, gt, bt = x                   # (B, H, dk) ... (B, H)
        state = state * jnp.exp(gt)[..., None]
        pred = jnp.einsum("bhkv,bhk->bhv", state, kt, precision=HIGHEST)
        state = state + jnp.einsum(
            "bhk,bhv->bhkv", kt * bt[..., None], vt - pred,
            precision=HIGHEST)
        return state, jnp.einsum("bhkv,bhk->bhv", state, qt,
                                 precision=HIGHEST)

    @jax.checkpoint
    def run_block(state, xs):
        return jax.lax.scan(step, state, xs)

    xs = tuple(jnp.moveaxis(a, 1, 0).reshape((s // block, block)
                                              + a.shape[:1] + a.shape[2:])
               for a in (q, k, v, g, beta))
    state = jnp.zeros((b, h, dk, dv), jnp.float32)
    _, o = jax.lax.scan(run_block, state, xs)
    return jnp.moveaxis(o.reshape((s, b, h, dv)), 0, 1)


def kda_mixer(cfg, p, x):
    lin = cfg["linear_attn_config"]
    h, dk = lin["num_heads"], lin["head_dim"]
    b, s, _ = x.shape
    q, k, v = (jax.nn.silu(short_conv(mm(x, p[f"w{n}"]), p[f"conv_{n}"]))
               .reshape(b, s, h, dk) for n in ("q", "k", "v"))
    q = l2_normalize(q) * dk ** -0.5
    k = l2_normalize(k)
    f = mm(mm(x, p["w_fa"]), p["w_fb"]) + p["dt_bias"]
    g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(
        f.reshape(b, s, h, dk))
    beta = jax.nn.sigmoid(mm(x, p["w_b"]))
    o = kda_recurrence(q, k, v, g, beta)
    gate = jax.nn.sigmoid(mm(mm(x, p["w_ga"]), p["w_gb"]))
    o = rms_norm(o, p["o_norm"]) * gate.reshape(b, s, h, dk)
    return mm(o.reshape(b, s, h * dk), p["wo"])


def mla_mixer(cfg, p, x, q_block=None):
    ha = cfg["num_attention_heads"]
    dn, dp, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    r = cfg["kv_lora_rank"]
    b, s, _ = x.shape
    q = mm(x, p["wq"]).reshape(b, s, ha, dn + dp)
    kva = mm(x, p["w_kva"])
    c_kv, k_pe = kva[..., :r], kva[..., r:]
    kvb = mm(rms_norm(c_kv, p["kv_norm"]), p["w_kvb"]).reshape(
        b, s, ha, dn + dv)
    k = jnp.concatenate(
        [kvb[..., :dn],
         jnp.broadcast_to(k_pe[:, :, None, :], (b, s, ha, dp))], -1)
    v = kvb[..., dn:]
    scale = (dn + dp) ** -0.5

    def rows(q_rows, first):
        """Explicit softmax of a block of query rows over every key."""
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_rows, k,
                            precision=HIGHEST) * scale
        q_pos = first + jnp.arange(q_rows.shape[1])[:, None]
        mask = q_pos >= jnp.arange(s)[None, :]
        scores = jnp.where(mask, scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v,
                          precision=HIGHEST)

    if q_block is None or q_block >= s:
        o = rows(q, 0)
    else:       # the same softmax, a block of rows at a time (memory only)
        o = jnp.concatenate(
            [jax.checkpoint(rows, static_argnums=1)(
                q[:, i:i + q_block], i) for i in range(0, s, q_block)], 1)
    return mm(o.reshape(b, s, ha * dv), p["wo"])


def moe_route(cfg, p, x):
    """(chosen experts (T, k), their weights (T, k)) for tokens x (T, D)."""
    scores = jax.nn.sigmoid(mm(x, p["w_r"]))
    _, idx = jax.lax.top_k(scores + jax.lax.stop_gradient(p["r_bias"]),
                           cfg["num_experts_per_token"])
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg["moe_renormalize"]:
        w = w / jnp.sum(w, -1, keepdims=True)
    return idx, w * cfg["routed_scaling_factor"]


def route_margin(cfg, p, x):
    """The smallest gap, over tokens x (T, D), between the score of the
    last expert chosen and that of the first passed over, among the tokens
    for which one of the two is held here (for the others the choice moves
    nothing but a sum of two nearly equal scores). Top-k is a step: two
    float32 implementations agree on it only where this gap is well above
    their rounding, so a comparison picks its sequence by it."""
    k = cfg["num_experts_per_token"]
    first, n = cfg["experts_held"]
    top, idx = jax.lax.top_k(jax.nn.sigmoid(mm(x, p["w_r"])) + p["r_bias"],
                             k + 1)
    held = (idx[:, k - 1:] >= first) & (idx[:, k - 1:] < first + n)
    return jnp.min(jnp.where(held[:, 0] | held[:, 1],
                             top[:, k - 1] - top[:, k], jnp.inf))


def moe_mlp(cfg, p, x, routed=True, shared=True):
    """The mixture over the experts held here plus the shared expert."""
    b, s, d = x.shape
    t = x.reshape(b * s, d)
    y = jnp.zeros_like(t)
    if routed:
        first, n = cfg["experts_held"]
        idx, w = moe_route(cfg, p, t)
        for e in range(n):
            w_e = jnp.sum(jnp.where(idx == first + e, w, 0.0), -1)
            y = y + w_e[:, None] * swiglu(t, p["e_gate"][e], p["e_up"][e],
                                          p["e_down"][e])
    if shared:
        y = y + swiglu(t, p["s_gate"], p["s_up"], p["s_down"])
    return y.reshape(b, s, d)


def layer_params(params, li):
    pre = f"l{li}_"
    return {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}


def layer(cfg, kinds, p, x, q_block=None, margins=None):
    mixer, mlp = kinds
    xn = rms_norm(x, p["norm1"])
    x = x + (kda_mixer(cfg, p, xn) if mixer == "kda"
             else mla_mixer(cfg, p, xn, q_block))
    xn = rms_norm(x, p["norm2"])
    if margins is not None and mlp == "moe":
        margins.append(route_margin(cfg, p, xn.reshape(-1, xn.shape[-1])))
    return x + (swiglu(xn, p["w_gate"], p["w_up"], p["w_down"])
                if mlp == "dense" else moe_mlp(cfg, p, xn))


def hidden_states(cfg, params, tokens, q_block=None, remat=False,
                  margins=None):
    """`q_block` (rows of the softmax at a time) and `remat` (each layer's
    backward recomputes it) change what is kept in memory, not one number:
    they let the published widths fit a chip at 8,192 tokens. A list given
    as `margins` gets each mixture layer's `route_margin` (not with
    `remat`)."""
    x = params["embed"][tokens]
    for li, kinds in enumerate(layer_kinds(cfg)):
        def run(p, x, kinds=kinds):
            return layer(cfg, kinds, p, x, q_block, margins)
        x = (jax.checkpoint(run) if remat else run)(layer_params(params, li),
                                                    x)
    return rms_norm(x, params["norm_f"])


def logits(cfg, params, tokens, q_block=None, remat=False, margins=None):
    """(B, S, vocab) float32 logits of tokens (B, S) int."""
    return mm(hidden_states(cfg, params, tokens, q_block, remat, margins),
              params["head"])


def losses_of_logits(lg, labels):
    """(B, S) cross-entropy of each position's logits against its label."""
    lse = jax.nn.logsumexp(lg, axis=-1)
    return lse - jnp.take_along_axis(lg, labels[..., None], -1)[..., 0]


def token_losses(cfg, params, tokens, labels, q_block=None, remat=False):
    """(B, S) cross-entropy of each position against its label."""
    return losses_of_logits(logits(cfg, params, tokens, q_block, remat),
                            labels)


def loss(cfg, params, tokens, labels, q_block=None, remat=False):
    return jnp.mean(token_losses(cfg, params, tokens, labels, q_block,
                                 remat))


def loss_and_grads(cfg, params, tokens, labels, q_block=None, remat=False,
                   wrt=None):
    """(loss, gradients of every parameter, or of those named in `wrt`)."""
    names = list(params) if wrt is None else list(wrt)

    def of(chosen):
        return loss(cfg, dict(params, **chosen), tokens, labels, q_block,
                    remat)
    return jax.value_and_grad(of)({n: params[n] for n in names})


def system_params(params, prefix=""):
    """The same weights under the names and layouts of the system's model
    (`mxnet_tpu.gluon.nn.DecoderLM`): `prefix` + name; the experts' three
    matrices (E, in, out), as a grouped product multiplies them."""
    out = {}
    for name, value in params.items():
        if name.split("_", 1)[-1] in ("e_gate", "e_up", "e_down"):
            value = np.swapaxes(np.asarray(value), 1, 2)
        out[prefix + name] = value
    return out


def adam_update(params, grads, moments, t, lr=1e-4, beta1=0.9, beta2=0.95,
                eps=1e-8):
    """Step `t` (from 1) of plain bias-corrected Adam: (params, moments)
    after it; `moments` is (m, v), or None before the first step."""
    m, v = moments or ({k: 0.0 for k in params}, {k: 0.0 for k in params})
    step = lr * math.sqrt(1.0 - beta2 ** t) / (1.0 - beta1 ** t)
    m = {k: beta1 * m[k] + (1.0 - beta1) * grads[k] for k in params}
    v = {k: beta2 * v[k] + (1.0 - beta2) * jnp.square(grads[k])
         for k in params}
    return {k: params[k] - step * m[k] / (jnp.sqrt(v[k]) + eps)
            for k in params}, (m, v)


def adam_steps(cfg, params, batches, **adam):
    """Plain Adam over `batches` [(tokens, labels)]; returns (params,
    [loss before each step])."""
    params = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    moments, losses = None, []
    step_grads = jax.jit(lambda p, tok, lab: loss_and_grads(cfg, p, tok, lab))
    for t, (tokens, labels) in enumerate(batches, start=1):
        value, grads = step_grads(params, tokens, labels)
        losses.append(float(value))
        params, moments = adam_update(params, grads, moments, t, **adam)
    return params, losses
