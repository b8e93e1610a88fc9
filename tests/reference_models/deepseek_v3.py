"""Plain reference of a `deepseek_v3` language model as Kanana-2-30B-A3B
configures it.

Source: https://huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601
(config.json, `model_type` `deepseek_v3`) and the DeepSeek-V3 technical
report, arXiv:2412.19437, sections 2.1.1 (multi-head latent attention with
a decoupled rotary key) and 2.1.2 (sigmoid-scored mixture with shared
experts and a selection bias). Forward, loss and (through `jax.grad`)
gradients in straightforward `jax.numpy`, float32, every product at
`highest` precision: an explicit rotation, an explicit softmax, a loop
over experts. No kernel, no cache, no batching trick, and nothing imported
from the system under test. `benchmarks/models/deepseek_v3_reference.py`
is a copy of this file (`tests/test_deepseek_v3.py` holds the two equal).

A chip's share (the `model-configs` guide, section 4): `experts_held =
(first, n)` makes the mixture route over all `n_routed_experts`,
renormalise over all chosen experts, and add only the terms of experts
first..first+n-1; the shared experts are whole. `vocab_size` is the slice
the chip holds.

DEPARTURES from the published description, and what it leaves open
(`assumed` in benchmarks/configs/kanana_2_30b_a3b.json lists the same):
  1. `rope_interleave: true` is taken at its word: pair i of the 64 rotary
     dims is (t[2i], t[2i+1]). The checkpoint's loader de-interleaves q_pe
     and k_pe and then rotates the halves (t[i], t[i+32]); q and k are
     permuted alike, so every score q.k is the same and so is the model.
     `rope(..., interleave=False)` is that other pairing, for tests.
  2. Positions are 0..S-1 of each sequence; `rope_scaling` is null, so no
     frequency is rescaled and the softmax scale is 192**-0.5 unchanged.
  3. One expert group (`n_group` 1, `topk_group` 1), so `noaux_tc` is the
     plain top-6 of `sigmoid(score) + bias`; the bias takes no gradient
     (the report moves it by a rule outside the loss, which is no part of
     a training step here) and starts at 0.
  4. The two shared experts are one SwiGLU of width 2 x 768, as the
     published model builds them.
  5. No bias anywhere (`attention_bias` false); no dropout; no auxiliary
     or sequence-wise balance loss; no multi-token prediction module.
  6. The weights are drawn normal(0, 0.02), norm scales 1: the published
     checkpoint's own initialiser is not part of config.json.
  7. The loss is the mean next-token cross-entropy over the vocabulary
     slice; Adam without weight decay, bias-corrected, eps 1e-8.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def layer_kinds(cfg):
    """["dense" | "moe"] of the layers that are kept: published layers
    1..num_hidden_layers; every mixer is latent attention."""
    return ["dense" if i <= cfg["first_k_dense_replace"] else "moe"
            for i in range(1, cfg["num_hidden_layers"] + 1)]


def param_shapes(cfg):
    """{name: shape} in a fixed order; matrices are (out, in)."""
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    dn, dp, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    r = cfg["kv_lora_rank"]
    e_all, e_held = cfg["n_routed_experts"], cfg["experts_held"][1]
    wi = cfg["moe_intermediate_size"]
    ws = wi * cfg["n_shared_experts"]
    shapes = {"embed": (cfg["vocab_size"], d)}
    for li, mlp in enumerate(layer_kinds(cfg)):
        p = f"l{li}_"
        shapes[p + "norm1"] = (d,)
        shapes[p + "wq"] = (h * (dn + dp), d)
        shapes[p + "w_kva"] = (r + dp, d)
        shapes[p + "kv_norm"] = (r,)
        shapes[p + "w_kvb"] = (h * (dn + dv), r)
        shapes[p + "wo"] = (d, h * dv)
        shapes[p + "norm2"] = (d,)
        if mlp == "dense":
            f = cfg["intermediate_size"]
            shapes[p + "w_gate"] = (f, d)
            shapes[p + "w_up"] = (f, d)
            shapes[p + "w_down"] = (d, f)
        else:
            shapes[p + "w_r"] = (e_all, d)
            shapes[p + "r_bias"] = (e_all,)
            shapes[p + "e_gate"] = (e_held, wi, d)
            shapes[p + "e_up"] = (e_held, wi, d)
            shapes[p + "e_down"] = (e_held, d, wi)
            shapes[p + "s_gate"] = (ws, d)
            shapes[p + "s_up"] = (ws, d)
            shapes[p + "s_down"] = (d, ws)
    shapes["norm_f"] = (d,)
    shapes["head"] = (cfg["vocab_size"], d)
    return shapes


def init_params(cfg, seed, std=0.02):
    """Seeded weights (numpy, float32): normal(0, std); norm scales 1; the
    router's bias 0."""
    rng = np.random.default_rng([int(seed), 11])
    out = {}
    for name, shape in param_shapes(cfg).items():
        short = name.split("_", 1)[-1] if name.startswith("l") else name
        if short in ("norm1", "norm2", "kv_norm") or name == "norm_f":
            out[name] = np.ones(shape, np.float32)
        elif short == "r_bias":
            out[name] = np.zeros(shape, np.float32)
        else:
            out[name] = (std * rng.standard_normal(shape)).astype(np.float32)
    return out


# -- the pieces ---------------------------------------------------------------

def mm(x, w):
    """x (..., in) times w (out, in), transposed, at highest precision."""
    return jnp.einsum("...i,oi->...o", x, w, precision=HIGHEST)


def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def swiglu(x, w_gate, w_up, w_down):
    return mm(jax.nn.silu(mm(x, w_gate)) * mm(x, w_up), w_down)


def rope(t, theta, interleave=True):
    """Rotate t (B, S, ..., R) by position: for position p and pair i the
    angle is a = p * theta^(-2i/R), and the pair (t0, t1) becomes
    (t0 cos a - t1 sin a, t0 sin a + t1 cos a). The pairs are
    (t[2i], t[2i+1]) when `interleave`, else (t[i], t[i + R/2]). The
    frequencies are rounded to float32 once; angle, cosine and sine are
    float32."""
    s, r = t.shape[1], t.shape[-1]
    freq = jnp.asarray(np.float32(float(theta) ** (-np.arange(0, r, 2) / r)))
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * freq     # (S, R/2)
    angle = angle.reshape((1, s) + (1,) * (t.ndim - 3) + (r // 2,))
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    if interleave:
        t0, t1 = t[..., 0::2], t[..., 1::2]
        return jnp.stack([t0 * cos - t1 * sin, t0 * sin + t1 * cos],
                         -1).reshape(t.shape)
    t0, t1 = t[..., :r // 2], t[..., r // 2:]
    return jnp.concatenate([t0 * cos - t1 * sin, t0 * sin + t1 * cos], -1)


def mla_mixer(cfg, p, x, q_block=None):
    h = cfg["num_attention_heads"]
    dn, dp, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    r = cfg["kv_lora_rank"]
    theta, interleave = cfg["rope_theta"], cfg["rope_interleave"]
    b, s, _ = x.shape
    q = mm(x, p["wq"]).reshape(b, s, h, dn + dp)
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], theta, interleave)],
                        -1)
    kva = mm(x, p["w_kva"])
    c_kv, k_pe = kva[..., :r], rope(kva[..., r:], theta, interleave)
    kvb = mm(rms_norm(c_kv, p["kv_norm"], cfg["rms_norm_eps"]),
             p["w_kvb"]).reshape(b, s, h, dn + dv)
    # the one rotated key is shared by every head
    k = jnp.concatenate(
        [kvb[..., :dn],
         jnp.broadcast_to(k_pe[:, :, None, :], (b, s, h, dp))], -1)
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

    if q_block is None or q_block >= s or s % q_block:
        o = rows(q, 0)
    else:       # the same softmax, a block of rows at a time (memory only:
        # one compiled body, whose backward recomputes a block's scores)
        blocks = jnp.moveaxis(
            q.reshape(b, s // q_block, q_block, h, dn + dp), 1, 0)
        o = jax.lax.map(lambda blk: jax.checkpoint(rows)(*blk),
                        (blocks, jnp.arange(0, s, q_block)))
        o = jnp.moveaxis(o, 0, 1).reshape(b, s, h, dv)
    return mm(o.reshape(b, s, h * dv), p["wo"])


def moe_route(cfg, p, x):
    """(chosen experts (T, k), their weights (T, k)) for tokens x (T, D)."""
    scores = jax.nn.sigmoid(mm(x, p["w_r"]))
    _, idx = jax.lax.top_k(scores + jax.lax.stop_gradient(p["r_bias"]),
                           cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / jnp.sum(w, -1, keepdims=True)
    return idx, w * cfg["routed_scaling_factor"]


def route_margin(cfg, p, x):
    """The smallest gap, over tokens x (T, D), between the score of the
    last expert chosen and that of the first passed over, among the tokens
    for which one of the two is held here (for the others the choice moves
    nothing but a sum of two nearly equal scores). Top-k is a step: two
    float32 implementations agree on it only where this gap is well above
    their rounding, so a comparison picks its sequence by it."""
    k = cfg["num_experts_per_tok"]
    first, n = cfg["experts_held"]
    top, idx = jax.lax.top_k(jax.nn.sigmoid(mm(x, p["w_r"])) + p["r_bias"],
                             k + 1)
    held = (idx[:, k - 1:] >= first) & (idx[:, k - 1:] < first + n)
    return jnp.min(jnp.where(held[:, 0] | held[:, 1],
                             top[:, k - 1] - top[:, k], jnp.inf))


def moe_mlp(cfg, p, x, routed=True, shared=True):
    """The mixture over the experts held here plus the shared experts."""
    b, s, d = x.shape
    t = x.reshape(b * s, d)
    y = jnp.zeros_like(t)
    if routed:
        first, n = cfg["experts_held"]
        idx, w = moe_route(cfg, p, t)

        def add_expert(y, expert):
            """y + (weight of the tokens that chose it) * expert(t); one
            held expert at a time (a scan: one compiled body)."""
            number, gate, up, down = expert
            w_e = jnp.sum(jnp.where(idx == number, w, 0.0), -1)
            return y + w_e[:, None] * swiglu(t, gate, up, down), None

        y, _ = jax.lax.scan(add_expert, y, (
            first + jnp.arange(n), p["e_gate"], p["e_up"], p["e_down"]))
    if shared:
        y = y + swiglu(t, p["s_gate"], p["s_up"], p["s_down"])
    return y.reshape(b, s, d)


def layer_params(params, li):
    pre = f"l{li}_"
    return {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}


def layer(cfg, mlp, p, x, q_block=None, margins=None):
    eps = cfg["rms_norm_eps"]
    x = x + mla_mixer(cfg, p, rms_norm(x, p["norm1"], eps), q_block)
    xn = rms_norm(x, p["norm2"], eps)
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
    for li, mlp in enumerate(layer_kinds(cfg)):
        def run(p, x, mlp=mlp):
            return layer(cfg, mlp, p, x, q_block, margins)
        x = (jax.checkpoint(run) if remat else run)(layer_params(params, li),
                                                    x)
    return rms_norm(x, params["norm_f"], cfg["rms_norm_eps"])


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
