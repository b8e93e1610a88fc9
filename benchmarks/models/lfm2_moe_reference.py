"""Plain reference of an `lfm2_moe` language model as LFM2-8B-A1B
configures it.

Source: https://huggingface.co/LiquidAI/LFM2-8B-A1B (config.json,
`model_type` `lfm2_moe`), the LFM2 technical report and model card (Liquid
AI, 2025) and the `lfm2_moe` model of the `transformers` library, whose
layers these are: a gated short convolution or grouped-query attention
with per-head q/k norms and a rotation of the whole head, then a dense
SwiGLU or a sigmoid-scored mixture without a shared expert; tied
embeddings. Forward, loss and (through `jax.grad`) gradients in
straightforward `jax.numpy`, float32, every product at `highest`
precision: an explicit shifted-sum convolution, an explicit rotation, an
explicit softmax over keys and values repeated by group, a loop over
experts. No kernel, no cache, no batching trick, and nothing imported from
the system under test. `benchmarks/models/lfm2_moe_reference.py` is a copy
of this file (`tests/test_lfm2_moe.py` holds the two equal).

A chip's share (the `model-configs` guide, section 4): `experts_held =
(first, n)` makes the mixture route over all `num_experts`, renormalise
over all chosen experts, and add only the terms of experts
first..first+n-1; there is no shared expert, so nothing is computed on
every chip alike. `vocab_size` is the slice the chip holds, of the
embedding and (the same matrix) of the head. `layer_types` and
`num_dense_layers` describe the layers that are kept, in order.

DEPARTURES from the published description, and what it leaves open
(`assumed` in benchmarks/configs/lfm2_8b_a1b.json lists the same):
  1. `tie_word_embeddings` is taken as true: config.json's catalog row has
     no such key, the family ties, and the card's 8.3B is the tied count.
  2. The renormalising sum of the chosen scores is the plain sum; the
     family's code adds 1e-6 to it (5e-7 of a weight at four sigmoid
     scores near a half).
  3. The selection bias (`use_expert_bias`) is a buffer: it takes no
     gradient, starts at 0 and stays there (its update rule is not in
     config.json and is no part of a training step here).
  4. Positions are 0..S-1 of each sequence; no rope scaling; the softmax
     scale is 64**-0.5.
  5. No bias anywhere (`conv_bias` false); no dropout; no auxiliary or
     balance loss.
  6. The weights are drawn normal(0, 0.02), norm scales 1, the taps of the
     convolution like every other weight: the published checkpoint's own
     initialiser is not part of config.json.
  7. The loss is the mean next-token cross-entropy over the vocabulary
     slice; Adam without weight decay, bias-corrected, eps 1e-8.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
NORMS = ("norm1", "norm2", "q_norm", "k_norm")


def layer_kinds(cfg):
    """[(operator, mlp)] of the layers that are kept: `layer_types[i]` is
    "conv" or "full_attention", the first `num_dense_layers` have the
    dense MLP and the rest the mixture."""
    return [(cfg["layer_types"][i],
             "dense" if i < cfg["num_dense_layers"] else "moe")
            for i in range(cfg["num_hidden_layers"])]


def param_shapes(cfg):
    """{name: shape} in a fixed order; matrices are (out, in). There is no
    head: the embedding matrix is the head."""
    d = cfg["hidden_size"]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = d // h
    e_all, e_held = cfg["num_experts"], cfg["experts_held"][1]
    wi = cfg["moe_intermediate_size"]
    shapes = {"embed": (cfg["vocab_size"], d)}
    for li, (operator, mlp) in enumerate(layer_kinds(cfg)):
        p = f"l{li}_"
        shapes[p + "norm1"] = (d,)
        if operator == "conv":
            shapes[p + "w_in"] = (3 * d, d)
            shapes[p + "taps"] = (d, cfg["conv_L_cache"])
            shapes[p + "w_out"] = (d, d)
        else:
            shapes[p + "wq"] = (h * dh, d)
            shapes[p + "wk"] = (hkv * dh, d)
            shapes[p + "wv"] = (hkv * dh, d)
            shapes[p + "q_norm"] = (dh,)
            shapes[p + "k_norm"] = (dh,)
            shapes[p + "wo"] = (d, h * dh)
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
    shapes["norm_f"] = (d,)
    return shapes


def init_params(cfg, seed, std=0.02):
    """Seeded weights (numpy, float32): normal(0, std); norm scales 1; the
    router's bias 0."""
    rng = np.random.default_rng([int(seed), 13])
    out = {}
    for name, shape in param_shapes(cfg).items():
        short = name.split("_", 1)[-1] if name.startswith("l") else name
        if short in NORMS or name == "norm_f":
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


def short_conv(z, taps):
    """c_t = sum_j taps[:, j] * z_(t - (L - 1) + j) for z (B, S, C) and
    taps (C, L): depthwise, causal, zeros before the start, no activation.
    An explicit sum of shifted copies."""
    s, length = z.shape[1], taps.shape[1]
    padded = jnp.concatenate(
        [jnp.zeros(z.shape[:1] + (length - 1,) + z.shape[2:], z.dtype), z], 1)
    c = jnp.zeros_like(z)
    for j in range(length):
        c = c + taps[:, j] * padded[:, j:j + s]
    return c


def conv_operator(cfg, p, x):
    """[B, C, u] = W_in x; y = C * conv(B * u); out = W_out y."""
    b_gate, c_gate, u = jnp.split(mm(x, p["w_in"]), 3, axis=-1)
    return mm(c_gate * short_conv(b_gate * u, p["taps"]), p["w_out"])


def rope(t, theta, interleave=False):
    """Rotate t (B, S, ..., R) by position over all R dims: for position p
    and pair i the angle is a = p * theta^(-2i/R), and the pair (t0, t1)
    becomes (t0 cos a - t1 sin a, t0 sin a + t1 cos a). The pairs are the
    halves (t[i], t[i + R/2]) as this family rotates them; `interleave`
    pairs (t[2i], t[2i+1]) instead, for tests. The frequencies are rounded
    to float32 once; angle, cosine and sine are float32."""
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


def gqa_operator(cfg, p, x, q_block=None):
    """Grouped-query attention: query head h attends to k/v head
    h // (heads / kv heads); q and k are normalised per head, then
    rotated."""
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    b, s, d = x.shape
    dh = d // h
    eps, theta = cfg["norm_eps"], cfg["rope_theta"]
    q = rms_norm(mm(x, p["wq"]).reshape(b, s, h, dh), p["q_norm"], eps)
    k = rms_norm(mm(x, p["wk"]).reshape(b, s, hkv, dh), p["k_norm"], eps)
    v = mm(x, p["wv"]).reshape(b, s, hkv, dh)
    q, k = rope(q, theta), rope(k, theta)
    # every query head gets its group's key and value, explicitly
    k, v = (jnp.repeat(t, h // hkv, axis=2) for t in (k, v))
    scale = dh ** -0.5

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
        blocks = jnp.moveaxis(q.reshape(b, s // q_block, q_block, h, dh), 1, 0)
        o = jax.lax.map(lambda blk: jax.checkpoint(rows)(*blk),
                        (blocks, jnp.arange(0, s, q_block)))
        o = jnp.moveaxis(o, 0, 1).reshape(b, s, h, dh)
    return mm(o.reshape(b, s, h * dh), p["wo"])


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


def moe_mlp(cfg, p, x):
    """The mixture over the experts held here; no shared expert."""
    b, s, d = x.shape
    t = x.reshape(b * s, d)
    first, n = cfg["experts_held"]
    idx, w = moe_route(cfg, p, t)

    def add_expert(y, expert):
        """y + (weight of the tokens that chose it) * expert(t); one held
        expert at a time (a scan: one compiled body)."""
        number, gate, up, down = expert
        w_e = jnp.sum(jnp.where(idx == number, w, 0.0), -1)
        return y + w_e[:, None] * swiglu(t, gate, up, down), None

    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(t), (
        first + jnp.arange(n), p["e_gate"], p["e_up"], p["e_down"]))
    return y.reshape(b, s, d)


def layer_params(params, li):
    pre = f"l{li}_"
    return {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}


def layer(cfg, kinds, p, x, q_block=None, margins=None):
    operator, mlp = kinds
    eps = cfg["norm_eps"]
    xn = rms_norm(x, p["norm1"], eps)
    x = x + (conv_operator(cfg, p, xn) if operator == "conv"
             else gqa_operator(cfg, p, xn, q_block))
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
    for li, kinds in enumerate(layer_kinds(cfg)):
        def run(p, x, kinds=kinds):
            return layer(cfg, kinds, p, x, q_block, margins)
        x = (jax.checkpoint(run) if remat else run)(layer_params(params, li),
                                                    x)
    return rms_norm(x, params["norm_f"], cfg["norm_eps"])


def logits(cfg, params, tokens, q_block=None, remat=False, margins=None):
    """(B, S, vocab) float32 logits of tokens (B, S) int: the head is the
    embedding matrix."""
    return mm(hidden_states(cfg, params, tokens, q_block, remat, margins),
              params["embed"])


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
