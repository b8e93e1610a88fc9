"""Plain reference of an `afmoe` language model as Trinity-Mini configures
it.

Source: https://huggingface.co/arcee-ai/Trinity-Mini (config.json,
`model_type` `afmoe`), the `afmoe` model of the `transformers` library and
Arcee's Trinity report, whose layers these are: grouped-query attention
with per-head q/k norms, a sliding window of `sliding_window` keys and a
rotation of the whole head in `sliding_attention` layers, no rotation in
`full_attention` layers, a sigmoid gate on the attention's output and an
RMSNorm on each sub-layer's output before its residual add; then a dense
SwiGLU or a sigmoid-scored mixture with one shared expert; an embedding
scaled by sqrt(hidden) (`mup_enabled`) and an untied head. Forward, loss
and (through `jax.grad`) gradients in straightforward `jax.numpy`,
float32, every product at `highest` precision: an explicit rotation, an
explicit softmax over keys and values repeated by group under an explicit
window mask, a loop over experts. No kernel, no cache, no batching trick,
and nothing imported from the system under test. The tests import it from
here.

A chip's share (the `model-configs` guide, section 4): `experts_held =
(first, n)` makes the mixture route over all `num_experts`, renormalise
over all chosen experts, and add only the terms of experts
first..first+n-1; the shared expert is whole, computed on every chip
alike. `vocab_size` is the slice the chip holds, of the embedding and of
the head. `layer_types` and `num_dense_layers` describe the layers that
are kept, in order.

DEPARTURES from the published description, and what it leaves open
(`assumed` in benchmarks/configs/trinity_mini_26b_a3b.json lists the same):
  1. Four features of the family are not keys of config.json and are
     taken from its modeling code and report: the per-head RMSNorms of q
     and k (a learned scale over `head_dim`), no rotation in
     `full_attention` layers, the output gate o * sigmoid(x W_g) with W_g
     (heads x head_dim, hidden), and the sandwich norms.
  2. The selection bias is a buffer: it takes no gradient, starts at 0
     and stays there. `load_balance_coeff` (0.001) is the bias's update a
     step outside the loss; it is no part of a training step here.
  3. One expert group (`n_group`, `num_expert_groups`, `topk_group`,
     `num_limited_groups` all 1), so the router is the plain top-8 of
     sigmoid(score) + bias, renormalised over the chosen (`route_norm`, a
     plain sum) and scaled by `route_scale`; the weights multiply the
     experts' outputs (not their inputs).
  4. Positions are 0..S-1 of each sequence; `rope_scaling` is null; the
     softmax scale is head_dim**-0.5; the window counts the query's own
     position: key j is seen from query i where i - window < j <= i.
  5. `mup_enabled` scales the embedding's output by sqrt(hidden) and
     nothing else.
  6. No bias anywhere; no dropout; no auxiliary or balance loss.
  7. The weights are drawn normal(0, 0.02), norm scales 1: the published
     checkpoint's own initialiser is not part of config.json.
  8. The loss is the mean next-token cross-entropy over the vocabulary
     slice; Adam without weight decay, bias-corrected, eps 1e-8.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
NORMS = ("norm1", "norm2", "post_norm1", "post_norm2", "q_norm", "k_norm")


def layer_kinds(cfg):
    """[(attention kind, mlp)] of the layers that are kept:
    `layer_types[i]` is "sliding_attention" or "full_attention", the first
    `num_dense_layers` have the dense MLP and the rest the mixture."""
    return [(cfg["layer_types"][i],
             "dense" if i < cfg["num_dense_layers"] else "moe")
            for i in range(cfg["num_hidden_layers"])]


def param_shapes(cfg):
    """{name: shape} in a fixed order; matrices are (out, in)."""
    d = cfg["hidden_size"]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = cfg["head_dim"]
    e_all, e_held = cfg["num_experts"], cfg["experts_held"][1]
    wi = cfg["moe_intermediate_size"]
    ws = wi * cfg["num_shared_experts"]
    shapes = {"embed": (cfg["vocab_size"], d)}
    for li, (_, mlp) in enumerate(layer_kinds(cfg)):
        p = f"l{li}_"
        shapes[p + "norm1"] = (d,)
        shapes[p + "wq"] = (h * dh, d)
        shapes[p + "wk"] = (hkv * dh, d)
        shapes[p + "wv"] = (hkv * dh, d)
        shapes[p + "q_norm"] = (dh,)
        shapes[p + "k_norm"] = (dh,)
        shapes[p + "wg"] = (h * dh, d)
        shapes[p + "wo"] = (d, h * dh)
        shapes[p + "post_norm1"] = (d,)
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
        shapes[p + "post_norm2"] = (d,)
    shapes["norm_f"] = (d,)
    shapes["head"] = (cfg["vocab_size"], d)
    return shapes


def init_params(cfg, seed, std=0.02):
    """Seeded weights (numpy, float32): normal(0, std); norm scales 1; the
    router's bias 0."""
    rng = np.random.default_rng([int(seed), 17])
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


def attention(cfg, kind, p, x, q_block=None, window=None, rotate=None,
              gate=True):
    """Grouped-query attention of one layer of `kind`: query head h attends
    to k/v head h // (heads / kv heads); q and k are normalised per head;
    a sliding layer rotates them and sees `sliding_window` keys, a full
    layer rotates nothing and sees every earlier key; the output is gated
    by sigmoid(x W_g). `window`, `rotate` and `gate` override what the
    kind decides (for tests: each is another model)."""
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = cfg["head_dim"]
    b, s, _ = x.shape
    eps = cfg["rms_norm_eps"]
    sliding = kind == "sliding_attention"
    window = window if window is not None else (
        cfg["sliding_window"] if sliding else s)
    rotate = sliding if rotate is None else rotate
    q = rms_norm(mm(x, p["wq"]).reshape(b, s, h, dh), p["q_norm"], eps)
    k = rms_norm(mm(x, p["wk"]).reshape(b, s, hkv, dh), p["k_norm"], eps)
    v = mm(x, p["wv"]).reshape(b, s, hkv, dh)
    if rotate:
        q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
    # every query head gets its group's key and value, explicitly
    k, v = (jnp.repeat(t, h // hkv, axis=2) for t in (k, v))
    scale = dh ** -0.5

    def rows(q_rows, first):
        """Explicit softmax of a block of query rows over every key, where
        query i sees key j only if i - window < j <= i."""
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_rows, k,
                            precision=HIGHEST) * scale
        q_pos = first + jnp.arange(q_rows.shape[1])[:, None]
        k_pos = jnp.arange(s)[None, :]
        mask = (q_pos >= k_pos) & (q_pos - k_pos < window)
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
        o = jnp.moveaxis(o, 0, 1)
    o = o.reshape(b, s, h * dh)
    if gate:
        o = o * jax.nn.sigmoid(mm(x, p["wg"]))
    return mm(o, p["wo"])


def moe_route(cfg, p, x):
    """(chosen experts (T, k), their weights (T, k)) for tokens x (T, D)."""
    scores = jax.nn.sigmoid(mm(x, p["w_r"]))
    _, idx = jax.lax.top_k(scores + jax.lax.stop_gradient(p["r_bias"]),
                           cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg["route_norm"]:
        w = w / jnp.sum(w, -1, keepdims=True)
    return idx, w * cfg["route_scale"]


def route_margin(cfg, p, x):
    """The smallest gap, over every token of x (T, D), between the score of
    the last expert chosen and that of the first passed over. Top-k is a
    step: two float32 implementations agree on it only where this gap is
    well above their rounding, so a comparison picks its sequence by it.
    Every token counts, held experts or not: where two experts that are not
    held here trade places, the forward moves by a sum of two nearly equal
    scores only, but the router's gradient moves from one row of its
    weight to another (a top-8 of 128 has gaps near 1e-7 in a sequence of
    8,192 tokens, and one such trade moved a router's float32 gradient by
    2.2e-3 on a v5e)."""
    k = cfg["num_experts_per_tok"]
    top, _ = jax.lax.top_k(jax.nn.sigmoid(mm(x, p["w_r"])) + p["r_bias"],
                           k + 1)
    return jnp.min(top[:, k - 1] - top[:, k])


def moe_mlp(cfg, p, x, routed=True, shared=True):
    """The mixture over the experts held here plus the shared expert."""
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


def layer(cfg, kinds, p, x, q_block=None, margins=None):
    kind, mlp = kinds
    eps = cfg["rms_norm_eps"]
    a = attention(cfg, kind, p, rms_norm(x, p["norm1"], eps), q_block)
    x = x + rms_norm(a, p["post_norm1"], eps)
    xn = rms_norm(x, p["norm2"], eps)
    if margins is not None and mlp == "moe":
        margins.append(route_margin(cfg, p, xn.reshape(-1, xn.shape[-1])))
    m = swiglu(xn, p["w_gate"], p["w_up"], p["w_down"]) if mlp == "dense" \
        else moe_mlp(cfg, p, xn)
    return x + rms_norm(m, p["post_norm2"], eps)


def hidden_states(cfg, params, tokens, q_block=None, remat=False,
                  margins=None):
    """`q_block` (rows of the softmax at a time) and `remat` (each layer's
    backward recomputes it) change what is kept in memory, not one number:
    they let the published widths fit a chip at 8,192 tokens. A list given
    as `margins` gets each mixture layer's `route_margin` (not with
    `remat`)."""
    x = params["embed"][tokens]
    if cfg.get("mup_enabled"):
        x = x * math.sqrt(cfg["hidden_size"])
    for li, kinds in enumerate(layer_kinds(cfg)):
        def run(p, x, kinds=kinds):
            return layer(cfg, kinds, p, x, q_block, margins)
        x = (jax.checkpoint(run) if remat else run)(layer_params(params, li),
                                                    x)
    return rms_norm(x, params["norm_f"], cfg["rms_norm_eps"])


def logits(cfg, params, tokens, q_block=None, remat=False, margins=None):
    """(B, S, vocab) float32 logits of tokens (B, S) int over the head."""
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
