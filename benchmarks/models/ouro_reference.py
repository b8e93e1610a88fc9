"""Plain reference of an `ouro` language model as Ouro-2.6B configures it.

Source: https://huggingface.co/ByteDance/Ouro-2.6B (config.json,
`model_type` `ouro`) and Zhu et al. 2025, "Scaling Latent Reasoning via
Looped Language Models" (arXiv:2510.25741): one stack of decoder layers
applied `total_ut_steps` T times with the same weights, the final norm
after every pass, a head and an exit gate on every pass's output, trained
on the expected cross-entropy under the exit distribution less an entropy
bonus. Each layer: multi-head attention (16 heads of 128 over 16 k/v heads)
with a rotation of the whole head in every layer and no q/k norm, then a
dense SwiGLU, with an RMSNorm before and after each sub-layer (sandwich
norms); an unscaled embedding and an untied head. Forward, loss and
(through `jax.grad`) gradients in straightforward `jax.numpy`, float32,
every product at `highest` precision: an explicit rotation, an explicit
softmax over keys under a causal mask, the passes a scan. No
kernel, no cache, no batching trick, and nothing imported from the system
under test. The tests import it from here.

For each token, N an RMSNorm with a learned scale and eps `rms_norm_eps`:

  h(0) = E[x]
  h(t) = N_f( B_L(... B_1(h(t-1)) ...) ),  t = 1..T
  B(x): u = x + N_a2( W_o MHA(RoPE(W_q N_a1(x)), RoPE(W_k N_a1(x)),
                              W_v N_a1(x)) )
        y = u + N_m2( W_down( silu(W_gate N_m1(u)) * W_up N_m1(u) ) )
  CE_t = -log softmax(W_head h(t))[label]
  lambda_t = sigmoid(w_g . h(t) + b_g),  t = 1..T-1
  p_1 = lambda_1;  p_t = lambda_t prod_{j<t} (1 - lambda_j);
  p_T = prod_{j<T} (1 - lambda_j)
  loss = sum_t p_t CE_t - beta H(p),  H(p) = -sum_t p_t log p_t

DEPARTURES from the published description, and what it leaves open
(`assumed` in benchmarks/configs/ouro_2_6b.json lists the same):
  1. beta = 0.1 (`BETA`, Ouro's stage-I setting): a training setting, no
     key of config.json.
  2. The norm between passes (its output feeds the next pass) and the gate
     reading the normed h(t) follow the family's modeling code
     (`modeling_ouro.py`), which is not part of config.json.
  3. No bias in the attention or the MLP, and no q/k norm: the config has
     no `attention_bias` key; the gate is a Linear(hidden -> 1) with bias.
  4. Positions are 0..S-1 of each sequence, every pass alike; rope_scaling
     is null; the softmax scale is head_dim**-0.5; the pairs rotated are
     the halves of the head.
  5. The weights are drawn normal(0, 0.02), norm scales 1, the gate's bias
     0: the published checkpoint's own initialiser is not in config.json.
  6. The loss is the mean over positions of each position's objective;
     Adam without weight decay, bias-corrected, eps 1e-8.
  7. Every token runs all T passes (`early_exit_threshold` 1): the logits
     of the model are the last pass's.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
NORMS = ("norm1", "norm2", "post_norm1", "post_norm2")
BETA = 0.1


def param_shapes(cfg):
    """{name: shape} in a fixed order; matrices are (out, in)."""
    d = cfg["hidden_size"]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh, f = cfg["head_dim"], cfg["intermediate_size"]
    shapes = {"embed": (cfg["vocab_size"], d)}
    for li in range(cfg["num_hidden_layers"]):
        p = f"l{li}_"
        shapes[p + "norm1"] = (d,)
        shapes[p + "wq"] = (h * dh, d)
        shapes[p + "wk"] = (hkv * dh, d)
        shapes[p + "wv"] = (hkv * dh, d)
        shapes[p + "wo"] = (d, h * dh)
        shapes[p + "post_norm1"] = (d,)
        shapes[p + "norm2"] = (d,)
        shapes[p + "w_gate"] = (f, d)
        shapes[p + "w_up"] = (f, d)
        shapes[p + "w_down"] = (d, f)
        shapes[p + "post_norm2"] = (d,)
    shapes["norm_f"] = (d,)
    shapes["head"] = (cfg["vocab_size"], d)
    if cfg["total_ut_steps"] > 1:
        shapes["exit_gate"] = (1, d)
        shapes["exit_gate_bias"] = (1,)
    return shapes


def init_params(cfg, seed, std=0.02):
    """Seeded weights (numpy, float32): normal(0, std); norm scales 1; the
    gate's bias 0."""
    rng = np.random.default_rng([int(seed), 17])
    out = {}
    for name, shape in param_shapes(cfg).items():
        short = name.split("_", 1)[-1] if name.startswith("l") else name
        if short in NORMS or name == "norm_f":
            out[name] = np.ones(shape, np.float32)
        elif name == "exit_gate_bias":
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


def rope(t, theta):
    """Rotate t (B, S, H, R) by position over all R dims: for position p
    and pair i the angle is a = p * theta^(-2i/R), and the pair (t[i],
    t[i + R/2]) becomes (t0 cos a - t1 sin a, t0 sin a + t1 cos a). The
    frequencies are rounded to float32 once; angle, cosine and sine are
    float32."""
    s, r = t.shape[1], t.shape[-1]
    freq = jnp.asarray(np.float32(float(theta) ** (-np.arange(0, r, 2) / r)))
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * freq     # (S, R/2)
    angle = angle.reshape(1, s, 1, r // 2)
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    t0, t1 = t[..., :r // 2], t[..., r // 2:]
    return jnp.concatenate([t0 * cos - t1 * sin, t0 * sin + t1 * cos], -1)


def attention(cfg, p, x, q_block=None, qk_norm=False):
    """Causal multi-head attention of one layer, every head rotated; query
    head h attends to k/v head h // (heads / kv heads). `qk_norm` puts an
    RMSNorm (scale 1) over each q and k head: another model, for tests."""
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = cfg["head_dim"]
    b, s, _ = x.shape
    q = mm(x, p["wq"]).reshape(b, s, h, dh)
    k = mm(x, p["wk"]).reshape(b, s, hkv, dh)
    v = mm(x, p["wv"]).reshape(b, s, hkv, dh)
    if qk_norm:
        one = jnp.ones((dh,), jnp.float32)
        q = rms_norm(q, one, cfg["rms_norm_eps"])
        k = rms_norm(k, one, cfg["rms_norm_eps"])
    q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
    k, v = (jnp.repeat(t, h // hkv, axis=2) for t in (k, v))
    scale = dh ** -0.5

    def rows(q_rows, first):
        """Explicit softmax of a block of query rows over every earlier
        key."""
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_rows, k,
                            precision=HIGHEST) * scale
        q_pos = first + jnp.arange(q_rows.shape[1])[:, None]
        scores = jnp.where(q_pos >= jnp.arange(s)[None, :], scores, -jnp.inf)
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
    return mm(o.reshape(b, s, h * dh), p["wo"])


def layer_params(params, li):
    pre = f"l{li}_"
    return {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}


def layer(cfg, p, x, q_block=None, sandwich=True, qk_norm=False):
    """One block; `sandwich` False drops the norms after each sub-layer
    (another model, for tests)."""
    eps = cfg["rms_norm_eps"]

    def out(y, scale):
        return rms_norm(y, scale, eps) if sandwich else y

    a = attention(cfg, p, rms_norm(x, p["norm1"], eps), q_block, qk_norm)
    x = x + out(a, p["post_norm1"])
    m = swiglu(rms_norm(x, p["norm2"], eps), p["w_gate"], p["w_up"],
               p["w_down"])
    return x + out(m, p["post_norm2"])


def pass_states(cfg, params, tokens, q_block=None, remat=False, passes=None,
                norm_between=True, sandwich=True, qk_norm=False):
    """[h(1), ..., h(T)] (B, S, hidden): the final norm's output after each
    pass. The passes are a `lax.scan` over the same weights, closed over,
    so the backward adds each weight's cotangent into one sum as it goes
    (as a Python loop the gradient program kept a copy for each pass: 10.8
    GiB of temporaries at the cell's size, compile for a v5e). `q_block`
    (rows of the softmax at a time) and `remat` (each layer's backward
    recomputes it) change what is kept in memory, not one number. The
    others are other models, for tests: `passes` in place of
    `total_ut_steps`; `norm_between` False feeds the next pass the stack's
    output before the final norm."""
    passes = cfg["total_ut_steps"] if passes is None else passes
    eps = cfg["rms_norm_eps"]

    def run(p, x):
        return layer(cfg, p, x, q_block, sandwich, qk_norm)

    def one_pass(x, _):
        for li in range(cfg["num_hidden_layers"]):
            x = (jax.checkpoint(run) if remat else run)(
                layer_params(params, li), x)
        h = rms_norm(x, params["norm_f"], eps)
        return (h if norm_between else x), h

    _, states = jax.lax.scan(one_pass, params["embed"][tokens], None,
                             length=passes)
    return [states[t] for t in range(passes)]


def exit_log_probs(params, states):
    """log p (B, S, T): the exit distribution of the gates on h(1..T-1),
    log lambda_t + sum_{j<t} log(1 - lambda_j)."""
    log_p, log_stay = [], 0.0
    for h in states[:-1]:
        z = mm(h, params["exit_gate"])[..., 0] + params["exit_gate_bias"][0]
        log_p.append(log_stay + jax.nn.log_sigmoid(z))
        log_stay = log_stay + jax.nn.log_sigmoid(-z)
    log_p.append(log_stay + jnp.zeros(states[-1].shape[:-1], jnp.float32))
    return jnp.stack(log_p, -1)


def losses_of_logits(lg, labels):
    """(B, S) cross-entropy of each position's logits against its label."""
    lse = jax.nn.logsumexp(lg, axis=-1)
    return lse - jnp.take_along_axis(lg, labels[..., None], -1)[..., 0]


def head_losses(params, h, labels, remat=False, block=None):
    """(B, S) cross-entropy of one pass. `block` (rows of the logits at a
    time) and `remat` (the backward recomputes the logits) change what is
    kept in memory, not one number: a pass's logits are 1.6 GB at the
    cell's size, and the gradient program over four passes needed 13.9 GiB
    of temporaries with them whole (compile for a v5e)."""
    def ce(head, h, labels):
        return losses_of_logits(mm(h, head), labels)
    b, s, d = h.shape
    if block is None or block >= s or s % block:
        return (jax.checkpoint(ce) if remat else ce)(params["head"], h,
                                                     labels)
    rows = (jnp.moveaxis(h.reshape(b, s // block, block, d), 1, 0),
            jnp.moveaxis(labels.reshape(b, s // block, block), 1, 0))
    out = jax.lax.map(lambda blk: jax.checkpoint(ce)(params["head"], *blk),
                      rows)
    return jnp.moveaxis(out, 0, 1).reshape(b, s)


def objective(cfg, params, states, labels, remat=False, last_only=False,
              block=None):
    """((B, S) loss of each position, p (B, S, T)). `last_only` is the
    control that trains the last pass's plain cross-entropy."""
    log_p = exit_log_probs(params, states)
    p = jnp.exp(log_p)
    if last_only:
        return head_losses(params, states[-1], labels, remat, block), p
    ce = jnp.stack([head_losses(params, h, labels, remat, block)
                    for h in states], -1)
    return jnp.sum(p * (ce + BETA * log_p), -1), p


def forward(cfg, params, tokens, labels, q_block=None, remat=False,
            last_only=False, **model):
    """(the last pass's logits (B, S, vocab), the loss of each position
    (B, S), p (B, S, T)); `model` takes `pass_states`' controls; the
    heads' cross-entropies go `q_block` rows at a time too."""
    states = pass_states(cfg, params, tokens, q_block, remat, **model)
    losses, p = objective(cfg, params, states, labels, remat, last_only,
                          q_block)
    return mm(states[-1], params["head"]), losses, p


def logits(cfg, params, tokens, q_block=None, remat=False, **model):
    """(B, S, vocab) float32 logits of the last pass."""
    return mm(pass_states(cfg, params, tokens, q_block, remat, **model)[-1],
              params["head"])


def token_losses(cfg, params, tokens, labels, q_block=None, remat=False,
                 **model):
    states = pass_states(cfg, params, tokens, q_block, remat, **model)
    return objective(cfg, params, states, labels, remat, block=q_block)[0]


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
    """The same weights under the names of the system's model
    (`mxnet_tpu.gluon.nn.DecoderLM`): `prefix` + name, the same layouts."""
    return {prefix + name: value for name, value in params.items()}


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
