"""A decoder language model assembled from a configuration's keys.

One pre-norm block, `x <- x + mixer(RMSNorm(x))`, `x <- x + mlp(RMSNorm(x))`,
whose mixer and MLP kinds are read per layer from the published keys of a
model's config.json (ROADMAP R0). A config with `layer_types` names each
layer's mixer there (0-based, as that list counts): `"conv"` is the gated
short convolution, `"full_attention"` grouped-query attention with per-head
q/k norms and a rotation of the whole head. Otherwise
`linear_attn_config.kda_layers` / `full_attn_layers` (1-based, as
config.json counts them) pick Kimi Delta Attention or multi-head latent
attention, and a config without `linear_attn_config` is latent attention in
every layer. `num_dense_layers` or `first_k_dense_replace` picks the dense
SwiGLU or the mixture of experts. Latent attention rotates its
`qk_rope_head_dim` dims (`rope_theta`, `rope_interleave`) unless the config
says `mla_use_nope`. The mixture's keys are read in any of three spellings
(`MIXTURE_KEYS`), the norms' epsilon in two (`rms_norm_eps`, `norm_eps`).
`tie_word_embeddings` makes the head the embedding matrix: one parameter
with two uses, whose gradient is the sum of both. Users: Kimi-Linear-48B-A3B
(arXiv:2510.26692), `deepseek_v3` configs (Kanana-2-30B-A3B) and `lfm2_moe`
configs (LFM2-8B-A1B). What a config asks for and no layer here computes
raises NotImplementedError with the key's name. `TransformerEncoder`
(transformer.py) is the older, hard-wired block and stays as it is.

A chip's share of a deployment: `experts_held = (first, n)` makes every
mixture layer route over all `num_experts` and compute the terms of the n
experts it holds; `vocab_size` is the slice of the vocabulary held here.

Parameters are named `<prefix>l<i>_<name>`; matrices are (out, in), the
experts (E, in, out) as `ops/lm.py` multiplies them (down: in is the
expert's width). Weights default to normal(0, 0.02), norm scales to 1,
`A_log`, `dt_bias` and the router's bias to 0. Under a Symbol every block
tags its nodes with `profiler_scope` (the executor turns it into a `jax.named_scope`, so the
profiler's operation metadata names the mechanism, forward and backward)
and each layer with a `mirror_stage` of its own, the unit
`MXNET_BACKWARD_DO_MIRROR` rematerialises.
"""
from __future__ import annotations

from ... import initializer
from ...base import AttrScope
from ..block import HybridBlock

__all__ = ["KDAMixer", "MLAMixer", "ShortConvMixer", "GQAMixer", "SwiGLU",
           "MoEMLP", "DecoderBlock", "DecoderLM"]


def _dense(F, x, weight, units):
    return F.FullyConnected(x, weight, num_hidden=units, no_bias=True,
                            flatten=False)


def _getter(block):
    """params.get with this file's defaults: `init` None is normal(0,
    0.02), else a registered name ("ones", "zeros")."""
    def get(name, shape, init=None):
        return block.params.get(
            name, shape=shape, init=init or initializer.Normal(0.02))
    return get


def _silu(F, x):
    return x * F.sigmoid(x)


def _scope(name):
    return AttrScope(profiler_scope=name)


# the mixture's settings: (Kimi-Linear's key, DeepSeek-V3's key); LFM2's
# third spelling takes a key from either (`num_experts`,
# `num_experts_per_tok`, `norm_topk_prob`), has none for shared experts and
# one of its own for the selection bias
MIXTURE_KEYS = {
    "num_experts": ("num_experts", "n_routed_experts"),
    "top_k": ("num_experts_per_token", "num_experts_per_tok"),
    "num_shared": ("num_shared_experts", "n_shared_experts"),
    "renormalize": ("moe_renormalize", "norm_topk_prob"),
    "scoring": ("moe_router_activation_func", "scoring_func"),
    "groups": ("num_expert_group", "n_group"),
    "method": ("topk_method", "topk_method"),
    "bias": ("use_expert_bias", "use_expert_bias"),
}
# what `ops/lm.py::moe_route` computes: sigmoid scores, one group, the top
# k of score + bias (a buffer that takes no gradient)
_ROUTER_COMPUTES = {"scoring": "sigmoid", "groups": 1, "method": "noaux_tc",
                    "bias": True}


def mixture_settings(cfg):
    """{setting: value} of the mixture layers, from whichever spelling the
    config uses; a router other than the one computed here raises by the
    key's name. A config silent on the router gets the one computed here;
    one silent on its shared experts has none only in LFM2's spelling (the
    family with `use_expert_bias` has no key for them), and a KeyError in
    Kimi's or DeepSeek-V3's."""
    silent = dict(_ROUTER_COMPUTES)
    if "use_expert_bias" in cfg:
        silent["num_shared"] = 0
    out = {}
    for name, keys in MIXTURE_KEYS.items():
        key = next((k for k in keys if k in cfg), None)
        if key is None and name not in silent:
            raise KeyError(f"mixture of experts: the config has neither "
                           f"{keys[0]!r} nor {keys[1]!r}")
        out[name] = cfg[key] if key else silent[name]
        if out[name] != _ROUTER_COMPUTES.get(name, out[name]):
            raise NotImplementedError(
                f"mixture of experts: {key} = {out[name]!r} (computed "
                f"here: {_ROUTER_COMPUTES[name]!r})")
    return out


def _either(cfg, *keys):
    """The value under the first of `keys` that the config has (one
    setting, spelled differently by family); the last one's KeyError."""
    return cfg[next((k for k in keys if k in cfg), keys[-1])]


def _eps(cfg):
    return _either(cfg, "rms_norm_eps", "norm_eps")


class KDAMixer(HybridBlock):
    """Kimi Delta Attention: gated delta-rule linear attention with short
    convolutions, a low-rank per-channel decay and a low-rank output gate."""

    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        lin = cfg["linear_attn_config"]
        d = cfg["hidden_size"]
        self._h, self._dk = lin["num_heads"], lin["head_dim"]
        self._kw = lin["short_conv_kernel_size"]
        self._d, self._eps = d, cfg["rms_norm_eps"]
        c, low = self._h * self._dk, self._dk
        get = _getter(self)
        for n in ("q", "k", "v"):
            setattr(self, f"w{n}", get(f"w{n}", shape=(c, d)))
            setattr(self, f"conv_{n}", get(f"conv_{n}", shape=(c, self._kw)))
        self.w_fa = get("w_fa", shape=(low, d))
        self.w_fb = get("w_fb", shape=(c, low))
        self.A_log = get("A_log", shape=(self._h,), init="zeros")
        self.dt_bias = get("dt_bias", shape=(c,), init="zeros")
        self.w_b = get("w_b", shape=(self._h, d))
        self.w_ga = get("w_ga", shape=(low, d))
        self.w_gb = get("w_gb", shape=(c, low))
        self.o_norm = get("o_norm", shape=(self._dk,), init="ones")
        self.wo = get("wo", shape=(d, c))

    def hybrid_forward(self, F, x, wq, wk, wv, conv_q, conv_k, conv_v, w_fa,
                       w_fb, A_log, dt_bias, w_b, w_ga, w_gb, o_norm, wo):
        h, dk = self._h, self._dk
        c, low = h * dk, dk
        with _scope("mx.kda"):
            o = F._contrib_kda(
                _dense(F, x, wq, c), _dense(F, x, wk, c), _dense(F, x, wv, c),
                _dense(F, _dense(F, x, w_fa, low), w_fb, c),
                _dense(F, x, w_b, h), conv_q, conv_k, conv_v, A_log, dt_bias,
                num_heads=h, kernel=self._kw)
            gate = F.sigmoid(_dense(F, _dense(F, x, w_ga, low), w_gb, c))
            o = F.RMSNorm(F.reshape(o, shape=(0, 0, h, dk)), o_norm,
                          eps=self._eps)
            o = F.reshape(o, shape=(0, 0, -1)) * gate
            return _dense(F, o, wo, self._d)


class MLAMixer(HybridBlock):
    """Multi-head latent attention: keys and values come up from a shared
    latent of `kv_lora_rank`; the `qk_rope_head_dim` extra key dims are
    shared by all heads. Query/key heads of nope + rope dims, value heads
    of v dims. The rope dims of every query head, and of the one key
    before the heads share it, are rotated by position (`_contrib_rope`)
    unless the config says `mla_use_nope`."""

    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        for key in ("q_lora_rank", "rope_scaling", "attention_bias"):
            if cfg.get(key):
                raise NotImplementedError(f"MLAMixer: {key} = {cfg[key]!r}")
        self._rope = None if cfg.get("mla_use_nope") else dict(
            rotary_dim=cfg["qk_rope_head_dim"], theta=cfg["rope_theta"],
            interleave=bool(cfg.get("rope_interleave", True)))
        d = cfg["hidden_size"]
        self._h = cfg["num_attention_heads"]
        self._dn, self._dp, self._dv = (cfg["qk_nope_head_dim"],
                                        cfg["qk_rope_head_dim"],
                                        cfg["v_head_dim"])
        self._r, self._d, self._eps = (cfg["kv_lora_rank"], d,
                                       cfg["rms_norm_eps"])
        h, get = self._h, _getter(self)
        self.wq = get("wq", shape=(h * (self._dn + self._dp), d))
        self.w_kva = get("w_kva", shape=(self._r + self._dp, d))
        self.kv_norm = get("kv_norm", shape=(self._r,), init="ones")
        self.w_kvb = get("w_kvb", shape=(h * (self._dn + self._dv), self._r))
        self.wo = get("wo", shape=(d, h * self._dv))

    def hybrid_forward(self, F, x, wq, w_kva, kv_norm, w_kvb, wo):
        h, dn, dp, dv, r = self._h, self._dn, self._dp, self._dv, self._r

        def heads(t, width):            # (B, S, H*w) -> (B, H, S, w)
            return F.transpose(F.reshape(t, shape=(0, 0, h, width)),
                               axes=(0, 2, 1, 3))

        with _scope("mx.mla"):
            q = heads(_dense(F, x, wq, h * (dn + dp)), dn + dp)
            kva = _dense(F, x, w_kva, r + dp)
            c_kv = F.slice_axis(kva, axis=-1, begin=0, end=r)
            k_pe = F.slice_axis(kva, axis=-1, begin=r, end=r + dp)
            kvb = heads(_dense(F, F.RMSNorm(c_kv, kv_norm, eps=self._eps),
                               w_kvb, h * (dn + dv)), dn + dv)
            if self._rope:
                with _scope("mx.mla.rope"):
                    q = F._contrib_rope(q, offset=dn, **self._rope)
                    k_pe = F._contrib_rope(k_pe, **self._rope)
            k_pe = F.broadcast_axis(F.expand_dims(k_pe, axis=1), axis=1,
                                    size=h)
            k = F.concat(F.slice_axis(kvb, axis=-1, begin=0, end=dn), k_pe,
                         dim=3)
            v = F.slice_axis(kvb, axis=-1, begin=dn, end=dn + dv)
            o = F._contrib_flash_attention(q, k, v, causal=True,
                                           scale=(dn + dp) ** -0.5)
            o = F.reshape(F.transpose(o, axes=(0, 2, 1, 3)),
                          shape=(0, 0, -1))
            return _dense(F, o, wo, self._d)


class ShortConvMixer(HybridBlock):
    """LFM2's gated short convolution: `[B, C, u] = W_in x`, a depthwise
    causal convolution of `conv_L_cache` taps over `B * u`, gated by `C`,
    then `W_out`. No bias, no activation."""

    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        from ...ops.lm import SHORT_CONV_MAX_TAPS
        if cfg.get("conv_bias"):
            raise NotImplementedError(
                f"ShortConvMixer: conv_bias = {cfg['conv_bias']!r}")
        self._kw = cfg["conv_L_cache"]
        if not 1 <= self._kw <= SHORT_CONV_MAX_TAPS:
            raise NotImplementedError(
                f"ShortConvMixer: conv_L_cache = {self._kw!r} "
                f"(_contrib_gated_short_conv takes 1 to "
                f"{SHORT_CONV_MAX_TAPS} taps)")
        self._d = d = cfg["hidden_size"]
        get = _getter(self)
        self.w_in = get("w_in", shape=(3 * d, d))
        self.taps = get("taps", shape=(d, self._kw))
        self.w_out = get("w_out", shape=(d, d))

    def hybrid_forward(self, F, x, w_in, taps, w_out):
        d = self._d
        with _scope("mx.sconv"):
            y = F._contrib_gated_short_conv(_dense(F, x, w_in, 3 * d), taps,
                                            kernel=self._kw)
            return _dense(F, y, w_out, d)


class GQAMixer(HybridBlock):
    """Grouped-query attention as LFM2 has it: `num_attention_heads` query
    heads over `num_key_value_heads` key/value heads of hidden / heads dims
    (query head h attends to k/v head h // group), an RMSNorm with a learned
    scale over each query and key head, then the rotation of the whole head
    by position, halves paired (`_contrib_rope`, `interleave` false). k and
    v reach `_contrib_flash_attention` at their own head count: a group's
    shared head is never repeated."""

    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        for key in ("rope_scaling", "attention_bias", "sliding_window"):
            if cfg.get(key):
                raise NotImplementedError(f"GQAMixer: {key} = {cfg[key]!r}")
        d = cfg["hidden_size"]
        self._h, self._hkv = (cfg["num_attention_heads"],
                              cfg["num_key_value_heads"])
        if d % self._h or self._h % self._hkv:
            raise ValueError(
                f"GQAMixer: hidden_size {d}, num_attention_heads {self._h}, "
                f"num_key_value_heads {self._hkv} do not divide")
        self._dh, self._d, self._eps = d // self._h, d, _eps(cfg)
        self._theta = cfg["rope_theta"]
        get = _getter(self)
        self.wq = get("wq", shape=(self._h * self._dh, d))
        self.wk = get("wk", shape=(self._hkv * self._dh, d))
        self.wv = get("wv", shape=(self._hkv * self._dh, d))
        self.q_norm = get("q_norm", shape=(self._dh,), init="ones")
        self.k_norm = get("k_norm", shape=(self._dh,), init="ones")
        self.wo = get("wo", shape=(d, self._h * self._dh))

    def hybrid_forward(self, F, x, wq, wk, wv, q_norm, k_norm, wo):
        h, hkv, dh = self._h, self._hkv, self._dh

        def heads(t, n):                # (B, S, n*dh) -> (B, n, S, dh)
            return F.transpose(F.reshape(t, shape=(0, 0, n, dh)),
                               axes=(0, 2, 1, 3))

        with _scope("mx.gqa"):
            q = F.RMSNorm(heads(_dense(F, x, wq, h * dh), h), q_norm,
                          eps=self._eps)
            k = F.RMSNorm(heads(_dense(F, x, wk, hkv * dh), hkv), k_norm,
                          eps=self._eps)
            v = heads(_dense(F, x, wv, hkv * dh), hkv)
            with _scope("mx.gqa.rope"):
                q, k = (F._contrib_rope(t, rotary_dim=dh, offset=0,
                                        theta=self._theta, interleave=False)
                        for t in (q, k))
            o = F._contrib_flash_attention(q, k, v, causal=True,
                                           scale=dh ** -0.5)
            o = F.reshape(F.transpose(o, axes=(0, 2, 1, 3)),
                          shape=(0, 0, -1))
            return _dense(F, o, wo, self._d)


class SwiGLU(HybridBlock):
    """down(SiLU(gate x) * up x); `names` are the three parameters' names."""

    def __init__(self, units, hidden, names=("w_gate", "w_up", "w_down"),
                 **kwargs):
        super().__init__(**kwargs)
        self._units, self._hidden = units, hidden
        get = _getter(self)
        self.w_gate = get(names[0], shape=(hidden, units))
        self.w_up = get(names[1], shape=(hidden, units))
        self.w_down = get(names[2], shape=(units, hidden))

    def hybrid_forward(self, F, x, w_gate, w_up, w_down):
        hid = _silu(F, _dense(F, x, w_gate, self._hidden)) * \
            _dense(F, x, w_up, self._hidden)
        return _dense(F, hid, w_down, self._units)


class MoEMLP(HybridBlock):
    """Sigmoid-routed mixture: the experts held here (grouped products over
    the token-expert pairs that fall on them) plus the shared expert, whole,
    where the config has one (no branch is built at 0 shared experts).
    Returns (y, stats); stats as `ops/lm.py::moe_experts` gives them."""

    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        d, w = cfg["hidden_size"], cfg["moe_intermediate_size"]
        first, n = cfg["experts_held"]
        moe = mixture_settings(cfg)
        self._attrs = dict(
            num_experts=moe["num_experts"], num_held=n, first_expert=first,
            hidden_size=w, top_k=moe["top_k"],
            scaling=cfg["routed_scaling_factor"],
            renormalize=bool(moe["renormalize"]))
        get = _getter(self)
        self.w_r = get("w_r", shape=(moe["num_experts"], d))
        self.r_bias = get("r_bias", shape=(moe["num_experts"],), init="zeros")
        self.e_gate = get("e_gate", shape=(n, d, w))
        self.e_up = get("e_up", shape=(n, d, w))
        self.e_down = get("e_down", shape=(n, w, d))
        self.shared = None
        if moe["num_shared"]:
            with self.name_scope():
                self.shared = SwiGLU(d, w * moe["num_shared"],
                                     names=("s_gate", "s_up", "s_down"),
                                     prefix="")

    def hybrid_forward(self, F, x, w_r, r_bias, e_gate, e_up, e_down):
        # the op names its two halves mx.moe.route and mx.moe.experts
        routed = F._contrib_moe_experts(x, w_r, r_bias, e_gate, e_up,
                                        e_down, **self._attrs)
        if self.shared is None:
            return routed[0], routed[1]
        with _scope("mx.moe.shared"):
            y = routed[0] + self.shared(x)
        return y, routed[1]


class DecoderBlock(HybridBlock):
    """One pre-norm layer; `layer` is its published 1-based number."""

    def __init__(self, cfg, layer, **kwargs):
        super().__init__(**kwargs)
        lin = cfg.get("linear_attn_config")
        if "layer_types" in cfg:
            kind = cfg["layer_types"][layer - 1]
            mixer = {"conv": ShortConvMixer,
                     "full_attention": GQAMixer}.get(kind)
            if mixer is None:
                raise NotImplementedError(
                    f"DecoderBlock: layer_types[{layer - 1}] = {kind!r}")
        elif lin is None or layer in lin["full_attn_layers"]:
            mixer = MLAMixer
        elif layer in lin["kda_layers"]:
            mixer = KDAMixer
        else:
            raise ValueError(f"layer {layer} is in neither kda_layers nor "
                             "full_attn_layers")
        d = cfg["hidden_size"]
        self._eps = _eps(cfg)
        self._moe = layer > _either(cfg, "num_dense_layers",
                                    "first_k_dense_replace")
        get = _getter(self)
        self.norm1 = get("norm1", shape=(d,), init="ones")
        self.norm2 = get("norm2", shape=(d,), init="ones")
        with self.name_scope():
            self.mixer = mixer(cfg, prefix="")
            self.mlp = MoEMLP(cfg, prefix="") if self._moe else \
                SwiGLU(d, cfg["intermediate_size"], prefix="")

    def hybrid_forward(self, F, x, norm1, norm2):
        # one stage a layer: its backward recomputes it from the stream
        with AttrScope(mirror_stage=self.prefix + "layer"):
            x = x + self.mixer(F.RMSNorm(x, norm1, eps=self._eps))
            h = F.RMSNorm(x, norm2, eps=self._eps)
            if self._moe:
                y, stats = self.mlp(h)
                return x + y, stats
            with _scope("mx.mlp"):
                return x + self.mlp(h), None


class DecoderLM(HybridBlock):
    """Embedding, `num_hidden_layers` blocks (the config's first n layers),
    final RMSNorm and a head: a parameter of its own (Kimi-Linear,
    `deepseek_v3`) or, under `tie_word_embeddings` (`lfm2_moe`), the
    embedding matrix itself, whose gradient is then the sum of its two
    uses'. `net(tokens)` gives the logits; `net(tokens, labels)` gives the
    (batch, sequence) cross-entropy of each position and, where there are
    mixture layers, their stacked stats (layers, experts held + 3) as a
    second output."""

    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        d, v = cfg["hidden_size"], cfg["vocab_size"]
        self._d, self._v, self._eps = d, v, _eps(cfg)
        get = _getter(self)
        self.embed = get("embed", shape=(v, d))
        self.norm_f = get("norm_f", shape=(d,), init="ones")
        if not cfg.get("tie_word_embeddings"):
            self.head = get("head", shape=(v, d))
        self.blocks = []
        with self.name_scope():
            for i in range(cfg["num_hidden_layers"]):
                block = DecoderBlock(cfg, i + 1, prefix=f"l{i}_")
                self.register_child(block)
                self.blocks.append(block)

    def hybrid_forward(self, F, tokens, labels=None, embed=None,
                       norm_f=None, head=None):
        x = F.Embedding(tokens, embed, input_dim=self._v, output_dim=self._d)
        if head is None:        # tied: the embedding matrix is the head
            head = embed
        stats = []
        for block in self.blocks:
            x, s = block(x)
            if s is not None:
                stats.append(F.expand_dims(s, axis=0))
        with _scope("mx.lm_head"), AttrScope(mirror_stage="head"):
            x = F.RMSNorm(x, norm_f, eps=self._eps)
            if labels is None:
                return _dense(F, x, head, self._v)
            loss = F._contrib_lm_head_ce(x, head, labels,
                                         num_classes=self._v)
        if not stats:
            return loss
        stats = stats[0] if len(stats) == 1 else F.concat(*stats, dim=0)
        return loss, F.BlockGrad(stats)
