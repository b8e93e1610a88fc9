"""Autocast policy: which registry ops run in half precision.

Role of the reference's AMP op lists (python/mxnet/contrib/amp/lists/
symbol_fp16.py: FP16_FUNCS / FP32_FUNCS / WIDEST_TYPE_CASTS), keyed on
OUR op registry names (ops/registry.py). Three buckets:

  ALLOW  — matmul/conv-class ops whose FLOPs dominate step time and whose
           MXU rate doubles in bf16/fp16: float inputs are cast DOWN to
           the amp dtype at the use site. Accumulation stays fp32 inside
           the kernels (dot_general preferred_element_type, the flash-
           attention VMEM accumulators, conv1x1's fp32 psum), so only
           storage/bandwidth and the MXU input width narrow.
  WIDEN  — numerically fragile reductions: softmax family, loss heads,
           and every normalization whose statistics must accumulate in
           fp32 (the Micikevicius et al. 2018 recipe). Float inputs are
           cast UP to fp32, so a bf16 activation entering softmax is
           widened and the exp/sum runs full width.
  (rest) — NEUTRAL: elementwise/shape ops run in whatever dtype arrives;
           casting them would only add convert traffic. Integer inputs
           (embedding ids, argmax indices) are never touched by any
           bucket — bf16's 8-bit mantissa corrupts ids (parallel/dp.py
           learned this the hard way).

  MIXED  — ops that hold several kinds of arithmetic and set each one's
           precision themselves (ops/lm.py): their matrix products take
           the dtype the inputs arrive in (the amp dtype, from the ALLOW
           ops before them) and accumulate in fp32; decays, cumulative
           sums, recurrent state, norm statistics, router scores with
           their top-k, a rotation's angles and sines, a short
           convolution's taps and sums and the loss are fp32 inside. The
           funnel casts nothing for them.

Two tables name inputs that no trainer may narrow on the way in
(parallel/dp.py casts parameters and float data to the compute dtype
before the graph runs; `amp.exact_variables` reads these tables):
KEEP_FP32 for parameters, EXACT_INPUTS for data that carries integers.

The lists are module-level frozensets so tests and docs/AMP.md can
introspect them; `amp.init` does not mutate them.
"""
from __future__ import annotations

# compute-bound ops: cast float inputs down to the amp dtype
ALLOW = frozenset({
    "FullyConnected",
    "Convolution",
    "Deconvolution",
    "dot",
    "batch_dot",
    "_linalg_gemm",
    "_linalg_gemm2",
    "_contrib_flash_attention",
})

# legacy loss-head ops whose custom VJP supplies its own gradient and
# IGNORES the incoming cotangent (the MXNet out_grad=False contract:
# ops/nn.py returns e.g. (softmax - onehot) * grad_scale regardless of
# what flows in from above). Multiplying the loss by the fp16 loss scale
# therefore does NOT scale gradients under these heads — the scale must
# be injected into the cotangent directly BELOW the head instead
# (amp.cast_op_inputs wraps the head's data input in a custom_vjp that
# multiplies the outgoing cotangent by the live scale). Graphs whose
# loss is an ordinary differentiable value keep the textbook
# `loss * scale` route in parallel/dp.py; the two mechanisms are
# mutually exclusive by construction (scaling the loss above a
# cotangent-ignoring head is a no-op, and injection only fires on the
# ops listed here).
LOSS_HEADS = frozenset({
    "SoftmaxOutput",
    "LinearRegressionOutput",
    "LogisticRegressionOutput",
    "MAERegressionOutput",
    "MakeLoss",
    "SVMOutput",
})

# reduction/loss/norm ops: cast float inputs up to fp32
WIDEN = frozenset({
    "softmax",
    "log_softmax",
    "SoftmaxActivation",
    "SoftmaxOutput",
    "softmax_cross_entropy",
    "BatchNorm",
    "LayerNorm",
    "InstanceNorm",
    "L2Normalization",
    "LRN",
    "norm",
    "MakeLoss",
    "make_loss",
    "SVMOutput",
    "smooth_l1",
    "IdentityAttachKLSparseReg",
})

# several kinds of arithmetic inside, each at its own precision (above)
MIXED = frozenset({
    "RMSNorm",
    "_contrib_kda",
    "_contrib_moe_experts",
    "_contrib_rope",
    "_contrib_gated_short_conv",
    "_contrib_lm_head_ce",
})

# op -> inputs whose PARAMETER stays fp32 all the way into the op: the
# decay's rate and bias (exp of a bf16 A_log is off by up to 0.4% a step,
# compounded over the sequence), the router and its selection bias (a
# rounded score changes which experts are chosen), the gated short
# convolution's taps (3 a channel: their gradient is a sum over every token)
KEEP_FP32 = {
    "_contrib_kda": ("A_log", "dt_bias"),
    "_contrib_moe_experts": ("router_weight", "router_bias"),
    "_contrib_gated_short_conv": ("weight",),
}

# op -> inputs that carry integers in a float array (MXNet's convention
# for ids): bf16 holds integers exactly only up to 256
EXACT_INPUTS = {
    "Embedding": ("data",),
    "_contrib_SparseEmbedding": ("data",),
    "take": ("indices",),
    "one_hot": ("indices",),
    "pick": ("index",),
    "_contrib_lm_head_ce": ("label",),
}
