"""Compile the Pallas kernels of the main paths for a DESCRIBED TPU.

No chip is attached here: the TPU compiler compiles for a topology that
is described ("v5e:2x2"), which refuses what interpret-mode tests cannot
see — a slice that misses the tiling, too much VMEM, a kernel that cannot
be partitioned. Real widths, a second or two each. A compile that passes
is not a chip run; chip_smoke.py is.

Only one process may load the TPU library, so the topology is described
inside a module-scoped fixture of THIS file (never at import, never in a
skipif/parametrize argument, never in conftest.py, not autouse), every
test of it lives here, and nothing compiles in a child process.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

KERNEL = "tpu_custom_call"


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent
    # cache but not read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *specs):
    return jax.jit(fn).lower(*specs).compile().as_text()


@pytest.mark.parametrize("b,h,h_kv,s", [
    (8, 8, 8, 4096),        # MHA, the bench shape
    (8, 8, 2, 4096),        # GQA: 2 kv heads shared in-kernel
    (1, 2, 2, 16384),       # past 8k the kernels raise their VMEM limit
])
def test_flash_attention_forward_and_gradient(one_chip, b, h, h_kv, s):
    from mxnet_tpu.ops.attention import flash_attention
    q = jax.ShapeDtypeStruct((b, h, s, 128), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((b, h_kv, s, 128), jnp.bfloat16,
                              sharding=one_chip)

    def fwd(q, k, v):       # the auto pick, told its target is a TPU
        return flash_attention(q, k, v, causal=True, platform="tpu")

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    assert _compiled_text(fwd, q, kv, kv).count(KERNEL) == 1
    # dq, dk and dv from ONE recompute kernel beside the forward
    assert _compiled_text(jax.grad(loss, argnums=(0, 1, 2)),
                          q, kv, kv).count(KERNEL) == 2


def test_flash_attention_unequal_head_widths(one_chip):
    """Latent attention at Kimi-Linear's published widths: query/key heads
    of 128 + 64, value heads of 128, 32 heads, one sequence of 8,192."""
    from mxnet_tpu.ops.attention import flash_attention
    qk = jax.ShapeDtypeStruct((1, 32, 8192, 192), jnp.bfloat16,
                              sharding=one_chip)
    v = jax.ShapeDtypeStruct((1, 32, 8192, 128), jnp.bfloat16,
                             sharding=one_chip)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True, scale=192 ** -0.5,
                               platform="tpu").astype(jnp.float32).sum()

    assert _compiled_text(jax.grad(loss, argnums=(0, 1, 2)),
                          qk, qk, v).count(KERNEL) == 2


@pytest.mark.parametrize("chunk", [64, 128])
def test_kda_kernels_forward_and_gradient(one_chip, chunk):
    """The delta rule's kernels at `kimi_linear.train`'s shape: 2 sequences
    of 8,192, 32 heads of 128 / 128, bf16 with a float32 log-decay."""
    from mxnet_tpu.ops import lm
    b, s, h, d = 2, 8192, 32, 128
    qkv = jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16, sharding=one_chip)
    g = jax.ShapeDtypeStruct((b, s, h, d), jnp.float32, sharding=one_chip)
    beta = jax.ShapeDtypeStruct((b, s, h), jnp.float32, sharding=one_chip)

    def fwd(*a):            # the auto pick, told its target is a TPU
        return lm.kda(*a, chunk=chunk, platform="tpu")

    def loss(*a):
        return fwd(*a).astype(jnp.float32).sum()

    assert _compiled_text(fwd, qkv, qkv, qkv, g, beta).count(KERNEL) == 1
    # the forward that keeps the chunk-start states, and the backward
    assert _compiled_text(jax.grad(loss, argnums=(0, 1, 2, 3, 4)),
                          qkv, qkv, qkv, g, beta).count(KERNEL) == 2


@pytest.mark.parametrize("cell,rows,d,w,held,dtype", [
    ("lfm2_moe.train", 65536, 2048, 1792, 8, "bfloat16"),
    ("kanana2.train", 49152, 2048, 768, 16, "bfloat16"),
    ("kimi_linear.train", 16384, 2304, 1024, 8, "bfloat16"),
    # check (b)'s program: one sequence, amp off, `highest`
    ("lfm2_moe.train", 32768, 2048, 1792, 8, "float32"),
])
def test_moe_grouped_kernels_forward_and_gradient(one_chip, cell, rows, d, w,
                                                  held, dtype):
    """The held experts' grouped SwiGLU at the three cells' shapes through
    the auto pick: two `mx_moe_gmm` forward; the forward that keeps the two
    products, hidden's and x's gradients and two `mx_moe_tgmm` backward;
    no `ragged-dot` left."""
    from mxnet_tpu.ops import lm

    def sds(shape, dt=dtype):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    specs = (sds((rows, d)), sds((held, d, w)), sds((held, d, w)),
             sds((held, w, d)), sds((held,), jnp.int32))

    def fwd(*a):            # the auto pick, told its target is a TPU
        return lm._swiglu_experts(*a, platform="tpu")

    def loss(*a):
        return fwd(*a).sum()

    with jax.default_matmul_precision(
            "highest" if dtype == "float32" else "default"):
        forward = _compiled_text(fwd, *specs)
        both = _compiled_text(jax.value_and_grad(loss, argnums=(0, 1, 2, 3)),
                              *specs)
    assert forward.count(KERNEL) == 2 and both.count(KERNEL) == 6
    assert "ragged-dot" not in forward + both


@pytest.mark.parametrize("cell,d,w,held,n_all,k,ratio", [
    ("lfm2_moe.train", 2048, 1792, 8, 32, 4, 1),
    ("kanana2.train", 2048, 768, 16, 128, 6, 2),
    ("trinity_mini.train", 2048, 1024, 8, 128, 8, 4),
    ("kimi_linear.train", 2304, 1024, 8, 256, 8, 8),
])
def test_moe_layer_dispatch_and_combine(one_chip, cell, d, w, held, n_all, k,
                                        ratio):
    """One mixture layer's value and gradients at a cell's shape (2 x 8,192
    tokens, bf16) through `lm.moe_experts`: the grouped kernels' 2 + 6
    calls, and `mx_moe_combine` once forward (the combine) and once more
    backward (x's gradient). At every ratio of T * k to the grouped rows
    no scatter writes an array of (T, D) or (rows, D), forward or
    backward, and no array of a token's k rows, (k, T, D) or (T * k, D),
    exists (at ratio 1 (T * k, D) is the grouped rows' own shape)."""
    from mxnet_tpu.ops import lm
    t = 2 * 8192
    capacity = t * k // ratio

    def sds(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    specs = (sds((t, d)), sds((n_all, d), jnp.float32),
             sds((n_all,), jnp.float32), sds((held, d, w)),
             sds((held, d, w)), sds((held, w, d)), sds((t, d)))

    def fwd(x, rw, rb, wg, wu, wd, _):
        return lm.moe_experts(x, rw, rb, wg, wu, wd, first_expert=0, top_k=k,
                              scaling=1.0, renormalize=True,
                              platform="tpu")[0]

    def loss(*a):               # the layer's output feeds what follows it
        return (fwd(*a).astype(jnp.float32) * a[-1]).sum()

    forward = _compiled_text(fwd, *specs)
    both = _compiled_text(jax.value_and_grad(loss, argnums=(0, 1, 3, 4, 5)),
                          *specs)
    assert forward.count(KERNEL) == 2 + 1 and both.count(KERNEL) == 6 + 2
    combine = r"%mx_moe_combine\S* = "
    assert len(re.findall(combine, forward)) == 1
    assert len(re.findall(combine, both)) == 2
    wide = re.findall(r"\[(?:%d|%d),%d\]\S* scatter\(" % (t, capacity, d),
                      forward + both)
    assert not wide, wide
    rows = [r"\[%d,%d,%d\]" % (k, t, d)] + (
        [r"\[%d,%d\]" % (t * k, d)] if ratio > 1 else [])
    kept = re.findall("|".join(rows), forward + both)
    assert not kept, kept


def test_flash_attention_grouped_heads_of_64(one_chip):
    """Grouped-query attention at LFM2-8B-A1B's published widths: 32 query
    heads over 8 k/v heads of 64, two sequences of 8,192. A minor dim of 64
    is tiled out to 128 lanes in VMEM, which `_vmem_params` counts
    (PERF.md, PR 32). k and v enter at their own 8 heads and dK and dV
    leave at them (a group's query heads add into one resident block):
    nothing of 32 heads' size but q, the output and their gradients
    exists."""
    from mxnet_tpu.ops.attention import flash_attention
    q = jax.ShapeDtypeStruct((2, 32, 8192, 64), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((2, 8, 8192, 64), jnp.bfloat16,
                              sharding=one_chip)

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True, scale=64 ** -0.5,
                               platform="tpu")

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    assert _compiled_text(fwd, q, kv, kv).count(KERNEL) == 1
    text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    assert text.count(KERNEL) == 2
    assert "bf16[16,8192,64]" in text and "repeat" not in text
    # no per-query-head dK / dV partial to sum over a group afterwards
    assert "f32[2,8,4,8192,64]" not in text and \
        "bf16[2,8,4,8192,64]" not in text


@pytest.mark.parametrize("window", [2048, 1000])
def test_flash_attention_sliding_window(one_chip, window):
    """Grouped-query attention over a sliding window at Trinity-Mini's
    published widths: 32 query heads over 4 k/v heads of 128, two sequences
    of 8,192, the window of 2,048 keys and one that is no multiple of a
    block. The forward and the one backward kernel under value_and_grad,
    each a body other than the causal call's (its loops start at the
    window's first block)."""
    from mxnet_tpu.ops.attention import flash_attention
    q = jax.ShapeDtypeStruct((2, 32, 8192, 128), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((2, 4, 8192, 128), jnp.bfloat16,
                              sharding=one_chip)

    def program(w):
        def loss(q, k, v):
            return flash_attention(q, k, v, causal=True, scale=128 ** -0.5,
                                   platform="tpu", window=w).astype(
                                       jnp.float32).sum()
        return _compiled_text(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                              q, kv, kv)

    windowed = program(window)
    assert windowed.count(KERNEL) == 2
    assert "bf16[8,8192,128]" in windowed and "repeat" not in windowed
    assert windowed != program(None)


def _kda_operands(one_chip, b=2, s=8192, h=32, d=128):
    """`_contrib_kda`'s ten operands at `kimi_linear.train`'s shape."""
    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    c = h * d
    return (sds((b, s, c)),) * 4 + (sds((b, s, h)),) + (sds((c, 4)),) * 3 + \
        (sds((h,), jnp.float32), sds((c,), jnp.float32))


def test_kda_prepare_kernels_forward_and_gradient(one_chip):
    """The short convolutions, normalisations and decay in one kernel each
    way at the cell's shape: 2 x 8,192 x 4,096 bf16 in, g float32 out."""
    from mxnet_tpu.ops import kda_pallas
    q, k, v, f, _, *params = _kda_operands(one_chip)

    def fwd(*a):
        return kda_pallas.prepare_kernels(*a, num_heads=32)

    def loss(*a):
        return sum(o.astype(jnp.float32).sum() for o in fwd(*a))

    text = _compiled_text(fwd, q, k, v, f, *params)
    assert text.count(KERNEL) == 1 and "mx_kdaprep_fwd" in text
    # a sum of the outputs needs none of them: the backward kernel alone
    text = _compiled_text(jax.grad(loss, argnums=tuple(range(9))),
                          q, k, v, f, *params)
    assert text.count(KERNEL) == 1 and "mx_kdaprep_bwd" in text


_ITEM = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
         "f32": 4, "s64": 8, "u64": 8, "f64": 8}
_ARRAY = re.compile(r"\b(%s)\[([\d,]*)\]" % "|".join(_ITEM))
_NO_TRAFFIC = {"parameter", "get-tuple-element", "bitcast", "tuple",
               "constant"}


def _entry_instructions(text):
    """[(name, opcode, [(elements, bytes)] of the result's arrays, operand
    names)] of a compiled program's entry computation."""
    def balanced(s):            # length of the parenthesised prefix of s
        depth = 0
        for i, ch in enumerate(s):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                return i + 1
        raise ValueError(s)

    out = []
    for line in text[text.index("\nENTRY "):].split("\n")[2:]:
        if line.startswith("}"):
            break
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (.*)$", line)
        if not m:
            continue
        name, rest = m.groups()
        cut = balanced(rest) if rest.startswith("(") else rest.index(" ")
        kind, rest = rest[:cut], rest[cut + 1:]
        opcode, rest = rest.split("(", 1)
        arrays = []
        for dtype, dims in _ARRAY.findall(kind):
            n = 1
            for d in filter(None, dims.split(",")):
                n *= int(d)
            arrays.append((n, n * _ITEM[dtype]))
        out.append((name, opcode, arrays, re.findall(
            r"%([\w.\-]+)", rest[:balanced("(" + rest) - 2])))
    return out


def _traffic(instructions):
    """Operand + result bytes summed over the instructions that move any."""
    size = {name: sum(b for _, b in arrays)
            for name, _, arrays, _ in instructions}
    return sum(size[name] + sum(size.get(o, 0) for o in operands)
               for name, opcode, _, operands in instructions
               if opcode not in _NO_TRAFFIC)


def test_contrib_kda_moves_its_operands_once(one_chip):
    """The compiled `_contrib_kda` at the cell's shape: what its entry
    computation's instructions read and write, summed. The XLA `prepare`
    moved 6.9 GB forward and 22.1 GB for the gradient around kernel calls
    of 0.8 and 3.4 (float32 copies, 4-D reshapes that are physical,
    materialised broadcasts: PERF.md, PR 29); one pass each way moves 2.4
    and 7.3, and nothing but a kernel writes an array of the operands' size
    on the way forward."""
    from mxnet_tpu.ops.registry import OpCtx, get_op
    operands = _kda_operands(one_chip)
    op = get_op("_contrib_kda")
    attrs = op.parse_attrs({"num_heads": "32"})

    def fwd(*a):
        return op.fcompute(attrs, OpCtx(is_train=True, platform="tpu"), *a)[0]

    def loss(*a):
        return fwd(*a).astype(jnp.float32).sum()

    forward = _entry_instructions(_compiled_text(fwd, *operands))
    assert [opcode for _, opcode, _, _ in forward].count("custom-call") == 2
    assert _traffic(forward) <= 2.8e9
    full = operands[0].size
    large = [(name, opcode) for name, opcode, arrays, _ in forward
             if opcode not in _NO_TRAFFIC | {"custom-call"}
             and any(n >= full for n, _ in arrays)]
    assert not large, large
    gradient = _entry_instructions(_compiled_text(
        jax.grad(loss, argnums=tuple(range(10))), *operands))
    assert _traffic(gradient) <= 8.5e9


@pytest.mark.parametrize("way", ["forward", "gradient"])
def test_rope_moves_its_operand_once(one_chip, way):
    """`lm.rope` at `kanana2.train`'s shape (2 x 32 heads x 8,192 x 192, the
    last 64 dims of a head rotated, bf16): one read and one write of q,
    0.40 GB, each way. The pair's other half comes from a product with a
    signed permutation, into which the compiler fuses the multiply-adds;
    fetched by a lane shift (`jnp.roll`) the same rotation compiled to
    float32 slices and copies of q, 2.6 GB a call (PERF.md, PR 30)."""
    from mxnet_tpu.ops import lm
    q = jax.ShapeDtypeStruct((2, 32, 8192, 192), jnp.bfloat16,
                             sharding=one_chip)

    def fwd(x):
        return lm.rope(x, 64, 128, 1e6, True)

    fn = fwd if way == "forward" else \
        (lambda x, dy: jax.vjp(fwd, x)[1](dy)[0])
    ins = _entry_instructions(_compiled_text(fn, *(q,) * (
        1 if way == "forward" else 2)))
    assert _traffic(ins) <= 0.46e9
    full = 2 * 32 * 8192 * 192
    large = [name for name, opcode, arrays, _ in ins
             if opcode not in _NO_TRAFFIC and any(n >= full
                                                  for n, _ in arrays)]
    assert len(large) == 1, large


@pytest.mark.parametrize("way,limit", [("forward", 0.30e9),
                                       ("gradient", 1.5e9)])
def test_gated_short_conv_traffic(one_chip, way, limit):
    """`lm.gated_short_conv` at `lfm2_moe.train`'s shape (2 x 8,192 tokens,
    three chunks of 2,048 channels, bf16, float32 taps). Forward: ONE
    fusion that reads the three chunks and writes one, 0.27 GB (pad,
    slices, converts and products fused; written with shifted copies of the
    float32 product it moved 2.2 GB: a float32 copy of x and of B * u).
    The gradient is not one pass yet: a multiply-reduce fusion (the taps'
    gradient, two chunks, float32 dz), a fusion for the third chunk and a
    concatenate move 1.34 GB where 0.47 would do (PERF.md section 7)."""
    from mxnet_tpu.ops import lm
    x = jax.ShapeDtypeStruct((2, 8192, 3 * 2048), jnp.bfloat16,
                             sharding=one_chip)
    w = jax.ShapeDtypeStruct((2048, 3), jnp.float32, sharding=one_chip)
    dy = jax.ShapeDtypeStruct((2, 8192, 2048), jnp.bfloat16,
                              sharding=one_chip)
    if way == "forward":
        ins = _entry_instructions(_compiled_text(lm.gated_short_conv, x, w))
        large = [name for name, opcode, arrays, _ in ins
                 if opcode not in _NO_TRAFFIC
                 and any(n >= 2 * 8192 * 2048 for n, _ in arrays)]
        assert len(large) == 1, large
    else:
        ins = _entry_instructions(_compiled_text(
            lambda x, w, dy: jax.vjp(lm.gated_short_conv, x, w)[1](dy),
            x, w, dy))
    assert _traffic(ins) <= limit


@pytest.mark.parametrize("h_kv", [8, 2])
def test_decode_attention(one_chip, h_kv):
    from mxnet_tpu.ops.attention import decode_attention
    b, h, s, d = 8, 8, 2048, 128
    q = jax.ShapeDtypeStruct((b, h, d), jnp.float32, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((b, h_kv, s, d), jnp.float32,
                              sharding=one_chip)
    lengths = jax.ShapeDtypeStruct((b,), jnp.int32, sharding=one_chip)

    def fn(q, k, v, lengths):
        return decode_attention(q, k, v, lengths, platform="tpu")

    assert _compiled_text(fn, q, kv, kv, lengths).count(KERNEL) == 1


@pytest.mark.parametrize("m", [1, 8])
@pytest.mark.parametrize("wdtype", ["int8", "float8_e4m3fn"])
def test_quantized_matmul(one_chip, wdtype, m):
    from mxnet_tpu.ops.quantization import quantized_matmul
    k, n = 4096, 4096
    x = jax.ShapeDtypeStruct((m, k), jnp.float32, sharding=one_chip)
    w = jax.ShapeDtypeStruct((k, n), jnp.dtype(wdtype), sharding=one_chip)
    scale = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip)

    def fn(x, w, scale):
        return quantized_matmul(x, w, scale, platform="tpu")

    text = _compiled_text(fn, x, w, scale)
    assert text.count(KERNEL) == 1
    # the narrow weight reaches the kernel as stored, never widened in HBM
    assert ("s8[4096,4096]" if wdtype == "int8" else "f8e4m3fn[4096,4096]") \
        in text


def test_ring_attention_over_four_chips(topo):
    """The sequence-parallel ring at 16k tokens over a 4-device ("sp",)
    mesh: the flash kernels inside shard_map plus the K/V rotation."""
    from mxnet_tpu.parallel import sp
    mesh = Mesh(topo.devices[:4], ("sp",))
    x = jax.ShapeDtypeStruct(
        (1, 4, 16384, 128), jnp.bfloat16,
        sharding=NamedSharding(mesh, P(None, None, "sp", None)))

    def loss(q, k, v):
        return sp.ring_attention(q, k, v, mesh, causal=True) \
            .astype(jnp.float32).sum()

    text = _compiled_text(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                          x, x, x)
    assert KERNEL in text and "collective-permute" in text


def test_mirror_stage_keeps_flash_residuals(one_chip):
    """One decoder-layer-shaped stage at the language-model cells' shape
    (2 sequences of 8,192, 32 heads of 192 / 128, bf16) under the
    executor's rematerialisation: the flash kernels declare out and lse as
    kept, so the loss-and-gradient program runs the forward kernel ONCE
    (twice under a bare checkpoint) and pays for it with out and lse,
    0.136 GB: the forward writes lse as rows along the lanes,
    (B*H, 1, S), 4 bytes a row in HBM, and the ONE backward kernel reads
    them so."""
    from mxnet_tpu import executor
    from mxnet_tpu.ops.attention import flash_attention
    b, h, s, d, dv, hidden = 2, 32, 8192, 192, 128, 2048

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def stage(x, wq, wk, wv, wo):
        def heads(w, width):
            return (x @ w).reshape(b, s, h, width).transpose(0, 2, 1, 3)
        o = flash_attention(heads(wq, d), heads(wk, d), heads(wv, dv),
                            causal=True, scale=d ** -0.5, platform="tpu")
        return x + o.transpose(0, 2, 1, 3).reshape(b, s, h * dv) @ wo

    def compiled(fn):
        def loss(*a):
            return fn(*a).astype(jnp.float32).sum()
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))) \
            .lower(sds(b, s, hidden), sds(hidden, h * d), sds(hidden, h * d),
                   sds(hidden, h * dv), sds(h * dv, hidden)).compile()

    def kernels(program):
        calls = [line for line in program.as_text().split("\n")
                 if "custom-call(" in line and KERNEL in line]
        return [sum(name in line for line in calls) for name in (
            "mx_flash_attention_fwd", "mx_flash_attention_bwd")]

    bare = compiled(jax.checkpoint(stage))
    kept = compiled(executor._rematerialised(stage))
    assert kernels(bare) == [2, 1]
    assert kernels(kept) == [1, 1]
    assert kept.memory_analysis().temp_size_in_bytes <= \
        bare.memory_analysis().temp_size_in_bytes + 0.16e9


def test_ouro_train_step_fits_one_chip(topo, monkeypatch):
    """`ouro.train`'s whole K = 2 step for one described v5e at the cell's
    size (benchmarks/configs/ouro_2_6b.json: 6 layers run 4 times, 2
    sequences of 8,192, adam, amp bf16, one mirror stage a (pass, layer)):
    the arguments (parameters and adam's two moments, float32) and the
    temporaries fit the chip's 15.75 GiB, and every one of the 24 layer
    applications runs the flash kernels. 5.70 + 7.17 GiB here, a minute."""
    import importlib.util
    import json
    import os
    from jax.sharding import Mesh
    import mxnet_tpu as mx
    from mxnet_tpu import amp
    from mxnet_tpu import random as mx_random
    from mxnet_tpu.parallel.dp import DataParallelTrainer

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks")
    spec = importlib.util.spec_from_file_location(
        "ouro_model_under_compile", os.path.join(bench, "models", "ouro.py"))
    model = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(model)
    with open(os.path.join(bench, "configs", "ouro_2_6b.json")) as f:
        cfg = json.load(f)
    b, s = cfg["per_chip_batch"], cfg["sequence_length"]
    monkeypatch.setenv("MXNET_BACKWARD_DO_MIRROR", "1")
    amp.init("bfloat16")
    try:
        sym = model.build(cfg)
        tr = DataParallelTrainer(
            sym, Mesh(topo.devices[:1], ("data",)), data_names=("data",),
            label_names=("label",), optimizer="adam", learning_rate=1e-4,
            rescale_grad=1.0 / (b * s), dtype="bfloat16", beta1=0.9,
            beta2=0.95, epsilon=1e-8)
        shapes = dict(zip(sym.list_arguments(),
                          sym.infer_shape(data=(b, s), label=(b, s))[0]))
        f32, sds = jnp.float32, jax.ShapeDtypeStruct
        params = tuple(sds(shapes[n], f32) for n in tr.param_names)
        states = tuple(tuple(sds(shapes[n], f32) for _ in range(2))
                       for n in tr.param_names)
        inputs = tuple(sds((2, b, s), jnp.int32) for _ in tr.input_names)
        key = mx_random.next_key()
        compiled = tr._multi_step_fn(2, "all").lower(
            params, states, (), inputs, sds(key.shape, key.dtype),
            sds((), f32), sds((), f32)).compile()
    finally:
        amp._reset_for_tests()
    assert sum(np.prod(p.shape) for p in params) == 509_661_185
    mem = compiled.memory_analysis()
    args_gib = mem.argument_size_in_bytes / 2**30
    temp_gib = mem.temp_size_in_bytes / 2**30
    assert 5.6 < args_gib < 5.8          # 12 B a parameter, whole
    assert args_gib + temp_gib < 15.75, (args_gib, temp_gib)
    calls = [line for line in compiled.as_text().split("\n")
             if "custom-call(" in line and KERNEL in line]
    # the forward kernel once a layer application (its out and lse kept)
    assert sum("mx_flash_attention_fwd" in line for line in calls) == 24
