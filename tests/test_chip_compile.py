"""Compile the main path's Pallas kernels, and one whole ResNet-50 train
step, for a described (not attached) TPU v5e with the chip's own compiler.

Interpret mode cannot see what the TPU compiler refuses: an op the vector
unit lacks (the bf16 compare of PR 22's finding 1) or more scoped VMEM
than a kernel may use (finding 2). These cases can, at no chip time. A
compile that passes here is not a chip run. Skipped where this jaxlib
cannot describe a ``v5e:2x2`` topology.
"""
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or libtpu logs to /tmp

import numpy as np
import pytest

import jax
import jax.numpy as jnp

B = 256  # every shape below is the b256 ResNet-50 train step's


@pytest.fixture(scope="module")
def one_chip():
    """SingleDeviceSharding on the first chip of a described v5e 2x2. The
    persistent compile cache is off around these compiles: an entry
    written for a described device cannot be read back without a chip."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or one that cannot describe it
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _n_kernels(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


# (HW, C, has_residual): the nine BN(+add)+ReLU epilogues of ResNet-50
EPILOGUES = [(112, 64, False), (56, 64, False), (56, 256, True),
             (28, 128, False), (28, 512, True), (14, 256, False),
             (14, 1024, True), (7, 512, False), (7, 2048, True)]


def _compile_epilogue(one_chip, hw, c, has_res, dtype):
    """Forward + backward of ``fused_bn_act`` at (B*hw*hw, c), compiled
    (``interpret=False`` handed to the builder)."""
    from mxnet_tpu.ops import pallas_kernels as pk
    f = pk._build_fused_bn_act(1e-5, has_res, False)
    x = jax.ShapeDtypeStruct((B * hw * hw, c), jnp.dtype(dtype),
                             sharding=one_chip)
    v = jax.ShapeDtypeStruct((c,), jnp.float32, sharding=one_chip)

    def loss(*args):
        out, _, _ = f(*args)
        return jnp.sum(out.astype(jnp.float32))

    args = (x, x, v, v) if has_res else (x, v, v)
    return jax.jit(jax.value_and_grad(
        loss, argnums=tuple(range(len(args))))).lower(*args).compile()


def _compile_kernels_alone(one_chip, hw, c, has_res, dtype):
    """Each of the four kernels between an elementwise producer and a
    reducing consumer. In this setting XLA holds a kernel to its scoped
    VMEM limit, as it does inside the whole train step; fed bare entry
    parameters it does not, which is how finding 2 hid."""
    from mxnet_tpu.ops import pallas_kernels as pk
    x = jax.ShapeDtypeStruct((B * hw * hw, c), jnp.dtype(dtype),
                             sharding=one_chip)
    c2 = jax.ShapeDtypeStruct((2, c), jnp.float32, sharding=one_chip)
    c5 = jax.ShapeDtypeStruct((5, c), jnp.float32, sharding=one_chip)

    def total(outs):
        return sum(jnp.sum(o.astype(jnp.float32))
                   for o in jax.tree_util.tree_leaves(outs))

    def program(a, b, d, co2, co5):
        a, b = a * 2, b + 1
        return total([
            pk._bn_stats_call(a, False),
            pk._bn_apply_call(a, b if has_res else None, co2, False),
            pk._bn_bwd_stats_call(a, b, d, co2, False),
            pk._bn_bwd_apply_call(a, b, d, co5, has_res, False)])

    return jax.jit(program).lower(x, x, x, c2, c5).compile()


@pytest.mark.parametrize("hw,c,has_res", EPILOGUES)
def test_fused_bn_act_bf16_compiles_for_v5e(one_chip, hw, c, has_res):
    compiled = _compile_epilogue(one_chip, hw, c, has_res, "bfloat16")
    assert _n_kernels(compiled) == 4  # stats, apply, bwd stats, bwd apply
    alone = _compile_kernels_alone(one_chip, hw, c, has_res, "bfloat16")
    assert _n_kernels(alone) == 4


@pytest.mark.parametrize("hw,c,has_res", [(56, 256, True), (7, 512, False)])
def test_fused_bn_act_f32_compiles_for_v5e(one_chip, hw, c, has_res):
    compiled = _compile_epilogue(one_chip, hw, c, has_res, "float32")
    assert _n_kernels(compiled) == 4
    alone = _compile_kernels_alone(one_chip, hw, c, has_res, "float32")
    assert _n_kernels(alone) == 4


@pytest.mark.parametrize("bh,t,d,dtype,causal", [
    (64, 1024, 64, "bfloat16", True),
    (64, 2048, 128, "bfloat16", True),
    (8, 8192, 128, "bfloat16", True),
    (64, 1000, 64, "float32", False),
])
def test_flash_attention_compiles_for_v5e(one_chip, bh, t, d, dtype, causal):
    from mxnet_tpu.ops import pallas_kernels as pk
    call = pk._build_flash(t, d, causal, 1.0 / np.sqrt(d), False)
    q = jax.ShapeDtypeStruct((bh, t, d), jnp.dtype(dtype), sharding=one_chip)
    compiled = jax.jit(call).lower(q, q, q).compile()
    assert _n_kernels(compiled) == 1


@pytest.mark.parametrize("bh,t,dk,dv,dtype,causal", [
    (20, 8192, 256, 256, "bfloat16", True),    # GLM-4.7-Flash's MLA at 8k
    (80, 8192, 256, 256, "bfloat16", True),    # the same, scoring 4 sequences
    (32, 8192, 128, 128, "bfloat16", True),    # Nemotron-3-Nano's attention
    (4, 32768, 256, 256, "bfloat16", True),    # the stretch does not grow
    (20, 2048, 256, 256, "bfloat16", True),
    (8, 1024, 128, 128, "bfloat16", False),
    (4, 4096, 192, 128, "float32", True),
])
def test_blocked_attention_compiles_for_v5e(one_chip, bh, t, dk, dv, dtype,
                                            causal):
    """The three kernels of ``blocked_attention`` (forward, dQ, dK/dV) at
    the benchmark's widths: the forward's stretch of four 512-row tiles of
    keys and values at head size 256, both buffers, and the 512x512 float32
    logits of the tiles in flight fit VMEM."""
    from mxnet_tpu.ops import pallas_kernels as pk
    fwd, bwd = pk._build_blocked_attention(t, dk, dv, causal, dk ** -0.5,
                                           dtype, False)
    q = jax.ShapeDtypeStruct((bh, t, dk), jnp.dtype(dtype), sharding=one_chip)
    v = jax.ShapeDtypeStruct((bh, t, dv), jnp.dtype(dtype), sharding=one_chip)
    lse = jax.ShapeDtypeStruct((bh, t), jnp.float32, sharding=one_chip)
    assert _n_kernels(jax.jit(fwd).lower(q, q, v).compile()) == 1
    assert _n_kernels(jax.jit(bwd).lower(q, q, v, v, lse, v).compile()) == 2


@pytest.mark.parametrize("bh,t", [(64, 32768), (8, 2048)])
def test_window_attention_with_a_sink_compiles_for_v5e(one_chip, bh, t):
    """The window variant's three kernels at MiMo-V2.5's window layers (64
    query heads, q and k 192 wide, v 128, a window of 128 keys with a sink
    logit a head, 32,768 tokens): tiles of 128 rows, two grid steps a block,
    and the sink's row of the running maximum lower and fit VMEM, under the
    window's own kernel names."""
    from mxnet_tpu.ops import pallas_kernels as pk
    dk, dv = 192, 128
    fwd, bwd = pk._build_blocked_attention(t, dk, dv, True, dk ** -0.5,
                                           "bfloat16", False, 128, True)

    def sds(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    q, v = sds(bh, t, dk), sds(bh, t, dv)
    compiled = jax.jit(fwd).lower(q, q, v, sds(bh, dtype=jnp.float32)) \
        .compile()
    assert _n_kernels(compiled) == 1
    assert "mx_attention_window_fwd" in compiled.as_text()
    compiled = jax.jit(bwd).lower(q, q, v, v, sds(bh, t, dtype=jnp.float32),
                                  v).compile()
    assert _n_kernels(compiled) == 2
    assert "mx_attention_window_dkv" in compiled.as_text()


@pytest.mark.parametrize("bh,kv,t,dk,dv,window", [
    (64, 8, 32768, 192, 128, 128),   # MiMo-V2.5's window layers, a sink
    (64, 4, 32768, 192, 128, None),  # its full layers
    (32, 2, 8192, 128, 128, None),   # Nemotron-3-Nano's attention, trained
])
def test_attention_kernels_compile_for_v5e_with_kv_at_the_kv_heads(
        one_chip, bh, kv, t, dk, dv, window):
    """The kernels as the grouped-KV layers hand them K and V, at the KV
    heads' rows: forward and backward lower and fit VMEM with a grid step's
    query rows reading their group's KV row."""
    from mxnet_tpu.ops import pallas_kernels as pk
    fwd, bwd = pk._build_blocked_attention(t, dk, dv, True, dk ** -0.5,
                                           "bfloat16", False, window,
                                           window is not None)

    def sds(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    q, k, v, o = sds(bh, t, dk), sds(kv, t, dk), sds(kv, t, dv), sds(bh, t, dv)
    sinks = (sds(bh, dtype=jnp.float32),) if window else ()
    assert _n_kernels(jax.jit(fwd).lower(q, k, v, *sinks).compile()) == 1
    compiled = jax.jit(bwd).lower(q, k, v, o, sds(bh, t, dtype=jnp.float32),
                                  o).compile()
    assert _n_kernels(compiled) == 2


@pytest.mark.parametrize("kv,window", [(8, 128), (4, None)])
def test_mimo_attention_layers_compile_for_v5e_with_no_kv_head_copied(
        one_chip, monkeypatch, kv, window):
    """MiMo-V2.5's window and full attention layers (``fused_qkv_attention``
    at 32,768 tokens, 64 query heads on 8 or 4 KV heads) as compiled for a
    v5e: the kernel takes K and V at the KV heads, and no array holds them
    copied out to 64 heads (a repeat's ``bf16[8,8,32768,192]``, 1.6 GB of
    such copies a layer). (A tracer lowers for the CPU: the kernel is told
    it is on the chip.)"""
    import re
    from mxnet_tpu.ops import lm_ops, pallas_kernels as pk
    monkeypatch.setattr(pk, "_interpret_for", lambda x: False)
    t, d, heads, qk, vd = 32768, 4096, 64, 192, 128
    rep = heads // kv

    def sds(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (sds(1, t, d), sds(d, (heads + kv) * qk + kv * vd),
            sds(heads * vd, d)) + ((sds(heads, dtype=jnp.float32),)
                                   if window else ())
    text = jax.jit(lambda *a: lm_ops.fused_qkv_attention(
        *a, heads=heads, kv_heads=kv, qk_dim=qk, v_dim=vd, rope_dim=64,
        window=window, value_scale=0.707)).lower(*args).compile().as_text()
    kernel = [line for line in text.splitlines()
              if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernel) == 1
    operands = kernel[0].split("operand_layout_constraints=")[1]
    assert re.findall(r"bf16\[([0-9,]+)\]", operands)[:3] == [
        f"{heads},{t},{qk}", f"{kv},{t},{qk}", f"{kv},{t},{vd}"]
    assert not re.search(rf"\[{kv},{rep},{t},", text)


def test_delta_rule_forward_compiles_for_v5e_as_one_kernel(one_chip,
                                                           monkeypatch):
    """The chunked delta rule's forward at Olmo-Hybrid-7B's widths (30
    heads, 96 x 192, chunk 64, 8,192 tokens): one kernel, whose blocks, the
    heads' state and the chunk matrices fit the VMEM it asks for, and no
    XLA loop beside it. (A tracer here lowers for the CPU: the kernel is
    told it is on the chip.)"""
    from mxnet_tpu.ops import lm_ops, pallas_kernels as pk
    monkeypatch.setattr(pk, "_interpret_for", lambda x: False)
    b, h, t, dk, dv = 1, 30, 8192, 96, 192

    def sds(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(lambda *a: lm_ops.gated_delta_rule_chunked(
        *a, chunk=64)).lower(
            sds(b, h, t, dk), sds(b, h, t, dk), sds(b, h, t, dv),
            sds(b, h, t, dtype=jnp.float32),
            sds(b, h, t, dtype=jnp.float32)).compile()
    assert _n_kernels(compiled) == 1
    assert " while(" not in compiled.as_text()


def test_mamba2_mixer_compiles_for_v5e_without_a_square_or_a_state_a_step(
        one_chip):
    """The Mamba-2 mixer of Nemotron-3-Nano at its published widths and
    8,192 tokens, forward and recomputed backward as the benchmark's cell
    runs it: XLA's lowering of the chunked scan fits beside 13 GB of model,
    and neither a (T, T) matrix a head nor the state at every step is among
    the program's arrays (the largest is the projection's output)."""
    import re
    from mxnet_tpu.ops import lm_ops
    t, d, h, p, g, n = 8192, 2688, 64, 64, 8, 128
    inner, conv = h * p, h * p + 2 * g * n
    bf = jnp.bfloat16

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, bf, sharding=one_chip)

    args = (sds(1, t, d), sds(d, inner + conv + h), sds(conv, 4), sds(conv),
            sds(h), sds(h), sds(h), sds(inner), sds(inner, d))

    def loss(*a):
        mixer = jax.checkpoint(lambda *a: lm_ops.mamba2_mixer(
            *a, heads=h, head_dim=p, groups=g, state=n, chunk=128))
        return jnp.sum(mixer(*a).astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=tuple(range(9)))).lower(
        *args).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 3 * 2**30
    sizes = [int(np.prod([int(x) for x in dims.split(",")]))
             for dims in re.findall(r"(?:f32|bf16)\[([0-9,]+)\]",
                                    compiled.as_text())]
    assert max(sizes) <= t * (inner + conv + h)          # 84M elements
    assert max(sizes) < min(h * t * t, t * h * p * n) // 40


def test_expert_layer_compiles_for_v5e_with_no_array_of_all_pairs(one_chip):
    """The expert layer of Nemotron-3-Nano at its published widths (8,192
    tokens, 8 of 128 relu2 experts held, six a token, the wider shared
    expert), forward and recomputed backward as the benchmark's cell runs
    it: nothing D or F wide has a row for every (token, choice) pair. The
    largest array is the float32 weight gradient of the experts held; the
    smallest array of all pairs would be 49,152 x 1,856, over twice that."""
    import re
    from mxnet_tpu.parallel import moe
    n, d, f, shared, router, k, held = 8192, 2688, 1856, 3712, 128, 6, 8
    bf = jnp.bfloat16

    def sds(*shape, dtype=bf):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (sds(1, n, d), sds(d, router, dtype=jnp.float32),
            sds(held, d, f), sds(held, f, d), sds(d, shared), sds(shared, d))

    def layer(x, gate, w_in, w_out, shared_in, shared_out):
        params = {"gate": gate, "bias": jnp.zeros((router,), jnp.float32),
                  "w_in": w_in, "w_out": w_out, "shared_in": shared_in,
                  "shared_out": shared_out}
        return moe.dropless_moe_ffn(x, params, k, tuple(range(held)), 2.5,
                                    activation="relu2")[0]

    def loss(*a):
        return jnp.sum(jax.checkpoint(layer)(*a).astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(loss, argnums=tuple(range(6)))).lower(
        *args).compile()
    # the compiler reads 829 MB (1,320 MB before the loop): two float32
    # weight gradients of 160 MB, the shared expert's 8,192 x 3,712 and
    # (N, D) accumulators
    assert compiled.memory_analysis().temp_size_in_bytes < 2**30
    sizes = [int(np.prod([int(x) for x in dims.split(",")]))
             for dims in re.findall(r"(?:f32|bf16)\[([0-9,]+)\]",
                                    compiled.as_text())]
    assert max(sizes) <= held * d * f                    # 40M elements
    assert max(sizes) < n * k * f // 2


def test_expert_layer_at_mimo_widths_combines_in_place_by_dma_for_v5e(
        one_chip, monkeypatch):
    """MiMo-V2.5's expert layer (32,768 tokens, 16 of 256 SwiGLU experts
    2,048 wide held, eight a token, hidden 4,096) as the scoring cell runs
    it, compiled for a v5e: the kernel ``mx_moe_zeros`` makes the (N, 1, D)
    float32 accumulator, the routed loop's body adds a tile's rows into it
    with the kernel ``mx_moe_combine``, in place: nothing in the body makes
    another array of its size (a relayout from (N, D) and back, or a copy,
    is 512 MB a tile). (A tracer lowers for the CPU: the kernels are told
    they are on the chip.)"""
    import re
    from mxnet_tpu.ops import pallas_kernels as pk
    from mxnet_tpu.parallel import moe
    monkeypatch.setattr(pk, "_interpret_for", lambda x: False)
    n, d, f, router, k, held = 32768, 4096, 2048, 256, 8, 16
    bf = jnp.bfloat16

    def sds(*shape, dtype=bf):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def layer(x, gate, w_in, w_out):
        params = {"gate": gate, "bias": jnp.zeros((router,), jnp.float32),
                  "w_in": w_in, "w_out": w_out}
        return moe.dropless_moe_ffn(x, params, k, tuple(range(held)))[0]

    compiled = jax.jit(layer).lower(
        sds(1, n, d), sds(d, router, dtype=jnp.float32),
        sds(held, d, 2 * f), sds(held, f, d)).compile()
    text = compiled.as_text()
    assert _n_kernels(compiled) == 2 and "mx_moe_zeros" in text
    loop = next(line for line in text.splitlines() if " while(" in line
                and re.search(rf"f32\[{n},(?:1,)?{d}\]", line))
    body_name = re.search(r"body=%([\w.\-]+)", loop).group(1)
    body = text.split(f"\n%{body_name} ", 1)[1].split("\n}\n", 1)[0]
    made = re.findall(rf"%\S+ = f32\[{n},(?:1,)?{d}\]\S* ([\w\-]+)\(", body)
    assert sorted(made) == ["custom-call", "get-tuple-element"]
    assert 'custom_call_target="tpu_custom_call"' in body
    assert "mx_moe_combine" in body
    # the accumulator (512 MB) and the layer's small arrays: no second one
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * n * d * 4


@pytest.mark.parametrize("n_tiles,n_f32", [(1, 2), (2, 2), (3, 4), (5, 3)])
@pytest.mark.parametrize("c,itemsize", [(64, 2), (1024, 2), (2048, 2),
                                        (256, 4)])
def test_epilogue_rows_counts_tiles_and_temporaries(c, itemsize, n_tiles,
                                                    n_f32):
    """The compiled-mode row block keeps the double-buffered tiles plus
    the body's f32 temporaries inside the budget, sublane aligned; the
    interpret-mode block is the whole array."""
    from mxnet_tpu.ops import pallas_kernels as pk
    r = B * 56 * 56
    assert pk._epilogue_rows(r, c, n_tiles, n_f32, True, itemsize) == r
    br = pk._epilogue_rows(r, c, n_tiles, n_f32, False, itemsize)
    lanes = max(c, 128)
    held = br * lanes * (2 * n_tiles * itemsize + 4 * n_f32)
    assert 0.5 * pk._EPILOGUE_VMEM_BUDGET < held <= pk._EPILOGUE_VMEM_BUDGET
    assert br % (32 // itemsize) == 0
    assert pk._epilogue_rows(24, c, n_tiles, n_f32, False, itemsize) == 24


def _relayouts(compiled, shapes):
    """Shape and minor-to-major order of every ``copy`` and ``transpose``
    left in the compiled program whose result has one of ``shapes``."""
    import re
    def ints(text):
        return tuple(int(d) for d in text.split(","))

    found = re.findall(
        r"= \w+\[([\d,]+)\]\{([\d,]+)[^=]* (?:copy|transpose)\(",
        compiled.as_text())
    return [(ints(shape), ints(order)) for shape, order in found
            if ints(shape) in shapes]


def test_recorded_bottleneck_hands_its_residuals_over_as_the_chip_keeps_them(
        one_chip):
    """ResNet-50's first bottleneck at b256 (then the pooling and a Dense
    layer, so that its cotangent is made inside the backward as in the
    net), hybridized and recorded, through ``CachedOp``'s own lowering.
    The chip's compiler keeps the two ``bf16[256,56,56,256]`` products in
    ``{3,0,2,1}`` (PR 32's trace: the forward copied each into the default
    layout, 1.23 ms apiece), so they cross to the backward as
    ``[56,56,256,256]``: the forward program then holds no copy and no
    transpose of either shape, every buffer of the set is written over the
    donated one, and the backward holds none either, where the one that is
    handed the products under their own shape (every backward before
    PR 40) re-lays them as it reads."""
    import re

    import mxnet_tpu as mx
    from mxnet_tpu import cached_op as co, nd
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.gluon.model_zoo.vision.resnet import BottleneckV1
    from mxnet_tpu.util import residual_policy_name

    net = nn.HybridSequential()
    net.add(BottleneckV1(256, 1, downsample=True, in_channels=64,
                         layout="NHWC"),
            nn.GlobalAvgPool2D(layout="NHWC"), nn.Dense(10))
    net.initialize(mx.init.Xavier())
    net(nd.zeros((1, 56, 56, 64)))  # resolves the deferred shapes
    net.cast("bfloat16")

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    op, entry = co.CachedOp(net), co._CacheEntry()
    params = tuple(sds(p.shape, jnp.bfloat16) for p in op._params())
    key = sds((2,), jnp.uint32)
    x = sds((B, 56, 56, 64), jnp.bfloat16)
    op._in_treedef = jax.tree_util.tree_structure((0,))
    record = (residual_policy_name(None),
              tuple(p.grad_req != "null" for p in op._params()), (False,))
    op._linearize(entry, True, record, params, key, [x])
    lin = entry.linear
    own, turned = (B, 56, 56, 256), (56, 56, B, 256)
    assert sorted(a.shape for a in lin.arena_avals if len(a.shape) == 4) \
        == [turned] * 2 + [(B, 56, 56, 64)] * 2
    assert lin.relaid == 2 and lin.relaid_bytes == 2 * B * 56 * 56 * 256 * 2

    arena = tuple(sds(a.shape, a.dtype) for a in lin.arena_avals)
    fwd = entry.jitted.lower(params, key, (x,), arena).compile()
    n = len(arena)
    assert _relayouts(fwd, {own, turned}) == []
    aliased = re.findall(r"\{(\d+)\}: \((\d+), \{\}",
                         fwd.as_text().split("\n", 1)[0])
    first = len(params) + 2  # the set follows params, key and the batch
    assert sorted((int(o), int(i)) for o, i in aliased) == \
        [(k, first + k) for k in range(n)]

    # the backward, as CachedOp lowers it, and as it was handed the set
    # before: every buffer under its own shape
    def backward(turn_back):
        flat_out = [sds(o.shape, o.dtype)
                    for o in jax.tree_util.tree_leaves(fwd.out_info)]
        flat_args = params + (key, x)
        leaves = [flat_out[s] if s >= 0 else flat_args[~s]
                  for s in lin.res_src]
        if not any(turn_back):
            leaves = [sds(own, a.dtype) if a.shape == turned else a
                      for a in leaves]
        closure = jax.tree_util.tree_unflatten(lin.closure_treedef, leaves)
        return co._backward_program(turn_back).lower(
            closure, tuple(flat_out[n:n + lin.n_outs])).compile()

    before = _relayouts(backward((None,) * len(lin.turn_back)),
                        {own, turned})
    assert len(before) >= 2 and set(before) == {(own, (3, 0, 2, 1))}
    assert _relayouts(backward(lin.turn_back), {own, turned}) == []


def _describe_step(one_chip):
    """The b256 ResNet-50 NHWC bf16 SPMDTrainer step and its arguments as
    shapes on the described chip."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.parallel import SPMDTrainer

    net = vision.resnet50_v1(classes=1000, layout="NHWC")
    net.initialize(mx.init.Xavier())
    tr = SPMDTrainer(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                     optimizer="sgd",
                     optimizer_params={"learning_rate": 0.1,
                                       "momentum": 0.9, "wd": 1e-4},
                     dtype=jnp.bfloat16)
    tr._collect(sample_data=np.zeros((2, 224, 224, 3), np.float32))

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    train = tuple(sds(p.shape, jnp.float32) for p in tr._trainable)
    aux = tuple(sds(p.shape, jnp.float32) for p in tr._aux)
    args = (train, aux, train, sds((2,), jnp.uint32), sds((), jnp.int32),
            sds((B, 224, 224, 3), jnp.float32), sds((B,), jnp.float32))
    return jax.jit(tr._build_step_fn(), donate_argnums=(0, 1, 2)), args


@pytest.mark.slow  # 35-50 s a case here, and tier-1 runs into its limit
@pytest.mark.parametrize("fused", [None, "1"])
def test_whole_train_step_compiles_for_v5e(one_chip, monkeypatch, fused):
    """One whole train step fits the chip: the compiler accepts it and its
    temporaries plus arguments stay inside the chip's 16 GB. The default
    lowering is the composed one; with the Pallas epilogues switched on
    (and their interpret decision steered to compiled, as on the chip)
    all 192 kernels are in the program and it still fits."""
    from mxnet_tpu.ops import pallas_kernels as pk
    from mxnet_tpu.telemetry.efficiency import DEVICE_PEAKS
    if fused is None:
        monkeypatch.delenv("MXTPU_FUSED_EPILOGUE", raising=False)
    else:
        monkeypatch.setenv("MXTPU_FUSED_EPILOGUE", fused)
        monkeypatch.setattr(pk, "_interpret_for", lambda _x: False)
    fn, args = _describe_step(one_chip)
    assert len(args[0]) == 161 and len(args[1]) == 106
    compiled = fn.lower(*args).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < \
        DEVICE_PEAKS["TPU v5 lite"]["hbm_bytes"]
    assert _n_kernels(compiled) == (0 if fused is None else 192)
