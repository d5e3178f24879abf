"""Neural-network operators: the MXU-heavy family.

Reference: src/operator/nn/ (Convolution, FullyConnected, BatchNorm, Pooling,
Activation, Softmax, Dropout, LayerNorm, LRN, UpSampling, Embedding ...) plus
legacy top-level ops (LeakyReLU, InstanceNorm, L2Normalization, Sequence*).

TPU-native notes:
- Convolution/FullyConnected lower to ``lax.conv_general_dilated`` /
  ``jnp.dot`` which XLA tiles onto the MXU; there is no cuDNN-autotune
  analog because XLA picks the layout/tiling (the reference's
  MXNET_CUDNN_AUTOTUNE_DEFAULT knob is subsumed by the compiler).
- Ops whose reference backward is *defined* rather than derived
  (SoftmaxOutput, MakeLoss-style grad scaling) use ``jax.custom_vjp`` so both
  the eager tape and whole-graph jit see identical gradients.
- Stateful-RNG ops (Dropout) take an explicit PRNG key input (rng=True) —
  functional randomness, reproducible under jit, instead of the reference's
  per-device PRNG resource (ref: include/mxnet/resource.h kRandom).
- BatchNorm returns (out, mean, var); moving-stat update is done by the
  caller rebinding its running buffers (the reference mutates aux states
  in-place inside the op — impossible and unnecessary in functional XLA).
"""
from __future__ import annotations

import numpy as _np

from ..base import MXNetError
from .registry import register


def _jnp():
    import jax.numpy as jnp
    return jnp


def _jax():
    import jax
    return jax


def _lax():
    import jax.lax as lax
    return lax


def _tuplify(v, n):
    if v is None or v == ():
        return (1,) * n
    if isinstance(v, int):
        return (v,) * n
    return tuple(v)


# ---------------------------------------------------------------------------
# FullyConnected (ref: src/operator/nn/fully_connected.cc)
# ---------------------------------------------------------------------------

@register("FullyConnected", aliases=("fully_connected",))
def _fully_connected(data, weight, *maybe_bias, num_hidden=1, no_bias=False,
                     flatten=True):
    jnp = _jnp()
    x = data
    if flatten and x.ndim > 2:
        x = x.reshape((x.shape[0], -1))
    elif not flatten and x.ndim > 2:
        pass  # apply to last axis
    out = jnp.matmul(x, weight.T)
    if not no_bias and maybe_bias:
        out = out + maybe_bias[0]
    return out


# ---------------------------------------------------------------------------
# Convolution / Deconvolution (ref: src/operator/nn/convolution.cc,
# deconvolution.cc; im2col replaced by XLA's native conv lowering)
# ---------------------------------------------------------------------------

# data layout -> (lhs, rhs, out) dimension-number specs. Channel-last
# ("TPU-native": C rides the 128-lane minor dim) uses MXNet's NHWC weight
# convention (num_filter, *spatial, C/num_group) = O...I.
_CONV_DN = {"NCW": ("NCW", "OIW", "NCW"),
            "NWC": ("NWC", "OWI", "NWC"),
            "NCHW": ("NCHW", "OIHW", "NCHW"),
            "NHWC": ("NHWC", "OHWI", "NHWC"),
            "NCDHW": ("NCDHW", "OIDHW", "NCDHW"),
            "NDHWC": ("NDHWC", "ODHWI", "NDHWC")}
_DEFAULT_LAYOUT = {1: "NCW", 2: "NCHW", 3: "NCDHW"}


def _conv_layout(layout, nd):
    layout = layout or _DEFAULT_LAYOUT[nd]
    if layout not in _CONV_DN or len(layout) != nd + 2:
        raise MXNetError(f"unsupported {nd}-d conv layout {layout!r}")
    return layout


@register("Convolution", aliases=("conv2d",))
def _convolution(data, weight, *maybe_bias, kernel=(), stride=(), dilate=(),
                 pad=(), num_filter=1, num_group=1, workspace=1024,
                 no_bias=False, cudnn_tune=None, cudnn_off=False, layout=None):
    lax = _lax()
    nd = len(kernel)
    stride = _tuplify(stride, nd)
    dilate = _tuplify(dilate, nd)
    pad = _tuplify(pad if pad else 0, nd)
    layout = _conv_layout(layout, nd)
    from . import resid8
    rdt = resid8.resid_dtype()
    is_float = _jnp().issubdtype(data.dtype, _jnp().floating)
    if is_float and resid8.conv_int8():
        # int8-on-MXU training conv (quantized forward, exact dx)
        out = resid8.conv_int8_train(data, weight, stride, pad, dilate,
                                     _CONV_DN[layout], num_group)
    elif rdt is not None and is_float:
        # 8-bit residual mode: the saved backward input is stored fp8
        # (bias add stays outside — its grad needs no residual)
        out = resid8.conv_resid8(data, weight, stride, pad, dilate,
                                 _CONV_DN[layout], num_group, rdt)
    else:
        dn = lax.conv_dimension_numbers(data.shape, weight.shape,
                                        _CONV_DN[layout])
        out = lax.conv_general_dilated(
            data, weight,
            window_strides=stride,
            padding=[(p, p) for p in pad],
            rhs_dilation=dilate,
            dimension_numbers=dn,
            feature_group_count=num_group,
        )
    if not no_bias and maybe_bias:
        bias = maybe_bias[0]
        bshape = [1] * (nd + 2)
        bshape[layout.index("C")] = -1
        out = out + bias.reshape(tuple(bshape))
    return out


@register("Deconvolution")
def _deconvolution(data, weight, *maybe_bias, kernel=(), stride=(), dilate=(),
                   pad=(), adj=(), target_shape=(), num_filter=1, num_group=1,
                   workspace=1024, no_bias=True, cudnn_tune=None,
                   cudnn_off=False, layout=None):
    lax = _lax()
    nd = len(kernel)
    stride = _tuplify(stride, nd)
    dilate = _tuplify(dilate if dilate else 1, nd)
    pad = _tuplify(pad if pad else 0, nd)
    adj = _tuplify(adj if adj else 0, nd)
    # transposed conv = gradient of conv wrt input: lhs-dilate by stride;
    # the effective kernel extent is dilate*(k-1)+1
    pads = [(dilate[i] * (kernel[i] - 1) - pad[i],
             dilate[i] * (kernel[i] - 1) - pad[i] + adj[i])
            for i in range(nd)]
    # weight layout is (C_in, num_filter, *k) in EVERY data layout (the
    # reference convention), so only the DATA spec follows `layout`; the
    # kernel spec is always the channel-first "OI*", which with
    # transpose_kernel=True lax treats relative to the FORWARD conv —
    # the exact gradient-of-conv semantics the reference implements.
    # Channel-last data layouts (NWC/NHWC/NDHWC) are first-class: on TPU
    # they avoid the transposes NCHW forces around every (de)convolution.
    layout = _conv_layout(layout, nd)
    kspec = _CONV_DN[_DEFAULT_LAYOUT[nd]][1]
    dn = (layout, kspec, layout)
    if num_group != 1:
        raise MXNetError("grouped Deconvolution not yet supported")
    out = lax.conv_transpose(data, weight, strides=stride, padding=pads,
                             rhs_dilation=dilate, dimension_numbers=dn,
                             transpose_kernel=True)
    if not no_bias and maybe_bias:
        bshape = [1] * (nd + 2)
        bshape[layout.index("C")] = -1
        out = out + maybe_bias[0].reshape(tuple(bshape))
    return out


# ---------------------------------------------------------------------------
# Pooling (ref: src/operator/nn/pooling.cc + pool.h)
# ---------------------------------------------------------------------------

@register("Pooling", aliases=("pooling",))
def _pooling(data, kernel=(), pool_type="max", global_pool=False,
             cudnn_off=False, pooling_convention="valid", stride=(), pad=(),
             p_value=2, count_include_pad=True, layout=None):
    jnp, lax = _jnp(), _lax()
    nd = data.ndim - 2
    layout = _conv_layout(layout, nd)
    # spatial axis positions for the layout (channel-first: 2..; NHWC: 1..)
    spatial = [layout.index(c) for c in layout if c not in ("N", "C")]
    if global_pool:
        axes = tuple(spatial)
        if pool_type == "max":
            return jnp.max(data, axis=axes, keepdims=True)
        if pool_type in ("avg", "sum"):
            r = jnp.sum(data, axis=axes, keepdims=True)
            if pool_type == "avg":
                r = r / _np.prod([data.shape[a] for a in axes])
            return r
        if pool_type == "lp":
            return jnp.power(jnp.sum(jnp.power(jnp.abs(data), p_value),
                                     axis=axes, keepdims=True), 1.0 / p_value)
        raise MXNetError(f"unknown pool_type {pool_type}")

    kernel = tuple(kernel)
    stride = _tuplify(stride if stride else 1, nd)
    pad = _tuplify(pad if pad else 0, nd)

    # ceil ("full") convention: extra high-side padding so the last window fits
    extra = [0] * nd
    if pooling_convention == "full":
        for i in range(nd):
            in_i = data.shape[spatial[i]]
            out_i = -(-(in_i + 2 * pad[i] - kernel[i]) // stride[i]) + 1  # ceil
            need = (out_i - 1) * stride[i] + kernel[i] - in_i - 2 * pad[i]
            extra[i] = max(0, need)

    window = [1] * (nd + 2)
    strides = [1] * (nd + 2)
    pads = [(0, 0)] * (nd + 2)
    for i, ax in enumerate(spatial):
        window[ax] = kernel[i]
        strides[ax] = stride[i]
        pads[ax] = (pad[i], pad[i] + extra[i])
    window, strides, pads = tuple(window), tuple(strides), tuple(pads)

    if pool_type == "max":
        init = -jnp.inf if jnp.issubdtype(data.dtype, jnp.floating) else \
            jnp.iinfo(data.dtype).min
        return lax.reduce_window(data, init, lax.max, window, strides, pads)
    if pool_type in ("avg", "sum"):
        s = lax.reduce_window(data, 0.0, lax.add, window, strides, pads)
        if pool_type == "sum":
            return s
        if count_include_pad:
            return s / float(_np.prod(kernel))
        ones = jnp.ones(data.shape, data.dtype)
        cnt = lax.reduce_window(ones, 0.0, lax.add, window, strides, pads)
        return s / cnt
    if pool_type == "lp":
        s = lax.reduce_window(jnp.power(jnp.abs(data), p_value), 0.0, lax.add,
                              window, strides, pads)
        return jnp.power(s, 1.0 / p_value)
    raise MXNetError(f"unknown pool_type {pool_type}")


# ---------------------------------------------------------------------------
# Normalization (ref: batch_norm.cc, layer_norm.cc, instance_norm.cc,
# l2_normalization.cc, lrn.cc)
# ---------------------------------------------------------------------------

def _bn_batch_stats(data, red, n):
    """Single-pass f32 (mean, var) over the reduce axes. Assumed-mean
    shift: subtracting one real sample per channel before reducing keeps
    |d| ~ std, so E[d^2] - E[d]^2 has no catastrophic cancellation even
    for data with mean >> std. The f32 converts fuse into the reduction,
    so HBM reads stay at the input dtype's width."""
    jnp = _jnp()
    idx0 = tuple(slice(0, 1) if i in red else slice(None)
                 for i in range(data.ndim))
    shift = _lax().stop_gradient(data[idx0]).astype(jnp.float32)
    d = data.astype(jnp.float32) - shift
    m1 = jnp.sum(d, axis=red) / n
    m2 = jnp.sum(jnp.square(d), axis=red) / n
    mean = shift.reshape(-1) + m1
    var = jnp.maximum(m2 - jnp.square(m1), 0.0)
    return mean, var


def _make_bn_core(resid_dtype_name=None):
    """Training-mode BatchNorm with a hand-fused backward
    (jax.custom_vjp). Why not plain autodiff: value_and_grad over the
    naive formula saves f32 activation-sized residuals (x - mean,
    squares, ...) and runs the whole backward chain at f32 width — on
    TPU that doubles the HBM traffic of exactly the op that is already
    bandwidth-bound (the gap BENCH_r02/README identified). Here the only
    activation-sized residual is the bf16 input itself — or, under
    MXNET_RESID_DTYPE (ops/resid8.py), the fp8 NORMALIZED input xhat,
    halving the residual bytes again AND skipping the backward's
    recompute of xhat. Forward and backward do their elementwise math in
    f32 REGISTERS but read/write compute-dtype, and the per-channel
    reductions accumulate in f32
    (ref: src/operator/nn/batch_norm.cu BatchNormalizationBackward —
    the same sum_dy / sum_dy_xhat closed form cuDNN uses)."""
    import jax
    jnp = _jnp()
    rdt = jnp.dtype(resid_dtype_name) if resid_dtype_name else None

    def _shapes(data, axis):
        ax = axis % data.ndim
        red = tuple(i for i in range(data.ndim) if i != ax)
        bshape = tuple(data.shape[ax] if i == ax else 1
                       for i in range(data.ndim))
        n = 1
        for i in red:
            n *= data.shape[i]
        return red, bshape, n

    def plain(data, g32, beta32, axis, eps):
        red, bshape, n = _shapes(data, axis)
        mean, var = _bn_batch_stats(data, red, n)
        inv = _lax().rsqrt(var + eps)
        out = (data.astype(jnp.float32) - mean.reshape(bshape)) \
            * (inv * g32).reshape(bshape) + beta32.reshape(bshape)
        return out.astype(data.dtype), mean, var

    def fwd(data, g32, beta32, axis, eps):
        # the plain function, not the custom_vjp made of it below: a
        # jax.checkpoint policy then sees the per-channel reductions and
        # can keep them (cached_op.py), where one opaque custom_vjp_call
        # would have the statistics read the whole input again in backward
        out, mean, var = plain(data, g32, beta32, axis, eps)
        inv = _lax().rsqrt(var + eps)
        if rdt is None:
            return (out, mean, var), (data, mean, inv, g32)
        _, bshape, _ = _shapes(data, axis)
        xhat = (data.astype(jnp.float32) - mean.reshape(bshape)) \
            * inv.reshape(bshape)
        from .resid8 import _sat_cast
        return (out, mean, var), (_sat_cast(xhat, rdt), inv, g32)

    def bwd(axis, eps, res, cots):
        cot_out = cots[0]  # mean/var outputs only feed running-stat
        #                    updates — no gradient path (stop-gradient
        #                    semantics, like the reference's aux states)
        if rdt is None:
            data, mean, inv, g32 = res
            red, bshape, n = _shapes(data, axis)
            xhat = (data.astype(jnp.float32) - mean.reshape(bshape)) \
                * inv.reshape(bshape)
            out_dtype = data.dtype
        else:
            xhat_q, inv, g32 = res
            red, bshape, n = _shapes(xhat_q, axis)
            xhat = xhat_q.astype(jnp.float32)
            out_dtype = cot_out.dtype
        dy32 = cot_out.astype(jnp.float32)
        sum_dy = jnp.sum(dy32, axis=red)
        sum_dy_xhat = jnp.sum(dy32 * xhat, axis=red)
        dbeta = sum_dy
        dgamma = sum_dy_xhat
        dx = (g32 * inv).reshape(bshape) * (
            dy32 - (sum_dy / n).reshape(bshape)
            - xhat * (sum_dy_xhat / n).reshape(bshape))
        return dx.astype(out_dtype), dgamma, dbeta

    core = jax.custom_vjp(plain, nondiff_argnums=(3, 4))
    core.defvjp(fwd, bwd)
    return core


_BN_CORE = {}


@register("BatchNorm", aliases=("batch_norm",), num_outputs=3,
          aux_inputs=(3, 4))
def _batch_norm(data, gamma, beta, moving_mean, moving_var, eps=1e-3,
                momentum=0.9, fix_gamma=True, use_global_stats=False,
                output_mean_var=False, axis=1, cudnn_off=False,
                _training=False):
    jnp = _jnp()
    ax = axis % data.ndim
    bshape = tuple(data.shape[ax] if i == ax else 1 for i in range(data.ndim))
    # statistics in f32 (bf16 inputs would lose too much precision; matches
    # the reference's fp16 BatchNorm running in fp32 internally)
    g = jnp.ones(gamma.shape, jnp.float32) if fix_gamma \
        else gamma.astype(jnp.float32)
    if _training and not use_global_stats:
        from . import resid8
        rdt = resid8.resid_dtype() if \
            jnp.issubdtype(data.dtype, jnp.floating) else None
        core = _BN_CORE.get(rdt)
        if core is None:
            core = _BN_CORE[rdt] = _make_bn_core(rdt)
        return core(data, g, beta.astype(jnp.float32), ax, float(eps))
    mean = moving_mean.astype(jnp.float32)
    var = moving_var.astype(jnp.float32)
    inv = _lax().rsqrt(var + eps)
    # inference: normalize in f32 registers (converts fuse into the
    # surrounding elementwise kernel; traffic stays at the input width)
    out = (data.astype(jnp.float32) - mean.reshape(bshape)) \
        * (inv * g).reshape(bshape) \
        + beta.astype(jnp.float32).reshape(bshape)
    return out.astype(data.dtype), mean, var


# ---------------------------------------------------------------------------
# Fused bottleneck epilogues: conv -> BN -> ReLU and
# conv -> BN -> add(residual) -> ReLU as ONE op (Pallas kernels in
# ops/pallas_kernels.py). The separate BatchNorm/add/Activation ops leave
# XLA free to materialize the intermediate activations between them —
# measured as the dominant HBM traffic of the ResNet-50 train step
# (docs/perf.md roofline). The kernels are opt-in: MXTPU_FUSED_EPILOGUE=1
# (trace-time flag, part of every jit-cache key); the default is the
# composed unfused lowering, which XLA can partition over a mesh.
# ---------------------------------------------------------------------------

def _fused_epilogue_enabled() -> bool:
    from ..base import env
    return bool(env.get("MXTPU_FUSED_EPILOGUE"))


def _fused_bn_act_impl(data, residual, gamma, beta, moving_mean, moving_var,
                       eps, fix_gamma, use_global_stats, axis, _training):
    jnp = _jnp()
    ax = axis % data.ndim
    g32 = jnp.ones(gamma.shape, jnp.float32) if fix_gamma \
        else gamma.astype(jnp.float32)
    b32 = beta.astype(jnp.float32)
    is_float = jnp.issubdtype(data.dtype, jnp.floating)
    if _training and not use_global_stats:
        if ax == data.ndim - 1 and is_float and _fused_epilogue_enabled():
            from .pallas_kernels import fused_bn_act
            return fused_bn_act(data, residual, g32, b32, float(eps))
        # composed lowering: exactly the unfused BatchNorm -> (add) ->
        # ReLU chain, including the fp8-residual lowering of each piece
        from . import resid8
        rdt = resid8.resid_dtype() if is_float else None
        core = _BN_CORE.get(rdt)
        if core is None:
            core = _BN_CORE[rdt] = _make_bn_core(rdt)
        out, mean, var = core(data, g32, b32, ax, float(eps))
        if residual is not None:
            out = out + residual
        return _activation(out, act_type="relu"), mean, var
    # inference: moving stats, f32 registers, one fused elementwise chain
    bshape = tuple(data.shape[ax] if i == ax else 1 for i in range(data.ndim))
    mean = moving_mean.astype(jnp.float32)
    var = moving_var.astype(jnp.float32)
    inv = _lax().rsqrt(var + eps)
    out = (data.astype(jnp.float32) - mean.reshape(bshape)) \
        * (inv * g32).reshape(bshape) + b32.reshape(bshape)
    if residual is not None:
        out = out + residual.astype(jnp.float32)
    return jnp.maximum(out, 0.0).astype(data.dtype), mean, var


@register("_contrib_fused_bn_relu", num_outputs=3, aux_inputs=(3, 4))
def _fused_bn_relu(data, gamma, beta, moving_mean, moving_var, eps=1e-5,
                   momentum=0.9, fix_gamma=False, use_global_stats=False,
                   axis=-1, _training=False):
    """Fused ``BatchNorm -> ReLU`` (returns (out, mean, var) like
    BatchNorm; moving-stat update is the caller's, as everywhere)."""
    return _fused_bn_act_impl(data, None, gamma, beta, moving_mean,
                              moving_var, eps, fix_gamma, use_global_stats,
                              axis, _training)


@register("_contrib_fused_bn_add_relu", num_outputs=3, aux_inputs=(4, 5))
def _fused_bn_add_relu(data, residual, gamma, beta, moving_mean, moving_var,
                       eps=1e-5, momentum=0.9, fix_gamma=False,
                       use_global_stats=False, axis=-1, _training=False):
    """Fused ``BatchNorm -> add(residual) -> ReLU`` — the ResNet
    bottleneck tail: relu(BN(conv(x)) + shortcut)."""
    return _fused_bn_act_impl(data, residual, gamma, beta, moving_mean,
                              moving_var, eps, fix_gamma, use_global_stats,
                              axis, _training)


@register("LayerNorm", aliases=("layer_norm",), num_outputs=3)
def _layer_norm(data, gamma, beta, axis=-1, eps=1e-5, output_mean_var=False):
    jnp = _jnp()
    ax = axis % data.ndim
    mean = jnp.mean(data, axis=ax, keepdims=True)
    var = jnp.var(data, axis=ax, keepdims=True)
    inv = _lax().rsqrt(var + eps)
    shape = tuple(data.shape[ax] if i == ax else 1 for i in range(data.ndim))
    out = (data - mean) * inv * gamma.reshape(shape) + beta.reshape(shape)
    return out, jnp.squeeze(mean, ax), jnp.squeeze(var, ax)


@register("InstanceNorm")
def _instance_norm(data, gamma, beta, eps=1e-3):
    jnp = _jnp()
    red = tuple(range(2, data.ndim))
    mean = jnp.mean(data, axis=red, keepdims=True)
    var = jnp.var(data, axis=red, keepdims=True)
    shape = (1, -1) + (1,) * (data.ndim - 2)
    return (data - mean) * _lax().rsqrt(var + eps) * gamma.reshape(shape) \
        + beta.reshape(shape)


@register("L2Normalization")
def _l2_normalization(data, eps=1e-10, mode="instance"):
    jnp = _jnp()
    if mode == "instance":
        red = tuple(range(1, data.ndim))
        n = jnp.sqrt(jnp.sum(jnp.square(data), axis=red, keepdims=True) + eps)
    elif mode == "channel":
        n = jnp.sqrt(jnp.sum(jnp.square(data), axis=1, keepdims=True) + eps)
    elif mode == "spatial":
        red = tuple(range(2, data.ndim))
        n = jnp.sqrt(jnp.sum(jnp.square(data), axis=red, keepdims=True) + eps)
    else:
        raise MXNetError(f"unknown L2Normalization mode {mode}")
    return data / n


@register("LRN")
def _lrn(data, alpha=1e-4, beta=0.75, knorm=2.0, nsize=5):
    jnp = _jnp()
    sq = jnp.square(data)
    half = nsize // 2
    padded = jnp.pad(sq, ((0, 0), (half, half), (0, 0), (0, 0)))
    c = data.shape[1]
    acc = sum(padded[:, i:i + c] for i in range(nsize))
    return data / jnp.power(knorm + alpha * acc / nsize, beta)


# ---------------------------------------------------------------------------
# Activations (ref: activation.cc, leaky_relu.cc)
# ---------------------------------------------------------------------------

@register("Activation", aliases=("activation",))
def _activation(data, act_type="relu"):
    jnp = _jnp()
    if act_type == "relu":
        from . import resid8
        rdt = resid8.resid_dtype()
        if rdt is not None and jnp.issubdtype(data.dtype, jnp.floating):
            return resid8.relu_resid8(data, rdt)
        return jnp.maximum(data, 0)
    if act_type == "sigmoid":
        return 1.0 / (1.0 + jnp.exp(-data))
    if act_type == "tanh":
        return jnp.tanh(data)
    if act_type == "softrelu":
        return jnp.logaddexp(data, 0.0)
    if act_type == "softsign":
        return data / (1.0 + jnp.abs(data))
    raise MXNetError(f"unknown act_type {act_type}")


@register("LeakyReLU")
def _leaky_relu(data, *maybe_gamma, act_type="leaky", slope=0.25,
                lower_bound=0.125, upper_bound=0.334):
    jnp = _jnp()
    if act_type == "leaky":
        return jnp.where(data >= 0, data, slope * data)
    if act_type == "elu":
        return jnp.where(data >= 0, data, slope * (jnp.exp(data) - 1.0))
    if act_type == "selu":
        a, l = 1.6732632423543772, 1.0507009873554805
        return l * jnp.where(data >= 0, data, a * (jnp.exp(data) - 1.0))
    if act_type == "gelu":
        import jax.scipy.special as jsp
        return 0.5 * data * (1.0 + jsp.erf(data / _np.sqrt(2.0)))
    if act_type == "prelu":
        gamma = maybe_gamma[0]
        shape = (1, -1) + (1,) * (data.ndim - 2) if data.ndim > 1 else (-1,)
        g = gamma.reshape(shape) if gamma.ndim == 1 else gamma
        return jnp.where(data >= 0, data, g * data)
    if act_type == "rrelu":
        mid = (lower_bound + upper_bound) / 2.0
        return jnp.where(data >= 0, data, mid * data)
    raise MXNetError(f"unknown act_type {act_type}")


# ---------------------------------------------------------------------------
# Softmax family (ref: softmax.cc, softmax_output.cc, softmax_activation.cc)
# ---------------------------------------------------------------------------

def _length_mask(data, length, axis):
    """Boolean mask selecting positions < length along ``axis`` (ref:
    softmax-inl.h length path: the length tensor has data's shape with
    the softmax axis removed)."""
    jnp = _jnp()
    ax = axis % data.ndim
    shape = [1] * data.ndim
    shape[ax] = data.shape[ax]
    positions = jnp.arange(data.shape[ax]).reshape(shape)
    return positions < jnp.expand_dims(length, ax).astype(jnp.int32)


@register("softmax")
def _softmax(data, *maybe_length, axis=-1, temperature=None, dtype=None,
             use_length=False):
    import jax
    jnp = _jnp()
    x = data if temperature in (None, 1.0) else data / temperature
    x = x.astype(jnp.float32)
    if use_length:
        if not maybe_length:
            raise MXNetError("softmax: use_length=True requires the "
                             "length input")
        # masked softmax: exp(finfo.min - max) is exactly 0 in f32, so
        # valid positions normalize over the valid slice alone and the
        # where() zeroes masked positions (all-masked rows -> all zeros)
        mask = _length_mask(data, maybe_length[0], axis)
        neg = jnp.finfo(jnp.float32).min
        p = jax.nn.softmax(jnp.where(mask, x, neg), axis=axis)
        out = jnp.where(mask, p, 0.0)
    else:
        out = jax.nn.softmax(x, axis=axis)
    return out.astype(_np.dtype(dtype)) if dtype is not None \
        else out.astype(data.dtype)


@register("log_softmax")
def _log_softmax(data, *maybe_length, axis=-1, temperature=None,
                 dtype=None, use_length=False):
    import jax
    jnp = _jnp()
    x = data if temperature in (None, 1.0) else data / temperature
    x = x.astype(jnp.float32)
    if use_length:
        if not maybe_length:
            raise MXNetError("log_softmax: use_length=True requires the "
                             "length input")
        mask = _length_mask(data, maybe_length[0], axis)
        neg = jnp.finfo(jnp.float32).min
        out = jax.nn.log_softmax(jnp.where(mask, x, neg), axis=axis)
        # masked positions output 0.0 like the reference kernel
        # (softmax-inl.h SoftmaxWithLength) so mask*logp stays finite
        out = jnp.where(mask, out, 0.0)
    else:
        out = jax.nn.log_softmax(x, axis=axis)
    return out.astype(_np.dtype(dtype)) if dtype is not None \
        else out.astype(data.dtype)


@register("softmin")
def _softmin(data, *maybe_length, axis=-1, temperature=None, dtype=None,
             use_length=False):
    return _softmax(-data, *maybe_length, axis=axis,
                    temperature=temperature, dtype=dtype,
                    use_length=use_length)


@register("SoftmaxActivation")
def _softmax_activation(data, mode="instance"):
    import jax
    if mode == "channel":
        return jax.nn.softmax(data, axis=1)
    return jax.nn.softmax(data.reshape((data.shape[0], -1)),
                          axis=-1).reshape(data.shape)


@register("softmax_cross_entropy")
def _softmax_cross_entropy(data, label):
    import jax
    logp = jax.nn.log_softmax(data, axis=-1)
    lbl = label.astype(_np.int32)
    nll = -_jnp().take_along_axis(logp, lbl[:, None], axis=-1)
    return _jnp().sum(nll)


def _make_softmax_output():
    import jax

    @jax.custom_vjp
    def softmax_output(data, label, grad_scale, ignore_label, use_ignore,
                       multi_output, normalization_id, smooth_alpha):
        return jax.nn.softmax(data, axis=-1 if data.ndim == 2 else 1)

    def fwd(data, label, grad_scale, ignore_label, use_ignore, multi_output,
            normalization_id, smooth_alpha):
        out = softmax_output(data, label, grad_scale, ignore_label,
                             use_ignore, multi_output, normalization_id,
                             smooth_alpha)
        return out, (out, label, grad_scale, ignore_label, use_ignore,
                     normalization_id, smooth_alpha)

    def bwd(res, g):
        jnp = _jnp()
        out, label, grad_scale, ignore_label, use_ignore, norm_id, smooth = res
        axis = -1 if out.ndim == 2 else 1
        nclass = out.shape[axis]
        lbl = label.astype(_np.int32)
        onehot = jax.nn.one_hot(lbl, nclass, axis=axis, dtype=out.dtype)
        if smooth > 0:
            onehot = onehot * (1 - smooth) + smooth / (nclass - 1) * (1 - onehot)
        grad = out - onehot
        if use_ignore:
            mask = (lbl != int(ignore_label)).astype(out.dtype)
            grad = grad * jnp.expand_dims(mask, axis)
        n = out.shape[0]
        if norm_id == 2:  # valid
            denom = jnp.maximum(jnp.sum(lbl != int(ignore_label)), 1) \
                if use_ignore else n
            grad = grad / denom
        elif norm_id == 1:  # batch
            grad = grad / n
        grad = grad * grad_scale
        return (grad, None, None, None, None, None, None, None)

    softmax_output.defvjp(fwd, bwd)
    return softmax_output


_SOFTMAX_OUTPUT = None
_NORM_IDS = {"null": 0, "batch": 1, "valid": 2}


@register("SoftmaxOutput", aliases=("Softmax",))
def _softmax_output_op(data, label, grad_scale=1.0, ignore_label=-1.0,
                       multi_output=False, use_ignore=False,
                       preserve_shape=False, normalization="null",
                       out_grad=False, smooth_alpha=0.0):
    """Softmax forward whose *defined* backward is (p - onehot(label)) —
    the reference's fused softmax+CE gradient (ref:
    src/operator/softmax_output-inl.h)."""
    global _SOFTMAX_OUTPUT
    if _SOFTMAX_OUTPUT is None:
        _SOFTMAX_OUTPUT = _make_softmax_output()
    return _SOFTMAX_OUTPUT(data, label, grad_scale, ignore_label,
                           bool(use_ignore), bool(multi_output),
                           _NORM_IDS.get(normalization, 0), smooth_alpha)


@register("LinearRegressionOutput")
def _linear_regression_output(data, label, grad_scale=1.0):
    import jax

    @jax.custom_vjp
    def f(d, l):
        return d

    def fwd(d, l):
        return d, (d, l)

    def bwd(res, g):
        d, l = res
        return ((d - l.reshape(d.shape)) * grad_scale, None)

    f.defvjp(fwd, bwd)
    return f(data, label)


@register("SVMOutput")
def _svm_output(data, label, margin=1.0, regularization_coefficient=1.0,
                use_linear=False):
    """Identity forward with hinge-loss backward
    (ref: src/operator/svm_output.cc L1_SVM/L2_SVM kernels)."""
    import jax
    jnp = _jnp()
    margin = float(margin)
    reg = float(regularization_coefficient)

    @jax.custom_vjp
    def f(d, l):
        return d

    def fwd(d, l):
        return d, (d, l)

    def bwd(res, g):
        d, l = res
        n_class = d.shape[1]
        onehot = jax.nn.one_hot(l.astype(jnp.int32), n_class,
                                dtype=d.dtype)
        if use_linear:  # L1-SVM
            pos = -(margin > d).astype(d.dtype) * reg
            neg = (margin > -d).astype(d.dtype) * reg
        else:  # L2-SVM
            pos = jnp.where(margin > d, 2.0 * (margin - d), 0.0) * -reg
            neg = jnp.where(margin > -d, -2.0 * (margin + d), 0.0) * -reg
        return (jnp.where(onehot > 0, pos, neg).astype(d.dtype), None)

    f.defvjp(fwd, bwd)
    return f(data, label)


@register("MakeLoss")
def _make_loss(data, grad_scale=1.0, valid_thresh=0.0,
               normalization="null"):
    """Identity forward; backward is the constant grad_scale, optionally
    normalized by batch size or the count of entries above valid_thresh
    (ref: src/operator/make_loss-inl.h)."""
    import jax
    jnp = _jnp()
    gs = float(grad_scale)

    @jax.custom_vjp
    def f(d):
        return d

    def fwd(d):
        return d, d

    def bwd(d, g):
        if normalization == "batch":
            scale = gs / d.shape[0]
            return (jnp.full(d.shape, scale, d.dtype),)
        if normalization == "valid":
            n_valid = jnp.maximum(
                jnp.sum((d > valid_thresh).astype(jnp.float32)), 1.0)
            return ((jnp.full(d.shape, gs, jnp.float32) / n_valid)
                    .astype(d.dtype),)
        return (jnp.full(d.shape, gs, d.dtype),)

    f.defvjp(fwd, bwd)
    return f(data)


@register("IdentityAttachKLSparseReg",
          aliases=("identity_attach_KL_sparse_reg",))
def _identity_attach_kl_sparse_reg(data, sparseness_target=0.1,
                                   penalty=0.001, momentum=0.9):
    """Identity forward; backward adds the KL-sparsity penalty gradient
    penalty * (-rho/rho_hat + (1-rho)/(1-rho_hat)) per hidden unit, with
    rho_hat the batch mean activation (ref:
    src/operator/identity_attach_KL_sparse_reg-inl.h; the reference's
    momentum-smoothed moving average is simplified to the batch average —
    pair only with sigmoid activations)."""
    import jax
    jnp = _jnp()
    rho = float(sparseness_target)
    pen = float(penalty)

    @jax.custom_vjp
    def f(d):
        return d

    def fwd(d):
        return d, d

    def bwd(d, g):
        avg = jnp.clip(jnp.mean(d, axis=0, keepdims=True), 1e-6, 1 - 1e-6)
        kl_grad = pen * (-(rho / avg) + (1.0 - rho) / (1.0 - avg))
        return ((g + kl_grad).astype(d.dtype),)

    f.defvjp(fwd, bwd)
    return f(data)


@register("MAERegressionOutput")
def _mae_regression_output(data, label, grad_scale=1.0):
    import jax

    @jax.custom_vjp
    def f(d, l):
        return d

    def fwd(d, l):
        return d, (d, l)

    def bwd(res, g):
        d, l = res
        return (_jnp().sign(d - l.reshape(d.shape)) * grad_scale, None)

    f.defvjp(fwd, bwd)
    return f(data, label)


@register("LogisticRegressionOutput")
def _logistic_regression_output(data, label, grad_scale=1.0):
    import jax

    @jax.custom_vjp
    def f(d, l):
        return 1.0 / (1.0 + _jnp().exp(-d))

    def fwd(d, l):
        return f(d, l), (f(d, l), l)

    def bwd(res, g):
        p, l = res
        return ((p - l.reshape(p.shape)) * grad_scale, None)

    f.defvjp(fwd, bwd)
    return f(data, label)


# ---------------------------------------------------------------------------
# Dropout (ref: src/operator/nn/dropout.cc) — explicit-key functional RNG
# ---------------------------------------------------------------------------

@register("Dropout", rng=True)
def _dropout(data, _key, p=0.5, mode="training", axes=(), cudnn_off=False,
             _training=False):
    if (not _training and mode != "always") or p <= 0:
        return data
    import jax
    # `axes` = variational dropout: mask is broadcast along the listed axes
    if axes:
        shape = [1 if i in tuple(axes) else data.shape[i]
                 for i in range(data.ndim)]
    else:
        shape = list(data.shape)
    keep = 1.0 - p
    mask = jax.random.bernoulli(_key, keep, tuple(shape)).astype(data.dtype)
    return data * mask / keep


# ---------------------------------------------------------------------------
# Embedding & sequence ops
# ---------------------------------------------------------------------------

@register("Embedding")
def _embedding(data, weight, input_dim=0, output_dim=0, dtype="float32",
               sparse_grad=False):
    idx = data.astype(_np.int32)
    return weight[idx]


@register("SequenceMask")
def _sequence_mask(data, *maybe_len, use_sequence_length=False, value=0.0,
                   axis=0):
    jnp = _jnp()
    if not use_sequence_length or not maybe_len:
        return data
    seq_len = maybe_len[0]
    T = data.shape[axis]
    pos = jnp.arange(T)
    # axis is the time axis; batch is the other of {0,1}
    if axis == 0:
        mask = pos[:, None] < seq_len[None, :]
        mask = mask.reshape(mask.shape + (1,) * (data.ndim - 2))
    else:
        mask = pos[None, :] < seq_len[:, None]
        mask = mask.reshape(mask.shape + (1,) * (data.ndim - 2))
    return jnp.where(mask, data, value)


@register("SequenceLast")
def _sequence_last(data, *maybe_len, use_sequence_length=False, axis=0):
    jnp = _jnp()
    if not use_sequence_length or not maybe_len:
        return jnp.take(data, data.shape[axis] - 1, axis=axis)
    seq_len = maybe_len[0].astype(_np.int32) - 1
    if axis == 0:
        batch = jnp.arange(data.shape[1])
        return data[seq_len, batch]
    batch = jnp.arange(data.shape[0])
    return data[batch, seq_len]


@register("SequenceReverse")
def _sequence_reverse(data, *maybe_len, use_sequence_length=False, axis=0):
    jnp = _jnp()
    if not use_sequence_length or not maybe_len:
        return jnp.flip(data, axis=0)
    seq_len = maybe_len[0].astype(_np.int32)
    T = data.shape[0]
    pos = jnp.arange(T)[:, None]
    rev = seq_len[None, :] - 1 - pos
    idx = jnp.where(rev >= 0, rev, pos)
    batch = jnp.arange(data.shape[1])[None, :]
    return data[idx, batch]


# ---------------------------------------------------------------------------
# UpSampling / resize (ref: upsampling.cc; bilinear via jax.image)
# ---------------------------------------------------------------------------

@register("UpSampling", variadic=True)
def _upsampling(*inputs, scale=1, sample_type="nearest", num_args=1,
                num_filter=0, multi_input_mode="concat", workspace=512):
    jnp = _jnp()
    import jax
    data = inputs[0]
    n, c, h, w = data.shape
    if sample_type == "nearest":
        out = jnp.repeat(jnp.repeat(data, scale, axis=2), scale, axis=3)
    else:
        out = jax.image.resize(data, (n, c, h * scale, w * scale), "bilinear")
    return out


@register("GridGenerator")
def _grid_generator(data, transform_type="affine", target_shape=(0, 0)):
    jnp = _jnp()
    if transform_type != "affine":
        raise MXNetError("only affine GridGenerator supported")
    h, w = target_shape
    ys = jnp.linspace(-1, 1, h)
    xs = jnp.linspace(-1, 1, w)
    gx, gy = jnp.meshgrid(xs, ys)
    ones = jnp.ones_like(gx)
    base = jnp.stack([gx.ravel(), gy.ravel(), ones.ravel()], axis=0)
    theta = data.reshape((-1, 2, 3))
    out = jnp.matmul(theta, base)
    return out.reshape((-1, 2, h, w))
