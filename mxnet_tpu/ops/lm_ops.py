"""Operators of today's sparse language models. The GLM-4.7-Flash /
DeepSeek-V2 family: RMSNorm, rotary embedding, multi-head latent attention
(MLA), the gated FFN and the dropless expert layer. The ``nemotron_h``
family: the Mamba-2 mixer (causal depthwise convolution, the selective
state-space recurrence by chunks, a gated grouped RMSNorm), grouped-KV
attention and the squared-ReLU FFN. The ``olmo_hybrid`` family: the Gated
DeltaNet mixer (the gated delta rule by chunks, per-head L2 norms, a
per-head norm then gate) and attention with QK-norm. The ``mimo_v2``
family: attention from a fused QKV projection with partial rotary
embedding, full or in a sliding window with a sink.

Pure JAX functions, registered like every other op, so one definition
serves eager NDArray calls, the autograd tape, hybridized blocks and
``SPMDTrainer``'s one-program step. Weights multiply from the right
(``x @ w``: ``w`` is (in, out)). Attention runs through
``pallas_kernels.blocked_attention``; the expert layer is
``parallel.moe.dropless_moe_ffn``. The model's blocks are in
``gluon/model_zoo/text``.
"""
from __future__ import annotations

from .registry import register


def rms_norm(x, weight, eps=1e-5):
    """x * rsqrt(mean(x^2) + eps) * weight over the last axis; the
    statistics in float32 whatever ``x`` is."""
    import jax
    import jax.numpy as jnp
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * weight.astype(jnp.float32)).astype(x.dtype)


def rope(x, theta=10000.0, dims=None):
    """Rotary embedding over the last axis of ``x`` (..., T, dim), position
    = index along T. Pairs are (i, i + dim/2) ("rotate half"); angles in
    float32. ``dims``: over the first ``dims`` of the axis alone, as if they
    were the whole of it; the rest pass unchanged (partial rotary)."""
    import jax.numpy as jnp
    if dims is not None and dims < x.shape[-1]:
        return jnp.concatenate([rope(x[..., :dims], theta), x[..., dims:]],
                               axis=-1)
    t, dim = x.shape[-2], x.shape[-1]
    half = dim // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / dim)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def swiglu(h):
    """silu(gate) * up of ``h = [gate | up]`` along the last axis."""
    import jax
    f = h.shape[-1] // 2
    return jax.nn.silu(h[..., :f]) * h[..., f:]


def swiglu_ffn(x, w_in, w_out):
    """(silu(x @ w_gate) * (x @ w_up)) @ w_out with ``w_in = [w_gate |
    w_up]`` one (D, 2F) product."""
    import jax.numpy as jnp
    return jnp.dot(swiglu(jnp.dot(x, w_in)), w_out)


def relu2(h):
    """relu(h)^2, the ``nemotron_h`` family's activation."""
    import jax
    return jax.numpy.square(jax.nn.relu(h))


def relu2_ffn(x, w_in, w_out):
    """relu(x @ w_in)^2 @ w_out: one product in, not SwiGLU's two halves."""
    import jax.numpy as jnp
    return jnp.dot(relu2(jnp.dot(x, w_in)), w_out)


# what an expert is: (activation of the first product, FFN)
FFN_ACTIVATIONS = {"swiglu": (swiglu, swiglu_ffn), "relu2": (relu2, relu2_ffn)}


def causal_conv1d(x, weight, bias=None):
    """Depthwise causal convolution along T: ``y[t, c] = bias[c] + sum_j
    weight[c, j] x[t - (W - 1) + j, c]`` with zeros before the sequence.
    x: (B, T, C); weight: (C, W); bias: (C,) or None (no bias). W shifted
    multiply-adds that XLA fuses into one pass (W is 4), in float32."""
    import jax.numpy as jnp
    t, width = x.shape[1], weight.shape[1]
    padded = jnp.pad(x.astype(jnp.float32),
                     ((0, 0), (width - 1, 0), (0, 0)))
    w = weight.astype(jnp.float32)
    y = None if bias is None else bias.astype(jnp.float32)
    for j in range(width):
        term = padded[:, j:j + t] * w[:, j]
        y = term if y is None else y + term
    return y.astype(x.dtype)


def ssd_chunked(u, dt, a, b, c, chunk=128, decay_dtype=None):
    """The selective state-space recurrence of Mamba-2 (the "SSD" form) by
    chunks of ``chunk`` steps.

        S_t = exp(dt_t a) S_{t-1} + dt_t u_t (x) B_t;   y_t = S_t C_t

    per head, ``S`` (P, N), ``S_0 = 0``; ``B`` and ``C`` are shared by the
    heads of a group. u: (B, T, H, P); dt: (B, T, H) (after softplus); a:
    (H,), negative; b, c: (B, T, G, N) with H a multiple of G -> y (B, T, H,
    P) float32 (the skip ``D u`` is the caller's).

    Within a chunk the outputs come from the masked decay matrix, ``y_t =
    sum_{s <= t} exp(L_t - L_s) dt_s (C_t . B_s) u_s`` with ``L`` the
    running sum of ``dt a`` inside the chunk: a (chunk, chunk) matrix a head
    and chunk, never (T, T). Across chunks the (P, N) state is carried: each
    chunk adds its own ``sum_s exp(L_end - L_s) dt_s u_s (x) B_s`` to the
    decayed state it was handed, and ``y_t`` gains ``exp(L_t) C_t . S`` of
    the state handed in. So the state exists once a chunk, not once a step,
    and that is all the backward pass (JAX's, through these products) keeps.
    Decay, running sums and the state are float32 whatever ``u`` is
    (``decay_dtype`` is for a precision check that wants to see a lower one
    fail); the products take ``u``'s dtype and accumulate in float32. A
    ``T`` that ``chunk`` does not divide is padded with steps of ``dt = 0``,
    which neither decay nor feed the state."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    slow = jnp.dtype(decay_dtype or f32)          # decays and running sums
    bsz, t, h, p = u.shape
    g, n = b.shape[2], b.shape[3]
    q = min(chunk, t)
    pad = -t % q
    if pad:
        u, dt, b, c = (
            jnp.pad(z, ((0, 0), (0, pad)) + ((0, 0),) * (z.ndim - 2))
            for z in (u, dt, b, c))
    nc = (t + pad) // q
    low = u.dtype
    u = u.reshape(bsz, nc, q, g, h // g, p)
    b = b.reshape(bsz, nc, q, g, n)
    c = c.reshape(bsz, nc, q, g, n)
    dt = dt.astype(f32).reshape(bsz, nc, q, g, h // g)
    run = jnp.cumsum((dt * a.astype(f32).reshape(g, h // g)).astype(slow),
                     axis=2)                                           # L
    end = run[:, :, -1]                                        # (B, nc, G, Hg)

    # inside a chunk: (C B^T) * decay * dt, one (q, q) matrix a head
    cb = jnp.einsum("bcqgn,bcsgn->bcgqs", c, b, preferred_element_type=f32)
    lq = run.transpose(0, 1, 3, 4, 2)                       # (B, nc, G, Hg, q)
    seg = lq[..., :, None] - lq[..., None, :]               # L_t - L_s
    causal = jnp.tril(jnp.ones((q, q), bool))
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    m = cb[:, :, :, None] * decay * dt.transpose(0, 1, 3, 4, 2)[..., None, :]
    y = jnp.einsum("bcghqs,bcsghp->bcqghp", m.astype(low), u,
                   preferred_element_type=f32)

    # each chunk's own contribution to the state at its end
    fed = u.astype(f32) * (jnp.exp(end[:, :, None] - run) * dt)[..., None]
    own = jnp.einsum("bcsgn,bcsghp->bcghpn", b, fed.astype(low),
                     preferred_element_type=f32)

    # across chunks: the state handed to each chunk
    def carry(state, xs):
        own_c, keep_c = xs
        return state * keep_c[..., None, None] + own_c, state

    _, handed = jax.lax.scan(
        carry, jnp.zeros((bsz, g, h // g, p, n), f32),
        (own.transpose(1, 0, 2, 3, 4, 5), jnp.exp(end).transpose(1, 0, 2, 3)))
    handed = handed.transpose(1, 0, 2, 3, 4, 5)          # (B, nc, G, Hg, P, N)
    y = y + jnp.exp(run)[..., None] * jnp.einsum(
        "bcqgn,bcghpn->bcqghp", c, handed.astype(low),
        preferred_element_type=f32)
    return y.reshape(bsz, nc * q, h, p)[:, :t]


def gated_group_rms_norm(y, z, weight, groups, eps=1e-5):
    """RMSNorm(y * silu(z)) normalised within each of ``groups`` equal
    groups of the last axis (the gate before the norm), one weight over
    the whole axis; the statistics in float32, the result in ``z``'s
    dtype."""
    import jax
    import jax.numpy as jnp
    x = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    grouped = x.reshape(x.shape[:-1] + (groups, -1))
    grouped = grouped * jax.lax.rsqrt(
        jnp.mean(grouped * grouped, axis=-1, keepdims=True) + eps)
    return (grouped.reshape(x.shape) * weight.astype(jnp.float32)) \
        .astype(z.dtype)


def mamba2_mixer(x, w_in, conv_weight, conv_bias, dt_bias, a_log, d, norm,
                 w_out, heads=1, head_dim=1, groups=1, state=1, chunk=128,
                 eps=1e-5):
    """The Mamba-2 mixer over x (B, T, D).

    ``[z | xBC | dt] = x w_in`` (heads x head_dim, heads x head_dim + 2 x
    groups x state, heads); ``xBC = silu(causal_conv1d(xBC))``, split into
    ``u``, ``B``, ``C``; ``dt = softplus(dt + dt_bias)``; ``a =
    -exp(a_log)``; ``y = ssd_chunked(u, dt, a, B, C) + d u``; ``y =
    gated_group_rms_norm(y, z)``; ``w_out`` back to D."""
    import jax
    import jax.numpy as jnp
    bsz, t, _ = x.shape
    inner, gn = heads * head_dim, groups * state
    with jax.named_scope("mx.mamba2"):
        proj = jnp.dot(x, w_in)
        z = proj[..., :inner]
        xbc = jax.nn.silu(causal_conv1d(
            proj[..., inner:2 * inner + 2 * gn], conv_weight, conv_bias))
        dt = jax.nn.softplus(proj[..., 2 * inner + 2 * gn:].astype(
            jnp.float32) + dt_bias.astype(jnp.float32))
        u = xbc[..., :inner].reshape(bsz, t, heads, head_dim)
        with jax.named_scope("mx.ssd"):
            y = ssd_chunked(
                u, dt, -jnp.exp(a_log.astype(jnp.float32)),
                xbc[..., inner:inner + gn].reshape(bsz, t, groups, state),
                xbc[..., inner + gn:].reshape(bsz, t, groups, state), chunk)
        y = y + d.astype(jnp.float32)[:, None] * u.astype(jnp.float32)
        y = gated_group_rms_norm(y.reshape(bsz, t, inner), z, norm, groups,
                                 eps)
        return jnp.dot(y, w_out)


def gated_delta_rule_chunked(q, k, v, g, beta, chunk=64):
    """The gated delta rule (Gated DeltaNet) by chunks of ``chunk`` steps.

        S_t = exp(g_t) (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T
        o_t = S_t^T q_t

    per head, ``S`` (dk, dv), ``S_0 = 0``. Heads first: q, k: (B, H, T, dk);
    v: (B, H, T, dv); g: (B, H, T), the log of the decay (<= 0); beta: (B, H,
    T), in (0, 2) where the transition may have negative eigenvalues -> o
    (B, H, T, dv) float32.

    Written as ``S_t = exp(g_t) S_{t-1} + k_t u_t^T``, the value a step
    writes is ``u_t = beta_t (v_t - exp(g_t) S_{t-1}^T k_t)``. Inside a
    chunk handed the state ``S``, with ``G`` the running sum of ``g`` there
    and ``Gamma_ts = exp(G_t - G_s)`` for s <= t, the writes solve one unit
    lower-triangular system (the "UT transform"): ``(I + A) U = diag(beta)
    V - diag(beta exp(G)) K S`` with ``A = tril(diag(beta) (Gamma * K K^T),
    -1)``, so ``U = U' - W S`` with ``W = R diag(beta exp(G)) K``, ``U' = R
    diag(beta) V``, ``R = (I + A)^-1``. Then ``O = diag(exp(G)) Q S + (Q K^T
    * Gamma) U`` and the state handed on is ``exp(G_end) S + (exp(G_end -
    G) K)^T U``. The forward is one kernel, ``mx_delta_rule``
    (``pallas_kernels.delta_rule``); the backward is ``jax.vjp`` of the same
    algebra as XLA ops (``_delta_rule_xla``), recomputed from the five
    inputs. Decays, running sums, the solve and the state are float32
    whatever ``q`` is; the products take ``k``'s dtype and accumulate in
    float32. A ``T`` that ``chunk`` does not divide is padded with steps of
    ``beta = 0`` and ``g = 0``, which neither decay nor write. ``chunk`` is
    a power of two, as the kernel's lane masks and its solve by doubling
    need: another raises ``ValueError``."""
    import jax
    from .pallas_kernels import delta_rule
    if chunk < 1 or chunk & (chunk - 1):
        raise ValueError(f"chunk must be a power of two, not {chunk}")

    @jax.custom_vjp
    def op(*args):
        return delta_rule(*args, chunk)

    def op_fwd(*args):
        return delta_rule(*args, chunk), args

    def op_bwd(args, do):
        return jax.vjp(lambda *a: _delta_rule_xla(*a, chunk), *args)[1](do)

    op.defvjp(op_fwd, op_bwd)
    return op(q, k, v, g, beta)


def _heads_first(z):
    """(B, T, H, ...) <-> (B, H, T, ...)."""
    return z.swapaxes(1, 2)


def _delta_rule_xla(q, k, v, g, beta, chunk=64, decay_dtype=None):
    """``gated_delta_rule_chunked`` as XLA ops, over the same heads-first
    operands and for any ``chunk``: R, W, U' and the masked products for
    every chunk at once, then a ``lax.scan`` over the chunks that carries
    the (dk, dv) state and computes U and O. The kernel's backward;
    ``decay_dtype`` runs the decays, running sums and state in a lower
    precision, for a check that wants to see one fail."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    slow = jnp.dtype(decay_dtype or f32)          # decays, running sums, state
    bsz, h, t, dk = k.shape
    dv = v.shape[-1]
    c = min(chunk, t)
    pad = -t % c
    if pad:
        q, k, v, g, beta = (
            jnp.pad(z, ((0, 0), (0, 0), (0, pad)) + ((0, 0),) * (z.ndim - 3))
            for z in (q, k, v, g, beta))
    n = (t + pad) // c
    low = k.dtype

    def chunks(z):                       # (B, H, T, ...) -> (n, B, H, C, ...)
        return jnp.moveaxis(z.reshape((bsz, h, n, c) + z.shape[3:]), 2, 0)

    def dot(spec, a, b):
        return jnp.einsum(spec, a.astype(low), b.astype(low),
                          preferred_element_type=f32)

    q, k, v = chunks(q), chunks(k), chunks(v)
    beta = chunks(beta).astype(f32)                            # (n, B, H, C)
    run = jnp.cumsum(chunks(g).astype(slow), axis=-1)                  # G
    end = run[..., -1:]
    causal = jnp.tril(jnp.ones((c, c), bool))
    gamma = jnp.exp(jnp.where(causal, run[..., :, None] - run[..., None, :],
                              -jnp.inf)).astype(f32)
    a = jnp.where(jnp.tril(causal, -1), beta[..., :, None] * gamma
                  * dot("...sd,...rd->...sr", k, k), 0.0)
    # R = (I + A)^-1: the unit diagonal is implied, A's own diagonal is 0
    solve = jax.lax.linalg.triangular_solve(
        a, jnp.broadcast_to(jnp.eye(c, dtype=f32), a.shape), left_side=True,
        lower=True, unit_diagonal=True)
    grown = jnp.exp(run).astype(f32)
    w = dot("...sr,...rd->...sd", solve, k * (beta * grown)[..., None])
    u = dot("...sr,...rd->...sd", solve, v * beta[..., None])
    qk = dot("...sd,...rd->...sr", q, k) * gamma
    qg = q * grown[..., None]
    kt = k * jnp.exp(end - run).astype(f32)[..., None]
    keep = jnp.exp(end[..., 0]).astype(f32)                       # (n, B, H)

    def carry(state, xs):
        w_c, u_c, qk_c, qg_c, kt_c, keep_c = xs
        new = u_c - dot("bhsd,bhde->bhse", w_c, state)                   # U
        o = dot("bhsd,bhde->bhse", qg_c, state) \
            + dot("bhsr,bhre->bhse", qk_c, new)
        state = state * keep_c[..., None, None] \
            + dot("bhsd,bhse->bhde", kt_c, new)
        return state.astype(slow), o

    _, o = jax.lax.scan(carry, jnp.zeros((bsz, h, dk, dv), slow),
                        (w, u, qk, qg, kt, keep))         # (n, B, H, C, dv)
    o = o.transpose(1, 2, 0, 3, 4).reshape(bsz, h, n * c, dv)
    return o[:, :, :t]


def l2_norm(x, eps=1e-6):
    """x / sqrt(sum(x^2) + eps) over the last axis, in float32, the result
    in ``x``'s dtype."""
    import jax
    import jax.numpy as jnp
    x32 = x.astype(jnp.float32)
    return (x32 * jax.lax.rsqrt(jnp.sum(x32 * x32, axis=-1, keepdims=True)
                                + eps)).astype(x.dtype)


def norm_then_gate(y, z, weight, eps=1e-6):
    """RMSNorm(y) * weight * silu(z) over the last axis: the norm BEFORE the
    gate (``gated_group_rms_norm``'s reverse order); the statistics in
    float32, the result in ``z``'s dtype."""
    import jax
    import jax.numpy as jnp
    normed = rms_norm(y.astype(jnp.float32), weight, eps)
    return (normed * jax.nn.silu(z.astype(jnp.float32))).astype(z.dtype)


def gated_deltanet_mixer(x, w_q, w_k, w_v, conv_q, conv_k, conv_v, w_a, a_log,
                         dt_bias, w_b, w_g, norm, w_o, heads=1, key_dim=1,
                         value_dim=1, neg_eigval=True, chunk=64, eps=1e-6):
    """The Gated DeltaNet mixer over x (B, T, D), ``heads`` key and value
    heads.

    ``q, k, v = silu(causal_conv1d(x w))`` for each of w_q, w_k, w_v (no
    bias); per head ``q = l2_norm(q) / sqrt(key_dim)``, ``k = l2_norm(k)``;
    ``beta = sigmoid(x w_b)``, doubled where ``neg_eigval`` (the transition
    then has eigenvalues down to -1); ``g = -exp(a_log) softplus(x w_a +
    dt_bias)``; ``o = gated_delta_rule_chunked(q, k, v, g, beta)``; per head
    ``o = norm_then_gate(o, x w_g)`` (one ``value_dim`` weight for all
    heads); ``w_o`` back to D."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    bsz, t, _ = x.shape

    def heads_of(y, dim):
        return y.reshape(bsz, t, heads, dim)

    def branch(w, conv, dim):
        return heads_of(jax.nn.silu(causal_conv1d(jnp.dot(x, w), conv)), dim)

    with jax.named_scope("mx.gdn"):
        q = l2_norm(branch(w_q, conv_q, key_dim).astype(f32)) * key_dim ** -0.5
        k = l2_norm(branch(w_k, conv_k, key_dim))
        v = branch(w_v, conv_v, value_dim)
        beta = jax.nn.sigmoid(jnp.dot(x, w_b).astype(f32))
        if neg_eigval:
            beta = 2.0 * beta
        g = -jnp.exp(a_log.astype(f32)) * jax.nn.softplus(
            jnp.dot(x, w_a).astype(f32) + dt_bias.astype(f32))
        q, k, v, g, beta = map(_heads_first, (q, k, v, g, beta))
        with jax.named_scope("mx.delta_rule"):
            o = gated_delta_rule_chunked(q.astype(x.dtype), k, v, g, beta,
                                         chunk)
        o = norm_then_gate(_heads_first(o), heads_of(jnp.dot(x, w_g),
                                                     value_dim), norm, eps)
        return jnp.dot(o.reshape(bsz, t, heads * value_dim), w_o)


def gqa_attention(x, w_q, w_k, w_v, w_o, heads=1, kv_heads=1, head_dim=1):
    """Causal grouped-KV attention over x (B, T, D), no position embedding:
    ``heads`` query heads, query head ``h`` reads KV head ``h // (heads /
    kv_heads)``; softmax of ``q.k / sqrt(head_dim)``. The attention kernels
    (``blocked_attention``) take the keys and values at the KV heads' count
    and read a group's KV head for each of its query heads; they sum its
    gradients over the group."""
    import jax
    import jax.numpy as jnp
    from .pallas_kernels import blocked_attention
    bsz, t, _ = x.shape

    def split(y, n):                      # (B, T, n*d) -> (B*n, T, d)
        return y.reshape(bsz, t, n, head_dim).transpose(0, 2, 1, 3) \
            .reshape(bsz * n, t, head_dim)

    with jax.named_scope("mx.gqa"):
        o = blocked_attention(
            split(jnp.dot(x, w_q), heads), split(jnp.dot(x, w_k), kv_heads),
            split(jnp.dot(x, w_v), kv_heads), causal=True,
            scale=head_dim ** -0.5)
        o = o.reshape(bsz, heads, t, head_dim).transpose(0, 2, 1, 3)
        return jnp.dot(o.reshape(bsz, t, heads * head_dim), w_o)


def qk_norm_attention(x, w_q, q_norm, w_k, k_norm, w_v, w_o, heads=1,
                      head_dim=1, eps=1e-6):
    """Causal multi-head attention over x (B, T, D) with QK-norm, no
    position embedding: ``q = RMSNorm(x w_q)``, ``k = RMSNorm(x w_k)``, each
    norm over the whole projection (all heads at once), ``v = x w_v``;
    softmax of ``q.k / sqrt(head_dim)`` per head through
    ``blocked_attention``; ``w_o`` back to D."""
    import jax
    import jax.numpy as jnp
    from .pallas_kernels import blocked_attention
    bsz, t, _ = x.shape

    def split(y):                         # (B, T, H*d) -> (B*H, T, d)
        return y.reshape(bsz, t, heads, head_dim).transpose(0, 2, 1, 3) \
            .reshape(bsz * heads, t, head_dim)

    with jax.named_scope("mx.attention"):
        q = rms_norm(jnp.dot(x, w_q), q_norm, eps)
        k = rms_norm(jnp.dot(x, w_k), k_norm, eps)
        o = blocked_attention(split(q), split(k), split(jnp.dot(x, w_v)),
                              causal=True, scale=head_dim ** -0.5)
        o = o.reshape(bsz, heads, t, head_dim).transpose(0, 2, 1, 3)
        return jnp.dot(o.reshape(bsz, t, heads * head_dim), w_o)


def fused_qkv_attention(x, w_qkv, w_o, *sink, heads=1, kv_heads=1,
                        qk_dim=1, v_dim=1, rope_dim=None, theta=10000.0,
                        window=None, value_scale=1.0):
    """Causal grouped-KV attention over x (B, T, D) from one fused
    projection, full or in a sliding window with a sink (MiMo-V2's two
    kinds of layer).

    ``[q | k | v] = x w_qkv``: ``heads`` query heads and ``kv_heads`` key
    heads ``qk_dim`` wide, ``kv_heads`` value heads ``v_dim`` wide; the
    rotary embedding (``rope``, base ``theta``) over the first ``rope_dim``
    dims of each q and k head; query head ``h`` reads KV head ``h //
    (heads / kv_heads)``; softmax of ``q.k / sqrt(qk_dim)`` over keys ``j
    <= i``, or with ``window`` over ``i - window < j <= i``; ``sink``: one
    logit a head, ``(heads,)``, of a key with no value, in the softmax's
    denominator; ``o = value_scale * sum_j p_ij v_j``; ``w_o`` from heads x
    v_dim back to D. A window layer runs under the scope ``mx.swa``, a full
    one under ``mx.full_attn``. The kernels (``blocked_attention``, which
    walks only the band's tiles under a window) take the keys and values at
    the KV heads' count and read a group's KV head for each of its query
    heads."""
    import jax
    import jax.numpy as jnp
    from .pallas_kernels import blocked_attention
    bsz, t, _ = x.shape
    n_q, n_k = heads * qk_dim, kv_heads * qk_dim

    def split(y, n, d):                       # (B, T, n*d) -> (B, n, T, d)
        return y.reshape(bsz, t, n, d).transpose(0, 2, 1, 3)

    with jax.named_scope("mx.full_attn" if window is None else "mx.swa"):
        qkv = jnp.dot(x, w_qkv)
        q = rope(split(qkv[..., :n_q], heads, qk_dim), theta, rope_dim)
        k = rope(split(qkv[..., n_q:n_q + n_k], kv_heads, qk_dim), theta,
                 rope_dim)
        v = split(qkv[..., n_q + n_k:], kv_heads, v_dim)
        o = blocked_attention(
            q.reshape(bsz * heads, t, qk_dim),
            k.reshape(bsz * kv_heads, t, qk_dim),
            v.reshape(bsz * kv_heads, t, v_dim), causal=True,
            scale=qk_dim ** -0.5, window=window,
            sink=jnp.tile(sink[0].astype(jnp.float32), bsz) if sink else None)
        o = o.reshape(bsz, heads, t, v_dim).transpose(0, 2, 1, 3) \
            * jnp.asarray(value_scale, o.dtype)
        return jnp.dot(o.reshape(bsz, t, heads * v_dim), w_o)


def mla_attention(x, w_qa, q_norm, w_qb, w_kva, kv_norm, w_kvb, w_o,
                  heads=1, nope=0, rope_dim=0, v_dim=0, theta=10000.0,
                  eps=1e-5):
    """Causal multi-head latent attention over x (B, T, D).

    ``c_q = RMSNorm(x w_qa)``; ``q = c_q w_qb`` -> heads x (nope + rope);
    ``[c_kv | k_r] = x w_kva``; ``[k_nope | v] = RMSNorm(c_kv) w_kvb`` ->
    heads x (nope + v_dim); rotary embedding on q's rope dimensions and on
    ``k_r``, ONE rope key that every head shares; softmax of
    ``q.k / sqrt(nope + rope)`` under the causal mask; ``w_o`` from heads x
    v_dim back to D. No biases. Attention is blocked over queries and keys
    in both passes (``blocked_attention``).
    """
    import jax
    import jax.numpy as jnp
    from .pallas_kernels import blocked_attention
    b, t, _ = x.shape
    rank = w_kva.shape[1] - rope_dim
    with jax.named_scope("mx.mla"):
        c_q = rms_norm(jnp.dot(x, w_qa), q_norm, eps)
        q = jnp.einsum("btr,rhk->bhtk", c_q,
                       w_qb.reshape(w_qb.shape[0], heads, nope + rope_dim))
        kva = jnp.dot(x, w_kva)
        c_kv = rms_norm(kva[..., :rank], kv_norm, eps)
        kv = jnp.einsum("btr,rhk->bhtk", c_kv,
                        w_kvb.reshape(rank, heads, nope + v_dim))
        k_rope = rope(kva[..., rank:], theta)                     # (B, T, r)
        q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], theta)], -1)
        k = jnp.concatenate(
            [kv[..., :nope],
             jnp.broadcast_to(k_rope[:, None], (b, heads, t, rope_dim))], -1)
        v = kv[..., nope:]
        o = blocked_attention(
            q.reshape(b * heads, t, -1), k.reshape(b * heads, t, -1),
            v.reshape(b * heads, t, v_dim), causal=True,
            scale=(nope + rope_dim) ** -0.5)
        return jnp.einsum("bhtv,hvd->btd", o.reshape(b, heads, t, v_dim),
                          w_o.reshape(heads, v_dim, w_o.shape[1]))


@register("_contrib_rms_norm", aliases=("rms_norm",))
def _rms_norm_op(x, weight, eps=1e-5):
    return rms_norm(x, weight, eps)


@register("_contrib_swiglu_ffn")
def _swiglu_ffn_op(x, w_in, w_out):
    return swiglu_ffn(x, w_in, w_out)


@register("_contrib_mla_attention")
def _mla_attention_op(x, w_qa, q_norm, w_qb, w_kva, kv_norm, w_kvb, w_o,
                      heads=1, nope=0, rope_dim=0, v_dim=0, theta=10000.0,
                      eps=1e-5):
    return mla_attention(x, w_qa, q_norm, w_qb, w_kva, kv_norm, w_kvb, w_o,
                         heads=heads, nope=nope, rope_dim=rope_dim,
                         v_dim=v_dim, theta=theta, eps=eps)


@register("_contrib_mamba2_mixer")
def _mamba2_mixer_op(x, w_in, conv_weight, conv_bias, dt_bias, a_log, d,
                     norm, w_out, heads=1, head_dim=1, groups=1, state=1,
                     chunk=128, eps=1e-5):
    return mamba2_mixer(x, w_in, conv_weight, conv_bias, dt_bias, a_log, d,
                        norm, w_out, heads=heads, head_dim=head_dim,
                        groups=groups, state=state, chunk=chunk, eps=eps)


@register("_contrib_gqa_attention")
def _gqa_attention_op(x, w_q, w_k, w_v, w_o, heads=1, kv_heads=1,
                      head_dim=1):
    return gqa_attention(x, w_q, w_k, w_v, w_o, heads=heads,
                         kv_heads=kv_heads, head_dim=head_dim)


@register("_contrib_fused_qkv_attention")
def _fused_qkv_attention_op(x, w_qkv, w_o, *sink, heads=1, kv_heads=1,
                            qk_dim=1, v_dim=1, rope_dim=None, theta=10000.0,
                            window=None, value_scale=1.0):
    """``fused_qkv_attention``; ``sink``: the (heads,) sink logits of a
    window layer, or nothing."""
    return fused_qkv_attention(
        x, w_qkv, w_o, *sink, heads=heads, kv_heads=kv_heads, qk_dim=qk_dim,
        v_dim=v_dim, rope_dim=rope_dim, theta=theta, window=window,
        value_scale=value_scale)


@register("_contrib_gated_deltanet_mixer")
def _gated_deltanet_mixer_op(x, w_q, w_k, w_v, conv_q, conv_k, conv_v, w_a,
                             a_log, dt_bias, w_b, w_g, norm, w_o, heads=1,
                             key_dim=1, value_dim=1, neg_eigval=True,
                             chunk=64, eps=1e-6):
    return gated_deltanet_mixer(
        x, w_q, w_k, w_v, conv_q, conv_k, conv_v, w_a, a_log, dt_bias, w_b,
        w_g, norm, w_o, heads=heads, key_dim=key_dim, value_dim=value_dim,
        neg_eigval=neg_eigval, chunk=chunk, eps=eps)


@register("_contrib_qk_norm_attention")
def _qk_norm_attention_op(x, w_q, q_norm, w_k, k_norm, w_v, w_o, heads=1,
                          head_dim=1, eps=1e-6):
    return qk_norm_attention(x, w_q, q_norm, w_k, k_norm, w_v, w_o,
                             heads=heads, head_dim=head_dim, eps=eps)


@register("_contrib_dropless_moe", num_outputs=3, aux_inputs=(2,))
def _dropless_moe_op(x, gate, bias, w_in, w_out, *shared, k=1,
                     experts_held=None, scaling=1.0, activation="swiglu"):
    """``parallel.moe.dropless_moe_ffn``: (y, load over all experts, pairs
    computed here), the two counters as float32. ``shared``: the shared
    expert's (w_in, w_out), or nothing for a layer without one."""
    import jax.numpy as jnp
    from ..parallel.moe import dropless_moe_ffn
    y, stats = dropless_moe_ffn(
        x, {"gate": gate, "bias": bias, "w_in": w_in, "w_out": w_out,
            **dict(zip(("shared_in", "shared_out"), shared))},
        k, experts_held, scaling, activation=activation)
    return (y, stats["load"].astype(jnp.float32),
            stats["tokens_here"].astype(jnp.float32))
