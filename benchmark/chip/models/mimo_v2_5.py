"""Plain reference of MiMo-V2.5's language model (``model_type mimo_v2``)
for one chip's share of it: forward pass and loss in ``jax.numpy`` float32
at ``highest`` matmul precision, no kernels: attention a block of queries
at a time against every key it may read (the full layers against all of
them, masked; the window layers against the keys of their band), the FFNs
a block of tokens at a time, every held expert over every token, weighted
by its routing weight (0 where the token did not choose it).

    model:  x_0 = Emb(t); h_l = x_l + Attn_l(RMSNorm(x_l));
            x_{l+1} = h_l + FFN_l(RMSNorm(h_l)); logits = RMSNorm(x_L)
            W_head, untied; eps 1e-5; weights multiply from the right
    Attn:   [q | k | v] = x W_qkv: 64 query heads of 192; K heads of 192
            and V heads of 128, num_key_value_heads (4) of each in a full
            layer (hybrid_layer_pattern 0), swa_num_key_value_heads (8) in a
            window layer (1); rotary embedding over the first
            int(192 x 0.334) = 64 dims of each q and k head, pairs (i, i +
            32), angle pos x theta^(-2i/64), theta = rope_theta 1e7 (full) or
            swa_rope_theta 1e4 (window); the other 128 dims unrotated;
            query head h reads KV head h // (64 / kv_heads);
            l_ij = q_i.k_j / sqrt(192), j <= i (full) or i - 128 < j <= i
            (window); window: p_ij = exp(l_ij) / (exp(s_h) + sum_j'
            exp(l_ij')), s_h the head's sink; full: softmax;
            o_i = 0.707 sum_j p_ij v_j; out = o W_o (64 x 128 -> 4096)
    FFN:    moe_layer_freq 0: SwiGLU (silu(x W_gate) * x W_up) W_down,
            16,384 wide. 1: s = sigmoid(x W_r) over the 256 experts of the
            router; chosen = top8(s + b) (b only selects); w = s_chosen /
            sum s_chosen; y = sum over the chosen experts HELD HERE of w_e
            SwiGLU_e(x), 2,048 wide; no shared expert
    loss:   mean over positions of the cross-entropy of position i against
            token i + 1

Departures from the published model, all stated in the configuration file:
the share (the first 7 of 48 layers, experts 0 - 15 of each layer's 256,
the first 19,072 ids of the vocabulary); no MTP layers, no vision or audio
tower. Forms the published config does not fix (its ``assumed``): which
rotary dims are rotated, where the value scale applies, the window's edge.
The SwiGLUs' two input matrices are held as one, ``W_in = [W_gate |
W_up]``, and an expert's likewise, as the program holds them; the fused
projection's columns are the query heads', then the key heads', then the
value heads'.

``params`` is the list of the net's arrays in the order the architecture
declares them (``paths/common.py:parameters``); ``unpack`` names them. They
may come in a lower precision (the cell hands the served bf16 values,
``paths/score_causal_lm_rounded_ref.py``): each is cast to float32 where it
is used, so that the float32 copies of 6.86 GB of weights never exist at
once.

Tolerance of the first step's loss (system: bf16; this: float32):
``|dloss| / (|loss| + 1) <= TOLERANCE``, the figure of the benchmark's other
cells: it catches a wrong vocabulary, a shifted label or a NaN and NOT a
precision; the logits' comparison does (``score``, the traffic file's
``limits``).
"""
import jax
import jax.numpy as jnp

TOLERANCE = 5e-3
QUERY_BLOCK = 128   # full layers: (64, 128, T) float32 logits, 1 GB at 32k
TOKEN_BLOCK = 4096  # FFNs: (4,096, 32,768) float32 of the dense SwiGLU


def unpack(params, config):
    """{name: array} with per-layer dicts, from the flat list."""
    c, it = config, iter(params)
    out = {"embed": next(it), "layers": []}
    for kind, moe in zip(c["hybrid_layer_pattern"], c["moe_layer_freq"]):
        p = {k: next(it) for k in ("attn_norm", "w_qkv", "w_o")}
        if c["add_swa_attention_sink_bias" if kind
               else "add_full_attention_sink_bias"]:
            p["sink"] = next(it)
        p["ffn_norm"] = next(it)
        names = ("gate", "bias", "w_in", "w_out", "load", "tokens_here") \
            if moe else ("w_in", "w_out")
        p.update((k, next(it)) for k in names)
        out["layers"].append(p)
    out["norm"], out["head"] = next(it), next(it)
    assert next(it, None) is None, "more arrays than the architecture names"
    return out


def f32(a):
    return a.astype(jnp.float32)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * f32(w)


def rotary(x, pos, dims, theta):
    """Rotate half over the first ``dims`` of x (n, heads, d) at positions
    ``pos`` (n,); the rest as they are."""
    half = dims // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / dims)
    angle = pos[:, None, None] * inv
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., :half], x[..., half:dims]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., dims:]], -1)


def kind_sizes(c, window):
    """(kv heads, q/k head size, v head size, rotary dims, theta, window or
    None) of a layer."""
    pre = "swa_" if window else ""
    qk = c[pre + "head_dim"]
    return (c[pre + "num_key_value_heads"], qk, c[pre + "v_head_dim"],
            int(qk * c["partial_rotary_factor"]), float(c[pre + "rope_theta"]),
            c["sliding_window"] if window else None)


def attention(x, p, c, window):
    """x (T, D) -> (T, D): one layer of either kind, a block of queries at
    a time."""
    t = x.shape[0]
    heads = c["swa_num_attention_heads" if window else "num_attention_heads"]
    kv, qk, dv, dims, theta, width = kind_sizes(c, window)
    group = heads // kv
    w = f32(p["w_qkv"])
    n_q, n_k = heads * qk, kv * qk
    pos = jnp.arange(t, dtype=jnp.float32)
    k = rotary((x @ w[:, n_q:n_q + n_k]).reshape(t, kv, qk), pos, dims, theta)
    v = (x @ w[:, n_q + n_k:]).reshape(t, kv, dv)
    blk = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
    # a window block reads the (width - 1) keys before it: pad in front
    before = 0 if width is None else width
    k = jnp.pad(k, ((before, 0), (0, 0), (0, 0)))
    v = jnp.pad(v, ((before, 0), (0, 0), (0, 0)))
    span = t if width is None else blk + before

    def block(i):
        start = i * blk
        xb = jax.lax.dynamic_slice_in_dim(x, start, blk)
        qpos = start + jnp.arange(blk)
        q = rotary((xb @ w[:, :n_q]).reshape(blk, heads, qk),
                   qpos.astype(jnp.float32), dims, theta)
        q = q.reshape(blk, kv, group, qk)
        first = 0 if width is None else start   # padded coordinates
        kb = jax.lax.dynamic_slice_in_dim(k, first, span)
        vb = jax.lax.dynamic_slice_in_dim(v, first, span)
        kpos = first - before + jnp.arange(span)
        s = jnp.einsum("qgrd,kgd->grqk", q, kb) / jnp.sqrt(1.0 * qk)
        live = kpos[None, :] <= qpos[:, None]
        if width is not None:
            live &= (kpos[None, :] > qpos[:, None] - width) \
                & (kpos[None, :] >= 0)
        s = jnp.where(live, s, -jnp.inf)
        if "sink" in p:
            sink = f32(p["sink"]).reshape(kv, group)[:, :, None, None]
            m = jnp.maximum(jnp.max(s, -1, keepdims=True), sink)
            e = jnp.exp(s - m)
            prob = e / (jnp.exp(sink - m) + jnp.sum(e, -1, keepdims=True))
        else:
            prob = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("grqk,kgd->qgrd", prob, vb)
        return c["attention_value_scale"] * o.reshape(blk, heads * dv)

    o = jax.lax.map(block, jnp.arange(t // blk)).reshape(t, heads * dv)
    return o @ f32(p["w_o"])


def swiglu(x, w_in, w_out):
    h = x @ f32(w_in)
    f = h.shape[-1] // 2
    return (jax.nn.silu(h[..., :f]) * h[..., f:]) @ f32(w_out)


def route(x, p, c):
    """(T, router) routing weights: w_e where the token chose expert e,
    else 0."""
    s = jax.nn.sigmoid(x @ f32(p["gate"]))
    _, chosen = jax.lax.top_k(s + f32(p["bias"]), c["num_experts_per_tok"])
    picked = jnp.sum(jax.nn.one_hot(chosen, s.shape[-1]), axis=1)
    w = s * picked
    return (c["routed_scaling_factor"] or 1.0) * w \
        / jnp.sum(w, -1, keepdims=True)


def experts(x, p, c):
    """The held experts' part of the layer's output, every expert over
    every token."""
    weight = route(x, p, c)[:, jnp.asarray(c["experts_held"])]
    y = jnp.zeros_like(x)
    for e in range(len(c["experts_held"])):
        y = y + weight[:, e:e + 1] * swiglu(x, p["w_in"][e], p["w_out"][e])
    return y


def by_tokens(fn, x):
    """fn over blocks of TOKEN_BLOCK rows of x (T, D)."""
    t = x.shape[0]
    blk = TOKEN_BLOCK if t % TOKEN_BLOCK == 0 else t
    return jax.lax.map(fn, x.reshape(t // blk, blk, -1)).reshape(t, -1)


def forward(params, tokens, config):
    """tokens (B, T) -> logits (B, T, V)."""
    c, p = config, unpack(params, config)
    eps = c["layernorm_epsilon"]

    def one(seq):
        x = f32(p["embed"])[seq]
        for kind, moe, lp in zip(c["hybrid_layer_pattern"],
                                 c["moe_layer_freq"], p["layers"]):
            h = x + attention(rms_norm(x, lp["attn_norm"], eps), lp, c,
                              kind == 1)
            ffn = (lambda z: experts(z, lp, c)) if moe \
                else (lambda z: swiglu(z, lp["w_in"], lp["w_out"]))
            x = h + by_tokens(ffn, rms_norm(h, lp["ffn_norm"], eps))
        return rms_norm(x, p["norm"], eps) @ f32(p["head"]).T

    return jnp.stack([one(tokens[b]) for b in range(tokens.shape[0])])


def cross_entropy(logits, label):
    """Mean over positions of logsumexp(logits) - the label's logit, in
    float32 whatever the logits are."""
    picked = jnp.take_along_axis(logits, label.astype(jnp.int32)[..., None],
                                 -1)[..., 0]
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), -1)
    return jnp.mean(lse - picked.astype(jnp.float32))


def loss_of_logits(heads, label, config):
    """The loss of a batch whose (logits,) are given."""
    logits, = heads
    return cross_entropy(logits, label)


def loss(params, data, label, config):
    with jax.default_matmul_precision("highest"):
        return cross_entropy(forward(params, data, config), label)


def score(params, data, label, config):
    """(loss, (logits,)) of one forward pass, float32: what a path that does
    not train is compared with, a sequence at a time."""
    with jax.default_matmul_precision("highest"):
        heads = (forward(params, data, config),)
        return loss_of_logits(heads, label, config), heads


# ---------------------------------------------------------------------------
# operations, from the shapes

def keys_a_query(c, window, t):
    """The mean number of keys a query reads: (T + 1) / 2 causal; under a
    window of W, sum_i min(i + 1, W) / T."""
    if not window:
        return (t + 1) / 2
    w = min(c["sliding_window"], t)
    return (w * (w + 1) / 2 + (t - w) * w) / t


def _macs_per_token(c, t):
    """Multiply-adds a token, forward, by part. A window layer's attention
    is its band's: a kernel that computes the masked rest earns nothing."""
    d, kinds = c["hidden_size"], c["hybrid_layer_pattern"]
    out = dict.fromkeys(("window_projections", "window_band",
                         "full_projections", "full_core", "dense_ffn",
                         "routers", "routed_experts"), 0.0)
    for kind, moe in zip(kinds, c["moe_layer_freq"]):
        name = "window" if kind else "full"
        heads = c["swa_num_attention_heads" if kind else "num_attention_heads"]
        kv, qk, dv, _, _, _ = kind_sizes(c, kind)
        out[name + "_projections"] += d * ((heads + kv) * qk + kv * dv) \
            + heads * dv * d
        out[name + ("_band" if kind else "_core")] += \
            heads * keys_a_query(c, kind, t) * (qk + dv)
        if moe:
            out["routers"] += d * c["router_experts"]
            # the pairs routed here under even routing, k x held / router
            out["routed_experts"] += c["num_experts_per_tok"] \
                * len(c["experts_held"]) / c["router_experts"] \
                * 3 * d * c["moe_intermediate_size"]
        else:
            out["dense_ffn"] += 3 * d * c["intermediate_size"]
    out["head"] = d * c["vocab_size"]
    return out


def flops_per_sample(config):
    """2 per multiply-add, forward x 3, a sample being one sequence."""
    t = config["tokens_per_sample"]
    return 3 * 2 * t * sum(_macs_per_token(config, t).values())
