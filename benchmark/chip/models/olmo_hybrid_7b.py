"""Plain reference of Olmo-Hybrid-7B (``model_type olmo_hybrid``) for one
chip's share of it: forward pass and loss in ``jax.numpy`` float32 at
``highest`` matmul precision, no kernels and no chunked algebra: the delta
rule one step at a time by a ``lax.scan`` over time, attention in blocks of
queries.

    model:  x_0 = Emb(t); h_l = x_l + RMSNorm(Mixer_l(x_l));
            x_{l+1} = h_l + RMSNorm(SwiGLU(h_l)), the mixer by
            layer_types[l]; logits = RMSNorm(x_L) W_head, untied; eps 1e-6;
            weights multiply from the right, no bias
    SwiGLU: (silu(x W_gate) * (x W_up)) W_down, 11,008 wide
    full:   q = RMSNorm(x W_q), k = RMSNorm(x W_k) (each norm over all
            3,840 channels), v = x W_v; 30 heads x 128, causal
            softmax(q.k / sqrt(128)), no rotary embedding; W_o
    linear: q, k, v = silu(conv(x W_q)), silu(conv(x W_k)), silu(conv(x W_v))
            (depthwise causal, width 4, no bias; q, k 30 x 96, v 30 x 192)
            per head q = q / |q| / sqrt(96), k = k / |k|
            beta_t = 2 sigmoid(x_t W_b)   (the 2: linear_allow_neg_eigval)
            g_t = -exp(A_log) softplus(x_t W_a + dt_bias), alpha_t = exp(g_t)
            S_t = alpha_t (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T
            S_0 = 0, (96, 192) a head;  o_t = S_t^T q_t
            o = RMSNorm_192(o) w_norm silu(x W_g)  per head, the norm THEN
            the gate, one weight for all heads;  out = o W_o
    loss:   mean over positions of the cross-entropy of position i against
            token i + 1

Departures from the published model, all stated in the configuration file:
the share (the first 8 of 32 layers, two whole periods of the pattern); no
rotary embedding (``rope_theta`` is null and no other key gives a base).
Forms the published config does not fix, taken from the published code of
the family and of flash-linear-attention (the configuration's ``assumed``):
QK-norm over the whole projection and the norms after each sublayer (Olmo
3); the three convolutions with SiLU, the L2 norm as ``x / sqrt(sum x^2 +
1e-6)``, the output's per-head norm then gate (``GatedDeltaNet``). The
SwiGLU's two input matrices are held as one, ``W_in = [W_gate | W_up]``,
as the program holds them.

``params`` is the list of the net's arrays in the order the architecture
declares them (``paths/common.py:parameters``); ``unpack`` names them.

Tolerance of the first step's loss (system: bf16; this: float32):
``|dloss| / (|loss| + 1) <= TOLERANCE``, the figure of the benchmark's other
cells. At initialisation (normal, 0.02) every logit is near 0 and the loss
near ln(vocabulary), so this limit catches a wrong vocabulary, a shifted
label or a NaN and NOT a precision: the logits' comparison does
(``score``, the traffic file's ``limits``).
"""
import jax
import jax.numpy as jnp

TOLERANCE = 5e-3
QUERY_BLOCK = 512

MIXER_PARAMS = {
    "linear_attention": ("w_q", "w_k", "w_v", "conv_q", "conv_k", "conv_v",
                         "w_a", "a_log", "dt_bias", "w_b", "w_g", "norm",
                         "w_o"),
    "full_attention": ("w_q", "q_norm", "w_k", "k_norm", "w_v", "w_o"),
}


def unpack(params, config):
    """{name: array} with per-layer dicts, from the flat list."""
    it = iter(params)
    out = {"embed": next(it), "layers": []}
    for kind in config["layer_types"]:
        p = {k: next(it) for k in MIXER_PARAMS[kind]}
        p.update((k, next(it)) for k in ("mixer_norm", "w_in", "w_out",
                                         "ffn_norm"))
        out["layers"].append(p)
    out["norm"], out["head"] = next(it), next(it)
    assert next(it, None) is None, "more arrays than the architecture names"
    return out


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def l2_norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def causal_conv(x, weight):
    """x (T, C), weight (C, W): y[t] = sum_j weight[:, j] x[t - (W - 1) + j],
    zeros before the sequence."""
    t, width = x.shape[0], weight.shape[1]
    padded = jnp.concatenate([jnp.zeros((width - 1, x.shape[1])), x])
    return sum(padded[j:j + t] * weight[:, j] for j in range(width))


def delta_rule(q, k, v, g, beta):
    """The gated delta rule, one step at a time. q, k (T, H, dk), v (T, H,
    dv), g, beta (T, H) -> o (T, H, dv) = S_t^T q_t."""
    t, h, dk = k.shape

    def step(state, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        state = jnp.exp(g_t)[:, None, None] * state             # alpha S
        wrote = v_t - jnp.einsum("hd,hde->he", k_t, state)
        state = state + b_t[:, None, None] * k_t[:, :, None] \
            * wrote[:, None, :]
        return state, jnp.einsum("hd,hde->he", q_t, state)

    _, o = jax.lax.scan(step, jnp.zeros((h, dk, v.shape[-1])),
                        (q, k, v, g, beta))
    return o


def gated_deltanet(x, p, c):
    heads = c["linear_num_key_heads"]
    dk, dv = c["linear_key_head_dim"], c["linear_value_head_dim"]
    eps = c["rms_norm_eps"]

    def one(x):                                               # x: (T, D)
        t = x.shape[0]

        def branch(w, conv, dim):
            return jax.nn.silu(causal_conv(x @ w, conv)).reshape(t, heads, dim)

        q = l2_norm(branch(p["w_q"], p["conv_q"], dk)) / jnp.sqrt(1.0 * dk)
        k = l2_norm(branch(p["w_k"], p["conv_k"], dk))
        v = branch(p["w_v"], p["conv_v"], dv)
        beta = jax.nn.sigmoid(x @ p["w_b"])
        if c["linear_allow_neg_eigval"]:
            beta = 2.0 * beta
        g = -jnp.exp(p["a_log"]) * jax.nn.softplus(x @ p["w_a"] + p["dt_bias"])
        o = rms_norm(delta_rule(q, k, v, g, beta), p["norm"], eps) \
            * jax.nn.silu(x @ p["w_g"]).reshape(t, heads, dv)
        return o.reshape(t, heads * dv) @ p["w_o"]

    return jnp.stack([one(x[b]) for b in range(x.shape[0])])


def causal_attention(q, k, v):
    """q, k, v (H, T, d) -> (H, T, d); QUERY_BLOCK queries at a time against
    all keys, masked."""
    h, t, d = q.shape
    blk = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
    pos = jnp.arange(t)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * blk, blk, axis=1)
        s = jnp.einsum("hqd,hkd->hqk", qb, k) / jnp.sqrt(1.0 * d)
        mask = (i * blk + jnp.arange(blk))[:, None] >= pos[None, :]
        prob = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,hkd->hqd", prob, v)

    out = jax.lax.map(block, jnp.arange(t // blk))           # (n, H, blk, d)
    return out.transpose(1, 0, 2, 3).reshape(h, t, d)


def full_attention(x, p, c):
    heads, eps = c["num_attention_heads"], c["rms_norm_eps"]
    d = c["hidden_size"] // heads

    def one(x):                                               # x: (T, D)
        t = x.shape[0]

        def split(y):
            return y.reshape(t, heads, d).transpose(1, 0, 2)

        o = causal_attention(split(rms_norm(x @ p["w_q"], p["q_norm"], eps)),
                             split(rms_norm(x @ p["w_k"], p["k_norm"], eps)),
                             split(x @ p["w_v"]))
        return o.transpose(1, 0, 2).reshape(t, heads * d) @ p["w_o"]

    return jnp.stack([one(x[b]) for b in range(x.shape[0])])


def swiglu(x, w_in, w_out):
    h = x @ w_in
    f = h.shape[-1] // 2
    return (jax.nn.silu(h[..., :f]) * h[..., f:]) @ w_out


MIXERS = {"linear_attention": gated_deltanet, "full_attention": full_attention}


def forward(params, tokens, config):
    """tokens (B, T) -> logits (B, T, V)."""
    c, p = config, unpack([a.astype(jnp.float32) for a in params], config)
    eps = c["rms_norm_eps"]
    x = p["embed"][tokens]
    for kind, lp in zip(c["layer_types"], p["layers"]):
        h = x + rms_norm(MIXERS[kind](x, lp, c), lp["mixer_norm"], eps)
        x = h + rms_norm(swiglu(h, lp["w_in"], lp["w_out"]), lp["ffn_norm"],
                         eps)
    return rms_norm(x, p["norm"], eps) @ p["head"].T


def cross_entropy(logits, label):
    """Mean over positions of logsumexp(logits) - the label's logit, in
    float32 whatever the logits are: the log-softmax's value at the label,
    with no (T, V) array of it or of the logits in float32 made (one
    sequence's float32 logits take 3.3 GB)."""
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

def rule_macs_per_token(c):
    """Multiply-adds a token and a layer of the chunked delta rule at the
    program's chunk: over the causal half of each (chunk, chunk) block the
    products K K^T and Q K^T and those of the solve's result with K and V,
    the solve itself (a sixth of the chunk squared), the masked product's
    with U; and the handed state's products with W and Q and the state's
    update."""
    heads = c["linear_num_key_heads"]
    dk, dv, q = c["linear_key_head_dim"], c["linear_value_head_dim"], \
        c["chunk_size"]
    return heads * (3 * q / 2 * dk + q * q / 6 + q * dv + 3 * dk * dv)


def _macs_per_token(c, t):
    """Multiply-adds a token, forward, by part."""
    d, ff, kinds = c["hidden_size"], c["intermediate_size"], c["layer_types"]
    heads = c["linear_num_key_heads"]
    dk, dv = c["linear_key_head_dim"], c["linear_value_head_dim"]
    n_lin, n_full = (kinds.count(k) for k in MIXERS)
    a_heads = c["num_attention_heads"]
    return {
        "linear_projections": n_lin * (
            d * heads * (2 * dk + 2 * dv + 2) + heads * dv * d
            + heads * (2 * dk + dv) * c["linear_conv_kernel_dim"]),
        "delta_rule": n_lin * rule_macs_per_token(c),
        "linear_ffn": n_lin * 3 * d * ff,
        "attention_projections": n_full * 4 * d * d,
        "attention_core": n_full * t / 2 * a_heads * 2 * (d // a_heads),
        "full_ffn": n_full * 3 * d * ff,
        "head": d * c["vocab_size"],
    }


def flops_per_sample(config):
    """2 per multiply-add, forward x 3, a sample being one sequence."""
    t = config["tokens_per_sample"]
    return 3 * 2 * t * sum(_macs_per_token(config, t).values())
