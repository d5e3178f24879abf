"""Plain reference of NVIDIA-Nemotron-3-Nano-30B-A3B (``model_type
nemotron_h``) for one chip's share of it: forward pass and loss in
``jax.numpy`` float32 at ``highest`` matmul precision, no kernels and no
chunked algebra: the state-space recurrence one step at a time by a
``lax.scan`` over time, attention in blocks of queries with the KV heads
indexed, routing by a plain ``top_k`` and a loop over the experts held.

    model:  x_0 = Emb(t); x_{l+1} = x_l + Mixer_l(RMSNorm(x_l)), the mixer by
            hybrid_override_pattern[l]; logits = W_head RMSNorm(x_L), untied;
            eps 1e-5; weights multiply from the right, no bias but the
            convolution's
    M:      [z | xBC | dt] = x W_in  (H P | H P + 2 G N | H)
            xBC = silu(conv1d_causal(xBC, width 4, depthwise, bias))
            u, B, C = split(xBC)     (H x P | G x N | G x N)
            D_t = softplus(dt + dt_bias); a = -exp(A_log)      per head
            S_t = exp(D_t a) S_{t-1} + D_t u_t (x) B_t         S_0 = 0, (P, N)
            y_t = S_t C_t + D u_t    B, C of the head's group
            y = RMSNorm_grouped(y silu(z))  within each of the G groups of
            H P / G channels, one weight, the gate BEFORE the norm
            out = y W_out
    *:      q = x W_q (32 x 128); k, v = x W_k, x W_v (2 x 128); query head h
            reads KV head h // 16; causal softmax(q.k / sqrt(128)); W_o
    E:      s = sigmoid(x W_r) over 128; chosen = top-6 of s + b
            w = 2.5 s[chosen] / (sum s[chosen] + 1e-20)
            y = sum_{e chosen and held} w_e E_e(x) + E_shared(x)
            E(x) = relu(x W_up)^2 W_down, 1,856 wide routed, 3,712 shared
    loss:   mean over positions of the cross-entropy of position i against
            token i + 1

Departures from the published model, all stated in the configuration file:
the share (8 of 128 experts held, what the other 120 would add is LEFT OUT
and the partial result goes on; 1/8 of the vocabulary; the first 9 of 52
layers), no rotary embedding in the attention layers (the ``nemotron_h``
reference code applies none though the config carries ``rope_theta``), the
step ``D_t`` not clamped (``time_step_limit`` is absent). The selection bias
``b`` is read, not updated: the reference computes one step's loss. The scan
over time is cut into blocks of ``SCAN_BLOCK`` steps, each under
``jax.checkpoint``, so that a gradient through it keeps the state once a
block and not once a step (8,192 x 64 x 64 x 128 floats do not fit); the
arithmetic is the step-by-step recurrence either way.

``params`` is the list of the net's arrays in the order the architecture
declares them (``paths/common.py:parameters``); ``unpack`` names them.

Tolerance of the on-chip comparison (system: bf16 compute, float32 masters;
this: float32): ``|dloss| / (|loss| + 1) <= TOLERANCE``, the figure of the
benchmark's other cells. At initialisation (normal, 0.02) every logit is near
0 and the loss near ln(vocabulary), so this limit catches a wrong vocabulary,
a shifted label or a NaN and NOT a precision: ``tools/chip_check_lm.py``
holds logits and gradients, and PERF.md says what it found.
"""
import jax
import jax.numpy as jnp

TOLERANCE = 5e-3
QUERY_BLOCK = 512
SCAN_BLOCK = 128

MIXER_PARAMS = {
    "M": ("w_in", "conv_weight", "conv_bias", "dt_bias", "a_log", "d",
          "gate_norm", "w_out"),
    "*": ("w_q", "w_k", "w_v", "w_o"),
    "E": ("gate", "bias", "w_in", "w_out", "shared_in", "shared_out",
          "load", "tokens_here"),
}


def unpack(params, config):
    """{name: array} with per-layer dicts, from the flat list."""
    it = iter(params)
    out = {"embed": next(it), "layers": []}
    for kind in config["hybrid_override_pattern"]:
        p = {"norm": next(it)}
        p.update((k, next(it)) for k in MIXER_PARAMS[kind])
        out["layers"].append(p)
    out["norm"], out["head"] = next(it), next(it)
    assert next(it, None) is None, "more arrays than the architecture names"
    return out


def kind_of(layer):
    """The pattern's letter of a layer's parameters."""
    return "M" if "conv_weight" in layer else "*" if "w_q" in layer else "E"


# "mamba_decay": the step's bias and A_log, whose gradients come through the
# recurrence alone
GROUPS = ("embedding", "head", "norms", "mamba_in", "mamba_out", "mamba_conv",
          "mamba_decay", "mamba_skip", "attention", "router", "experts",
          "shared_expert")
_GROUP_OF = {
    "M": {"w_in": "mamba_in", "w_out": "mamba_out",
          "conv_weight": "mamba_conv", "conv_bias": "mamba_conv",
          "dt_bias": "mamba_decay", "a_log": "mamba_decay",
          "d": "mamba_skip", "gate_norm": "norms"},
    "*": {k: "attention" for k in MIXER_PARAMS["*"]},
    "E": {"gate": "router", "w_in": "experts", "w_out": "experts",
          "shared_in": "shared_expert", "shared_out": "shared_expert"},
}


def parameter_groups(arrays, config):
    """{group: [arrays]} of a list in ``params``' order (the parameters or
    their gradients), for comparisons by parameter group. The selection
    bias and the counters are in no group: they take no gradient."""
    u = unpack(list(arrays), config)
    out = {g: [] for g in GROUPS}
    out["embedding"].append(u["embed"])
    out["head"].append(u["head"])
    out["norms"].append(u["norm"])
    for layer in u["layers"]:
        out["norms"].append(layer["norm"])
        for name, group in _GROUP_OF[kind_of(layer)].items():
            out[group].append(layer[name])
    return out


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def causal_conv(x, weight, bias):
    """x (T, C), weight (C, W), bias (C,): y[t] = bias + sum_j weight[:, j]
    x[t - (W - 1) + j], zeros before the sequence."""
    t, width = x.shape[0], weight.shape[1]
    padded = jnp.concatenate([jnp.zeros((width - 1, x.shape[1])), x])
    return bias + sum(padded[j:j + t] * weight[:, j] for j in range(width))


def recurrence(u, dt, a, b, c):
    """The selective state-space recurrence, one step at a time. u (T, H,
    P), dt (T, H), a (H,), b, c (T, G, N) -> y (T, H, P) = S_t C_t."""
    t, h, p = u.shape
    g, n = b.shape[1:]
    blk = SCAN_BLOCK if t % SCAN_BLOCK == 0 else t

    def step(state, xs):
        u_t, dt_t, b_t, c_t = xs
        b_h = jnp.repeat(b_t, h // g, axis=0)                        # (H, N)
        c_h = jnp.repeat(c_t, h // g, axis=0)
        state = jnp.exp(dt_t * a)[:, None, None] * state \
            + (dt_t[:, None] * u_t)[:, :, None] * b_h[:, None, :]
        return state, jnp.sum(state * c_h[:, None, :], axis=-1)

    @jax.checkpoint
    def block(state, xs):
        return jax.lax.scan(step, state, xs)

    xs = jax.tree_util.tree_map(
        lambda z: z.reshape((t // blk, blk) + z.shape[1:]), (u, dt, b, c))
    _, y = jax.lax.scan(block, jnp.zeros((h, p, n)), xs)
    return y.reshape(t, h, p)


def mamba2(x, p, c):
    heads, hd = c["mamba_num_heads"], c["mamba_head_dim"]
    groups, n = c["n_groups"], c["ssm_state_size"]
    inner, gn = heads * hd, groups * n

    def one(x):                                               # x: (T, D)
        t = x.shape[0]
        proj = x @ p["w_in"]
        z, xbc, dt = jnp.split(proj, [inner, 2 * inner + 2 * gn], axis=-1)
        xbc = jax.nn.silu(causal_conv(xbc, p["conv_weight"], p["conv_bias"]))
        u = xbc[:, :inner].reshape(t, heads, hd)
        y = recurrence(
            u, jax.nn.softplus(dt + p["dt_bias"]), -jnp.exp(p["a_log"]),
            xbc[:, inner:inner + gn].reshape(t, groups, n),
            xbc[:, inner + gn:].reshape(t, groups, n))
        y = (y + p["d"][:, None] * u).reshape(t, inner) * jax.nn.silu(z)
        y = rms_norm(y.reshape(t, groups, inner // groups), 1.0,
                     c["layer_norm_epsilon"]).reshape(t, inner)
        return (y * p["gate_norm"]) @ p["w_out"]

    return jnp.stack([one(x[b]) for b in range(x.shape[0])])


def grouped_attention(q, k, v):
    """q (KV, R, T, d): R query heads on each KV head; k, v (KV, T, d) ->
    (KV, R, T, d); QUERY_BLOCK queries at a time against all keys, masked."""
    t, d = q.shape[2:]
    blk = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
    pos = jnp.arange(t)

    @jax.checkpoint   # a gradient through it keeps no block's probabilities
    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * blk, blk, axis=2)
        s = jnp.einsum("grqd,gkd->grqk", qb, k) / jnp.sqrt(1.0 * d)
        mask = (i * blk + jnp.arange(blk))[:, None] >= pos[None, :]
        prob = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return jnp.einsum("grqk,gkd->grqd", prob, v)

    out = jax.lax.map(block, jnp.arange(t // blk))       # (n, KV, R, blk, d)
    return out.transpose(1, 2, 0, 3, 4).reshape(q.shape)


def attention(x, p, c):
    heads, kv = c["num_attention_heads"], c["num_key_value_heads"]
    d = c["head_dim"]

    def one(x):                                               # x: (T, D)
        t = x.shape[0]
        q = (x @ p["w_q"]).reshape(t, kv, heads // kv, d).transpose(1, 2, 0, 3)
        k = (x @ p["w_k"]).reshape(t, kv, d).transpose(1, 0, 2)
        v = (x @ p["w_v"]).reshape(t, kv, d).transpose(1, 0, 2)
        o = grouped_attention(q, k, v)                    # (KV, R, T, d)
        return o.transpose(2, 0, 1, 3).reshape(t, heads * d) @ p["w_o"]

    return jnp.stack([one(x[b]) for b in range(x.shape[0])])


def relu2_ffn(x, w_in, w_out):
    return jnp.square(jax.nn.relu(x @ w_in)) @ w_out


def moe(x, p, c, experts_held=None):
    """The held experts' part of the layer plus the shared expert."""
    held = c["experts_held"] if experts_held is None else experts_held
    s = jax.nn.sigmoid(x @ p["gate"])
    _, chosen = jax.lax.top_k(s + p["bias"], c["num_experts_per_tok"])
    w = jnp.take_along_axis(s, chosen, -1)
    w = c["routed_scaling_factor"] * w / (jnp.sum(w, -1, keepdims=True)
                                          + 1e-20)
    y = relu2_ffn(x, p["shared_in"], p["shared_out"])
    for j, e in enumerate(held):
        w_e = jnp.sum(jnp.where(chosen == e, w, 0.0), -1, keepdims=True)
        y = y + w_e * relu2_ffn(x, p["w_in"][j], p["w_out"][j])
    return y


MIXERS = {"M": mamba2, "*": attention, "E": moe}


def layer(x, p, c):
    return x + MIXERS[kind_of(p)](
        rms_norm(x, p["norm"], c["layer_norm_epsilon"]), p, c)


def forward(params, tokens, config):
    """tokens (B, T) -> logits (B, T, V)."""
    p = unpack([a.astype(jnp.float32) for a in params], config)
    x = p["embed"][tokens]
    for lp in p["layers"]:
        x = layer(x, lp, config)
    return rms_norm(x, p["norm"], config["layer_norm_epsilon"]) @ p["head"].T


def cross_entropy(logits, label):
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.mean(jnp.take_along_axis(
        logp, label.astype(jnp.int32)[..., None], -1))


def loss(params, data, label, config):
    with jax.default_matmul_precision("highest"):
        return cross_entropy(forward(params, data, config), label)


# ---------------------------------------------------------------------------
# operations, from the shapes

def _macs_per_token(c, t):
    """Multiply-adds a token, forward, by part."""
    d, pattern = c["hidden_size"], c["hybrid_override_pattern"]
    heads, hd = c["mamba_num_heads"], c["mamba_head_dim"]
    gn = c["n_groups"] * c["ssm_state_size"]
    inner, q = heads * hd, c["chunk_size"]
    a_heads, a_dim = c["num_attention_heads"], c["head_dim"]
    kv = c["num_key_value_heads"]
    ff, shared = c["moe_intermediate_size"], \
        c["moe_shared_expert_intermediate_size"]
    # routed experts at their expectation under even routing
    routed = c["num_experts_per_tok"] * len(c["experts_held"]) \
        / c["router_experts"] * 2 * d * ff
    n_m, n_a, n_e = (pattern.count(k) for k in "M*E")
    return {
        "mamba_projections": n_m * (d * (2 * inner + 2 * gn + heads)
                                    + inner * d
                                    + (inner + 2 * gn) * c["conv_kernel"]),
        # the chunked scan at chunk q: C B^T and its product with u over the
        # causal half of each (q, q) block, every chunk's own state, and the
        # handed state's product with C
        "mamba_scan": n_m * (q / 2 * gn + q / 2 * inner
                             + 2 * inner * c["ssm_state_size"]),
        "attention_projections": n_a * (2 * d * a_heads * a_dim
                                        + 2 * d * kv * a_dim),
        "attention_core": n_a * t / 2 * a_heads * 2 * a_dim,  # half the square
        "expert_layers": n_e * (2 * d * shared * c["n_shared_experts"]
                                + routed + d * c["router_experts"]),
        "head": d * c["vocab_size"],
    }


def flops_per_sample(config):
    """2 per multiply-add, forward x 3, a sample being one sequence."""
    t = config["tokens_per_sample"]
    return 3 * 2 * t * sum(_macs_per_token(config, t).values())
