"""Plain reference of GLM-4.7-Flash (``model_type glm4_moe_lite``) for one
chip's share of it: forward pass and both losses in ``jax.numpy`` float32 at
``highest`` matmul precision, no kernels, routing by a plain ``top_k`` and a
loop over the experts held, attention in blocks of queries so that it fits
beside the net at 8,192 tokens.

    block:  h = x + MLA(RMSNorm(x));  y = h + FFN(RMSNorm(h)); eps 1e-5
            FFN = SwiGLU(10,240) in layer 0, the expert layer after
    MLA:    c_q = RMSNorm(W_qa x); q = W_qb c_q -> heads x (192 + 64)
            [c_kv | k_r] = W_kva x; [k_nope | v] = W_kvb RMSNorm(c_kv)
            RoPE on q's 64 and on k_r (one rope key for all heads)
            causal softmax(q.k / sqrt(256)); W_o from heads x 256 to 2048
    MoE:    s = sigmoid(W_r x) over 64; chosen = top-4 of s + b
            w = 1.8 s[chosen] / sum s[chosen]
            y = sum_{e chosen and held} w_e E_e(x) + E_shared(x)
    MTP:    h' = W_eh [RMSNorm(h_L) ; RMSNorm(Emb(t_{i+1}))], one expert
            block, the shared final norm and head, predicting t_{i+2}
    loss:   CE(main) + 0.3 CE(MTP), mean over positions

Departures from the published model, all stated in the configuration file:
the share (8 of 64 experts held, what the other 56 would add is LEFT OUT and
the partial result goes on; 1/8 of the vocabulary; 5 of 47 layers), RoPE
pairs (i, i + 32) ("rotate half"), and the MTP module sharing embedding and
head with the model. ``h_L`` is the last layer's output BEFORE the final
norm, as DeepSeek-V3's MTP has it. The selection bias ``b`` is read, not
updated: the reference computes one step's loss.

``params`` is the list of the net's arrays in the order the architecture
declares them (``paths/common.py:parameters``); ``unpack`` names them.

Tolerance of the on-chip comparison (system: bf16 compute, float32 masters;
this: float32): ``|dloss| / (|loss| + 1) <= TOLERANCE``, the figure of the
benchmark's other cells. At initialisation (normal, 0.02) every logit is
near 0 and both losses are near ln(vocabulary), so this limit catches a wrong
vocabulary, a wrong loss weight or a NaN and NOT a precision: PERF.md says so
and what the builder's own chip check (logits and gradient norms) found.
"""
import jax
import jax.numpy as jnp

TOLERANCE = 5e-3
QUERY_BLOCK = 512


def unpack(params, config):
    """{name: array} with per-layer dicts, from the flat list."""
    it = iter(params)

    def norm():
        return next(it)

    def layer(dense):
        p = {"attn_norm": norm()}
        p.update((k, next(it)) for k in
                 ("w_qa", "q_norm", "w_qb", "w_kva", "kv_norm", "w_kvb", "w_o"))
        p["ffn_norm"] = norm()
        names = ("w_in", "w_out") if dense else (
            "gate", "bias", "w_in", "w_out", "shared_in", "shared_out",
            "load", "tokens_here")
        p.update((k, next(it)) for k in names)
        return p

    out = {"embed": next(it)}
    out["layers"] = [layer(i < config["first_k_dense_replace"])
                     for i in range(config["num_hidden_layers"])]
    out["norm"], out["head"] = norm(), next(it)
    if config["num_nextn_predict_layers"]:
        out["mtp_hnorm"], out["mtp_enorm"] = norm(), norm()
        out["mtp_proj"] = next(it)
        out["mtp_layer"] = layer(False)
    assert next(it, None) is None, "more arrays than the architecture names"
    return out


GROUPS = ("embedding", "head", "mla", "norms", "dense_ffn", "router",
          "experts", "shared_expert", "mtp_projection")


def parameter_groups(arrays, config):
    """{group: [arrays]} of a list in ``params``' order (the parameters or
    their gradients), for comparisons by parameter group. The selection
    bias and the counters are in no group: they take no gradient."""
    u = unpack(list(arrays), config)
    out = {g: [] for g in GROUPS}
    out["embedding"].append(u["embed"])
    out["head"].append(u["head"])
    out["mtp_projection"].append(u["mtp_proj"])
    out["norms"] += [u["norm"], u["mtp_hnorm"], u["mtp_enorm"]]
    for layer in u["layers"] + [u["mtp_layer"]]:
        out["mla"] += [layer[k] for k in
                       ("w_qa", "w_qb", "w_kva", "w_kvb", "w_o")]
        out["norms"] += [layer[k] for k in
                         ("attn_norm", "ffn_norm", "q_norm", "kv_norm")]
        if "gate" in layer:
            out["router"].append(layer["gate"])
            out["experts"] += [layer["w_in"], layer["w_out"]]
            out["shared_expert"] += [layer["shared_in"], layer["shared_out"]]
        else:
            out["dense_ffn"] += [layer["w_in"], layer["w_out"]]
    return out


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, theta):
    t, dim = x.shape[-2], x.shape[-1]
    inv = theta ** (-jnp.arange(dim // 2, dtype=jnp.float32) * 2.0 / dim)
    a = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    x1, x2 = x[..., :dim // 2], x[..., dim // 2:]
    return jnp.concatenate([x1 * jnp.cos(a) - x2 * jnp.sin(a),
                            x2 * jnp.cos(a) + x1 * jnp.sin(a)], -1)


def causal_attention(q, k, v):
    """q, k (H, T, dk), v (H, T, dv) -> (H, T, dv); QUERY_BLOCK queries at a
    time against all keys, masked."""
    h, t, dk = q.shape
    blk = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
    pos = jnp.arange(t)

    @jax.checkpoint   # a gradient through it keeps no block's probabilities
    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * blk, blk, axis=1)
        s = jnp.einsum("hqd,hkd->hqk", qb, k) / jnp.sqrt(1.0 * dk)
        mask = (i * blk + jnp.arange(blk))[:, None] >= pos[None, :]
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,hkd->hqd", p, v)

    out = jax.lax.map(block, jnp.arange(t // blk))          # (n, H, blk, dv)
    return out.transpose(1, 0, 2, 3).reshape(h, t, -1)


def mla(x, p, c):
    heads, nope = c["num_attention_heads"], c["qk_nope_head_dim"]
    rdim, vdim, rank = c["qk_rope_head_dim"], c["v_head_dim"], c["kv_lora_rank"]
    eps, theta = c["rms_norm_eps"], float(c["rope_theta"])

    def one(x):                                               # x: (T, D)
        t = x.shape[0]
        q = (rms_norm(x @ p["w_qa"], p["q_norm"], eps) @ p["w_qb"]) \
            .reshape(t, heads, nope + rdim).transpose(1, 0, 2)
        kva = x @ p["w_kva"]
        kv = (rms_norm(kva[:, :rank], p["kv_norm"], eps) @ p["w_kvb"]) \
            .reshape(t, heads, nope + vdim).transpose(1, 0, 2)
        k_r = rope(kva[:, rank:], theta)
        q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], theta)], -1)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_r[None], (heads, t, rdim))], -1)
        o = causal_attention(q, k, kv[..., nope:])
        return o.transpose(1, 0, 2).reshape(t, heads * vdim) @ p["w_o"]

    return jnp.stack([one(x[b]) for b in range(x.shape[0])])


def swiglu(x, w_in, w_out):
    h = x @ w_in
    f = h.shape[-1] // 2
    return (jax.nn.silu(h[..., :f]) * h[..., f:]) @ w_out


def moe(x, p, c, experts_held=None):
    """The held experts' part of the layer plus the shared expert."""
    held = c["experts_held"] if experts_held is None else experts_held
    s = jax.nn.sigmoid(x @ p["gate"])
    _, chosen = jax.lax.top_k(s + p["bias"], c["num_experts_per_tok"])
    w = jnp.take_along_axis(s, chosen, -1)
    w = c["routed_scaling_factor"] * w / jnp.sum(w, -1, keepdims=True)
    y = swiglu(x, p["shared_in"], p["shared_out"])
    for j, e in enumerate(held):
        w_e = jnp.sum(jnp.where(chosen == e, w, 0.0), -1, keepdims=True)
        y = y + w_e * swiglu(x, p["w_in"][j], p["w_out"][j])
    return y


def layer(x, p, c):
    eps = c["rms_norm_eps"]
    h = x + mla(rms_norm(x, p["attn_norm"], eps), p, c)
    g = rms_norm(h, p["ffn_norm"], eps)
    return h + (moe(g, p, c) if "gate" in p else swiglu(g, p["w_in"], p["w_out"]))


def forward(params, tokens, config):
    """tokens (B, T + 1) -> (logits (B, T, V), MTP logits (B, T, V))."""
    c, p = config, unpack([a.astype(jnp.float32) for a in params], config)
    eps = c["rms_norm_eps"]
    t = tokens.shape[1] - c["num_nextn_predict_layers"]
    emb = p["embed"][tokens]
    x = emb[:, :t]
    for lp in p["layers"]:
        x = layer(x, lp, c)
    logits = rms_norm(x, p["norm"], eps) @ p["head"].T
    if not c["num_nextn_predict_layers"]:
        return logits, None
    h = jnp.concatenate([rms_norm(x, p["mtp_hnorm"], eps),
                         rms_norm(emb[:, 1:t + 1], p["mtp_enorm"], eps)], -1)
    h = layer(h @ p["mtp_proj"].T, p["mtp_layer"], c)
    return logits, rms_norm(h, p["norm"], eps) @ p["head"].T


def cross_entropy(logits, label):
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.mean(jnp.take_along_axis(
        logp, label.astype(jnp.int32)[..., None], -1))


def losses(params, data, label, config):
    """(CE of the main head, CE of the MTP head), float32."""
    with jax.default_matmul_precision("highest"):
        main, mtp = forward(params, data, config)
        return cross_entropy(main, label[..., 0]), \
            cross_entropy(mtp, label[..., 1])


def loss(params, data, label, config, mtp_weight=None):
    main, mtp = losses(params, data, label, config)
    weight = config["mtp_loss_weight"] if mtp_weight is None else mtp_weight
    return main + weight * mtp


def loss_of_logits(heads, label, config):
    """The loss of a batch whose (logits, MTP logits) are given, float32."""
    main, mtp = (h.astype(jnp.float32) for h in heads)
    return cross_entropy(main, label[..., 0]) + config["mtp_loss_weight"] \
        * cross_entropy(mtp, label[..., 1])


def score(params, data, label, config):
    """(loss, (logits, MTP logits)) of one forward pass, float32: what a
    path that does not train is compared with, a sequence at a time."""
    with jax.default_matmul_precision("highest"):
        heads = forward(params, data, config)
        return loss_of_logits(heads, label, config), heads


# ---------------------------------------------------------------------------
# operations and bytes, from the shapes

def _macs_per_token(c, t):
    """Multiply-adds a token, forward, by part."""
    d, heads = c["hidden_size"], c["num_attention_heads"]
    dk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    dv, ff = c["v_head_dim"], c["moe_intermediate_size"]
    mla_proj = d * c["q_lora_rank"] + c["q_lora_rank"] * heads * dk \
        + d * (c["kv_lora_rank"] + c["qk_rope_head_dim"]) \
        + c["kv_lora_rank"] * heads * (c["qk_nope_head_dim"] + dv) \
        + heads * dv * d
    core = t / 2 * heads * (dk + dv)       # causal: half the square
    dense = c["first_k_dense_replace"]
    expert_layers = c["num_hidden_layers"] - dense \
        + c["num_nextn_predict_layers"]
    layers = dense + expert_layers
    # routed experts at their expectation under even routing
    routed = c["num_experts_per_tok"] * len(c["experts_held"]) \
        / c["router_experts"] * 3 * d * ff
    return {
        "mla_projections": layers * mla_proj,
        "attention_core": layers * core,
        "dense_ffn": dense * 3 * d * c["intermediate_size"],
        "expert_layers": expert_layers * (
            3 * d * ff * c["n_shared_experts"] + routed
            + d * c["router_experts"]),
        "heads": (1 + c["num_nextn_predict_layers"]) * d * c["vocab_size"],
        "mtp_projection": c["num_nextn_predict_layers"] * 2 * d * d,
    }


def flops_per_sample(config):
    """2 per multiply-add, forward x 3, a sample being one sequence."""
    t = config["tokens_per_sample"]
    return 3 * 2 * t * sum(_macs_per_token(config, t).values())


def kernel_costs(config, batch):
    """{kernel: (FLOPs, bytes)} a step over all its call sites (the
    forward kernel runs once a step, trained or not; the other two only
    where the step trains):
    what the algorithm needs, causal attention at half the square, every
    operand read and every result written once, bf16 (the log-sum-exp and
    the row sums float32)."""
    c, t = config, config["tokens_per_sample"]
    bh = batch * c["num_attention_heads"]
    dk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    dv = c["v_head_dim"]
    sites = c["num_hidden_layers"] + c["num_nextn_predict_layers"]
    half = bh * t * t / 2 * 2               # FLOPs of one (T, T/2) product
    row = bh * t
    return {
        "mx_attention_fwd": (sites * half * (dk + dv),
                             sites * row * (2 * (2 * dk + 2 * dv) + 4)),
        "mx_attention_dq": (sites * half * (2 * dk + dv),
                            sites * row * (2 * (3 * dk + 2 * dv) + 8)),
        "mx_attention_dkv": (sites * half * (2 * dk + 2 * dv),
                             sites * row * (2 * (3 * dk + 3 * dv) + 8)),
    }
