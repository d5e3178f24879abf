"""The ``mimo_v2`` language model (MiMo-V2.5's ``model_type``, the
MiMo-V2-Flash family): attention layers of two kinds, full and in a sliding
window with a sink, their kinds read from ``hybrid_layer_pattern``; a
leading dense SwiGLU layer, then expert layers with no shared expert.

    layer:  h = x + Attn(RMSNorm(x));  y = h + FFN(RMSNorm(h)); eps 1e-5
    Attn:   [q | k | v] = x W_qkv, 64 query heads of 192, K heads of 192
            and V heads of 128 (4 of each in a full layer, 8 in a window
            layer); rotary embedding ("rotate half") over the FIRST 64 dims
            of each q and k head (int(192 x partial_rotary_factor 0.334)),
            base rope_theta 1e7 (full) or swa_rope_theta 1e4 (window);
            query head h reads KV head h // (64 / kv_heads);
            l_ij = q_i.k_j / sqrt(192) over j <= i (full) or
            i - 128 < j <= i (window: sliding_window keys, its own included);
            window layers: p_ij = exp(l_ij) / (exp(s_h) + sum_j' exp(l_ij')),
            s_h a learnt sink logit a head (add_swa_attention_sink_bias);
            full layers: the plain softmax;
            o_i = 0.707 sum_j p_ij v_j (attention_value_scale), then W_o
    FFN:    layer l with moe_layer_freq[l] = 0: SwiGLU 16,384 wide; the
            others: s = sigmoid(x W_r) over all 256 experts, the 8 chosen
            are top8(s + b) (b, the correction bias, only selects), w =
            s_chosen / sum s_chosen, y = sum over the chosen experts held
            here of w_e SwiGLU_e(x), each 2,048 wide; no shared expert
    head:   W_head RMSNorm(x_L), untied

The sizes are keyword arguments named as the published ``config.json``
names them. ``n_routed_experts`` and ``vocab_size`` are what THIS chip
holds; the router stays ``router_experts`` wide and ``experts_held`` says
which of its experts these are, as in ``glm_moe_lite``. Weights multiply
from the right and carry no bias.

A sink starts as ``log u``, ``u`` uniform in [64, 1,024]: at init (every
matrix normal 0.02) a query's logits over its window are of unit order, so
its 128 keys' ``sum exp(l)`` is several hundred and a sink of 0 would take
a fraction of a percent of the mass; from this draw it takes an eighth to
two thirds of it, and a program that drops it is seen.
"""
from __future__ import annotations

from ....base import check
from ...block import HybridBlock
from ... import nn
from .glm_moe_lite import DroplessMoE, RMSNorm, SwiGLU
from .nemotron_h import LogUniform

__all__ = ["MiMoV2", "mimo_v2", "CONFIG_KEYS"]

FULL, WINDOW = 0, 1  # the entries of hybrid_layer_pattern


class FusedQKVAttention(HybridBlock):
    """One attention layer of either kind
    (``ops.lm_ops.fused_qkv_attention``)."""

    def __init__(self, cfg, window, **kwargs):
        super().__init__(**kwargs)
        pre = "swa_" if window else ""
        hidden = cfg["hidden_size"]
        heads, kv = (cfg[pre + "num_attention_heads"],
                     cfg[pre + "num_key_value_heads"])
        qk, v = cfg[pre + "head_dim"], cfg[pre + "v_head_dim"]
        check(heads % kv == 0,
              "the query heads are a multiple of the KV heads")
        self._attrs = dict(
            heads=heads, kv_heads=kv, qk_dim=qk, v_dim=v,
            rope_dim=int(qk * cfg["partial_rotary_factor"]),
            theta=float(cfg[pre + "rope_theta"]),
            window=cfg["sliding_window"] if window else None,
            value_scale=float(cfg["attention_value_scale"]))
        get = self.params.get
        self.w_qkv = get("w_qkv", shape=(hidden, (heads + kv) * qk + kv * v))
        self.w_o = get("w_o", shape=(heads * v, hidden))
        if cfg["add_swa_attention_sink_bias" if window
               else "add_full_attention_sink_bias"]:
            self.sink = get("sink", shape=(heads,),
                            init=LogUniform(64.0, 1024.0))

    def hybrid_forward(self, F, x, w_qkv, w_o, **sink):
        return F.contrib.fused_qkv_attention(x, w_qkv, w_o, *sink.values(),
                                             **self._attrs)


class DecoderLayer(HybridBlock):
    """x + Attn(RMSNorm(x)), then h + FFN(RMSNorm(h)), for one layer."""

    def __init__(self, cfg, window, dense, **kwargs):
        super().__init__(**kwargs)
        hidden, eps = cfg["hidden_size"], cfg["layernorm_epsilon"]
        with self.name_scope():
            self.attn_norm = RMSNorm(hidden, eps)
            self.attn = FusedQKVAttention(cfg, window)
            self.ffn_norm = RMSNorm(hidden, eps)
            self.ffn = SwiGLU(hidden, cfg["intermediate_size"]) if dense \
                else DroplessMoE(hidden, cfg["moe_intermediate_size"],
                                 cfg["router_experts"], cfg["experts_held"],
                                 cfg["num_experts_per_tok"],
                                 cfg["routed_scaling_factor"] or 1.0,
                                 cfg["bias_update_speed"], shared_width=0)

    def hybrid_forward(self, F, x):
        h = x + self.attn(self.attn_norm(x))
        return h + self.ffn(self.ffn_norm(h))


class MiMoV2(HybridBlock):
    """tokens (B, T) int -> logits (B, T, V); position i predicts token
    i + 1."""

    def __init__(self, **cfg):
        super().__init__()
        cfg = dict(_DEFAULTS, **cfg)
        cfg.setdefault("router_experts", cfg["n_routed_experts"])
        cfg.setdefault("experts_held", tuple(range(cfg["n_routed_experts"])))
        check(len(cfg["experts_held"]) == cfg["n_routed_experts"],
              "experts_held names as many experts as n_routed_experts holds")
        pattern, dense = cfg["hybrid_layer_pattern"], cfg["moe_layer_freq"]
        check(len(pattern) == len(dense) == cfg["num_hidden_layers"]
              and set(pattern) <= {FULL, WINDOW} and set(dense) <= {0, 1},
              "hybrid_layer_pattern and moe_layer_freq have a 0 or 1 a layer")
        check(not cfg["n_shared_experts"], "no shared expert")
        check(cfg["scoring_func"] == "sigmoid"
              and cfg["topk_method"] == "noaux_tc" and cfg["n_group"] == 1
              and cfg["norm_topk_prob"],
              "sigmoid scores, noaux_tc top-k in one group, normalised")
        check(not cfg["tie_word_embeddings"], "the head is untied")
        hidden, vocab = cfg["hidden_size"], cfg["vocab_size"]
        with self.name_scope():
            self.embed = nn.Embedding(vocab, hidden)
            self.layers = nn.HybridSequential()
            for kind, moe in zip(pattern, dense):
                self.layers.add(DecoderLayer(cfg, kind == WINDOW, not moe))
            self.norm = RMSNorm(hidden, cfg["layernorm_epsilon"])
            self.head = nn.Dense(vocab, use_bias=False, flatten=False,
                                 in_units=hidden)

    def hybrid_forward(self, F, tokens):
        import jax
        x = self.embed(tokens)
        for layer in self.layers:
            x = layer(x)
        with jax.named_scope("mx.lm_head"):
            return self.head(self.norm(x))


# MiMo-V2.5's published config.json, the keys that shape the language model
_DEFAULTS = dict(
    hidden_size=4096, intermediate_size=16384, moe_intermediate_size=2048,
    num_hidden_layers=48,
    hybrid_layer_pattern=(0,) + (1, 1, 1, 1, 0) + (1, 1, 1, 1, 1, 0) * 7,
    moe_layer_freq=(0,) + (1,) * 47,
    num_attention_heads=64, num_key_value_heads=4, head_dim=192,
    v_head_dim=128, swa_num_attention_heads=64, swa_num_key_value_heads=8,
    swa_head_dim=192, swa_v_head_dim=128, partial_rotary_factor=0.334,
    rope_theta=10000000.0, swa_rope_theta=10000.0, sliding_window=128,
    add_full_attention_sink_bias=False, add_swa_attention_sink_bias=True,
    attention_value_scale=0.707, n_routed_experts=256, num_experts_per_tok=8,
    n_shared_experts=None, n_group=1, norm_topk_prob=True,
    routed_scaling_factor=None, scoring_func="sigmoid", topk_method="noaux_tc",
    layernorm_epsilon=1e-5, vocab_size=152576, tie_word_embeddings=False,
    bias_update_speed=0.001)
# what a configuration file may hand ``mimo_v2``: the sizes above, and which
# of the router's experts this chip holds
CONFIG_KEYS = tuple(_DEFAULTS) + ("router_experts", "experts_held")


def mimo_v2(**kwargs):
    """MiMo-V2.5's language model by default; every size is a keyword
    argument."""
    return MiMoV2(**kwargs)
