"""The ``olmo_hybrid`` language model (Olmo-Hybrid-7B's ``model_type``):
layers of Gated DeltaNet (linear attention with the delta rule) and of full
softmax attention, their kinds read from ``layer_types``, each followed by
a SwiGLU FFN; no position embedding.

    layer:  h = x + RMSNorm(Mixer(x));  y = h + RMSNorm(SwiGLU(h))
            the norms AFTER the sublayer, as Olmo 2 and 3 place them
      linear_attention  Gated DeltaNet (``ops.lm_ops.gated_deltanet_mixer``):
                        recurrent state by chunks
      full_attention    causal attention with QK-norm
                        (``ops.lm_ops.qk_norm_attention``)
    head:   W_head RMSNorm(x_L), untied

The sizes are keyword arguments named as the published ``config.json``
names them; ``chunk_size`` is the program's own (the delta rule's chunk: it
changes no result). Weights multiply from the right and carry no bias.
"""
from __future__ import annotations

from ....base import check
from ....initializer import Uniform
from ...block import HybridBlock
from ... import nn
from .glm_moe_lite import RMSNorm, SwiGLU
from .nemotron_h import InverseSoftplusStep, LogUniform

__all__ = ["OlmoHybrid", "olmo_hybrid", "CONFIG_KEYS"]

LAYER_KINDS = ("linear_attention", "full_attention")


class GatedDeltaNet(HybridBlock):
    """The linear-attention mixer, its parts as flash-linear-attention's
    ``GatedDeltaNet`` builds them: three short convolutions with no bias,
    ``A_log`` the log of a uniform draw from [0, 16], ``dt_bias`` the
    inverse softplus of a log-uniform step in [0.001, 0.1]."""

    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        hidden, heads = cfg["hidden_size"], cfg["linear_num_key_heads"]
        check(cfg["linear_num_value_heads"] == heads,
              "linear_num_value_heads equals linear_num_key_heads")
        dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
        width = cfg["linear_conv_kernel_dim"]
        self._attrs = dict(heads=heads, key_dim=dk, value_dim=dv,
                           neg_eigval=bool(cfg["linear_allow_neg_eigval"]),
                           chunk=cfg["chunk_size"], eps=cfg["rms_norm_eps"])
        get = self.params.get
        # as the published code leaves a depthwise Conv1d: the framework
        # default, uniform within 1 / sqrt(kernel width)
        conv = Uniform(width ** -0.5)
        self.w_q = get("w_q", shape=(hidden, heads * dk))
        self.w_k = get("w_k", shape=(hidden, heads * dk))
        self.w_v = get("w_v", shape=(hidden, heads * dv))
        self.conv_q = get("conv_q", shape=(heads * dk, width), init=conv)
        self.conv_k = get("conv_k", shape=(heads * dk, width), init=conv)
        self.conv_v = get("conv_v", shape=(heads * dv, width), init=conv)
        self.w_a = get("w_a", shape=(hidden, heads))
        self.a_log = get("a_log", shape=(heads,), init=LogUniform(0.0, 16.0))
        self.dt_bias = get("dt_bias", shape=(heads,),
                           init=InverseSoftplusStep(0.001, 0.1, 1e-4))
        self.w_b = get("w_b", shape=(hidden, heads))
        self.w_g = get("w_g", shape=(hidden, heads * dv))
        self.norm = get("norm", shape=(dv,), init="ones")
        self.w_o = get("w_o", shape=(heads * dv, hidden))

    def hybrid_forward(self, F, x, w_q, w_k, w_v, conv_q, conv_k, conv_v,
                       w_a, a_log, dt_bias, w_b, w_g, norm, w_o):
        return F.contrib.gated_deltanet_mixer(
            x, w_q, w_k, w_v, conv_q, conv_k, conv_v, w_a, a_log, dt_bias,
            w_b, w_g, norm, w_o, **self._attrs)


class QKNormAttention(HybridBlock):
    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        hidden, heads = cfg["hidden_size"], cfg["num_attention_heads"]
        check(cfg["num_key_value_heads"] == heads,
              "num_key_value_heads equals num_attention_heads")
        check(hidden % heads == 0, "hidden_size is a multiple of "
              "num_attention_heads")
        self._attrs = dict(heads=heads, head_dim=hidden // heads,
                           eps=cfg["rms_norm_eps"])
        get = self.params.get
        self.w_q = get("w_q", shape=(hidden, hidden))
        self.q_norm = get("q_norm", shape=(hidden,), init="ones")
        self.w_k = get("w_k", shape=(hidden, hidden))
        self.k_norm = get("k_norm", shape=(hidden,), init="ones")
        self.w_v = get("w_v", shape=(hidden, hidden))
        self.w_o = get("w_o", shape=(hidden, hidden))

    def hybrid_forward(self, F, x, w_q, q_norm, w_k, k_norm, w_v, w_o):
        return F.contrib.qk_norm_attention(x, w_q, q_norm, w_k, k_norm, w_v,
                                           w_o, **self._attrs)


class HybridLayer(HybridBlock):
    """x + RMSNorm(Mixer(x)), then the same round the FFN, for one entry of
    ``layer_types``."""

    def __init__(self, kind, cfg, **kwargs):
        super().__init__(**kwargs)
        self.kind = kind
        hidden, eps = cfg["hidden_size"], cfg["rms_norm_eps"]
        with self.name_scope():
            self.mixer = GatedDeltaNet(cfg) if kind == "linear_attention" \
                else QKNormAttention(cfg)
            self.mixer_norm = RMSNorm(hidden, eps)
            self.ffn = SwiGLU(hidden, cfg["intermediate_size"])
            self.ffn_norm = RMSNorm(hidden, eps)

    def hybrid_forward(self, F, x):
        h = x + self.mixer_norm(self.mixer(x))
        return h + self.ffn_norm(self.ffn(h))


class OlmoHybrid(HybridBlock):
    """tokens (B, T) int -> logits (B, T, V); position i predicts token
    i + 1."""

    def __init__(self, **cfg):
        super().__init__()
        cfg = dict(_DEFAULTS, **cfg)
        kinds = list(cfg["layer_types"])
        check(len(kinds) == cfg["num_hidden_layers"]
              and set(kinds) <= set(LAYER_KINDS),
              f"layer_types has one of {LAYER_KINDS} a layer")
        check(not cfg["tie_word_embeddings"], "the head is untied")
        hidden, vocab = cfg["hidden_size"], cfg["vocab_size"]
        with self.name_scope():
            self.embed = nn.Embedding(vocab, hidden)
            self.layers = nn.HybridSequential()
            for kind in kinds:
                self.layers.add(HybridLayer(kind, cfg))
            self.norm = RMSNorm(hidden, cfg["rms_norm_eps"])
            self.head = nn.Dense(vocab, use_bias=False, flatten=False,
                                 in_units=hidden)

    def hybrid_forward(self, F, tokens):
        import jax
        x = self.embed(tokens)
        for layer in self.layers:
            x = layer(x)
        with jax.named_scope("mx.lm_head"):
            return self.head(self.norm(x))


# Olmo-Hybrid-7B's published config.json, the keys that shape the model
_DEFAULTS = dict(
    hidden_size=3840, intermediate_size=11008, num_hidden_layers=32,
    layer_types=(("linear_attention",) * 3 + ("full_attention",)) * 8,
    num_attention_heads=30, num_key_value_heads=30, linear_num_key_heads=30,
    linear_num_value_heads=30, linear_key_head_dim=96,
    linear_value_head_dim=192, linear_conv_kernel_dim=4,
    linear_allow_neg_eigval=True, rms_norm_eps=1e-6, vocab_size=100352,
    tie_word_embeddings=False, chunk_size=64)
CONFIG_KEYS = tuple(_DEFAULTS)


def olmo_hybrid(**kwargs):
    """Olmo-Hybrid-7B by default; every size is a keyword argument."""
    return OlmoHybrid(**kwargs)
