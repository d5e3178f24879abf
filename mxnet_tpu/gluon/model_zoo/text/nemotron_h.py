"""The ``nemotron_h`` language model (NVIDIA-Nemotron-3-Nano-30B-A3B's
``model_type``): layers of ONE mixer each, their kinds read from a pattern
string, no position embedding.

    layer:  x + Mixer(RMSNorm(x)), the mixer by ``hybrid_override_pattern``
      M     Mamba-2 (``ops.lm_ops.mamba2_mixer``): recurrent state by chunks
      *     grouped-KV attention (``ops.lm_ops.gqa_attention``)
      E     the dropless expert layer with squared-ReLU experts and a
            shared expert of its own width (``DroplessMoE``)
    head:   W_head RMSNorm(x_L), untied

The sizes are keyword arguments named as the published ``config.json``
names them. ``n_routed_experts`` and ``vocab_size`` are what THIS chip
holds; the router stays ``router_experts`` wide and ``experts_held`` says
which of its experts these are, as in ``glm_moe_lite``. Weights multiply
from the right and carry no bias but the convolution's.
"""
from __future__ import annotations

import numpy as _np

from ....base import check
from ....initializer import Initializer, Uniform
from ...block import HybridBlock
from ... import nn
from .glm_moe_lite import DroplessMoE, RMSNorm

__all__ = ["NemotronH", "nemotron_h", "CONFIG_KEYS"]


class _Fill(Initializer):
    """An initializer of one parameter, whatever its name ends in."""

    def _init_bias(self, desc, arr):
        self._init_weight(desc, arr)

    def _uniform(self, shape):
        return (self._rand(shape) + 1.0) / 2.0               # [0, 1)


class LogUniform(_Fill):
    """log of a uniform draw from [low, high]: Mamba-2's ``A_log``."""

    def __init__(self, low=1.0, high=16.0):
        super().__init__(low=low, high=high)

    def _init_weight(self, desc, arr):
        low, high = self._kwargs["low"], self._kwargs["high"]
        self._set(arr, _np.log(low + (high - low) * self._uniform(arr.shape)))


class InverseSoftplusStep(_Fill):
    """``dt_bias`` such that softplus(dt_bias) is log-uniform in [low,
    high] and at least ``floor`` (``time_step_min/max/floor``)."""

    def __init__(self, low=0.001, high=0.1, floor=1e-4):
        super().__init__(low=low, high=high, floor=floor)

    def _init_weight(self, desc, arr):
        low, high, floor = (self._kwargs[k] for k in ("low", "high", "floor"))
        dt = _np.exp(_np.log(low) + self._uniform(arr.shape)
                     * (_np.log(high) - _np.log(low)))
        dt = _np.maximum(dt, floor).astype(_np.float64)
        self._set(arr, dt + _np.log(-_np.expm1(-dt)))


class Mamba2(HybridBlock):
    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        hidden = cfg["hidden_size"]
        heads, head_dim = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
        groups, state = cfg["n_groups"], cfg["ssm_state_size"]
        check(heads % groups == 0, "mamba_num_heads is a multiple of n_groups")
        inner, conv = heads * head_dim, heads * head_dim + 2 * groups * state
        self._attrs = dict(heads=heads, head_dim=head_dim, groups=groups,
                           state=state, chunk=cfg["chunk_size"],
                           eps=cfg["layer_norm_epsilon"])
        get = self.params.get
        self.w_in = get("w_in", shape=(hidden, inner + conv + heads))
        # as the published code leaves it: the framework default of a
        # depthwise Conv1d, uniform within 1 / sqrt(kernel width). At the
        # 0.02 of the matrices the state would be a thousandth of the skip
        self.conv_weight = get("conv_weight", shape=(conv, cfg["conv_kernel"]),
                               init=Uniform(cfg["conv_kernel"] ** -0.5))
        self.conv_bias = get("conv_bias", shape=(conv,), init="zeros")
        self.dt_bias = get("dt_bias", shape=(heads,),
                           init=InverseSoftplusStep(
                               cfg["time_step_min"], cfg["time_step_max"],
                               cfg["time_step_floor"]))
        self.a_log = get("a_log", shape=(heads,), init=LogUniform(1.0, 16.0))
        self.d = get("d", shape=(heads,), init="ones")
        self.norm = get("norm", shape=(inner,), init="ones")
        self.w_out = get("w_out", shape=(inner, hidden))

    def hybrid_forward(self, F, x, w_in, conv_weight, conv_bias, dt_bias,
                       a_log, d, norm, w_out):
        return F.contrib.mamba2_mixer(x, w_in, conv_weight, conv_bias,
                                      dt_bias, a_log, d, norm, w_out,
                                      **self._attrs)


class GroupedKVAttention(HybridBlock):
    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        hidden, dim = cfg["hidden_size"], cfg["head_dim"]
        heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
        check(heads % kv == 0,
              "num_attention_heads is a multiple of num_key_value_heads")
        self._attrs = dict(heads=heads, kv_heads=kv, head_dim=dim)
        get = self.params.get
        self.w_q = get("w_q", shape=(hidden, heads * dim))
        self.w_k = get("w_k", shape=(hidden, kv * dim))
        self.w_v = get("w_v", shape=(hidden, kv * dim))
        self.w_o = get("w_o", shape=(heads * dim, hidden))

    def hybrid_forward(self, F, x, w_q, w_k, w_v, w_o):
        return F.contrib.gqa_attention(x, w_q, w_k, w_v, w_o, **self._attrs)


def _mixer(kind, cfg):
    if kind == "M":
        return Mamba2(cfg)
    if kind == "*":
        return GroupedKVAttention(cfg)
    return DroplessMoE(
        cfg["hidden_size"], cfg["moe_intermediate_size"],
        cfg["router_experts"], cfg["experts_held"],
        cfg["num_experts_per_tok"], cfg["routed_scaling_factor"],
        cfg["bias_update_speed"], activation="relu2",
        shared_width=cfg["moe_shared_expert_intermediate_size"])


class MixerLayer(HybridBlock):
    """x + Mixer(RMSNorm(x)) for one letter of the pattern."""

    def __init__(self, kind, cfg, **kwargs):
        super().__init__(**kwargs)
        self.kind = kind
        with self.name_scope():
            self.norm = RMSNorm(cfg["hidden_size"], cfg["layer_norm_epsilon"])
            self.mixer = _mixer(kind, cfg)

    def hybrid_forward(self, F, x):
        return x + self.mixer(self.norm(x))


class NemotronH(HybridBlock):
    """tokens (B, T) int -> logits (B, T, V); position i predicts token
    i + 1. ``remat``: every layer is recomputed in the backward pass and only
    its input (and an attention layer's output) is kept."""

    def __init__(self, remat=False, **cfg):
        super().__init__()
        cfg = dict(_DEFAULTS, **cfg)
        cfg.setdefault("router_experts", cfg["n_routed_experts"])
        cfg.setdefault("experts_held", tuple(range(cfg["n_routed_experts"])))
        check(len(cfg["experts_held"]) == cfg["n_routed_experts"],
              "experts_held names as many experts as n_routed_experts holds")
        pattern = cfg["hybrid_override_pattern"]
        check(len(pattern) == cfg["num_hidden_layers"]
              and set(pattern) <= set("M*E"),
              "hybrid_override_pattern has one of M, *, E a layer (the "
              "family's dense FFN layers, '-', are not built)")
        self._remat = remat
        hidden, vocab = cfg["hidden_size"], cfg["vocab_size"]
        with self.name_scope():
            self.embed = nn.Embedding(vocab, hidden)
            self.layers = nn.HybridSequential()
            for kind in pattern:
                self.layers.add(MixerLayer(kind, cfg))
            self.norm = RMSNorm(hidden, cfg["layer_norm_epsilon"])
            self.head = nn.Dense(vocab, use_bias=False, flatten=False,
                                 in_units=hidden)

    def hybrid_forward(self, F, tokens):
        import jax
        x = self.embed(tokens)
        for layer in self.layers:
            x = layer.remat_call(x) if self._remat else layer(x)
        with jax.named_scope("mx.lm_head"):
            return self.head(self.norm(x))


# NVIDIA-Nemotron-3-Nano-30B-A3B-BF16's published config.json, the keys that
# shape the model
_DEFAULTS = dict(
    hidden_size=2688, num_hidden_layers=52,
    hybrid_override_pattern="MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*"
                            "EMEMEMEME",
    mamba_num_heads=64, mamba_head_dim=64, n_groups=8, ssm_state_size=128,
    conv_kernel=4, chunk_size=128, time_step_min=0.001, time_step_max=0.1,
    time_step_floor=1e-4, num_attention_heads=32, num_key_value_heads=2,
    head_dim=128, moe_intermediate_size=1856,
    moe_shared_expert_intermediate_size=3712, n_routed_experts=128,
    num_experts_per_tok=6, routed_scaling_factor=2.5,
    layer_norm_epsilon=1e-5, vocab_size=131072, bias_update_speed=0.001)
# what a configuration file may hand the builder: the sizes above, and which
# of the router's experts this chip holds
CONFIG_KEYS = tuple(_DEFAULTS) + ("router_experts", "experts_held")


def nemotron_h(**kwargs):
    """Nemotron-3-Nano-30B-A3B by default; every size is a keyword
    argument."""
    return NemotronH(**kwargs)
