"""The ``glm4_moe_lite`` language model (GLM-4.7-Flash's ``model_type``):
multi-head latent attention, a leading dense SwiGLU layer, then expert
layers (sigmoid routing without an auxiliary loss, top-k without drops, one
shared expert) and multi-token-prediction modules.

    block:  h = x + MLA(RMSNorm(x));  y = h + FFN(RMSNorm(h))
    MTP:    h' = W_eh [RMSNorm(h_L) ; RMSNorm(Emb(t_{i+1}))], one expert
            block, the model's final norm and head, predicting t_{i+2}

The sizes are keyword arguments named as the published ``config.json``
names them. ``n_routed_experts`` and ``vocab_size`` are what THIS chip
holds; the router stays ``router_experts`` wide and ``experts_held`` says
which of its experts these are (expert parallelism: the layer computes its
own experts' part and nothing stands in for the rest). Weights multiply from
the right (``ops/lm_ops.py``); ``Embedding`` and the head's ``Dense`` are
the layers every other model uses.
"""
from __future__ import annotations

import weakref

from ....base import check
from ...block import HybridBlock
from ... import nn
from ...loss import Loss

__all__ = ["GLM4MoELite", "LMLoss", "glm4_moe_lite", "CONFIG_KEYS"]


class RMSNorm(HybridBlock):
    def __init__(self, size, eps, **kwargs):
        super().__init__(**kwargs)
        self._eps = eps
        self.weight = self.params.get("weight", shape=(size,), init="ones")

    def hybrid_forward(self, F, x, weight):
        return F.contrib.rms_norm(x, weight, eps=self._eps)


class MLA(HybridBlock):
    """Multi-head latent attention (``ops.lm_ops.mla_attention``)."""

    def __init__(self, hidden, heads, q_rank, kv_rank, nope, rope, v_dim,
                 theta, eps, **kwargs):
        super().__init__(**kwargs)
        self._attrs = dict(heads=heads, nope=nope, rope_dim=rope,
                           v_dim=v_dim, theta=float(theta), eps=eps)
        get = self.params.get
        self.w_qa = get("w_qa", shape=(hidden, q_rank))
        self.q_norm = get("q_norm", shape=(q_rank,), init="ones")
        self.w_qb = get("w_qb", shape=(q_rank, heads * (nope + rope)))
        self.w_kva = get("w_kva", shape=(hidden, kv_rank + rope))
        self.kv_norm = get("kv_norm", shape=(kv_rank,), init="ones")
        self.w_kvb = get("w_kvb", shape=(kv_rank, heads * (nope + v_dim)))
        self.w_o = get("w_o", shape=(heads * v_dim, hidden))

    def hybrid_forward(self, F, x, w_qa, q_norm, w_qb, w_kva, kv_norm,
                       w_kvb, w_o):
        return F.contrib.mla_attention(x, w_qa, q_norm, w_qb, w_kva, kv_norm,
                                       w_kvb, w_o, **self._attrs)


class SwiGLU(HybridBlock):
    def __init__(self, hidden, width, **kwargs):
        super().__init__(**kwargs)
        self.w_in = self.params.get("w_in", shape=(hidden, 2 * width))
        self.w_out = self.params.get("w_out", shape=(width, hidden))

    def hybrid_forward(self, F, x, w_in, w_out):
        return F.contrib.swiglu_ffn(x, w_in, w_out)


class DroplessMoE(HybridBlock):
    """The expert layer for the experts held here
    (``parallel.moe.dropless_moe_ffn``): SwiGLU experts, or with
    ``activation="relu2"`` experts of one product in; the shared expert is
    ``shared_width`` wide (``width`` by default; 0: no shared expert, and
    no ``shared_in``/``shared_out`` parameters). ``bias`` is the router's
    selection bias: no gradient; in training mode each forward moves it by
    ``gamma * sign(mean load - load)``. ``load`` (tokens routed to each of
    the router's experts in the last step) and ``tokens_here`` ((token,
    expert) pairs computed here) are counters kept on the device;
    ``DroplessMoE.instances`` is where a reader finds the live layers."""

    instances = weakref.WeakSet()

    def __init__(self, hidden, width, router_experts, experts_held, top_k,
                 scaling, gamma, activation="swiglu", shared_width=None,
                 **kwargs):
        super().__init__(**kwargs)
        self.instances.add(self)
        held = len(experts_held)
        self._attrs = dict(k=top_k, experts_held=tuple(experts_held),
                           scaling=float(scaling), activation=activation)
        self._gamma = gamma
        shared = width if shared_width is None else shared_width
        halves = 2 if activation == "swiglu" else 1
        get = self.params.get
        self.gate = get("gate", shape=(hidden, router_experts))
        self.bias = get("bias", shape=(router_experts,), init="zeros",
                        grad_req="null", differentiable=False)
        self.w_in = get("w_in", shape=(held, hidden, halves * width))
        self.w_out = get("w_out", shape=(held, width, hidden))
        if shared:
            self.shared_in = get("shared_in", shape=(hidden, halves * shared))
            self.shared_out = get("shared_out", shape=(shared, hidden))
        self.load = get("load", shape=(router_experts,), init="zeros",
                        grad_req="null", differentiable=False)
        self.tokens_here = get("tokens_here", shape=(1,), init="zeros",
                               grad_req="null", differentiable=False)

    def hybrid_forward(self, F, x, gate, bias, w_in, w_out, load,
                       tokens_here, **shared):
        from .... import autograd
        from ....parallel.moe import balance_bias_update
        y, new_load, here = F.contrib.dropless_moe(
            x, gate, bias, w_in, w_out,
            *(shared[k] for k in ("shared_in", "shared_out") if k in shared),
            **self._attrs)
        if autograd.is_training():
            with autograd.pause():
                load._rebind(new_load._data)
                tokens_here._rebind(here._data.reshape(1))
                bias._rebind(balance_bias_update(
                    bias._data, new_load._data, self._gamma))
        return y


class DecoderLayer(HybridBlock):
    def __init__(self, cfg, dense, **kwargs):
        super().__init__(**kwargs)
        hidden, eps = cfg["hidden_size"], cfg["rms_norm_eps"]
        with self.name_scope():
            self.attn_norm = RMSNorm(hidden, eps)
            self.attn = MLA(hidden, cfg["num_attention_heads"],
                            cfg["q_lora_rank"], cfg["kv_lora_rank"],
                            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                            cfg["v_head_dim"], cfg["rope_theta"], eps)
            self.ffn_norm = RMSNorm(hidden, eps)
            self.ffn = SwiGLU(hidden, cfg["intermediate_size"]) if dense \
                else DroplessMoE(hidden, cfg["moe_intermediate_size"],
                                 cfg["router_experts"], cfg["experts_held"],
                                 cfg["num_experts_per_tok"],
                                 cfg["routed_scaling_factor"],
                                 cfg["bias_update_speed"])

    def hybrid_forward(self, F, x):
        h = x + self.attn(self.attn_norm(x))
        return h + self.ffn(self.ffn_norm(h))


class GLM4MoELite(HybridBlock):
    """tokens (B, T + 1) int -> (logits (B, T, V), MTP logits (B, T, V)).

    The model reads the first T tokens; the MTP module also reads tokens
    1..T (its ``t_{i+1}``), so position i of the first output predicts
    token i + 1 and position i of the second token i + 2. With
    ``num_nextn_predict_layers=0`` the input is (B, T) and the second
    output is absent. ``remat``: every layer is recomputed in the backward
    pass and only its input (and its attention output) is kept.
    """

    def __init__(self, remat=False, **cfg):
        super().__init__()
        self._cfg = cfg = dict(_DEFAULTS, **cfg)
        cfg.setdefault("router_experts", cfg["n_routed_experts"])
        cfg.setdefault("experts_held", tuple(range(cfg["n_routed_experts"])))
        check(len(cfg["experts_held"]) == cfg["n_routed_experts"],
              "experts_held names as many experts as n_routed_experts holds")
        self._remat = remat
        hidden, vocab = cfg["hidden_size"], cfg["vocab_size"]
        dense = cfg["first_k_dense_replace"]
        self._mtp = cfg["num_nextn_predict_layers"]
        check(self._mtp in (0, 1), "one MTP module at most")
        with self.name_scope():
            self.embed = nn.Embedding(vocab, hidden)
            self.layers = nn.HybridSequential()
            for i in range(cfg["num_hidden_layers"]):
                self.layers.add(DecoderLayer(cfg, dense=i < dense))
            self.norm = RMSNorm(hidden, cfg["rms_norm_eps"])
            self.head = nn.Dense(vocab, use_bias=False, flatten=False,
                                 in_units=hidden)
            if self._mtp:
                self.mtp_hnorm = RMSNorm(hidden, cfg["rms_norm_eps"])
                self.mtp_enorm = RMSNorm(hidden, cfg["rms_norm_eps"])
                self.mtp_proj = nn.Dense(hidden, use_bias=False,
                                         flatten=False, in_units=2 * hidden)
                self.mtp_layer = DecoderLayer(cfg, dense=False)

    def _layer(self, layer, x):
        return layer.remat_call(x) if self._remat else layer(x)

    def hybrid_forward(self, F, tokens):
        import jax
        t = tokens.shape[1] - self._mtp
        emb = self.embed(tokens)
        x = F.slice_axis(emb, axis=1, begin=0, end=t)
        for layer in self.layers:
            x = self._layer(layer, x)
        with jax.named_scope("mx.lm_head"):
            logits = self.head(self.norm(x))
        if not self._mtp:
            return logits
        with jax.named_scope("mx.mtp"):
            nxt = F.slice_axis(emb, axis=1, begin=1, end=t + 1)
            h = self.mtp_proj(F.concat(self.mtp_hnorm(x),
                                       self.mtp_enorm(nxt), dim=2))
            h = self._layer(self.mtp_layer, h)
        with jax.named_scope("mx.lm_head"):
            return logits, self.head(self.norm(h))


class LMLoss(Loss):
    """CE(main, label[..., 0]) + weight * CE(MTP, label[..., 1]), each the
    mean over positions of a float32 log-softmax; label is (B, T, 2) (or
    (B, T) for a model without an MTP module). Returns (B,)."""

    def __init__(self, mtp_weight=0.3, batch_axis=0, **kwargs):
        super().__init__(mtp_weight, batch_axis, **kwargs)

    @staticmethod
    def _ce(F, logits, label):
        logp = F.log_softmax(logits.astype("float32"), axis=-1)
        return -F.mean(F.pick(logp, label, axis=-1), axis=0, exclude=True)

    def hybrid_forward(self, F, pred, label):
        if not isinstance(pred, (list, tuple)):
            return self._ce(F, pred, label)
        main, mtp = pred
        first = F.slice_axis(label, axis=2, begin=0, end=1).reshape((0, -1))
        second = F.slice_axis(label, axis=2, begin=1, end=2).reshape((0, -1))
        return self._ce(F, main, first) \
            + self._weight * self._ce(F, mtp, second)


# GLM-4.7-Flash's published config.json, the keys that shape the model
_DEFAULTS = dict(
    hidden_size=2048, intermediate_size=10240, moe_intermediate_size=1536,
    num_hidden_layers=47, first_k_dense_replace=1, num_attention_heads=20,
    q_lora_rank=768, kv_lora_rank=512, qk_nope_head_dim=192,
    qk_rope_head_dim=64, v_head_dim=256, n_routed_experts=64,
    num_experts_per_tok=4, routed_scaling_factor=1.8,
    num_nextn_predict_layers=1, rms_norm_eps=1e-5, rope_theta=1000000.0,
    vocab_size=154880, bias_update_speed=0.001)
# what a configuration file may hand the builder: the sizes above, and which
# of the router's experts this chip holds
CONFIG_KEYS = tuple(_DEFAULTS) + ("router_experts", "experts_held")


def glm4_moe_lite(**kwargs):
    """GLM-4.7-Flash by default; every size is a keyword argument."""
    return GLM4MoELite(**kwargs)
