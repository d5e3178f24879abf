"""Mixture-of-Experts with expert parallelism (the 'ep' mesh axis).

The reference has no MoE (SURVEY.md §2.3: expert parallelism **Absent**);
this is a capability the TPU-native design adds as a first-class
parallelism strategy. Design is the dense Switch/GShard formulation that
GSPMD shards well:

- expert weights are stacked on a leading E axis and sharded
  ``P('ep', ...)`` — each ep slice owns E/ep experts,
- token dispatch/combine are einsums against a (tokens, E, capacity)
  one-hot dispatch tensor, so the cross-expert exchange lowers to the
  all-to-all-style collectives GSPMD inserts on the ep axis,
- top-1 (Switch) or top-2 (GShard) routing with capacity dropping and the
  standard load-balancing auxiliary loss.

Everything is static-shaped (capacity fixes the per-expert token count) so
the whole layer stays MXU/XLA friendly — no dynamic gather loops.

``moe_ffn`` pads every expert to a capacity and DROPS the tokens beyond it.
``dropless_moe_ffn`` is the layer of today's sparse language models: it
routes every token (sigmoid scores, top-k on score + bias, no auxiliary
loss), is told WHICH experts it holds, and computes those experts' part of
the result by a sorted dispatch and a grouped product. Both draw their
router and their expert weights from ``init_router`` / ``init_expert_ffn``.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

__all__ = ["init_router", "init_expert_ffn", "init_moe_params",
           "moe_param_specs", "moe_ffn", "init_dropless_moe_params",
           "route_topk", "dropless_moe_ffn", "grouped_matmul",
           "balance_bias_update"]

_INIT_SCALE = 0.02


def init_router(key, d_model: int, n_experts: int):
    """Router weights (d_model, n_experts), float32 whatever the experts'
    dtype: the scores decide a discrete choice."""
    import jax
    import jax.numpy as jnp
    return (jax.random.normal(key, (d_model, n_experts)) * _INIT_SCALE
            ).astype(jnp.float32)


def init_expert_ffn(key, n_experts: int, d_model: int, d_ff: int,
                    gated: bool = False, dtype=None) -> Dict[str, Any]:
    """Stacked expert FFN weights, leading axis = expert: ``w_in``
    (E, d_model, d_ff), or (E, d_model, 2 * d_ff) for a gated (SwiGLU)
    expert whose gate and up projections are one product, and ``w_out``
    (E, d_ff, d_model)."""
    import jax
    import jax.numpy as jnp
    dt = dtype or jnp.float32
    k_in, k_out = jax.random.split(key)
    width = 2 * d_ff if gated else d_ff
    return {
        "w_in": (jax.random.normal(k_in, (n_experts, d_model, width))
                 * _INIT_SCALE).astype(dt),
        "w_out": (jax.random.normal(k_out, (n_experts, d_ff, d_model))
                  * _INIT_SCALE).astype(dt),
    }


def init_moe_params(key, d_model: int, d_ff: int, n_experts: int,
                    dtype=None) -> Dict[str, Any]:
    """``moe_ffn``'s parameters: router, stacked expert weights, biases."""
    import jax
    import jax.numpy as jnp
    dt = dtype or jnp.float32
    k_gate, k_ffn = jax.random.split(key)
    return {
        "gate": init_router(k_gate, d_model, n_experts),
        **init_expert_ffn(k_ffn, n_experts, d_model, d_ff, dtype=dt),
        "b_in": jnp.zeros((n_experts, d_ff), dt),
        "b_out": jnp.zeros((n_experts, d_model), dt),
    }


def moe_param_specs(mesh) -> Dict[str, Any]:
    """ep-sharded expert stacking; gate replicated. tp (if present) shards
    the expert hidden dim, composing ep x tp."""
    from jax.sharding import PartitionSpec as P
    names = mesh.axis_names if mesh is not None else ()
    ep = "ep" if "ep" in names else None
    tp = "tp" if "tp" in names else None
    return {
        "gate": P(),
        "w_in": P(ep, None, tp),
        "b_in": P(ep, tp),
        "w_out": P(ep, tp, None),
        "b_out": P(ep, None),
    }


def moe_ffn(x, params: Dict[str, Any], n_experts: int,
            capacity_factor: float = 1.25, k: int = 1,
            act=None) -> Tuple[Any, Any]:
    """Apply the expert-parallel FFN. Capacity routing: each expert takes
    at most ``ceil(N * capacity_factor * k / E)`` tokens and the tokens
    beyond that are DROPPED (their output is zero). In no benchmark cell;
    the language models' layer is ``dropless_moe_ffn``.

    x: (B, T, D) -> (out (B, T, D), aux_loss scalar).
    aux_loss is the Switch load-balance loss (mean over tokens of
    fraction_routed * mean_gate_prob, scaled by E); add it to the task
    loss with a small coefficient (~1e-2).
    """
    import jax
    import jax.numpy as jnp
    act = act or jax.nn.gelu
    b, t, d = x.shape
    n = b * t
    e = n_experts
    cap = max(1, int(math.ceil(n * capacity_factor * k / e)))

    xf = x.reshape(n, d)
    scores = xf.astype(jnp.float32) @ params["gate"]          # (N, E)
    probs = jax.nn.softmax(scores, axis=-1)

    dispatch = jnp.zeros((n, e), jnp.float32)
    combine_w = jnp.zeros((n, e), jnp.float32)
    remaining = probs
    for _ in range(k):
        idx = jnp.argmax(remaining, axis=-1)                  # (N,)
        oh = jax.nn.one_hot(idx, e, dtype=jnp.float32)        # (N, E)
        combine_w = combine_w + remaining * oh
        dispatch = dispatch + oh
        remaining = remaining * (1.0 - oh)

    # position of each token within its expert's buffer (per expert-slot)
    pos = jnp.cumsum(dispatch, axis=0) * dispatch             # (N, E), 1-based
    keep = (pos > 0) & (pos <= cap)
    pos0 = jnp.clip(pos - 1.0, 0, cap - 1).astype(jnp.int32)
    slot = jax.nn.one_hot(pos0, cap, dtype=jnp.float32)       # (N, E, C)
    disp = slot * keep[..., None]                             # (N, E, C)

    # load-balance aux loss (Switch eq. 4): E * sum_e f_e * P_e
    frac = jnp.mean(dispatch, axis=0)                         # (E,)
    mean_prob = jnp.mean(probs, axis=0)                       # (E,)
    aux = e * jnp.sum(frac / max(k, 1) * mean_prob)

    # dispatch -> expert compute -> combine (all einsums; ep collectives
    # are inserted by GSPMD from the P('ep',...) weight shardings)
    xe = jnp.einsum("nec,nd->ecd", disp.astype(x.dtype), xf)  # (E, C, D)
    h = act(jnp.einsum("ecd,edf->ecf", xe, params["w_in"])
            + params["b_in"][:, None, :])
    ye = jnp.einsum("ecf,efd->ecd", h, params["w_out"]) \
        + params["b_out"][:, None, :]                         # (E, C, D)
    comb = (disp * combine_w[..., None]).astype(x.dtype)      # (N, E, C)
    out = jnp.einsum("nec,ecd->nd", comb, ye)                 # (N, D)
    return out.reshape(b, t, d), aux


# ---------------------------------------------------------------------------
# The dropless layer
# ---------------------------------------------------------------------------

_TILE = 256  # rows of one product of the grouped matmul


def init_dropless_moe_params(key, d_model: int, d_ff: int, n_experts: int,
                             experts_held=None, dtype=None,
                             activation: str = "swiglu",
                             shared_ff: Optional[int] = None
                             ) -> Dict[str, Any]:
    """``dropless_moe_ffn``'s parameters: a router over all ``n_experts``, a
    selection bias (no gradient), expert weights for the experts held (all
    of them by default; gated for ``swiglu``, one product in for ``relu2``)
    and one shared expert of width ``shared_ff`` (``d_ff`` by default)."""
    import jax
    import jax.numpy as jnp
    held = n_experts if experts_held is None else len(experts_held)
    gated = activation == "swiglu"
    k_gate, k_ffn, k_shared = jax.random.split(key, 3)
    shared = init_expert_ffn(k_shared, 1, d_model, shared_ff or d_ff, gated,
                             dtype)
    return {
        "gate": init_router(k_gate, d_model, n_experts),
        "bias": jnp.zeros((n_experts,), jnp.float32),
        **init_expert_ffn(k_ffn, held, d_model, d_ff, gated, dtype),
        "shared_in": shared["w_in"][0], "shared_out": shared["w_out"][0],
    }


def route_topk(x, gate, bias, k: int, scaling: float = 1.0):
    """Sigmoid routing without an auxiliary loss (``noaux_tc``, one group).

    ``s = sigmoid(x @ gate)`` over ALL experts; the ``k`` experts of a token
    are the top-k of ``s + bias``; their weights are ``s`` alone (the bias
    only selects), normalised over the k and scaled. x: (N, D) ->
    (chosen (N, k) int32, weights (N, k) float32). Gradients reach ``gate``
    through the weights; ``bias`` gets none."""
    import jax
    import jax.numpy as jnp
    s = jax.nn.sigmoid(jnp.dot(x, gate.astype(x.dtype),
                               preferred_element_type=jnp.float32))
    _, chosen = jax.lax.top_k(s + jax.lax.stop_gradient(bias), k)
    w = jnp.take_along_axis(s, chosen, axis=-1)
    w = scaling * w / jnp.sum(w, axis=-1, keepdims=True)
    return chosen, w


def balance_bias_update(bias, load, gamma: float):
    """The selection bias after a step: up by ``gamma`` for an expert that
    got fewer tokens than the mean, down for one that got more."""
    import jax.numpy as jnp
    load = load.astype(jnp.float32)
    return bias + gamma * jnp.sign(jnp.mean(load) - load)


def _grouped_product(x, w, tile_group, n_active, transpose_w: bool):
    """out[rows of tile i] = x[rows of tile i] @ w[tile_group[i]] for the
    first ``n_active`` tiles, zero below. A loop with a traced trip count:
    only the tiles that hold tokens cost anything."""
    import jax
    import jax.numpy as jnp
    tile = x.shape[0] // tile_group.shape[0]
    n_out = w.shape[1] if transpose_w else w.shape[2]
    dims = (((1,), (1,)), ((), ())) if transpose_w \
        else (((1,), (0,)), ((), ()))

    def body(i, out):
        rows = jax.lax.dynamic_slice_in_dim(x, i * tile, tile)
        wi = jax.lax.dynamic_index_in_dim(w, tile_group[i], keepdims=False)
        y = jax.lax.dot_general(rows, wi, dims,
                                preferred_element_type=jnp.float32)
        return jax.lax.dynamic_update_slice_in_dim(
            out, y.astype(out.dtype), i * tile, 0)

    return jax.lax.fori_loop(
        0, n_active, body, jnp.zeros((x.shape[0], n_out), x.dtype))


def grouped_matmul(x, w, tile_group, n_active):
    """Grouped product over the experts held. ``x`` (R, K) holds the
    dispatched tokens sorted by expert, every expert's rows padded to whole
    tiles of ``R / len(tile_group)`` rows; ``w`` is (G, K, N);
    ``tile_group[i]`` is the expert of tile i and ``n_active`` the number of
    tiles in use. Returns (R, N), zero in the unused tiles. Differentiable
    in ``x`` and ``w``; the weight gradient accumulates in float32."""
    return _grouped_matmul_op()(x, w, tile_group, n_active)


@functools.lru_cache(maxsize=None)
def _grouped_matmul_op():
    import jax
    import jax.numpy as jnp

    @jax.custom_vjp
    def op(x, w, tile_group, n_active):
        return _grouped_product(x, w, tile_group, n_active, False)

    def fwd(x, w, tile_group, n_active):
        return op(x, w, tile_group, n_active), (x, w, tile_group, n_active)

    def bwd(res, dy):
        x, w, tile_group, n_active = res
        tile = x.shape[0] // tile_group.shape[0]
        dx = _grouped_product(dy, w, tile_group, n_active, True)

        def body(i, dw):
            rows = jax.lax.dynamic_slice_in_dim(x, i * tile, tile)
            dyi = jax.lax.dynamic_slice_in_dim(dy, i * tile, tile)
            g = jax.lax.dot_general(rows, dyi, (((0,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            return dw.at[tile_group[i]].add(g)

        dw = jax.lax.fori_loop(0, n_active, body,
                               jnp.zeros(w.shape, jnp.float32))
        return dx.astype(x.dtype), dw.astype(w.dtype), None, None

    op.defvjp(fwd, bwd)
    return op


def _dispatch_plan(chosen, experts_held, n_experts: int, tile: int):
    """Where every (token, choice) pair goes. Rows are sorted by held
    expert and each expert's rows start on a tile boundary; a pair whose
    expert is not held here has no row. Returns (row of each pair (N, k),
    -1 where not held; token of each row (R,), N where the row is padding;
    tile_group; n_active; per-expert load over ALL experts)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    n, k = chosen.shape
    g = len(experts_held)
    local = np.full((n_experts,), g, np.int32)        # g: "not held here"
    local[np.asarray(experts_held)] = np.arange(g, dtype=np.int32)
    flat = chosen.reshape(-1)
    group = jnp.asarray(local)[flat]                                 # (N*k,)
    onehot = (group[:, None] == jnp.arange(g)[None, :]).astype(jnp.int32)
    rank = jnp.sum((jnp.cumsum(onehot, axis=0) - onehot) * onehot, axis=1)
    count = jnp.sum(onehot, axis=0)                                   # (g,)
    tiles = (count + tile - 1) // tile
    first_tile = jnp.cumsum(tiles) - tiles
    n_tiles = (n * k) // tile + g        # whole tiles of every expert fit
    held = group < g
    row = jnp.where(held, first_tile[jnp.minimum(group, g - 1)] * tile
                    + rank, -1)
    token = jnp.full((n_tiles * tile,), n, jnp.int32).at[
        jnp.where(held, row, n_tiles * tile)].set(
            jnp.arange(n * k, dtype=jnp.int32) // k, mode="drop")
    tile_group = jnp.clip(
        jnp.searchsorted(jnp.cumsum(tiles), jnp.arange(n_tiles),
                         side="right"), 0, g - 1).astype(jnp.int32)
    load = jnp.sum(flat[:, None] == jnp.arange(n_experts)[None, :], axis=0)
    return (row.reshape(n, k), token, tile_group,
            jnp.sum(tiles).astype(jnp.int32), load)


def _gather_rows(x, index, valid):
    import jax.numpy as jnp
    rows = jnp.take(x, jnp.where(valid, index, 0), axis=0)
    return jnp.where(valid[..., None], rows, jnp.zeros((), x.dtype))


def _dispatch(x, row, token):
    """xs[r] = x[token[r]] (zero in padding rows). Its transpose is a
    gather too: dx[n] = sum over n's choices of dxs[row[n, choice]], so
    neither direction scatters."""
    return _dispatch_ops()[0](x, row, token)


def _combine(ys, w, row, token):
    """out[n] = sum over n's choices of w[n, choice] * ys[row[n, choice]];
    a choice with no row here adds nothing."""
    return _dispatch_ops()[1](ys, w, row, token)


@functools.lru_cache(maxsize=None)
def _dispatch_ops():
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32

    @jax.custom_vjp
    def dispatch(x, row, token):
        return _gather_rows(x, token, token < x.shape[0])

    def dispatch_bwd(row, dxs):
        return jnp.sum(_gather_rows(dxs, row, row >= 0), axis=1), None, None

    dispatch.defvjp(lambda x, row, token: (dispatch(x, row, token), row),
                    dispatch_bwd)

    @jax.custom_vjp
    def combine(ys, w, row, token):
        picked = _gather_rows(ys, row, row >= 0)                 # (N, k, D)
        return jnp.einsum("nkd,nk->nd", picked, w.astype(ys.dtype),
                          preferred_element_type=f32).astype(ys.dtype)

    def combine_bwd(res, dout):
        ys, w, row, token = res
        picked = _gather_rows(ys, row, row >= 0)
        dw = jnp.einsum("nkd,nd->nk", picked, dout,
                        preferred_element_type=f32).astype(w.dtype)
        # weight of each row: its pair's; rows are unique per pair
        w_row = jnp.zeros((ys.shape[0],), f32).at[
            jnp.where(row >= 0, row, ys.shape[0]).reshape(-1)].set(
                w.reshape(-1).astype(f32), mode="drop")
        dys = _gather_rows(dout, token, token < row.shape[0]) \
            * w_row[:, None].astype(dout.dtype)
        return dys.astype(ys.dtype), dw, None, None

    combine.defvjp(lambda ys, w, row, token:
                   (combine(ys, w, row, token), (ys, w, row, token)),
                   combine_bwd)
    return dispatch, combine


def dropless_moe_ffn(x, params: Dict[str, Any], k: int,
                     experts_held=None, scaling: float = 1.0,
                     tile: int = _TILE, activation: str = "swiglu"):
    """The expert layer of a sparse language model, for the experts held.

    ``y = sum_e w_e E_e(x) + E_shared(x)``, the sum over the token's top-k
    experts THAT ARE HELD HERE (``experts_held``: their ids among the
    router's; None: all, the whole layer). ``E`` is a SwiGLU (``w_in`` holds
    gate and up, 2F wide) or, with ``activation="relu2"``, ``relu(x w_in)^2
    w_out``; the shared expert's width is its weights' own. Routing is over
    all of the router's experts and drops nothing: tokens are sorted by
    expert into whole tiles and a grouped product runs over the tiles in
    use. What an absent expert would add is left out, and nothing stands in
    for the chips that hold it or for their exchange.

    x: (B, T, D) -> (y (B, T, D), stats) with ``stats["load"]`` the tokens
    routed to each of ALL experts (int32) and ``stats["tokens_here"]`` the
    (token, expert) pairs computed here.
    """
    import jax
    import jax.numpy as jnp
    from ..ops.lm_ops import FFN_ACTIVATIONS
    act, ffn = FFN_ACTIVATIONS[activation]
    b, t, d = x.shape
    n = b * t
    n_experts = params["gate"].shape[1]
    held = tuple(range(n_experts)) if experts_held is None \
        else tuple(int(e) for e in experts_held)
    tile = min(tile, n * k)
    xf = x.reshape(n, d)
    with jax.named_scope("mx.moe.route"):
        chosen, w = route_topk(xf, params["gate"], params["bias"], k, scaling)
        row, token, tile_group, n_active, load = _dispatch_plan(
            chosen, held, n_experts, tile)
    with jax.named_scope("mx.moe.experts"):
        xs = _dispatch(xf, row, token)
        h = act(grouped_matmul(xs, params["w_in"], tile_group, n_active))
        ys = grouped_matmul(h, params["w_out"], tile_group, n_active)
        y = _combine(ys, w, row, token)
        shared = ffn(xf, params["shared_in"], params["shared_out"])
    stats = {"load": load.astype(jnp.int32),
             "tokens_here": jnp.sum(row >= 0).astype(jnp.int32)}
    return (y + shared).reshape(b, t, d), stats
