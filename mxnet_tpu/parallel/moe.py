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
the result in one loop over the tiles of rows that were routed here. What
is sized by what: the plan's index arrays (the pair of each row, the expert
of each tile) have a place for all N k (token, choice) pairs, so any routing
fits; everything D or F wide has N rows (the input, the float32 accumulator
of the result, their gradients) or the rows of one tile (the gathered
tokens, the hidden activations, the expert's output), made and used inside
the loop, so the routed path costs what was routed here. A tile adds its
rows into the accumulator with the kernel ``mx_moe_combine``: one grid step
a tile, each row's copy in and back by DMA, all of the tile's in flight at
once, into an accumulator kept (N, 1, D) for it; where D is not a multiple
of 128, with XLA's scatter-add. Both layers draw their router and their
expert weights from ``init_router`` / ``init_expert_ffn``.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

__all__ = ["init_router", "init_expert_ffn", "init_moe_params",
           "moe_param_specs", "moe_ffn", "init_dropless_moe_params",
           "route_topk", "dropless_moe_ffn", "balance_bias_update"]

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

# Rows of one pass of the routed experts' loop. Padding costs rows of one tile
# an expert, and a tile of the backward reads and writes the expert's two
# float32 weight gradients whole: at both language models' widths 512 beats
# 256 and 1024 on a v5e, under even routing and with every token on one
# expert (PERF.md section 6, PR 34).
_TILE = 512


def init_dropless_moe_params(key, d_model: int, d_ff: int, n_experts: int,
                             experts_held=None, dtype=None,
                             activation: str = "swiglu",
                             shared_ff: Optional[int] = None
                             ) -> Dict[str, Any]:
    """``dropless_moe_ffn``'s parameters: a router over all ``n_experts``, a
    selection bias (no gradient), expert weights for the experts held (all
    of them by default; gated for ``swiglu``, one product in for ``relu2``)
    and one shared expert of width ``shared_ff`` (``d_ff`` by default; 0:
    none, and no ``shared_in``/``shared_out``)."""
    import jax
    import jax.numpy as jnp
    held = n_experts if experts_held is None else len(experts_held)
    gated = activation == "swiglu"
    k_gate, k_ffn, k_shared = jax.random.split(key, 3)
    params = {
        "gate": init_router(k_gate, d_model, n_experts),
        "bias": jnp.zeros((n_experts,), jnp.float32),
        **init_expert_ffn(k_ffn, held, d_model, d_ff, gated, dtype),
    }
    width = d_ff if shared_ff is None else shared_ff
    if width:
        shared = init_expert_ffn(k_shared, 1, d_model, width, gated, dtype)
        params.update(shared_in=shared["w_in"][0],
                      shared_out=shared["w_out"][0])
    return params


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
    # s at the chosen experts, as a masked sum of one term: a gather of N k
    # scalars and the scatter that transposes it cost the chip 0.4 ms each
    picked = chosen[..., None] == jnp.arange(s.shape[-1])[None, None, :]
    w = jnp.sum(jnp.where(picked, s[:, None, :], 0.0), axis=-1)
    w = scaling * w / jnp.sum(w, axis=-1, keepdims=True)
    return chosen, w


def balance_bias_update(bias, load, gamma: float):
    """The selection bias after a step: up by ``gamma`` for an expert that
    got fewer tokens than the mean, down for one that got more."""
    import jax.numpy as jnp
    load = load.astype(jnp.float32)
    return bias + gamma * jnp.sign(jnp.mean(load) - load)


def _take_rows(x, index):
    """x[index], zero where the index is past the end (a padding row)."""
    import jax.numpy as jnp
    return jnp.take(x, index, axis=0, mode="fill", fill_value=0)


def _accumulator(n: int, d: int, dtype=None):
    """Zeros (float32 by default) for ``_add_rows`` to add N rows D wide
    into, which reshape to the (N, D) result: (N, 1, D) where the kernel
    ``mx_moe_combine`` adds the rows (on the TPU a row of it is one run of
    memory), (N, D) where XLA's scatter does."""
    import jax.numpy as jnp
    from ..ops.pallas_kernels import moe_combine_fits, moe_zeros
    dtype = dtype or jnp.float32
    if moe_combine_fits(d, dtype):
        return moe_zeros(n, d)
    return jnp.zeros((n, d), dtype)


def _add_rows(acc, index, rows):
    """acc[index] += rows, the combine of a tile of the routed loop, into an
    accumulator of ``_accumulator``; an index past the end (a padding row)
    is dropped. One of (N, 1, D) takes the kernel ``mx_moe_combine``: one
    grid step a tile, every row's copy in and back by DMA in flight at once
    (``ops/pallas_kernels.py:moe_combine``). One of (N, D), where D is not a
    multiple of 128 or the rows are not float32, takes XLA's scatter, which
    on a v5e takes 0.4 us for a row of 2,688 float32 and 1.0 us once it is
    told that the indices are sorted and unique (PERF.md section 6): so it
    is not told. Under the scope ``mx.moe.combine`` either way."""
    import jax
    from ..ops.pallas_kernels import moe_combine
    with jax.named_scope("mx.moe.combine"):
        if acc.ndim == 3:
            return moe_combine(acc, index, rows)
        return acc.at[index].add(rows, mode="drop")


def _dot(a, b, contract):
    import jax
    import jax.numpy as jnp
    return jax.lax.dot_general(a, b, ((contract[:1], contract[1:]), ((), ())),
                               preferred_element_type=jnp.float32)


@functools.lru_cache(maxsize=None)
def _routed_experts_op(act, tile: int):
    """The routed path as ONE differentiable op, ``out[n] = sum over n's
    pairs held here of w[pair] * E_expert(x[n])``: a loop with a traced
    trip count over the tiles in use, in each direction. Nothing D or F
    wide is larger than (N, D) or one tile, and the backward keeps only the
    op's own inputs: it makes a tile's rows, pre-activation and ``h``
    again."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32

    def rows_of(i, w, pair, tile_group):
        """Tile i: (its pairs, past the end in a padding row; their tokens,
        likewise; their routing weights, 0 in a padding row; its expert)."""
        n, k = w.shape
        pairs = jax.lax.dynamic_slice_in_dim(pair, i * tile, tile)
        tokens = jnp.where(pairs < n * k, pairs // k, n)
        scale = jnp.take(w.reshape(-1), pairs, mode="fill", fill_value=0)
        return pairs, tokens, scale[:, None], tile_group[i]

    def hidden(x, w_in, tokens, group):
        xs = _take_rows(x, tokens)
        return xs, _dot(xs, w_in[group], (1, 0)).astype(x.dtype)

    @jax.custom_vjp
    def op(x, w_in, w_out, w, pair, tile_group, n_active):
        def body(i, out):
            _, tokens, scale, group = rows_of(i, w, pair, tile_group)
            h = act(hidden(x, w_in, tokens, group)[1])
            return _add_rows(out, tokens,
                             scale * _dot(h, w_out[group], (1, 0)))

        acc = jax.lax.fori_loop(0, n_active, body, _accumulator(*x.shape))
        return acc.reshape(x.shape).astype(x.dtype)

    def fwd(*args):
        return op(*args), args

    def bwd(res, dout):
        x, w_in, w_out, w, pair, tile_group, n_active = res

        def body(i, carry):
            dx, dw_in, dw_out, dw = carry
            pairs, tokens, scale, group = rows_of(i, w, pair, tile_group)
            xs, pre = hidden(x, w_in, tokens, group)
            h, act_vjp = jax.vjp(act, pre)
            dys = _take_rows(dout, tokens)
            # d out / d (h w_out) before the routing weight scales it: with
            # h it is the weight's own gradient, sum_d y dout, and y is not
            # made again
            dh = _dot(dys, w_out[group], (1, 1))
            dw = dw.at[pairs].set(jnp.sum(h.astype(f32) * dh, axis=1),
                                  mode="drop")
            dy = (scale * dys.astype(f32)).astype(x.dtype)
            dw_out = dw_out.at[group].add(_dot(h, dy, (0, 0)))
            dpre, = act_vjp((scale * dh).astype(h.dtype))
            dw_in = dw_in.at[group].add(_dot(xs, dpre, (0, 0)))
            dx = _add_rows(dx, tokens, _dot(dpre, w_in[group], (1, 1)))
            return dx, dw_in, dw_out, dw

        dx, dw_in, dw_out, dw = jax.lax.fori_loop(
            0, n_active, body,
            (_accumulator(*x.shape), jnp.zeros(w_in.shape, f32),
             jnp.zeros(w_out.shape, f32), jnp.zeros((w.size,), f32)))
        return (dx.reshape(x.shape).astype(x.dtype),
                dw_in.astype(w_in.dtype), dw_out.astype(w_out.dtype),
                dw.reshape(w.shape).astype(w.dtype), None, None, None)

    op.defvjp(fwd, bwd)
    return op


def _dispatch_plan(chosen, experts_held, n_experts: int, tile: int):
    """Where every (token, choice) pair goes. Rows are sorted by held
    expert, an expert's rows in token order and starting on a tile
    boundary; a pair whose expert is not held here has no row. Index arrays
    only, sized for all N k pairs (R = N k + g tiles' worth of rows: whole
    tiles of every expert fit whatever the routing). Returns (pair of each
    row (R,), N k where the row is padding; expert of each tile; tiles in
    use; load over ALL the router's experts; pairs with a row here)."""
    import jax.numpy as jnp
    n, k = chosen.shape
    g = len(experts_held)
    flat = chosen.reshape(-1)
    onehot = (flat[:, None] == jnp.asarray(experts_held, flat.dtype)[None, :]
              ).astype(jnp.int32)                                 # (N k, g)
    rank = jnp.sum((jnp.cumsum(onehot, axis=0) - onehot) * onehot, axis=1)
    count = jnp.sum(onehot, axis=0)                                   # (g,)
    tiles = (count + tile - 1) // tile
    last_tile = jnp.cumsum(tiles)
    n_tiles = (n * k) // tile + g
    first_row = jnp.sum(onehot * ((last_tile - tiles) * tile)[None, :], axis=1)
    row = jnp.where(jnp.sum(onehot, axis=1) > 0, first_row + rank,
                    n_tiles * tile)
    pair = jnp.full((n_tiles * tile,), n * k, jnp.int32).at[row].set(
        jnp.arange(n * k, dtype=jnp.int32), mode="drop")
    tile_group = jnp.minimum(
        jnp.sum(jnp.arange(n_tiles)[:, None] >= last_tile[None, :], axis=1),
        g - 1).astype(jnp.int32)
    load = jnp.sum(flat[:, None] == jnp.arange(n_experts)[None, :], axis=0)
    return (pair, tile_group, last_tile[-1].astype(jnp.int32), load,
            jnp.sum(count))


def dropless_moe_ffn(x, params: Dict[str, Any], k: int,
                     experts_held=None, scaling: float = 1.0,
                     tile: int = _TILE, activation: str = "swiglu"):
    """The expert layer of a sparse language model, for the experts held.

    ``y = sum_e w_e E_e(x) + E_shared(x)``, the sum over the token's top-k
    experts THAT ARE HELD HERE (``experts_held``: their ids among the
    router's, each once; None: all, the whole layer). ``E`` is a SwiGLU
    (``w_in`` holds gate and up, 2F wide) or, with ``activation="relu2"``,
    ``relu(x w_in)^2 w_out``; the shared expert's width is its weights' own,
    and a layer whose ``params`` hold no ``shared_in`` has none.
    Routing is over all of the router's experts and drops nothing, whatever
    it sends here: the pairs held here are sorted by expert into whole tiles
    of ``tile`` rows, and ONE loop with a traced trip count runs over the
    tiles in use (one more in the backward pass, which makes a tile's rows
    and activations again from the layer's inputs). A tile gathers its
    tokens from ``x``, runs its expert's two products (operands in ``x``'s
    dtype, float32 accumulation, the pre-activation rounded to ``x``'s
    dtype) and adds its rows, scaled by their routing weights, into an
    (N, D) float32 accumulator (``_add_rows``: by DMA, the kernel
    ``mx_moe_combine``, where D is a multiple of 128). So the index arrays
    of the plan are sized for all N k pairs, and every D- or F-wide array
    by N or by one tile: the layer costs what was routed here. What an
    absent expert would add is left out, and nothing stands in for the chips
    that hold it or for their exchange.

    x: (B, T, D) -> (y (B, T, D), stats) with ``stats["load"]`` the tokens
    routed to each of ALL experts (int32), ``stats["tokens_here"]`` the
    (token, expert) pairs computed here and ``stats["tiles_run"]`` the
    tiles the loop ran (each pass): under ``tokens_here / tile`` + the
    number of experts held.
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
        pair, tile_group, n_active, load, here = _dispatch_plan(
            chosen, held, n_experts, tile)
    with jax.named_scope("mx.moe.experts"):
        y = _routed_experts_op(act, tile)(
            xf, params["w_in"], params["w_out"], w, pair, tile_group,
            n_active)
        shared = ffn(xf, params["shared_in"], params["shared_out"]) \
            if "shared_in" in params else None
    stats = {"load": load.astype(jnp.int32),
             "tokens_here": here.astype(jnp.int32), "tiles_run": n_active}
    if shared is not None:
        y = y + shared
    return y.reshape(b, t, d), stats
