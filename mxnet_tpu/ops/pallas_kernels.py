"""Pallas TPU kernels for hot ops.

Where the reference hand-writes CUDA (src/operator/*.cu) or leans on cuDNN,
the TPU build leans on XLA — except where fusion across the softmax is
needed: attention. This module provides a fused attention kernel
(flash-style: per-query-block compute with K/V streamed through VMEM, the
(T, T) score matrix never hits HBM), and the forward of the chunked gated
delta rule (every chunk's matrices in VMEM, the recurrent state carried in
scratch across the grid).

On non-TPU backends the kernels run in interpret mode (correct, slow) so
the test suite exercises the same code path.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

from .registry import register

_BQ = 128  # query block (MXU-aligned)


def _interpret_for(x) -> bool:
    """Interpret-mode decision for a kernel whose operand is ``x``:
    compiled Pallas exactly when ``x`` lives on a TPU, interpret mode
    elsewhere (``mx.cpu()`` arrays inside a TPU process included). A tracer
    or host array has no device yet and lowers for the default backend.
    The platform decides alone: a kernel the TPU compiler refuses raises,
    it is never retried interpreted."""
    import jax
    if isinstance(x, jax.Array) and not isinstance(x, jax.core.Tracer):
        return next(iter(x.devices())).platform != "tpu"
    return jax.default_backend() != "tpu"


@functools.lru_cache(maxsize=None)
def _build_flash(t: int, d: int, causal: bool, scale: float, interpret: bool):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    bq = min(_BQ, t)

    def kernel(q_ref, k_ref, v_ref, o_ref):
        qi = pl.program_id(1)
        q = q_ref[0].astype(jnp.float32)          # (bq, d)
        k = k_ref[0].astype(jnp.float32)          # (t, d)
        v = v_ref[0].astype(jnp.float32)          # (t, d)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (bq, t)
        if causal:
            qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, t), 0)
            kpos = jax.lax.broadcasted_iota(jnp.int32, (bq, t), 1)
            logits = jnp.where(qpos >= kpos, logits, -1e30)
        m = jnp.max(logits, axis=-1, keepdims=True)
        p = jnp.exp(logits - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        o = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32) / l
        o_ref[0] = o.astype(o_ref.dtype)

    def call(q, k, v):
        bh = q.shape[0]
        grid = (bh, t // bq if t % bq == 0 else -(-t // bq))
        specs_kv = pl.BlockSpec((1, t, d), lambda i, j: (i, 0, 0))
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
            grid=grid,
            in_specs=[pl.BlockSpec((1, bq, d), lambda i, j: (i, j, 0)),
                      specs_kv, specs_kv],
            out_specs=pl.BlockSpec((1, bq, d), lambda i, j: (i, j, 0)),
            interpret=interpret,
        )(q, k, v)

    return call


def flash_attention(q, k, v, causal: bool = False, scale=None):
    """Fused attention. q,k,v: (B, T, H, D) -> (B, T, H, D).

    Forward is the Pallas kernel; backward recomputes through the reference
    jax formulation (jax.custom_vjp) — numerically identical, and XLA fuses
    the recompute well.
    """
    import jax
    import jax.numpy as jnp
    from ..parallel.ring_attention import attention as ref_attention

    b, t, h, d = q.shape
    sc = scale if scale is not None else 1.0 / math.sqrt(d)

    @jax.custom_vjp
    def _op(q, k, v):
        qt = q.transpose(0, 2, 1, 3).reshape(b * h, t, d)
        kt = k.transpose(0, 2, 1, 3).reshape(b * h, t, d)
        vt = v.transpose(0, 2, 1, 3).reshape(b * h, t, d)
        call = _build_flash(t, d, causal, sc, _interpret_for(q))
        o = call(qt, kt, vt)
        return o.reshape(b, h, t, d).transpose(0, 2, 1, 3)

    def fwd(q, k, v):
        return _op(q, k, v), (q, k, v)

    def bwd(res, g):
        q, k, v = res
        _, vjp = jax.vjp(
            lambda q_, k_, v_: ref_attention(q_, k_, v_, causal=causal,
                                             scale=sc), q, k, v)
        return vjp(g)

    _op.defvjp(fwd, bwd)
    return _op(q, k, v)


@register("_contrib_flash_attention", aliases=("flash_attention",))
def _flash_attention_op(q, k, v, causal=False, scale=None):
    return flash_attention(q, k, v, causal=causal, scale=scale)


# ---------------------------------------------------------------------------
# Blocked attention: both passes tiled over queries AND keys, so no
# (T, T) score matrix exists for a head in either direction and VMEM holds
# a few tiles of logits whatever T is. A grid step owns one block of queries
# (of keys in dK/dV) and a STRETCH of the other operand, several tiles
# fetched by one BlockSpec; the kernel body walks the stretch's tiles
# itself, as straight-line code with its running state (the online
# softmax's m, l and accumulator; the gradients' accumulators) in values,
# read from scratch and written back once a grid step. The products of tile
# j+1 that need nothing of tile j are written into the program BEFORE tile
# j's softmax: the matrix unit takes its work in program order, so that is
# what lets it run them while the vector unit is busy. (The forward walks
# four tiles a step so; the backward kernels are written the same way and
# handed one tile a step: ``_attention_walk``.) Causal: the stretch
# that holds the diagonal runs the tiles at or below it (one variant of the
# body for each place the diagonal can take in a stretch) and masks the one
# on it; stretches wholly above the diagonal do nothing, and the index map
# re-names the stretch already held, so they copy nothing either. The
# backward is two kernels: dK/dV walk the query tiles for one key block
# (logits transposed, so the saved log-sum-exp broadcasts as a row), dQ
# walks the key tiles for one query block. Query/key head size and value
# head size may differ (MLA: 256/256, with the 64 rope dimensions shared by
# all heads already concatenated). Keys and values may have fewer rows of BH
# than the queries (grouped KV heads): a query row reads its group's KV row
# through the index maps, and no KV head is ever copied out to the queries'
# count.
# ---------------------------------------------------------------------------

_MASKED = -0.7 * 3.0e38  # not -inf: exp(-inf - -inf) is NaN

# What one stretch of the walked operands (keys and values, or queries and
# output gradients) may hold in VMEM, both buffers of the pipeline counted;
# with the owned blocks, the scratch and a few tiles of float32 logits the
# kernel stays under the 16 MB the compiler gives it.
_ATTENTION_STRETCH_VMEM = 4 * 1024 * 1024
_ATTENTION_STRETCH_TILES = 4  # variants of the body grow with its square
# rows of BH a grid step of the band's forward takes: a step of one head's
# 128 x 128 tile costs the chip more to run than its products and copies
# take (PERF.md, section 7, item 12)
_BAND_HEADS = 8


class _Walk(NamedTuple):
    """Rows of the block a grid step owns, which is also a tile of the
    walked operand (a tile of logits is ``block`` x ``block``), and of the
    stretch of tiles a grid step is handed (a multiple of ``block`` that
    divides T)."""
    block: int
    stretch: int

    def diagonal(self, i):
        """(stretch that holds own block ``i``'s diagonal tile, its place
        in it); ints or traced ints."""
        places = self.stretch // self.block
        s = i // places
        return s, i - s * places

    def after_diagonal(self, i, s, keys_own: bool):
        """How far stretch ``s`` lies on the masked side of own block
        ``i``'s diagonal: 0 holds it, below 0 is wholly live, above 0
        wholly masked (a dead step). Queries own their block unless
        ``keys_own`` (dK/dV), where later stretches are the live ones."""
        s_d, _ = self.diagonal(i)
        return s_d - s if keys_own else s - s_d

    def visits(self, place, keys_own: bool):
        """The tiles of its stretch a grid step visits, in order, as (place
        in the stretch, on the diagonal). ``place``: the diagonal tile's,
        None for a stretch with no diagonal (all of its tiles are live)."""
        places = self.stretch // self.block
        if place is None:
            return tuple((j, False) for j in range(places))
        if keys_own:
            return ((place, True),) + tuple(
                (j, False) for j in range(place + 1, places))
        return tuple((j, False) for j in range(place)) + ((place, True),)


class _Band(NamedTuple):
    """The walk of a sliding window of ``window`` keys a query, its own
    included (query i reads keys i - window < j <= i). A grid step owns a
    block of queries (of keys in dK/dV) and visits ONE tile of the other
    operand: the tiles from ``reach`` tiles before its own up to its own,
    so a block takes ``reach + 1`` grid steps, whatever T is. Every visited
    tile is masked on both of the band's edges. The forward's grid step
    takes up to ``_BAND_HEADS`` rows of BH at once, whole KV groups or a
    part of one, the backward's one."""
    block: int
    reach: int

    @property
    def stretch(self):
        return self.block


def _attention_walk(t: int, dk: int, dv: int, itemsize: int,
                    backward: bool = False, window=None):
    """Block and stretch from the shapes alone. A block is the largest of
    512/256/128 rows that divides T (512x512 float32 logits are 1 MB of
    VMEM); a T they do not divide is one block (the small shapes of the
    tests). The forward's stretch is the most tiles, up to
    ``_ATTENTION_STRETCH_TILES`` and ``_ATTENTION_STRETCH_VMEM``, that
    divide T's tiles. The ``backward`` kernels keep one tile a grid step:
    a tile of theirs is three and four products long, and a training step
    that held the stretch's variants of both took 10 s longer to load from
    the compile cache (2 s with stretches of two tiles) for 6 - 12% less
    of their time (``PERF.md``, section 6, PR 37)."""
    # Under a window the walk is a _Band in all three passes: a block of 128
    # rows where the window is no wider (a window of 128 then touches two
    # tiles of 128 x 128, twice the band's logits, where tiles of 512 would
    # compute eight times them), else of 256.
    if window is not None:
        sizes = (128, 256) if window <= 128 else (256, 128)
        block = next((b for b in sizes if t % b == 0), t)
        return _Band(block, min(-(-(window - 1) // block), t // block - 1))
    block = next((b for b in (512, 256, 128) if t % b == 0), t)
    most = 1 if backward else min(
        _ATTENTION_STRETCH_TILES,
        max(1, _ATTENTION_STRETCH_VMEM // (2 * block * (dk + dv) * itemsize)))
    tiles = max(n for n in range(1, most + 1) if (t // block) % n == 0)
    return _Walk(block, tiles * block)


@functools.lru_cache(maxsize=None)
def _build_blocked_attention(t: int, dk: int, dv: int, causal: bool,
                             scale: float, dtype: str, interpret: bool,
                             window=None, sink: bool = False):
    """(fwd, bwd) over (BH, T, dk) queries, (BH_kv, T, dk) keys and
    (BH_kv, T, dv) values, BH_kv dividing BH: query row ``r`` reads KV row
    ``r // (BH / BH_kv)``. ``fwd(q, k, v[, sink]) -> (o, lse)`` with ``lse``
    (BH, T) float32 (the sink's term included); ``bwd(q, k, v, o, lse, do)
    -> (dq, dk, dv)``. ``window``: causal attention to the last ``window`` keys,
    on a ``_Band`` walk, under kernel names of its own; ``sink`` (a window
    only): ``fwd`` takes (BH,) float32 sink logits."""
    import jax.numpy as jnp
    itemsize = jnp.dtype(dtype).itemsize
    if window is None:
        walks = (_attention_walk(t, dk, dv, itemsize),
                 _attention_walk(t, dk, dv, itemsize, True))
    else:
        walks = (_attention_walk(t, dk, dv, itemsize, window=window),) * 2
    fwd, _ = _attention_passes(t, dk, dv, causal, scale, interpret, walks[0],
                               window, sink)
    _, bwd = _attention_passes(t, dk, dv, causal, scale, interpret, walks[1],
                               window)
    return fwd, bwd


def _attention_passes(t, dk, dv, causal, scale, interpret, walk, window=None,
                      sink=False):
    """``_build_blocked_attention``'s (fwd, bwd) with both passes on one
    walk."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    blk = walk.block
    band = isinstance(walk, _Band)
    n = t // blk
    n_s = walk.reach + 1 if band else t // walk.stretch
    name = "mx_attention_window_" if band else "mx_attention_"
    f32 = jnp.float32
    nt = (((1,), (1,)), ((), ()))  # a @ b.T

    def dot(a, b, dims=(((1,), (0,)), ((), ()))):
        return jax.lax.dot_general(a, b, dims, preferred_element_type=f32)

    def logits(a, b, on_diagonal, transposed):
        """a @ b.T * scale, (blk, blk); on the diagonal tile the
        entries whose key comes after their query are masked.
        ``transposed``: rows are keys. On a band ``on_diagonal`` is the
        (traced) count of tiles the query tile lies after the key tile, and
        every entry outside the window is masked."""
        s = dot(a, b, nt) * scale
        if band:
            r = jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 0)
            c = jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 1)
            # the query's position less the key's
            ahead = (c - r if transposed else r - c) + on_diagonal * blk
            s = jnp.where(jnp.logical_and(ahead >= 0, ahead < window), s,
                          _MASKED)
        elif on_diagonal:
            r = jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 0)
            c = jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 1)
            s = jnp.where(r <= c if transposed else r >= c, s, _MASKED)
        return s

    def one_ahead(visits, products):
        """(rows of the tile, products(rows, on_diagonal)) for each visit,
        the products of visit j+1 written into the program before what the
        caller does with those of visit j (see the comment above)."""
        def tile(j, on_diagonal):
            rows = slice(j * blk, (j + 1) * blk)
            return rows, products(rows, on_diagonal)
        ahead = tile(*visits[0])
        for visit in visits[1:] + (None,):
            here, ahead = ahead, visit and tile(*visit)
            yield here

    def on_stretch(i, s, body, keys_own=False):
        """Run ``body(visits)`` where own block ``i`` and stretch ``s`` have
        anything unmasked: one straight-line variant for a stretch wholly
        live and one for each place of the diagonal. On a band, step ``s``
        visits the one tile ``reach - s`` tiles before its own block (in
        dK/dV: ``s`` tiles after), where there is one."""
        if band:
            apart = s if keys_own else walk.reach - s
            other = i + apart if keys_own else i - apart
            pl.when(jnp.logical_and(other >= 0, other < n))(
                lambda: body(((0, apart),)))
            return
        if not causal:
            body(walk.visits(None, keys_own))
            return
        pl.when(walk.after_diagonal(i, s, keys_own) < 0)(
            lambda: body(walk.visits(None, keys_own)))
        s_d, place = walk.diagonal(i)
        for at in range(walk.stretch // blk):
            pl.when(jnp.logical_and(s == s_d, place == at))(
                lambda at=at: body(walk.visits(at, keys_own)))

    def kv_rows(q, k):
        """(rep, the KV row of query row b): ``rep`` query rows share a KV
        row, as ``jnp.repeat(k, rep, axis=0)`` would lay them out. At rep 1
        the index maps are the identity's."""
        rep = q.shape[0] // k.shape[0]
        return rep, (lambda b: b) if rep == 1 else (lambda b: b // rep)

    # blocks named by (head, own block, stretch); the stretch is clamped to
    # the causal range (on a band, to the sequence) so a dead step copies
    # nothing
    def own(width, head=lambda b: b):
        return pl.BlockSpec((1, blk, width), lambda b, i, s: (head(b), i, 0))

    def walked(shape, index, keys_own=False):
        if band:
            return pl.BlockSpec(shape, (lambda b, i, s: index(
                b, jnp.minimum(i + s, n - 1) if keys_own
                else jnp.maximum(i - walk.reach + s, 0))))
        clamp = jnp.maximum if keys_own else jnp.minimum
        return pl.BlockSpec(
            shape, (lambda b, i, s: index(b, clamp(s, walk.diagonal(i)[0])))
            if causal else (lambda b, i, s: index(b, s)))

    def last(i):
        """The grid step that finishes own block ``i`` (queries own)."""
        return walk.diagonal(i)[0] if causal and not band else n_s - 1

    def walked_rows(width, keys_own=False, head=lambda b: b):
        return walked((1, walk.stretch, width),
                      lambda b, s: (head(b), s, 0), keys_own)

    column = pl.BlockSpec((1, blk, 1), lambda b, i, s: (b, i, 0))
    params = None if interpret else pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))
    kw = dict(interpret=interpret, compiler_params=params) \
        if params is not None else dict(interpret=interpret)

    # ---- forward ---------------------------------------------------------
    # m, l and alpha a row are kept across a vreg's 128 lanes where the
    # widths allow: a row maximum comes out of its reduction that way, and
    # widening a (blk, 1) column to the logits or to the accumulator is a
    # lane permute a row group a tile, which at head size 128 cost more
    # than the tile's products
    lanes = 128 if blk % 128 == 0 and dv % 128 == 0 else 1

    def across(a, width):
        """(..., blk, lanes) against (..., blk, width) operands."""
        return jnp.tile(a, (1,) * (a.ndim - 1) + (width // lanes,)) \
            if lanes > 1 else a

    def fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_s, l_s, acc_s):
        qi, si = pl.program_id(1), pl.program_id(2)

        @pl.when(si == 0)
        def _():
            m_s[...] = jnp.full_like(m_s, -jnp.inf)
            l_s[...] = jnp.zeros_like(l_s)
            acc_s[...] = jnp.zeros_like(acc_s)

        def update(visits):
            q = q_ref[0]
            m, l, acc = m_s[...], l_s[...], acc_s[...]
            for rows, s in one_ahead(visits, lambda rows, on_diagonal: logits(
                    q, k_ref[0, rows], on_diagonal, False)):
                m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
                alpha = jnp.exp(m - m_new)
                p = jnp.exp(s - across(m_new, blk))
                l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
                acc = across(alpha, dv) * acc + dot(p.astype(v_ref.dtype),
                                                    v_ref[0, rows])
                m = m_new
            m_s[...], l_s[...], acc_s[...] = m, l, acc

        on_stretch(qi, si, update)

        @pl.when(si == last(qi))
        def _():
            o_ref[0] = (acc_s[...] / across(l_s[...], dv)).astype(o_ref.dtype)
            lse_ref[0] = (m_s[...] + jnp.log(l_s[...]))[:, :1]

    def fwd(q, k, v):
        bh = q.shape[0]
        _, kv = kv_rows(q, k)
        o, lse = pl.pallas_call(
            fwd_kernel, grid=(bh, n, n_s),
            in_specs=[own(dk), walked_rows(dk, head=kv),
                      walked_rows(dv, head=kv)],
            out_specs=[own(dv), column],
            out_shape=[jax.ShapeDtypeStruct((bh, t, dv), q.dtype),
                       jax.ShapeDtypeStruct((bh, t, 1), f32)],
            scratch_shapes=[pltpu.VMEM((blk, lanes), f32),
                            pltpu.VMEM((blk, lanes), f32),
                            pltpu.VMEM((blk, dv), f32)],
            name="mx_attention_fwd", **kw)(q, k, v)
        return o, lse[..., 0]

    # ---- forward of a band: _BAND_HEADS rows of BH a grid step -----------
    def band_fwd_kernel(q_ref, k_ref, v_ref, *refs):
        if sink:  # the sink is a logit with no value: it starts m and l
            sink_ref, o_ref, lse_ref, m_s, l_s, acc_s = refs
        else:
            o_ref, lse_ref, m_s, l_s, acc_s = refs
        qi, si = pl.program_id(1), pl.program_id(2)
        batched = ((0,), (0,))
        # the step's query rows in groups of ``per_kv`` that share a KV
        # tile: a group's products with it are one product, its rows stacked
        groups = k_ref.shape[0]
        per_kv = q_ref.shape[0] // groups

        def stacked(x):           # (hb, blk, w) -> (groups, per_kv blk, w)
            return x if per_kv == 1 else x.reshape(groups, per_kv * blk,
                                                   x.shape[2])

        def unstacked(x):         # and back
            return x if per_kv == 1 else x.reshape(groups * per_kv, blk,
                                                   x.shape[2])

        @pl.when(si == 0)
        def _():
            if sink:
                m_s[...] = jnp.broadcast_to(sink_ref[...], m_s.shape)
                l_s[...] = jnp.ones_like(l_s)
            else:
                m_s[...] = jnp.full_like(m_s, -jnp.inf)
                l_s[...] = jnp.zeros_like(l_s)
            acc_s[...] = jnp.zeros_like(acc_s)

        apart = walk.reach - si  # tiles between the keys' and the queries'

        @pl.when(qi >= apart)
        def _():
            s = unstacked(dot(stacked(q_ref[...]), k_ref[...],
                              (((2,), (2,)), batched))) * scale
            r = jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 0)
            c = jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 1)
            ahead = r - c + apart * blk
            s = jnp.where(jnp.logical_and(ahead >= 0, ahead < window)[None],
                          s, _MASKED)
            m = m_s[...]
            m_new = jnp.maximum(m, jnp.max(s, axis=2, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - across(m_new, blk))
            l_s[...] = alpha * l_s[...] + jnp.sum(p, axis=2, keepdims=True)
            acc_s[...] = across(alpha, dv) * acc_s[...] + unstacked(dot(
                stacked(p.astype(v_ref.dtype)), v_ref[...],
                (((2,), (1,)), batched)))
            m_s[...] = m_new

        @pl.when(si == n_s - 1)
        def _():
            o_ref[...] = (acc_s[...] / across(l_s[...], dv)).astype(
                o_ref.dtype)
            lse_ref[...] = (m_s[...] + jnp.log(l_s[...]))[..., :1]

    def band_fwd(q, k, v, *sinks):
        bh = q.shape[0]
        rep = bh // k.shape[0]
        # a step's rows are whole KV groups or a part of one, so that its
        # keys and values are whole rows of theirs
        hb = max(h for h in range(1, _BAND_HEADS + 1)
                 if bh % h == 0 and (rep % h == 0 or h % rep == 0))
        steps = max(1, rep // hb)                  # grid steps a KV row
        kv = (lambda b: b) if steps == 1 else (lambda b: b // steps)

        def keys(width):
            return pl.BlockSpec((max(1, hb // rep), blk, width),
                                lambda b, i, s: (kv(b), jnp.maximum(
                                    i - walk.reach + s, 0), 0))

        def queries(width):
            return pl.BlockSpec((hb, blk, width), lambda b, i, s: (b, i, 0))

        # a head's sink, (BH,) float32, as a row of the running maximum
        o, lse = pl.pallas_call(
            band_fwd_kernel, grid=(bh // hb, n, n_s),
            in_specs=[queries(dk), keys(dk), keys(dv)] + [pl.BlockSpec(
                (hb, 1, lanes), lambda b, i, s: (b, 0, 0))] * len(sinks),
            out_specs=[queries(dv), queries(1)],
            out_shape=[jax.ShapeDtypeStruct((bh, t, dv), q.dtype),
                       jax.ShapeDtypeStruct((bh, t, 1), f32)],
            scratch_shapes=[pltpu.VMEM((hb, blk, lanes), f32),
                            pltpu.VMEM((hb, blk, lanes), f32),
                            pltpu.VMEM((hb, blk, dv), f32)],
            name="mx_attention_window_fwd", **kw)(
                q, k, v, *(jnp.broadcast_to(z.astype(f32)[:, None, None],
                                            (bh, 1, lanes)) for z in sinks))
        return o, lse[..., 0]

    # ---- backward: dQ ----------------------------------------------------
    def dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                  acc_s):
        qi, si = pl.program_id(1), pl.program_id(2)

        @pl.when(si == 0)
        def _():
            acc_s[...] = jnp.zeros_like(acc_s)

        def update(visits):
            q, do = q_ref[0], do_ref[0]
            lse, delta = lse_ref[0], delta_ref[0]
            acc = acc_s[...]

            def products(rows, on_diagonal):
                return (logits(q, k_ref[0, rows], on_diagonal, False),
                        dot(do, v_ref[0, rows], nt))

            for rows, (s, dp) in one_ahead(visits, products):
                k = k_ref[0, rows]
                ds = jnp.exp(s - lse) * (dp - delta) * scale
                acc = acc + dot(ds.astype(k.dtype), k)
            acc_s[...] = acc

        on_stretch(qi, si, update)

        @pl.when(si == last(qi))
        def _():
            dq_ref[0] = acc_s[...].astype(dq_ref.dtype)

    # ---- backward: dK, dV (rows are keys) ---------------------------------
    def dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
                   dv_ref, dk_s, dv_s):
        ki, si = pl.program_id(1), pl.program_id(2)

        @pl.when(si == (walk.diagonal(ki)[0] if causal and not band else 0))
        def _():
            dk_s[...] = jnp.zeros_like(dk_s)
            dv_s[...] = jnp.zeros_like(dv_s)

        def update(visits):
            k, v = k_ref[0], v_ref[0]
            dk_acc, dv_acc = dk_s[...], dv_s[...]

            def products(rows, on_diagonal):
                return (logits(k, q_ref[0, rows], on_diagonal, True),
                        dot(v, do_ref[0, rows], nt))

            for rows, (s, dp) in one_ahead(visits, products):
                q, do = q_ref[0, rows], do_ref[0, rows]
                p = jnp.exp(s - lse_ref[0, :, rows])  # lse, delta: (1, blk)
                dv_acc = dv_acc + dot(p.astype(do.dtype), do)
                ds = p * (dp - delta_ref[0, :, rows]) * scale
                dk_acc = dk_acc + dot(ds.astype(q.dtype), q)
            dk_s[...], dv_s[...] = dk_acc, dv_acc

        on_stretch(ki, si, update, keys_own=True)

        @pl.when(si == n_s - 1)
        def _():
            dk_ref[0] = dk_s[...].astype(dk_ref.dtype)
            dv_ref[0] = dv_s[...].astype(dv_ref.dtype)

    def bwd(q, k, v, o, lse, do):
        bh = q.shape[0]
        rep, kv = kv_rows(q, k)
        delta = jnp.sum(o.astype(f32) * do.astype(f32), axis=-1)   # (BH, T)
        dq = pl.pallas_call(
            dq_kernel, grid=(bh, n, n_s),
            in_specs=[own(dk), walked_rows(dk, head=kv),
                      walked_rows(dv, head=kv), own(dv), column, column],
            out_specs=own(dk),
            out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
            scratch_shapes=[pltpu.VMEM((blk, dk), f32)],
            name=name + "dq", **kw)(
                q, k, v, do, lse[..., None], delta[..., None])
        row = walked((1, 1, walk.stretch), lambda b, s: (b, 0, s), True)
        # a query row's share of its KV row's gradients, summed over the
        # group below, as the transpose of a repeat of the KV rows sums them
        dk_, dv_ = pl.pallas_call(
            dkv_kernel, grid=(bh, n, n_s),
            in_specs=[walked_rows(dk, True), own(dk, kv), own(dv, kv),
                      walked_rows(dv, True), row, row],
            out_specs=[own(dk), own(dv)],
            out_shape=[jax.ShapeDtypeStruct((bh, t, dk), k.dtype),
                       jax.ShapeDtypeStruct((bh, t, dv), v.dtype)],
            scratch_shapes=[pltpu.VMEM((blk, dk), f32),
                            pltpu.VMEM((blk, dv), f32)],
            name=name + "dkv", **kw)(
                q, k, v, do, lse[:, None, :], delta[:, None, :])
        if rep > 1:
            dk_, dv_ = (z.reshape(-1, rep, t, z.shape[2]).sum(axis=1)
                        for z in (dk_, dv_))
        return dq, dk_, dv_

    return (band_fwd if band else fwd), bwd


def blocked_attention(q, k, v, causal: bool = True, scale=None, window=None,
                      sink=None):
    """Softmax attention blocked over queries and keys in both passes.

    q: (BH, T, dk); k: (BH_kv, T, dk); v: (BH_kv, T, dv) -> (BH, T, dv).
    BH_kv divides BH, and query row ``r`` reads KV row ``r // (BH /
    BH_kv)``: for rows ``b * heads + h`` and ``b * kv_heads + g``, query
    head ``h`` reads KV head ``h // (heads / kv_heads)``, with no copy of a
    KV head made (the kernels' index maps name the group's row; ``dk`` and
    ``dv`` are summed over the group). Differentiable; the
    backward keeps the output and the (BH, T) log-sum-exp and recomputes the
    probabilities block by block. Under ``jax.checkpoint`` with
    ``save_only_these_names("mx.attention")`` those two are what a layer
    keeps, so a recomputed layer does not run the forward kernel again.

    ``window``: query i attends to keys i - window < j <= i alone (causal
    only); the kernels then visit only the key tiles that meet that band
    (``_Band``) and are named ``mx_attention_window_fwd``, ``_dq``,
    ``_dkv``. ``sink`` (with a window): (BH,) logits, one a row of BH, of a
    key with no value, ``p_ij = exp(l_ij) / (exp(sink) + sum_j'
    exp(l_ij'))``; its gradient is ``-sum_i p_sink,i (do_i . o_i)``.
    """
    import jax
    import jax.numpy as jnp
    from jax.ad_checkpoint import checkpoint_name

    bh, t, dk = q.shape
    if k.shape[0] != v.shape[0] or bh % k.shape[0]:
        raise ValueError(f"{bh} query rows cannot share {k.shape[0]} key "
                         f"and {v.shape[0]} value rows in equal groups")
    if window is not None and not causal:
        raise ValueError("a window is causal")
    sc = float(scale) if scale is not None else 1.0 / math.sqrt(dk)
    if sink is not None and window is None:
        raise ValueError("a sink comes with a window")
    fwd, bwd = _build_blocked_attention(
        t, dk, v.shape[-1], bool(causal), sc, str(q.dtype), _interpret_for(q),
        None if window is None else int(window), sink is not None)
    sinks = () if sink is None else (sink,)

    @jax.custom_vjp
    def op(q, k, v, *sinks):
        return fwd(q, k, v, *sinks)[0]

    def op_fwd(q, k, v, *sinks):
        o, lse = fwd(q, k, v, *sinks)
        o = checkpoint_name(o, "mx.attention")
        lse = checkpoint_name(lse, "mx.attention")
        return o, (q, k, v, o, lse) + sinks

    def op_bwd(res, do):
        grads = bwd(*res[:5], do)
        if not sinks:
            return grads
        o, lse, sink = res[3:]
        f32 = jnp.float32
        delta = jnp.sum(o.astype(f32) * do.astype(f32), axis=-1)
        taken = jnp.exp(sink.astype(f32)[:, None] - lse)       # p_sink
        return grads + (-jnp.sum(taken * delta, axis=1).astype(sink.dtype),)

    op.defvjp(op_fwd, op_bwd)
    return op(q, k, v, *sinks)


@register("_contrib_blocked_attention")
def _blocked_attention_op(q, k, v, causal=True, scale=None):
    return blocked_attention(q, k, v, causal=causal, scale=scale)


# ---------------------------------------------------------------------------
# The gated delta rule by chunks (``lm_ops.gated_delta_rule_chunked``), its
# forward as one kernel over heads-first operands. A grid step owns a block
# of tokens of every head; the grid walks a sequence's blocks in order, each
# head's (dk, dv) float32 state in VMEM scratch from one to the next, and
# the body walks the heads a group at a time, the group batched in every
# product. A block is one or more chunks. Everything of a chunk that needs
# no state (the running sums, the decay mask, K K^T and Q K^T, the UT
# transform's R = (I + A)^-1) is computed for the block's chunks at once as
# their (chunk, chunk) matrices side by side, one (chunk, block) array;
# spread to block-diagonal (block, block) matrices they give W, U' and the
# intra-chunk output in one product each. The chunks then take the state in
# turn, three products each. q, k, v, g and beta are read once, o is
# written once; nothing of a chunk reaches HBM. The casts are the XLA
# formulation's: product operands in k's dtype, accumulation, decays,
# running sums, the solve and the state in float32.
# ---------------------------------------------------------------------------

_DELTA_BLOCK = 128  # tokens a grid step: g and beta fill the 128 lanes
_DELTA_GROUP = 6    # most heads the products batch
_DELTA_VMEM = 64 * 1024 * 1024


@functools.lru_cache(maxsize=None)
def _build_delta_rule(b: int, h: int, t: int, dk: int, dv: int, chunk: int,
                      dtype: str, interpret: bool):
    """``call(q, k, v, g, beta) -> o``: q, k (B, H, T, dk), v (B, H, T, dv)
    in ``dtype``; g, beta (B, H, 1, T) float32; o (B, H, T, dv) float32. T
    is a multiple of the block. The heads go ``hg`` at a time through a
    loop, not straight-line code: the body's code grows with ``hg``."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    low = jnp.dtype(dtype)
    blk = max(_DELTA_BLOCK, chunk)
    assert chunk & (chunk - 1) == 0 and t % blk == 0, (t, chunk)
    chunks = range(blk // chunk)
    hg = max(n for n in range(1, min(h, _DELTA_GROUP) + 1) if h % n == 0)
    exact = jax.lax.Precision.HIGHEST

    def dot(a, b_, precision=None):
        """a @ b batched over the leading (head) axis."""
        return jax.lax.dot_general(
            a, b_, (((2,), (1,)), ((0,), (0,))), precision=precision,
            preferred_element_type=f32)

    def low_dot(a, b_):
        return dot(a.astype(low), b_.astype(low))

    def column(row):
        """(hg, 1, blk) -> (hg, blk, 1)."""
        return jnp.swapaxes(jnp.broadcast_to(row, (hg, 8, blk)), 1, 2)[..., :1]

    def kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, s_ref, u_ref):
        @pl.when(pl.program_id(1) == 0)
        def _():
            s_ref[...] = jnp.zeros_like(s_ref)

        lane = jax.lax.broadcasted_iota(jnp.int32, (hg, 1, blk), 2)
        pos = lane & (chunk - 1)                    # place in the chunk
        # side by side: row t, lane (chunk, place s)
        row = jax.lax.broadcasted_iota(jnp.int32, (chunk, blk), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (chunk, blk), 1) \
            & (chunk - 1)
        of = jax.lax.broadcasted_iota(jnp.int32, (chunk, blk), 1) // chunk
        apart = row ^ col           # < size: t and s in one block of size

        def side_by_side(full):
            """(hg, blk, x) down the chunks -> (hg, chunk, blk), chunk c's
            rows in its own lanes: of (block, block) products with nothing
            between chunks, and of columns."""
            return sum(jnp.where(of == c, full[:, c * chunk:(c + 1) * chunk],
                                 0.0) for c in chunks)

        def spread(side):
            """Side by side -> (hg, blk, blk), block-diagonal."""
            return jnp.concatenate([jnp.where(of == c, side, 0.0)
                                    for c in chunks], axis=1)

        def group(i, carry):
            heads = pl.ds(pl.multiple_of(i * hg, hg), hg)
            # the running sum G of g inside each chunk, and its value at
            # the chunk's end, along the lanes (log-step shifts, float32)
            run = g_ref[0, heads]                              # (hg, 1, blk)
            step = 1
            while step < chunk:
                run = run + jnp.where(pos >= step, pltpu.roll(run, step, 2),
                                      0.0)
                step *= 2
            end = jnp.where(pos == chunk - 1, run, 0.0)
            step = 1
            while step < chunk:
                end = end + jnp.where(pos < chunk - step,
                                      pltpu.roll(end, blk - step, 2), 0.0)
                step *= 2
            run_c, end_c = column(run), column(end)
            beta_c = column(b_ref[0, heads])

            # Gamma_ts = exp(G_t - G_s), s <= t in one chunk
            causal = col <= row
            gamma = jnp.where(causal, jnp.exp(side_by_side(run_c) - run), 0.0)
            q, k, v = q_ref[0, heads], k_ref[0, heads], v_ref[0, heads]
            k_t = jnp.swapaxes(k, 1, 2)                       # (hg, dk, blk)
            a = jnp.where(apart > 0, side_by_side(beta_c) * gamma
                          * side_by_side(low_dot(k, k_t)), 0.0)
            # R = (I + A)^-1 by doubling: the inverse of A's diagonal blocks
            # of ``size`` gives that of blocks twice the size, Y - Y E Y
            # with E the part of A between the two halves
            inv = jnp.where(apart == 0, 1.0, 0.0) - jnp.where(apart < 2, a, 0.0)
            size = 2
            while size < chunk:
                e = jnp.where((apart >= size) & (apart < 2 * size), a, 0.0)
                inv = inv - dot(dot(inv, spread(e), exact), spread(inv),
                                exact)
                size *= 2
            inv = spread(inv)
            grown = jnp.exp(run_c)
            w = low_dot(inv, k * (beta_c * grown))
            u = low_dot(inv, v * beta_c)
            qk = spread(side_by_side(low_dot(q, k_t)) * gamma)
            qg = q * grown
            # (exp(G_end - G) K)^T: the state's update contracts over the
            # block, with 0 in the columns of the other chunks
            kt_t = k_t * jnp.exp(end - run)

            # the chunks take the state in turn; U of the chunks still to
            # come is 0 until it is written
            u_ref[...] = jnp.zeros_like(u_ref)
            state = s_ref[heads]
            for j in chunks:
                rows = slice(j * chunk, (j + 1) * chunk)
                new = u[:, rows] - low_dot(w[:, rows], state)          # U
                o_ref[0, heads, rows] = low_dot(qg[:, rows], state)
                u_ref[:, rows] = new.astype(u_ref.dtype)
                keep = jnp.exp(jnp.broadcast_to(         # (hg, 1, dv)
                    end_c[:, j * chunk:j * chunk + 1], (hg, 1, dv)))
                mine = jnp.where(lane // chunk == j, kt_t, 0.0)
                state = state * keep + low_dot(mine, u_ref[...])
            s_ref[heads] = state
            o_ref[0, heads] = o_ref[0, heads] + low_dot(qk, u_ref[...])
            return carry

        jax.lax.fori_loop(0, h // hg, group, 0)

    def heads_tokens(d):
        return pl.BlockSpec((1, h, blk, d), lambda i, s: (i, 0, s, 0))

    lanes = pl.BlockSpec((1, h, 1, blk), lambda i, s: (i, 0, 0, s))
    kw = dict(interpret=interpret)
    if not interpret:
        kw["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_DELTA_VMEM)

    def call(q, k, v, g, beta):
        return pl.pallas_call(
            kernel, grid=(b, t // blk),
            in_specs=[heads_tokens(dk), heads_tokens(dk), heads_tokens(dv),
                      lanes, lanes],
            out_specs=heads_tokens(dv),
            out_shape=jax.ShapeDtypeStruct((b, h, t, dv), f32),
            scratch_shapes=[pltpu.VMEM((h, dk, dv), f32),
                            pltpu.VMEM((hg, blk, dv), low)],
            name="mx_delta_rule", **kw)(q, k, v, g, beta)

    return call


def delta_rule(q, k, v, g, beta, chunk: int):
    """The forward of the chunked gated delta rule over heads-first
    operands: q, k (B, H, T, dk); v (B, H, T, dv); g, beta (B, H, T) -> o
    (B, H, T, dv) float32, products in k's dtype (``lm_ops.
    gated_delta_rule_chunked`` says what is computed). A T that the block
    does not divide is padded with steps of beta = 0 and g = 0, which
    neither decay nor write. Not differentiable by itself: the caller's
    ``custom_vjp`` holds the backward."""
    import jax.numpy as jnp
    bsz, h, t, dk = k.shape
    pad = -t % max(_DELTA_BLOCK, chunk)
    low = k.dtype

    def padded(z, dtype):
        return jnp.pad(z.astype(dtype), ((0, 0), (0, 0), (0, pad))
                       + ((0, 0),) * (z.ndim - 3))

    call = _build_delta_rule(bsz, h, t + pad, dk, v.shape[-1], chunk,
                             str(low), _interpret_for(k))
    o = call(padded(q, low), padded(k, low), padded(v, low),
             padded(g, jnp.float32)[:, :, None],
             padded(beta, jnp.float32)[:, :, None])
    return o[:, :, :t]


# ---------------------------------------------------------------------------
# The routed experts' combine (``parallel/moe.py:_add_rows``): a tile's rows
# added into the rows of an (N, 1, D) float32 accumulator that stays in HBM,
# by DMA. One grid step a tile: it starts a copy of every row it adds to,
# waits on them all, adds, starts the copies back and waits on those, so the
# step's fixed cost is paid once a tile and a tile's copies are in flight
# together (XLA's scatter-add runs a row at a time: 0.40 us a row of 2,688
# float32 on a v5e, PERF.md section 6). The accumulator is (N, 1, D)
# and not (N, D): the TPU lays the first out in tiles of 1 x 128, so a row is
# one run of memory that a DMA can take, and the second in tiles of 8 x 128,
# whose single rows it refuses.
# ---------------------------------------------------------------------------

_COMBINE_ADD_ROWS = 8  # rows of the add a step: a row of vregs


def moe_combine_fits(d: int, dtype) -> bool:
    """Whether ``moe_combine`` takes rows D wide of ``dtype``."""
    import jax.numpy as jnp
    return jnp.dtype(dtype) == jnp.float32 and d % 128 == 0


@functools.lru_cache(maxsize=None)
def _build_moe_combine(n: int, d: int, tile: int, interpret: bool):
    """``call(index, acc, rows) -> acc``: index (tile,) int32, acc (n, 1, d)
    float32 updated in place (aliased), rows (tile, d) float32."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    step = math.gcd(tile, _COMBINE_ADD_ROWS)

    def kernel(index_ref, acc_ref, rows_ref, out_ref, buf, sem):
        def copy(r, back):
            row = pl.ds(index_ref[r], 1)
            if back:
                return pltpu.make_async_copy(buf.at[pl.ds(r, 1)],
                                             out_ref.at[row], sem)
            return pltpu.make_async_copy(acc_ref.at[row], buf.at[pl.ds(r, 1)],
                                         sem)

        def each_row(do):
            """``do(r)`` for every row whose index is in range, in order:
            the starts and the waits of one direction count the same
            copies."""
            def body(r, carry):
                @pl.when(index_ref[r] < n)
                def _():
                    do(r)
                return carry
            jax.lax.fori_loop(0, tile, body, 0)

        def add(j, carry):
            rows = pl.ds(pl.multiple_of(j * step, step), step)
            buf[rows, 0, :] = buf[rows, 0, :] + rows_ref[rows]
            return carry

        each_row(lambda r: copy(r, False).start())
        each_row(lambda r: copy(r, False).wait())
        jax.lax.fori_loop(0, tile // step, add, 0)
        each_row(lambda r: copy(r, True).start())
        each_row(lambda r: copy(r, True).wait())

    kw = dict(interpret=interpret)
    if not interpret:
        # the rows and their copy in scratch, whole: 16 MB at 512 x 4,096
        kw["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=2 * tile * d * 4 + 4 * 1024 * 1024)

    def call(index, acc, rows):
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(1,),
                in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                          pl.BlockSpec(memory_space=pltpu.VMEM)],
                out_specs=pl.BlockSpec(memory_space=pl.ANY),
                scratch_shapes=[pltpu.VMEM((tile, 1, d), f32),
                                pltpu.SemaphoreType.DMA(())]),
            out_shape=jax.ShapeDtypeStruct((n, 1, d), f32),
            input_output_aliases={1: 0},
            name="mx_moe_combine", **kw)(index, acc, rows)

    return call


@functools.lru_cache(maxsize=None)
def _build_moe_zeros(n: int, d: int, interpret: bool):
    """``call() -> zeros (n, 1, d) float32``, blocks of 256 rows."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    rows = math.gcd(n, 256)

    def kernel(o_ref):
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    return pl.pallas_call(
        kernel, grid=(n // rows,),
        out_specs=pl.BlockSpec((rows, 1, d), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 1, d), jnp.float32),
        name="mx_moe_zeros", interpret=interpret)


def moe_zeros(n: int, d: int):
    """The accumulator ``moe_combine`` adds into, (N, 1, D) float32 zeros.
    XLA fills an array laid out in tiles of 1 x 128 at two thirds of the
    rate of one in tiles of 8 x 128 (2.26 ms against 1.41 for 32,768 x
    4,096 on a v5e, PERF.md section 6); this kernel fills it at the
    latter's (1.47 ms)."""
    return _build_moe_zeros(n, d, _interpret_for(None))()


def moe_combine(acc, index, rows):
    """``acc[index, 0] += rows`` for one tile of the routed experts' loop, by
    row DMAs: acc (N, 1, D) float32, index (tile,) int, rows (tile, D)
    float32, D a multiple of 128 (``moe_combine_fits``). An index of N or
    more is a padding row and adds nothing, as XLA's scatter-add with
    ``mode="drop"`` drops it. Keep ``acc`` (N, 1, D) from one tile to the
    next: a reshape from (N, D) and back is a copy of the whole of it.

    A tile's copies run at once, so no two of them may touch one row of
    ``acc``: the indices of a tile must be unique (padding aside); their
    order does not matter. The routed loop's are unique: a tile holds the
    rows of one expert, and top-k names an expert at most once for a token,
    so an expert has at most one row of a token. Tiles that share tokens run
    one after the other, each tile's copies back done before the next
    tile's start. So each row of ``acc`` gets the additions that XLA's
    scatter-add makes, in the same order, and the sums are the same to the
    bit."""
    import jax.numpy as jnp
    n, _, d = acc.shape
    call = _build_moe_combine(n, d, rows.shape[0], _interpret_for(acc))
    return call(index.astype(jnp.int32), acc, rows)


@register("_contrib_interleaved_matmul_selfatt_qk")
def _interleaved_qk(qkv, heads=1):
    """(ref: src/operator/contrib/transformer.cc interleaved matmul helpers)
    qkv: (T, B, 3*H*D) interleaved; returns (B*H, T, T) scores."""
    import jax.numpy as jnp
    t, b, three_hd = qkv.shape
    d = three_hd // (3 * heads)
    x = qkv.reshape(t, b, heads, 3, d)
    q = x[:, :, :, 0].transpose(1, 2, 0, 3).reshape(b * heads, t, d)
    k = x[:, :, :, 1].transpose(1, 2, 0, 3).reshape(b * heads, t, d)
    return jnp.matmul(q, k.transpose(0, 2, 1)) / math.sqrt(d)


# ---------------------------------------------------------------------------
# Fused bottleneck epilogues: BatchNorm(+residual add)+ReLU consuming the
# convolution output (the ResNet hot path — docs/perf.md roofline: the bf16
# activations materialized BETWEEN the conv and its BN/ReLU/add epilogue are
# the dominant HBM traffic of the train step). Two passes over the conv
# output, both hand-tiled through VMEM:
#   pass 1 (stats):  per-channel sum / sum-of-squares, f32 accumulators
#   pass 2 (apply):  out = relu(norm(x) [+ residual]), written once
# Backward mirrors it (custom_vjp): the ReLU mask is RE-DERIVED from the
# saved output inside both backward passes, so the masked cotangent — an
# activation-sized intermediate the unfused lowering materializes between
# the ReLU backward and the BN reductions — never touches HBM.
# Channel-last (NHWC) only: C rides the 128-lane minor dim.
# ---------------------------------------------------------------------------

# The TPU compiler gives one kernel 16 MB of scoped VMEM unless told
# otherwise; 12 MB leaves it room for its own stack.
_EPILOGUE_VMEM_BUDGET = 12 * 1024 * 1024


def _epilogue_rows(r: int, c: int, n_tiles: int, n_f32: int,
                   interpret: bool, itemsize: int = 2) -> int:
    """Row-block size for the (R, C) flattened activation.

    Interpret mode runs one whole-array block (each grid step is a python
    round-trip; correctness is identical and tests stay fast). Compiled
    mode sizes the block from everything the kernel holds in VMEM per
    row: ``n_tiles`` double-buffered (BR, C) operand tiles of
    ``itemsize`` bytes plus the ``n_f32`` f32 (BR, C) temporaries its
    body makes, rows aligned to the dtype's sublane packing."""
    if interpret:
        return max(1, r)
    lanes = -(-c // 128) * 128  # lane-padded row
    per_row = lanes * (2 * n_tiles * itemsize + 4 * n_f32)
    align = max(8, 32 // itemsize)
    br = _EPILOGUE_VMEM_BUDGET // per_row
    return min(max(align, br - br % align), r)


def _row_mask(i, br, r, xb):
    """Zero rows past R (the last block of a non-divisible grid reads
    padding whose contents are unspecified)."""
    import jax
    import jax.numpy as jnp
    rows = i * br + jax.lax.broadcasted_iota(jnp.int32, (br, 1), 0)
    return jnp.where(rows < r, xb, 0.0)


def _bn_stats_call(x2d, interpret):
    """(R, C) -> (2, C) f32: per-channel [sum, sum of squares]."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    r, c = x2d.shape
    # tile: x; f32 temporaries: xb, xb * xb
    br = _epilogue_rows(r, c, 1, 2, interpret, x2d.dtype.itemsize)

    def kernel(x_ref, acc_ref):
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        xb = _row_mask(i, br, r, x_ref[...].astype(jnp.float32))
        acc_ref[0:1, :] += jnp.sum(xb, axis=0, keepdims=True)
        acc_ref[1:2, :] += jnp.sum(xb * xb, axis=0, keepdims=True)

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((2, c), jnp.float32),
        grid=(pl.cdiv(r, br),),
        in_specs=[pl.BlockSpec((br, c), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((2, c), lambda i: (0, 0)),
        interpret=interpret,
    )(x2d)


def _bn_apply_call(x2d, res2d, coef, interpret):
    """out = relu(x * coef[0] + coef[1] [+ res]), written once."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    r, c = x2d.shape
    has_res = res2d is not None
    # tiles: x, (res,) out; f32 temporaries: y, the residual's cast
    br = _epilogue_rows(r, c, 3 if has_res else 2, 2, interpret,
                        x2d.dtype.itemsize)

    def kernel(*refs):
        if has_res:
            x_ref, res_ref, coef_ref, o_ref = refs
        else:
            x_ref, coef_ref, o_ref = refs
        y = x_ref[...].astype(jnp.float32) * coef_ref[0:1, :] \
            + coef_ref[1:2, :]
        if has_res:
            y = y + res_ref[...].astype(jnp.float32)
        o_ref[...] = jnp.maximum(y, 0.0).astype(o_ref.dtype)

    row_spec = pl.BlockSpec((br, c), lambda i: (i, 0))
    coef_spec = pl.BlockSpec((2, c), lambda i: (0, 0))
    in_specs = [row_spec, row_spec, coef_spec] if has_res \
        else [row_spec, coef_spec]
    args = (x2d, res2d, coef) if has_res else (x2d, coef)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((r, c), x2d.dtype),
        grid=(pl.cdiv(r, br),),
        in_specs=in_specs,
        out_specs=row_spec,
        interpret=interpret,
    )(*args)


def _bn_bwd_stats_call(dy2d, out2d, x2d, coef, interpret):
    """(2, C) f32 per-channel [sum g, sum g*xhat] with g = relu-masked dy
    (mask from the saved output — no materialized masked cotangent) and
    xhat = (x - coef[0]) * coef[1]."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    r, c = x2d.shape
    # tiles: dy, out, x; f32 temporaries: dyb, g, xhat, g * xhat
    br = _epilogue_rows(r, c, 3, 4, interpret, x2d.dtype.itemsize)

    def kernel(dy_ref, out_ref, x_ref, coef_ref, acc_ref):
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        dyb = dy_ref[...].astype(jnp.float32)
        # compare in f32: the v5e's vector unit has no bf16 compare
        g = jnp.where(out_ref[...].astype(jnp.float32) > 0, dyb, 0.0)
        g = _row_mask(i, br, r, g)
        xhat = (x_ref[...].astype(jnp.float32) - coef_ref[0:1, :]) \
            * coef_ref[1:2, :]
        acc_ref[0:1, :] += jnp.sum(g, axis=0, keepdims=True)
        # mask the PRODUCT (where() selects, so Inf/NaN decoded from the
        # last block's unspecified padding rows cannot produce 0*Inf=NaN
        # in the accumulator — g alone being 0 there is not enough)
        acc_ref[1:2, :] += jnp.sum(_row_mask(i, br, r, g * xhat),
                                   axis=0, keepdims=True)

    row_spec = pl.BlockSpec((br, c), lambda i: (i, 0))
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((2, c), jnp.float32),
        grid=(pl.cdiv(r, br),),
        in_specs=[row_spec, row_spec, row_spec,
                  pl.BlockSpec((2, c), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((2, c), lambda i: (0, 0)),
        interpret=interpret,
    )(dy2d, out2d, x2d, coef)


def _bn_bwd_apply_call(dy2d, out2d, x2d, coef, has_res, interpret):
    """dx = coef[2] * (g - coef[3] - xhat * coef[4]); g re-derived from the
    saved output in-kernel; dres (the residual branch cotangent) is g,
    emitted as a second output of the SAME pass."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    r, c = x2d.shape
    # tiles: dy, out, x, dx(, dres); f32 temporaries: g, xhat, dx
    br = _epilogue_rows(r, c, 5 if has_res else 4, 3, interpret,
                        x2d.dtype.itemsize)

    def kernel(*refs):
        if has_res:
            dy_ref, out_ref, x_ref, coef_ref, dx_ref, dres_ref = refs
        else:
            dy_ref, out_ref, x_ref, coef_ref, dx_ref = refs
        g = jnp.where(out_ref[...].astype(jnp.float32) > 0,
                      dy_ref[...].astype(jnp.float32), 0.0)
        xhat = (x_ref[...].astype(jnp.float32) - coef_ref[0:1, :]) \
            * coef_ref[1:2, :]
        dx = coef_ref[2:3, :] * (g - coef_ref[3:4, :]
                                 - xhat * coef_ref[4:5, :])
        dx_ref[...] = dx.astype(dx_ref.dtype)
        if has_res:
            dres_ref[...] = g.astype(dres_ref.dtype)

    row_spec = pl.BlockSpec((br, c), lambda i: (i, 0))
    out_shape = jax.ShapeDtypeStruct((r, c), x2d.dtype)
    out_shapes = (out_shape, out_shape) if has_res else out_shape
    out_specs = (row_spec, row_spec) if has_res else row_spec
    return pl.pallas_call(
        kernel,
        out_shape=out_shapes,
        grid=(pl.cdiv(r, br),),
        in_specs=[row_spec, row_spec, row_spec,
                  pl.BlockSpec((5, c), lambda i: (0, 0))],
        out_specs=out_specs,
        interpret=interpret,
    )(dy2d, out2d, x2d, coef)


@functools.lru_cache(maxsize=None)
def _build_fused_bn_act(eps: float, has_res: bool, interpret: bool):
    """Training-mode fused BN(+add)+ReLU over (R, C) channel-last data
    with a hand-fused backward (jax.custom_vjp).

    Residuals saved for backward: the bf16 input x (conv output), the
    bf16 output (already materialized for the next layer — XLA CSEs the
    two into one buffer) and the per-channel mean/inv/gamma vectors.
    Returns (out, mean, var); mean/var feed running-stat updates only
    (stop-gradient, like the unfused BatchNorm)."""
    import jax
    import jax.numpy as jnp

    def run_fwd(x2d, res2d, g32, beta32):
        n = float(x2d.shape[0])
        sums = _bn_stats_call(x2d, interpret)
        mean = sums[0] / n
        var = jnp.maximum(sums[1] / n - mean * mean, 0.0)
        inv = jax.lax.rsqrt(var + eps)
        scale = inv * g32
        coef = jnp.stack([scale, beta32 - mean * scale])
        out2d = _bn_apply_call(x2d, res2d, coef, interpret)
        return out2d, mean, var, inv

    def run_bwd(x2d, out2d, mean, inv, g32, dy2d):
        n = float(x2d.shape[0])
        sums = _bn_bwd_stats_call(dy2d, out2d, x2d,
                                  jnp.stack([mean, inv]), interpret)
        sum_g, sum_gxhat = sums[0], sums[1]
        coef = jnp.stack([mean, inv, g32 * inv, sum_g / n,
                          sum_gxhat / n])
        outs = _bn_bwd_apply_call(dy2d, out2d, x2d, coef, has_res,
                                  interpret)
        return outs, sum_g, sum_gxhat

    if has_res:
        @jax.custom_vjp
        def f(x2d, res2d, g32, beta32):
            out2d, mean, var, _ = run_fwd(x2d, res2d, g32, beta32)
            return out2d, mean, var

        def fwd(x2d, res2d, g32, beta32):
            out2d, mean, var, inv = run_fwd(x2d, res2d, g32, beta32)
            return (out2d, mean, var), (x2d, out2d, mean, inv, g32)

        def bwd(res, cots):
            x2d, out2d, mean, inv, g32 = res
            (dx, dres), sum_g, sum_gxhat = run_bwd(x2d, out2d, mean, inv,
                                                   g32, cots[0])
            return dx, dres, sum_gxhat, sum_g
    else:
        @jax.custom_vjp
        def f(x2d, g32, beta32):
            out2d, mean, var, _ = run_fwd(x2d, None, g32, beta32)
            return out2d, mean, var

        def fwd(x2d, g32, beta32):
            out2d, mean, var, inv = run_fwd(x2d, None, g32, beta32)
            return (out2d, mean, var), (x2d, out2d, mean, inv, g32)

        def bwd(res, cots):
            x2d, out2d, mean, inv, g32 = res
            dx, sum_g, sum_gxhat = run_bwd(x2d, out2d, mean, inv, g32,
                                           cots[0])
            return dx, sum_gxhat, sum_g

    f.defvjp(fwd, bwd)
    return f


def fused_bn_act(data, residual, gamma32, beta32, eps):
    """Fused training-mode ``BatchNorm [+ add(residual)] + ReLU`` epilogue.

    ``data``: channel-LAST activation (the conv output); ``residual``:
    same shape or None; ``gamma32``/``beta32``: f32 ``(C,)`` vectors.
    Returns ``(out, mean, var)`` with out in data's dtype and f32 batch
    stats. Dispatches compiled Pallas on TPU, interpret mode elsewhere
    (same code path, so CPU tests exercise the real kernels)."""
    c = data.shape[-1]
    x2d = data.reshape(-1, c)
    interpret = _interpret_for(data)
    f = _build_fused_bn_act(float(eps), residual is not None, interpret)
    if residual is not None:
        out2d, mean, var = f(x2d, residual.reshape(-1, c), gamma32, beta32)
    else:
        out2d, mean, var = f(x2d, gamma32, beta32)
    return out2d.reshape(data.shape), mean, var


@register("_contrib_interleaved_matmul_selfatt_valatt")
def _interleaved_valatt(qkv, att, heads=1):
    import jax.numpy as jnp
    t, b, three_hd = qkv.shape
    d = three_hd // (3 * heads)
    x = qkv.reshape(t, b, heads, 3, d)
    v = x[:, :, :, 2].transpose(1, 2, 0, 3).reshape(b * heads, t, d)
    out = jnp.matmul(att, v)  # (B*H, T, D)
    return out.reshape(b, heads, t, d).transpose(2, 0, 1, 3) \
        .reshape(t, b, heads * d)
