"""The routed experts' combine, ``acc[index] += rows`` for a tile of the
routed loop (``parallel/moe.py:_add_rows``): the kernel ``mx_moe_combine``
(``ops/pallas_kernels.py:moe_combine``, interpreted on the CPU) against
XLA's scatter-add with ``mode="drop"``, to the bit, and the choice between
the two by the rows' width and dtype."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.ops import pallas_kernels as pk
from mxnet_tpu.parallel import moe

N = 48


def _tile_index(rng, tile, valid, tokens=N):
    """``valid`` distinct tokens of the first ``tokens`` and padding (N and
    past it), unsorted."""
    index = np.concatenate([rng.permutation(tokens)[:valid],
                            N + rng.integers(0, 3, tile - valid)])
    return jnp.asarray(rng.permutation(index), jnp.int32)


def _normal(rng, *shape):
    return jnp.asarray(rng.normal(size=shape), jnp.float32)


# (rows' width, rows a tile, rows that are not padding)
@pytest.mark.parametrize("d,tile,valid", [(128, 16, 11), (128, 16, 0),
                                          (128, 16, 16), (2688, 8, 5),
                                          (256, 12, 7)])
def test_combine_is_the_scatter_add_to_the_bit(d, tile, valid):
    rng = np.random.default_rng(d + tile + valid)
    acc, rows = _normal(rng, N, d), _normal(rng, tile, d)
    index = _tile_index(rng, tile, valid)
    want = acc.at[index].add(rows, mode="drop")
    got = jax.jit(pk.moe_combine)(acc[:, None], index, rows)[:, 0]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    if valid == 0:
        np.testing.assert_array_equal(np.asarray(got), np.asarray(acc))


@pytest.mark.parametrize("d", [128, 2688])
def test_tiles_in_a_loop_that_share_tokens_add_as_the_scatter_does(d):
    """Five tiles, each of distinct tokens, that touch the same tokens
    across tiles, in a ``fori_loop`` as the routed loop runs them: each
    row's additions in the same order as the scatter's."""
    rng = np.random.default_rng(d)
    tile, tiles = 16, 5
    index = jnp.concatenate([_tile_index(rng, tile, valid, tokens=20)
                             for valid in (16, 12, 16, 3, 0)])
    rows = _normal(rng, tiles * tile, d)
    acc = _normal(rng, N, d)

    def run(add, acc):
        def body(i, acc):
            at = i * tile
            return add(acc, jax.lax.dynamic_slice_in_dim(index, at, tile),
                       jax.lax.dynamic_slice_in_dim(rows, at, tile))
        return jax.lax.fori_loop(0, tiles, body, acc)

    want = jax.jit(lambda a: run(
        lambda a, i, r: a.at[i].add(r, mode="drop"), a))(acc)
    got = jax.jit(lambda a: run(pk.moe_combine, a[:, None])[:, 0])(acc)
    first, second = (set(np.asarray(index[at:at + tile])) - {N, N + 1, N + 2}
                     for at in (0, tile))
    assert len(first & second) >= 8
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("d,dtype,kernel", [
    (128, jnp.float32, True), (2688, jnp.float32, True),
    (96, jnp.float32, False), (2700, jnp.float32, False),
    (128, jnp.bfloat16, False), (2688, jnp.bfloat16, False)])
def test_add_rows_takes_the_kernel_for_float32_rows_of_whole_lanes(
        d, dtype, kernel):
    """``_accumulator`` and ``_add_rows`` choose the kernel where D is a
    multiple of 128 and the accumulator float32, XLA's scatter elsewhere;
    either way the (N, D) result is the scatter's."""
    rng = np.random.default_rng(d)
    tile = 8
    index = _tile_index(rng, tile, 6)
    rows = _normal(rng, tile, d).astype(dtype)
    acc = moe._accumulator(N, d, dtype)
    assert acc.dtype == dtype
    assert acc.shape == ((N, 1, d) if kernel else (N, d))
    assert not np.any(np.asarray(acc.astype(jnp.float32)))
    program = str(jax.make_jaxpr(moe._add_rows)(acc, index, rows))
    assert ("mx_moe_combine" in program) == kernel
    assert ("scatter" in program) != kernel
    got = jax.jit(moe._add_rows)(acc, index, rows).reshape(N, d)
    want = jnp.zeros((N, d), dtype).at[index].add(rows, mode="drop")
    np.testing.assert_array_equal(np.asarray(got.astype(jnp.float32)),
                                  np.asarray(want.astype(jnp.float32)))
