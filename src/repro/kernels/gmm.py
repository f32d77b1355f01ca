"""Grouped matmul kernel for MoE expert GEMMs (Pallas / TPU).

The MoE hot loop after sort-based dispatch is a ragged batched GEMM:
rows of x are sorted by expert, each contiguous row-group multiplies a
different expert's weight matrix. The dense alternatives either waste
FLOPs (one-hot dispatch einsum over capacity slots) or HBM (gathering
w[g(t)] per token). The kernel instead walks row tiles; a scalar-prefetch
array maps each row tile to its expert, so the weight tile index_map picks
the right expert's [D, BF] tile — each expert's weights stream through VMEM
exactly once per F-tile pass, and every row tile is a dense MXU matmul.

The ops wrapper pads each group to the row-tile boundary so a tile never
spans two experts (padding rows multiply real weights but are dropped on
gather-back; the FLOP overhead is <= E * (BT-1) rows, negligible for
tokens >> experts * BT).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

DEFAULT_BLOCK_T = 128
DEFAULT_BLOCK_F = 512


LANE = 128


def _fit_block(dim: int, pref: int) -> int:
    """Lane tile for a dim of ``dim``: the largest multiple of 128 that
    divides it and is <= ``pref`` (at least 128), or the whole dim when
    it is not a multiple of 128 (Mosaic takes a full-extent block)."""
    if dim % LANE:
        return dim
    b = max(min(dim, pref) // LANE * LANE, LANE)
    while dim % b:
        b -= LANE
    return b


def _gmm_kernel(tile_eid_ref, x_ref, w_ref, o_ref):
    del tile_eid_ref  # consumed by the index maps
    x = x_ref[...]                                    # [BT, D]
    w = w_ref[0]                                      # [D, BF]
    o_ref[...] = jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_t", "block_f", "interpret"))
def gmm_padded(
    x: Array,                    # [Tp, D] — group-aligned (padded) rows
    w: Array,                    # [E, D, F]
    tile_eid: Array,             # [Tp // block_t] int32 expert of each row tile
    *,
    block_t: int = DEFAULT_BLOCK_T,
    block_f: int = DEFAULT_BLOCK_F,
    interpret: bool = True,
) -> Array:
    tp, d = x.shape
    e, _, f = w.shape
    block_t = min(block_t, tp)
    block_f = _fit_block(f, block_f)
    assert tp % block_t == 0 and f % block_f == 0
    grid = (tp // block_t, f // block_f)

    return pl.pallas_call(
        _gmm_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_t, d), lambda tb, fb, eid: (tb, 0)),
                pl.BlockSpec((1, d, block_f),
                             lambda tb, fb, eid: (eid[tb], 0, fb)),
            ],
            out_specs=pl.BlockSpec((block_t, block_f),
                                   lambda tb, fb, eid: (tb, fb)),
        ),
        out_shape=jax.ShapeDtypeStruct((tp, f), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(tile_eid.astype(jnp.int32), x, w)


def pad_groups(x_sorted: Array, group_sizes: Array, block_t: int
               ) -> tuple[Array, Array, Array]:
    """Scatter group-sorted rows into a group-aligned padded buffer.

    Returns (x_padded [Tp, D], tile_eid [Tp // block_t], row_map [T] int32)
    where row_map gives each original row's position in the padded buffer.
    Tp = T rounded up so each group starts on a block_t boundary (static:
    T + E * block_t, the worst case).
    """
    t, _ = x_sorted.shape
    e = group_sizes.shape[0]
    tp = (t + e * block_t + block_t - 1) // block_t * block_t

    offs = jnp.concatenate([jnp.zeros((1,), group_sizes.dtype),
                            jnp.cumsum(group_sizes)[:-1]])
    pad_sizes = (group_sizes + block_t - 1) // block_t * block_t
    pad_offs = jnp.concatenate([jnp.zeros((1,), group_sizes.dtype),
                                jnp.cumsum(pad_sizes)[:-1]])
    # row i of group g sits at pad_offs[g] + (i - offs[g])
    gid = jnp.searchsorted(jnp.cumsum(group_sizes), jnp.arange(t), side="right")
    row_map = (jnp.take(pad_offs, gid) + jnp.arange(t)
               - jnp.take(offs, gid)).astype(jnp.int32)
    x_padded = jnp.zeros((tp, x_sorted.shape[1]), x_sorted.dtype)
    x_padded = x_padded.at[row_map].set(x_sorted)
    # expert of each row tile: tile k covers rows [k*bt, (k+1)*bt)
    tile_starts = jnp.arange(tp // block_t) * block_t
    tile_eid = jnp.searchsorted(jnp.cumsum(pad_sizes), tile_starts,
                                side="right").astype(jnp.int32)
    tile_eid = jnp.minimum(tile_eid, e - 1)
    return x_padded, tile_eid, row_map


def gmm(x_sorted: Array, w: Array, group_sizes: Array, *,
        block_t: int = DEFAULT_BLOCK_T, block_f: int = DEFAULT_BLOCK_F,
        interpret: bool = True) -> Array:
    """Ragged grouped matmul: pad to tiles, run the kernel, gather back."""
    x_pad, tile_eid, row_map = pad_groups(x_sorted, group_sizes, block_t)
    out_pad = gmm_padded(x_pad, w, tile_eid, block_t=block_t,
                         block_f=block_f, interpret=interpret)
    return jnp.take(out_pad, row_map, axis=0)
