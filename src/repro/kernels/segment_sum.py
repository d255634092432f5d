"""Pallas TPU kernel: segment sum — the paper's aggregation hot-spot.

The group-by-SUM reducer (paper §V) reduces to: given values and their
(sorted) segment ids, produce per-segment sums.  TPU adaptation: instead
of a scalar scatter-add loop (GPU-style atomics have no TPU analogue),
each (segment-tile × input-block) cell becomes a one-hot **matmul** on
the MXU:   out[t0:t0+T] += v_blk (1×B) @ onehot(ids_blk − t0) (B×T).

With ids sorted, the cells whose id ranges meet form a monotone
staircase through the (tile, block) plane, so the grid walks only that
path: ``n_blocks + n_tiles − 1`` cells, each visited once, instead of
all ``n_tiles × n_blocks``.  The path is computed on the host side of
the call from each block's last id and handed to the index maps by
scalar prefetch; cells on the path whose ids miss the tile skip their
matmul under ``pl.when``.  Unsorted ids are sorted first.

Arrays are laid out along the lane axis, ``(1, N)`` tiled by
``(1, block)``: Mosaic needs a block's last two dims (8, 128)-aligned
or equal to the array's.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Block row of the (1, N) lane layout; a typed int32 so that index maps
# stay 32-bit under x64.
_ROW = np.int32(0)


def _kernel(tile_ref, block_ref, ids_ref, val_ref, out_ref, *,
            seg_tile: int, block: int):
    del block_ref                       # consumed by the index maps
    w = pl.program_id(0)
    tile = tile_ref[w]

    # The path visits a tile's cells consecutively; its first cell
    # zeroes the output block, later ones accumulate into it.
    @pl.when((w == 0) | (tile_ref[jnp.maximum(w - 1, 0)] != tile))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    ids = ids_ref[0, :]
    t0 = tile * seg_tile

    @pl.when((ids[block - 1] >= t0) & (ids[0] < t0 + seg_tile))
    def _accumulate():
        v = val_ref[0, :]
        local = ids - t0
        onehot = (
            local[:, None] == jax.lax.broadcasted_iota(jnp.int32, (block, seg_tile), 1)
        ).astype(jnp.float32)
        contrib = jnp.dot(v[None, :], onehot,
                          precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)  # (1, T)
        out_ref[...] += contrib


def _staircase(last_tile: jnp.ndarray, n_tiles: int):
    """(tile, block) of every cell on the monotone path.

    ``last_tile[b]`` is the tile of block b's largest id (non-decreasing
    in b).  Block b's cells run from tile ``last_tile[b-1]`` to
    ``last_tile[b]``, so block b starts at path index
    ``b + last_tile[b-1]`` and every index ``w`` is reached by
    ``block + tile == w`` moves."""
    n_blocks = last_tile.shape[0]
    w = jnp.arange(n_blocks + n_tiles - 1, dtype=jnp.int32)
    starts = jnp.arange(1, n_blocks, dtype=jnp.int32) + last_tile[:-1]
    blk = jnp.searchsorted(starts, w, side="right").astype(jnp.int32)
    return w - blk, blk


@functools.partial(jax.jit, static_argnames=("num_segments", "seg_tile",
                                             "block", "indices_are_sorted",
                                             "interpret"))
def segment_sum(values: jnp.ndarray, segment_ids: jnp.ndarray,
                num_segments: int, *, seg_tile: int = 512, block: int = 1024,
                indices_are_sorted: bool = False,
                interpret: bool = False) -> jnp.ndarray:
    """Per-segment sums of ``values`` (float32 accumulation).

    values/segment_ids: (N,).  Ids outside [0, num_segments) are dropped.
    ``indices_are_sorted`` promises non-decreasing ids over the in-range
    prefix with every out-of-range id after it (the group-by's run-head
    ids); otherwise the pairs are sorted by id first.
    Result: (num_segments,) float32.
    """
    n = values.shape[0]
    block = min(block, max(128, 1 << (n - 1).bit_length())) if n else block
    pad_n = -n % block
    seg_tile = min(seg_tile, max(128, 1 << (max(num_segments, 1) - 1).bit_length()))
    pad_s = -num_segments % seg_tile

    # Out-of-range ids (incl. padding) -> sentinel segment beyond the last
    # tile so they never accumulate.  In range, ids fit int32 whatever
    # their input width, so the kernel sees 32-bit lanes only.
    n_seg_pad = num_segments + pad_s
    sentinel = n_seg_pad + seg_tile
    ids = jnp.where((segment_ids >= 0) & (segment_ids < num_segments),
                    segment_ids, sentinel).astype(jnp.int32)
    vals = values.astype(jnp.float32)
    if not indices_are_sorted:
        ids, vals = jax.lax.sort((ids, vals), num_keys=1)
    ids = jnp.pad(ids, (0, pad_n), constant_values=sentinel)
    vals = jnp.pad(vals, (0, pad_n))

    n_blocks = (n + pad_n) // block
    n_tiles = n_seg_pad // seg_tile
    last_tile = jnp.minimum(ids[block - 1::block] // seg_tile, n_tiles - 1)
    tiles, blocks = _staircase(last_tile, n_tiles)

    out = pl.pallas_call(
        functools.partial(_kernel, seg_tile=seg_tile, block=block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_blocks + n_tiles - 1,),
            in_specs=[
                pl.BlockSpec((1, block), lambda w, t, b: (_ROW, b[w])),
                pl.BlockSpec((1, block), lambda w, t, b: (_ROW, b[w])),
            ],
            out_specs=pl.BlockSpec((1, seg_tile), lambda w, t, b: (_ROW, t[w])),
        ),
        out_shape=jax.ShapeDtypeStruct((1, n_seg_pad), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="segment_sum",
    )(tiles, blocks, ids.reshape(1, -1), vals.reshape(1, -1))
    return out.reshape(-1)[:num_segments]
