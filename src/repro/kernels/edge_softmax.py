"""Graph Engine attention kernel: edge softmax and weighted aggregation of
every head in one walk of the graph's column-compacted edge tiles (GAT).

The destination nodes are cut into row tiles of R rows. For each row tile
the sorted distinct sources of its in-edges are cut into chunks of C
columns, and a chunk holds the (R × C) adjacency over its columns
(:func:`edge_tiles`). Chunks lie in row-tile order, and a row tile with
no edge still gets one (empty) chunk, so every output row is written:

    grid = (T,)                          # chunks, T a bound of the shapes
    for chunk k of row tile t:           # a row tile's chunks in a row
      if first[k]: m, l, acc = -inf, 0, 0     # per (row, head), in VMEM
      for head:
        e = LeakyReLU(s_dst[t] + s_src[cols[k]]) where mask[k] != 0
        m' = max(m, max_c e);  p = exp(e - m')
        l = l·exp(m - m') + Σ_c p
        acc[:, head] = acc[:, head]·exp(m - m') + p @ z[cols[k]][:, head]
      if last[k]: out[t] = acc / l      # 0 where a row has no edge

Only the source columns that hold an edge are walked: on full PubMed
(0.026% of the S²n² pairs are edges) a few percent of the dense grid's
entries. The running max and denominator follow the online softmax of
``flash_attention.py``, with the tile's mask in place of causality and
per-node scores in place of q·k; the attention weights α live only as the
(R × C) tile of one head in VMEM.

Layouts: the chunk's columns of z and of the source scores are gathered
once per layer before the kernel, as (T, C, H·F) and (T, H, C), so that
each head's source scores are one lane-dense row; the destination scores
come as (S·n/R, R, H), each head one column against the rows. The
per-head product p @ z multiplies the whole (C, H·F) tile, whose H·F lanes
the MXU pads to 128 anyway, and keeps the head's own F columns.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.utils import cdiv

# the kernel's name in the compiled program and in the profiler's trace
KERNEL_NAME = "gnn_edge_softmax_aggregate"

# the score of a pair with no edge: finite, so that a row with no edge yet
# subtracts it from itself without a NaN
_MASKED = -0.7 * float(jnp.finfo(jnp.float32).max)
# running max and denominator: one (rows, 128) tile per head, the value
# repeated over the lanes
_LANES = 128
# destination rows and source columns of a chunk. Fewer rows leave fewer
# columns without an edge in each chunk, more rows keep the MXU's weight
# tile longer (PERF.md §6)
_ROWS = 256
_WIDTH = 128


class EdgeTiles(NamedTuple):
    """A graph's in-edges as column-compacted (R × C) tiles, T of them
    (module doc). Numpy from :func:`edge_tiles`; device arrays once
    uploaded (``core.engines``), the mask then in bfloat16."""

    cols: object        # (T, C) int32: source node of each column
    mask: object        # (T, R, C): nonzero where the edge exists
    chunk_tile: object  # (T,) int32: the row tile of each chunk
    first: object       # (T,) int32: 1 on a row tile's first chunk
    last: object        # (T,) int32: 1 on a row tile's last chunk
    count: object       # (1,) int32: chunks in use; the rest are skipped


def tile_shape(n: int) -> tuple[int, int]:
    """``(rows, width)`` of a chunk for shards of ``n`` nodes: the most
    rows up to ``_ROWS``, a multiple of 8 dividing ``n`` (or ``n``
    itself), and ``_WIDTH`` columns."""
    for rows in range(min(n, _ROWS), 7, -1):
        if n % rows == 0 and rows % 8 == 0:
            return rows, _WIDTH
    return n, _WIDTH


def chunk_bound(num_rows: int, e_cap: int, rows: int, width: int) -> int:
    """T: the chunks of any graph of ``num_rows`` destination rows and at
    most ``e_cap`` edges. Each edge adds at most one column to one row
    tile, and each row tile adds one chunk beyond its columns' share."""
    return num_rows // rows + cdiv(e_cap, width)


def edge_tiles(src: np.ndarray, dst: np.ndarray, num_rows: int, *,
               e_cap: int, rows: int, width: int) -> EdgeTiles:
    """The edge tiles (numpy) of the edges ``src[i] -> dst[i]`` (node ids
    below ``num_rows``; a repeated edge is one entry of the mask), padded
    to ``chunk_bound(num_rows, e_cap, rows, width)`` chunks: their shapes
    depend on those four numbers alone. Padding columns point at node 0
    with no edge; padding chunks repeat the last row tile. Raises
    ``ValueError`` where the edges need more chunks than that bound (more
    distinct edges than ``e_cap``)."""
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    if num_rows % rows:
        raise ValueError(f"{rows} rows do not divide {num_rows}")
    tiles = num_rows // rows
    t_max = chunk_bound(num_rows, e_cap, rows, width)
    # the distinct (row tile, source) pairs, by tile, then source
    pair, inv = np.unique(dst // rows * num_rows + src, return_inverse=True)
    p_tile, p_src = pair // num_rows, pair % num_rows
    per_tile = np.bincount(p_tile, minlength=tiles)
    rank = np.arange(pair.size) - (np.cumsum(per_tile) - per_tile)[p_tile]
    chunks = np.maximum(1, -(-per_tile // width))
    start = np.cumsum(chunks) - chunks
    used = int(chunks.sum())
    if used > t_max:
        raise ValueError(f"the edges need {used} chunks, more than the "
                         f"{t_max} that {e_cap} edges allow")
    p_chunk = start[p_tile] + rank // width
    p_col = rank % width
    cols = np.zeros((t_max, width), np.int32)
    cols[p_chunk, p_col] = p_src
    mask = np.zeros((t_max, rows, width), np.bool_)
    mask[p_chunk[inv], dst % rows, p_col[inv]] = True
    chunk_tile = np.full(t_max, tiles - 1, np.int32)
    chunk_tile[:used] = np.repeat(np.arange(tiles), chunks)
    first = np.zeros(t_max, np.int32)
    first[start] = 1
    last = np.zeros(t_max, np.int32)
    last[start + chunks - 1] = 1
    return EdgeTiles(cols, mask, chunk_tile, first, last,
                     np.array([used], np.int32))


def _kernel(tile_ref, first_ref, last_ref, count_ref,
            a_ref, z_ref, ss_ref, sd_ref, o_ref, m_ref, l_ref, acc_ref, *,
            heads: int, f: int, slope: float):
    k = pl.program_id(0)  # chunk; a row tile's chunks accumulate
    live = k < count_ref[0]
    col = jax.lax.broadcasted_iota(jnp.int32, (1, acc_ref.shape[-1]), 1)

    @pl.when(live & (first_ref[k] == 1))
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _MASKED)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _step():
        # the mask in float32 layout, once for all heads: a bfloat16 tile
        # compared as it is cost 3% more a layer on v5e (PERF.md §6)
        edge = a_ref[...].astype(jnp.float32) != 0  # (R, C), all heads
        z = z_ref[...].astype(jnp.float32)          # (C, H·F)
        s_src = ss_ref[...].astype(jnp.float32)     # (H, C)
        s_dst = sd_ref[...].astype(jnp.float32)     # (R, H)
        acc = acc_ref[...]
        for h in range(heads):
            e = s_dst[:, h:h + 1] + s_src[h:h + 1, :]   # (R, C)
            e = jnp.maximum(e, slope * e)           # LeakyReLU, 0 <= slope <= 1
            e = jnp.where(edge, e, _MASKED)
            m_prev = m_ref[h]                       # (R, 128)
            m_new = jnp.maximum(m_prev, jnp.max(e, axis=1, keepdims=True))
            m1 = m_new[:, :1]
            alpha = jnp.exp(m_prev[:, :1] - m1)     # rescales the old sums
            # a pair with no edge gives exp(_MASKED - m1) = 0 once the row
            # has met an edge; before that it gives 1, and the first
            # edge's alpha of 0 clears what it added
            p = jnp.exp(e - m1)
            l_ref[h] = alpha * l_ref[h] + jnp.sum(p, axis=1, keepdims=True)
            m_ref[h] = m_new
            pz = jnp.dot(p, z, preferred_element_type=jnp.float32)
            own = (col >= h * f) & (col < (h + 1) * f)
            acc = jnp.where(own, acc * alpha + pz, acc)
        acc_ref[...] = acc

    @pl.when(live & (last_ref[k] == 1))
    def _finish():
        scale = jnp.zeros(acc_ref.shape, jnp.float32)
        for h in range(heads):
            m, l = m_ref[h][:, :1], l_ref[h][:, :1]
            inv = jnp.where(m > 0.5 * _MASKED, 1.0 / jnp.maximum(l, 1e-30),
                            0.0)                    # a row with no edge: 0
            own = (col >= h * f) & (col < (h + 1) * f)
            scale = jnp.where(own, inv, scale)
        o_ref[...] = (acc_ref[...] * scale).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("heads", "negative_slope",
                                             "interpret"))
def edge_softmax_aggregate(
    tiles: EdgeTiles,
    z: jax.Array,
    s_src: jax.Array,
    s_dst: jax.Array,
    *,
    heads: int,
    negative_slope: float,
    interpret: bool,
) -> jax.Array:
    """out[i, v, head] = Σ_{j,u} α[i,j,v,u,head] z[j, u, head], with α the
    softmax over the edges u -> v of the tiles of
    LeakyReLU(s_dst[i, v, head] + s_src[j, u, head]).

    tiles: the graph's :class:`EdgeTiles` on the device; z: (S, n, H·F)
    source features, head-major; s_src, s_dst: (S, n, H) source and
    destination scores. Returns (S, n, H·F); a row with no edge is 0.
    """
    s, n, d = z.shape
    t_max, rows, width = tiles.mask.shape
    assert d % heads == 0, (z.shape, heads)
    assert s_src.shape == s_dst.shape == (s, n, heads), (s_src.shape,
                                                          s_dst.shape)
    assert tiles.cols.shape == (t_max, width), tiles.cols.shape
    assert (s * n) % rows == 0, (s, n, rows)
    assert 0.0 <= negative_slope <= 1.0, negative_slope
    # each chunk's columns of z and of the source scores, once per layer
    cols = tiles.cols.reshape(-1)
    z_cols = jnp.take(z.reshape(s * n, d), cols, axis=0, mode="clip")
    s_cols = jnp.take(s_src.reshape(s * n, heads), cols, axis=0,
                      mode="clip")
    z_cols = z_cols.reshape(t_max, width, d)
    s_cols = jnp.swapaxes(s_cols.reshape(t_max, width, heads), 1, 2)
    s_rows = s_dst.reshape(s * n // rows, rows, heads)

    def chunk(k, tile, first, last, count):
        # a padding chunk reads the last chunk in use again: no new copy
        return (jnp.minimum(k, count[0] - 1), 0, 0)

    def row_tile(k, tile, first, last, count):
        return (tile[k], 0, 0)

    out = pl.pallas_call(
        functools.partial(_kernel, heads=heads, f=d // heads,
                          slope=float(negative_slope)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(t_max,),
            in_specs=[
                pl.BlockSpec((None, rows, width), chunk),   # mask
                pl.BlockSpec((None, width, d), chunk),      # z[cols]
                pl.BlockSpec((None, heads, width), chunk),  # s_src[cols]
                pl.BlockSpec((None, rows, heads), row_tile),
            ],
            out_specs=pl.BlockSpec((None, rows, d), row_tile),
            scratch_shapes=[pltpu.VMEM((heads, rows, _LANES), jnp.float32),
                            pltpu.VMEM((heads, rows, _LANES), jnp.float32),
                            pltpu.VMEM((rows, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((s * n // rows, rows, d), z.dtype),
        interpret=interpret,
        name=KERNEL_NAME,
    )(tiles.chunk_tile, tiles.first, tiles.last, tiles.count,
      tiles.mask, z_cols, s_cols, s_rows)
    return out.reshape(s, n, d)
