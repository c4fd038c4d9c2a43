"""Graph Engine attention kernel: edge softmax and weighted aggregation of
every head in one walk of the shard grid (GAT).

    grid = (S_dst, n/R, S_src)
    for dst, row tile:                   # dst-stationary, R rows at a time
      m, l, acc = -inf, 0, 0             # per (row, head), in VMEM
      for src:                           # moving source shards
        for head:
          e = LeakyReLU(s_dst[v] + s_src[u]) where A[dst, src, v, u] != 0
          m' = max(m, max_u e);  p = exp(e - m')
          l = l·exp(m - m') + Σ_u p
          acc[:, head] = acc[:, head]·exp(m - m') + p @ z[src][:, head]
      out[dst, rows] = acc / l           # 0 where a row has no edge

The adjacency block is read once for all heads, and the attention weights
α live only as the (R × n) tile of one head in VMEM: no α grid ever
reaches HBM. The running max and denominator follow the online softmax of
``flash_attention.py``, with the adjacency as the mask and per-node scores
in place of q·k.

Layouts: the source scores come as (S, H, n), so that each head is one
lane-dense row, and the destination scores as (S, n, H), so that each head
is one column against the rows. The per-head product p @ z multiplies the
whole (n, H·F) source tile, whose H·F lanes the MXU pads to 128 anyway,
and keeps the head's own F columns.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the kernel's name in the compiled program and in the profiler's trace
KERNEL_NAME = "gnn_edge_softmax_aggregate"

# the score of a pair with no edge: finite, so that a row with no edge yet
# subtracts it from itself without a NaN
_MASKED = -0.7 * float(jnp.finfo(jnp.float32).max)
# running max and denominator: one (rows, 128) tile per head, the value
# repeated over the lanes
_LANES = 128
# destination rows resident at a time, so that one (rows, n) f32 temporary
# is 2 MiB: 512 of n = 1024 fits v5e's scoped VMEM and ran 11% faster than
# 256 (8.9 against 10.0 ms a layer at full PubMed shapes, one v5e)
_TILE_ELEMS = 512 * 1024


def block_rows(n: int) -> int:
    """The most destination rows, a multiple of 8 dividing ``n`` (or ``n``
    itself), whose (rows, n) tile holds at most ``_TILE_ELEMS``."""
    for rows in range(min(n, max(8, _TILE_ELEMS // n)), 7, -1):
        if n % rows == 0 and (rows % 8 == 0 or rows == n):
            return rows
    return n


def _kernel(a_ref, z_ref, ss_ref, sd_ref, o_ref, m_ref, l_ref, acc_ref, *,
            heads: int, f: int, slope: float, ns: int):
    j = pl.program_id(2)  # src shard (innermost, accumulated)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _MASKED)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # the mask in float32 layout, once for all heads: a bfloat16 tile
    # compared as it is cost 3% more a layer on v5e (PERF.md §6)
    edge = a_ref[...].astype(jnp.float32) != 0      # (R, n), all heads
    z = z_ref[...].astype(jnp.float32)              # (n, H·F)
    s_src = ss_ref[...].astype(jnp.float32)         # (H, n)
    s_dst = sd_ref[...].astype(jnp.float32)         # (R, H)
    col = jax.lax.broadcasted_iota(jnp.int32, (1, z.shape[-1]), 1)
    acc = acc_ref[...]
    for h in range(heads):
        e = s_dst[:, h:h + 1] + s_src[h:h + 1, :]   # (R, n)
        e = jnp.maximum(e, slope * e)               # LeakyReLU, 0 <= slope <= 1
        e = jnp.where(edge, e, _MASKED)
        m_prev = m_ref[h]                           # (R, 128)
        m_new = jnp.maximum(m_prev, jnp.max(e, axis=1, keepdims=True))
        m1 = m_new[:, :1]
        alpha = jnp.exp(m_prev[:, :1] - m1)         # rescales the old sums
        # a pair with no edge gives exp(_MASKED - m1) = 0 once the row has
        # met an edge; before that it gives 1, and the first edge's alpha
        # of 0 clears what it added
        p = jnp.exp(e - m1)
        l_ref[h] = alpha * l_ref[h] + jnp.sum(p, axis=1, keepdims=True)
        m_ref[h] = m_new
        pz = jnp.dot(p, z, preferred_element_type=jnp.float32)
        own = (col >= h * f) & (col < (h + 1) * f)
        acc = jnp.where(own, acc * alpha + pz, acc)
    acc_ref[...] = acc

    @pl.when(j == ns - 1)
    def _finish():
        scale = jnp.zeros_like(acc)
        for h in range(heads):
            m, l = m_ref[h][:, :1], l_ref[h][:, :1]
            inv = jnp.where(m > 0.5 * _MASKED, 1.0 / jnp.maximum(l, 1e-30),
                            0.0)                    # a row with no edge: 0
            own = (col >= h * f) & (col < (h + 1) * f)
            scale = jnp.where(own, inv, scale)
        o_ref[...] = (acc_ref[...] * scale).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("heads", "negative_slope",
                                             "rows", "interpret"))
def edge_softmax_aggregate(
    blocks: jax.Array,
    z: jax.Array,
    s_src: jax.Array,
    s_dst: jax.Array,
    *,
    heads: int,
    negative_slope: float,
    rows: int | None = None,
    interpret: bool,
) -> jax.Array:
    """out[i, v, head] = Σ_{j,u} α[i,j,v,u,head] z[j, u, head], with α the
    softmax over the u of A[i, j, v, u] != 0 of
    LeakyReLU(s_dst[i, v, head] + s_src[j, u, head]).

    blocks: (S, S, n, n) adjacency, nonzero where an edge u -> v exists;
    z: (S, n, H·F) source features, head-major; s_src: (S, H, n) source
    scores; s_dst: (S, n, H) destination scores. Returns (S, n, H·F); a
    row with no edge at all is 0. ``rows`` destination rows are resident
    at a time (None: ``block_rows(n)``).
    """
    s, s_src_n, n, n2 = blocks.shape
    d = z.shape[-1]
    assert s_src_n == s and n == n2, blocks.shape
    assert z.shape == (s, n, d) and d % heads == 0, (z.shape, heads)
    assert s_src.shape == (s, heads, n), s_src.shape
    assert s_dst.shape == (s, n, heads), s_dst.shape
    assert 0.0 <= negative_slope <= 1.0, negative_slope
    rows = block_rows(n) if rows is None else rows
    assert n % rows == 0, (n, rows)
    grid = (s, n // rows, s)  # (dst, row tile, src)

    return pl.pallas_call(
        functools.partial(_kernel, heads=heads, f=d // heads,
                          slope=float(negative_slope), ns=s),
        grid=grid,
        in_specs=[
            # adjacency rows of (dst=i, src=j), read once for all heads
            pl.BlockSpec((None, None, rows, n),
                         lambda i, r, j: (i, j, r, 0)),
            pl.BlockSpec((None, n, d), lambda i, r, j: (j, 0, 0)),
            pl.BlockSpec((None, heads, n), lambda i, r, j: (j, 0, 0)),
            pl.BlockSpec((None, rows, heads), lambda i, r, j: (i, r, 0)),
        ],
        out_specs=pl.BlockSpec((None, rows, d), lambda i, r, j: (i, r, 0)),
        out_shape=jax.ShapeDtypeStruct((s, n, d), z.dtype),
        scratch_shapes=[pltpu.VMEM((heads, rows, _LANES), jnp.float32),
                        pltpu.VMEM((heads, rows, _LANES), jnp.float32),
                        pltpu.VMEM((rows, d), jnp.float32)],
        interpret=interpret,
        name=KERNEL_NAME,
    )(blocks, z, s_src, s_dst)
