"""Graph Engine gather/scatter kernel for non-linear aggregation.

Max-pool aggregation (GraphsagePool) is not a matmul, so the densified
shard_spmm path does not apply. This kernel keeps the ASIC's edge-by-edge
view: the Edge Fetcher walks the shard's COO edge list, the Feature Fetcher
gathers source rows, and the SIMD Reduce lane scatter-reduces into the
destination scratchpad — all on an (n × B) dimension block resident in
VMEM, with the same (blockD, dst, src) loop nest as shard_spmm.

TPU form: each edge is packed into one int32 (dst·n + src, -1 for an
empty slot); a shard pair's packed list is DMA'd from HBM into SMEM (so
the scalar core can index it), and the body loops over the edges, moving
one (1 × B) source row per edge with dynamic sublane slices — Mosaic has
no vector gather/scatter over VMEM rows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the kernel's name in the compiled program and in the profiler's trace
KERNEL_NAME = "gnn_seg_gather"

_NEG = -3.0e38  # python float: jnp constants would be captured as consts
_TILE = 1024    # Mosaic's tiling of a 1-D int32 array in HBM


def _kernel(key_hbm, h_ref, o_ref, acc_ref, key_smem, sem, *, ns: int,
            ne: int, n: int, op: str):
    i, j = pl.program_id(1), pl.program_id(2)
    # Edge Fetcher: this shard pair's packed edge list, HBM -> SMEM
    start = pl.multiple_of((i * ns + j) * ne, _TILE)
    fetch = pltpu.make_async_copy(key_hbm.at[pl.ds(start, ne)], key_smem,
                                  sem)
    fetch.start()

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.full_like(acc_ref, _NEG if op == "max" else 0.0)

    fetch.wait()

    def edge(e, carry):
        key = key_smem[e]

        @pl.when(key >= 0)
        def _reduce():
            row = h_ref[pl.ds(key % n, 1), :].astype(jnp.float32)
            dst = pl.ds(key // n, 1)
            cur = acc_ref[dst, :]
            acc_ref[dst, :] = (jnp.maximum(cur, row) if op == "max"
                               else cur + row)
        return carry

    jax.lax.fori_loop(0, ne, edge, 0)

    @pl.when(j == ns - 1)
    def _writeback():
        out = acc_ref[...]
        if op == "max":
            out = jnp.where(out <= _NEG / 2, 0.0, out)
        o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("op", "block_b", "interpret"))
def seg_gather_aggregate(
    edge_src: jax.Array,
    edge_dst: jax.Array,
    edge_valid: jax.Array,
    h: jax.Array,
    *,
    op: str = "max",
    block_b: int = 128,
    interpret: bool,
) -> jax.Array:
    """Edge-list shard-grid aggregation, feature-blocked.

    edge_src/edge_dst: (S, S, E) int32 local ids; edge_valid: (S, S, E)
    int8/bool; h: (S, n, D). Returns (S, n, D) aggregated per destination.
    """
    s, s2, e = edge_src.shape
    s3, n, d = h.shape
    assert s == s2 == s3, (edge_src.shape, h.shape)
    assert d % block_b == 0, (d, block_b)
    assert op in ("max", "sum"), op
    assert n * n < 2 ** 31, n          # packed (dst, src) fits an int32
    # one int32 per edge: dst·n + src, or -1 for an empty slot
    # flattened 1-D in HBM, each pair's list padded to whole 1-D tiles so
    # every per-pair DMA slice is tile-aligned
    ne = -(-e // _TILE) * _TILE
    key = jnp.where(edge_valid.astype(bool),
                    edge_dst.astype(jnp.int32) * n
                    + edge_src.astype(jnp.int32), -1)
    key = jnp.pad(key, ((0, 0), (0, 0), (0, ne - e)),
                  constant_values=-1).reshape(-1)
    grid = (d // block_b, s, s)  # (blockD, dst, src)

    return pl.pallas_call(
        functools.partial(_kernel, ns=s, ne=ne, n=n, op=op),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.HBM),
            pl.BlockSpec((None, n, block_b), lambda bd, i, j: (j, 0, bd)),
        ],
        out_specs=pl.BlockSpec((None, n, block_b), lambda bd, i, j: (i, 0, bd)),
        out_shape=jax.ShapeDtypeStruct((s, n, d), h.dtype),
        scratch_shapes=[pltpu.VMEM((n, block_b), jnp.float32),
                        pltpu.SMEM((ne,), jnp.int32),
                        pltpu.SemaphoreType.DMA(())],
        interpret=interpret,
        name=KERNEL_NAME,
    )(key, h)
