"""Graph Engine linear-aggregation kernel with feature dimension-blocking.

This kernel IS the paper's Algorithm 1 expressed as a Pallas grid:

    grid = (D/B, S_dst, S_src)          # (blockD, dst, src) loop nest
    for blockD:                          # dimension-blocking outer loop
      for dst:                           # dst-stationary traversal
        for src:                         # moving source shards
          out[dst, :, blockD] += A[dst, src] @ h[src, :, blockD]

Only an (n × B) feature tile per shard is resident in VMEM at a time —
exactly the paper's trade: larger shards (n) for a fixed on-chip budget at
the cost of walking the shard grid D/B times. The densified (n × n)
adjacency block feeds the MXU (the TPU-native replacement for the ASIC's
edge-by-edge SIMD Apply/Reduce lanes; see DESIGN.md §2).

The (n × B) f32 accumulator in VMEM scratch plays the role of the Graph
Engine's destination scratchpad: destination features stay resident until
fully aggregated (dst-stationary), then are written back once.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the kernel's name in the compiled program and in the profiler's trace
KERNEL_NAME = "gnn_shard_spmm"


def _kernel(a_ref, h_ref, o_ref, acc_ref, *, ns: int):
    j = pl.program_id(2)  # src shard (innermost, accumulated)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # a bfloat16 grid (a TPU at the default matmul precision) meets each
    # feature tile rounded to bfloat16 here, as the MXU's one pass rounds
    # a float32 operand; rounding h outside the kernel instead made XLA
    # move the next layer's input out of fast memory (PERF.md §6)
    a = a_ref[...]
    acc_ref[...] += jnp.dot(
        a, h_ref[...].astype(a.dtype), preferred_element_type=jnp.float32
    )

    @pl.when(j == ns - 1)
    def _writeback():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def shard_spmm(
    blocks: jax.Array,
    h: jax.Array,
    *,
    block_b: int = 128,
    interpret: bool,
) -> jax.Array:
    """out[i] = sum_j A[i, j] @ h[j], feature-blocked.

    blocks: (S_dst, S_src, n, n) densified adjacency; h: (S_src, n, D)
    shard-grouped node features; D must be divisible by block_b (ops.py
    pads). Returns (S_dst, n, D). The grid may be rectangular — the
    sharded executable (dist/gnn.py) hands each data-group its own
    contiguous dst rows against the full gathered source grid.
    """
    s, s_src, n, n2 = blocks.shape
    s3, n3, d = h.shape
    assert s_src == s3 and n == n2 == n3, (blocks.shape, h.shape)
    assert d % block_b == 0, (d, block_b)
    grid = (d // block_b, s, s_src)  # (blockD, dst, src) — Algorithm 1

    return pl.pallas_call(
        functools.partial(_kernel, ns=s_src),
        grid=grid,
        in_specs=[
            # adjacency block for (dst=i, src=j); dims 0,1 squeezed
            pl.BlockSpec((None, None, n, n), lambda bd, i, j: (i, j, 0, 0)),
            # source features: shard j, dimension block bd
            pl.BlockSpec((None, n, block_b), lambda bd, i, j: (j, 0, bd)),
        ],
        out_specs=pl.BlockSpec((None, n, block_b), lambda bd, i, j: (i, 0, bd)),
        out_shape=jax.ShapeDtypeStruct((s, n, d), h.dtype),
        scratch_shapes=[pltpu.VMEM((n, block_b), jnp.float32)],
        interpret=interpret,
        name=KERNEL_NAME,
    )(blocks, h)
