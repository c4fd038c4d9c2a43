"""Fused Graph-Engine → Dense-Engine kernel (inter-stage pipelining).

The paper's GNNerator Controller lets the Dense Engine start as soon as the
Graph Engine has aggregated one *dimension block* of a destination shard
(§VI-A: "the Graph Engine only has to aggregate a small fraction of the
dimensions before the Dense Engine can begin"). On TPU there are no two
engines to synchronize — the equivalent is *fusion*: the aggregated block
h_agg is consumed by the feature-extraction matmul directly out of VMEM,
never round-tripping HBM, and the Dense Engine's partial sums over
dimension blocks accumulate in a second VMEM scratch.

    grid = (S_dst, D/B, S_src)
    for dst:
      for blockD:                      # dimension-blocking
        h_agg = 0
        for src:  h_agg += A[dst,src] @ h[src,:,blockD]      # Graph Engine
        out[dst] += h_agg @ W[blockD, :]                     # Dense Engine
      out[dst] = act(out[dst])
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import _activate

# the kernel's name in the compiled program and in the profiler's trace
KERNEL_NAME = "gnn_fused_aggregate_extract"


def _kernel(a_ref, h_ref, w_ref, o_ref, agg_ref, acc_ref, *, nd: int, ns: int,
            activation: str):
    d = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init_agg():
        agg_ref[...] = jnp.zeros_like(agg_ref)

    # Graph Engine step: aggregate source shard j into the resident block
    # (a bfloat16 grid meets h in bfloat16, as in shard_spmm).
    a = a_ref[...]
    agg_ref[...] += jnp.dot(
        a, h_ref[...].astype(a.dtype), preferred_element_type=jnp.float32
    )

    last_j = j == ns - 1

    @pl.when(jnp.logical_and(last_j, d == 0))
    def _init_out():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(last_j)
    def _dense_step():
        # Dense Engine step: consume the aggregated block from VMEM.
        acc_ref[...] += jnp.dot(
            agg_ref[...].astype(w_ref.dtype),
            w_ref[...],
            preferred_element_type=jnp.float32,
        )

    @pl.when(jnp.logical_and(last_j, d == nd - 1))
    def _writeback():
        o_ref[...] = _activate(acc_ref[...], activation).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_b", "activation", "interpret"))
def fused_gnn_layer(
    blocks: jax.Array,
    h: jax.Array,
    w: jax.Array,
    *,
    block_b: int = 128,
    activation: str = "none",
    interpret: bool,
) -> jax.Array:
    """act((A · H) · W) without materializing A·H in HBM.

    blocks: (S, S, n, n); h: (S, n, D); w: (D, F). Returns (S, n, F).
    """
    s, s2, n, n2 = blocks.shape
    s3, n3, d = h.shape
    d2, f = w.shape
    assert s == s2 == s3 and n == n2 == n3 and d == d2, (blocks.shape, h.shape, w.shape)
    assert d % block_b == 0, (d, block_b)
    nd = d // block_b
    grid = (s, nd, s)  # (dst, blockD, src)

    return pl.pallas_call(
        functools.partial(_kernel, nd=nd, ns=s, activation=activation),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, n, n), lambda i, bd, j: (i, j, 0, 0)),
            pl.BlockSpec((None, n, block_b), lambda i, bd, j: (j, 0, bd)),
            pl.BlockSpec((block_b, f), lambda i, bd, j: (bd, 0)),
        ],
        out_specs=pl.BlockSpec((None, n, f), lambda i, bd, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((s, n, f), h.dtype),
        scratch_shapes=[
            pltpu.VMEM((n, block_b), jnp.float32),  # h_agg (Graph Engine out)
            pltpu.VMEM((n, f), jnp.float32),        # Dense Engine accumulator
        ],
        interpret=interpret,
        name=KERNEL_NAME,
    )(blocks, h, w)
