"""Dense Engine kernel (paper §III-A) — Pallas blocked matmul on the MXU.

The ASIC's 2-D systolic array with double-buffered input/weight/output
scratchpads and *partial-sum reload* maps to: a (bm × bn) f32 accumulator
held in VMEM scratch, K-blocked accumulation over the contraction axis
(the psum "reload" never leaves VMEM), fused bias + activation on the last
K step (the ASIC's 1-D activation unit), and Pallas's implicit grid
pipelining standing in for double-buffering.

Target: TPU (MXU-aligned tiles, multiples of 128). Validated on CPU via
interpret mode against kernels/ref.py::dense_engine.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import _activate

# the kernel's name in the compiled program and in the profiler's trace
KERNEL_NAME = "gnn_dense_engine"


def _kernel(x_ref, w_ref, b_ref, o_ref, acc_ref, *, activation: str, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...], w_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(k == nk - 1)
    def _finish():
        out = acc_ref[...]
        if b_ref is not None:
            out = out + b_ref[...].astype(jnp.float32)
        o_ref[...] = _activate(out, activation).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("activation", "bm", "bn", "bk", "interpret"),
)
def dense_engine_matmul(
    x: jax.Array,
    w: jax.Array,
    b: jax.Array | None = None,
    *,
    activation: str = "none",
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool,
) -> jax.Array:
    """act(x @ w + b) with explicit VMEM tiling.

    x: (M, K), w: (K, N), b: (N,) optional. M/K/N must be divisible by the
    block sizes (ops.py pads).
    """
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (x.shape, w.shape, bm, bn, bk)
    nk = k // bk
    grid = (m // bm, n // bn, nk)

    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
        pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
    ]
    args = [x, w]
    if b is not None:
        # bias as a (1, N) row: a 1-D block would need Mosaic's 1-D tiling
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)))
        args.append(b.reshape(1, n))
        kernel = functools.partial(_kernel, activation=activation, nk=nk)
    else:
        kernel = functools.partial(
            lambda xr, wr, orf, accr, **kw: _kernel(xr, wr, None, orf, accr, **kw),
            activation=activation,
            nk=nk,
        )

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        name=KERNEL_NAME,
    )(*args)
