"""The arithmetic a plain reference runs in.

``highest``: float32 arrays, every matmul at ``Precision.HIGHEST`` and
float32 sums: the reference itself. The lower modes are the controls:
the same reference computed the way a later change to the program might
be tempted to compute it.

``bfloat16``: every array, product and sum in bfloat16.
``int8`` and ``fp8``: every operand of a matmul or of an aggregation
(features, weights, edge weights, activations) rounded with one scale per
tensor, to 255 symmetric levels or to float8_e4m3fn (its largest value at
the tensor's largest magnitude), then float32 sums; in a backward pass
the incoming gradient of each matmul and aggregation is rounded the same
way, and the rounding of the operands passes gradients straight through.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

MODES = ("highest", "bfloat16", "int8", "fp8")


def _int8(a):
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 127.0
    return jnp.round(a / scale) * scale


def _fp8(a):
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 448.0
    return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


_ROUND = {"int8": _int8, "fp8": _fp8}


def _round_grad(mode: str):
    @jax.custom_vjp
    def f(x):
        return x

    f.defvjp(lambda x: (x, None), lambda _, g: (_ROUND[mode](g),))
    return f


class Numerics:
    def __init__(self, mode: str = "highest"):
        if mode not in MODES:
            raise ValueError(f"precision mode must be one of {MODES}, "
                             f"got {mode!r}")
        self.mode = mode
        self.dtype = jnp.bfloat16 if mode == "bfloat16" else jnp.float32
        if mode in _ROUND:
            self._round_out = _round_grad(mode)

    def cast(self, a):
        return jnp.asarray(a).astype(self.dtype)

    def _q(self, a):
        if self.mode not in _ROUND:
            return a.astype(self.dtype)
        return a + jax.lax.stop_gradient(_ROUND[self.mode](a) - a)

    def _out(self, a):
        return self._round_out(a) if self.mode in _ROUND else a

    def matmul(self, a, b):
        a, b = self._q(a), self._q(b)
        if self.mode == "bfloat16":
            return jnp.dot(a, b, preferred_element_type=jnp.bfloat16)
        return self._out(jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST))

    def aggregate(self, h, src, dst, w, num_nodes: int):
        """out[v] = sum over edges (u, v) of w_e h[u]."""
        msgs = self._q(w)[:, None] * self._q(h)[src]
        return self._out(jax.ops.segment_sum(msgs, dst,
                                             num_segments=num_nodes))
