"""Blocked online-softmax (flash) attention kernel.

Not part of the paper's GNN contribution, but the LM fleet's dominant
compute hot-spot — and the clearest transfer of the paper's insight to
transformers: *block a reduction axis so only a small tile is resident*.
Here the "feature block" is a kv-chunk: the (bq × bk) logit tile and the
(bq × dh) accumulator live in VMEM; the Skv axis is walked blockwise with
running max/denominator, so the S×S score matrix never exists in HBM.

Supports GQA (Hq multiple of Hkv), causal masking, and local (sliding
window) masking. Validated in interpret mode against ref.flash_attention.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_MASKED = -0.7 * float(jnp.finfo(jnp.float32).max)


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, causal: bool, window: int | None,
            sq: int, skv: int, bq: int, bk: int, nk: int):
    i, kk = pl.program_id(1), pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _MASKED)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...].astype(jnp.float32)            # (bq, dh)
    k = k_ref[...].astype(jnp.float32)            # (bk, dh)
    logits = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale

    qpos = (skv - sq) + i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = kk * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), dtype=bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    logits = jnp.where(mask, logits, _MASKED)

    m_prev, l_prev, acc_prev = m_ref[...], l_ref[...], acc_ref[...]
    m_cur = jnp.max(logits, axis=-1)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)               # rescale old accumulator
    p = jnp.exp(logits - m_new[:, None])
    p = jnp.where(mask, p, 0.0)
    l_new = l_prev * alpha + jnp.sum(p, axis=-1)
    v = v_ref[...].astype(jnp.float32)            # (bk, dh)
    acc_new = acc_prev * alpha[:, None] + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32
    )
    m_ref[...], l_ref[...], acc_ref[...] = m_new, l_new, acc_new

    @pl.when(kk == nk - 1)
    def _finish():
        o_ref[...] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[:, None]
        ).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "scale", "bq", "bk", "interpret")
)
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
    bq: int = 128,
    bk: int = 128,
    interpret: bool,
) -> jax.Array:
    """softmax(q kᵀ · scale + mask) v, blockwise.

    q: (B, Hq, Sq, Dh); k, v: (B, Hkv, Skv, Dh); Hq % Hkv == 0.
    Sq % bq == 0 and Skv % bk == 0 (ops.py pads).
    """
    b, hq, sq, dh = q.shape
    _, hkv, skv, _ = k.shape
    assert hq % hkv == 0, (hq, hkv)
    assert sq % bq == 0 and skv % bk == 0, (sq, skv, bq, bk)
    g = hq // hkv
    s = scale if scale is not None else dh ** -0.5
    nk = skv // bk

    qf = q.reshape(b * hq, sq, dh)
    kf = k.reshape(b * hkv, skv, dh)
    vf = v.reshape(b * hkv, skv, dh)

    def kv_index(bh, i, kk):
        return (bh // hq) * hkv + (bh % hq) // g, kk, 0

    out = pl.pallas_call(
        functools.partial(
            _kernel, scale=s, causal=causal, window=window,
            sq=sq, skv=skv, bq=bq, bk=bk, nk=nk,
        ),
        grid=(b * hq, sq // bq, nk),
        in_specs=[
            pl.BlockSpec((None, bq, dh), lambda bh, i, kk: (bh, i, 0)),
            pl.BlockSpec((None, bk, dh), kv_index),
            pl.BlockSpec((None, bk, dh), kv_index),
        ],
        out_specs=pl.BlockSpec((None, bq, dh), lambda bh, i, kk: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, sq, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, dh), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, hq, sq, dh)
