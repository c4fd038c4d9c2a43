"""Pluggable kernel-backend registry (the runtime's hardware abstraction).

Every compute primitive the engines need is one method on the
:class:`KernelBackend` protocol. Three backends ship in-tree:

  pallas      the Pallas kernels (interpret mode on CPU, compiled on TPU),
              shape-safe padding at the boundary, backward pass derived
              from the pure-jnp oracles via ``custom_vjp``.
  jax         pure-XLA lowering: fully vectorized ``jnp`` implementations
              (vmapped segment ops instead of per-shard Python loops) that
              XLA fuses on any device. Ad-traceable end to end.
  reference   the semantic ground truth from :mod:`repro.kernels.ref` —
              written for clarity (explicit per-shard-pair loops), used as
              the oracle everything else is pinned against.

:func:`resolve` picks one for a whole model: an explicit backend (name or
object) passed to ``runtime.compile(...)`` or ``runtime.forward.forward``,
else the ``REPRO_KERNEL_BACKEND`` env var, else ``pallas``. The engines
call the backend they are given.
"""
from __future__ import annotations

import os
from typing import Protocol, runtime_checkable

import jax
import jax.numpy as jnp

from repro.kernels import dense_engine as _de
from repro.kernels import edge_softmax as _es
from repro.kernels import flash_attention as _fa
from repro.kernels import fused_gnn as _fg
from repro.kernels import ref
from repro.kernels import seg_gather as _sg
from repro.kernels import shard_spmm as _ss
from repro.utils import round_up

DEFAULT_BACKEND = "pallas"


@runtime_checkable
class KernelBackend(Protocol):
    """One implementation of every engine compute primitive."""

    name: str

    def dense_matmul(self, x, w, b=None, *, activation: str = "none",
                     bm: int = 128, bn: int = 128, bk: int = 128):
        """act(x @ w + b); x (M, K), w (K, N), b (N,) or None."""
        ...

    def graph_aggregate(self, blocks, h, *, block_b: int = 128):
        """Linear shard-grid aggregation: out[i] = Σ_j A[i,j] @ h[j]."""
        ...

    def fused_aggregate_extract(self, blocks, h, w, *,
                                activation: str = "none", block_b: int = 128):
        """act((A·H)·W) with h_agg never leaving on-chip memory."""
        ...

    def gather_aggregate(self, edge_src, edge_dst, edge_valid, h, *,
                         op: str = "max", block_b: int = 128):
        """Edge-list (gather/scatter) aggregation; supports max/sum."""
        ...

    def edge_softmax_aggregate(self, blocks, z, s_src, s_dst, *, heads: int,
                               negative_slope: float = 0.2):
        """GAT attention of every head on the shard grid: out[i, v, head] =
        Σ_{j,u} α z[j, u, head], α the softmax over the edges into v of
        LeakyReLU(s_dst[v] + s_src[u]). z (S, n, H·F), scores (S, n, H)."""
        ...

    def attention(self, q, k, v, *, causal: bool = True,
                  window: int | None = None, scale: float | None = None,
                  bq: int = 128, bk: int = 128):
        """Attention; q (B,Hq,Sq,Dh), k/v (B,Hkv,Skv,Dh)."""
        ...


# --------------------------------------------------------------------------
# shared helpers
# --------------------------------------------------------------------------

def _with_ref_vjp(kernel_fn, ref_fn):
    """custom_vjp wrapper: FORWARD runs the Pallas kernel, BACKWARD
    differentiates the pure-jnp oracle (recomputing the forward pass —
    Pallas calls are not ad-traceable, and shipping explicit
    VJPs per kernel is exactly what production kernel libraries do; the
    oracle-derived gradient is validated in tests/test_kernels_grad.py)."""
    @jax.custom_vjp
    def f(*args):
        return kernel_fn(*args)

    def fwd(*args):
        return kernel_fn(*args), args

    def bwd(args, g):
        _, vjp = jax.vjp(ref_fn, *args)
        return vjp(g)

    f.defvjp(fwd, bwd)
    return f


def _interpret() -> bool:
    """Compile the kernels on TPU; interpret them on any other platform
    (CPU tests). Every kernel call below passes this explicitly."""
    return jax.default_backend() != "tpu"


# matmul precisions that give each f32 operand one bfloat16 MXU pass
_ONE_BF16_PASS = (None, "default", "bfloat16")


def grid_dtype(platform: str, precision: str | None) -> jnp.dtype:
    """The dtype the shard grid lives in on the device, for the platform
    the kernels run on and the ``jax_default_matmul_precision`` in effect.

    On a TPU at one bfloat16 pass every consumer of the grid rounds it to
    bfloat16 anyway (the kernels' and XLA's dots), or only tests it
    ``!= 0`` (the edge softmax), so storing it in bfloat16 changes no
    operand and halves the bytes each walk reads. Elsewhere, or at a
    higher precision, the dots see the float32 value: keep float32."""
    if platform == "tpu" and precision in _ONE_BF16_PASS:
        return jnp.dtype(jnp.bfloat16)
    return jnp.dtype(jnp.float32)


def device_grid_dtype() -> jnp.dtype:
    """:func:`grid_dtype` for this process's backend and the matmul
    precision in effect where it is called (the grid's upload)."""
    return grid_dtype(jax.default_backend(),
                      jax.config.jax_default_matmul_precision)


def _feature_block(d: int, block_b: int) -> tuple[int, int]:
    """(kernel block, padded dim) for a requested block over a lane dim.

    Mosaic only accepts a block whose lane (last) dim is a multiple of
    128 or the whole (padded) array dim. A requested block below that is
    rounded up to whole lanes; one that covers the dim becomes a single
    full-width block. The feature blocks B of the graph kernels and the
    dense kernel's K/N tiles all go through here."""
    full = round_up(d, 8)
    bb = round_up(block_b, 128)
    if bb >= full:
        return full, full
    return bb, round_up(d, bb)


def grid_walks(d: int, block_b: int) -> int:
    """Times a graph kernel reads the whole (S, S, n, n) shard grid to
    aggregate ``d`` features at requested block ``block_b``: once per
    kernel feature block, after ``_feature_block``'s lane rounding."""
    bb, dp = _feature_block(d, block_b)
    return dp // bb


def _pad(x, size, axis):
    pad = size - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _gather_loop(edge_src, edge_dst, edge_valid, h, *, op: str):
    """Per-shard-pair Python loop over the grid (the readable reference)."""
    s, n, _ = h.shape
    outs = []
    for i in range(s):
        acc = None
        for j in range(s):
            part = ref.seg_gather_agg(
                edge_src[i, j], edge_dst[i, j], edge_valid[i, j],
                h[j], n, op=op, keep_identity=(op == "max"))
            acc = part if acc is None else (
                jnp.maximum(acc, part) if op == "max" else acc + part)
        if op == "max":
            acc = jnp.where(jnp.isfinite(acc), acc, 0.0).astype(h.dtype)
        outs.append(acc)
    return jnp.stack(outs)


def _attention_blocks(blocks, s_src, s_dst, negative_slope: float):
    """One head's attention weights α laid out on the shard grid.

    s_src/s_dst: (S, n) scores. Returns α as (S, S, n, n) blocks
    [dst_shard, src_shard, v, u], the softmax over all of v's in-neighbours
    (axes src_shard and u)."""
    mask = blocks != 0
    logits = s_dst[:, None, :, None] + s_src[None, :, None, :]
    logits = jax.nn.leaky_relu(logits, negative_slope)
    logits = jnp.where(mask, logits, -jnp.inf)
    m = jnp.max(logits, axis=(1, 3), keepdims=True)
    m = jnp.where(jnp.isfinite(m), m, 0.0)
    e = jnp.where(mask, jnp.exp(logits - m), 0.0)
    denom = jnp.sum(e, axis=(1, 3), keepdims=True)
    return jnp.where(denom > 0, e / jnp.maximum(denom, 1e-30), 0.0)


# --------------------------------------------------------------------------
# reference backend: the oracles, verbatim
# --------------------------------------------------------------------------

class ReferenceBackend:
    """Semantic ground truth (kernels/ref.py); clarity over speed."""

    name = "reference"

    def dense_matmul(self, x, w, b=None, *, activation="none",
                     bm=128, bn=128, bk=128):
        return ref.dense_engine(x, w, b, activation=activation)

    def graph_aggregate(self, blocks, h, *, block_b=128):
        return ref.shard_spmm(blocks, h)

    def fused_aggregate_extract(self, blocks, h, w, *, activation="none",
                                block_b=128):
        return ref.fused_gnn(blocks, h, w, activation=activation)

    def gather_aggregate(self, edge_src, edge_dst, edge_valid, h, *,
                         op="max", block_b=128):
        return _gather_loop(edge_src, edge_dst, edge_valid, h, op=op)

    def edge_softmax_aggregate(self, blocks, z, s_src, s_dst, *, heads,
                               negative_slope=0.2):
        return ref.edge_softmax_aggregate(blocks, z, s_src, s_dst,
                                          heads=heads,
                                          negative_slope=negative_slope)

    def attention(self, q, k, v, *, causal=True, window=None, scale=None,
                  bq=128, bk=128):
        return ref.flash_attention(q, k, v, causal=causal, scale=scale,
                                   window=window)


# --------------------------------------------------------------------------
# jax backend: pure-XLA lowering, fully vectorized
# --------------------------------------------------------------------------

class JaxBackend(ReferenceBackend):
    """Pure-XLA lowering. The dense/spmm/fused/attention oracles are
    already single fused einsums, so those are shared with ``reference``;
    the one op where reference trades speed for readability — the
    per-shard-pair Python gather loop — is replaced by a vmapped segment
    aggregation that scales to large shard grids on CPU/GPU/TPU without
    Pallas, and the edge softmax runs on the grid itself, one head at a
    time, instead of on the flattened (S·n, S·n) adjacency."""

    name = "jax"

    def gather_aggregate(self, edge_src, edge_dst, edge_valid, h, *,
                         op="max", block_b=128):
        s, n, _ = h.shape

        def one_pair(es, ed, ev, h_src):
            return ref.seg_gather_agg(es, ed, ev, h_src, n, op=op,
                                      keep_identity=(op == "max"))

        def one_dst(es_row, ed_row, ev_row):
            # (S, E) edge rows against all S source shards at once
            parts = jax.vmap(one_pair)(es_row, ed_row, ev_row, h)
            if op == "max":
                acc = jnp.max(parts, axis=0)
                return jnp.where(jnp.isfinite(acc), acc, 0.0).astype(h.dtype)
            return jnp.sum(parts, axis=0).astype(h.dtype)

        return jax.vmap(one_dst)(edge_src, edge_dst, edge_valid)

    def edge_softmax_aggregate(self, blocks, z, s_src, s_dst, *, heads,
                               negative_slope=0.2):
        s, n, d = z.shape
        zh = z.reshape(s, n, heads, d // heads)
        outs = [ref.shard_spmm(
            _attention_blocks(blocks, s_src[..., h], s_dst[..., h],
                              negative_slope), zh[..., h, :])
                for h in range(heads)]
        return jnp.concatenate(outs, axis=-1)

    def attention(self, q, k, v, *, causal=True, window=None, scale=None,
                  bq=128, bk=128):
        return ref.flash_attention(q, k, v, causal=causal, scale=scale,
                                   window=window)


# --------------------------------------------------------------------------
# pallas backend: the kernels, padded at the boundary, oracle-derived VJPs
# --------------------------------------------------------------------------

class PallasBackend:
    """The Pallas kernels (compiled on TPU, interpreted elsewhere). Inputs
    are padded to the kernels' block multiples and sliced back; backward
    passes come from the oracles via custom_vjp."""

    name = "pallas"

    def dense_matmul(self, x, w, b=None, *, activation="none",
                     bm=128, bn=128, bk=128):
        def kernel(x, w, *opt_b):
            m, k = x.shape
            n = w.shape[1]
            bm_ = min(bm, round_up(m, 8))
            mp = round_up(m, bm_)
            bk_, kp = _feature_block(k, bk)
            bn_, np_ = _feature_block(n, bn)
            xp = _pad(_pad(x, mp, 0), kp, 1)
            wp = _pad(_pad(w, kp, 0), np_, 1)
            bp = _pad(opt_b[0], np_, 0) if opt_b else None
            out = _de.dense_engine_matmul(
                xp, wp, bp, activation=activation, bm=bm_, bn=bn_, bk=bk_,
                interpret=_interpret())
            return out[:m, :n]

        def ref_fn(x, w, *opt_b):
            return ref.dense_engine(x, w, opt_b[0] if opt_b else None,
                                    activation=activation)

        args = (x, w) if b is None else (x, w, b)
        return _with_ref_vjp(kernel, ref_fn)(*args)

    def graph_aggregate(self, blocks, h, *, block_b=128):
        def kernel(blocks, h):
            d = h.shape[-1]
            bb, dp = _feature_block(d, block_b)
            out = _ss.shard_spmm(blocks, _pad(h, dp, 2), block_b=bb,
                                 interpret=_interpret())
            return out[..., :d]

        return _with_ref_vjp(kernel, ref.shard_spmm)(blocks, h)

    def fused_aggregate_extract(self, blocks, h, w, *, activation="none",
                                block_b=128):
        def kernel(blocks, h, w):
            d = h.shape[-1]
            bb, dp = _feature_block(d, block_b)
            return _fg.fused_gnn_layer(
                blocks, _pad(h, dp, 2), _pad(w, dp, 0),
                block_b=bb, activation=activation, interpret=_interpret())

        def ref_fn(blocks, h, w):
            return ref.fused_gnn(blocks, h, w, activation=activation)

        return _with_ref_vjp(kernel, ref_fn)(blocks, h, w)

    def gather_aggregate(self, edge_src, edge_dst, edge_valid, h, *,
                         op="max", block_b=128):
        # the edge tensors MUST be custom_vjp arguments, not closure
        # captures: under the streaming-graph forward they arrive as jit
        # tracers, and a custom_vjp closing over a tracer bakes it into the
        # backward jaxpr as a dead constant (int args cost nothing — their
        # cotangents are float0)
        def kernel(e_src, e_dst, e_val, h):
            d = h.shape[-1]
            bb, dp = _feature_block(d, block_b)
            out = _sg.seg_gather_aggregate(
                e_src, e_dst, e_val, _pad(h, dp, 2), op=op,
                block_b=bb, interpret=_interpret())
            return out[..., :d]

        def ref_fn(e_src, e_dst, e_val, h):
            return _gather_loop(e_src, e_dst, e_val, h, op=op)

        return _with_ref_vjp(kernel, ref_fn)(
            edge_src, edge_dst, edge_valid, h)

    def edge_softmax_aggregate(self, blocks, z, s_src, s_dst, *, heads,
                               negative_slope=0.2):
        def kernel(blocks, z, s_src, s_dst):
            return _es.edge_softmax_aggregate(
                blocks, z, jnp.swapaxes(s_src, 1, 2), s_dst, heads=heads,
                negative_slope=negative_slope, interpret=_interpret())

        def ref_fn(blocks, z, s_src, s_dst):
            return ref.edge_softmax_aggregate(
                blocks, z, s_src, s_dst, heads=heads,
                negative_slope=negative_slope)

        return _with_ref_vjp(kernel, ref_fn)(blocks, z, s_src, s_dst)

    def attention(self, q, k, v, *, causal=True, window=None, scale=None,
                  bq=128, bk=128):
        sq, skv = q.shape[2], k.shape[2]
        bq_, bk_ = min(bq, sq), min(bk, skv)
        if sq % bq_ or skv % bk_:
            # Padding the sequence axes would shift the causal-offset
            # alignment (qpos = skv - sq + i), so the kernel path needs
            # block-multiple shapes; other shapes belong to another backend
            raise ValueError(
                f"pallas attention needs sequence lengths that are block "
                f"multiples: sq={sq} (bq={bq_}), skv={skv} (bk={bk_}); "
                f"pick block sizes that divide them or use the "
                f"'reference' backend")

        def kernel(q, k, v):
            return _fa.flash_attention(q, k, v, causal=causal, window=window,
                                       scale=scale, bq=bq_, bk=bk_,
                                       interpret=_interpret())

        def ref_fn(q, k, v):
            return ref.flash_attention(q, k, v, causal=causal, scale=scale,
                                       window=window)

        return _with_ref_vjp(kernel, ref_fn)(q, k, v)


# --------------------------------------------------------------------------
# registry + resolution
# --------------------------------------------------------------------------

_REGISTRY: dict[str, KernelBackend] = {}


def register_backend(backend: KernelBackend) -> KernelBackend:
    """Register a backend under ``backend.name``. Re-registering a name
    replaces it — deliberate, so tests/plugins can swap implementations."""
    _REGISTRY[backend.name] = backend
    return backend


def get_backend(name: str) -> KernelBackend:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(f"unknown kernel backend {name!r}; "
                         f"registered: {list_backends()}") from None


def list_backends() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def resolve(override: str | KernelBackend | None = None) -> KernelBackend:
    """The backend a model runs on: ``override`` (a registered name or a
    backend object) if given, else ``REPRO_KERNEL_BACKEND``, else
    ``pallas``."""
    if override is None:
        override = os.environ.get("REPRO_KERNEL_BACKEND", DEFAULT_BACKEND)
    if isinstance(override, str):
        return get_backend(override)
    return override


register_backend(PallasBackend())
register_backend(JaxBackend())
register_backend(ReferenceBackend())
