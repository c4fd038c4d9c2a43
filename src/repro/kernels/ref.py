"""Pure-jnp oracles for every Pallas kernel in this package.

Each function is the semantic ground truth used by tests/test_kernels.py
(assert_allclose vs the kernel in interpret mode across shape/dtype sweeps)
and behind the ``reference`` backend in kernels/registry.py.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _activate(x, activation: str):
    if activation == "none":
        return x
    if activation == "relu":
        return jax.nn.relu(x)
    if activation == "elu":
        return jax.nn.elu(x)
    if activation == "gelu":
        return jax.nn.gelu(x)
    if activation == "silu":
        return jax.nn.silu(x)
    raise ValueError(f"unknown activation {activation}")


def dense_engine(x, w, b=None, *, activation: str = "none"):
    """Dense Engine oracle: act(x @ w + b).

    x: (M, K), w: (K, N), b: (N,) or None.
    """
    out = jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32),
                  preferred_element_type=jnp.float32)
    if b is not None:
        out = out + b.astype(jnp.float32)
    return _activate(out, activation).astype(x.dtype)


def shard_spmm(blocks, h):
    """Graph Engine (linear aggregation) oracle.

    blocks: (S_dst, S_src, n, n) densified per-shard adjacency,
            A[i, j, v, u] (rectangular grids welcome — dist/gnn.py
            aggregates local dst rows against the full source grid).
    h:      (S_src, n, D) node features grouped by shard.
    returns (S_dst, n, D): out[i, v] = sum_{j,u} A[i,j,v,u] * h[j,u].
    """
    return jnp.einsum(
        "ijvu,jud->ivd",
        blocks.astype(jnp.float32),
        h.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    ).astype(h.dtype)


def fused_gnn(blocks, h, w, *, activation: str = "none"):
    """Fused aggregation + feature extraction oracle (inter-stage fusion).

    out = act( (A · H) · W ):  blocks (S,S,n,n), h (S,n,D), w (D,F)
    returns (S, n, F).
    """
    agg = jnp.einsum(
        "ijvu,jud->ivd",
        blocks.astype(jnp.float32),
        h.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    out = jnp.einsum("ivd,df->ivf", agg, w.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return _activate(out, activation).astype(h.dtype)


def seg_gather_agg(edge_src, edge_dst, edge_valid, h_src, n_dst: int, *, op: str = "max",
                   keep_identity: bool = False):
    """Edge-list aggregation oracle for one (dst, src) shard pair.

    edge_src/edge_dst: (E,) int32 local node ids; edge_valid: (E,) bool.
    h_src: (n_src, D). Returns (n_dst, D) with identity element where a
    destination has no valid in-edges (0 for sum/mean, -inf->0 for max,
    unless keep_identity — used when combining partial maxes across shards).
    """
    d = h_src.shape[-1]
    gathered = h_src.astype(jnp.float32)[edge_src]            # (E, D)
    if op == "max":
        neg = jnp.float32(-jnp.inf)
        gathered = jnp.where(edge_valid[:, None], gathered, neg)
        out = jnp.full((n_dst, d), neg, dtype=jnp.float32)
        out = out.at[edge_dst].max(gathered, mode="drop")
        if not keep_identity:
            out = jnp.where(jnp.isfinite(out), out, 0.0)
        return out.astype(jnp.float32) if keep_identity else out.astype(h_src.dtype)
    elif op in ("sum", "mean"):
        gathered = jnp.where(edge_valid[:, None], gathered, 0.0)
        out = jnp.zeros((n_dst, d), dtype=jnp.float32)
        out = out.at[edge_dst].add(
            jnp.where(edge_valid[:, None], gathered, 0.0), mode="drop")
        if op == "mean":
            cnt = jnp.zeros((n_dst,), jnp.float32).at[edge_dst].add(
                edge_valid.astype(jnp.float32), mode="drop")
            out = out / jnp.maximum(cnt, 1.0)[:, None]
    else:
        raise ValueError(f"unknown op {op}")
    return out.astype(h_src.dtype)


# --------------------------------------------------------------------------
# GNN model-zoo layer oracles (repro.gnn.models). These operate on FLAT
# (N, D) features and a densified (N, N) adjacency — the ground truth the
# shard-grid engine path must reproduce exactly (tests/test_gnn_models.py).
# The adjacency carries the normalization baked by core.sharding.shard_graph
# (gcn / mean / sum weights); masks are derived as adj != 0.
# --------------------------------------------------------------------------

def gcn_layer(adj, h, w, *, activation: str = "none"):
    """act((Â H) W) — flat GCN layer; adj is the gcn-normalized adjacency."""
    agg = jnp.dot(adj.astype(jnp.float32), h.astype(jnp.float32),
                  preferred_element_type=jnp.float32)
    return dense_engine(agg.astype(h.dtype), w, activation=activation)


def sage_mean_layer(adj_mean, h, w, *, activation: str = "none"):
    """act(W [mean_agg(h); h]) — GraphSAGE mean aggregator (adj row-mean)."""
    agg = jnp.dot(adj_mean.astype(jnp.float32), h.astype(jnp.float32),
                  preferred_element_type=jnp.float32).astype(h.dtype)
    return dense_engine(jnp.concatenate([agg, h], axis=-1), w,
                        activation=activation)


def sage_max_pool_layer(adj_mask, h, w_pool, b_pool, w, *,
                        activation: str = "none"):
    """GraphSAGE max-pool: z = relu(h W_p + b_p); z̄ = max_N z; act(W [z̄;h])."""
    z = dense_engine(h, w_pool, b_pool, activation="relu").astype(jnp.float32)
    mask = (adj_mask != 0)
    neg = jnp.float32(-jnp.inf)
    # zbar[v] = max over u in N(v); identity 0 where no neighbors
    cand = jnp.where(mask[:, :, None], z[None, :, :], neg)
    zbar = jnp.max(cand, axis=1)
    zbar = jnp.where(jnp.isfinite(zbar), zbar, 0.0).astype(h.dtype)
    return dense_engine(jnp.concatenate([zbar, h], axis=-1), w,
                        activation=activation)


def gin_layer(adj_sum, h, eps, w1, b1, w2, b2, *, activation: str = "none"):
    """GIN: MLP((1+ε) h + Σ_N h); adj_sum has NO self loops (ε handles it)."""
    agg = jnp.dot(adj_sum.astype(jnp.float32), h.astype(jnp.float32),
                  preferred_element_type=jnp.float32)
    x = ((1.0 + eps) * h.astype(jnp.float32) + agg).astype(h.dtype)
    hid = dense_engine(x, w1, b1, activation="relu")
    return dense_engine(hid, w2, b2, activation=activation)


def edge_softmax(adj_mask, z, s_src, s_dst, *, negative_slope: float = 0.2):
    """Attention-weighted aggregation of every head (GAT's edge softmax).

    adj_mask: (V, U) nonzero where edge u->v exists at [v, u]; z: (U, H, F);
    s_src: (U, H); s_dst: (V, H). Returns (V, H, F):
    out[v, h] = Σ_u α_vuh z[u, h], α_vuh = softmax over u in N(v) of
    leakyrelu(s_dst[v, h] + s_src[u, h]); 0 for a row with no edge.
    """
    logits = s_dst[:, None, :] + s_src[None, :, :]          # (V, U, H)
    logits = jax.nn.leaky_relu(logits, negative_slope)
    mask = (adj_mask != 0)[:, :, None]
    logits = jnp.where(mask, logits, -jnp.inf)
    m = jnp.max(logits, axis=1, keepdims=True)
    m = jnp.where(jnp.isfinite(m), m, 0.0)
    e = jnp.where(mask, jnp.exp(logits - m), 0.0)
    denom = jnp.sum(e, axis=1, keepdims=True)
    alpha = jnp.where(denom > 0, e / jnp.maximum(denom, 1e-30), 0.0)
    return jnp.einsum("vuh,uhf->vhf", alpha, z.astype(jnp.float32))


def edge_softmax_aggregate(blocks, z, s_src, s_dst, *, heads: int,
                           negative_slope: float = 0.2):
    """Edge softmax aggregation on the shard grid (the Graph Engine's
    attention op), flattened to :func:`edge_softmax`.

    blocks: (S, S, n, n) adjacency; z: (S, n, H·F) head-major features;
    s_src/s_dst: (S, n, H) scores. Returns (S, n, H·F).
    """
    s, _, n, _ = blocks.shape
    d = z.shape[-1]
    mask = (blocks != 0).transpose(0, 2, 1, 3).reshape(s * n, s * n)
    out = edge_softmax(mask, z.reshape(s * n, heads, d // heads),
                       s_src.reshape(s * n, heads).astype(jnp.float32),
                       s_dst.reshape(s * n, heads).astype(jnp.float32),
                       negative_slope=negative_slope)
    return out.reshape(s, n, d).astype(z.dtype)


def gat_layer(adj_mask, h, w, a_src, a_dst, *, negative_slope: float = 0.2,
              activation: str = "none", concat_heads: bool = True):
    """Multi-head GAT layer.

    h: (N, D); w: (D, H*F); a_src/a_dst: (H, F); adj_mask: (N, N) nonzero
    where edge u->v exists at [v, u] (self loops included upstream).
    α_vu = softmax_u( leakyrelu(a_dst·z_v + a_src·z_u) ), out_v = Σ α z_u.
    Heads are concatenated (hidden layers) or averaged (output layer).
    """
    n = h.shape[0]
    heads, f = a_src.shape
    z = jnp.dot(h.astype(jnp.float32), w.astype(jnp.float32),
                preferred_element_type=jnp.float32).reshape(n, heads, f)
    s_src = jnp.einsum("nhf,hf->nh", z, a_src.astype(jnp.float32))
    s_dst = jnp.einsum("nhf,hf->nh", z, a_dst.astype(jnp.float32))
    out = edge_softmax(adj_mask, z, s_src, s_dst,
                       negative_slope=negative_slope)
    out = out.reshape(n, heads * f) if concat_heads else out.mean(axis=1)
    return _activate(out, activation).astype(h.dtype)


def flash_attention(q, k, v, *, causal: bool = True, scale: float | None = None,
                    window: int | None = None):
    """Attention oracle: softmax(q k^T * scale + mask) v.

    q: (B, Hq, Sq, Dh), k/v: (B, Hkv, Skv, Dh) with Hq % Hkv == 0 (GQA).
    window: local attention window (keys within [i-window+1, i]).
    """
    b, hq, sq, dh = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    g = hq // hkv
    qf = q.astype(jnp.float32).reshape(b, hkv, g, sq, dh)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    s = scale if scale is not None else dh ** -0.5
    logits = jnp.einsum("bhgqd,bhkd->bhgqk", qf, kf) * s
    qpos = jnp.arange(sq)[:, None] + (skv - sq)
    kpos = jnp.arange(skv)[None, :]
    mask = jnp.ones((sq, skv), dtype=bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    logits = jnp.where(mask[None, None, None], logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", p, vf)
    return out.reshape(b, hq, sq, dh).astype(q.dtype)
