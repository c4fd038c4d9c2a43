"""Per-kernel correctness: Pallas (interpret mode) vs pure-jnp oracle,
swept over shapes and dtypes, plus hypothesis property tests."""
import numpy as np
import pytest
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from repro.kernels import ref, registry
from repro.kernels.dense_engine import dense_engine_matmul
from repro.kernels.edge_softmax import (EdgeTiles, chunk_bound,
                                       edge_softmax_aggregate, edge_tiles,
                                       tile_shape)
from repro.kernels.flash_attention import flash_attention
from repro.kernels.fused_gnn import fused_gnn_layer
from repro.kernels.seg_gather import seg_gather_aggregate
from repro.kernels.shard_spmm import shard_spmm

RNG = np.random.default_rng(42)


def _rand(shape, dtype):
    x = RNG.standard_normal(shape).astype(np.float32)
    return x.astype(dtype)


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
@pytest.mark.parametrize("m,k,n,bm,bk,bn", [
    (64, 64, 64, 32, 32, 32),
    (128, 256, 64, 64, 64, 64),
    (32, 96, 160, 32, 32, 32),
])
def test_dense_engine(m, k, n, bm, bk, bn, dtype):
    x, w, b = _rand((m, k), dtype), _rand((k, n), dtype), _rand((n,), dtype)
    out = dense_engine_matmul(x, w, b, activation="relu", bm=bm, bn=bn, bk=bk,
                              interpret=True)
    exp = ref.dense_engine(x, w, b, activation="relu")
    tol = 1e-4 if dtype == np.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
@pytest.mark.parametrize("s,n,d,bb", [(2, 16, 32, 16), (4, 8, 64, 32), (3, 32, 48, 16)])
def test_shard_spmm(s, n, d, bb, dtype):
    a = (RNG.random((s, s, n, n)) < 0.2).astype(np.float32)
    h = _rand((s, n, d), dtype)
    out = shard_spmm(a, h, block_b=bb, interpret=True)
    exp = ref.shard_spmm(a, h)
    tol = 1e-4 if dtype == np.float32 else 1e-1
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("s,n,d,f,bb", [(2, 16, 32, 8, 16), (3, 8, 64, 24, 16)])
def test_fused_gnn(s, n, d, f, bb):
    a = (RNG.random((s, s, n, n)) < 0.2).astype(np.float32)
    h = _rand((s, n, d), np.float32)
    w = _rand((d, f), np.float32)
    out = fused_gnn_layer(a, h, w, block_b=bb, activation="relu",
                          interpret=True)
    exp = ref.fused_gnn(a, h, w, activation="relu")
    np.testing.assert_allclose(out, exp, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("op", ["max", "sum"])
@pytest.mark.parametrize("s,n,e,d,bb", [(2, 16, 24, 32, 16), (3, 8, 40, 16, 16)])
def test_seg_gather(op, s, n, e, d, bb):
    es = RNG.integers(0, n, (s, s, e)).astype(np.int32)
    ed = RNG.integers(0, n, (s, s, e)).astype(np.int32)
    ev = RNG.random((s, s, e)) < 0.6
    h = _rand((s, n, d), np.float32)
    out = seg_gather_aggregate(es, ed, ev, h, op=op, block_b=bb,
                               interpret=True)
    # oracle: combine per-pair refs across the src axis
    exp = registry.get_backend("reference").gather_aggregate(
        es, ed, ev, h, op=op)
    np.testing.assert_allclose(out, exp, atol=1e-4, rtol=1e-4)


def _attention_graph(s: int, n: int, heads: int, f: int):
    """A shard grid with the rows the edge softmax must get right, and
    scores of ±50: rows of the last shard's padding with no edge, a row
    (shard 0, node 1) whose neighbours all lie in the last source shard, a
    row (shard 0, node 2) with only its self loop, and a row (shard 0,
    node 3) whose first neighbours score -50 and whose last scores +50,
    so that its running max rises after it has summed."""
    r = np.random.default_rng(s * 100 + heads * 10 + f)
    blocks = (r.random((s, s, n, n)) < 0.2).astype(np.float32)
    blocks[-1, :, n - 3:, :] = 0.0
    blocks[0, :, 1:4, :] = 0.0
    blocks[0, -1, 1, 5:9] = 1.0
    blocks[0, 0, 2, 2] = 1.0
    blocks[0, 0, 3, 6:8] = 1.0
    blocks[0, -1, 3, n - 2] = 1.0
    z = r.standard_normal((s, n, heads * f)).astype(np.float32)
    s_src = 50.0 * r.choice([-1.0, 1.0], (s, n, heads)).astype(np.float32)
    s_dst = 50.0 * r.choice([-1.0, 1.0], (s, n, heads)).astype(np.float32)
    s_src[0, 6:8], s_src[-1, n - 2] = -50.0, 50.0
    return blocks, z, s_src, s_dst


def _grid_edges(blocks):
    """(src, dst) global node ids of the nonzeros of an (S, S, n, n) grid."""
    s, _, n, _ = blocks.shape
    dst, src = np.nonzero((blocks != 0).transpose(0, 2, 1, 3)
                          .reshape(s * n, s * n))
    return src, dst


def _tiles(blocks, *, rows=None, width=None, e_cap=None):
    """The device edge tiles of a dense grid, in the tile rule's shape
    unless ``rows`` and ``width`` are given, sized for its edge count
    unless ``e_cap`` is."""
    s, _, n, _ = blocks.shape
    src, dst = _grid_edges(blocks)
    r, w = tile_shape(n)
    host = edge_tiles(src, dst, s * n, e_cap=e_cap or src.size,
                      rows=rows or r, width=width or w)
    tiles = EdgeTiles(*map(jnp.asarray, host))
    return tiles._replace(mask=tiles.mask.astype(jnp.bfloat16))


@pytest.mark.parametrize("n,shape", [(16, (16, 128)), (1000, (200, 128)),
                                     (1024, (256, 128)), (2048, (256, 128))])
def test_edge_softmax_row_tile(n, shape):
    """Destination rows and source columns of a chunk: rows a multiple of
    8 dividing n (or n itself), at most 256, and 128 columns."""
    assert tile_shape(n) == shape


def _check_edge_softmax(blocks, z, s_src, s_dst, heads, tiles):
    out = np.asarray(edge_softmax_aggregate(
        tiles, z, s_src, s_dst, heads=heads, negative_slope=0.2,
        interpret=True))
    exp = np.asarray(ref.edge_softmax_aggregate(
        blocks, z, s_src, s_dst, heads=heads, negative_slope=0.2))
    assert np.isfinite(out).all()
    # float32 on both sides (interpret mode); the kernel sums chunk by
    # chunk and rescales by exp(m - m'), the oracle in one pass, so only
    # rounding differs: exp of scores near ±60 is exact to about 1e-5
    np.testing.assert_allclose(out, exp, rtol=5e-5, atol=5e-5)
    return out


@pytest.mark.parametrize("s", [1, 3])
@pytest.mark.parametrize("f", [3, 8])
@pytest.mark.parametrize("heads", [1, 8])
def test_edge_softmax_aggregate(heads, f, s):
    n = 16
    blocks, z, s_src, s_dst = _attention_graph(s, n, heads, f)
    out = _check_edge_softmax(blocks, z, s_src, s_dst, heads,
                              _tiles(blocks))
    # padding rows with no edge: exactly 0, never NaN
    assert (out[-1, n - 3:] == 0).all()
    # a self loop alone weighs 1
    np.testing.assert_allclose(out[0, 2], z[0, 2], rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("f", [3, 8])
@pytest.mark.parametrize("heads", [1, 8])
@pytest.mark.parametrize("layout", ["hub", "padded", "empty_tile"])
def test_edge_softmax_walks_compacted_tiles(layout, heads, f):
    """Chunks of 8 rows and 8 columns on a 2 x 16 grid: a hub row whose 21
    sources span three chunks of its row tile; chunks past those in use
    (room for three times the edges), which the kernel skips; and a row
    tile (the last shard's rows 8-15) with no edge, whose one empty chunk
    writes its rows as 0."""
    s, n = 2, 16
    blocks, z, s_src, s_dst = _attention_graph(s, n, heads, f)
    e_cap = None
    if layout == "hub":
        blocks[0, :, 4, :] = 0.0
        blocks[0, 0, 4, :11] = 1.0
        blocks[0, 1, 4, 6:] = 1.0
    elif layout == "padded":
        e_cap = 3 * int((blocks != 0).sum())
    else:
        blocks[-1, :, 8:, :] = 0.0
    tiles = _tiles(blocks, rows=8, width=8, e_cap=e_cap)
    used, t_max = int(tiles.count[0]), tiles.cols.shape[0]
    tile_of = np.asarray(tiles.chunk_tile)[:used]
    if layout == "hub":
        assert (tile_of == 0).sum() >= 3
    elif layout == "padded":
        assert t_max > 2 * used
    else:
        assert (tile_of == 3).sum() == 1
        assert not np.asarray(tiles.mask)[np.flatnonzero(tile_of == 3)].any()
    out = _check_edge_softmax(blocks, z, s_src, s_dst, heads, tiles)
    if layout == "empty_tile":
        assert (out[-1, 8:] == 0).all()
    np.testing.assert_allclose(out[0, 2], z[0, 2], rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("seed", range(6))
def test_edge_tiles_scatter_back_to_the_grid(seed):
    """Scattering each chunk's mask back through its columns gives the
    grid's nonzeros exactly, the chunks in use stay within the static
    bound, each row tile's chunks lie in a row between its first and its
    last flag, and the chunks past the count hold no edge."""
    r = np.random.default_rng(seed)
    s = int(r.integers(1, 4))
    n = int(r.choice([8, 16, 32]))
    blocks = (r.random((s, s, n, n)) < r.uniform(0.01, 0.4)).astype(
        np.float32)
    blocks[:, :, int(r.integers(n)), :] = 1.0       # a hub row
    rows = int(r.choice([8, n]))
    width = int(r.choice([8, 16, 128]))
    src, dst = _grid_edges(blocks)
    t = edge_tiles(src, dst, s * n, e_cap=src.size, rows=rows, width=width)
    used = int(t.count[0])
    assert used <= t.cols.shape[0] == chunk_bound(s * n, src.size, rows,
                                                  width)
    back = np.zeros((s * n, s * n), bool)
    k, v, c = np.nonzero(t.mask[:used])
    back[t.chunk_tile[k] * rows + v, t.cols[k, c]] = True
    want = (blocks != 0).transpose(0, 2, 1, 3).reshape(s * n, s * n)
    np.testing.assert_array_equal(back, want)
    assert not t.mask[used:].any()
    tile_of = t.chunk_tile[:used]
    assert (np.diff(tile_of) >= 0).all()
    assert set(tile_of) == set(range(s * n // rows))
    np.testing.assert_array_equal(
        t.first[:used], np.r_[1, np.diff(tile_of) != 0])
    np.testing.assert_array_equal(
        t.last[:used], np.r_[np.diff(tile_of) != 0, 1])


def test_edge_tiles_shapes_depend_on_the_bound_alone():
    """Two graphs of one (S, n, E_cap, R, C), one of them with no edge,
    give tiles of one shape; edges that need more chunks than E_cap
    allows are refused."""
    r = np.random.default_rng(0)
    s, n, e_cap = 3, 16, 400
    shapes = []
    for density in (0.0, 0.1):
        blocks = (r.random((s, s, n, n)) < density).astype(np.float32)
        src, dst = _grid_edges(blocks)
        assert src.size <= e_cap
        t = edge_tiles(src, dst, s * n, e_cap=e_cap, rows=8, width=16)
        shapes.append([a.shape for a in t])
    assert shapes[0] == shapes[1]
    assert shapes[0][1] == (chunk_bound(s * n, e_cap, 8, 16), 8, 16)
    # 48 distinct sources in each of 6 row tiles: 18 chunks, 7 allowed
    src, dst = _grid_edges(np.ones((s, s, n, n), np.float32))
    with pytest.raises(ValueError, match="chunks"):
        edge_tiles(src, dst, s * n, e_cap=16, rows=8, width=16)


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,hq,hkv,sq,skv,dh,window", [
    (1, 4, 4, 64, 64, 32, None),
    (2, 4, 2, 64, 64, 32, None),     # GQA
    (1, 2, 1, 32, 128, 16, None),    # cross lengths (q suffix of kv)
    (1, 4, 4, 128, 128, 32, 48),     # local window
])
def test_flash_attention(b, hq, hkv, sq, skv, dh, window, dtype):
    q = _rand((b, hq, sq, dh), dtype)
    k = _rand((b, hkv, skv, dh), dtype)
    v = _rand((b, hkv, skv, dh), dtype)
    out = flash_attention(q, k, v, causal=True, window=window, bq=32, bk=32,
                          interpret=True)
    exp = ref.flash_attention(q, k, v, causal=True, window=window)
    tol = 2e-4 if dtype == np.float32 else 8e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), atol=tol, rtol=tol)


# ---------------------------------------------------------------------------
# Property tests
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(
    s=st.integers(1, 3), n=st.sampled_from([8, 16]),
    d=st.sampled_from([16, 32]), bb=st.sampled_from([8, 16]),
    seed=st.integers(0, 2 ** 16),
)
def test_spmm_matches_dense_matmul(s, n, d, bb, seed):
    """Property: shard-grid SpMM == the flat (N×N)·(N×D) matmul."""
    r = np.random.default_rng(seed)
    a = (r.random((s, s, n, n)) < 0.3).astype(np.float32)
    h = r.standard_normal((s, n, d)).astype(np.float32)
    out = shard_spmm(a, h, block_b=bb, interpret=True)
    # flatten the block-structured adjacency to (S*n, S*n)
    a_flat = a.transpose(0, 2, 1, 3).reshape(s * n, s * n)
    exp = (a_flat @ h.reshape(s * n, d)).reshape(s, n, d)
    np.testing.assert_allclose(out, exp, atol=1e-4, rtol=1e-4)


@settings(max_examples=20, deadline=None)
@given(
    b=st.sampled_from([8, 16, 32]), d=st.sampled_from([32, 64]),
    seed=st.integers(0, 2 ** 16),
)
def test_blocking_invariance(b, d, seed):
    """Property: the paper's core claim — dimension-blocking does not change
    the result, only the schedule. Any B must give identical output."""
    r = np.random.default_rng(seed)
    s, n = 2, 16
    a = (r.random((s, s, n, n)) < 0.3).astype(np.float32)
    h = r.standard_normal((s, n, d)).astype(np.float32)
    full = shard_spmm(a, h, block_b=d, interpret=True)      # conventional dataflow (B = D)
    blocked = shard_spmm(a, h, block_b=b, interpret=True)   # dimension-blocked
    np.testing.assert_allclose(full, blocked, atol=1e-5, rtol=1e-5)


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2 ** 16), act=st.sampled_from(["none", "relu", "gelu"]))
def test_fusion_invariance(seed, act):
    """Property: fused engine == GraphEngine then DenseEngine."""
    r = np.random.default_rng(seed)
    s, n, d, f = 2, 8, 32, 16
    a = (r.random((s, s, n, n)) < 0.3).astype(np.float32)
    h = r.standard_normal((s, n, d)).astype(np.float32)
    w = r.standard_normal((d, f)).astype(np.float32)
    fused = fused_gnn_layer(a, h, w, block_b=16, activation=act,
                            interpret=True)
    agg = shard_spmm(a, h, block_b=16, interpret=True)
    twostep = ref.dense_engine(agg.reshape(s * n, d), w, activation=act)
    np.testing.assert_allclose(fused, twostep.reshape(s, n, f),
                               atol=1e-3, rtol=1e-3)
