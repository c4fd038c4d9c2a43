"""Backend parity: every op of the KernelBackend protocol must produce the
same numbers on `pallas`, `jax` and `reference` over hypothesis-generated
shard grids (extending the test_gnn_models oracle pattern one level down:
the reference backend IS the oracle, the others must match it allclose);
and `registry.resolve`'s one order: explicit backend, then
REPRO_KERNEL_BACKEND, then pallas."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import registry

RNG = np.random.default_rng(42)
TOL = dict(atol=1e-4, rtol=1e-4)


def _others():
    return [registry.get_backend(n) for n in registry.list_backends()
            if n != "reference"]


def _check(op_name, make_args, **kw):
    ref = registry.get_backend("reference")
    ref_out = getattr(ref, op_name)(*make_args(), **kw)
    for be in _others():
        out = getattr(be, op_name)(*make_args(), **kw)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref_out),
            err_msg=f"{op_name}: backend {be.name} diverges from reference",
            **TOL)


class TestRegistryParity:
    def test_all_backends_registered(self):
        assert set(registry.list_backends()) >= {"pallas", "jax", "reference"}
        for name in registry.list_backends():
            be = registry.get_backend(name)
            for op, fn in vars(registry.KernelBackend).items():
                if callable(fn) and not op.startswith("_"):
                    assert callable(getattr(be, op)), (name, op)

    @settings(deadline=None, max_examples=8)
    @given(m=st.sampled_from([3, 16, 64]), k=st.sampled_from([8, 33]),
           n=st.sampled_from([4, 24]),
           act=st.sampled_from(["none", "relu", "gelu"]),
           bias=st.booleans())
    def test_dense_matmul(self, m, k, n, act, bias):
        x = RNG.standard_normal((m, k)).astype(np.float32)
        w = RNG.standard_normal((k, n)).astype(np.float32)
        b = RNG.standard_normal((n,)).astype(np.float32) if bias else None
        _check("dense_matmul", lambda: (x, w, b), activation=act)

    @settings(deadline=None, max_examples=8)
    @given(s=st.sampled_from([1, 2, 4]), n=st.sampled_from([8, 16]),
           d=st.sampled_from([4, 20, 32]))
    def test_graph_aggregate(self, s, n, d):
        blocks = (RNG.random((s, s, n, n)) < 0.2).astype(np.float32)
        h = RNG.standard_normal((s, n, d)).astype(np.float32)
        _check("graph_aggregate", lambda: (blocks, h), block_b=16)

    @settings(deadline=None, max_examples=8)
    @given(s=st.sampled_from([1, 2, 3]), n=st.sampled_from([8, 16]),
           d=st.sampled_from([4, 24]), f=st.sampled_from([4, 12]),
           act=st.sampled_from(["none", "relu"]))
    def test_fused_aggregate_extract(self, s, n, d, f, act):
        blocks = (RNG.random((s, s, n, n)) < 0.2).astype(np.float32)
        h = RNG.standard_normal((s, n, d)).astype(np.float32)
        w = RNG.standard_normal((d, f)).astype(np.float32)
        _check("fused_aggregate_extract", lambda: (blocks, h, w),
               activation=act, block_b=16)

    @settings(deadline=None, max_examples=8)
    @given(s=st.sampled_from([1, 2, 3]), n=st.sampled_from([8, 16]),
           e=st.sampled_from([12, 40]), d=st.sampled_from([4, 24]),
           op=st.sampled_from(["max", "sum"]))
    def test_gather_aggregate(self, s, n, e, d, op):
        es = RNG.integers(0, n, (s, s, e)).astype(np.int32)
        ed = RNG.integers(0, n, (s, s, e)).astype(np.int32)
        ev = RNG.random((s, s, e)) < 0.6
        h = RNG.standard_normal((s, n, d)).astype(np.float32)
        _check("gather_aggregate", lambda: (es, ed, ev, h), op=op,
               block_b=16)

    @settings(deadline=None, max_examples=6)
    @given(s=st.sampled_from([1, 2, 3]), n=st.sampled_from([8, 16]),
           heads=st.sampled_from([1, 4]), f=st.sampled_from([3, 8]))
    def test_edge_softmax_aggregate(self, s, n, heads, f):
        blocks = (RNG.random((s, s, n, n)) < 0.2).astype(np.float32)
        z = RNG.standard_normal((s, n, heads * f)).astype(np.float32)
        s_src = RNG.standard_normal((s, n, heads)).astype(np.float32)
        s_dst = RNG.standard_normal((s, n, heads)).astype(np.float32)
        _check("edge_softmax_aggregate", lambda: (blocks, z, s_src, s_dst),
               heads=heads, negative_slope=0.2)

    @settings(deadline=None, max_examples=4)
    @given(sq=st.sampled_from([32, 64]), heads=st.sampled_from([2, 4]),
           window=st.sampled_from([None, 24]))
    def test_attention(self, sq, heads, window):
        q = RNG.standard_normal((1, heads, sq, 16)).astype(np.float32)
        k = RNG.standard_normal((1, heads, sq, 16)).astype(np.float32)
        v = RNG.standard_normal((1, heads, sq, 16)).astype(np.float32)
        _check("attention", lambda: (q, k, v), causal=True, window=window,
               bq=32, bk=32)

    def test_pallas_attention_refuses_non_block_multiples(self):
        q = RNG.standard_normal((1, 2, 48, 16)).astype(np.float32)
        with pytest.raises(ValueError, match="block multiples"):
            registry.get_backend("pallas").attention(q, q, q, bq=32, bk=32)

    @pytest.mark.parametrize("d,b", [(500, 16), (1433, 64), (16, 16),
                                     (7, 128), (256, 128), (300, 256)])
    def test_feature_block_is_lane_legal(self, d, b):
        """Mosaic's rule: a lane block is whole 128-lane tiles or the
        whole padded dim, and it tiles the padded dim exactly."""
        bb, dp = registry._feature_block(d, b)
        assert dp >= d and dp % bb == 0
        assert bb % 128 == 0 or bb == dp


class TestResolution:
    def test_env_selects_backend(self, monkeypatch):
        monkeypatch.setenv("REPRO_KERNEL_BACKEND", "reference")
        assert registry.resolve().name == "reference"
        monkeypatch.delenv("REPRO_KERNEL_BACKEND")
        assert registry.resolve().name == registry.DEFAULT_BACKEND

    def test_explicit_override_wins(self, monkeypatch):
        monkeypatch.setenv("REPRO_KERNEL_BACKEND", "pallas")
        assert registry.resolve("reference").name == "reference"
        be = registry.get_backend("jax")
        assert registry.resolve(be) is be

    @pytest.mark.parametrize("name", ["fpga", "ref"])
    def test_unknown_backend_raises(self, name, monkeypatch):
        with pytest.raises(ValueError) as err:
            registry.get_backend(name)
        for known in registry.list_backends():
            assert repr(known) in str(err.value)
        monkeypatch.setenv("REPRO_KERNEL_BACKEND", name)
        with pytest.raises(ValueError, match=repr(name)):
            registry.resolve()
