"""Gradients through the Pallas ops: the custom_vjp (oracle-derived
backward) must match differentiating the pure-jnp reference directly."""
import numpy as np
import jax
import jax.numpy as jnp

from repro.kernels import ref, registry

RNG = np.random.default_rng(7)
PALLAS = registry.get_backend("pallas")


def _check(fn_op, fn_ref, *args, atol=1e-3):
    g_op = jax.grad(lambda *a: jnp.sum(jnp.square(fn_op(*a))), argnums=tuple(
        range(len(args))))(*args)
    g_ref = jax.grad(lambda *a: jnp.sum(jnp.square(fn_ref(*a))), argnums=tuple(
        range(len(args))))(*args)
    for a, b in zip(g_op, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=atol, rtol=atol)


def test_dense_matmul_grad():
    x = jnp.asarray(RNG.standard_normal((24, 16)), jnp.float32)
    w = jnp.asarray(RNG.standard_normal((16, 8)), jnp.float32)
    b = jnp.asarray(RNG.standard_normal((8,)), jnp.float32)
    _check(lambda x, w, b: PALLAS.dense_matmul(x, w, b, activation="relu",
                                            bm=8, bn=8, bk=8),
           lambda x, w, b: ref.dense_engine(x, w, b, activation="relu"),
           x, w, b)


def test_shard_spmm_grad():
    a = jnp.asarray((RNG.random((2, 2, 8, 8)) < 0.3), jnp.float32)
    h = jnp.asarray(RNG.standard_normal((2, 8, 16)), jnp.float32)
    _check(lambda a, h: PALLAS.graph_aggregate(a, h, block_b=8),
           ref.shard_spmm, a, h)


def test_fused_gnn_grad():
    a = jnp.asarray((RNG.random((2, 2, 8, 8)) < 0.3), jnp.float32)
    h = jnp.asarray(RNG.standard_normal((2, 8, 16)), jnp.float32)
    w = jnp.asarray(RNG.standard_normal((16, 4)), jnp.float32)
    _check(lambda a, h, w: PALLAS.fused_aggregate_extract(a, h, w,
                                                          activation="relu",
                                                          block_b=8),
           lambda a, h, w: ref.fused_gnn(a, h, w, activation="relu"),
           a, h, w)


def test_gather_aggregate_max_grad():
    s, n, e, d = 2, 8, 12, 16
    es = jnp.asarray(RNG.integers(0, n, (s, s, e)), jnp.int32)
    ed = jnp.asarray(RNG.integers(0, n, (s, s, e)), jnp.int32)
    ev = jnp.asarray(RNG.random((s, s, e)) < 0.6)
    h = jnp.asarray(RNG.standard_normal((s, n, d)), jnp.float32)

    def op_fn(h):
        return PALLAS.gather_aggregate(es, ed, ev, h, op="max", block_b=8)

    g = jax.grad(lambda h: jnp.sum(jnp.square(op_fn(h))))(h)
    assert bool(jnp.isfinite(g).all())
    assert float(jnp.max(jnp.abs(g))) > 0


def test_flash_attention_grad():
    q = jnp.asarray(RNG.standard_normal((1, 2, 32, 16)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((1, 2, 32, 16)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((1, 2, 32, 16)), jnp.float32)
    _check(lambda q, k, v: PALLAS.attention(q, k, v, causal=True, bq=16, bk=16),
           lambda q, k, v: ref.flash_attention(q, k, v, causal=True),
           q, k, v)


def test_gnn_end_to_end_training_step():
    """A GCN training step through the Pallas kernels must move params."""
    from repro.gnn.models import ZooSpec, init_zoo
    from repro.runtime.forward import build_graph_tensors, forward
    edges = RNG.integers(0, 40, (150, 2))
    feats = jnp.asarray(RNG.standard_normal((40, 12)), jnp.float32)
    labels = jnp.asarray(RNG.integers(0, 4, 40), jnp.int32)
    gt = build_graph_tensors(edges, 40, 16, "gcn")
    spec = ZooSpec("gcn", 12, 16, 4, num_layers=2)
    params = init_zoo(jax.random.key(0), spec)
    hg = gt.group(feats)

    def loss(p):
        logits = forward(spec, p, gt, hg, backend=PALLAS)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], 1))

    l0, g = jax.value_and_grad(loss)(params)
    assert np.isfinite(float(l0))
    gn = sum(float(jnp.sum(jnp.abs(x))) for x in jax.tree.leaves(g))
    assert gn > 0
