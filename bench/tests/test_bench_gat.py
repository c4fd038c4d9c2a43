"""The GAT configuration: the program through ``runtime.compile`` against
the plain reference, the reference against the program's own layer
oracle, the work it counts, and the attention kernel's roofline reader."""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import run as bench_run
from bench.harness import common, graphgen, kernels, program, reference
from bench.harness import work
from bench.harness.precision import Numerics

CONFIG = "gat-pubmed"


def _config() -> dict:
    spec = common.load_json(common.ROOT / "BENCHMARK.json")
    entry = next(c for c in spec["configs"] if c["name"] == CONFIG)
    return common.load_json(common.ROOT / entry["file"])


def _ref():
    return common.load_module(common.BENCH / "references" / "gat.py")


def _small(num_nodes=300, num_edges=1200, feature_dim=32) -> dict:
    """The configuration's model at a size the CPU holds: the same heads,
    widths and slope, a smaller graph and grid."""
    cfg = _config()
    cfg["graph"].update(num_nodes=num_nodes, num_edges=num_edges,
                        feature_dim=feature_dim)
    cfg["shard_n"] = 128
    return cfg


def test_configuration_states_the_published_model():
    cfg = _config()
    assert (cfg["arch"], cfg["num_layers"], cfg["hidden_dim"]) == \
        ("gat", 2, 64)
    assert cfg["spec"] == {"heads": 8, "out_heads": 8,
                           "negative_slope": 0.2}
    assert cfg["reduced"] == [] and cfg["backend"] == "pallas"
    assert [l["w"] for l in _ref().param_shapes(cfg)] == [(500, 64), (64, 24)]


@pytest.mark.parametrize("backend", ["pallas", "jax"])
def test_program_matches_reference(backend):
    """The compiled program on seeded weights against the edge-list
    reference, both in float32 on the CPU (the pallas kernels
    interpreted): only the order of sums differs, about 1e-6 of the
    logits' scale."""
    cfg = _small()
    cfg["backend"] = backend
    ref = _ref()
    seed = 2 ** 31 + 7
    graph = graphgen.benchmark_graph(cfg["graph"], seed)
    params = reference.init_params(ref, cfg, seed)
    ctx = types.SimpleNamespace(cell=types.SimpleNamespace(config=cfg),
                                graph=graph, params=params)
    exe = program.compile_program(ctx)
    out = np.asarray(exe.forward())
    want = reference.Forward(ref, graph.num_nodes)(
        params, graph.features, graph.edges)
    assert out.shape == want.shape == (300, 3)
    err = np.abs(out - want).max() / np.abs(want).max()
    assert err < 2e-5, err


def test_reference_matches_the_program_layer_oracle():
    """Each layer of the reference equals ``kernels/ref.py::gat_layer`` on
    the dense adjacency with self loops: ELU over 8 concatenated heads,
    then 8 output heads averaged."""
    from repro.kernels import ref as oracle
    cfg = _small(num_nodes=120, num_edges=480, feature_dim=16)
    ref = _ref()
    graph = graphgen.benchmark_graph(cfg["graph"], 5)
    params = reference.init_params(ref, cfg, 5)
    n = graph.num_nodes
    src, dst, _ = ref.edge_weights(graph.edges, n)
    adj = np.zeros((n, n), np.float32)
    adj[dst, src] = 1.0
    h = jnp.asarray(graph.features)
    l0, l1 = params["layers"]
    want = oracle.gat_layer(adj, h, l0["w"], l0["a_src"], l0["a_dst"],
                            activation="elu")
    want = oracle.gat_layer(adj, want, l1["w"], l1["a_src"], l1["a_dst"],
                            concat_heads=False)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(want)
    got = np.asarray(ref.forward(params, h, src, dst, None, n,
                                 Numerics("highest")))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_reference_refuses_another_slope():
    cfg = _config()
    cfg["spec"] = {**cfg["spec"], "negative_slope": 0.1}
    with pytest.raises(ValueError, match="slope"):
        _ref().param_shapes(cfg)


def test_ops_count_by_hand():
    """At PubMed shapes, 108,365 nonzeros with the self loops: layer 0
    (8 heads of 8) and layer 1 (8 heads of 3)."""
    cfg, ref = _config(), _ref()
    z, n = 108_365, 19_717
    ops = work.forward_ops(cfg, ref)
    assert [o.kind for o in ops] == ["dense", "attention"] * 2
    # per edge and head: score, LeakyReLU, max, exp, sum, divide, 2F
    assert ops[1].flops == z * 8 * (6 + 16) + 2 * n * 64 * 2
    assert ops[3].flops == z * 8 * (6 + 6) + 2 * n * 24 * 2
    # edge list 8 B a nonzero, z and the output, two scores, float32
    assert ops[1].bytes == z * 8 + 4 * n * (64 + 64 + 16)
    assert ops[3].bytes == z * 8 + 4 * n * (24 + 24 + 16)
    assert ops[0].flops == 2 * n * 500 * 64 and ops[2].flops == 2 * n * 64 * 24
    peak = work.peak("TPU v5 lite")
    need = kernels.roofline_s({"config": cfg, "ref_mod": ref, "peak": peak},
                              "attention")
    # bound by the bytes: about 22 microseconds a forward
    assert need == pytest.approx((ops[1].bytes + ops[3].bytes)
                                 / peak["bytes_per_s"])
    assert 20e-6 < need < 24e-6


def test_attention_reader_divides_the_kernels_time():
    cfg, ref = _config(), _ref()
    red = {"device_ops": [["gnn_edge_softmax_aggregate.2", 0.6],
                          ["gnn_edge_softmax_aggregate.3", 0.3],
                          ["gnn_dense_engine.2", 0.08], ["fusion.1", 0.01]],
           "pallas_s": 0.98, "busy_s": 0.99, "window_s": 1.0}
    ctx = {"config": cfg, "ref_mod": ref, "counters": {"forwards": 50},
           "trace": red, "peak": work.peak("TPU v5 lite")}
    read = common.metric_reader("attention_roofline.infer")
    need = kernels.roofline_s(ctx, "attention")
    assert read(ctx) == pytest.approx(100 * need * 50 / 0.9)
    # a program that predates the kernel names reads nothing
    ctx["trace"] = {**red, "device_ops": [["edge_softmax.2", 0.9]]}
    assert read(ctx) is None
    # a kernel that fell out of the device ops is no partial sum
    ctx["trace"] = {**red, "device_ops": red["device_ops"][:1]}
    with pytest.raises(RuntimeError, match="unaccounted"):
        read(ctx)
    ctx["trace"] = {**red, "pallas_s": 0.0}
    with pytest.raises(RuntimeError, match="no Pallas call"):
        read(ctx)


def altered_row(forward):
    """One node's logits replaced by another's where they are produced."""
    def broken(*args, **kw):
        out = forward(*args, **kw)
        return out.at[0].set(out[1] + 1.0)
    return broken


@pytest.mark.parametrize("hooks,correct", [(None, True),
                                           ({"forward": altered_row}, False)],
                         ids=["sound", "altered_row"])
def test_cell_is_correct_only_when_sound(hooks, correct):
    """The cell through the whole harness (the look for a chip skipped) at
    600 nodes on the plain-jnp backend: a forward with one row altered
    comes out not correct, a sound one correct."""
    cell = common.resolve("gat-pubmed.infer-full")
    cell.config["graph"].update(num_nodes=600, num_edges=2400,
                                feature_dim=64)
    cell.config["shard_n"] = 128
    cell.config["backend"] = "reference"
    out = bench_run.run_cell(cell, 2 ** 33 + 11, 0.5, False,
                             require_tpu=False, hooks=hooks)
    assert out["correct"] is correct, out["checks"]
    over = [k for k, c in out["checks"].items() if c["value"] > c["limit"]]
    assert bool(over) is not correct
    if correct:
        assert out["failed"] == 0 and out["attempted"] > 0
