"""A configuration reaches the program's ``ZooSpec`` whole: its model keys
in ``spec`` pass as they are, and a key the program does not have fails
at once."""
import types

import numpy as np
import pytest

from bench.harness import common, graphgen, program

CONFIGS = ("gcn-pubmed", "sage_mean-pubmed")


def _config(name: str) -> dict:
    spec = common.load_json(common.ROOT / "BENCHMARK.json")
    entry = next(c for c in spec["configs"] if c["name"] == name)
    return common.load_json(common.ROOT / entry["file"])


def _gat(**spec) -> dict:
    cfg = _config("sage_mean-pubmed")
    cfg.update(name="gat-test", arch="gat", hidden_dim=64, spec=spec)
    return cfg


@pytest.mark.parametrize("name", CONFIGS)
def test_configurations_without_spec_build_the_same_zoo_spec(name):
    from repro.gnn.models import ZooSpec
    cfg = _config(name)
    assert "spec" not in cfg
    assert program.zoo_spec(cfg) == ZooSpec(
        cfg["arch"], 500, cfg["hidden_dim"], 3, num_layers=2)


def test_spec_keys_reach_the_zoo_spec():
    zs = program.zoo_spec(_gat(heads=8, negative_slope=0.2))
    assert (zs.arch, zs.hidden_dim, zs.heads, zs.negative_slope) == \
        ("gat", 64, 8, 0.2)


@pytest.mark.parametrize("spec", [{"output_heads": 8}, {"hidden_dim": 32}],
                         ids=["unknown", "given-twice"])
def test_a_key_the_program_lacks_fails_at_once(spec):
    with pytest.raises(TypeError):
        program.zoo_spec(_gat(**spec))


def test_gat_with_eight_heads_compiles_and_runs():
    """8 heads of 8 reach the compiled program, whose forward gives one
    row of logits per node."""
    cfg = _gat(heads=8)
    cfg["graph"].update(num_nodes=300, num_edges=1200, feature_dim=32)
    cfg.update(shard_n=128, backend="reference")
    graph = graphgen.benchmark_graph(cfg["graph"], 2 ** 31 + 11)
    ctx = types.SimpleNamespace(
        cell=types.SimpleNamespace(config=cfg), graph=graph, params=None)
    exe = program.compile_program(ctx)
    assert exe.spec.heads == 8
    w0 = exe.params["layers"][0]
    assert w0["w"].shape == (32, 64) and w0["a_src"].shape == (8, 8)
    out = np.asarray(exe.forward())
    assert out.shape == (300, 3) and np.isfinite(out).all()
