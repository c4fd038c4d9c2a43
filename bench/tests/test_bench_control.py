"""The control, at full size on the CPU: the plain reference computed in
fp8 (each matmul and aggregation operand, and each backward gradient,
rounded to float8_e4m3fn with one scale per tensor) in the program's
place fails the cell's comparison.

Only the edge-list reference runs here, not the program, so full PubMed
fits: the numbers are those a run on the chip compares.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import common, compare, graphgen, reference

CONTROL = "fp8"
SEED = 2 ** 32 + 17


def _setup(name):
    cell = common.resolve(name)
    cfg = cell.config
    ref_mod = common.load_module(cell.reference_path)
    graph = graphgen.benchmark_graph(cfg["graph"], SEED)
    params = reference.init_params(ref_mod, cfg, SEED)
    return cell, cfg, ref_mod, graph, params


def _over(numbers, limits):
    return [k for k, v in numbers.items() if v > limits[k]]


def test_control_fails_the_training_cell():
    cell, cfg, ref_mod, g, params = _setup("gcn-pubmed.train-full")
    train = common.load_module(cell.driver_path)
    ref = reference.train_steps(ref_mod, cfg, params, g, train.CHECK_STEPS)
    ctl = reference.train_steps(ref_mod, cfg, params, g, train.CHECK_STEPS,
                                mode=CONTROL)
    p0 = jax.device_get(params)
    assert _over(train.numbers(ctl, ref, p0), cell.limits)


def test_control_fails_the_inference_cell():
    cell, cfg, ref_mod, g, params = _setup("sage_mean-pubmed.infer-full")
    ref = reference.Forward(ref_mod, g.num_nodes)(params, g.features, g.edges)
    ctl = reference.Forward(ref_mod, g.num_nodes, CONTROL)(
        params, g.features, g.edges)
    assert _over({"logit_err": compare.logit_err(ctl, ref)}, cell.limits)


@pytest.mark.parametrize("mode", ["highest"])
def test_reference_agrees_with_itself(mode):
    _, _, ref_mod, g, params = _setup("sage_mean-pubmed.infer-full")
    a = reference.Forward(ref_mod, g.num_nodes, mode)(params, g.features,
                                                      g.edges)
    perm = np.random.default_rng(0).permutation(g.edges.shape[0])
    b = reference.Forward(ref_mod, g.num_nodes, mode)(params, g.features,
                                                      g.edges[perm])
    assert compare.logit_err(b, a) < 1e-5
    assert jnp.isfinite(jnp.asarray(a)).all()
