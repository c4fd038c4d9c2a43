"""A run with its timed path broken underneath comes out not correct, and
a sound run comes out correct, for every cell, through the whole harness
(the look for a chip skipped) at a size the CPU holds."""
import jax
import jax.numpy as jnp
import pytest

from bench import run as bench_run
from bench.harness import common


def small(name: str) -> common.Cell:
    """The cell at a few hundred nodes, on the plain-jnp kernel backend
    (interpreted Pallas kernels are too slow for a test run)."""
    cell = common.resolve(name)
    cell.config["graph"].update(num_nodes=600, num_edges=2400,
                                feature_dim=64)
    cell.config["shard_n"] = 128
    cell.config["backend"] = "reference"
    if cell.traffic["kind"] == "serve":
        cell.traffic.update(request_rate_per_s=60, mutation_rate_per_s=4,
                            warm_patch_pairs=[1, 4], check_requests=40)
    return cell


def state_unchanged(step):
    """The step runs but hands back the state it was given."""
    def broken(p, s, batch):
        keep = jax.tree.map(jnp.copy, (p, s))
        _, _, metrics = step(p, s, batch)
        return keep[0], keep[1], metrics
    return broken


def half_batch(step):
    """Half of the nodes left out, the mean taken over the rest."""
    def broken(p, s, batch):
        h, labels, mask, *graph = batch
        half = mask & (jnp.arange(mask.shape[0]) < mask.shape[0] // 2)
        return step(p, s, (h, labels, half, *graph))
    return broken


def altered_row(forward):
    """One node's logits replaced by another's where they are produced."""
    def broken(*args, **kw):
        out = forward(*args, **kw)
        return out.at[0].set(out[1] + 1.0)
    return broken


def altered_answer(engine_step):
    """The first node of every answer gets the next class."""
    def broken(key, payloads):
        out = engine_step(key, payloads)
        for pred in out:
            if getattr(pred, "classes", None) is not None and \
                    len(pred.classes):
                pred.classes = pred.classes.copy()
                pred.classes[0] = (pred.classes[0] + 1) % 3
        return out
    return broken


CASES = [
    ("gcn-pubmed.train-full", None, True),
    ("gcn-pubmed.train-full", {"step": state_unchanged}, False),
    ("gcn-pubmed.train-full", {"step": half_batch}, False),
    ("sage_mean-pubmed.infer-full", None, True),
    ("sage_mean-pubmed.infer-full", {"forward": altered_row}, False),
    ("gcn-pubmed.serve-mutate", None, True),
    ("gcn-pubmed.serve-mutate", {"engine_step": altered_answer}, False),
]


@pytest.mark.parametrize("cell,hooks,correct", CASES, ids=[
    f"{c}-{'sound' if h is None else next(iter(h.values())).__name__}"
    for c, h, _ in CASES])
def test_run_is_correct_only_when_sound(cell, hooks, correct):
    out = bench_run.run_cell(small(cell), 2 ** 33 + 11,
                             2.0 if cell.endswith("serve-mutate") else 0.5,
                             False, require_tpu=False, hooks=hooks)
    assert out["correct"] is correct, out["checks"]
    assert list(out)[-1] == "checks"
    over = [k for k, c in out["checks"].items() if c["value"] > c["limit"]]
    assert bool(over) is not correct
    if correct:
        assert out["failed"] == 0 and out["attempted"] > 0
