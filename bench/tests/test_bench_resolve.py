"""The harness finds every part of a cell by name, and BENCHMARK.json
keeps to the shape the harness and its readers rely on."""
import re

import pytest

from bench.harness import common

SPEC = common.load_json(common.ROOT / "BENCHMARK.json")
CELLS = [w["name"] for w in SPEC["workloads"]]
CANDIDATES = sorted(p.stem for p in (common.BENCH / "candidates").glob("*.json"))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("cell", CELLS + CANDIDATES)
def test_cell_resolves_by_name(cell):
    c = common.resolve(cell)
    w = next((w for w in SPEC["workloads"] if w["name"] == cell), None) or \
        common.load_json(common.BENCH / "candidates" / f"{cell}.json")[
            "workload"]
    assert c.config["name"] == w["config"]
    assert c.driver_path.is_file() and c.reference_path.is_file()
    driver = common.load_module(c.driver_path)
    assert callable(driver.Run)
    ref = common.load_module(c.reference_path)
    for fn in ("param_shapes", "ops", "edge_weights", "forward"):
        assert callable(getattr(ref, fn))
    # every per-layer metric has a reader, and every metric of the cell
    # moves an end-to-end metric the cell reports
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer
    for m in c.per_layer:
        assert callable(common.metric_reader(m["name"]))
        assert m["moves"] in e2e
    assert all(isinstance(v, (int, float)) and v > 0
               for v in c.limits.values())


def test_unknown_cell_is_refused():
    with pytest.raises(KeyError):
        common.resolve("no-such.cell")


def test_benchmark_json_shape():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    names = [c["name"] for c in SPEC["configs"]] + CELLS + [
        m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == {c["name"] for c in SPEC["configs"]}
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert set(m["workloads"]) <= set(CELLS)
        assert "bound" not in m
    layers = {}
    for m in SPEC["per_layer"]:
        layers.setdefault(m["layer"], set()).add(m["name"])
    assert set(layers) == {"model step", "kernels", "device"}
    assert not set(CANDIDATES) & set(CELLS)
