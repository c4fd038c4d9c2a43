"""Single- vs multi-device sharded GNN execution, recorded to
BENCH_gnn.json (section ``dist_scaling``).

    PYTHONPATH=src python -m benchmarks.dist_scaling

Forces 8 virtual host devices (so it must run standalone, not from
benchmarks.run — jax pins the device count at first init) and compares,
per (arch, graph, partition method):

  * full-graph forward latency of the single-device Executable vs the
    sharded one on a data=4 x model=2 mesh,
  * the sharded module's measured cross-device traffic (HLO-parsed
    all-gather / all-reduce wire bytes) against the PartitionPlan models
    (``verify_comm``, hub terms included),
  * the partition balance report (cross-group edge fraction, imbalance),
  * for ``fennel`` rows, the wire-byte reduction vs the contiguous
    baseline of the same (arch, graph) cell.

On this container the 8 "devices" are slices of one CPU, so sharded
wall-clock measures SPMD overhead rather than speedup; the numbers that
transfer to real multi-chip runs are the communication volumes and the
balance profile.
"""
from __future__ import annotations

import os

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import time                   # noqa: E402

import numpy as np            # noqa: E402

from benchmarks.report import merge_bench_json  # noqa: E402

DEVICES = 8
MODEL_PARALLEL = 2
ARCHS = ("gcn", "sage_mean", "gin")
GRAPHS = (("cora", 1.0), ("citeseer", 1.0))
PARTITIONS = ("contiguous", "fennel")
HUB_CACHE = 256
ITERS = 5
BACKEND = "reference"


def _time_forward(exe, iters: int = ITERS) -> float:
    import jax
    jax.block_until_ready(exe.forward())        # compile + warm
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(exe.forward())
    return (time.perf_counter() - t0) / iters * 1e3


def sharded_throughput(exe, wall_ms: float) -> float:
    """Nodes/s of a sharded forward, normalized by the TRUE node count.

    The sharded executable pads the shard grid to equal row groups
    (``S_pad * n`` slots >= N real nodes) — counting padded rows would
    flatter the sharded side on every non-divisible S/n_data split, so
    throughput divides by ``exe.gt.num_nodes``, never a padded count."""
    return exe.gt.num_nodes / (wall_ms / 1e3)


def bench_dist_scaling():
    import jax

    from repro import runtime
    from repro.gnn.models import ZooSpec
    from repro.graphs.datasets import make_dataset
    from repro.launch.mesh import make_mesh_for

    assert jax.device_count() >= DEVICES, (
        f"needs {DEVICES} devices; run standalone so the XLA_FLAGS "
        f"override above takes effect (got {jax.device_count()})")
    mesh = make_mesh_for(DEVICES, model_parallel=MODEL_PARALLEL)
    n_data = DEVICES // MODEL_PARALLEL

    rows = []
    for graph, scale in GRAPHS:
        ds = make_dataset(graph, seed=0, scale=scale)
        for arch in ARCHS:
            spec = ZooSpec(arch, ds.profile.feature_dim, 16,
                           ds.profile.num_classes)
            exe = runtime.compile(spec, ds, backend=BACKEND,
                                  max_shard_n=256)
            single_ms = _time_forward(exe)
            baseline_ag = None
            for method in PARTITIONS:
                sexe = runtime.compile(spec, ds, backend=BACKEND,
                                       max_shard_n=256, mesh=mesh,
                                       partition=method,
                                       hub_cache=HUB_CACHE)
                np.testing.assert_allclose(
                    np.asarray(exe.forward()), np.asarray(sexe.forward()),
                    rtol=5e-4, atol=5e-4)
                sharded_ms = _time_forward(sexe)
                cs = sexe.verify_comm()
                ag = cs["measured_allgather_wire_bytes"]
                if method == "contiguous":
                    baseline_ag = ag
                row = {
                    "graph": graph, "arch": arch, "partition": method,
                    "nodes": ds.profile.num_nodes,
                    "edges": int(ds.edges.shape[0]),
                    "single_device_ms": round(single_ms, 3),
                    "sharded_8dev_ms": round(sharded_ms, 3),
                    "nodes_per_s_single": round(
                        ds.profile.num_nodes / (single_ms / 1e3), 1),
                    "nodes_per_s_sharded": round(
                        sharded_throughput(sexe, sharded_ms), 1),
                    "allgather_wire_bytes": ag,
                    "allreduce_wire_bytes":
                        cs["measured_wire_bytes"].get("all-reduce", 0.0),
                    "plan_edge_pull_bound_bytes": sum(
                        cs["plan_transfer_bytes_per_layer"].values()),
                    "cross_group_edge_frac": round(
                        cs["cross_group_edge_frac"], 4),
                    "imbalance": round(sexe.partition.edge_imbalance, 3),
                }
                if method == "fennel":
                    row["hub_rows"] = cs["hub_rows"]
                    row["hub_cap"] = cs["hub_cap"]
                    row["halo_cap"] = cs["halo_cap"]
                    row["allgather_reduction_vs_contiguous"] = round(
                        baseline_ag / max(ag, 1.0), 1)
                rows.append(row)
                extra = (f" ({row['allgather_reduction_vs_contiguous']}x "
                         f"less wire)" if method == "fennel" else "")
                print(f"{graph:10s} {arch:10s} {method:10s} "
                      f"single {single_ms:8.1f} ms | "
                      f"sharded {sharded_ms:8.1f} ms | "
                      f"ag {ag / 2**20:7.2f} MiB cross "
                      f"{row['cross_group_edge_frac']:.3f} imb "
                      f"{row['imbalance']:.2f}{extra}", flush=True)

    payload = {
        "devices": DEVICES,
        "mesh": {"data": n_data, "model": MODEL_PARALLEL},
        "backend": BACKEND,
        "iters": ITERS,
        "hub_cache": HUB_CACHE,
        "note": "8 virtual host devices on one CPU: wall-clock measures "
                "SPMD overhead, not speedup; comm volumes are exact",
        "rows": rows,
    }
    merge_bench_json("dist_scaling", payload)
    derived = (f"{len(rows)} cells, mesh data={n_data} x "
               f"model={MODEL_PARALLEL}, partitions={PARTITIONS}")
    return rows, derived


def main() -> None:
    from repro import env
    env.enable_compile_cache()
    t0 = time.perf_counter()
    rows, derived = bench_dist_scaling()
    us = (time.perf_counter() - t0) * 1e6
    print(f'dist_scaling,{us:.1f},"{derived}"')


if __name__ == "__main__":
    main()
