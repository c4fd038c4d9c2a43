#!/usr/bin/env python3
"""Start the GNN main path on a TPU and check its answers.

One process, one chip, the entry points a user calls:

  1. device check — a TPU or nothing (no CPU fallback);
  2. forward — ``runtime.compile`` of GCN (Kipf & Welling: 2 layers,
     hidden 16) on the full pubmed profile with the ``pallas`` backend
     compiled for the chip, compared with the reference oracle run at
     ``highest`` matmul precision; then sage_mean, sage_max (the
     edge-list gather kernel), gin and gat (8 heads x 8 hidden,
     Velickovic et al.) on full cora the same way;
  3. serve — ``GNNServeEngine(backend="pallas")`` behind a ``Server``
     answering node requests on pubmed/gcn; every ticket must complete;
  4. train — five full-batch ``runtime.fit`` steps on pubmed/gcn; the
     loss must stay finite and fall;
  5. report — set-up seconds and peak device memory (smoke figures, not
     benchmark results), then one JSON line naming the device.

``--mesh4`` runs only the sharded path on four chips: gcn on full pubmed
compiled onto a (data=2, model=2) mesh with the fennel partitioner,
compared with a single-device forward, ``verify_comm()``, and two
data-parallel ``runtime.fit`` steps.

Weights and graphs are generated from ``--seed``. Exits non-zero, and
prints no result line, when any phase fails or JAX finds no TPU.

    python chip_smoke.py            # one chip
    python chip_smoke.py --mesh4    # four chips
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# max |out - oracle| over max |oracle|, per compared forward. The kernels
# accumulate in f32, but the MXU may round f32 operands to bf16 (2^-9
# relative) and XLA's default precision does so outside the kernels; a
# wrong edge, block or normalization moves outputs by O(1) of their scale.
TOL = 1e-2
SERVE_REQUESTS = 32
TRAIN_STEPS = 5


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def compare(name: str, out, ref) -> None:
    """Print and gate the error of ``out`` against ``ref``."""
    import numpy as np
    out = np.asarray(out, np.float64)
    ref = np.asarray(ref, np.float64)
    check(out.shape == ref.shape, f"{name}: shape {out.shape} != "
          f"{ref.shape}")
    check(bool(np.isfinite(out).all()), f"{name}: non-finite outputs")
    scale = float(np.abs(ref).max())
    abs_err = float(np.abs(out - ref).max())
    big = np.abs(ref) > 1e-3 * scale
    rel_err = float((np.abs(out - ref)[big] / np.abs(ref)[big]).max())
    print(f"  {name}: shape {out.shape} max|ref| {scale:.6g} "
          f"max abs err {abs_err:.6g} max rel err {rel_err:.6g} "
          f"(entries > 1e-3 max|ref|) normalized {abs_err / scale:.6g} "
          f"<= {TOL:g}", flush=True)
    check(abs_err <= TOL * scale, f"{name}: error {abs_err / scale:.3g} "
          f"of the output scale exceeds {TOL:g}")


def kernel_count(exe) -> int:
    """Pallas kernels in the compiled forward's lowering — zero would
    mean the interpreter (or a non-kernel path) ran instead."""
    ga = exe._graph_args()
    lowered = exe._jit_forward.lower(exe.params, exe._h_grouped, *ga)
    return lowered.as_text().count("tpu_custom_call")


def oracle_forward(spec, graph, params):
    """The same model through the reference backend at full f32, its grid
    uploaded in float32 too (compiled under the same precision)."""
    import jax
    from repro import runtime
    with jax.default_matmul_precision("highest"):
        ref = runtime.compile(spec, graph, backend="reference",
                              params=params)
        return jax.block_until_ready(ref.forward())


def phase_forward(pubmed, cora, seed: int) -> dict:
    import jax
    from repro import runtime
    from repro.gnn.models import ZooSpec

    prof = pubmed.profile
    spec = ZooSpec("gcn", prof.feature_dim, 16, prof.num_classes,
                   num_layers=2)
    t0 = time.perf_counter()
    exe = runtime.compile(spec, pubmed, backend="pallas", plan="analytic",
                          seed=seed)
    compile_s = time.perf_counter() - t0
    print(exe.summary(), flush=True)
    kernels = kernel_count(exe)
    print(f"  pallas kernels in the gcn forward: {kernels}", flush=True)
    check(kernels > 0, "gcn forward lowered without a Pallas kernel")
    t0 = time.perf_counter()
    logits = jax.block_until_ready(exe.forward())
    first_call_s = time.perf_counter() - t0
    compare("pubmed/gcn", logits, oracle_forward(spec, pubmed, exe.params))

    cprof = cora.profile
    for arch, hidden, heads in (("sage_mean", 16, 2), ("sage_max", 16, 2),
                                ("gin", 16, 2), ("gat", 64, 8)):
        cspec = ZooSpec(arch, cprof.feature_dim, hidden, cprof.num_classes,
                        num_layers=2, heads=heads)
        cexe = runtime.compile(cspec, cora, backend="pallas",
                               plan="analytic", seed=seed)
        check(kernel_count(cexe) > 0, f"cora/{arch} lowered without a "
              f"Pallas kernel")
        compare(f"cora/{arch}", cexe.forward(),
                oracle_forward(cspec, cora, cexe.params))
    return {"exe": exe, "logits": logits, "compile_s": compile_s,
            "first_call_s": first_call_s}


def phase_serve(pubmed, spec, params, logits) -> None:
    import numpy as np
    from repro.serving import Completed, SchedulerConfig, Server
    from repro.serving.gnn_engine import GNNServeEngine, NodeRequest

    engine = GNNServeEngine(backend="pallas", plan="analytic")
    engine.register_graph("pubmed", pubmed)
    engine.register_model("gcn", spec, params=params)
    server = Server(engine, SchedulerConfig(max_batch_size=8,
                                            max_queue_depth=SERVE_REQUESTS))
    rng = np.random.default_rng(0)
    n = pubmed.profile.num_nodes
    reqs = [NodeRequest("pubmed", rng.integers(0, n, size=int(k)), "gcn")
            for k in rng.integers(1, 65, size=SERVE_REQUESTS)]
    tickets = [server.submit(r) for r in reqs]
    server.drain()
    outcomes = [t.poll() for t in tickets]
    bad = [o for o in outcomes if not isinstance(o, Completed)]
    print(f"  {server.report()}", flush=True)
    check(not bad, f"{len(bad)} of {len(tickets)} tickets not completed, "
          f"first: {bad[:1]}")
    # the served classes are the compiled forward's argmax wherever the
    # top two logits are not within rounding of each other
    host = np.asarray(logits, np.float64)
    top2 = np.sort(host, axis=-1)[:, -2:]
    decisive = (top2[:, 1] - top2[:, 0]) > 1e-4 * np.abs(host).max()
    for r, o in zip(reqs, outcomes):
        ids = np.asarray(r.node_ids)
        want = host[ids].argmax(-1)
        keep = decisive[ids]
        check(bool((o.value.classes[keep] == want[keep]).all()),
              "served classes disagree with the compiled forward")
    forwards = engine.stats["logits_cache_misses"]
    print(f"  served {len(tickets)} tickets, all Completed; device "
          f"forwards {forwards}", flush=True)
    check(forwards >= 1, "no request ran a forward on the device")


def phase_train(pubmed, spec, seed: int, *, steps: int, mesh=None,
                partition: str = "contiguous") -> list:
    from repro import runtime
    res = runtime.fit(spec, pubmed, steps=steps, lr=1e-2, backend="pallas",
                      plan="analytic", seed=seed, log_every=1, mesh=mesh,
                      partition=partition,
                      log=lambda s: print(f"  {s}", flush=True))
    losses = [loss for _, loss in res.history]
    check(len(losses) == steps, f"expected {steps} losses, got {losses}")
    check(all(math.isfinite(v) for v in losses), f"non-finite loss "
          f"{losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    return losses


def run_one_chip(seed: int) -> None:
    import jax
    from repro.graphs.datasets import make_dataset

    pubmed = make_dataset("pubmed", seed=seed)
    cora = make_dataset("cora", seed=seed)
    print(f"[forward] pubmed {pubmed.profile.num_nodes} nodes "
          f"{pubmed.edges.shape[0]} edges; cora {cora.profile.num_nodes} "
          f"nodes; tolerance {TOL:g} of max|oracle|", flush=True)
    fwd = phase_forward(pubmed, cora, seed)
    spec, params = fwd["exe"].spec, fwd["exe"].params
    print("[serve] pubmed/gcn behind a Server", flush=True)
    phase_serve(pubmed, spec, params, fwd["logits"])
    print(f"[train] pubmed/gcn, {TRAIN_STEPS} full-batch steps", flush=True)
    losses = phase_train(pubmed, spec, seed, steps=TRAIN_STEPS)
    stats = jax.devices()[0].memory_stats() or {}
    print(f"[report] smoke figures, not benchmark results: pubmed/gcn "
          f"compile {fwd['compile_s']:.3f} s, first forward "
          f"{fwd['first_call_s']:.3f} s, peak_bytes_in_use "
          f"{stats.get('peak_bytes_in_use', 'not reported')}; losses "
          f"{losses}", flush=True)


def run_mesh4(seed: int) -> None:
    import jax
    from repro import runtime
    from repro.gnn.models import ZooSpec
    from repro.graphs.datasets import make_dataset
    from repro.launch.mesh import make_mesh_for

    check(jax.device_count() >= 4, f"--mesh4 needs 4 devices, jax sees "
          f"{jax.device_count()}")
    pubmed = make_dataset("pubmed", seed=seed)
    prof = pubmed.profile
    spec = ZooSpec("gcn", prof.feature_dim, 16, prof.num_classes,
                   num_layers=2)
    mesh = make_mesh_for(4, model_parallel=2)
    print(f"[mesh4] data=2 x model=2 over {mesh.devices.ravel().tolist()}",
          flush=True)
    single = runtime.compile(spec, pubmed, backend="pallas",
                             plan="analytic", seed=seed)
    want = jax.block_until_ready(single.forward())
    t0 = time.perf_counter()
    exe = runtime.compile(spec, pubmed, backend="pallas", plan="analytic",
                          params=single.params, mesh=mesh,
                          partition="fennel")
    print(exe.summary(), flush=True)
    got = jax.block_until_ready(exe.forward())
    print(f"  sharded compile + first forward "
          f"{time.perf_counter() - t0:.3f} s (smoke figure)", flush=True)
    # the dense grid is the big array: each device must hold a shard of
    # it, not the whole grid sitting on the first device
    blocks = exe._graph_args()[0]
    per_dev = {s.device.id: s.data.nbytes for s in blocks.addressable_shards}
    print(f"  dense grid {blocks.nbytes} B, bytes per device {per_dev}",
          flush=True)
    check(len(per_dev) == 4 and max(per_dev.values()) < blocks.nbytes,
          f"dense grid not sharded over 4 devices: {per_dev}")
    compare("pubmed/gcn sharded vs single-device", got, want)
    cs = exe.verify_comm()
    print(f"  verify_comm: all-gather measured "
          f"{cs['measured_allgather_wire_bytes']:.0f} B, expected "
          f"{cs['expected_allgather_wire_bytes']:.0f} B; cross-group edges "
          f"{cs['cross_group_edge_frac']:.4f}", flush=True)
    print("[mesh4] 2 data-parallel fit steps", flush=True)
    phase_train(pubmed, spec, seed, steps=2, mesh=mesh, partition="fennel")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--mesh4", action="store_true",
                    help="run only the sharded path on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    from repro import env
    env.enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    print(f"[device] {dev.platform} {dev.device_kind} x{len(devices)}, "
          f"jax {jax.__version__}", flush=True)
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, jax found {dev.platform}",
              file=sys.stderr)
        return 1
    try:
        if args.mesh4:
            run_mesh4(args.seed)
        else:
            run_one_chip(args.seed)
    except SmokeFailure as err:
        print(f"chip_smoke: FAILED: {err}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
