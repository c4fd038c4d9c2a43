"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — smoke tests and benches must keep seeing the
single real CPU device; only launch/dryrun.py forces 512 host devices.

Production target: TPU v5e pods. Single pod = 16×16 = 256 chips
(data, model); multi-pod = 2×16×16 = 512 chips (pod, data, model) where
the leading "pod" axis crosses DCN. Designed so the same logical sharding
rules scale to N pods by growing the leading axis (elastic scaling: see
dist/shardings.py — batch shards over ("pod","data") and re-lowers for any
pod count without code changes).

``make_mesh_for`` is the elastic variant the GNN runtime uses:
``runtime.compile(spec, graph, mesh=make_mesh_for(jax.device_count()))``
returns a sharded Executable (see dist/gnn.py). Mesh construction goes
through dist/compat.py.
"""
from __future__ import annotations

from repro.dist.compat import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh_for(devices: int, *, model_parallel: int = 16):
    """Elastic variant: build a (data, model) mesh for whatever device
    count the scheduler hands us (node failures / scale-up)."""
    assert devices % model_parallel == 0, (devices, model_parallel)
    return make_mesh((devices // model_parallel, model_parallel),
                     ("data", "model"))


def mesh_from_cli(devices: int, model_parallel: int):
    """Launcher-side `--mesh N --model-parallel M` handling, shared by
    serve.py and train_gnn.py: validate the visible device count (with
    the CPU XLA_FLAGS hint) and build the (data, model) mesh."""
    import jax
    if jax.device_count() < devices:
        raise SystemExit(
            f"--mesh {devices} needs {devices} devices but jax sees "
            f"{jax.device_count()}; on CPU export XLA_FLAGS="
            f"--xla_force_host_platform_device_count={devices}")
    return make_mesh_for(devices, model_parallel=model_parallel)
