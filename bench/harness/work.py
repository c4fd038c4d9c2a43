"""The work a forward requires, counted from a configuration's shapes, so
that it reads the same whatever implements the operation.

  aggregation over d features: 2 nnz d FLOPs, nnz 8 + 2 N d 4 bytes
    (one f32 weight and one int32 index per nonzero, features read and
    written once), where nnz counts the edges and one self loop per node;
  dense extraction (k -> m): 2 N k m FLOPs, 4 (N k + k m + N m) bytes;
  an operation the reference counts itself, such as an edge softmax's
    per-edge, per-head scores, exponent, normalisation and weighted sum:
    the kind, FLOPs and bytes it gives.

It is never the MACs of the dense (n x n) blocks the kernels walk: a
sparser kernel must raise a share of the roofline, not shrink its base.
A training step counts three forwards.
"""
from __future__ import annotations

import dataclasses

from bench.harness.common import BENCH, load_json


@dataclasses.dataclass(frozen=True)
class Op:
    kind: str       # "agg" | "dense" | a counted operation's own kind
    flops: float
    bytes: float

    def roofline_s(self, peak: dict) -> float:
        """Least time the chip could take: the larger of the compute and
        the memory bound."""
        return max(self.flops / peak["flops_per_s"],
                   self.bytes / peak["bytes_per_s"])


def nnz(cfg: dict) -> int:
    g = cfg["graph"]
    return g["num_edges"] + (g["num_nodes"] if cfg["self_loops"] else 0)


def forward_ops(cfg: dict, ref_mod) -> list[Op]:
    """The forward's required operations, in order, from the reference's
    ``ops(cfg)`` list of ("agg", d), ("dense", k, m) and
    ("counted", kind, flops, bytes)."""
    n, z = cfg["graph"]["num_nodes"], nnz(cfg)
    out = []
    for op in ref_mod.ops(cfg):
        if op[0] == "agg":
            d = op[1]
            out.append(Op("agg", 2.0 * z * d, z * 8.0 + 2.0 * n * d * 4))
        elif op[0] == "dense":
            k, m = op[1], op[2]
            out.append(Op("dense", 2.0 * n * k * m,
                          4.0 * (n * k + k * m + n * m)))
        elif op[0] == "counted":
            _, kind, flops, nbytes = op
            if flops < 0 or nbytes < 0:
                raise ValueError(f"negative work in {op!r}")
            out.append(Op(kind, float(flops), float(nbytes)))
        else:
            raise ValueError(f"unknown op {op!r}")
    return out


def forward_flops(cfg: dict, ref_mod) -> float:
    return sum(op.flops for op in forward_ops(cfg, ref_mod))


def train_step_flops(cfg: dict, ref_mod) -> float:
    return 3.0 * forward_flops(cfg, ref_mod)


def peak(device_kind: str) -> dict:
    """The chip's published peaks from ``peaks.json``; an unknown device is
    an error, not a default."""
    table = load_json(BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (known: {sorted(table)})")
    return table[device_kind]
