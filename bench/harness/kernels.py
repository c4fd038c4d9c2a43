"""Device time of the program's Pallas kernels by name, from a reduced
trace (``trace.reduce``).

The program names each Pallas call after its kernel; the trace shows the
call's HLO instruction, which XLA names ``<kernel>.<N>``, one ``N`` per
call site. A kernel's time is the sum over its instructions among the
reduction's ``device_ops``.

The benchmark keeps its own record of the program's kernels, one file
each: ``kernels/<name>.json`` gives the name, the module that defines it
(``KERNEL_NAME``) and the work kinds (``work.Op.kind``) the kernel runs.
A rename in the program then shows here as a missing kernel, not as a
quietly moved metric, and a new kernel is added with a new file.
"""
from __future__ import annotations

import collections
import pathlib
import re

from bench.harness import work
from bench.harness.common import BENCH, load_json

KERNELS = BENCH / "kernels"
FIELDS = {"name", "module", "work"}


def records(directory=KERNELS) -> dict[str, dict]:
    """Every kernel file in ``directory``, by name. A file must hold just
    ``FIELDS``, and be named after its kernel."""
    out = {}
    for path in sorted(pathlib.Path(directory).glob("*.json")):
        rec = load_json(path)
        if set(rec) != FIELDS or rec["name"] != path.stem:
            raise ValueError(f"{path}: a kernel file holds {sorted(FIELDS)}"
                             f" and is named after its kernel, not {rec}")
        out[rec["name"]] = rec
    return out


NAMES = tuple(records())
FUSED = "gnn_fused_aggregate_extract"
SPMM = "gnn_shard_spmm"
DENSE = "gnn_dense_engine"
GATHER = "gnn_seg_gather"

# the share of Pallas time the named kernels may leave unaccounted
UNACCOUNTED = 0.01


def base_name(op: str) -> str:
    """``gnn_shard_spmm.3`` -> ``gnn_shard_spmm``."""
    return re.sub(r"\.\d+$", "", op)


def device_s(red: dict, name: str) -> float | None:
    """Device seconds of kernel ``name`` in the reduced trace ``red``.

    None where no Pallas time carries any of the kernels' names: a program
    that predates them, whose metric is left out. Raises where the kernel
    is absent while others are named, or where the named kernels leave
    more than ``UNACCOUNTED`` of the Pallas time unaccounted (a kernel fell
    out of the reduction's top operations, or was renamed), so that no
    partial sum is ever reported.
    """
    if red["pallas_s"] <= 0:
        raise RuntimeError("no Pallas call in the trace: the kernels did "
                           "not run")
    by = collections.Counter()
    for op, s in red["device_ops"]:
        by[base_name(op)] += s
    named = sum(by[n] for n in NAMES)
    if named == 0:
        return None
    if by[name] <= 0:
        raise RuntimeError(f"kernel {name} absent from the trace's device "
                           f"ops: {[op for op, _ in red['device_ops']]}")
    if named < (1.0 - UNACCOUNTED) * red["pallas_s"]:
        raise RuntimeError(
            f"the named kernels leave {red['pallas_s'] - named:.6f} s of "
            f"{red['pallas_s']:.6f} s of Pallas time unaccounted: a kernel "
            f"is missing from the device ops or was renamed")
    return by[name]


def roofline_s(ctx: dict, kind: str | None = None) -> float:
    """Least time of one forward's required work (``work.forward_ops``),
    of the operations of ``kind`` (``agg``, ``dense`` or a counted
    operation's own) or of all."""
    return sum(op.roofline_s(ctx["peak"])
               for op in work.forward_ops(ctx["config"], ctx["ref_mod"])
               if kind is None or op.kind == kind)
