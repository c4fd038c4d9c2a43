"""The edge softmax kernel's share of its roofline in full-graph
inference (GAT): the least time the forward's attention operations need
(the reference's own count per layer: per edge and head the score,
softmax and weighted sum; the edge list, z, the scores and the output
read or written once) times the forwards, over the device time of the
``gnn_edge_softmax_aggregate`` kernel.

The kernel is named here, beside the kernels the harness names, so its
time is summed over its instructions as ``kernels.device_s`` sums theirs,
and no partial sum is reported: the read raises where the kernels leave
more than ``kernels.UNACCOUNTED`` of the Pallas time unaccounted."""
import collections

from bench.harness import kernels

KERNEL = "gnn_edge_softmax_aggregate"


def read(ctx):
    red = ctx["trace"]
    if red["pallas_s"] <= 0:
        raise RuntimeError("no Pallas call in the trace: the kernels did "
                           "not run")
    by = collections.Counter()
    for op, s in red["device_ops"]:
        by[kernels.base_name(op)] += s
    t = by[KERNEL]
    if t <= 0:
        return None
    named = sum(by[n] for n in set(kernels.NAMES) | {KERNEL})
    if named < (1.0 - kernels.UNACCOUNTED) * red["pallas_s"]:
        raise RuntimeError(
            f"the named kernels leave {red['pallas_s'] - named:.6f} s of "
            f"{red['pallas_s']:.6f} s of Pallas time unaccounted")
    need = kernels.roofline_s(ctx, "attention")
    return 100.0 * need * ctx["counters"]["forwards"] / t
