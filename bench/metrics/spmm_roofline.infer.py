"""The shard-grid aggregation kernel's share of its roofline in full-graph
inference: the least time the forward's aggregations need (per operation,
from the configuration's shapes) times the forwards, over the device time
of the ``gnn_shard_spmm`` kernel."""
from bench.harness import kernels


def read(ctx):
    t = kernels.device_s(ctx["trace"], kernels.SPMM)
    if t is None:
        return None
    need = kernels.roofline_s(ctx, "agg")
    return 100.0 * need * ctx["counters"]["forwards"] / t
