"""Aggregation kernels' share of their roofline in the training step.

Until the kernels carry names of their own, every Pallas call in the trace
counts: the forward's aggregations and the extractions fused with them run
as Pallas calls, the backward as XLA operations. The least time that
forward work needs (per operation, the larger of its required FLOPs over
the peak and its required bytes over the bandwidth, counted from the
configuration's shapes) times the steps, over the device time of the
Pallas calls."""
from bench.harness import work


def read(ctx):
    pallas_s = ctx["trace"]["pallas_s"]
    if pallas_s <= 0:
        raise RuntimeError("no Pallas call in the trace: the kernels did "
                           "not run, or their trace name changed")
    need = sum(op.roofline_s(ctx["peak"])
               for op in work.forward_ops(ctx["config"], ctx["ref_mod"]))
    return 100.0 * need * ctx["counters"]["steps"] / pallas_s
