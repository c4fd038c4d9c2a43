"""Aggregation kernels' share of their roofline in full-graph inference:
the least time the forward's required work needs (per operation, from the
configuration's shapes) times the forwards, over the device time of the
Pallas calls, which run every aggregation and extraction of the forward."""
from bench.harness import work


def read(ctx):
    pallas_s = ctx["trace"]["pallas_s"]
    if pallas_s <= 0:
        raise RuntimeError("no Pallas call in the trace: the kernels did "
                           "not run, or their trace name changed")
    need = sum(op.roofline_s(ctx["peak"])
               for op in work.forward_ops(ctx["config"], ctx["ref_mod"]))
    return 100.0 * need * ctx["counters"]["forwards"] / pallas_s
