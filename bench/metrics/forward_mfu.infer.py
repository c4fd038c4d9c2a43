"""The forward's share of the chip's peak: the FLOPs a full-graph forward
requires (counted from the configuration's shapes) times the forwards
completed, over the window and the peak."""
from bench.harness import work


def read(ctx):
    flops = work.forward_flops(ctx["config"], ctx["ref_mod"])
    rate = ctx["counters"]["forwards"] * flops / ctx["window_s"]
    return 100.0 * rate / ctx["peak"]["flops_per_s"]
