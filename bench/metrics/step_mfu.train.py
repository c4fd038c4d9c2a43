"""The training step's share of the chip's peak: the FLOPs a step requires
(three forwards, counted from the configuration's shapes) times the steps
completed, over the window and the peak."""
from bench.harness import work


def read(ctx):
    flops = work.train_step_flops(ctx["config"], ctx["ref_mod"])
    rate = ctx["counters"]["steps"] * flops / ctx["window_s"]
    return 100.0 * rate / ctx["peak"]["flops_per_s"]
