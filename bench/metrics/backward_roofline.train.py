"""The training step beyond its forward kernel, against its roofline:
a step requires three forwards' work (``work.py``), one of them the
forward the ``gnn_fused_aggregate_extract`` kernel runs, so the least time
of two forwards' work times the steps, over the device's busy time less
that kernel's time (the backward, the loss and the optimizer)."""
from bench.harness import kernels


def read(ctx):
    t = ctx["trace"]
    fused = kernels.device_s(t, kernels.FUSED)
    if fused is None:
        return None
    rest = t["busy_s"] - fused
    if rest <= 0:
        raise RuntimeError(f"busy {t['busy_s']} s is no more than the "
                           f"forward kernel's {fused} s")
    need = 2.0 * kernels.roofline_s(ctx)
    return 100.0 * need * ctx["counters"]["steps"] / rest
