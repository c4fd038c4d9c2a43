"""The training step beyond its forward kernels, against its roofline:
a step requires three forwards' work (``work.py``), one of them the
forward the Pallas kernels run, so the least time of two forwards' work
times the steps, over the device's busy time less all Pallas time (the
backward, the loss and the optimizer). Every Pallas call of the step is
the forward: the kernels' custom VJP differentiates their jax.numpy
oracles, so the backward runs as XLA operations whichever kernels the
forward takes."""
from bench.harness import kernels


def read(ctx):
    t = ctx["trace"]
    if t["pallas_s"] <= 0:
        raise RuntimeError("no Pallas call in the trace: the kernels did "
                           "not run")
    rest = t["busy_s"] - t["pallas_s"]
    if rest <= 0:
        raise RuntimeError(f"busy {t['busy_s']} s is no more than the "
                           f"forward kernels' {t['pallas_s']} s")
    need = 2.0 * kernels.roofline_s(ctx)
    return 100.0 * need * ctx["counters"]["steps"] / rest
