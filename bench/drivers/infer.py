"""Closed-loop full-graph inference: back-to-back ``Executable.forward()``
calls, each blocked until its logits are ready.

Set-up compiles the model and runs one forward. The window keeps a few of
its forwards' logits, drawn from the seed; each is compared with the
reference's logits of the same graph and weights.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import compare, reference
from bench.harness.program import compile_program

KEEP = 4


class Run:
    def __init__(self, ctx):
        self.ctx = ctx
        self.p0 = jax.device_get(ctx.params)

    def setup(self) -> None:
        self._exe = compile_program(self.ctx)
        self.forward = self.ctx.hook("forward", self._exe.forward)
        jax.block_until_ready(self.forward())

    def window(self, seconds: float) -> dict:
        rng = np.random.default_rng([self.ctx.seed, 4])
        span = self.ctx.span
        kept, n = [], 0
        t0 = time.perf_counter()
        while True:
            with span("bench.forward"):
                out = jax.block_until_ready(self.forward())
            n += 1
            # reservoir sample of KEEP outputs over the whole window
            if len(kept) < KEEP:
                kept.append(out)
            elif (j := rng.integers(n)) < KEEP:
                kept[j] = out
            if time.perf_counter() - t0 >= seconds:
                break
        t1 = time.perf_counter()
        self.kept = [np.asarray(jax.device_get(o)) for o in kept]
        return {"attempted": n, "failed": 0, "window_s": t1 - t0,
                "e2e": {"infer_ms": (t1 - t0) * 1e3 / n},
                "counters": {"forwards": n}}

    def release(self) -> None:
        self._exe = self.forward = None

    def check(self, control: str | None = None) -> dict:
        g = self.ctx.graph
        p0 = jax.tree.map(jnp.asarray, self.p0)
        ref = reference.Forward(self.ctx.ref_mod, g.num_nodes)(
            p0, g.features, g.edges)
        outs = self.kept if control is None else [reference.Forward(
            self.ctx.ref_mod, g.num_nodes, control)(p0, g.features, g.edges)]
        return {"logit_err": max(compare.logit_err(o, ref) for o in outs)}
