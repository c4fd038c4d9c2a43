"""Closed-loop full-batch training: back-to-back steps of one compiled
``TrainableExecutable`` (forward through the Pallas kernels the plan
picks, the custom-VJP backward, AdamW). The host dispatches steps ahead of
the chip, up to the traffic's ``ahead_s`` seconds of them, so that a stall
of the host shorter than that leaves the chip fed; losses are read after
the window.

Set-up compiles the model, builds the trainable object and drives it from
the seed through its first steps with the window's own call and batch;
the window then carries on from that state. The reference replays those
first steps: each step's loss, the first gradient as the optimizer holds
it after one step (its first moment over 1 - b1), and each leaf's change
over the steps are compared: the losses as relative gaps, the gradient
as the norm of its difference from the reference's, the change as the
gap of its norm from the reference's (all by the worst leaf).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import compare, reference
from bench.harness.program import compile_program

CHECK_STEPS = 3


class Run:
    def __init__(self, ctx):
        self.ctx = ctx
        self.p0 = jax.device_get(ctx.params)

    def setup(self) -> None:
        from repro.runtime.fit import TrainableExecutable
        from repro.training.optimizer import AdamWConfig

        cfg, g = self.ctx.cell.config, self.ctx.graph
        o = cfg["optimizer"]
        exe = compile_program(self.ctx)
        te = TrainableExecutable(
            exe, g.labels, train_mask=g.train_mask,
            opt_cfg=AdamWConfig(lr=o["lr"], b1=o["b1"], b2=o["b2"],
                                eps=o["eps"],
                                weight_decay=o["weight_decay"],
                                grad_clip=0.0, schedule="constant",
                                warmup_steps=0))
        self.step = self.ctx.hook("step", te.step_fn)
        self.batch = te.data(0)
        p, s = te.params, te.opt_state
        losses = []
        for k in range(CHECK_STEPS):
            p, s, m = self.step(p, s, self.batch)
            losses.append(m["loss"])
            if k == 0:
                m1 = jax.device_get(s["m"])
        self.prog = {
            "losses": [float(x) for x in jax.device_get(losses)],
            "grads": jax.tree.map(lambda a: np.asarray(a) / (1 - o["b1"]), m1),
            "params": jax.device_get(p)}
        self.state = (p, s)
        self._te = te

    def window(self, seconds: float) -> dict:
        p, s = self.state
        span = self.ctx.span
        ahead_s = float(self.ctx.cell.traffic["ahead_s"])
        losses, n, done = [], 0, 0
        t0 = time.perf_counter()
        while True:
            with span("bench.train_step"):
                p, s, m = self.step(p, s, self.batch)
            losses.append(m["loss"])
            n += 1
            if time.perf_counter() - t0 >= seconds:
                break
            # queue no more than ahead_s seconds of steps, by the rate of
            # the steps waited for so far: wait on the oldest beyond that
            while n - done > max(1.0, ahead_s * done
                                 / (time.perf_counter() - t0)):
                losses[done].block_until_ready()
                done += 1
        # nothing more is sent; all that was sent counts, over all the time
        # it took
        jax.block_until_ready((p, s, losses))
        t1 = time.perf_counter()
        self.state = (p, s)
        vals = np.asarray(jax.device_get(losses), np.float64)
        failed = int((~np.isfinite(vals)).sum())
        return {"attempted": n, "failed": failed, "window_s": t1 - t0,
                "e2e": {"train_step_ms": (t1 - t0) * 1e3 / n},
                "counters": {"steps": n},
                "notes": [f"{n} steps, loss {vals[0]:.6f} -> {vals[-1]:.6f}"]}

    def release(self) -> None:
        self.state = self.step = self.batch = self._te = None

    def check(self, control: str | None = None) -> dict:
        """The compared numbers; ``control`` puts the reference computed in
        that precision in the program's place."""
        cfg, g = self.ctx.cell.config, self.ctx.graph
        p0 = jax.tree.map(jnp.asarray, self.p0)
        ref = reference.train_steps(self.ctx.ref_mod, cfg, p0, g, CHECK_STEPS)
        prog = self.prog if control is None else reference.train_steps(
            self.ctx.ref_mod, cfg, p0, g, CHECK_STEPS, mode=control)
        return numbers(prog, ref, self.p0)


def numbers(prog: dict, ref: dict, p0) -> dict:
    return {
        "loss_gap": max(compare.rel_gap(a, b) for a, b in
                        zip(prog["losses"], ref["losses"])),
        "grad_err": compare.leaf_diff(prog["grads"], ref["grads"],
                                      ref["grads"]),
        "update_gap": compare.leaf_norm_gap(
            compare.tree_sub(prog["params"], p0),
            compare.tree_sub(ref["params"], p0), ref["grads"]),
    }
