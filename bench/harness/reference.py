"""Drive a configuration's plain reference: forward logits, and the first
full-batch training steps with its own AdamW. Nothing here imports the
program."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness.precision import Numerics


def init_params(ref_mod, cfg: dict, seed: int):
    """Glorot-normal weights in the program's layout, made on the device in
    one jitted call from the seed."""
    shapes = ref_mod.param_shapes(cfg)

    @jax.jit
    def make(key):
        layers = []
        for layer in shapes:
            out = {}
            for name, shape in layer.items():
                key, sub = jax.random.split(key)
                std = jnp.sqrt(2.0 / (shape[0] + shape[-1]))
                out[name] = jax.random.normal(sub, shape, jnp.float32) * std
            layers.append(out)
        return {"layers": layers}

    return make(jax.random.key(weight_seed(seed)))


def weight_seed(seed: int) -> int:
    """A 31-bit key seed drawn from the run's seed (which may be larger
    than JAX's key takes)."""
    return int(np.random.default_rng([seed, 1]).integers(2 ** 31))


class Forward:
    """The reference forward over one graph size, jitted once; the edge
    arrays are arguments, so graphs with the same edge count share it."""

    def __init__(self, ref_mod, num_nodes: int, mode: str = "highest"):
        self.ref_mod, self.num_nodes = ref_mod, num_nodes
        num = Numerics(mode)
        self._fn = jax.jit(lambda p, x, s, d, w: ref_mod.forward(
            p, x, s, d, w, num_nodes, num))

    def __call__(self, params, features, edges) -> np.ndarray:
        s, d, w = self.ref_mod.edge_weights(edges, self.num_nodes)
        out = self._fn(params, features, s, d, w)
        return np.asarray(jax.device_get(out), np.float64)


def masked_cross_entropy(logits, labels, mask):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    m = mask.astype(jnp.float32)
    return jnp.sum(nll * m) / jnp.maximum(jnp.sum(m), 1.0)


def train_steps(ref_mod, cfg: dict, params, graph, steps: int,
                mode: str = "highest", mask=None) -> dict:
    """``steps`` full-batch AdamW steps of the reference from ``params``:
    the loss of each step, the first step's gradient per leaf, and the
    parameters after the last step, all as host arrays. ``mask`` replaces
    the graph's train mask (for planted faults)."""
    opt = cfg["optimizer"]
    lr, b1, b2, eps = opt["lr"], opt["b1"], opt["b2"], opt["eps"]
    num = Numerics(mode)
    n = graph.num_nodes
    s, d, w = ref_mod.edge_weights(graph.edges, n)
    x = jnp.asarray(graph.features)
    labels = jnp.asarray(graph.labels)
    mask = jnp.asarray(graph.train_mask if mask is None else mask)
    data = (x, s, d, w, labels, mask)

    def loss_fn(p, x, s, d, w, labels, mask):
        logits = ref_mod.forward(p, x, s, d, w, n, num)
        return masked_cross_entropy(logits, labels, mask)

    @jax.jit
    def step(p, m, v, t, *data):
        loss, g = jax.value_and_grad(loss_fn)(p, *data)
        g = jax.tree.map(lambda a: a.astype(jnp.float32), g)
        m = jax.tree.map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
        v = jax.tree.map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, v, g)
        bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t
        p = jax.tree.map(
            lambda p_, m_, v_: p_ - lr * (m_ / bc1) / (jnp.sqrt(v_ / bc2)
                                                       + eps), p, m, v)
        return p, m, v, loss, g

    zeros = jax.tree.map(jnp.zeros_like, params)
    p, m, v = params, zeros, zeros
    losses, grads = [], None
    for t in range(1, steps + 1):
        p, m, v, loss, g = step(p, m, v, jnp.float32(t), *data)
        losses.append(float(loss))
        if t == 1:
            grads = jax.device_get(g)
    return {"losses": losses, "grads": grads, "params": jax.device_get(p)}
