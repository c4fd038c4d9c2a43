"""The numbers that decide ``correct``: each a gap between what the timed
path produced and what the plain reference gives, as a share of a scale
the reference sets."""
from __future__ import annotations

import numpy as np


def logit_err(out, ref) -> float:
    """Widest |out - ref| over the widest |ref|."""
    out, ref = np.asarray(out, np.float64), np.asarray(ref, np.float64)
    if out.shape != ref.shape or not np.isfinite(out).all():
        return float("inf")
    return float(np.abs(out - ref).max() / max(np.abs(ref).max(), 1e-30))


def rel_gap(a: float, b: float) -> float:
    if not np.isfinite(a):
        return float("inf")
    return abs(a - b) / max(abs(b), 1e-30)


def _leaves(tree) -> list[np.ndarray]:
    return [np.asarray(x, np.float64) for x in _flat(tree)]


def _flat(tree):
    if isinstance(tree, dict):
        for k in sorted(tree):
            yield from _flat(tree[k])
    elif isinstance(tree, (list, tuple)):
        for x in tree:
            yield from _flat(x)
    else:
        yield tree


def _worst_leaf(prog, ref, ref_grads, gap) -> float:
    """Worst leaf's ``gap(p, r)`` over the larger of the reference leaf's
    norm and the median leaf's norm. Leaves whose reference gradient is
    under a thousandth of the median leaf's gradient move by round-off
    alone and are left out."""
    p, r = _leaves(prog), _leaves(ref)
    g = [float(np.linalg.norm(x)) for x in _leaves(ref_grads)]
    if len(p) != len(r) or any(a.shape != b.shape for a, b in zip(p, r)):
        return float("inf")
    g_med = float(np.median(g))
    keep = [i for i in range(len(r)) if g[i] >= 1e-3 * g_med]
    norms_r = [float(np.linalg.norm(r[i])) for i in keep]
    med = float(np.median(norms_r))
    worst = 0.0
    for i, nr in zip(keep, norms_r):
        d = gap(p[i], r[i])
        if not np.isfinite(d):
            return float("inf")
        worst = max(worst, d / max(nr, med, 1e-30))
    return worst


def leaf_norm_gap(prog, ref, ref_grads) -> float:
    """Worst leaf's | |prog| - |ref| | (see ``_worst_leaf``)."""
    return _worst_leaf(prog, ref, ref_grads, lambda p, r: abs(
        float(np.linalg.norm(p)) - float(np.linalg.norm(r))))


def leaf_diff(prog, ref, ref_grads) -> float:
    """Worst leaf's |prog - ref| (see ``_worst_leaf``)."""
    return _worst_leaf(prog, ref, ref_grads,
                       lambda p, r: float(np.linalg.norm(p - r)))


def tree_sub(a, b):
    """Leafwise a - b over two trees of the same layout (host arrays)."""
    if isinstance(a, dict):
        return {k: tree_sub(a[k], b[k]) for k in a}
    if isinstance(a, (list, tuple)):
        return [tree_sub(x, y) for x, y in zip(a, b)]
    return np.asarray(a, np.float64) - np.asarray(b, np.float64)


def prob_errors(classes, probs, ref_probs) -> np.ndarray:
    """Per answered node: the served probability less the reference's
    probability of the served class."""
    return probs - ref_probs[np.arange(len(classes)), classes]


def softmax(x: np.ndarray) -> np.ndarray:
    x = np.asarray(x, np.float64)
    e = np.exp(x - x.max(axis=-1, keepdims=True))
    return e / e.sum(axis=-1, keepdims=True)
