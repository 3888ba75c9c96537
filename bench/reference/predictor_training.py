"""Plain rebuild of the first steps of the learned prefetcher's training.

The predictor is trained inside each grid by minibatch AdamW on the mean
cross-entropy of its last-token classifier: the gradient clipped to a
global norm of 1, betas 0.9 and 0.999, epsilon 1e-8, decoupled weight
decay 1e-4, and a learning rate that rises linearly from 0 to 3e-3 over
``min(50, steps // 10 + 1)`` steps and then falls on a cosine to 5% of
it at ``steps``.  These are the settings with which the program's
predictor service trains; nothing here imports the program.

The reference starts from the parameters the program's first step was
given and feeds the batches its first steps were given; it follows
:data:`STEPS` steps with its own gradients, in float32 at the precision
the forward states, and its own optimizer in NumPy.  What it gives back
is compared with what the program's steps produced:

* ``train_loss_gap``: the widest relative gap of a step's loss;
* ``train_grad_gap``: the first gradient as the optimizer got it, which
  is its first moment after one step over ``1 - beta1``: the widest gap
  between a leaf's norm in the program and in the reference, over the
  larger of that leaf's norm in the reference and the median leaf's;
* ``train_update_gap``: the same gap of each leaf's change over the
  steps followed.  Leaves whose reference gradient is under
  :data:`NOUGHT` of the median leaf's move by round-off alone under
  Adam and are left out of it.
"""
from __future__ import annotations

import math
from typing import Callable, Dict, List, Sequence, Tuple

import numpy as np

LR = 3e-3
BETA1 = 0.9
BETA2 = 0.999
EPS = 1e-8
WEIGHT_DECAY = 1e-4
CLIP_NORM = 1.0
MIN_FRAC = 0.05
#: the steps the reference follows
STEPS = 3
#: a leaf whose reference gradient norm is under this share of the
#: median leaf's is left out of ``train_update_gap``
NOUGHT = 1e-3


def learning_rate(step: int, total: int) -> float:
    """The learning rate of step ``step`` (from 0) of ``total``."""
    warm = min(50, total // 10 + 1)
    if step < warm:
        return LR * step / warm
    span = max(total - warm, 1)
    t = min(step - warm, span) / span
    return LR * (MIN_FRAC + (1 - MIN_FRAC) * 0.5 * (1 + math.cos(math.pi * t)))


def leaves(tree) -> List[np.ndarray]:
    """The leaves of a tree of arrays, as float32 NumPy arrays."""
    import jax

    return [np.asarray(a, np.float32) for a in jax.tree_util.tree_leaves(tree)]


def follow(loss_and_grad: Callable, init, batches: Sequence[Tuple],
           total: int):
    """The reference's steps from parameters ``init`` over ``batches``
    (``(x, y)`` pairs), ``loss_and_grad(params, x, y) -> (loss, grads)``:
    the loss of each step, the first clipped gradient, and the parameters
    after the last step, as lists of float32 leaves in ``init``'s order."""
    import jax

    treedef = jax.tree_util.tree_structure(init)
    params = leaves(init)
    mu = [np.zeros_like(p) for p in params]
    nu = [np.zeros_like(p) for p in params]
    losses, first = [], None
    for k, (x, y) in enumerate(batches):
        loss, grads = loss_and_grad(
            jax.tree_util.tree_unflatten(treedef, params), x, y)
        g = leaves(grads)
        norm = math.sqrt(sum(float(np.sum(np.square(a, dtype=np.float64)))
                             for a in g))
        scale = np.float32(min(1.0, CLIP_NORM / (norm + 1e-12)))
        g = [a * scale for a in g]
        if first is None:
            first = g
        mu = [BETA1 * m + (1 - BETA1) * a for m, a in zip(mu, g)]
        nu = [BETA2 * v + (1 - BETA2) * a * a for v, a in zip(nu, g)]
        bc1, bc2 = 1 - BETA1 ** (k + 1), 1 - BETA2 ** (k + 1)
        lr = np.float32(learning_rate(k, total))
        params = [(p - lr * ((m / bc1) / (np.sqrt(v / bc2) + EPS)
                             + WEIGHT_DECAY * p)).astype(np.float32)
                  for p, m, v in zip(params, mu, nu)]
        losses.append(float(loss))
    return losses, first, params


def worst_leaf(got: Sequence[np.ndarray], want: Sequence[np.ndarray],
               keep: Sequence[bool]) -> float:
    """The widest gap between a kept leaf's norm in ``got`` and in
    ``want``, over the larger of its norm in ``want`` and the median kept
    leaf's."""
    norm = lambda a: float(np.linalg.norm(np.asarray(a, np.float64)))
    pairs = [(norm(g), norm(w)) for g, w, k in zip(got, want, keep) if k]
    if not pairs:
        return 0.0
    med = float(np.median([w for _, w in pairs]))
    return max(abs(g - w) / max(w, med, 1e-30) for g, w in pairs)


def readings(ref, got, init) -> Dict[str, float]:
    """The three gaps between the steps of the program (``got``: losses,
    first gradient and parameters after the steps, as :func:`follow`
    gives them) and the reference's (``ref``), from parameters ``init``
    (float32 leaves)."""
    ref_loss, ref_grad, ref_params = ref
    loss, grad, params = got
    if len(loss) != len(ref_loss) or len(grad) != len(ref_grad):
        return {"train_loss_gap": 1.0, "train_grad_gap": 1.0,
                "train_update_gap": 1.0}
    loss_gap = max((abs(a - b) / max(abs(b), 1e-30)
                    for a, b in zip(loss, ref_loss)), default=0.0)
    every = [True] * len(ref_grad)
    gnorm = [float(np.linalg.norm(g)) for g in ref_grad]
    med = float(np.median(gnorm)) if gnorm else 0.0
    moved = [n >= NOUGHT * med for n in gnorm]
    return {"train_loss_gap": loss_gap,
            "train_grad_gap": worst_leaf(grad, ref_grad, every),
            "train_update_gap": worst_leaf(
                [p - i for p, i in zip(params, init)],
                [p - i for p, i in zip(ref_params, init)], moved)}
