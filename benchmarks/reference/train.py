"""The reference's first training steps: loss, gradient and SGD-momentum
update in float32, from the same weights and the same batches as the program.

What it returns is what `correct` compares (see harness/check.py): each step's
loss, the norm of every leaf of the first gradient, the norm of every leaf's
change after the last step and, kept on the device as ``grad1`` and
``dparam``, those two trees themselves, so that the distance between two
sides' leaves can be taken (:func:`diff_norms`).

``quant`` turns the reference into the control (8-bit float products).
``rows`` plants the faults the benchmark's tests and the chip readings use:
the step sees only the first ``rows`` rows of each batch and takes the mean
over those (half of the batch left out; on four chips, one chip's shard alone
is the gradient with the exchange left out).
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp

from . import nn


def first_steps(forward: Callable, cfg: dict, weights: Dict,
                batches: Sequence, lrs: Sequence[float],
                quant: nn.Quant = None, rows: Optional[int] = None,
                batch_sharding=None) -> Dict:
    """Drive ``len(batches)`` steps. ``batches`` are (uint8 images, labels)
    host arrays; ``lrs`` the learning rate of each step. With
    ``batch_sharding`` the rows of a batch are laid over several chips and
    the same plain program runs on all of them."""
    momentum = cfg["optimizer"]["momentum"]

    def loss_of(params, x, y):
        return nn.softmax_cross_entropy(forward(cfg, params, x, quant), y)

    def step(params, trace, x, y, lr):
        loss, grads = jax.value_and_grad(loss_of)(params, x, y)
        gnorm = {k: jnp.sqrt(jnp.sum(jnp.square(g))) for k, g in grads.items()}
        params, trace = nn.sgd_momentum(params, trace, grads, lr, momentum)
        return params, trace, loss, gnorm, grads

    jstep = jax.jit(step, donate_argnums=(0, 1))
    delta = jax.jit(lambda new, old: {k: new[k] - old[k] for k in old})

    params = jax.tree.map(jnp.copy, weights)
    trace = jax.tree.map(jnp.zeros_like, weights)
    losses: List[float] = []
    grad1 = None
    for (x, y), lr in zip(batches, lrs):
        if rows is not None:
            x, y = x[:rows], y[:rows]
        if batch_sharding is not None:
            x = jax.device_put(x, batch_sharding(x.ndim))
            y = jax.device_put(y, batch_sharding(y.ndim))
        params, trace, loss, gnorm, grads = jstep(params, trace, x, y,
                                                  jnp.float32(lr))
        losses.append(float(loss))
        if grad1 is None:
            grad1, grad1_norm = grads, {
                k: float(v) for k, v in jax.device_get(gnorm).items()}
        del grads
    dparam = delta(params, weights)
    del params, trace
    return {"losses": losses, "grad1_norm": grad1_norm,
            "dparam_norm": leaf_norms(dparam), "grad1": grad1,
            "dparam": dparam}


def leaf_norms(tree: Dict) -> Dict[str, float]:
    norms = jax.jit(lambda t: {
        k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
        for k, v in t.items()})(tree)
    return {k: float(v) for k, v in jax.device_get(norms).items()}


def diff_norms(a: Dict, b: Dict) -> Dict[str, float]:
    """Per leaf, the norm of the difference of two sides' trees."""
    return leaf_norms(jax.jit(lambda x, y: {
        k: x[k].astype(jnp.float32) - y[k].astype(jnp.float32)
        for k in y})(a, b))
