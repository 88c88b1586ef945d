"""Plain pieces the configurations' references share: layer norm, the tanh
GELU, a masked log-softmax, dense layers and Adam with a separate learning
rate for ``log_z``, written out from their definitions in ``jax.numpy``.
Nothing here imports the program under test.

Every function takes ``dt``, the dtype the reference computes in:
float32 at ``highest`` matmul precision for the reference, bfloat16 for
the control that stands for a lower-precision program.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def cast(tree, dt):
    return jax.tree_util.tree_map(lambda x: jnp.asarray(x).astype(dt), tree)


def dense(p, x):
    return x @ p["w"].astype(x.dtype) + p["b"].astype(x.dtype)


def layernorm(p, x, eps=1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"].astype(x.dtype) \
        + p["bias"].astype(x.dtype)


def gelu_tanh(x):
    c = math.sqrt(2.0 / math.pi)
    return 0.5 * x * (1.0 + jnp.tanh(c * (x + 0.044715 * x ** 3)))


def masked_log_softmax(logits, mask):
    z = jnp.where(mask, logits, -jnp.inf)
    m = jnp.max(z, axis=-1, keepdims=True)
    return z - (m + jnp.log(jnp.sum(jnp.exp(z - m), axis=-1, keepdims=True)))


def adam_init(params):
    z = jax.tree_util.tree_map(lambda p: jnp.zeros_like(p, jnp.float32),
                               params)
    return {"count": 0, "mu": z, "nu": z}


def adam_step(params, grads, state, lr, log_z_lr, b1=0.9, b2=0.999,
              eps=1e-8):
    """One Adam step (bias-corrected), with ``log_z`` at its own rate."""
    count = state["count"] + 1
    tmap = jax.tree_util.tree_map
    mu = tmap(lambda m, g: b1 * m + (1 - b1) * g.astype(jnp.float32),
              state["mu"], grads)
    nu = tmap(lambda v, g: b2 * v + (1 - b2) * jnp.square(
        g.astype(jnp.float32)), state["nu"], grads)
    bc1, bc2 = 1 - b1 ** count, 1 - b2 ** count

    def new(path, p, m, v):
        rate = log_z_lr if "log_z" in jax.tree_util.keystr(path) else lr
        upd = (m / bc1) / (jnp.sqrt(v / bc2) + eps)
        return (p.astype(jnp.float32) - rate * upd).astype(p.dtype)

    params = jax.tree_util.tree_map_with_path(new, params, mu, nu)
    return params, {"count": count, "mu": mu, "nu": nu}


def train_three_steps(loss_and_aux, params0, batches, lr, log_z_lr, dt):
    """Follow the program's first three steps on its own batches: at each
    step the loss (and per-step log-probs, log-rewards) of the batch under
    the reference's current parameters, then an Adam step.  Returns the
    per-step outputs, the first gradient and the parameters after three
    steps, all as float64 numpy on the host."""
    grad_fn = jax.jit(jax.value_and_grad(loss_and_aux, has_aux=True))
    params = cast(params0, dt)
    state = adam_init(params)
    steps, grads0 = [], None
    for batch in batches:
        (loss, aux), grads = grad_fn(params, batch)
        steps.append({"loss": float(loss),
                      **{k: np.asarray(v, np.float64) for k, v in aux.items()}})
        if grads0 is None:
            grads0 = grads
        params, state = adam_step(params, grads, state, lr, log_z_lr)
    to_np = lambda t: jax.tree_util.tree_map(
        lambda x: np.asarray(jnp.asarray(x, jnp.float32), np.float64), t)
    return {"steps": steps, "grads": to_np(grads0), "params": to_np(params)}
