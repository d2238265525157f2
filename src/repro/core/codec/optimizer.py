"""AdamW in pure JAX, for the codec trainer.

Alg. 2 backprops "only the layers of the autoencoder": freezing is done by
optimizing only the trainable subtree.  State is a pytree mirroring params.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

__all__ = [
    "AdamWConfig",
    "AdamWState",
    "adamw_init",
    "adamw_update",
    "clip_by_global_norm",
    "global_norm",
]


class AdamWConfig(NamedTuple):
    lr: float = 1e-3
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8
    weight_decay: float = 0.0
    grad_clip: Optional[float] = None


class AdamWState(NamedTuple):
    step: jax.Array
    mu: Any
    nu: Any


def adamw_init(params) -> AdamWState:
    zeros = lambda: jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    return AdamWState(jnp.zeros((), jnp.int32), zeros(), zeros())


def global_norm(tree) -> jax.Array:
    leaves = jax.tree.leaves(tree)
    return jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2) for g in leaves))


def clip_by_global_norm(grads, max_norm: float):
    gn = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(gn, 1e-12))
    return jax.tree.map(lambda g: g * scale, grads), gn


def adamw_update(
    params,
    grads,
    state: AdamWState,
    cfg: AdamWConfig,
    lr_scale: jax.Array | float = 1.0,
):
    """Returns (new_params, new_state)."""
    if cfg.grad_clip is not None:
        grads, _ = clip_by_global_norm(grads, cfg.grad_clip)
    step = state.step + 1
    b1, b2 = cfg.b1, cfg.b2
    bc1 = 1.0 - b1 ** step.astype(jnp.float32)
    bc2 = 1.0 - b2 ** step.astype(jnp.float32)
    lr = cfg.lr * lr_scale

    new_mu = jax.tree.map(
        lambda m, g: b1 * m + (1 - b1) * g.astype(jnp.float32), state.mu, grads
    )
    new_nu = jax.tree.map(
        lambda v, g: b2 * v + (1 - b2) * jnp.square(g.astype(jnp.float32)),
        state.nu,
        grads,
    )

    def upd(p, m, v):
        delta = lr * (m / bc1) / (jnp.sqrt(v / bc2) + cfg.eps)
        if cfg.weight_decay:
            delta = delta + lr * cfg.weight_decay * p.astype(jnp.float32)
        return (p.astype(jnp.float32) - delta).astype(p.dtype)

    new_params = jax.tree.map(upd, params, new_mu, new_nu)
    return new_params, AdamWState(step, new_mu, new_nu)
