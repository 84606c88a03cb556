"""First-order optimizers for subspace learning (paper §4.1: AdamW on Σ).

Pure-pytree implementation (no external deps): fp32 master state over
possibly-bf16 params, per-leaf trainability masking (only Σ and the
electronic leaves — embeddings, norms, routers — receive updates; frozen
U/V bases are masked out), global-norm clipping, decoupled weight decay.
"""

from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

__all__ = [
    "AdamWConfig", "SGDConfig", "OptState", "init_opt_state",
    "apply_updates", "clip_by_global_norm", "global_norm",
]

PyTree = Any


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    lr: float = 2e-3                # paper: 0.002 for SL-from-scratch
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8
    weight_decay: float = 0.01      # paper: 0.01
    grad_clip: float | None = 1.0

    kind: str = dataclasses.field(default="adamw", init=False)


@dataclasses.dataclass(frozen=True)
class SGDConfig:
    lr: float = 0.1
    momentum: float = 0.9
    weight_decay: float = 0.0
    grad_clip: float | None = None

    kind: str = dataclasses.field(default="sgd", init=False)


class OptState(NamedTuple):
    step: jax.Array
    mu: PyTree        # first moment / momentum (fp32)
    nu: PyTree        # second moment (fp32; zeros pytree for SGD)
    master: PyTree    # fp32 master params (same pytree as params)


def _f32(t: PyTree) -> PyTree:
    return jax.tree.map(lambda a: a.astype(jnp.float32), t)


def init_opt_state(params: PyTree, trainable: PyTree | None = None
                   ) -> OptState:
    """``trainable`` False leaves get scalar placeholders — frozen U/V
    bases carry NO optimizer state (2/3 of an LM's params)."""
    if trainable is None:
        trainable = jax.tree.map(lambda _: True, params)

    def z(a, tr):
        return jnp.zeros(a.shape if tr else (), jnp.float32)

    def m(a, tr):
        # a copy even where ``a`` is already f32: the master weights are
        # their own buffers, so a step may donate params and state both
        return (jnp.array(a, jnp.float32, copy=True) if tr
                else jnp.zeros((), jnp.float32))

    return OptState(step=jnp.zeros((), jnp.int32),
                    mu=jax.tree.map(z, params, trainable),
                    nu=jax.tree.map(z, params, trainable),
                    master=jax.tree.map(m, params, trainable))


def global_norm(tree: PyTree) -> jax.Array:
    leaves = [jnp.sum(jnp.square(l.astype(jnp.float32)))
              for l in jax.tree.leaves(tree)]
    return jnp.sqrt(jnp.sum(jnp.stack(leaves))) if leaves else jnp.zeros(())


def clip_by_global_norm(grads: PyTree, max_norm: float) -> tuple[PyTree, jax.Array]:
    norm = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / (norm + 1e-12))
    return jax.tree.map(lambda g: g * scale, grads), norm


def apply_updates(params: PyTree, grads: PyTree, state: OptState,
                  cfg: AdamWConfig | SGDConfig,
                  lr_scale: jax.Array | float = 1.0,
                  trainable: PyTree | None = None,
                  ) -> tuple[PyTree, OptState, jax.Array]:
    """One optimizer step.  ``trainable``: bool pytree (same structure);
    False leaves are passed through untouched (frozen U/V bases).
    Returns (new_params, new_state, grad_norm)."""
    if cfg.grad_clip is not None:
        grads, gnorm = clip_by_global_norm(grads, cfg.grad_clip)
    else:
        gnorm = global_norm(grads)
    step = state.step + 1
    lr = cfg.lr * lr_scale

    if trainable is None:
        trainable = jax.tree.map(lambda _: True, params)

    def upd(g, m, v, p):
        g = g.astype(jnp.float32)
        if cfg.kind == "adamw":
            m = cfg.b1 * m + (1 - cfg.b1) * g
            v = cfg.b2 * v + (1 - cfg.b2) * jnp.square(g)
            mhat = m / (1 - cfg.b1 ** step)
            vhat = v / (1 - cfg.b2 ** step)
            delta = mhat / (jnp.sqrt(vhat) + cfg.eps) + cfg.weight_decay * p
        else:
            m = cfg.momentum * m + g
            delta = m + cfg.weight_decay * p
        return p - lr * delta, m, v

    flat_p, treedef = jax.tree.flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_m = treedef.flatten_up_to(state.mu)
    flat_v = treedef.flatten_up_to(state.nu)
    flat_master = treedef.flatten_up_to(state.master)
    flat_tr = treedef.flatten_up_to(trainable)

    new_master, new_m, new_v, new_p = [], [], [], []
    for g, m, v, pm, p, tr in zip(flat_g, flat_m, flat_v, flat_master,
                                  flat_p, flat_tr):
        if not tr:
            new_master.append(pm)
            new_m.append(m)
            new_v.append(v)
            new_p.append(p)
            continue
        pm2, m2, v2 = upd(g, m, v, pm)
        new_master.append(pm2)
        new_m.append(m2)
        new_v.append(v2)
        new_p.append(pm2.astype(p.dtype))

    new_params = treedef.unflatten(new_p)
    new_state = OptState(step=step, mu=treedef.unflatten(new_m),
                         nu=treedef.unflatten(new_v),
                         master=treedef.unflatten(new_master))
    return new_params, new_state, gnorm
