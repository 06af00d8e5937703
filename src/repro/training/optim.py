"""Pure-JAX optimizers (no optax): Adam/AdamW with grad clipping + schedules.

State is a plain pytree, so ``m``/``v`` take the parameters' shardings.
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

Array = jax.Array


class AdamState(NamedTuple):
    step: Array
    m: any
    v: any


def _step_zero(params) -> Array:
    """Step count 0, replicated over the params' mesh when they have one:
    a jitted update returns it there, and a step count placed elsewhere on
    the first call would retrace the update on the second."""
    sharding = getattr(next(iter(jax.tree.leaves(params)), None), "sharding",
                       None)
    step = jnp.zeros((), jnp.int32)
    if isinstance(sharding, NamedSharding):
        step = jax.device_put(step, NamedSharding(sharding.mesh,
                                                  PartitionSpec()))
    return step


class Adam(NamedTuple):
    lr: float | Callable[[Array], Array] = 5e-4
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8
    weight_decay: float = 1e-12  # paper's default (Table IX)
    grad_clip: Optional[float] = None

    def init(self, params) -> AdamState:
        zeros = jax.tree.map(lambda p: jnp.zeros_like(p), params)
        return AdamState(step=_step_zero(params), m=zeros,
                         v=jax.tree.map(lambda p: jnp.zeros_like(p), params))

    def update(self, grads, state: AdamState, params):
        """One Adam step, under the name scope ``adam_update``."""
        with jax.named_scope("adam_update"):
            step = state.step + 1
            if self.grad_clip is not None:
                gnorm = optax_global_norm(grads)
                scale = jnp.minimum(1.0, self.grad_clip / (gnorm + 1e-9))
                grads = jax.tree.map(lambda g: g * scale, grads)
            lr = self.lr(step) if callable(self.lr) else self.lr
            b1, b2 = self.b1, self.b2
            m = jax.tree.map(lambda mm, g: b1 * mm + (1 - b1) * g,
                             state.m, grads)
            v = jax.tree.map(lambda vv, g: b2 * vv + (1 - b2) * g * g,
                             state.v, grads)
            mh_c = 1.0 - b1 ** step.astype(jnp.float32)
            vh_c = 1.0 - b2 ** step.astype(jnp.float32)

            def upd(p, mm, vv):
                u = (mm / mh_c) / (jnp.sqrt(vv / vh_c) + self.eps)
                return p - lr * (u + self.weight_decay * p)

            new_params = jax.tree.map(upd, params, m, v)
            return new_params, AdamState(step=step, m=m, v=v)


def optax_global_norm(tree) -> Array:
    leaves = jax.tree.leaves(tree)
    return jnp.sqrt(sum(jnp.sum(x.astype(jnp.float32) ** 2) for x in leaves))


def cosine_schedule(base_lr: float, warmup: int, total: int) -> Callable[[Array], Array]:
    def sched(step):
        step = step.astype(jnp.float32)
        warm = base_lr * step / max(warmup, 1)
        frac = jnp.clip((step - warmup) / max(total - warmup, 1), 0.0, 1.0)
        cos = 0.5 * base_lr * (1.0 + jnp.cos(jnp.pi * frac))
        return jnp.where(step < warmup, warm, cos)

    return sched
