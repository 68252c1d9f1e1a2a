"""AdamW + schedules, from scratch (no optax in this environment).

Optimizer state mirrors the parameter pytree (m, v per leaf in fp32), so
the parameter sharding specs apply verbatim to the state — ZeRO-1 falls out
of the FSDP parameter sharding with zero extra code (dist/sharding.py).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.obs.scopes import OPTIMIZER, scoped

__all__ = ["AdamWConfig", "adamw_init", "adamw_update", "global_norm",
           "cosine_schedule", "linear_warmup"]


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10_000
    min_lr_ratio: float = 0.1


def adamw_init(params: Any) -> Any:
    zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
    return dict(
        m=jax.tree.map(zeros, params),
        v=jax.tree.map(zeros, params),
        count=jnp.zeros((), jnp.int32),
    )


def global_norm(tree: Any) -> jax.Array:
    leaves = [jnp.sum(jnp.square(x.astype(jnp.float32)))
              for x in jax.tree.leaves(tree)]
    return jnp.sqrt(sum(leaves))


def cosine_schedule(cfg: AdamWConfig) -> Callable[[jax.Array], jax.Array]:
    def lr(step):
        step = step.astype(jnp.float32)
        warm = jnp.minimum(1.0, step / jnp.maximum(1, cfg.warmup_steps))
        frac = jnp.clip((step - cfg.warmup_steps)
                        / jnp.maximum(1, cfg.total_steps - cfg.warmup_steps),
                        0.0, 1.0)
        cos = 0.5 * (1 + jnp.cos(jnp.pi * frac))
        scale = cfg.min_lr_ratio + (1 - cfg.min_lr_ratio) * cos
        return cfg.lr * warm * scale
    return lr


def linear_warmup(cfg: AdamWConfig) -> Callable[[jax.Array], jax.Array]:
    def lr(step):
        return cfg.lr * jnp.minimum(
            1.0, (step.astype(jnp.float32) + 1) / jnp.maximum(1, cfg.warmup_steps))
    return lr


@scoped(OPTIMIZER)
def adamw_update(
    grads: Any, state: Any, params: Any, cfg: AdamWConfig,
    schedule: Optional[Callable] = None,
) -> Tuple[Any, Any, dict]:
    """One AdamW step with global-norm clipping. Returns (params', state',
    metrics).  fp32 math; params cast back to their stored dtype."""
    count = state["count"] + 1
    gnorm = global_norm(grads)
    scale = jnp.minimum(1.0, cfg.clip_norm / jnp.maximum(gnorm, 1e-12)) \
        if cfg.clip_norm > 0 else 1.0
    lr = (schedule or cosine_schedule(cfg))(count)
    b1c = 1 - cfg.b1 ** count.astype(jnp.float32)
    b2c = 1 - cfg.b2 ** count.astype(jnp.float32)

    def upd(g, m, v, p):
        gf = g.astype(jnp.float32) * scale
        m = cfg.b1 * m + (1 - cfg.b1) * gf
        v = cfg.b2 * v + (1 - cfg.b2) * gf * gf
        mh = m / b1c
        vh = v / b2c
        step = mh / (jnp.sqrt(vh) + cfg.eps)
        if p.ndim >= 2:  # decay matrices only (standard practice)
            step = step + cfg.weight_decay * p.astype(jnp.float32)
        return (p.astype(jnp.float32) - lr * step).astype(p.dtype), m, v

    out = jax.tree.map(upd, grads, state["m"], state["v"], params)
    new_params = jax.tree.map(lambda t: t[0], out,
                              is_leaf=lambda x: isinstance(x, tuple))
    new_m = jax.tree.map(lambda t: t[1], out,
                         is_leaf=lambda x: isinstance(x, tuple))
    new_v = jax.tree.map(lambda t: t[2], out,
                         is_leaf=lambda x: isinstance(x, tuple))
    return new_params, dict(m=new_m, v=new_v, count=count), dict(
        grad_norm=gnorm, lr=lr)
