"""AdamW with global-norm clipping and configurable moment dtype.

Self-contained (no optax in the container).  Moments can be kept in
bfloat16 (``moment_dtype="bfloat16"``) to fit very large models — the
deepseek-v3 config uses this (see EXPERIMENTS.md memory table).  State is a
plain dict pytree ({"step", "m", "v"}) so abstract lowering, sharding and
checkpointing all share one structure; moments reuse the parameters' logical
axes, so optimizer state is ZeRO-sharded wherever params are.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class AdamW:
    learning_rate: Callable[[jax.Array], jax.Array]
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0
    moment_dtype: str = "float32"

    def init(self, params) -> Dict[str, Any]:
        dt = jnp.dtype(self.moment_dtype)
        zeros = lambda p: jnp.zeros(p.shape, dt)
        return {"step": jnp.zeros((), jnp.int32),
                "m": jax.tree_util.tree_map(zeros, params),
                "v": jax.tree_util.tree_map(zeros, params)}

    def state_specs(self, param_specs) -> Dict[str, Any]:
        """ParamSpec pytree for the optimizer state (dry-run / checkpoint)."""
        from repro.models.param import ParamSpec, tree_map_specs
        remap = lambda s: ParamSpec(s.shape, s.axes, init="zeros",
                                    dtype=self.moment_dtype)
        return {"step": ParamSpec((), (), init="zeros", dtype="int32"),
                "m": tree_map_specs(remap, param_specs),
                "v": tree_map_specs(remap, param_specs)}

    @jax.named_scope("optimizer")
    def update(self, grads, state: Dict[str, Any], params
               ) -> Tuple[Any, Dict[str, Any], Dict[str, jax.Array]]:
        step = state["step"] + 1
        gnorm = global_norm(grads)
        scale = jnp.minimum(1.0, self.clip_norm / jnp.maximum(gnorm, 1e-9)) \
            if self.clip_norm else jnp.float32(1.0)
        mdt = jnp.dtype(self.moment_dtype)

        def upd(g, m, v, p):
            gf = g.astype(jnp.float32) * scale
            m_new = self.b1 * m.astype(jnp.float32) + (1 - self.b1) * gf
            v_new = self.b2 * v.astype(jnp.float32) + (1 - self.b2) * gf * gf
            mh = m_new / (1 - self.b1 ** step.astype(jnp.float32))
            vh = v_new / (1 - self.b2 ** step.astype(jnp.float32))
            delta = mh / (jnp.sqrt(vh) + self.eps)
            if self.weight_decay and p.ndim >= 2:   # no decay on norms/scalars
                delta = delta + self.weight_decay * p.astype(jnp.float32)
            return (-self.learning_rate(step) * delta).astype(p.dtype), \
                m_new.astype(mdt), v_new.astype(mdt)

        triples = jax.tree_util.tree_map(upd, grads, state["m"], state["v"],
                                         params)
        is_triple = lambda x: isinstance(x, tuple) and len(x) == 3 \
            and all(isinstance(t, jax.Array) for t in x)
        pick = lambda i: jax.tree_util.tree_map(lambda t: t[i], triples,
                                                is_leaf=is_triple)
        return pick(0), {"step": step, "m": pick(1), "v": pick(2)}, \
            {"grad_norm": gnorm}


@jax.named_scope("optimizer")
def global_norm(tree) -> jax.Array:
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                        for l in leaves))


@jax.named_scope("optimizer")
def apply_updates(params, updates):
    return jax.tree_util.tree_map(lambda p, u: p + u.astype(p.dtype),
                                  params, updates)


def cosine_schedule(peak_lr: float, warmup: int, total: int,
                    floor: float = 0.1) -> Callable[[jax.Array], jax.Array]:
    def lr(step):
        s = step.astype(jnp.float32)
        warm = peak_lr * s / max(warmup, 1)
        prog = jnp.clip((s - warmup) / max(total - warmup, 1), 0.0, 1.0)
        cos = peak_lr * (floor + (1 - floor) * 0.5 * (1 + jnp.cos(jnp.pi * prog)))
        return jnp.where(s < warmup, warm, cos)
    return lr
