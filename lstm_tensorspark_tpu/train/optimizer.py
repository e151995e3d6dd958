"""Optimizers via optax.

Reference parity: SURVEY.md §2 "Optimizer / update rule" [D][I] — the
reference applies plain SGD on the driver after gradient averaging
(``params -= lr * avg_grad``). SGD is therefore the default; momentum/adam
and gradient clipping are capability extensions (BASELINE.md configs 2–5
train poorly without them).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax


def clip_by_global_norm(max_norm: float) -> optax.GradientTransformationExtraArgs:
    """`optax.clip_by_global_norm`, able to be GIVEN the norm: the
    data-parallel step holds large leaves' gradients as one chip's share
    (train/sharded_update.py), so a norm taken from the tree this stage
    sees would differ from chip to chip. ``update(..., global_norm=n)``
    clips by ``n``; without it the norm is the tree's own, and the stage is
    optax's to the letter. Same (empty) state, so a chain built with either
    reads the other's checkpoint."""

    def update_fn(updates, state, params=None, *, global_norm=None, **_):
        del params
        g_norm = (optax.global_norm(updates) if global_norm is None
                  else global_norm)
        trigger = jnp.squeeze(g_norm < max_norm)

        def clip_fn(t):
            return jax.lax.select(
                trigger, t, (t / g_norm.astype(t.dtype)) * max_norm)

        return jax.tree.map(clip_fn, updates), state

    return optax.GradientTransformationExtraArgs(
        optax.init_empty_state, update_fn)


def make_optimizer(
    name: str = "sgd",
    learning_rate: float = 1.0,
    *,
    momentum: float = 0.0,
    clip_norm: float | None = None,
    weight_decay: float = 0.0,
    warmup_steps: int = 0,
    decay_steps: int | None = None,
) -> optax.GradientTransformation:
    """Build an optax chain: [clip] -> optimizer [-> wd] with optional
    linear-warmup cosine-decay schedule."""
    if decay_steps is not None:
        schedule = optax.warmup_cosine_decay_schedule(
            init_value=0.0 if warmup_steps > 0 else learning_rate,
            peak_value=learning_rate,
            warmup_steps=max(warmup_steps, 1),
            decay_steps=max(decay_steps, warmup_steps + 1),
            end_value=learning_rate * 0.1,
        )
    elif warmup_steps > 0:
        # Warmup with no decay horizon: ramp to peak, then HOLD at peak.
        schedule = optax.join_schedules(
            [
                optax.linear_schedule(0.0, learning_rate, warmup_steps),
                optax.constant_schedule(learning_rate),
            ],
            [warmup_steps],
        )
    else:
        schedule = learning_rate

    name = name.lower()
    if name == "sgd":
        opt = optax.sgd(schedule, momentum=momentum if momentum > 0 else None)
    elif name == "momentum":
        opt = optax.sgd(schedule, momentum=momentum or 0.9)
    elif name == "adam":
        opt = optax.adam(schedule)
    elif name == "adamw":
        opt = optax.adamw(schedule, weight_decay=weight_decay)
    elif name == "rmsprop":
        opt = optax.rmsprop(schedule)
    else:
        raise ValueError(f"unknown optimizer {name!r}")

    chain = []
    if clip_norm is not None:
        chain.append(clip_by_global_norm(clip_norm))
    chain.append(opt)
    return optax.chain(*chain)
