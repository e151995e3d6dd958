"""Data-parallel training over the device mesh — the north-star replacement
for the reference's Spark backend (BASELINE.json north_star; SURVEY.md §3.3).

Mapping, component by component:
  Spark ``sc.broadcast(weights)``      → params replicated on-device (no
                                         per-step broadcast exists at all)
  ``rdd.mapPartitions(train_partition)`` → the same per-shard step body
                                         running under `shard_map` on every
                                         device's batch shard
  ``treeAggregate`` grad tree-reduce   → `lax.psum` (ICI all-reduce); being
                                         an all-reduce, every device gets the
                                         averaged grads, which also deletes
                                         the re-broadcast (SURVEY.md §3.3)
  driver-side ``params -= lr*grad``    → optimizer update runs replicated
                                         on-device inside the same XLA program;
                                         a LARGE leaf and its moments live
                                         sharded 1/dp a chip: all-gather it
                                         for the forward, reduce-scatter its
                                         gradient, update the share
                                         (train/sharded_update.py)

The entire reference round (3 process boundaries, 2 network serializations)
compiles to ONE jitted program per step.
"""

from __future__ import annotations

from typing import Callable

import jax
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map

# TrainState plus re-exports from train.loop (their dependency-free
# home): the per-shard rng fold-in and the pmean gradient reduction
# shared by every DP step builder here, in train/multistep.py and
# train/device_step.py.
from ..train.loop import (  # noqa: F401
    TrainState,
    dp_reduce_fn,
    dp_rng_transform,
)
from ..train.sharded_update import dp_shard_map


def shard_batch(batch, mesh: Mesh, axis: str = "data", *, dim: int = 0):
    """Place a host batch with dim ``dim`` sharded over ``axis`` (replicated
    over the other mesh axes). ``dim=1`` is the K-steps-per-call layout
    [K, B, ...] where B is the sharded batch axis (train/multistep.py)."""
    sharding = NamedSharding(mesh, P(*([None] * dim), axis))
    return jax.tree.map(lambda a: jax.device_put(a, sharding), batch)


def replicate(tree, mesh: Mesh):
    """Fully-replicated placement — the reference's broadcast, done once."""
    sharding = NamedSharding(mesh, P())
    return jax.tree.map(lambda a: jax.device_put(a, sharding), tree)


def make_dp_train_step(
    loss_fn: Callable,
    optimizer: optax.GradientTransformation,
    mesh: Mesh,
    *,
    axis: str = "data",
    jit: bool = True,
    donate: bool = True,
    stateful: bool = False,
    grad_accum: int = 1,
):
    """Build the data-parallel train step.

    ``loss_fn(params, batch, dropout_rng) -> (loss, aux)`` — the identical
    per-shard body used single-chip (SURVEY.md §3.2's train_partition), so
    single-device and DP runs are the same program modulo the psum.

    With ``stateful=True`` the loss_fn also takes/returns recurrent carries
    (see train/loop.py); carries live sharded over the data axis — each
    shard's stream keeps its own recurrent state, exactly like a Spark
    partition's worker-local state.
    """

    from ..train.loop import step_body

    def per_shard_step(part, state: TrainState, batch):
        return step_body(
            loss_fn,
            optimizer,
            state,
            batch,
            stateful=stateful,
            grad_accum=grad_accum,
            rng_transform=dp_rng_transform(axis),
            # treeAggregate + broadcast, collapsed into one ICI all-reduce
            # (a large leaf: gathered, reduce-scattered, updated as shares):
            reduce_fn=dp_reduce_fn(part),
        )

    sharded = dp_shard_map(per_shard_step, mesh, (P(axis),), axis=axis,
                           stateful=stateful)
    if jit:
        sharded = jax.jit(sharded, donate_argnums=(0,) if donate else ())
    return sharded


def make_dp_eval_step(
    loss_fn: Callable,
    mesh: Mesh,
    *,
    axis: str = "data",
    jit: bool = True,
    stateful: bool = False,
):
    from ..train.loop import call_loss

    def _metrics(loss, aux):
        # Mirror make_eval_step's token reporting so evaluate() token-weights
        # DP eval identically to single-device eval. Shards are equal-shape,
        # so pmean of per-shard per-token means is the exact batch mean; the
        # batch's total token count is the psum of shard counts.
        m = {"loss": jax.lax.pmean(loss, axis)}
        if isinstance(aux, dict) and "tokens" in aux:
            m["tokens"] = jax.lax.psum(aux["tokens"], axis)
        return m

    if stateful:

        def per_shard_eval(params, batch, carries):
            loss, aux = call_loss(loss_fn, params, batch, None, carries, stateful=True)
            return _metrics(loss, aux), aux["carries"]

        sharded = shard_map(
            per_shard_eval,
            mesh=mesh,
            in_specs=(P(), P(axis), P(axis)),
            out_specs=(P(), P(axis)),
            check_vma=False,
        )
    else:

        def per_shard_eval(params, batch):
            loss, aux = loss_fn(params, batch, None)
            return _metrics(loss, aux)

        sharded = shard_map(
            per_shard_eval,
            mesh=mesh,
            in_specs=(P(), P(axis)),
            out_specs=P(),
            check_vma=False,
        )
    if jit:
        sharded = jax.jit(sharded)
    return sharded
