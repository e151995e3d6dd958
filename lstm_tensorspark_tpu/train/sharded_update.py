"""The data-parallel step's sharded weight update (Xu et al.,
arXiv:2004.13336; ZeRO stage 1, arXiv:1910.02054).

Plain DP all-reduces every gradient and then runs the SAME optimizer update
on every chip. For a large leaf that is `dp` chips reading and writing the
whole parameter and both moments to produce bit-identical results. Here a
leaf that qualifies (`shard_dim`) LIVES sharded between steps — the
parameter and its moments, as global arrays of the full logical shape with
`P(axis)` on one dimension (`dp_state_spec`), so checkpoints do not change —
and a step

1. all-gathers the parameter: forward and backward read the whole leaf, the
   one this step's predecessor wrote (`Partition.gather`);
2. reduce-scatters the gradient: each chip receives the mean gradient of
   its 1/dp of the leaf (`DpReduce`);
3. updates that share alone, in place.

Every other leaf keeps the all-reduce and the replicated update. The global
gradient norm (the clip's, and the one the step reports and tests for
finiteness) is taken from partial sums (`Partition.global_norm`) and handed
to the optimizer chain (`train/optimizer.py::clip_by_global_norm`).

Why the gather comes FIRST and the parameter is not kept whole: a collective
cannot write into a donated buffer. Gathering the updated share back into a
replicated parameter compiled, on the v5e, to a `copy` of the whole leaf
into the step program and one out of it (0.63 ms each for 205 MB: all the
update saved; PERF.md §6, PR 35). Gathered into a temporary there is none.

Which leaves: from their shapes and the mesh alone — no flag, no model name.
Imports nothing of this package, so train/loop.py can import it.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import optax
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# A leaf's update is sharded from this many bytes up. Below it the two
# collectives' fixed cost outweighs the optimizer pass they save. Four TPU
# v5 lite, one float32 leaf a program, chip 0's busy time an update:
# all-reduce + whole Adam against all-gather + reduce-scatter + Adam on a
# quarter (tools/dp_update_probe.py; PERF.md §6, PR 35): 16.8 MB 0.39
# against 0.35 ms; 67 MB 1.75 against 1.39; 205 MB 5.76 against 4.61.
# Config 5's 32 layer matrices are 4 MB each; its two of 205 MB are 75% of
# its bytes.
MIN_SHARDED_BYTES = 64 * 1024 * 1024


def shard_dim(shape, itemsize: int, dp: int) -> int | None:
    """The dimension along which a leaf is sharded over ``dp`` chips, or
    None when it stays replicated: searching from the minor dimension, the
    first whose 1/dp share keeps the chip's (sublanes, 128) tile whole (8
    sublanes of float32, 16 of bf16), so neither collective needs a
    relayout. Minor first: along it the scatter compiled to ONE
    reduce-scatter in every probed shape, along a major dimension in three
    of four to a ring of collective-permutes and adds, 0.3 ms slower at
    67 MB."""
    if dp <= 1 or len(shape) < 2:
        return None
    if math.prod(shape) * itemsize < MIN_SHARDED_BYTES:
        return None
    tiles = [1] * (len(shape) - 2) + [32 // itemsize, 128]
    for d in reversed(range(len(shape))):
        if shape[d] % (dp * tiles[d]) == 0:
            return d
    return None


def _nbytes(leaf) -> int:
    return math.prod(leaf.shape) * jnp.dtype(leaf.dtype).itemsize


def shard_dims(params, dp: int) -> tuple:
    """`shard_dim` of every leaf of WHOLE ``params`` (global shapes), in
    `jax.tree.leaves` order."""
    return tuple(shard_dim(p.shape, jnp.dtype(p.dtype).itemsize, dp)
                 for p in jax.tree.leaves(params))


def sharded_share(params, dp: int) -> float:
    """Percent of the parameters' bytes whose update is sharded."""
    leaves = jax.tree.leaves(params)
    picked = sum(_nbytes(p) for p, d in zip(leaves, shard_dims(params, dp))
                 if d is not None)
    return 100.0 * picked / max(sum(map(_nbytes, leaves)), 1)


class Partition(NamedTuple):
    """How a step's parameters are split over ``axis``: ``dims[i]`` is leaf
    i's sharded dimension or None. With no sharded leaf every method is the
    plain form, so a step without one is the replicated step."""

    axis: str | None = None
    dims: tuple = ()

    @property
    def sharded(self) -> bool:
        return any(d is not None for d in self.dims)

    def gather(self, params):
        """Whole leaves from the chips' shares."""
        if not self.sharded:
            return params
        leaves, treedef = jax.tree.flatten(params)
        return treedef.unflatten([
            p if d is None
            else lax.all_gather(p, self.axis, axis=d, tiled=True)
            for p, d in zip(leaves, self.dims)])

    def reduce(self, grads):
        """The cross-chip mean of whole per-chip ``grads``: replicated
        leaves in one all-reduce, a sharded leaf as this chip's share."""
        if not self.sharded:
            return lax.pmean(grads, self.axis)
        dp = lax.axis_size(self.axis)
        leaves, treedef = jax.tree.flatten(grads)
        whole = iter(lax.pmean(
            [g for g, d in zip(leaves, self.dims) if d is None], self.axis))
        return treedef.unflatten([
            next(whole) if d is None else lax.psum_scatter(
                g, self.axis, scatter_dimension=d, tiled=True) / dp
            for g, d in zip(leaves, self.dims)])

    def global_norm(self, grads):
        """`optax.global_norm` of the whole gradient, from a tree whose
        sharded leaves hold this chip's share: their squares summed over
        the chips, plus the replicated leaves'."""
        if not self.sharded:
            return optax.global_norm(grads)
        leaves = jax.tree.leaves(grads)
        mine = sum(jnp.sum(jnp.square(g))
                   for g, d in zip(leaves, self.dims) if d is not None)
        whole = sum(jnp.sum(jnp.square(g))
                    for g, d in zip(leaves, self.dims) if d is None)
        return jnp.sqrt(lax.psum(mine, self.axis) + whole)


WHOLE = Partition()


class DpReduce:
    """The treeAggregate replacement, `step_body`'s ``reduce_fn`` under
    every DP step builder: mean grads (and loss, for logging) across
    shards, with ``part`` saying which leaves arrive and leave as one
    chip's share. The ONE definition — change the gradient-reduction
    contract here."""

    def __init__(self, part: Partition):
        self.part = part

    def __call__(self, grads, loss):
        return self.part.reduce(grads), lax.pmean(loss, self.part.axis)


# ---- where the state lives: specs for the step and for its placement ----


def param_specs(params, dp: int, axis: str = "data"):
    """A `PartitionSpec` a parameter leaf: ``P(axis)`` on the sharded
    dimension, ``P()`` for a replicated leaf. A moment of the leaf lives as
    the leaf does."""
    return jax.tree.unflatten(jax.tree.structure(params), [
        P() if d is None else P(*([None] * d), axis)
        for d in shard_dims(params, dp)])


def opt_state_specs(opt_state, params, dp: int, axis: str = "data"):
    """Specs for an optax state: every subtree shaped like ``params`` (a
    moment tree) takes `param_specs`, every other leaf (counts, schedule
    state) is replicated."""
    pdef = jax.tree.structure(params)
    shapes = [p.shape for p in jax.tree.leaves(params)]
    specs = param_specs(params, dp, axis)

    def like_params(x):
        return (jax.tree.structure(x) == pdef
                and [getattr(a, "shape", None)
                     for a in jax.tree.leaves(x)] == shapes)

    return jax.tree.map(
        lambda x: specs if like_params(x) else P(),
        opt_state, is_leaf=like_params)


def dp_state_spec(state, dp: int, axis: str = "data", *, stateful: bool):
    """The DP step's `TrainState` spec, one `PartitionSpec` a leaf: step and
    rng replicated, recurrent carries sharded by batch rows, parameters and
    their moments as `param_specs` says. Derived from the state's own
    (global) shapes; every DP builder and `place_dp_state` use this one
    definition."""
    return state._replace(
        step=P(), rng=jax.tree.map(lambda _: P(), state.rng),
        params=param_specs(state.params, dp, axis),
        opt_state=opt_state_specs(state.opt_state, state.params, dp, axis),
        carries=jax.tree.map(
            lambda _: P(axis) if stateful else P(), state.carries),
    )


def place_dp_state(state, mesh: Mesh, axis: str = "data", *, stateful: bool):
    """Put a fresh or restored state where the DP step hands it back, so the
    second dispatch is the first one's program and the first donates."""
    spec = dp_state_spec(state, mesh.shape[axis], axis, stateful=stateful)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), state, spec)


def dp_shard_map(per_shard, mesh: Mesh, rest_specs, *, axis: str = "data",
                 stateful: bool):
    """``per_shard(part, state, *rest) -> (state, metrics)`` under
    `shard_map` over ``axis``. The state's spec and the `Partition` handed
    to ``per_shard`` follow from the GLOBAL shapes of the state the step is
    called with (inside the map a sharded leaf shows its share's shape), so
    the map is built when traced."""
    dp = mesh.shape[axis]

    def call(state, *rest):
        spec = dp_state_spec(state, dp, axis, stateful=stateful)
        part = Partition(axis, shard_dims(state.params, dp))
        return shard_map(
            lambda state, *rest: per_shard(part, state, *rest),
            mesh=mesh, in_specs=(spec, *rest_specs),
            out_specs=(spec, P()), check_vma=False,
        )(state, *rest)

    # the program keeps its builder's name (`jit(core)`): compile counters
    # and trace readers find it by that
    call.__name__ = call.__qualname__ = per_shard.__name__
    return call
