"""ZeRO-1 data parallelism: optimizer-state sharding over the data axis.

The reference's DP (and this repo's default `make_dp_train_step`) keeps
params AND optimizer state fully replicated — every chip stores Adam's two
moment pytrees for the whole model. This module shards the OPTIMIZER state
1/dp per chip (the ZeRO stage-1 recipe, arXiv:1910.02054, re-derived
TPU-natively): per-shard gradients are `psum_scatter`-reduced so each chip
receives only its 1/dp slice of the summed gradient vector, updates its
slice of the raveled parameter vector with its slice of the optimizer
state, and an `all_gather` rebuilds the full (replicated) params for the
next forward. Communication VOLUME per step is that of the pmean DP step
(reduce-scatter + all-gather = one all-reduce, ring-wise); the TIME is not:
on four TPU v5 lite a reduce-scatter of 205 MB takes 2.43 ms and the
all-gather 1.73, 4.16 ms for what one all-reduce does in 3.60 (PERF.md §6,
PR 35; PR 30 measured 4.26), and an all-gather whose result is a donated
parameter adds a copy of the leaf into the program and one out of it. What
pays for the difference is the optimizer pass on 1/dp of the state.
`train/sharded_update.py` is the leaf-wise form of the same idea that the
default DP builders apply to large leaves by themselves; it keeps full
logical shapes, which this raveled form does not.

Numerics: the update is elementwise (SGD/momentum/Adam/AdamW/RMSProp on a
contiguous slice of the raveled vector ≡ the same transform leaf-wise), so
trajectories match plain DP to float-reassociation. The one NON-elementwise
transform — global-norm clipping — cannot run per-slice (each shard would
clip by a different norm and slices would diverge), so clipping is done
HERE from the globally-psum'd norm, and the optimizer chain passed in must
exclude its own clip stage (`make_zero1_train_step(clip_norm=...)`).

Scope: stateless losses; composes with K-step dispatch
(``steps_per_call`` — the scan runs inside the shard_map). Params stay
replicated — sharding them too (ZeRO-3) would re-gather per layer per
step; at LSTM sizes the win is in the moments, which dominate optimizer
memory.

TWO implementations live here, because the raveled-flat form above is
hostile to tensor parallelism (raveling a model-sharded leaf would gather
it):

- the shard_map/ravel form (`make_zero1_train_step`) for the pure-DP
  backend — explicit reduce-scatter/all-gather, K-step scan inside;
- a GSPMD form (`zero1_tp_opt_specs`) for the TP task runners: the
  optimizer-state moment leaves get a PartitionSpec that ADDS the data
  axis on a dimension the param leaves unsharded (the classic XLA
  weight-update-sharding recipe — annotate, let GSPMD place the update).
  Grads stay logically replicated over data, so global-norm clipping
  needs no special casing, and the sharded leaves keep their full
  logical shapes, so checkpoints reshard across ANY later dp×tp (no
  padded-flat-length contract like the ravel form).
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.flatten_util import ravel_pytree
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

from ..train.loop import TrainState, dp_rng_transform


def _flat_meta(params, dp: int):
    """(n, chunk) for the raveled parameter vector padded to dp chunks."""
    n = sum(int(jnp.size(a)) for a in jax.tree.leaves(params))
    chunk = -(-n // dp)  # ceil
    return n, chunk


def _local_slice(flat_pad: jax.Array, chunk: int, axis: str) -> jax.Array:
    idx = lax.axis_index(axis)
    return lax.dynamic_slice(flat_pad, (idx * chunk,), (chunk,))


def _opt_state_specs(optimizer, chunk: int, axis: str):
    """out_specs for the chunked optimizer state: vector leaves shard over
    ``axis``, scalar leaves (e.g. Adam's count) stay replicated."""
    shapes = jax.eval_shape(optimizer.init, jnp.zeros((chunk,), jnp.float32))
    return jax.tree.map(lambda s: P() if s.ndim == 0 else P(axis), shapes)


def make_zero1_opt_init(
    optimizer: optax.GradientTransformation,
    mesh: Mesh,
    axis: str = "data",
):
    """Jitted initializer: full (replicated) params -> optimizer state over
    each shard's [chunk] parameter slice, sharded P(axis) on vector leaves.
    Use its result as TrainState.opt_state for `make_zero1_train_step` (and
    as the checkpoint template — the checkpointer's per-leaf reshard
    handles the sharded leaves like any PP-sharded state)."""
    dp = mesh.shape[axis]

    def per_shard_init(params):
        n, chunk = _flat_meta(params, dp)
        flat, _ = ravel_pytree(params)
        flat = jnp.pad(flat.astype(jnp.float32), (0, dp * chunk - n))
        return optimizer.init(_local_slice(flat, chunk, axis))

    def build(params):
        n, chunk = _flat_meta(params, dp)
        return jax.jit(shard_map(
            per_shard_init,
            mesh=mesh,
            in_specs=(P(),),
            out_specs=_opt_state_specs(optimizer, chunk, axis),
            check_vma=False,
        ))(params)

    return build


def make_zero1_train_step(
    loss_fn: Callable,
    optimizer: optax.GradientTransformation,
    mesh: Mesh,
    *,
    axis: str = "data",
    clip_norm: float | None = None,
    jit: bool = True,
    donate: bool = True,
    steps_per_call: int = 1,
):
    """Build the ZeRO-1 DP train step.

    ``loss_fn(params, batch, dropout_rng) -> (loss, aux)`` — the same
    per-shard body as every other step builder. ``optimizer`` must NOT
    include a global-norm clip stage; pass ``clip_norm`` here instead
    (module docstring: clipping needs the GLOBAL norm, computed by psum
    before the sliced update). The state is donated into the step, as in
    every step builder: the memory-saving step must not hold a second copy
    of params + moments.

    ``steps_per_call=K`` scans the per-shard step over K stacked batches
    ([K, b_local, ...]) INSIDE the shard_map — K optimizer steps per host
    dispatch, the same amortization as train/multistep.py. Collectives
    inside the scan are uniform across shards (same trip count
    everywhere), so the composition is lockstep-safe; metrics follow the
    multi-step contract (mean loss + final step's loss/grad_norm).

    CHECKPOINT SHAPE CONTRACT: the sharded moment leaves bake in the
    padded flat length dp*ceil(n_params/dp), so a ZeRO-1 checkpoint
    resumes at the SAME data-shard count it was written with. To change
    dp across a restart, round-trip through a non-zero1 run (restore
    full state, re-save), or re-init the moments.
    """
    dp = mesh.shape[axis]

    def per_shard_step(state: TrainState, batch):
        rng, sub = jax.random.split(state.rng)
        sub = dp_rng_transform(axis)(sub)
        (loss, aux), grads = jax.value_and_grad(
            lambda p: loss_fn(p, batch, sub), has_aux=True
        )(state.params)
        from ..resilience import faults as _faults

        grads = _faults.tamper_grads(grads, state.step)  # identity unarmed

        n, chunk = _flat_meta(state.params, dp)
        g_flat, _ = ravel_pytree(grads)
        g_flat = jnp.pad(g_flat.astype(jnp.float32), (0, dp * chunk - n))
        # reduce-scatter: this shard receives the cross-shard SUM of its
        # 1/dp gradient slice; /dp makes it the treeAggregate-style mean
        g_local = lax.psum_scatter(g_flat, axis, tiled=True) / dp

        # global grad norm from the scattered slices (pad lanes are zero)
        gsq = lax.psum(jnp.sum(jnp.square(g_local)), axis)
        gnorm = jnp.sqrt(gsq)
        if clip_norm is not None:
            g_local = g_local * jnp.minimum(1.0, clip_norm / (gnorm + 1e-12))

        p_flat, unravel = ravel_pytree(state.params)
        p_dtype = p_flat.dtype
        p_flat = jnp.pad(p_flat.astype(jnp.float32), (0, dp * chunk - n))
        p_local = _local_slice(p_flat, chunk, axis)

        updates, opt_state = optimizer.update(g_local, state.opt_state, p_local)
        p_new = optax.apply_updates(p_local, updates)

        loss = lax.pmean(loss, axis)
        # Non-finite guard (same contract as train/loop.py step_body): skip
        # the sliced update AND the moment update when loss/grad-norm is
        # NaN/Inf. Both predicates are collective results (pmean'd loss,
        # psum'd norm), so every shard takes the same branch and the
        # all-gather below rebuilds consistent params either way.
        finite = jnp.isfinite(loss) & jnp.isfinite(gnorm)
        p_local = jnp.where(finite, p_new, p_local)
        opt_state = jax.tree.map(
            lambda new, old: jnp.where(finite, new, old),
            opt_state, state.opt_state,
        )

        p_flat = lax.all_gather(p_local, axis, tiled=True)[:n].astype(p_dtype)
        params = unravel(p_flat)

        metrics = {"loss": loss, "grad_norm": gnorm,
                   "anomalous": (~finite).astype(jnp.float32)}
        return (
            TrainState(state.step + 1, params, opt_state, rng, state.carries),
            metrics,
        )

    if steps_per_call > 1:
        from ..train.loop import summarize_scan_metrics

        inner = per_shard_step

        def per_shard_multi(state: TrainState, batches):
            state, ms = lax.scan(inner, state, batches)
            return state, summarize_scan_metrics(ms)

        per_shard = per_shard_multi
        batch_spec = P(None, axis)  # [K, b_local, ...]
    else:
        per_shard = per_shard_step
        batch_spec = P(axis)

    def build_specs(params):
        n, chunk = _flat_meta(params, dp)
        opt_spec = _opt_state_specs(optimizer, chunk, axis)
        state_spec = TrainState(
            step=P(), params=P(), opt_state=opt_spec, rng=P(), carries=P(),
        )
        return state_spec

    def step(state: TrainState, batch):
        state_spec = build_specs(state.params)
        fn = shard_map(
            per_shard,
            mesh=mesh,
            in_specs=(state_spec, batch_spec),
            out_specs=(state_spec, P()),
            check_vma=False,
        )
        return fn(state, batch)

    if not jit:
        return step
    return jax.jit(step, donate_argnums=(0,) if donate else ())


def _zero1_leaf_spec(spec: P, shape, dp: int, dp_axis: str) -> P:
    """Extend a param leaf's PartitionSpec with ``dp_axis`` on the first
    dimension the param leaves unsharded and the axis divides. A leaf with
    no such dimension keeps the param's own sharding (no memory win on it,
    but nothing breaks — GSPMD just replicates it over data as before)."""
    parts = list(spec) + [None] * (len(shape) - len(spec))
    for i, (p, dim) in enumerate(zip(parts, shape)):
        if p is None and dim >= dp and dim % dp == 0:
            parts[i] = dp_axis
            break
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def zero1_tp_opt_specs(
    optimizer: optax.GradientTransformation,
    params_template,
    param_specs,
    mesh: Mesh,
    *,
    dp_axis: str = "data",
):
    """PartitionSpec pytree for the optimizer state that composes ZeRO-1
    with GSPMD tensor parallelism (the TP task runners' recipe).

    Moment leaves mirror the params tree inside optax's state NamedTuples;
    they are matched to their param by TREE-PATH SUFFIX (an adam ``mu``
    leaf at ``[0].mu['fwd'][0].W_i`` matches the param path
    ``['fwd'][0].W_i``), guarded by shape equality, longest suffix wins.
    Matched leaves get the param's spec extended with the data axis
    (`_zero1_leaf_spec`); scalars and unmatched leaves stay replicated.
    Use the result as ``opt_state_specs`` for `make_tp_train_step` and to
    `place_params` the initial/restored state."""
    from jax.tree_util import tree_flatten_with_path, tree_unflatten

    dp = mesh.shape[dp_axis]
    param_leaves, _ = tree_flatten_with_path(params_template)
    spec_flat, _ = tree_flatten_with_path(
        param_specs, is_leaf=lambda x: isinstance(x, P))
    # pair by PATH, not position: a same-count tree with a typoed key
    # would silently mispair under zip and the step would then PIN wrong
    # placements with no error
    spec_by_path = {tuple(path): spec for path, spec in spec_flat}
    param_paths = {tuple(p) for p, _ in param_leaves}
    if spec_by_path.keys() != param_paths:
        from jax.tree_util import keystr
        odd = [keystr(p) for p in
               (param_paths ^ spec_by_path.keys())][:3]
        raise ValueError(
            "param_specs does not mirror params_template "
            f"(mismatched leaf paths, e.g. {odd})")
    by_path = [
        (tuple(path), leaf.shape, spec_by_path[tuple(path)])
        for path, leaf in param_leaves
    ]
    by_path.sort(key=lambda t: -len(t[0]))  # longest suffix wins

    shapes = jax.eval_shape(optimizer.init, params_template)
    flat, treedef = tree_flatten_with_path(shapes)
    matched = 0

    def match(path, shape):
        nonlocal matched
        for q, qshape, spec in by_path:
            if (len(path) >= len(q) and tuple(path[-len(q):]) == q
                    and tuple(shape) == tuple(qshape)):
                matched += 1
                return _zero1_leaf_spec(spec, shape, dp, dp_axis)
        return P()

    out = tree_unflatten(treedef, [match(tuple(p), s.shape) for p, s in flat])
    if matched == 0 and any(s.ndim > 0 for _, s in flat):
        # nothing mirrors the params (e.g. a factored optimizer like
        # adafactor): pinning everything P() would use MORE memory than
        # plain propagation — refuse rather than silently regress
        raise ValueError(
            "no optimizer-state leaf mirrors the params (factored "
            "optimizer?) — GSPMD ZeRO-1 only shards param-shaped moments; "
            "drop opt_state_specs and let propagation place this state")
    return out
