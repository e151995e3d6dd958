"""Multi-step training: K optimizer steps per host dispatch via `lax.scan`.

The single-step path (train/loop.py) already collapses the reference's whole
round — broadcast, mapPartitions, treeAggregate, update (SURVEY.md §3.1) —
into one XLA program, leaving host→device dispatch as the only per-step host
cost. For small models that dispatch dominates: the PTB config's step is
tens of microseconds of TPU compute, less than one host dispatch costs.
This module removes it the TPU-native way: stage K batches on device
([K, ...] leading axis) and `lax.scan` the SAME step body K times inside one
jitted call, so the host pays one dispatch per K steps.

This is the moral opposite of the reference's design point: Spark pays
per-round *network serialization*; single-step jit pays per-step *dispatch*;
multi-step amortises even that. The step body is shared verbatim with the
single-step and DP paths (step_body), so the K-step program is provably K
iterations of the same update — tests/test_multistep.py asserts bit-level
parity against K sequential single steps.

Metrics: ``loss`` is the mean over the K steps (the natural logging quantity
for a K-step window), ``loss_last``/``grad_norm`` are the final step's.

Fused eval composes with the HOST-FED feed too: only the EVAL data must be
device-resident for the in-executable eval pass (device_step.py), so
``eval_data`` (LM valid stream) or ``metric_fn`` (stacked task eval
batches) turn these builders into fused train+eval steps — the case where
the train set exceeds HBM but the valid split fits.
"""

from __future__ import annotations

from typing import Callable

import jax
import optax
from jax.sharding import Mesh, PartitionSpec as P

from ..data.device_dataset import DeviceLMData
from .device_step import _gated_eval_batches, _gated_lm_eval, _jit_step
from .loop import (
    TrainState,
    dp_reduce_fn,
    dp_rng_transform,
    step_body,
    summarize_scan_metrics,
)
from .sharded_update import dp_shard_map


def _scan_steps(loss_fn, optimizer, state, batches, *, stateful, rng_transform=None,
                reduce_fn=None, grad_accum=1):
    """scan step_body over the leading [K] axis of ``batches``."""

    def body(s, b):
        s2, m = step_body(
            loss_fn, optimizer, s, b, stateful=stateful,
            rng_transform=rng_transform, reduce_fn=reduce_fn,
            grad_accum=grad_accum,
        )
        return s2, m

    state, ms = jax.lax.scan(body, state, batches)
    return state, summarize_scan_metrics(ms)


def _fused_tail(loss_fn, eval_data, eval_windows, metric_fn, metric_keys,
                stateful, psum_axis=None):
    """Resolve which fused-eval tail (if any) the builder should append:
    returns None (plain step) or a closure (params_fn, ms, *eval_args) ->
    ms, ``params_fn()`` giving the whole parameters (device_step.py)."""
    if eval_data is not None:
        n_ev = min(eval_data.n_windows, eval_windows or eval_data.n_windows)
        ev_T = eval_data.seq_len

        def tail(params_fn, ms, eval_arrays, do_eval, eval_carries=None):
            return _gated_lm_eval(
                loss_fn, params_fn, eval_arrays, do_eval, ms, n_windows=n_ev,
                seq_len=ev_T, stateful=stateful, eval_carries=eval_carries,
                psum_axis=psum_axis,
            )

        return tail
    if metric_fn is not None:
        keys = tuple(metric_keys)

        def tail(params_fn, ms, eval_batches, do_eval):
            return _gated_eval_batches(
                metric_fn, params_fn, eval_batches, do_eval, ms, keys
            )

        return tail
    return None


def make_multi_train_step(
    loss_fn: Callable,
    optimizer: optax.GradientTransformation,
    *,
    eval_data: DeviceLMData | None = None,
    eval_windows: int | None = None,
    metric_fn: Callable | None = None,
    metric_keys=(),
    jit: bool = True,
    donate: bool = True,
    stateful: bool = False,
    grad_accum: int = 1,
):
    """Single-chip K-steps-per-call train step.

    ``multi_step(state, batches)`` where ``batches`` is the usual batch pytree
    with an extra leading K axis (see data.batching.stacked_batches). K is
    read from the array shapes — one compilation per distinct K.

    With ``eval_data`` (LM valid stream) or ``metric_fn`` (stacked task
    eval batches), returns the FUSED step
    ``multi_step(state, batches, <eval args>, do_eval[, eval_carries])`` —
    identical semantics to device_step.py's fused builders but with a
    host-fed train feed.
    """
    tail = _fused_tail(loss_fn, eval_data, eval_windows, metric_fn,
                       metric_keys, stateful)

    def core(state: TrainState, batches):
        return _scan_steps(
            loss_fn, optimizer, state, batches,
            stateful=stateful, grad_accum=grad_accum,
        )

    if tail is None:
        multi_step = core
    else:

        def multi_step(state: TrainState, batches, *eval_args):
            state, ms = core(state, batches)
            return state, tail(lambda: state.params, ms, *eval_args)

    return _jit_step(multi_step, jit, donate)


def make_dp_multi_train_step(
    loss_fn: Callable,
    optimizer: optax.GradientTransformation,
    mesh: Mesh,
    *,
    eval_data: DeviceLMData | None = None,
    eval_windows: int | None = None,
    metric_fn: Callable | None = None,
    metric_keys=(),
    axis: str = "data",
    jit: bool = True,
    donate: bool = True,
    stateful: bool = False,
    grad_accum: int = 1,
):
    """Data-parallel K-steps-per-call: the DP per-shard body (rng fold-in +
    pmean grad all-reduce — parallel/data_parallel.py) scanned K times inside
    the shard_map, so the ICI all-reduce happens every step but the host
    dispatch only once per K. ``batches`` leading axes are [K, B, ...] with B
    sharded over the data axis (spec ``P(None, axis)``).

    ``eval_data``/``metric_fn`` append the fused eval tail (device_step.py
    sharding contracts: LM valid stream shards batch rows + psums the
    token-weighted sums; task eval batches replicate)."""
    tail = _fused_tail(loss_fn, eval_data, eval_windows, metric_fn,
                       metric_keys, stateful,
                       psum_axis=axis if eval_data is not None else None)

    def core(part, state: TrainState, batches):
        return _scan_steps(
            loss_fn, optimizer, state, batches, stateful=stateful,
            grad_accum=grad_accum,
            rng_transform=dp_rng_transform(axis),
            reduce_fn=dp_reduce_fn(part),
        )

    if tail is None:
        per_shard = core
        in_specs = (P(None, axis),)
    elif eval_data is not None:
        stream_spec = {"streams": P(axis, None), "shifted": P(axis, None)}

        def per_shard(part, state, batches, eval_arrays, do_eval,
                      eval_carries):
            state, ms = core(part, state, batches)
            return state, tail(lambda: part.gather(state.params), ms,
                               eval_arrays, do_eval, eval_carries)

        in_specs = (P(None, axis), stream_spec, P(),
                    P(axis) if stateful else P())
    else:

        def per_shard(part, state, batches, eval_batches, do_eval):
            state, ms = core(part, state, batches)
            return state, tail(lambda: part.gather(state.params), ms,
                               eval_batches, do_eval)

        in_specs = (P(None, axis), P(), P())

    sharded = dp_shard_map(per_shard, mesh, in_specs, axis=axis,
                           stateful=stateful)
    return _jit_step(sharded, jit, donate)
