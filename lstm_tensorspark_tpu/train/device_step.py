"""Train steps over device-resident datasets (data/device_dataset.py).

The step takes (state, staged arrays, per-step index array) and runs K
optimizer steps, materialising each batch out of HBM inside the scan — the
per-dispatch host traffic is the tiny index array (one scalar for the LM's
contiguous windows, [K, B] row ids for examples/series). Combines the
K-steps-per-call dispatch amortisation (train/multistep.py) with the
reference's cached-RDD data locality (SURVEY.md §3.1: executors iterate
their *resident* shard).

Three dataset shapes share ONE generic core (`make_device_train_step` /
`make_device_dp_train_step`, parameterised by a traced ``window_fn``):
  - LM contiguous windows (`slice_window`) — wrappers below keep the
    scalar-w0 API used by the CLI and bench;
  - per-example gather (`take_batch`) — classification;
  - series windows (`slice_forecast_batch`) — forecasting.

The scan body is the shared `step_body`, so semantics are identical to the
host-fed paths — tests/test_device_data.py asserts bit-level parity.

Fused train+eval — the eval pass lives INSIDE the train executable.
Switching between a train and an eval executable costs a host round-trip
per swap, which at small dims can exceed either program's compute (what
it costs on the current chip is not measured). The reference never had
this problem only because it never had
executables: eval was one more Spark job. The TPU-native answer is ONE
program: the K-step train scan followed by a lax.cond-gated forward-only
eval pass, requested by passing ``metric_fn``/``metric_keys`` (generic,
over stacked eval batches) or ``eval_data`` (LM, over a staged valid
stream) to the builders below. The ``do_eval`` flag is a traced scalar —
both cadences run the SAME executable, and XLA's cond skips the eval
branch entirely on non-eval calls (tests/test_fused_eval.py).
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..data.device_dataset import DeviceLMData, slice_window
from .loop import (
    TrainState,
    call_loss,
    dp_reduce_fn,
    dp_rng_transform,
    step_body,
    summarize_scan_metrics,
)
from .sharded_update import dp_shard_map


def _scan_indexed(loss_fn, optimizer, state, arrays, idxs, *, window_fn,
                  stateful, grad_accum, rng_transform=None, reduce_fn=None):
    """lax.scan over the leading [K] axis of ``idxs``; each step builds its
    batch with ``window_fn(arrays, idx)`` and runs the shared step_body."""

    def body(s, idx):
        return step_body(
            loss_fn, optimizer, s, window_fn(arrays, idx), stateful=stateful,
            grad_accum=grad_accum, rng_transform=rng_transform,
            reduce_fn=reduce_fn,
        )

    state, ms = lax.scan(body, state, idxs)
    return state, summarize_scan_metrics(ms)


def _jit_step(step, jit: bool, donate: bool):
    """The ONE jit/donation wrapper shared by every builder here."""
    if not jit:
        return step
    return jax.jit(step, donate_argnums=(0,) if donate else ())


# ---- traced eval bodies (the on-device forms of the host eval loops) ----


def _device_eval_batches(metric_fn, params, eval_batches, keys):
    """Traced weighted-mean eval over a stacked [n_ev, ...] batch pytree:
    ``metric_fn(params, batch) -> (metrics dict, weight)``; returns
    ``{k: sum(m_k * w) / sum(w)}`` — the on-device body of the task
    runners' host eval loops."""

    def body(acc, batch):
        tot, wt = acc
        m, w = metric_fn(params, batch)
        w = w.astype(jnp.float32)
        tot = {k: tot[k] + m[k].astype(jnp.float32) * w for k in keys}
        return (tot, wt + w), None

    zeros = {k: jnp.zeros((), jnp.float32) for k in keys}
    (tot, wt), _ = lax.scan(
        body, (zeros, jnp.zeros((), jnp.float32)), eval_batches
    )
    wt = jnp.maximum(wt, 1.0)
    return {k: tot[k] / wt for k in keys}


def _gated_eval_batches(metric_fn, params_fn, eval_batches, do_eval, ms, keys):
    """``params_fn()`` gives the whole parameters; called inside the eval
    branch, so a DP step gathers its sharded leaves on eval calls only."""
    ms.update(lax.cond(
        do_eval,
        lambda _: _device_eval_batches(metric_fn, params_fn(), eval_batches,
                                       keys),
        lambda _: {k: jnp.float32(jnp.nan) for k in keys},
        operand=None,
    ))
    return ms


def _device_lm_eval(loss_fn, params, eval_arrays, n_windows, seq_len, *,
                    stateful, eval_carries, psum_axis=None):
    """Traced token-weighted eval over the staged valid stream — the
    on-device body of `evaluate()` (train/loop.py): sum(loss*tokens) /
    sum(tokens) over the epoch's windows, carries threaded when stateful."""

    def body(acc, w):
        carries, tot, wt = acc
        batch = slice_window(eval_arrays, w, seq_len)
        loss, aux = call_loss(loss_fn, params, batch, None, carries,
                              stateful=stateful)
        tok = (aux["tokens"] if isinstance(aux, dict) and "tokens" in aux
               else jnp.float32(1.0))
        carries = aux["carries"] if stateful else carries
        return (carries, tot + loss * tok, wt + tok), None

    zero = jnp.zeros((), jnp.float32)
    (_, tot, wt), _ = lax.scan(
        body, (eval_carries, zero, zero),
        jnp.arange(n_windows, dtype=jnp.int32),
    )
    if psum_axis is not None:
        # per-shard sums → exact global token-weighted mean (equal-shape
        # shards make this identical to make_dp_eval_step + evaluate())
        tot = lax.psum(tot, psum_axis)
        wt = lax.psum(wt, psum_axis)
    return tot / jnp.maximum(wt, 1.0)


def _gated_lm_eval(loss_fn, params_fn, eval_arrays, do_eval, ms, *, n_windows,
                   seq_len, stateful, eval_carries, psum_axis=None):
    """``params_fn()`` as in `_gated_eval_batches`."""
    ms["eval_loss"] = lax.cond(
        do_eval,
        lambda _: _device_lm_eval(
            loss_fn, params_fn(), eval_arrays, n_windows, seq_len,
            stateful=stateful, eval_carries=eval_carries,
            psum_axis=psum_axis,
        ),
        lambda _: jnp.float32(jnp.nan),
        operand=None,
    )
    return ms


# ---- generic builders (classification / forecasting / any window_fn) ----


def make_device_train_step(
    loss_fn: Callable,
    optimizer: optax.GradientTransformation,
    window_fn: Callable,
    *,
    metric_fn: Callable | None = None,
    metric_keys=(),
    stateful: bool = False,
    grad_accum: int = 1,
    jit: bool = True,
    donate: bool = True,
):
    """Generic single-chip device-data step: ``step(state, arrays, idxs)``
    with ``idxs`` carrying a leading K axis (one entry per optimizer step).

    With ``metric_fn`` set, returns the FUSED train+eval step
    ``step(state, arrays, idxs, eval_batches, do_eval)``: a lax.cond-gated
    weighted eval over the HBM-staged ``eval_batches`` follows the train
    scan in the SAME executable; its metrics appear under ``metric_keys``
    (NaN on non-eval calls)."""
    def core(state: TrainState, arrays, idxs):
        return _scan_indexed(
            loss_fn, optimizer, state, arrays, idxs, window_fn=window_fn,
            stateful=stateful, grad_accum=grad_accum,
        )

    if metric_fn is None:
        step = core
    else:
        keys = tuple(metric_keys)

        def step(state: TrainState, arrays, idxs, eval_batches, do_eval):
            state, ms = core(state, arrays, idxs)
            return state, _gated_eval_batches(
                metric_fn, lambda: state.params, eval_batches, do_eval, ms,
                keys
            )

    return _jit_step(step, jit, donate)


def make_device_dp_train_step(
    loss_fn: Callable,
    optimizer: optax.GradientTransformation,
    window_fn: Callable,
    mesh: Mesh,
    arrays_spec,
    *,
    metric_fn: Callable | None = None,
    metric_keys=(),
    idx_spec=P(),
    axis: str = "data",
    stateful: bool = False,
    grad_accum: int = 1,
    jit: bool = True,
    donate: bool = True,
):
    """Generic data-parallel device-data step. ``arrays_spec`` gives the
    staged arrays' shardings (LM streams shard their batch rows; example/
    series arrays replicate); ``idx_spec`` the index array's (P() when every
    shard uses the same indices, P(None, axis) to split a [K, B] batch of
    row ids). Grads pmean over the ICI mesh as always.

    With ``metric_fn`` set, the fused step's eval batches are REPLICATED
    (``P()``): every shard runs the identical eval concurrently — same
    wall-clock as one shard, exact same value on all, no collective."""
    def core(part, state: TrainState, arrays, idxs):
        return _scan_indexed(
            loss_fn, optimizer, state, arrays, idxs, window_fn=window_fn,
            stateful=stateful, grad_accum=grad_accum,
            rng_transform=dp_rng_transform(axis),
            reduce_fn=dp_reduce_fn(part),
        )

    if metric_fn is None:
        per_shard = core
        in_specs = (arrays_spec, idx_spec)
    else:
        keys = tuple(metric_keys)

        def per_shard(part, state: TrainState, arrays, idxs, eval_batches,
                      do_eval):
            state, ms = core(part, state, arrays, idxs)
            return state, _gated_eval_batches(
                metric_fn, lambda: part.gather(state.params), eval_batches,
                do_eval, ms, keys
            )

        in_specs = (arrays_spec, idx_spec, P(), P())

    sharded = dp_shard_map(per_shard, mesh, in_specs, axis=axis,
                           stateful=stateful)
    return _jit_step(sharded, jit, donate)


# ---- LM wrappers: scalar-w0 per-dispatch API (window indices computed
# ON-DEVICE from the traced scalar — per-dispatch host traffic really is
# one int32) ----


def _lm_window_idxs(w0, data: DeviceLMData, steps_per_call: int):
    return lax.rem(
        w0 + jnp.arange(steps_per_call, dtype=jnp.int32),
        jnp.int32(data.n_windows),
    )


def make_device_lm_train_step(
    loss_fn: Callable,
    optimizer: optax.GradientTransformation,
    data: DeviceLMData,
    *,
    eval_data: DeviceLMData | None = None,
    eval_windows: int | None = None,
    steps_per_call: int = 1,
    stateful: bool = False,
    grad_accum: int = 1,
    jit: bool = True,
    donate: bool = True,
):
    """Single-chip LM device-data step: ``step(state, data.arrays, w0)``.

    With ``eval_data`` (a staged valid stream) set, returns the FUSED
    train+eval step ``step(state, arrays, w0, eval_arrays, do_eval
    [, eval_carries])`` whose ``metrics["eval_loss"]`` is the token-weighted
    valid loss when ``do_eval`` is true, NaN otherwise. ``eval_windows``
    caps the eval pass (the --eval-batches bound)."""
    window_fn = lambda arrays, w: slice_window(arrays, w, data.seq_len)  # noqa: E731

    def core(state: TrainState, arrays, w0):
        return _scan_indexed(
            loss_fn, optimizer, state, arrays,
            _lm_window_idxs(w0, data, steps_per_call),
            window_fn=window_fn, stateful=stateful, grad_accum=grad_accum,
        )

    if eval_data is None:
        step = core
    else:
        n_ev = min(eval_data.n_windows, eval_windows or eval_data.n_windows)
        ev_T = eval_data.seq_len

        def step(state: TrainState, arrays, w0, eval_arrays, do_eval,
                 eval_carries=None):
            state, ms = core(state, arrays, w0)
            return state, _gated_lm_eval(
                loss_fn, lambda: state.params, eval_arrays, do_eval, ms,
                n_windows=n_ev, seq_len=ev_T, stateful=stateful,
                eval_carries=eval_carries,
            )

    return _jit_step(step, jit, donate)


class TrainStepCompileCache:
    """Keyed train-step executables with trace-time compile counting and
    a warmup path — the serve engine's compile-key discipline applied to
    the training side. A (bucket, bptt_mode) step program that first
    traces mid-measurement charges one timed sample a full XLA compile;
    the ``("train_step", bucket, bptt_mode)`` family is gated by
    graftlint's warmup-coverage rule like the serve families, so an
    unwarmed consumer cannot land.

    ``builder(bucket, bptt_mode)`` must return an UNJITTED step
    ``(state, batch) -> (state', metrics)`` (e.g. `make_train_step`
    with ``jit=False``); this cache owns the jit so the trace-time
    counter sits inside the traced callable.
    """

    def __init__(self, builder):
        self._builder = builder
        self._fns: dict = {}
        self.compile_counts: dict = {}

    def step_fn(self, bucket, bptt_mode: str):
        key = (bucket, bptt_mode)
        if key not in self._fns:
            raw = self._builder(bucket, bptt_mode)

            def counted(state, batch, _raw=raw, _key=key):
                # bumped at TRACE time (python side effect inside the
                # jitted callable) — one count per compiled program
                count_key = ("train_step", _key[0], _key[1])
                self.compile_counts[count_key] = (
                    self.compile_counts.get(count_key, 0) + 1)
                return _raw(state, batch)

            # not donated: warmup() dispatches states its caller goes on
            # to train from
            self._fns[key] = jax.jit(counted)
        return self._fns[key]

    def warmup(self, cases):
        """Dispatch each ``(bucket, bptt_mode, state, batch)`` once so
        every program in the lattice compiles before timed traffic."""
        for bucket, mode, state, batch in cases:
            out = self.step_fn(bucket, mode)(state, batch)
            jax.block_until_ready(jax.tree.leaves(out)[0])


def make_device_dp_lm_train_step(
    loss_fn: Callable,
    optimizer: optax.GradientTransformation,
    data: DeviceLMData,
    mesh: Mesh,
    *,
    eval_data: DeviceLMData | None = None,
    eval_windows: int | None = None,
    axis: str = "data",
    steps_per_call: int = 1,
    stateful: bool = False,
    grad_accum: int = 1,
    jit: bool = True,
    donate: bool = True,
):
    """Data-parallel LM device-data step: streams live sharded
    ``P(axis, None)`` (each chip's HBM holds only its batch rows — a cached
    RDD partition); the window slice is along time, so the feed needs no
    collective.

    With ``eval_data`` set (FUSED step), the valid stream shards its batch
    rows the same way and the per-shard eval sums psum into the exact
    global token-weighted mean (same value as make_dp_eval_step +
    evaluate())."""
    window_fn = lambda arrays, w: slice_window(arrays, w, data.seq_len)  # noqa: E731
    stream_spec = {"streams": P(axis, None), "shifted": P(axis, None)}

    def core(part, state: TrainState, arrays, w0):
        return _scan_indexed(
            loss_fn, optimizer, state, arrays,
            _lm_window_idxs(w0, data, steps_per_call),
            window_fn=window_fn, stateful=stateful, grad_accum=grad_accum,
            rng_transform=dp_rng_transform(axis),
            reduce_fn=dp_reduce_fn(part),
        )

    if eval_data is None:
        per_shard = core
        in_specs = (stream_spec, P())
    else:
        n_ev = min(eval_data.n_windows, eval_windows or eval_data.n_windows)
        ev_T = eval_data.seq_len

        def per_shard(part, state: TrainState, arrays, w0, eval_arrays,
                      do_eval, eval_carries):
            state, ms = core(part, state, arrays, w0)
            return state, _gated_lm_eval(
                loss_fn, lambda: part.gather(state.params), eval_arrays,
                do_eval, ms, n_windows=n_ev, seq_len=ev_T, stateful=stateful,
                eval_carries=eval_carries, psum_axis=axis,
            )

        in_specs = (stream_spec, P(), stream_spec, P(),
                    P(axis) if stateful else P())

    sharded = dp_shard_map(per_shard, mesh, in_specs, axis=axis,
                           stateful=stateful)
    return _jit_step(sharded, jit, donate)
